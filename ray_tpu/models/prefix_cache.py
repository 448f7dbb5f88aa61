"""Host-side index for the DecodeEngine's shared-prefix KV cache.

Serving traffic is dominated by shared prompt prefixes (system prompts,
few-shot preambles, multi-turn history): vLLM's PagedAttention and
SGLang's RadixAttention showed that REUSING the K/V of an
already-computed prefix, instead of re-running prefill over it, is the
single largest remaining throughput lever once decode itself is fused.

This module is the pure-host half of that design: a radix/trie index at
BLOCK granularity (``block_tokens`` tokens per node — only full blocks
are shareable, the vLLM rule) mapping token-sequence prefixes to blocks
of the engine's refcounted ``BlockPool`` — the same pool that backs
every live request's block table, so sharing a cached prefix is an
incref and publishing one copies nothing. The device half — the pool
arrays — lives in ``models/engine.py``; this index never touches a
device buffer, so matching and eviction cost zero dispatches.

Concurrency/ordering contract with the engine (single-threaded, but
dispatch-ordered): a node is created PENDING when a row that is about
to prefill its block registers it, and COMMITTED once that row's
prefill frontier has covered the block (the program that writes it has
been dispatched). `match` only walks committed nodes; eviction only
takes committed leaves. Because XLA executes same-device programs in
dispatch order, a block evicted and reassigned on the host is still
read with its OLD content by any program dispatched before the new
owner's write.

Eviction is LRU over committed leaf nodes that nobody but the trie
holds: evicting a leaf releases exactly one block; interior nodes
become leaves as their children go, so cold chains drain tail-first
while hot shared prefixes (recent ``last_use``) survive.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

from ray_tpu.models.block_pool import BlockPool, kv_planes


def block_bytes(n_layers: int, block_tokens: int, kv_heads: int,
                head_dim: int, dtype_bytes: int, *,
                per_layer: bool = False) -> int:
    """Device bytes one cached block occupies (K and V).

    Two axes of "whole vs slice" used to be conflated here (flagged in
    the PR-7 docs), so both are now explicit:

    - LAYERS: a block id indexes the pool's ``n_blocks`` axis of BOTH
      pool arrays (``[L, NB, T, KV*D]``), so one block holds T tokens'
      K/V for ALL ``n_layers`` decoder layers. The default (and the
      number every byte budget must divide by) is therefore the
      layer-SUMMED figure ``2 * L * T * KV * D * dtype``;
      ``per_layer=True`` returns the single-layer slice (what one
      layer's gather touches).
    - MESH: the returned figure is GLOBAL across the serving mesh. On
      a tensor-parallel engine whose KV-head axis shards over tp, each
      chip holds block_bytes/tp of it; ``kv_pool_bytes`` therefore
      sizes the pool in global bytes at
      every tp degree (same block count, smaller per-chip slice), so
      eviction/preemption behavior — and the emitted token stream — is
      identical sharded or not.

    Pool sizing from a byte budget is exact: a budget of
    ``k * block_bytes(...)`` buys exactly k shareable blocks (the
    reserved null block 0 rides on top — it is part of the pool
    allocation but never holds cached data)."""
    layers = 1 if per_layer else n_layers
    return sum(plane.block_bytes(block_tokens, dtype_bytes) for plane in
               kv_planes("full", layers, kv_heads, head_dim, None))


class _Node:
    __slots__ = ("key", "block_id", "parent", "children", "committed",
                 "last_use")

    def __init__(self, key: Optional[Tuple[int, ...]], block_id: int,
                 parent: Optional["_Node"]):
        self.key = key
        self.block_id = block_id
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.committed = False
        self.last_use = 0


class PrefixCacheIndex:
    """Radix index over cached prompt prefixes at block granularity,
    over the engine's shared refcounted ``pool``.

    ``match(prompt)`` returns the pool block ids of the longest
    COMMITTED chain of full blocks prefixing ``prompt``.

    ``register(prompt, block_ids)`` binds the chain for every full
    block of ``prompt`` to the caller's own blocks, creating missing
    nodes as PENDING; the caller calls ``commit(node)`` once its
    prefill has covered a node's block.

    The trie holds ONE pool reference per cached block (`register`
    increfs a row's freshly filled blocks instead of copying them out;
    warm admissions incref matched blocks instead of copying them in),
    and eviction is HARDENED: only blocks whose sole remaining holder
    is the trie itself (``pool.ref(bid) == 1``) are eviction
    candidates, so a block shared with any live (or swapped-out) row
    can never be recycled under its reader — the
    refcount-never-evicted property, tested in
    tests/test_engine_paged.py.
    """

    def __init__(self, *, block_tokens: int, pool: BlockPool,
                 on_evict: Optional[Callable[[int], None]] = None):
        if block_tokens < 1:
            raise ValueError("block_tokens must be >= 1")
        self.block_tokens = block_tokens
        self.pool = pool
        self._root = _Node(None, -1, None)
        self._nodes: List[_Node] = []
        self._clock = 0
        self.evictions = 0
        self._on_evict = on_evict

    # -- introspection -----------------------------------------------------

    def __len__(self) -> int:
        return len(self._nodes)

    @property
    def blocks_in_use(self) -> int:
        return len(self._nodes)

    @property
    def blocks_total(self) -> int:
        return self.pool.blocks_total     # null block excluded

    # -- core ops ----------------------------------------------------------

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _chunk(self, prompt, j: int) -> Tuple[int, ...]:
        T = self.block_tokens
        return tuple(prompt[j * T:(j + 1) * T])

    def match(self, prompt, *, peek: bool = False,
              allow_full: bool = False) -> Tuple[List[int], bool]:
        """Longest committed full-block chain prefixing ``prompt``.

        Returns (block_ids, next_is_pending): the matched chain walks at
        most ``(len(prompt) - 1) // block_tokens`` blocks (at least one
        suffix token is always left for the engine to prefill), and
        ``next_is_pending`` reports whether the walk stopped at a node
        another row is still filling — the prefix-affinity scheduler
        defers such requests one step so they admit warm.

        ``allow_full=True`` lifts the one-suffix-token cap to
        ``len(prompt) // block_tokens`` — the admission path's entry: a
        block-aligned prompt matching its whole chain shares every
        block and COPY-ON-WRITES the last one (recomputing only the
        final token inside the private copy for its logits), instead
        of recomputing a full block of suffix. Probes leave it off:
        they report what can be shared without a copy.

        ``peek=True`` leaves LRU recency untouched: a pure read for
        load probes (the fleet router scores EVERY replica's trie per
        request — touching last_use from probes that lose the routing
        decision would let routing traffic evict genuinely hot blocks)."""
        node = self._root
        ids: List[int] = []
        cap = len(prompt) if allow_full else len(prompt) - 1
        max_blocks = cap // self.block_tokens
        while len(ids) < max_blocks:
            child = node.children.get(self._chunk(prompt, len(ids)))
            if child is None:
                return ids, False
            if not child.committed:
                return ids, True
            if not peek:
                child.last_use = self._tick()
            ids.append(child.block_id)
            node = child
        return ids, False

    def register(self, prompt, block_ids: List[int]
                 ) -> List[Tuple[int, "_Node"]]:
        """Bind the chain for every full block of ``prompt`` to the
        caller's OWN pool blocks (``block_ids[j]`` backs chain position
        j) — the row that is about to prefill those blocks donates a
        share, so publication is zero-copy: the trie
        increfs each newly registered block and there is nothing to
        copy out when the prefill lands. Positions already in the trie
        are left untouched (their existing block holds identical
        content; the caller keeps its own reference to its own block).
        Returns the nodes CREATED — pending until the caller's prefill
        frontier covers them and it calls ``commit``."""
        node = self._root
        created: List[Tuple[int, _Node]] = []
        for j in range(len(prompt) // self.block_tokens):
            if j >= len(block_ids):
                break
            key = self._chunk(prompt, j)
            child = node.children.get(key)
            if child is None:
                self.pool.incref([block_ids[j]])
                child = _Node(key, block_ids[j], node)
                node.children[key] = child
                self._nodes.append(child)
                created.append((j, child))
            child.last_use = self._tick()
            node = child
        return created

    def commit(self, node: "_Node") -> None:
        """Mark a pending node's block as filled (the prefill that
        writes it has been dispatched)."""
        node.committed = True
        node.last_use = self._tick()

    # -- eviction ----------------------------------------------------------

    def _evictable(self, n: "_Node") -> bool:
        """Eviction candidacy, HARDENED for the refcounted pool: a
        victim must be a committed childless leaf whose only remaining
        holder is the trie itself. A refcount above 1 means a live
        row's block table (or a swapped-out request) still reads the
        block; recycling it would corrupt that reader, so such blocks
        are simply not candidates until their last sharer releases
        them."""
        return (not n.children and n.committed
                and self.pool.ref(n.block_id) == 1)

    def evict_one(self) -> bool:
        """Release the LRU evictable leaf's block back to the shared
        pool (the engine calls this when `BlockPool.alloc` runs dry —
        cold cache always gives way before any live request is
        preempted). Returns False when nothing is evictable."""
        victim = None
        for n in self._nodes:
            if not self._evictable(n):
                continue
            if victim is None or n.last_use < victim.last_use:
                victim = n
        if victim is None:
            return False
        victim.parent.children.pop(victim.key, None)
        self._nodes.remove(victim)
        self.evictions += 1
        if self._on_evict is not None:
            self._on_evict(1)
        self.pool.decref([victim.block_id])
        return True

    def evictable_blocks(self) -> int:
        """How many cached blocks COULD be released by repeated
        `evict_one` calls (the engine's admission gate counts these as
        available capacity; the fleet router scores replicas on free +
        evictable).

        This is the CASCADE fixpoint, not just the current leaves:
        evicting a childless leaf makes its parent childless, so a
        whole cold chain is reclaimable even though only its tail is
        evictable right now. Counting only the instantaneous leaves
        under-reports capacity and livelocks the engine's admission
        gate — `_fits_now` says a swapped-out request can never fit
        while `_pool_alloc`'s evict loop would in fact free the chain.
        A node is reclaimable iff it is committed,
        the trie holds its only reference, and EVERY descendant is
        reclaimable too (a shared or pending descendant pins the whole
        path to the root above it)."""
        def reclaimable(n) -> bool:
            if not n.committed:
                return False
            if self.pool.ref(n.block_id) != 1:
                return False
            return all(reclaimable(c) for c in n.children.values())

        return sum(sum(1 for _ in self._subtree_if(n, reclaimable))
                   for n in self._root.children.values())

    def _subtree_if(self, node, pred):
        """Yield `node`'s whole subtree when `pred(node)` holds (the
        cascade reclaims subtrees from the root down: an unreclaimable
        ancestor keeps its reclaimable descendants pinned only until
        the ancestor itself is evicted, which cannot happen while it
        has children — so reclaimability is decided at the subtree
        root)."""
        if not pred(node):
            for c in node.children.values():
                yield from self._subtree_if(c, pred)
            return
        stack = [node]
        while stack:
            n = stack.pop()
            yield n
            stack.extend(n.children.values())

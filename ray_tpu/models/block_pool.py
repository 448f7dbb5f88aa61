"""Refcounted allocator over the engine's paged KV block pool.

The paged DecodeEngine keeps EVERY request's K/V in fixed-size token
blocks of one device pool ``[L, NB, T, KV*D]`` and addresses them
through per-request block tables — the vLLM/PagedAttention memory
plane. This module is the pure-host ledger for that pool: which block
ids are free, and how many holders reference each allocated block.

Reference counting is what turns prefix-cache hits into zero-copy
SHARES: a warm admission increfs the matched blocks instead of copying
them (no device-to-device copy), the trie holds one reference of its own for every cached block, and a
block returns to the free list only when its LAST holder drops it —
so a shared block can never be recycled under a live reader (the
refcount-never-evicted property, tested). Everything here is host-side
integers: alloc/incref/decref cost zero device dispatches.

Block id 0 is RESERVED as the null/scratch block: unoccupied block-table entries point at it, padded
gather/scatter programs write garbage into it, and it is never handed
out by ``alloc``.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Protocol, Tuple


class CachePlane(NamedTuple):
    """One array of a family's cache: what a token stores in the layers
    that write it. A config answers `cache_planes()` with these, and the
    engine sizes its pools, prices a block and reports its geometry from
    them alone. Planes of one ``table`` advance together behind one block
    table a row and one `BlockPool`."""

    name: str        # "k" | "v" | "latent" | "index" | "window_k" | ...
    table: str       # "full" | "window"
    layers: int      # layers that write this plane
    lanes: int       # values a token stores in one of them, as laid out
    dtype: Any
    heads: int = 1   # KV heads the lanes hold: a quantized block's scales

    def block_bytes(self, block_tokens: int,
                    itemsize: Optional[int] = None) -> int:
        """Device bytes of one block of this plane, every layer of it."""
        size = self.dtype.itemsize if itemsize is None else itemsize
        return self.layers * block_tokens * self.lanes * size


def kv_planes(table: str, layers: int, kv_heads: int, head_dim: int,
              dtype, prefix: str = "") -> tuple:
    """The K and V planes of ``layers`` attention layers that store
    ``kv_heads`` heads of ``head_dim`` a token, head-major in one lane
    axis."""
    lanes = kv_heads * head_dim
    return (CachePlane(prefix + "k", table, layers, lanes, dtype, kv_heads),
            CachePlane(prefix + "v", table, layers, lanes, dtype, kv_heads))


class StatePlane(NamedTuple):
    """One array of a family's RECURRENT state: what a row keeps in the
    layers that own it, whatever the row's length (a state-space layer's
    scan state, a delta-rule layer's matrix state, a conv's last inputs).
    A config answers `state_planes()` with these, and the engine keeps
    ``[layers, slots, *shape]`` of each, zeroed, donated through every
    program and indexed by engine slot; a family without recurrent state
    answers ``()``."""

    name: str        # "ssm" | "conv" | "delta"
    layers: int      # layers that own this plane
    shape: Tuple[int, ...]   # what ONE row keeps in one of them
    dtype: Any

    def row_bytes(self) -> int:
        """Device bytes a row keeps in this plane, every layer of it."""
        n = self.layers * self.dtype.itemsize
        for s in self.shape:
            n *= s
        return n


def zero_state_planes(planes, slots: int) -> Dict[str, Any]:
    """``{name: zeros [layers, slots, *shape]}`` of `StatePlane`s."""
    import jax.numpy as jnp

    return {pl.name: jnp.zeros((pl.layers, slots, *pl.shape), pl.dtype)
            for pl in planes}


class ServedConfig(Protocol):
    """What `DecodeEngine` and solo `generate` ask a model family, and all
    they know of it (`docs/serving.md`, "Adding a family"). A declaration:
    nothing inherits it or is checked against it at run time."""

    def cache_planes(self) -> Tuple[CachePlane, ...]:
        """Two planes of table "full" (the pool behind a row's table) and
        none or two of table "window": a second pool and table, kept in
        the stack's state under the planes' names, of which a row holds
        its last `sliding_window` slots (the config's, as is
        `n_window_layers`)."""

    def state_planes(self) -> Tuple[StatePlane, ...]:
        """What a row keeps whatever its length; ``()`` for none."""

    def refusals(self) -> Dict[str, str]:
        """The engine options it cannot be served with and the sentence
        each raises, in the order they are checked: keys among
        "prefix_cache", "preempt_swap", "draft", "kv_quant", "lora", "tp"
        and "handoff" (its sentence keeps ``{}`` for the refused call)."""

    def stack(self):
        """The module of its own layers, or None for `generate._layer_body`'s,
        which the engine scans itself: `lm_head`, solo `generate`'s
        `init_cache` and `forward_cached`, and `layers_paged(params, toks,
        pool_a, pool_b, bt, starts, cfg, *, ...)`, all rows of ``toks``
        [B, S] at slots ``starts + arange(S)`` against the two "full"
        pools through ``bt``, each family ignoring what it has no use for:

          state     {name: array} of its window and state planes, or None
          bt_w      the rows' window table
          live      [B, S] bool: the positions that advance recurrent state
                    (a prefix of each row)
          rows      [B] the engine slot of each row (prefill's admission
                    group; None: row b is slot b, decode). A row with
                    ``starts == 0`` begins from ZERO state, whatever its
                    slot holds: that is how a slot is reset at admission
          n_valid   [B] real tokens of a prefill chunk (None: all S)
          last_idx  [B] the position whose hidden state is wanted
                    (prefill); None: every position (decode)
          final     False: a chunk that is not a prompt's last: stop after
                    `prefill_layers` and return no hidden state
          moe_live  [B, S] bool or None: the positions the expert layers'
                    counters count (None: none are traced)

        -> (h [B, S, d] or [B, 1, d] with ``last_idx``, pool_a, pool_b,
        expert-layer counts or None, state or None).

        A stack whose decode token updates recurrent state through a
        kernel of its own also has `state_step_kernel(cfg) -> bool`, which
        says when: the engine counts such decode blocks."""

    def prefill_layers(self) -> int:
        """Layers that see every prompt token: fewer than `n_layers` where
        a chunk that is not a prompt's last stops early."""


class BlockPool:
    """Host ledger of a device block pool: free list + refcounts.

    ``alloc(n)`` hands out n block ids (each with refcount 1) or None
    if fewer than n are free — the caller decides whether to evict
    cold prefix-cache blocks or preempt a victim request. ``incref``
    adds a holder (a warm admission sharing a cached block, or the
    trie registering a row's freshly filled block); ``decref`` drops
    one, freeing the block when the count reaches zero. All O(1) per
    block, pure host state."""

    def __init__(self, n_blocks: int, *, label: str = "kv"):
        if n_blocks < 2:
            raise ValueError(
                "n_blocks must be >= 2 (block 0 is the reserved "
                "null/scratch block); raise kv_pool_bytes or shrink "
                "kv_block_tokens")
        self.n_blocks = n_blocks
        # Which plane this ledger backs — the speculative engine runs
        # TWO pools side by side (target "kv" + "draft_kv"), and the
        # label keeps their snapshots distinguishable in the state API.
        self.label = label
        # Stack of free ids, low ids on top (pop order is deterministic
        # so engine runs — and their compiled gather shapes — replay
        # identically across processes).
        self._free: List[int] = list(range(n_blocks - 1, 0, -1))
        self._refs = [0] * n_blocks

    # -- introspection -----------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def blocks_total(self) -> int:
        return self.n_blocks - 1          # scratch block 0 excluded

    @property
    def blocks_in_use(self) -> int:
        return self.blocks_total - len(self._free)

    def ref(self, bid: int) -> int:
        """Current holder count of a block (0 = free)."""
        return self._refs[bid]

    def snapshot(self) -> dict:
        """Plain-dict ledger view for the state API / status CLI:
        totals plus how sharing is distributed (blocks with >1 holder
        are the zero-copy prefix shares; `refs_max` is the hottest
        block's holder count). Pure host arithmetic over the refcount
        list — no allocation state is touched."""
        shared = sum(1 for r in self._refs if r > 1)
        return {
            "label": self.label,
            "blocks_total": self.blocks_total,
            "blocks_in_use": self.blocks_in_use,
            "free_blocks": len(self._free),
            "blocks_shared": shared,
            "refs_max": max(self._refs) if self._refs else 0,
        }

    # -- alloc / share / release -------------------------------------------

    def alloc(self, n: int) -> Optional[List[int]]:
        """Take n blocks off the free list, each with refcount 1.
        All-or-nothing: returns None (and takes nothing) when fewer
        than n are free, so a caller never holds a partial chain."""
        if n < 0:
            raise ValueError("alloc(n) needs n >= 0")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for bid in ids:
            self._refs[bid] = 1
        return ids

    def incref(self, ids) -> None:
        """Add one holder to each block (shared admission / trie
        registration). Blocks must be allocated — sharing a free block
        is a ledger bug, not a recoverable condition."""
        for bid in ids:
            if self._refs[bid] <= 0:
                raise ValueError(
                    f"incref on free block {bid}: sharing requires an "
                    "existing holder")
            self._refs[bid] += 1

    def decref(self, ids) -> List[int]:
        """Drop one holder from each block; returns the ids FREED by
        this call (refcount hit zero), in drop order."""
        freed: List[int] = []
        for bid in ids:
            r = self._refs[bid]
            if r <= 0:
                raise ValueError(f"decref on free block {bid}")
            r -= 1
            self._refs[bid] = r
            if r == 0:
                self._free.append(bid)
                freed.append(bid)
        return freed

"""Request-scheduler policies for the continuous-batching DecodeEngine.

The engine's admission loop used to be an implicit FIFO deque buried in
`DecodeEngine.submit()`/`step()`. Serving heavy traffic needs that seam
to be a first-class, pluggable policy — the analog of the reference
Serve router/scheduler plane (python/ray/serve/_private/router.py picks
replicas; this picks which QUEUED request gets the next freed decode
slot) — plus the two admission-control knobs every production LLM
server grows:

- a BOUNDED queue with backpressure (`max_queue` + `on_full`): reject
  (raise `EngineOverloaded`, the caller sheds load / retries elsewhere)
  or block (drive the engine until a queue slot frees — the
  single-threaded analog of awaiting queue room; `block_timeout_s`
  bounds the wait and raises `SubmitTimeout` when a wedged engine
  would otherwise block the caller forever);
- a per-step PREFILL ADMISSION BUDGET (`max_prefills_per_step`): each
  admission runs a whole prompt-prefill program before the shared
  decode step, so a burst of long prompts admitted at once would stall
  every in-flight decode row for the full burst; capping admissions
  per step bounds the inter-token latency in-flight requests can lose
  to newcomers. A row still mid-prompt (chunked prefill) prefills a
  chunk every step and counts against the budget.

Scheduling only changes WHICH request is admitted when a slot frees —
and, via `horizon_hint`, how many decode iterations the engine fuses
into one program before it re-consults the queue (TTFT vs throughput)
— never what any admitted request computes: outputs stay
token-identical to solo `generate` under every policy and every
horizon (tested).
"""

from __future__ import annotations

import collections
import heapq
from typing import List, Optional


class EngineOverloaded(RuntimeError):
    """Raised by `DecodeEngine.submit()` when the bounded queue is full
    and the engine was configured with on_full="reject"."""


class EngineDraining(RuntimeError):
    """Raised by `DecodeEngine.submit()` after `begin_drain()`: a
    draining engine finishes its in-flight and queued work but accepts
    no new requests (the fleet routes around it until removal)."""


class SubmitTimeout(EngineOverloaded):
    """Raised by `DecodeEngine.submit()` in on_full="block" mode when
    the queue stays full past ``block_timeout_s``: the engine was
    driven that long without freeing a queue slot, so it is wedged or
    hopelessly oversubscribed — surface a typed error instead of
    spinning forever. Subclasses EngineOverloaded so existing
    overload handlers keep catching it."""


class SchedulerPolicy:
    """Ordering policy for queued (not-yet-admitted) requests.

    Implementations hold requests between `submit()` and admission and
    decide which one takes the next freed slot. They never see or
    touch in-flight rows."""

    name = "base"

    def push(self, req) -> None:
        raise NotImplementedError

    def push_front(self, req) -> None:
        """Re-queue a request at the HEAD of the policy's order — used
        by the paged engine when an admission gate turns out stale
        (pool momentarily full) and, crucially, when a live row is
        PREEMPTED: the victim must be first in line to swap back in,
        not re-ranked behind the traffic that evicted it. Policies
        without a natural front (e.g. priority heaps, where `req.seq`
        already restores the original rank) may fall back to push."""
        self.push(req)

    def pop(self):
        """Remove and return the next request to admit."""
        raise NotImplementedError

    def choose_victim(self, rows: List[int], requests) -> int:
        """Pick which live row the paged engine preempts when the KV
        pool runs dry mid-decode. `rows` is ordered oldest-admitted
        first; `requests[row]` is the in-flight request. Default is
        LIFO — evict the newest admission (vLLM's discipline: the
        oldest request is closest to finishing and has absorbed the
        most compute, so it is the worst thing to throw away).
        Policies may override, e.g. priority-aware victim choice."""
        return rows[-1]

    def __len__(self) -> int:
        raise NotImplementedError

    def snapshot(self) -> List[int]:
        """Queued request ids, in no particular order (introspection)."""
        raise NotImplementedError

    def queued_requests(self) -> list:
        """The queued request OBJECTS, in no particular order — a
        read-only view for load probes (the fleet router sums queued
        prompt lengths into a replica's pending-prefill estimate).
        Callers must not mutate the returned requests or the list."""
        raise NotImplementedError

    def queued_state(self) -> List[dict]:
        """Plain-dict view of the queue for the state API
        (`ray_tpu.util.state.list_requests`): one entry per queued
        request with the fields an operator reads — and the request
        object itself under ``"request"`` so the caller can classify
        further (swap ledger, deadlines) without re-walking the queue.
        Falls back to id-only entries for a custom policy that
        implements `snapshot()` but not `queued_requests()`. Read-only:
        never mutates queue order or the requests."""
        try:
            reqs = self.queued_requests()
        except NotImplementedError:
            return [{"req_id": rid} for rid in self.snapshot()]
        return [{"req_id": r.req_id, "priority": r.priority,
                 "prompt_tokens": len(r.prompt),
                 "max_new_tokens": r.max_new_tokens,
                 "deadline": r.deadline, "resume": r.resume,
                 # Imported from a prefill-class replica, waiting for
                 # decode admission (disaggregated fleets; always
                 # False elsewhere). Surfaced flat so state-API
                 # callers need not reach into the request object.
                 "handoff": bool(getattr(r, "handoff", False)),
                 "request": r} for r in reqs]

    def horizon_hint(self, *, free_slots: int,
                     max_horizon: int) -> int:
        """Suggested fused-decode horizon for the NEXT engine step
        (how many decode iterations to fuse into one program before
        the host looks at the queue again).

        Default policy, shared by every built-in: while a queued
        request could take a free slot next step (queue non-empty AND
        free_slots > 0 — admission was capped by the prefill budget
        this step), answer 1 so the newcomer's TTFT is not held behind
        a long horizon; otherwise (slots saturated, or nothing queued)
        answer `max_horizon` and amortize dispatch overhead. Policies
        may override — e.g. a deadline-aware policy shortening the
        horizon as the head-of-queue deadline approaches. The engine
        additionally caps the hint at the largest remaining row budget
        and rounds it down to a power of two (bounded compile count)."""
        if len(self) and free_slots > 0:
            return 1
        return max_horizon

    def spec_window_hint(self, *, rates: List[Optional[float]],
                         spec_window: int) -> List[int]:
        """Per-row ADAPTIVE draft window for the next speculative
        dispatch — the speculation analog of `horizon_hint`. `rates`
        has one entry per candidate row: that row's recent acceptance
        rate (accepted / proposed over the engine's sliding window of
        rounds), or None for a row with no history yet (fresh
        admission). Returns one draft width per row, each in
        [1, spec_window].

        Default policy: trust a fresh row with the full window
        (optimistic — the first rounds measure it), then track the
        measured acceptance rate linearly: a row accepting everything
        keeps `spec_window`, a row rejecting everything shrinks to 1
        (one proposal still rides free on the verify pass), rows in
        between get `1 + rate * (spec_window - 1)` rounded. The engine
        takes the max over rows (rounded up to a power of two, capped
        at `spec_window`) as the dispatch width and applies each row's
        hint as its per-row acceptance cap, so one shrinking row never
        recompiles the program. Policies may override — e.g. a
        deadline-aware policy forcing 1 to minimize per-round latency
        variance."""
        out = []
        for r in rates:
            if r is None:
                out.append(spec_window)
            else:
                out.append(max(1, min(spec_window,
                                      1 + int(r * (spec_window - 1)
                                              + 0.5))))
        return out

    def admissions_pending(self) -> bool:
        """Could an admission decision change the batch soon? The
        engine's async decode pipeline consults this before running
        ahead: a pending admission means every freed slot must be
        re-examined with fully-replayed host state, so the engine
        FLUSHES its in-flight ring and steps synchronously instead of
        dispatching run-ahead decode blocks the newcomer could not
        join. Default: queue non-empty. Policies that defer requests
        (e.g. prefix affinity holding followers for a warm trie) must
        still answer True while anything is queued — a deferred
        request is admissible again next round."""
        return len(self) > 0


class FIFOPolicy(SchedulerPolicy):
    """Admit in submission order (the engine's historical behavior)."""

    name = "fifo"

    def __init__(self):
        self._q: collections.deque = collections.deque()

    def push(self, req) -> None:
        self._q.append(req)

    def push_front(self, req) -> None:
        self._q.appendleft(req)

    def pop(self):
        return self._q.popleft()

    def __len__(self) -> int:
        return len(self._q)

    def snapshot(self) -> List[int]:
        return [r.req_id for r in self._q]

    def queued_requests(self) -> list:
        return list(self._q)


class PriorityPolicy(SchedulerPolicy):
    """Admit by priority class (LOWER number = admitted first), FIFO
    within a class — `submit(..., priority=0)` interactive traffic
    overtakes queued `priority=10` batch traffic at the next free slot.
    The submission sequence number breaks ties, so equal-priority
    requests never reorder (and the heap never compares request
    objects)."""

    name = "priority"

    def __init__(self):
        self._heap: list = []

    def push(self, req) -> None:
        heapq.heappush(self._heap, (req.priority, req.seq, req))

    def pop(self):
        return heapq.heappop(self._heap)[2]

    def __len__(self) -> int:
        return len(self._heap)

    def snapshot(self) -> List[int]:
        return [r.req_id for _, _, r in self._heap]

    def queued_requests(self) -> list:
        return [r for _, _, r in self._heap]


class PrefixAffinityPolicy(FIFOPolicy):
    """FIFO order, made prefix-cache aware: maximize KV reuse by never
    admitting a request COLD when admitting it one step later would be
    WARM.

    The engine (when built with prefix_cache=True) attaches a probe via
    `attach_prefix_probe`: ``probe(prompt) -> (matched_tokens,
    prefix_group_key, next_block_pending)`` — a pure host walk of the
    prefix trie. `pop` scans the queue in FIFO order and SKIPS, for
    this admission round only, any request that is about to become
    warmer than it is now:

    - its next prefix block is PENDING — an in-flight row is already
      prefilling exactly the blocks this request would recompute; once
      that row's copy-out commits (at most a few steps), this request
      admits warm and prefills only its own suffix;
    - a same-prefix-group request (same first block) was already popped
      COLD this round — the classic burst of N requests sharing one
      system prompt: the first becomes the group's leader and computes
      the shared blocks once; the other N-1 wait for it rather than
      all recomputing the prefix in parallel rows.

    `pop` returns None when every queued request is deferred (the
    engine stops admitting for the step). Progress is guaranteed: the
    leader IS admitted and its prefill always advances, so the blocks
    followers wait on commit after finitely many steps — deferral
    trades one short admission delay for an order-of-magnitude prefill
    saving. Without a probe attached the policy degrades to plain
    FIFO. Like every policy, this reorders ADMISSION only: admitted
    requests compute exactly what they would under FIFO (token-identity
    is tested)."""

    name = "prefix"

    def __init__(self):
        super().__init__()
        self._probe = None
        self._round_cold: set = set()   # group keys popped cold this round
        self.deferrals = 0   # pops skipped to wait for a warmer admit
        #                      (observability: the tracer's
        #                      admission_defer events and this counter
        #                      say how often affinity held a request)

    def attach_prefix_probe(self, probe) -> None:
        self._probe = probe

    def begin_admission_round(self) -> None:
        self._round_cold = set()

    def pop(self):
        if self._probe is None:
            return super().pop()
        for i, req in enumerate(self._q):
            if getattr(req, "resume", False):
                # Preempted row swapping back in: its KV is in the host
                # swap buffer (or replayed from its own history), not
                # the trie — probing/deferring it can only delay the
                # restart it is owed.
                del self._q[i]
                return req
            matched, key, pending = self._probe(req.prompt)
            if pending or (key is not None and key in self._round_cold):
                self.deferrals += 1
                continue                 # warmer next round — defer
            if key is not None and matched == 0:
                self._round_cold.add(key)   # cold leader for its group
            del self._q[i]
            return req
        return None


class AdapterAffinityPolicy(FIFOPolicy):
    """FIFO order, made multi-LoRA aware: group admissions by adapter
    residency so cold-adapter requests wait on their PREFETCH instead
    of stalling the admission round.

    The engine (when built with `lora=`) attaches a probe via
    `attach_adapter_probe`: ``probe(adapter_id) -> (resident,
    fetching)`` — a pure host lookup against the AdapterPool's ledger.
    `pop` scans the queue in FIFO order and SKIPS, for this admission
    round only, any request whose adapter is not resident yet:

    - its adapter's prefetch is IN FLIGHT — the async host->device
      stage was already enqueued; once `drain_prefetches` commits it
      (at most a few steps), this request admits against a warm slot;
    - a same-adapter request was already popped cold this round — the
      first becomes the adapter's leader (the engine's admission gate
      starts the prefetch and requeues it); the rest wait for that one
      transfer rather than each re-triggering the gate.

    `pop` returns None when every queued request is deferred. Progress
    is guaranteed: base-model (adapter_id=None) and resident-adapter
    requests always admit, and a deferred adapter's prefetch commits
    after finitely many steps. Without a probe the policy degrades to
    plain FIFO. Like every policy, this reorders ADMISSION only —
    outputs stay token-identical to FIFO (tested)."""

    name = "adapter"

    def __init__(self):
        super().__init__()
        self._probe = None
        self._round_cold: set = set()   # adapter_ids popped cold this round
        self.deferrals = 0   # pops skipped to wait for a warm slot

    def attach_adapter_probe(self, probe) -> None:
        self._probe = probe

    def begin_admission_round(self) -> None:
        self._round_cold = set()

    def pop(self):
        if self._probe is None:
            return super().pop()
        for i, req in enumerate(self._q):
            aid = getattr(req, "adapter_id", None)
            if aid is None or getattr(req, "resume", False):
                # Base-model rows gather the null slot; a preempted
                # resume is owed its restart (its re-admission re-runs
                # the engine's adapter gate anyway).
                del self._q[i]
                return req
            resident, fetching = self._probe(aid)
            if resident:
                del self._q[i]
                return req
            if fetching or aid in self._round_cold:
                self.deferrals += 1
                continue                 # warmer next round — defer
            self._round_cold.add(aid)    # cold leader for its adapter
            del self._q[i]
            return req
        return None


_POLICIES = {"fifo": FIFOPolicy, "priority": PriorityPolicy,
             "prefix": PrefixAffinityPolicy,
             "adapter": AdapterAffinityPolicy}


def make_policy(spec) -> SchedulerPolicy:
    """Resolve a policy spec: an instance passes through, a name
    ("fifo" | "priority" | "prefix") constructs the built-in."""
    if isinstance(spec, SchedulerPolicy):
        return spec
    try:
        return _POLICIES[spec]()
    except (KeyError, TypeError):
        raise ValueError(
            f"unknown scheduler policy {spec!r}: expected a "
            f"SchedulerPolicy instance or one of {sorted(_POLICIES)}")

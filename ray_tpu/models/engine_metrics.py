"""Request-lifecycle telemetry for the continuous-batching DecodeEngine.

Every request moves queued → admitted (prefill) → decoding → finished;
this module timestamps each transition and exports the serving numbers
a vLLM-class engine is judged by:

- queue wait      (submit → prefill admission)
- prefill         (admission → the row becomes decodable: its last
                   prompt chunk is dispatched)
- first block     (decodable → first token on the host; the first token
                   rides a fused decode block that the host drains up
                   to a pipeline depth later), itself in two at the
                   dispatch of THAT block (`on_block`): first dispatch
                   (decodable → the dispatch's return) and first return
                   (→ the token on the host), with the blocks in flight
                   in front of it and its horizon
- TTFT            (submit → first emitted token; exactly the sum of the
                   three above, per request, on the engine's clock)
- TPOT            (gap between consecutive tokens of one request)
- tokens/steps    (throughput counters)
- slot occupancy / batch efficiency per step (how full the shared
  decode program actually runs)

Export goes through the ordinary `ray_tpu.util.metrics`
Counter/Gauge/Histogram plane, so inside a cluster the series flow to
the GCS metrics table and the dashboard /metrics Prometheus endpoint
exactly like every other runtime metric (reference analog: Serve's
replica request/latency series in python/ray/serve/_private/replica.py
feeding python/ray/_private/metrics_agent.py). Outside a cluster the
registry is still populated locally — tests and notebooks read
`stats()` or `ray_tpu._private.metrics.snapshots()` directly.

A drained block hands a request `n` tokens at once: they are ONE
weighted observation (`_Agg.add(v, n)`, `Histogram.observe(v, n=n)`), the
same count, sum, max, percentiles and bucket counts as `n` calls, and the
instruments resolve their series once, not a call.

`DecodeEngine.stats()` adds the engine's own counters to these series,
among them the step clocks, kept with the metrics plane off: a step's wall
time by seam (`step_s_total` = `step_flush_s_total` + `step_admit_s_total`
+ `step_prefill_dispatch_s_total` + `step_dispatch_s_total` +
`step_emit_s_total` + `device_wait_s` + `step_other_s_total`, each the
seconds inside the `eng.*` lane of its name), the seconds the device had
nothing to run as far as the host knows by cause
(`device_starved_s_total`, `device_starved_{retire,admit,chunk,other}
_s_total`, `device_starved_dispatches_total`) and the steps of
`engine.STALL_STEP_S` or more with their seconds inside `_device_get`
(`steps_stalled_total`, `step_stalled_s_total`,
`step_stalled_device_wait_s_total`); `docs/serving.md` has the glossary.
Two of them are named for the family they first counted and count MORE:
``ssm_state_resets_total`` (rows admitted from zero recurrent state: a
request's first chunk, a recompute) and ``ssm_row_steps_total`` (live rows
x decode tokens whose recurrent state a dispatch advances) count recurrent
state of ANY kind a config declares with `state_planes()`: a
`HybridConfig`'s state-space layers and a `GdnConfig`'s delta-rule layers
alike; ``kv_walk_tokens_full_total`` counts, a decode dispatch, the tokens
the paged kernel is asked to read once for each layer that READS the
table's pool (a `HybridConfig`'s one full layer and its cross-attention
readers, each attention layer of a `GdnConfig`). They are 0 for a family
without recurrent state. A SELECTING family (an `MlaConfig`) counts its
own: the ``indexer_*tokens*`` counters are token-layers over the layers
that select (all but its window layers), ``indexer_pages_walked_total`` of
``indexer_pages_table_total`` the page-layers the selection walks of its
rows' tables (`ops.indexer_select`: a block of queries the pages up to its
last slot's, none below `index_topk`) and
``indexer_queries_unselected_total`` of ``indexer_queries_total`` the
query-layers with at most `index_topk` slots to see, which keep them all;
``sparse_decode_pages_walked_total`` of ``sparse_decode_pages_table_total``
the page-layers a decode dispatch's tokens walk in the layers that select
(`ops.sparse_latent_attention.pages_walked`: the pages up to a token's
slot's) of the table entries of the rows that ask: the share of a
grid-a-table-entry walk that would be live;
and where it has window layers
``swa_window_rows_total`` / ``swa_window_slots_total`` count, a decode
dispatch, the row-tokens that asked and the slots ONE window layer reads
for them (min(row length, `sliding_window`) each; times the config's
window layers for what the stack reads).

All instruments carry an ``engine`` tag (one DecodeEngine = one tag
value) so several engines in one process — or one per replica — stay
separable in the same Prometheus plane.
"""

from __future__ import annotations

import itertools
import time
from array import array
from typing import Callable, Dict, List, Optional

import numpy as np

from ray_tpu.util.metrics import Counter, Gauge, Histogram

# Token-scale latency buckets: default runtime boundaries top out at
# 1000 (s) for RPCs; decode cadences live in the 0.5 ms – 30 s range.
LATENCY_BOUNDARIES_S = [
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0]

# Fused-decode horizon buckets (tokens per dispatch): powers of two up
# to well past the default decode_horizon of 8.
HORIZON_BOUNDARIES = [1, 2, 4, 8, 16, 32, 64]

_engine_ids = itertools.count()


class _Agg:
    """Running aggregate (count/sum/max) plus a bounded ring of recent
    observations for tail-percentile snapshots. Mean/max alone hide the
    tail — the autoscaler scales on TTFT p95 and the SLO bench reports
    p95/p99, so `fields` additionally emits `_p50`/`_p95`/`_p99` over
    the last ``WINDOW`` entries (a sliding window, the serving
    convention: an SLO is judged on RECENT traffic, and the bound keeps
    a long-running engine's snapshot cost flat). An entry is one `add`:
    a value and the number of observations it stands for (`n`, the
    tokens of one drained block), and a percentile is taken over the
    observations, so `add(v, n)` reads as `n` calls of `add(v)` in every
    field while costing one. The full unbounded distribution still lives
    in the Histogram instruments."""

    WINDOW = 2048

    __slots__ = ("count", "sum", "max", "_ring", "_ring_n", "_ring_i")

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.max = 0.0
        self._ring = array("d")             # values, and beside them the
        self._ring_n = array("q")           # observations each stands for
        self._ring_i = 0

    def add(self, v: float, n: int = 1) -> None:
        if n <= 0:
            return
        self.count += n
        self.sum += v * n
        if v > self.max:
            self.max = v
        if len(self._ring) < self.WINDOW:
            self._ring.append(v)
            self._ring_n.append(n)
        else:                       # overwrite oldest: O(1), no shift
            self._ring[self._ring_i] = v
            self._ring_n[self._ring_i] = n
            self._ring_i = (self._ring_i + 1) % self.WINDOW

    def percentiles(self, qs) -> List[float]:
        """The q-th percentiles (0..100) of the retained window's
        observations — nearest rank over ONE sort, an entry of `n`
        observations taking `n` ranks; 0.0 when empty."""
        if not self._ring:
            return [0.0] * len(qs)
        vals = np.frombuffer(self._ring)            # no copy
        ns = np.frombuffer(self._ring_n, np.int64)
        order = np.argsort(vals, kind="stable")
        upto = np.cumsum(ns[order])     # observations up to each entry
        total = int(upto[-1])
        ranks = [max(0, min(total - 1, int(round(q / 100.0 * (total - 1)))))
                 for q in qs]
        at = np.searchsorted(upto, ranks, side="right")
        return vals[order[at]].tolist()

    def percentile(self, q: float) -> float:
        return self.percentiles((q,))[0]

    def fields(self, prefix: str, out: Dict[str, float]) -> None:
        out[f"{prefix}_count"] = self.count
        out[f"{prefix}_mean"] = self.sum / self.count if self.count else 0.0
        out[f"{prefix}_max"] = self.max
        (out[f"{prefix}_p50"], out[f"{prefix}_p95"],
         out[f"{prefix}_p99"]) = self.percentiles((50.0, 95.0, 99.0))


class _ReqTimes:
    __slots__ = ("submit_t", "admit_t", "decodable_t", "first_token_t",
                 "last_token_t", "n_tokens")

    def __init__(self, submit_t: float):
        self.submit_t = submit_t
        self.admit_t: Optional[float] = None
        self.decodable_t: Optional[float] = None
        self.first_token_t: Optional[float] = None
        self.last_token_t: Optional[float] = None
        self.n_tokens = 0


class EngineMetrics:
    """One instance per DecodeEngine. The engine calls the on_* hooks
    at each lifecycle transition; `stats()` returns a flat numeric
    snapshot (gauge-friendly — see serve.metrics.report_engine_stats).

    ``clock`` is injectable for deterministic tests."""

    def __init__(self, *, engine_id: Optional[str] = None,
                 batch_slots: int = 1,
                 clock: Callable[[], float] = time.monotonic):
        self.engine_id = engine_id or f"engine-{next(_engine_ids)}"
        self.batch_slots = max(1, batch_slots)
        self._clock = clock
        self._req: Dict[int, _ReqTimes] = {}

        self.requests_submitted = 0
        self.requests_admitted = 0
        self.requests_finished = 0
        self.requests_rejected = 0
        self.requests_shed = 0
        self.tokens_generated = 0
        self.steps = 0
        self.queue_depth = 0
        self.live_slots = 0
        self.batch_efficiency = 0.0
        self.queue_wait_s = _Agg()
        self.prefill_s = _Agg()
        self.first_block_s = _Agg()
        # first_block_s in two, at the dispatch of the block that
        # carries the first token (`on_block`), and what stood in front
        # of that block
        self.first_dispatch_s = _Agg()
        self.first_return_s = _Agg()
        self.first_blocks_ahead = _Agg()
        self.first_block_horizon = _Agg()
        self._block = (None, 0, 0)     # the block being replayed
        self.ttft_s = _Agg()
        self.tpot_s = _Agg()
        self.decode_dispatches = 0
        self.host_syncs = 0
        self.decode_horizon = _Agg()

        tag = {"engine": self.engine_id}
        keys = ("engine",)

        def counter(name, desc):
            return Counter(name, desc, tag_keys=keys).set_default_tags(tag)

        def gauge(name, desc):
            return Gauge(name, desc, tag_keys=keys).set_default_tags(tag)

        def hist(name, desc):
            return Histogram(name, desc, boundaries=LATENCY_BOUNDARIES_S,
                             tag_keys=keys).set_default_tags(tag)

        self._m_submitted = counter(
            "llm_engine_requests_submitted_total",
            "Requests accepted into the engine queue")
        self._m_finished = counter(
            "llm_engine_requests_finished_total",
            "Requests that completed (budget, eos, or max_len)")
        self._m_rejected = counter(
            "llm_engine_requests_rejected_total",
            "Requests shed by bounded-queue backpressure")
        self._m_shed = counter(
            "llm_engine_requests_shed_total",
            "Requests shed past their deadline before burning prefill "
            "(at submit, or expired mid-queue at admission)")
        self._m_tokens = counter(
            "llm_engine_tokens_generated_total",
            "Tokens emitted across all requests")
        self._m_steps = counter(
            "llm_engine_steps_total",
            "Shared decode steps executed")
        self._m_queue_wait = hist(
            "llm_engine_queue_wait_s",
            "Seconds from submit to prefill admission")
        self._m_ttft = hist(
            "llm_engine_ttft_s",
            "Seconds from submit to first emitted token")
        self._m_tpot = hist(
            "llm_engine_tpot_s",
            "Seconds between consecutive tokens of one request")
        self._m_queue_depth = gauge(
            "llm_engine_queue_depth",
            "Requests queued awaiting a decode slot")
        self._m_occupancy = gauge(
            "llm_engine_slot_occupancy",
            "Live decode slots / total slots (0..1)")
        self._m_batch_eff = gauge(
            "llm_engine_batch_efficiency",
            "Tokens emitted this step / total slots (0..1; ~occupancy "
            "unless rows finished mid-step)")
        self._m_dispatches = counter(
            "llm_engine_decode_dispatches_total",
            "Fused decode program launches (one per step horizon)")
        self._m_host_syncs = counter(
            "llm_engine_host_syncs_total",
            "Blocking device->host transfers in the serving loop")
        self._m_horizon = Histogram(
            "llm_engine_decode_horizon",
            "Decode iterations fused per dispatch (adaptive horizon)",
            boundaries=HORIZON_BOUNDARIES,
            tag_keys=keys).set_default_tags(tag)
        # Prefix-reuse / prefill-efficiency plane (PR: shared-prefix KV
        # cache + chunked prefill):
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_reused_tokens = 0
        self.prefix_evictions = 0
        self.prefill_real_tokens = 0
        self.prefill_padded_tokens = 0
        self.prefill_stalls = 0
        self._m_prefix_lookups = counter(
            "llm_engine_prefix_lookups_total",
            "Admissions probed against the prefix-cache trie")
        self._m_prefix_hits = counter(
            "llm_engine_prefix_hits_total",
            "Admissions that matched >= 1 cached prefix block")
        self._m_prefix_reused = counter(
            "llm_engine_prefix_reused_tokens_total",
            "Prompt tokens copied from the prefix pool, not prefilled")
        self._m_prefix_evictions = counter(
            "llm_engine_prefix_evictions_total",
            "Cold prefix blocks recycled by LRU eviction")
        self._m_prefill_real = counter(
            "llm_engine_prefill_tokens_total",
            "True prompt/suffix tokens run through batched prefill")
        self._m_prefill_padded = counter(
            "llm_engine_prefill_padded_tokens_total",
            "Length-bucket + pow2-group filler tokens run through "
            "batched prefill (padding waste)")
        self._m_prefill_stalls = counter(
            "llm_engine_chunked_prefill_stalls_total",
            "Engine steps with >= 1 row frozen mid-chunked-prefill")
        # Async-pipeline plane (PR: double-buffered decode):
        self.pipeline_flushes = 0
        self.pipeline_overrun_tokens = 0
        self.host_lag_steps = 0
        self.pipeline_depth = _Agg()
        self._m_pipe_flushes = counter(
            "llm_engine_pipeline_flushes_total",
            "Forced full drains of the in-flight decode ring "
            "(pending admission, mid-prefill row, or end of stream)")
        self._m_pipe_overrun = counter(
            "llm_engine_pipeline_overrun_tokens_total",
            "Masked run-ahead decode iterations dispatched for rows "
            "that had already finished")
        self._m_host_lag = gauge(
            "llm_engine_host_lag_steps",
            "Fused decode steps dispatched but not yet replayed on "
            "the host (ring length after the last drain)")
        # Tensor-parallel plane (PR: sharded engine over an ICI mesh):
        self.tp_degree = 1
        self.host_transfer_bytes = 0
        self._m_tp_degree = gauge(
            "llm_engine_tp_degree",
            "Tensor-parallel degree of the serving mesh (1 = "
            "unsharded single-chip engine)")
        self._m_transfer_bytes = counter(
            "llm_engine_host_transfer_bytes_total",
            "Bytes moved device->host by the serving loop (drained "
            "[H, B] token blocks — replicated, so per-token bytes do "
            "not grow with tp degree)")
        # Paged-KV plane (PR: one refcounted block pool, zero-copy
        # prefix shares, preempt-and-swap):
        self.kv_blocks_shared = 0
        self.kv_block_cows = 0
        self.preemptions = 0
        self.swap_in_bytes = 0
        self.swap_out_bytes = 0
        self.kv_pool_blocks_total = 0
        self.kv_pool_blocks_in_use = 0
        self.kv_pool_blocks_free = 0
        self.kv_bytes_per_token = 0.0
        self._m_kv_shared = counter(
            "llm_engine_kv_blocks_shared_total",
            "Prefix-cache blocks SHARED into warm admissions by "
            "refcount (zero bytes copied — the block count beside "
            "prefix_reused_tokens)")
        self._m_kv_cow = counter(
            "llm_engine_kv_block_cow_total",
            "Shared blocks duplicated copy-on-write (a full-prompt "
            "hit whose tail block the new row must extend)")
        self._m_preemptions = counter(
            "llm_engine_preemptions_total",
            "Live decode rows evicted to free KV pool blocks "
            "(preempt-and-swap or preempt-and-recompute)")
        self._m_swap_out = counter(
            "llm_engine_swap_out_bytes_total",
            "Bytes spilled device->host by preemption swap-outs")
        self._m_swap_in = counter(
            "llm_engine_swap_in_bytes_total",
            "Bytes restored host->device by preemption swap-ins")
        # Disaggregated prefill/decode handoff plane:
        self.handoffs_out = 0
        self.handoffs_in = 0
        self.handoff_out_bytes = 0
        self.handoff_in_bytes = 0
        self._m_handoffs_out = counter(
            "llm_engine_handoffs_out_total",
            "Requests exported post-prefill to a decode-class "
            "replica (disaggregated fleet handoff)")
        self._m_handoffs_in = counter(
            "llm_engine_handoffs_in_total",
            "Requests imported from a prefill-class replica for "
            "decode (disaggregated fleet handoff)")
        self._m_handoff_out = counter(
            "llm_engine_handoff_out_bytes_total",
            "KV + logits bytes staged device->host by handoff "
            "exports")
        self._m_handoff_in = counter(
            "llm_engine_handoff_in_bytes_total",
            "KV + logits bytes accepted by handoff imports (swap "
            "pre-seed; 0 for a recompute-fallback import)")
        self._m_kv_pool_total = gauge(
            "llm_engine_kv_pool_blocks",
            "KV pool size in blocks (scratch block excluded)")
        self._m_kv_pool_in_use = gauge(
            "llm_engine_kv_pool_blocks_in_use",
            "KV pool blocks currently referenced by rows or the "
            "prefix trie")
        self._m_kv_pool_free = gauge(
            "llm_engine_kv_pool_blocks_free",
            "KV pool blocks on the free list")
        self._m_kv_bytes_per_token = gauge(
            "llm_engine_kv_bytes_per_token",
            "HBM bytes one cached token costs (quant dtype + its "
            "share of the per-block scale slab; the admission-"
            "capacity lever — see docs/serving.md)")
        # Speculative plane (PR: engine-integrated draft/verify). The
        # per-spec-plane llm_spec_* series live in SpecMetrics, tagged
        # with the SAME engine id; these engine-tagged aggregates let
        # dashboards join acceptance onto the other engine series.
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self._m_spec_rounds = counter(
            "llm_engine_spec_rounds_total",
            "Draft-propose / target-verify rounds replayed at drain")
        self._m_spec_proposed = counter(
            "llm_engine_spec_proposed_total",
            "Draft tokens proposed inside fused spec dispatches")
        self._m_spec_accepted = counter(
            "llm_engine_spec_accepted_total",
            "Proposed draft tokens the target accepted")
        self._m_spec_rate = gauge(
            "llm_engine_spec_acceptance_rate",
            "Cumulative accepted / proposed (0..1; 0 with spec off)")
        # Multi-LoRA plane (PR: batched heterogeneous-adapter decode
        # with HBM adapter residency). Counters track the AdapterPool's
        # LRU: a lookup is one admission-gate slot acquisition attempt,
        # a hit means the adapter was already resident.
        self.adapter_lookups = 0
        self.adapter_hits = 0
        self.adapter_prefetches = 0
        self.adapter_evictions = 0
        self.adapter_deferrals = 0
        self.adapter_slots = 0
        self.adapter_slots_resident = 0
        self.adapter_slots_pinned = 0
        self._m_adapter_lookups = counter(
            "llm_engine_adapter_lookups_total",
            "Adapter-slot acquisition attempts at the admission gate")
        self._m_adapter_hits = counter(
            "llm_engine_adapter_hits_total",
            "Slot acquisitions that found the adapter already "
            "resident in HBM")
        self._m_adapter_prefetches = counter(
            "llm_engine_adapter_prefetches_total",
            "Async host->device adapter weight transfers started for "
            "cold adapters")
        self._m_adapter_evictions = counter(
            "llm_engine_adapter_evictions_total",
            "Refcount-0 resident adapters evicted LRU-first to free "
            "a slot for a committing prefetch")
        self._m_adapter_deferrals = counter(
            "llm_engine_adapter_prefetch_deferrals_total",
            "Admissions requeued because their adapter was cold and "
            "its prefetch had not committed yet")
        self._m_adapter_slots = gauge(
            "llm_engine_adapter_slots",
            "Adapter slots in the device-resident stacks (null slot "
            "0 excluded)")
        self._m_adapter_resident = gauge(
            "llm_engine_adapter_slots_resident",
            "Slots currently holding a committed adapter")
        self._m_adapter_pinned = gauge(
            "llm_engine_adapter_slots_pinned",
            "Resident slots pinned by >= 1 live row (ineligible for "
            "eviction)")

    # -- lifecycle hooks (called by DecodeEngine) --------------------------

    def on_submit(self, req_id: int) -> None:
        self._req[req_id] = _ReqTimes(self._clock())
        self.requests_submitted += 1
        self._m_submitted.inc()

    def on_reject(self) -> None:
        self.requests_rejected += 1
        self._m_rejected.inc()

    def on_shed(self, req_id: int) -> None:
        """A queued request crossed its deadline and was retired
        WITHOUT prefilling (the overload plane's reject-before-prefill
        path). Distinct from on_reject: rejection is queue-full
        backpressure at submit; shedding is deadline expiry of an
        accepted request."""
        self.requests_shed += 1
        self._m_shed.inc()
        self._req.pop(req_id, None)

    def on_admit(self, req_id: int) -> None:
        rt = self._req.get(req_id)
        if rt is None or rt.admit_t is not None:
            return
        rt.admit_t = self._clock()
        wait = rt.admit_t - rt.submit_t
        self.requests_admitted += 1
        self.queue_wait_s.add(wait)
        self._m_queue_wait.observe(wait)

    def on_decodable(self, req_id: int) -> None:
        """The request's row can decode: its last prompt chunk is
        dispatched (or its K/V was swapped back in). Before the first
        token this may fire again (a preempted row prefills twice); the
        last one before the first token splits the wait."""
        rt = self._req.get(req_id)
        if rt is not None and rt.first_token_t is None:
            rt.decodable_t = self._clock()

    def on_block(self, dispatch_t: float, blocks_ahead: int,
                 horizon: int) -> None:
        """The drained block whose tokens the `on_tokens` calls that
        follow hand over: the engine clock at its dispatch's return, the
        blocks in flight in front of it then, its horizon."""
        self._block = (dispatch_t, blocks_ahead, horizon)

    def _on_first_token(self, rt: _ReqTimes, now: float) -> None:
        """TTFT and its inner parts close together, so that
        queue_wait + prefill + first_block == ttft, and first_dispatch
        + first_return == first_block, for every request."""
        rt.first_token_t = now
        admit_t = rt.submit_t if rt.admit_t is None else rt.admit_t
        dec_t = admit_t if rt.decodable_t is None else rt.decodable_t
        self.prefill_s.add(dec_t - admit_t)
        self.first_block_s.add(now - dec_t)
        disp_t, ahead, horizon = self._block
        if disp_t is None or disp_t < dec_t:   # no block named: all wait
            disp_t = dec_t
        self.first_dispatch_s.add(disp_t - dec_t)
        self.first_return_s.add(now - disp_t)
        self.first_blocks_ahead.add(ahead)
        self.first_block_horizon.add(horizon)
        ttft = now - rt.submit_t
        self.ttft_s.add(ttft)
        self._m_ttft.observe(ttft)

    def on_token(self, req_id: int, n: int = 1) -> None:
        rt = self._req.get(req_id)
        now = self._clock()
        self.tokens_generated += n
        self._m_tokens.inc(n)
        if rt is None:
            return
        if rt.first_token_t is None:
            self._on_first_token(rt, now)
        else:
            tpot = now - rt.last_token_t
            self.tpot_s.add(tpot)
            self._m_tpot.observe(tpot)
        rt.last_token_t = now
        rt.n_tokens += n

    def on_tokens(self, req_id: int, n: int) -> None:
        """`n` tokens of one request landing TOGETHER (one drained
        [H, B] block) — the vectorized twin of per-token `on_token`
        calls, preserving its observation arithmetic: TTFT once at the
        request's first token, then one TPOT observation per further
        token (total = tokens - 1 per request). A later block's `n`
        tokens each take an `n`-th of the wall gap since the block
        before (same count, same sum as one real gap and `n - 1` zeros,
        but a median that is the device's cadence and not 0); the
        tokens that land WITH the first one wait 0.0 behind it. The
        block's observations are ONE weighted call of the aggregate and
        of the histogram, not a call a token."""
        if n <= 0:
            return
        rt = self._req.get(req_id)
        now = self._clock()
        self.tokens_generated += n
        self._m_tokens.inc(n)
        if rt is None:
            return
        if rt.first_token_t is None:
            self._on_first_token(rt, now)
            k, tpot = n - 1, 0.0
        else:
            k, tpot = n, (now - rt.last_token_t) / n
        if k:
            self.tpot_s.add(tpot, k)
            self._m_tpot.observe(tpot, n=k)
        rt.last_token_t = now
        rt.n_tokens += n

    def on_finish(self, req_id: int) -> None:
        self.requests_finished += 1
        self._m_finished.inc()
        self._req.pop(req_id, None)

    def on_step(self, live_slots: int, queue_depth: int,
                tokens_emitted: int) -> None:
        self.steps += 1
        self.live_slots = live_slots
        self.queue_depth = queue_depth
        self.batch_efficiency = tokens_emitted / self.batch_slots
        self._m_steps.inc()
        self._m_queue_depth.set(queue_depth)
        self._m_occupancy.set(live_slots / self.batch_slots)
        self._m_batch_eff.set(self.batch_efficiency)

    def on_dispatch(self, horizon: int, host_syncs: int = 1) -> None:
        """One fused decode dispatch of `horizon` iterations, costing
        `host_syncs` blocking device->host transfers (1 on the fused
        path: the [H, B] token block)."""
        self.decode_dispatches += 1
        self.host_syncs += host_syncs
        self.decode_horizon.add(horizon)
        self._m_dispatches.inc()
        if host_syncs > 0:
            self._m_host_syncs.inc(host_syncs)
        self._m_horizon.observe(horizon)

    def on_host_sync(self, n: int = 1, nbytes: int = 0) -> None:
        """A blocking device->host pull completed (a drained token
        block of `nbytes` bytes). Decoupled from `on_dispatch` by the
        async pipeline — dispatch happens up to `pipeline_depth` steps
        before its block's sync; totals converge once the ring
        drains."""
        self.host_syncs += n
        self._m_host_syncs.inc(n)
        if nbytes > 0:
            self.host_transfer_bytes += nbytes
            self._m_transfer_bytes.inc(nbytes)

    def on_tp_degree(self, tp: int) -> None:
        """Record the engine's tensor-parallel degree (once, at
        construction)."""
        self.tp_degree = int(tp)
        self._m_tp_degree.set(float(tp))

    def on_pipeline_drain(self, depth: int, lag: int) -> None:
        """One in-flight block replayed: `depth` fused steps were in
        flight when the drain started (1 = synchronous), `lag` remain
        after it (the host_lag_steps gauge)."""
        self.pipeline_depth.add(depth)
        self.host_lag_steps = lag
        self._m_host_lag.set(lag)

    def on_pipeline_flush(self, n: int = 1) -> None:
        self.pipeline_flushes += n
        self._m_pipe_flushes.inc(n)

    def on_pipeline_overrun(self, n: int) -> None:
        if n > 0:
            self.pipeline_overrun_tokens += n
            self._m_pipe_overrun.inc(n)

    def on_prefix(self, *, hit: bool, reused_tokens: int = 0) -> None:
        """One admission probed the prefix-cache trie; on a hit,
        `reused_tokens` prompt tokens were copied instead of run."""
        self.prefix_lookups += 1
        self._m_prefix_lookups.inc()
        if hit:
            self.prefix_hits += 1
            self._m_prefix_hits.inc()
        if reused_tokens > 0:
            self.prefix_reused_tokens += reused_tokens
            self._m_prefix_reused.inc(reused_tokens)

    def on_prefix_evictions(self, n: int = 1) -> None:
        if n > 0:
            self.prefix_evictions += n
            self._m_prefix_evictions.inc(n)

    def on_kv_shared(self, n: int) -> None:
        """`n` pool blocks handed to an admission by incref — the warm
        part of the prompt cost zero copy bytes."""
        if n > 0:
            self.kv_blocks_shared += n
            self._m_kv_shared.inc(n)

    def on_kv_cow(self, n: int = 1) -> None:
        if n > 0:
            self.kv_block_cows += n
            self._m_kv_cow.inc(n)

    def on_preempt(self, n: int = 1) -> None:
        if n > 0:
            self.preemptions += n
            self._m_preemptions.inc(n)

    def on_swap_out(self, nbytes: int) -> None:
        if nbytes > 0:
            self.swap_out_bytes += nbytes
            self._m_swap_out.inc(nbytes)

    def on_swap_in(self, nbytes: int) -> None:
        if nbytes > 0:
            self.swap_in_bytes += nbytes
            self._m_swap_in.inc(nbytes)

    def on_handoff_out(self, req_id: int, nbytes: int) -> None:
        """A request left this engine mid-flight (prefill→decode
        handoff): its per-request timing record goes with it — the
        importing engine owns TTFT/TPOT from here (the fleet stitches
        end-to-end TTFT itself)."""
        self.handoffs_out += 1
        self._m_handoffs_out.inc()
        if nbytes > 0:
            self.handoff_out_bytes += nbytes
            self._m_handoff_out.inc(nbytes)
        self._req.pop(req_id, None)

    def on_handoff_in(self, nbytes: int) -> None:
        self.handoffs_in += 1
        self._m_handoffs_in.inc()
        if nbytes > 0:
            self.handoff_in_bytes += nbytes
            self._m_handoff_in.inc(nbytes)

    def on_kv_pool(self, total: int, in_use: int, free: int,
                   bytes_per_token: float = 0.0) -> None:
        """Gauge update at step end: pool occupancy in blocks, plus
        the engine's per-token KV cost (constant per engine — quant
        dtype + scale-slab share — but exported per step so the fleet
        plane can weight occupancy into bytes)."""
        self.kv_pool_blocks_total = total
        self.kv_pool_blocks_in_use = in_use
        self.kv_pool_blocks_free = free
        self._m_kv_pool_total.set(total)
        self._m_kv_pool_in_use.set(in_use)
        self._m_kv_pool_free.set(free)
        if bytes_per_token > 0:
            self.kv_bytes_per_token = bytes_per_token
            self._m_kv_bytes_per_token.set(bytes_per_token)

    def on_prefill_batch(self, real_tokens: int,
                         padded_tokens: int) -> None:
        """One batched prefill program: `real_tokens` true chunk tokens
        plus `padded_tokens` bucket/pow2 filler riding along."""
        self.prefill_real_tokens += real_tokens
        self.prefill_padded_tokens += padded_tokens
        if real_tokens > 0:
            self._m_prefill_real.inc(real_tokens)
        if padded_tokens > 0:
            self._m_prefill_padded.inc(padded_tokens)

    def on_prefill_stall(self, n: int = 1) -> None:
        """One engine step ran with >= 1 row frozen mid-chunked-prefill
        (decode advanced without it, or was skipped entirely)."""
        if n > 0:
            self.prefill_stalls += n
            self._m_prefill_stalls.inc(n)

    def on_spec_round(self, rounds: int, proposed: int,
                      accepted: int) -> None:
        """One drained speculative block's acceptance accounting:
        `rounds` live greedy rows each verified their proposals —
        `proposed` draft tokens total, of which `accepted` matched the
        target's argmax chain (and were emitted for free)."""
        self.spec_rounds += rounds
        self.spec_proposed += proposed
        self.spec_accepted += accepted
        if rounds > 0:
            self._m_spec_rounds.inc(rounds)
        if proposed > 0:
            self._m_spec_proposed.inc(proposed)
        if accepted > 0:
            self._m_spec_accepted.inc(accepted)
        if self.spec_proposed:
            self._m_spec_rate.set(self.spec_accepted
                                  / self.spec_proposed)

    def on_adapter_lookup(self, hit: bool) -> None:
        """One adapter-slot acquisition attempt at the admission gate
        (AdapterPool.alloc for a non-None adapter_id)."""
        self.adapter_lookups += 1
        self._m_adapter_lookups.inc()
        if hit:
            self.adapter_hits += 1
            self._m_adapter_hits.inc()

    def on_adapter_prefetch(self, n: int = 1) -> None:
        if n > 0:
            self.adapter_prefetches += n
            self._m_adapter_prefetches.inc(n)

    def on_adapter_evict(self, n: int = 1) -> None:
        if n > 0:
            self.adapter_evictions += n
            self._m_adapter_evictions.inc(n)

    def on_adapter_defer(self, n: int = 1) -> None:
        """An admission was requeued waiting on its adapter's
        prefetch instead of stalling the step."""
        if n > 0:
            self.adapter_deferrals += n
            self._m_adapter_deferrals.inc(n)

    def on_adapter_slots(self, total: int, resident: int,
                         pinned: int) -> None:
        """Gauge update after a pool state change (commit/evict)."""
        self.adapter_slots = total
        self.adapter_slots_resident = resident
        self.adapter_slots_pinned = pinned
        self._m_adapter_slots.set(total)
        self._m_adapter_resident.set(resident)
        self._m_adapter_pinned.set(pinned)

    def observe_queue_depth(self, depth: int) -> None:
        """Gauge update outside a step (e.g. right after submit)."""
        self.queue_depth = depth
        self._m_queue_depth.set(depth)

    # -- snapshot ----------------------------------------------------------

    def stats(self) -> Dict[str, float]:
        """Flat numeric snapshot of everything above — each field can
        be re-published as a gauge (serve.metrics.report_engine_stats)
        or asserted on directly in tests."""
        out: Dict[str, float] = {
            "requests_submitted": self.requests_submitted,
            "requests_admitted": self.requests_admitted,
            "requests_finished": self.requests_finished,
            "requests_rejected": self.requests_rejected,
            "requests_shed": self.requests_shed,
            "tokens_generated": self.tokens_generated,
            "steps": self.steps,
            "queue_depth": self.queue_depth,
            "live_slots": self.live_slots,
            "slot_occupancy": self.live_slots / self.batch_slots,
            "batch_efficiency": self.batch_efficiency,
        }
        out["decode_dispatches"] = self.decode_dispatches
        out["host_syncs"] = self.host_syncs
        out["host_syncs_per_token"] = (
            self.host_syncs / self.tokens_generated
            if self.tokens_generated else 0.0)
        out["tp_degree"] = self.tp_degree
        out["host_transfer_bytes"] = self.host_transfer_bytes
        out["host_transfer_bytes_per_token"] = (
            self.host_transfer_bytes / self.tokens_generated
            if self.tokens_generated else 0.0)
        out["dispatches_per_token"] = (
            self.decode_dispatches / self.tokens_generated
            if self.tokens_generated else 0.0)
        out["prefix_lookups"] = self.prefix_lookups
        out["prefix_hits"] = self.prefix_hits
        out["prefix_hit_rate"] = (
            self.prefix_hits / self.prefix_lookups
            if self.prefix_lookups else 0.0)
        out["prefix_reused_tokens"] = self.prefix_reused_tokens
        out["prefix_evictions"] = self.prefix_evictions
        out["prefill_real_tokens"] = self.prefill_real_tokens
        out["prefill_padded_tokens"] = self.prefill_padded_tokens
        prefill_total = self.prefill_real_tokens + self.prefill_padded_tokens
        out["prefill_padding_waste_frac"] = (
            self.prefill_padded_tokens / prefill_total
            if prefill_total else 0.0)
        out["chunked_prefill_stalls"] = self.prefill_stalls
        out["pipeline_flushes"] = self.pipeline_flushes
        out["pipeline_overrun_tokens"] = self.pipeline_overrun_tokens
        out["kv_blocks_shared"] = self.kv_blocks_shared
        out["kv_block_cows"] = self.kv_block_cows
        out["preemptions"] = self.preemptions
        out["swap_in_bytes"] = self.swap_in_bytes
        out["swap_out_bytes"] = self.swap_out_bytes
        out["handoffs_out"] = self.handoffs_out
        out["handoffs_in"] = self.handoffs_in
        out["handoff_out_bytes"] = self.handoff_out_bytes
        out["handoff_in_bytes"] = self.handoff_in_bytes
        out["kv_pool_blocks_total"] = self.kv_pool_blocks_total
        out["kv_pool_blocks_in_use"] = self.kv_pool_blocks_in_use
        out["kv_pool_blocks_free"] = self.kv_pool_blocks_free
        out["kv_bytes_per_token"] = self.kv_bytes_per_token
        out["kv_pool_occupancy"] = (
            self.kv_pool_blocks_in_use / self.kv_pool_blocks_total
            if self.kv_pool_blocks_total else 0.0)
        out["host_lag_steps"] = self.host_lag_steps
        out["pipeline_depth_effective"] = (
            self.pipeline_depth.sum / self.pipeline_depth.count
            if self.pipeline_depth.count else 0.0)
        out["spec_rounds"] = self.spec_rounds
        out["spec_proposed"] = self.spec_proposed
        out["spec_accepted"] = self.spec_accepted
        out["spec_acceptance_rate"] = (
            self.spec_accepted / self.spec_proposed
            if self.spec_proposed else 0.0)
        out["adapter_lookups"] = self.adapter_lookups
        out["adapter_hits"] = self.adapter_hits
        out["adapter_hit_rate"] = (
            self.adapter_hits / self.adapter_lookups
            if self.adapter_lookups else 0.0)
        out["adapter_prefetches"] = self.adapter_prefetches
        out["adapter_evictions"] = self.adapter_evictions
        out["adapter_prefetch_deferrals"] = self.adapter_deferrals
        out["adapter_slots"] = self.adapter_slots
        out["adapter_slots_resident"] = self.adapter_slots_resident
        out["adapter_slots_pinned"] = self.adapter_slots_pinned
        self.queue_wait_s.fields("queue_wait_s", out)
        self.prefill_s.fields("prefill_s", out)
        self.first_block_s.fields("first_block_s", out)
        self.first_dispatch_s.fields("first_dispatch_s", out)
        self.first_return_s.fields("first_return_s", out)
        self.first_blocks_ahead.fields("first_blocks_ahead", out)
        self.first_block_horizon.fields("first_block_horizon", out)
        self.ttft_s.fields("ttft_s", out)
        self.tpot_s.fields("tpot_s", out)
        self.decode_horizon.fields("decode_horizon", out)
        return out


class NullEngineMetrics:
    """No-op twin for benchmark loops that must not pay even the
    timestamping cost (DecodeEngine(..., enable_metrics=False))."""

    engine_id = "disabled"

    def on_submit(self, req_id): pass

    def on_reject(self): pass

    def on_shed(self, req_id): pass

    def on_admit(self, req_id): pass

    def on_decodable(self, req_id): pass

    def on_token(self, req_id, n=1): pass

    def on_tokens(self, req_id, n): pass

    def on_block(self, dispatch_t, blocks_ahead, horizon): pass

    def on_finish(self, req_id): pass

    def on_step(self, live_slots, queue_depth, tokens_emitted): pass

    def on_dispatch(self, horizon, host_syncs=1): pass

    def on_host_sync(self, n=1, nbytes=0): pass

    def on_tp_degree(self, tp): pass

    def on_pipeline_drain(self, depth, lag): pass

    def on_pipeline_flush(self, n=1): pass

    def on_pipeline_overrun(self, n): pass

    def on_prefix(self, *, hit, reused_tokens=0): pass

    def on_prefix_evictions(self, n=1): pass

    def on_kv_shared(self, n): pass

    def on_kv_cow(self, n=1): pass

    def on_preempt(self, n=1): pass

    def on_swap_out(self, nbytes): pass

    def on_swap_in(self, nbytes): pass

    def on_handoff_out(self, req_id, nbytes): pass

    def on_handoff_in(self, nbytes): pass

    def on_kv_pool(self, total, in_use, free, bytes_per_token=0.0): pass

    def on_prefill_batch(self, real_tokens, padded_tokens): pass

    def on_prefill_stall(self, n=1): pass

    def on_spec_round(self, rounds, proposed, accepted): pass

    def on_adapter_lookup(self, hit): pass

    def on_adapter_prefetch(self, n=1): pass

    def on_adapter_evict(self, n=1): pass

    def on_adapter_defer(self, n=1): pass

    def on_adapter_slots(self, total, resident, pinned): pass

    def observe_queue_depth(self, depth): pass

    def stats(self):
        return {}

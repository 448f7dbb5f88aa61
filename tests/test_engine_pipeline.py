"""Async double-buffered decode pipeline (ray_tpu/models/engine.py).

With `pipeline_depth >= 2` the engine keeps a bounded ring of fused
decode steps in flight during pure-decode stretches: step N+1 is
dispatched BEFORE step N's token block is pulled to the host, chained
off the previous dispatch's device-carried row state, with the block's
`copy_to_host_async` overlapping the next step's compute. These tests
pin the contract:

- output stays TOKEN-IDENTICAL to the synchronous engine (and hence to
  solo `generate`, which the depth-1 engine is already tested against)
  at every depth, every sampling mode, with and without the prefix
  cache and chunked prefill;
- the ring FLUSHES before any admission (scheduling sees fully
  replayed host state) and at end of stream (no stranded blocks); a
  queue behind FULL slots is no admission: the ring runs ahead of every
  block in which no row's budget ends, and a slot freed by budget
  admits its newcomer in the step the synchronous engine would;
- rows finishing mid-flight retire exactly as in the sync engine, and
  their run-ahead iterations are accounted as pipeline_overrun_tokens;
- the loop never blocks on a host sync before dispatching the next
  queued step (the non-blocking-dispatch gate — the pipelining analog
  of test_engine_horizon's transfer gate).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, llama_init  # noqa: E402
from ray_tpu.models import engine as engine_mod  # noqa: E402
from ray_tpu.models.engine import DecodeEngine  # noqa: E402
from ray_tpu.models.scheduler import (FIFOPolicy, PriorityPolicy,  # noqa: E402
                                      PrefixAffinityPolicy)


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(n, cfg, seed=7, lo=3, hi=9):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size,
                        size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


def _run(params, cfg, prompts, budgets, depth, *, eng_kw=None,
         sub_kw=None):
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       pipeline_depth=depth, **(eng_kw or {}))
    ids = [eng.submit(p, n, **(sub_kw or {}))
           for p, n in zip(prompts, budgets)]
    out = eng.run()
    return [out[r] for r in ids], eng


def _run_saturated(params, cfg, prompts, budgets, depth, *, eng_kw=None):
    """A queue that is never empty while a request is left to submit:
    two slots, and before every step the queue is fed up to three, more
    than a step can admit. Returns (tokens per request, per-call
    emissions, engine)."""
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       pipeline_depth=depth, **(eng_kw or {}))
    work = list(zip(prompts, budgets))
    ids, calls = [], []
    while work or eng.pending():
        while work and len(eng.scheduler) < 3:
            ids.append(eng.submit(*work.pop(0)))
        calls.append(eng.step())
    return [eng.pop_result(r) for r in ids], calls, eng


# ---------------------------------------------------------------------------
# Token identity: depth x sampling mode x prefix cache x chunked prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    {"greedy": True},
    {"greedy": False, "temperature": 0.9, "top_k": 5},
    {"greedy": False, "temperature": 1.1, "top_p": 0.9},
], ids=["greedy", "top_k", "top_p"])
@pytest.mark.parametrize("features", [
    {},
    {"prefix_cache": True, "kv_block_tokens": 4},
    {"prefill_chunk": 3},
    {"prefix_cache": True, "kv_block_tokens": 4, "prefill_chunk": 3},
], ids=["plain", "prefix", "chunked", "prefix+chunked"])
@pytest.mark.parametrize("queue", ["drains", "never_empty"])
def test_pipeline_token_identity_matrix(nano_model, mode, features, queue):
    """Every (depth, sampling, prefix/chunk) combination produces the
    SAME tokens as the synchronous depth-1 engine — the pipeline is a
    pure latency optimization. Shared-prefix prompts exercise the trie
    under the prefix-cache variants; 5 requests through 2 slots churn
    admissions between pure-decode stretches. `never_empty` keeps a
    request waiting behind two full slots for the whole run, with
    budgets that outlast a block: the ring runs ahead with a queue
    behind it, call for call what the synchronous engine emits."""
    cfg, params = nano_model
    base = _prompts(6, cfg)
    # Give two prompts a shared 8-token prefix so the prefix cache hits.
    shared = list(range(3, 11))
    prompts = [shared + p for p in base[:2]] + base[2:]
    kw = {**mode, **features}
    if queue == "drains":
        prompts, budgets = prompts[:5], [7, 4, 9, 5, 6]
    else:
        budgets = [13, 9, 17, 11, 12, 10]
        kw["decode_horizon"] = 4

    def run(depth):
        if queue == "never_empty":
            return _run_saturated(params, cfg, prompts, budgets, depth,
                                  eng_kw=kw)
        got, eng = _run(params, cfg, prompts, budgets, depth, eng_kw=kw)
        return got, None, eng

    ref, ref_calls, _ = run(1)
    for depth in (2, 4):
        got, calls, eng = run(depth)
        assert calls == ref_calls, f"depth={depth}: a call differs"
        assert got == ref, f"depth={depth} diverged"
        s = eng.stats()
        # The drained engine holds no in-flight blocks and every
        # dispatch got exactly one drain.
        assert s["host_lag_steps"] == 0.0
        assert s["decode_dispatches"] == s["host_syncs"]
        if queue == "never_empty":
            assert s["decode_dispatches_chained_queued"] > 0


def test_saturated_queue_identity_sparse_family(nano_olmoe):
    """The sparse family behind a queue that never empties: depth 2 is
    the synchronous engine call for call (the dense family's case is the
    matrix above, the hybrid and latent families' are in their own
    files)."""
    cfg, params = nano_olmoe
    prompts = _prompts(6, cfg, seed=31)
    budgets = [13, 9, 17, 11, 12, 10]
    kw = {"decode_horizon": 4}
    ref, ref_calls, _ = _run_saturated(params, cfg, prompts, budgets, 1,
                                       eng_kw=kw)
    got, calls, eng = _run_saturated(params, cfg, prompts, budgets, 2,
                                     eng_kw=kw)
    assert got == ref and calls == ref_calls
    assert eng.stats()["decode_dispatches_chained_queued"] > 0


def test_pipeline_identity_under_eviction_pressure(nano_model):
    """A prefix pool too small for the working set (constant LRU
    eviction + re-prefill) must not perturb pipelined output."""
    from ray_tpu.models.prefix_cache import block_bytes

    cfg, params = nano_model
    rng = np.random.RandomState(3)
    # 4 usable blocks; 3 distinct 8-token prefixes x 2 blocks = 6
    # committed blocks wanted -> guaranteed eviction churn.
    bb = block_bytes(cfg.n_layers, 4, cfg.n_kv_heads, cfg.head_dim, 4)
    prompts = []
    for i in range(3):
        pref = rng.randint(1, cfg.vocab_size, size=8).tolist()
        prompts += [pref + [30 + i], pref + [40 + i]]
    budgets = [5] * 6
    kw = {"prefix_cache": True, "kv_block_tokens": 4,
          "kv_pool_bytes": 4 * bb}
    ref, eng = _run(params, cfg, prompts, budgets, 1, eng_kw=kw)
    assert eng.stats()["prefix_evictions"] > 0   # pressure was real
    for depth in (2, 4):
        got, _ = _run(params, cfg, prompts, budgets, depth, eng_kw=kw)
        assert got == ref


def test_pipeline_per_call_emissions_match_sync(nano_model):
    """Not just final outputs: EACH step() call's emitted dict matches
    the synchronous engine's call-for-call (the drain-one-behind ring
    reproduces sync's per-call horizon arithmetic), so streaming
    callers see identical chunk boundaries."""
    cfg, params = nano_model
    prompts = _prompts(3, cfg, seed=11)
    budgets = [6, 9, 4]

    def stream(depth):
        eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                           pipeline_depth=depth)
        for p, n in zip(prompts, budgets):
            eng.submit(p, n)
        seq = []
        while eng.pending():
            seq.append(eng.step())
        return seq

    assert stream(2) == stream(1)
    assert stream(4) == stream(1)


# ---------------------------------------------------------------------------
# Retirement / flush semantics
# ---------------------------------------------------------------------------

def test_mid_flight_eos_retires_like_sync(nano_model):
    """A row hitting eos inside a RUN-AHEAD block retires with exactly
    the tokens sync emits (truncated at eos), the already-dispatched
    successor block's iterations for that row are masked on device and
    counted as overrun, and the freed slot admits a newcomer only
    after the flush."""
    cfg, params = nano_model
    prompts = _prompts(2, cfg, seed=5)
    ref, _ = _run(params, cfg, prompts, [12, 12], 1,
                  eng_kw={"eos_id": 9})
    got, eng = _run(params, cfg, prompts, [12, 12], 2,
                    eng_kw={"eos_id": 9})
    assert got == ref
    s = eng.stats()
    if any(len(t) < 12 for t in ref):     # some row did hit eos early
        assert all(t[-1] == 9 for t in ref if len(t) < 12)
    assert s["host_lag_steps"] == 0.0


@pytest.mark.parametrize("slots", [3, 2], ids=["a_slot_free", "slots_full"])
def test_flush_before_admission(nano_model, slots):
    """Submitting while blocks are in flight forces a pipeline flush
    BEFORE the admission WHEN A SLOT IS FREE for the newcomer: the
    admitted prompt's prefill must not race run-ahead decode blocks that
    assumed a pure-decode batch. The flush shows up in pipeline_flushes.
    With both slots taken by rows whose budgets outlast the ring, the
    queued request is no admission: nothing is flushed, the ring stays
    a block ahead, and the newcomer is admitted (after a flush-free
    drain) once a budget ends. Its output is unperturbed either way."""
    cfg, params = nano_model
    prompts = _prompts(3, cfg, seed=13)
    eng = DecodeEngine(params, cfg, batch_slots=slots, max_len=64,
                       pipeline_depth=2, decode_horizon=4)
    a = eng.submit(prompts[0], 16)
    b = eng.submit(prompts[1], 16)
    eng.step()   # admit both -> queue empty -> pure decode: the step
    #              dispatches, tops the ring up, drains one behind
    assert eng.stats()["host_lag_steps"] >= 1.0
    flushes0 = eng.stats()["pipeline_flushes"]
    c = eng.submit(prompts[2], 6)
    eng.step()
    s = eng.stats()
    if slots == 3:               # pending admission -> flush
        assert s["pipeline_flushes"] == flushes0 + 1
    else:                        # nobody to admit: still a block ahead
        assert s["pipeline_flushes"] == flushes0
        assert s["host_lag_steps"] == 1.0
        assert s["decode_dispatches_chained_queued"] == 1.0
    out = eng.run()
    ref, _ = _run(params, cfg, [prompts[2]], [6], 1)
    assert out[c] == ref[0]
    assert len(out[a]) == 16 and len(out[b]) == 16


def test_no_run_ahead_of_a_block_in_which_a_budget_ends(nano_model):
    """Two full slots and a request queued: `a` (6 tokens) ends inside
    the second block of 4. The ring runs ahead of the first block (no
    budget ends in it) and NOT of the second: when that block is pulled
    nothing is in flight, so the step after it admits the newcomer, the
    very step the synchronous engine admits it in."""
    cfg, params = nano_model
    prompts = _prompts(3, cfg, seed=37)
    budgets = [6, 16, 5]

    def drive(depth):
        eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                           pipeline_depth=depth, decode_horizon=4)
        ids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        lag, calls, admitted = [], [], {}
        while eng.pending():
            calls.append(eng.step())
            lag.append(eng.stats()["host_lag_steps"])
            for r in eng.row_req:
                if r is not None:
                    admitted.setdefault(r.req_id, len(calls) - 1)
        return ids, admitted, calls, lag, eng.stats()

    ids1, adm1, calls1, _, s1 = drive(1)
    ids2, adm2, calls2, lag2, s2 = drive(2)
    assert ids1 == ids2 and calls2 == calls1
    assert adm2 == adm1 and adm1[ids1[2]] == 2     # the third call
    # call 0 ran ahead of block 0 with the newcomer queued; call 1 pulled
    # the block in which `a` ends with nothing dispatched behind it
    assert lag2[:2] == [1.0, 0.0]
    assert s2["decode_dispatches_chained_queued"] == 1.0
    assert s2["pipeline_flushes"] == 0.0
    assert s1["decode_dispatches_chained"] == 0.0


def test_eos_behind_a_queue_frees_its_slot_at_most_a_block_late(nano_model):
    """The one delay run-ahead behind a queue can cost: a row that ends
    by EOS inside block N while N+1 is in flight (the host cannot know
    an EOS before it has the block) frees its slot one block later. The
    newcomer's first tokens come at most one call after the synchronous
    engine's (here exactly one: the flushing call hands over block N+1
    and dispatches the newcomer's), and every request's tokens are the
    synchronous engine's."""
    cfg, params = nano_model
    prompts = _prompts(3, cfg, seed=41)
    budgets = [24, 24, 6]
    free, _ = _run(params, cfg, prompts[:2], budgets[:2], 1,
                   eng_kw={"decode_horizon": 4})
    # an EOS id that row 0 emits first inside its second block of 4
    eos = next(t for i, t in enumerate(free[0][4:8], 4)
               if t not in free[0][:i] and t not in free[1][:i + 4])
    out, first = {}, {}
    for depth in (1, 2):
        eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                           pipeline_depth=depth, decode_horizon=4,
                           eos_id=eos)
        ids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        calls = []
        while eng.pending():
            calls.append(eng.step())
        first[depth] = next(i for i, c in enumerate(calls) if ids[2] in c)
        out[depth] = [eng.pop_result(r) for r in ids]
        s = eng.stats()
        assert s["decode_dispatches_chained_queued"] == 2 * (depth - 1)
        assert (s["pipeline_overrun_tokens"] > 0) == (depth == 2)
    assert out[2] == out[1]
    assert out[1][0][-1] == eos and 5 <= len(out[1][0]) <= 8
    assert first[1] == 2 and first[2] == 3


def test_a_flushing_step_returns_what_the_flush_drained(nano_model):
    """A step that finds an admission while a run-ahead block is in
    flight drains that block, admits, dispatches the prefill and the
    next decode block, and RETURNS the drained tokens: it does not hold
    them until the block it just dispatched has run as well (a request
    whose last tokens ride the flushed block would end a whole prefill
    and decode block late, or not, by when the newcomer arrived). The
    newcomer's first tokens come from the next call; every stream is the
    synchronous engine's."""
    cfg, params = nano_model
    prompts = _prompts(2, cfg, seed=21)
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=64,
                       pipeline_depth=2, decode_horizon=4)
    a = eng.submit(prompts[0], 16)
    first = eng.step()              # admit, dispatch, run ahead, drain one
    assert len(first[a]) == 4 and eng.stats()["host_lag_steps"] == 1.0
    c = eng.submit(prompts[1], 6)   # pending admission -> flush
    syncs0 = eng.stats()["host_syncs"]
    got = eng.step()
    s = eng.stats()
    assert s["host_syncs"] == syncs0 + 1        # the flushed block only
    assert got == {a: got[a]} and len(got[a]) == 4
    # the new block alone: a second newcomer would wait for one block
    assert s["host_lag_steps"] == 1.0
    assert s["prefill_dispatches"] >= 2.0       # the newcomer IS admitted
    nxt = eng.step()
    assert len(nxt[a]) == 4 and 1 <= len(nxt[c]) <= 4
    out = eng.run()
    ref, _ = _run(params, cfg, prompts, [16, 6], 1)
    assert [out[a], out[c]] == ref


def test_end_of_stream_flush_never_strands_blocks(nano_model):
    """When the last live row finishes while run-ahead blocks remain,
    the same step drains them (all-masked overrun): pending() turns
    false, results are complete, host_lag_steps reads 0."""
    cfg, params = nano_model
    prompts = _prompts(2, cfg, seed=17)
    got, eng = _run(params, cfg, prompts, [8, 8], 4,
                    eng_kw={"decode_horizon": 2})
    assert all(len(t) == 8 for t in got)
    assert not eng.pending()
    s = eng.stats()
    assert s["host_lag_steps"] == 0.0
    assert s["decode_dispatches"] == s["host_syncs"]


def test_overrun_tokens_accounted(nano_model):
    """Uneven budgets in a pure-decode stretch guarantee some row
    finishes while a chained block is in flight: its masked run-ahead
    iterations must be visible as pipeline_overrun_tokens (and the
    effective depth must exceed 1 — run-ahead actually happened)."""
    cfg, params = nano_model
    prompts = _prompts(2, cfg, seed=19)
    _, eng = _run(params, cfg, prompts, [3, 17], 2,
                  eng_kw={"decode_horizon": 2})
    s = eng.stats()
    assert s["pipeline_overrun_tokens"] > 0
    assert s["pipeline_depth_effective"] > 1.0


# ---------------------------------------------------------------------------
# Gates: non-blocking dispatch, knob validation, scheduler hint
# ---------------------------------------------------------------------------

def test_nonblocking_dispatch_gate(nano_model, monkeypatch):
    """THE pipelining gate: in a pure-decode stretch at depth >= 2, the
    engine must issue its second fused dispatch BEFORE the first
    blocking `_device_get` pull — i.e. the host never waits on a token
    block while it could be feeding the device. A depth-1 engine on
    the same workload interleaves strictly get-after-dispatch, which
    the same log proves."""
    cfg, params = nano_model

    def drive(depth):
        events = []
        real_get = engine_mod._device_get
        real_multi = engine_mod._decode_multi_paged

        def logged_get(x):
            events.append("get")
            return real_get(x)

        def logged_multi(*a, **k):
            events.append("dispatch")
            return real_multi(*a, **k)

        monkeypatch.setattr(engine_mod, "_device_get", logged_get)
        monkeypatch.setattr(engine_mod, "_decode_multi_paged", logged_multi)
        try:
            eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                               pipeline_depth=depth, decode_horizon=4)
            for p in _prompts(2, cfg, seed=23):
                eng.submit(p, 12)
            eng.run()
        finally:
            monkeypatch.setattr(engine_mod, "_device_get", real_get)
            monkeypatch.setattr(engine_mod, "_decode_multi_paged",
                                real_multi)
        return events

    piped = drive(2)
    # Find the first decode dispatch; at depth 2 the SECOND dispatch
    # must come before ANY get that follows the first dispatch.
    first = piped.index("dispatch")
    tail = piped[first + 1:]
    assert "dispatch" in tail
    assert tail.index("dispatch") < tail.index("get"), (
        "engine blocked on a host sync before dispatching the queued "
        f"step: {piped}")

    sync = drive(1)
    first = sync.index("dispatch")
    tail = sync[first + 1:]
    assert tail.index("get") < tail.index("dispatch"), (
        "depth-1 engine should be strictly synchronous")


def test_pipeline_depth_validation(nano_model):
    cfg, params = nano_model
    with pytest.raises(ValueError, match="pipeline_depth"):
        DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                     pipeline_depth=0)


def test_admissions_pending_hint():
    """The scheduler-side flush hint: non-empty queue -> True on every
    built-in policy (including the deferring prefix policy — a
    deferred request is admissible next round, so run-ahead must not
    start)."""

    class _R:
        def __init__(self, i):
            self.req_id = i
            self.priority = 0
            self.seq = i
            self.prompt = [1, 2, 3]

    for pol in (FIFOPolicy(), PriorityPolicy(), PrefixAffinityPolicy()):
        assert pol.admissions_pending() is False
        pol.push(_R(0))
        assert pol.admissions_pending() is True
        pol.pop()
        assert pol.admissions_pending() is False


@pytest.mark.parametrize("waves", [1, 2], ids=["queue_empty",
                                               "queue_waiting"])
def test_run_ahead_leaves_fewer_dispatch_gaps(nano_model, monkeypatch,
                                              waves):
    """A dispatch GAP is a blocking pull issued while nothing else is
    in flight: the device has nothing queued behind the block the host
    is waiting for, so it idles through the host's replay. The
    synchronous engine pays one per decode block; at depth 2 only the
    flushes and the end of the stream do, and the mean ring depth at a
    drain says the same (exactly 1 against more than 1). With a second
    wave of requests waiting behind the two full slots the count falls
    the same way: only the block in which the first wave's budgets end
    is pulled with nothing behind it, and nothing is flushed."""
    cfg, params = nano_model

    def drive(depth):
        inflight = gaps = 0
        real_get = engine_mod._device_get
        real_multi = engine_mod._decode_multi_paged

        def counted_get(x):
            nonlocal inflight, gaps
            gaps += inflight == 1
            inflight -= 1
            return real_get(x)

        def counted_multi(*a, **k):
            nonlocal inflight
            inflight += 1
            return real_multi(*a, **k)

        monkeypatch.setattr(engine_mod, "_device_get", counted_get)
        monkeypatch.setattr(engine_mod, "_decode_multi_paged",
                            counted_multi)
        try:
            eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                               pipeline_depth=depth, decode_horizon=4)
            for p in _prompts(2 * waves, cfg, seed=23):
                eng.submit(p, 24)
            eng.run()
        finally:
            monkeypatch.setattr(engine_mod, "_device_get", real_get)
            monkeypatch.setattr(engine_mod, "_decode_multi_paged",
                                real_multi)
        assert inflight == 0
        return gaps, eng.stats()

    gaps1, s1 = drive(1)
    gaps2, s2 = drive(2)
    assert s1["decode_dispatches"] == s2["decode_dispatches"] == 6 * waves
    assert gaps1 == s1["decode_dispatches"]       # one per block
    assert gaps2 == waves                         # a wave's last block
    assert s1["pipeline_depth_effective"] == 1.0
    assert 1.5 < s2["pipeline_depth_effective"] <= 2.0
    assert s2["pipeline_flushes"] == 0.0
    # every block but a wave's last ran ahead; the first wave's with the
    # second waiting in the queue
    assert s2["decode_dispatches_chained"] == 5 * waves
    assert s2["decode_dispatches_chained_queued"] == 5 * (waves - 1)
    assert s1["decode_dispatches_chained"] == 0.0


# ---------------------------------------------------------------------------
# Stats plane
# ---------------------------------------------------------------------------

def test_fresh_engine_pipeline_stats_are_zero(nano_model):
    """Fresh engine: every pipeline ratio/counter reads 0.0 — never
    NaN (the _ratio guard) — and the knob itself is reported."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       pipeline_depth=4)
    s = eng.stats()
    assert s["pipeline_depth"] == 4.0
    assert s["pipeline_depth_effective"] == 0.0
    assert s["pipeline_flushes"] == 0.0
    assert s["pipeline_overrun_tokens"] == 0.0
    assert s["host_lag_steps"] == 0.0
    assert s["decode_dispatches_chained"] == 0.0
    assert s["decode_dispatches_chained_queued"] == 0.0


def test_pipeline_plane_reaches_metrics_registry(nano_model):
    """The pipeline counters flow through util.metrics like every
    other engine series: flushes/overrun counters and the host-lag
    gauge appear in the process-local registry tagged with this
    engine's id, matching stats()."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       pipeline_depth=2, decode_horizon=2,
                       engine_id="pipeline-metrics-test")
    prompts = _prompts(3, cfg, seed=29)
    # Uneven budgets in a pure-decode stretch -> a row finishes while a
    # chained block is in flight (overrun > 0); a submit mid-stretch ->
    # a forced flush (flushes > 0). Both counters must land non-zero so
    # their registry rows exist and match stats().
    eng.submit(prompts[0], 3)
    eng.submit(prompts[1], 17)
    eng.step()
    eng.step()
    eng.submit(prompts[2], 5)        # pending admission -> flush
    eng.run()
    s = eng.stats()
    assert s["pipeline_flushes"] > 0
    assert s["pipeline_overrun_tokens"] > 0

    from ray_tpu._private import metrics as _impl

    rows = [r for r in _impl.snapshots()
            if r["tags"].get("engine") == "pipeline-metrics-test"]
    by_name = {r["name"]: r for r in rows}
    assert by_name["llm_engine_pipeline_flushes_total"]["value"] == \
        s["pipeline_flushes"]
    assert by_name["llm_engine_pipeline_overrun_tokens_total"][
        "value"] == s["pipeline_overrun_tokens"]
    assert by_name["llm_engine_host_lag_steps"]["value"] == \
        s["host_lag_steps"] == 0.0
    assert by_name["llm_engine_host_syncs_total"]["value"] == \
        s["host_syncs"]
    # Pipelining must not break the PR-3 invariant: one transfer per
    # drained horizon, dispatches == syncs once drained.
    assert s["decode_dispatches"] == s["host_syncs"]


# ---------------------------------------------------------------------------
# A mid-prompt row's next chunk goes out before the host waits for the
# step's decode block
# ---------------------------------------------------------------------------

def _chunky(params, cfg, depth=2, **kw):
    return DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                        pipeline_depth=depth, prefill_chunk=3, **kw)


def test_chunks_ahead_change_no_token(nano_model):
    """A decoding row beside a row of seven chunks: the chunks after the
    first are dispatched ahead of the block the host waits for, and every
    request's tokens are what an engine that prefills whole prompts
    returns."""
    cfg, params = nano_model
    prompts = _prompts(4, cfg, seed=11, lo=4, hi=8) \
        + _prompts(2, cfg, seed=12, lo=19, hi=22)
    budgets = [12, 9, 14, 6, 7, 10]
    whole, _ = _run(params, cfg, prompts, budgets, 2)
    eng = _chunky(params, cfg)
    ids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    out = eng.run()
    assert [out[r] for r in ids] == whole
    s = eng.stats()
    assert 0 < s["prefill_dispatches_ahead"] < s["prefill_dispatches"]
    assert not eng._chunk_ahead or not eng._row_prefill


def test_a_row_takes_one_chunk_a_step_ahead_or_not(nano_model):
    """The cadence is the synchronous engine's: a mid-prompt row's
    frontier moves one chunk a `step()`, whether the chunk went out at the
    step's start or at the end of the step before."""
    cfg, params = nano_model
    eng = _chunky(params, cfg, decode_horizon=1)
    eng.submit(_prompts(1, cfg, seed=3, lo=5, hi=6)[0], 30)
    for _ in range(4):
        eng.step()                   # one row decoding, nothing mid-prompt
    assert not eng._row_prefill and eng.tokens_out
    eng.submit(_prompts(1, cfg, seed=4, lo=20, hi=21)[0], 4)
    seen = []
    while True:
        eng.step()
        if not eng._row_prefill:
            break
        seen.append(next(iter(eng._row_prefill.values())).pos)
    # the first step's own chunk and the one it sent ahead: 6; then one
    # chunk a step up to the last, which leaves the row decodable
    assert seen == [6, 9, 12, 15, 18]
    assert eng.stats()["prefill_dispatches_ahead"] == 6
    assert eng.stats()["prefill_dispatches"] == 2 + 7


def test_no_chunk_goes_ahead_where_nothing_is_waited_for(nano_model):
    """A lone row mid-prompt has no decode block to wait for: its steps
    return at once and nothing is sent ahead."""
    cfg, params = nano_model
    eng = _chunky(params, cfg)
    rid = eng.submit(_prompts(1, cfg, seed=5, lo=20, hi=21)[0], 3)
    out = eng.run()
    assert len(out[rid]) == 3
    assert eng.stats()["prefill_dispatches_ahead"] == 0
    assert eng.stats()["prefill_dispatches"] == 7


@pytest.mark.parametrize("depth", [1, 2])
def test_a_newcomer_beside_a_chunk_sent_ahead_is_a_group_of_its_own(
        nano_model, depth):
    """Three slots: a row decodes, a long prompt is mid-way (its next
    chunk already out), and a newcomer is admitted: its first chunk is a
    second program of that step, the long row is not advanced twice, and
    all three streams are the whole-prompt engine's."""
    cfg, params = nano_model
    p = [_prompts(1, cfg, seed=21, lo=5, hi=6)[0],
         _prompts(1, cfg, seed=22, lo=20, hi=21)[0],
         _prompts(1, cfg, seed=23, lo=7, hi=8)[0]]
    ref = DecodeEngine(params, cfg, batch_slots=3, max_len=64,
                       pipeline_depth=depth, decode_horizon=1)
    want = []
    for q, n in zip(p, (25, 5, 6)):
        r = ref.submit(q, n)
        want.append(ref.run()[r])
    eng = DecodeEngine(params, cfg, batch_slots=3, max_len=64,
                       pipeline_depth=depth, prefill_chunk=3,
                       decode_horizon=1)
    a = eng.submit(p[0], 25)
    eng.step()
    eng.step()
    eng.step()
    b = eng.submit(p[1], 5)
    eng.step()
    eng.step()
    before = eng.stats()["prefill_dispatches"]
    pos = next(iter(eng._row_prefill.values())).pos
    c = eng.submit(p[2], 6)
    eng.step()
    # the newcomer's chunk + the long row's next one sent ahead, and the
    # newcomer's second sent ahead with it (one group: one bucket)
    assert eng.stats()["prefill_dispatches"] == before + 2
    assert sorted(st.pos for st in eng._row_prefill.values()) == [
        6, pos + 3]
    out = eng.run()
    assert [out[a], out[b], out[c]] == want


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("mpps", [1, 2])
def test_chunks_ahead_keep_the_schedule(nano_model, monkeypatch, depth,
                                        mpps):
    """Call for call, what `step()` returns is what an engine that sends
    no chunk ahead returns: the same rows admitted in the same steps (a
    row that left its prompt in a chunk sent ahead still counts against
    that step's `max_prefills_per_step`), the same tokens."""
    cfg, params = nano_model
    prompts = _prompts(10, cfg, seed=31, lo=4, hi=21)
    budgets = [5, 9, 3, 12, 7, 4, 10, 6, 8, 5]

    def calls(ahead):
        eng = DecodeEngine(params, cfg, batch_slots=4, max_len=64,
                           pipeline_depth=depth, prefill_chunk=3,
                           max_prefills_per_step=mpps)
        if not ahead:
            plain = eng._advance_prefills
            monkeypatch.setattr(
                eng, "_advance_prefills",
                lambda ahead=False: None if ahead else plain())
        for p, n in zip(prompts, budgets):
            eng.submit(p, n)
        out = []
        while eng.pending():
            out.append(eng.step())
        return out, eng.stats()["prefill_dispatches_ahead"]

    want, none = calls(False)
    got, some = calls(True)
    assert none == 0 and some > 0
    assert got == want

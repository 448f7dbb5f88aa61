"""The engine's own names in a profiler trace and its per-phase counters
(PR 24): engine-lane spans on the profiler's clock with no knob, inert and
allocation-free with no session; scopes and kernel names in the compiled
programs; `queue_wait + prefill + first_block == ttft` per request;
`device_wait_s`; the repaired `tpot_s`; the step clocks (PR 54): a step's
wall time by seam, the seconds the device had nothing to run by cause, a
first token's path, a stalled step, a block as one weighted observation.
"""

import functools
import glob
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, llama_init  # noqa: E402
from ray_tpu.models import engine as engine_mod  # noqa: E402
from ray_tpu.models import engine_trace  # noqa: E402
from ray_tpu.models.engine import DecodeEngine  # noqa: E402
from ray_tpu.ops import scope_names as sn  # noqa: E402


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    return cfg, llama_init(jax.random.PRNGKey(0), cfg)


def _pool_bytes(cfg, blocks, T=4):
    from ray_tpu.models.prefix_cache import block_bytes
    return blocks * block_bytes(cfg.n_layers, T, cfg.n_kv_heads,
                                cfg.head_dim,
                                jnp.dtype(cfg.dtype).itemsize)


# -- engine lanes in a jax.profiler trace ------------------------------------

def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                lines.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events
                              if e.name.startswith("eng.")])
    return [ln for ln in lines if ln]


@pytest.mark.parametrize("trace", [None, True], ids=["ring_off", "ring_on"])
def test_engine_lanes_appear_in_a_profiler_session(nano_model, tmp_path,
                                                   trace):
    """No knob: whatever `trace=` says, a running `jax.profiler` session
    gets `eng.device_wait` and `eng.emit` inside `eng.host_drain`, and the
    other seams, on the host plane."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32, trace=trace)
    eng.submit([5, 6, 7], 6)
    eng.run()                                   # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i in range(3):
            eng.submit([5, 6, 7 + i], 6)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (events,) = _host_events(str(tmp_path))     # one thread drove it
    names = {n for n, _, _ in events}
    assert {"eng.admit", "eng.advance_prefills", "eng.prefill_dispatch",
            "eng.dispatch", "eng.host_drain", "eng.device_wait",
            "eng.emit"} <= names
    drains = [(s, e) for n, s, e in events if n == "eng.host_drain"]
    for child in ("eng.device_wait", "eng.emit"):
        kids = [(s, e) for n, s, e in events if n == child]
        assert len(kids) == len(drains) > 0
        for s, e in kids:
            assert any(ds <= s and e <= de for ds, de in drains), child
    assert (len(eng.trace) > 0) == bool(trace)


def test_no_session_the_null_tracer_hands_out_one_inert_span(nano_model):
    """With no session the helper allocates nothing: the null tracer
    returns the same shared no-op object every time (the tracemalloc
    gate in test_perf_gates.py runs the engine over it)."""
    span = engine_trace.NULL_TRACER.lane("dispatch", "dispatch", horizon=8)
    assert span is engine_trace.NULL_TRACER.lane("emit", "drain")
    with span as s:
        s.note(bytes=1)
    assert len(engine_trace.NULL_TRACER) == 0


def test_ring_keeps_host_drain_as_parent_of_wait_and_emit(nano_model):
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32, trace=True)
    eng.submit([5, 6, 7], 6)
    eng.run()
    lanes = {}
    for name, rid, lane, t0, dur, args in eng.trace.events():
        if rid is None:
            lanes.setdefault(name, []).append((lane, t0, t0 + dur, args))
    assert {"admit", "advance_prefills", "prefill_dispatch", "dispatch",
            "host_drain", "device_wait", "emit"} <= set(lanes)
    assert {ln for ln, *_ in lanes["device_wait"] + lanes["emit"]
            + lanes["host_drain"]} == {"drain"}
    for (_, s, e, args), (_, ws, we, _), (_, es, ee, _) in zip(
            lanes["host_drain"], lanes["device_wait"], lanes["emit"]):
        assert s <= ws <= we <= es <= ee <= e
        assert set(args) == {"horizon", "depth", "bytes"}   # as before


# -- counters ----------------------------------------------------------------

def test_device_wait_counts_the_seconds_inside_device_get(
        nano_model, fake_clock, monkeypatch):
    cfg, params = nano_model
    real = engine_mod._device_get

    def slow_get(x):
        fake_clock.advance(0.25)
        return real(x)

    monkeypatch.setattr(engine_mod, "_device_get", slow_get)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       clock=fake_clock)
    eng.submit([5, 6, 7], 9)
    eng.run()
    s = eng.stats()
    assert s["device_waits"] == s["host_syncs"] >= 2
    assert s["device_wait_s"] == pytest.approx(0.25 * s["device_waits"])


def test_compile_counters_hold_still_when_warm_and_rise_with_a_new_bucket(
        nano_model):
    """`stats()` carries the PROCESS's compile ledger: nothing moves over
    twenty steady decode steps, and a prompt bucket met for the first time
    raises `compiles_total` by exactly the programs it built."""
    from ray_tpu.util.compile_cache import ledger

    cfg, params = nano_model
    keys = ("compiles_total", "compile_cache_misses_total",
            "compile_s_total")
    # a shape no other engine of this module has, so its programs are new
    eng = DecodeEngine(params, cfg, batch_slots=3, max_len=96,
                       decode_horizon=2)
    assert set(keys) <= set(eng.stats())
    eng.submit([5, 6, 7], 60)
    eng.run()                                   # warm-up
    eng.submit([5, 6, 8], 60)
    eng.step()                                  # admitted and prefilled
    warm = [eng.stats()[k] for k in keys]
    assert warm[0] >= 2 and warm[2] > 0         # prefill + decode at least
    for _ in range(20):
        assert eng.pending()
        eng.step()
        assert [eng.stats()[k] for k in keys] == warm
    eng.run()
    # records ever written, not a position in the ring: a worker that ran
    # other modules first has filled its 4,096 and `events()[n:]` is empty
    n = len(ledger()) + ledger().events_dropped
    eng.submit(list(range(1, 20)), 4)           # bucket 32: never met
    eng.run()
    new = len(ledger()) + ledger().events_dropped - n
    built = [e for e in ledger().events()[-new:] if e[4] is not None]
    assert "_prefill_rows_paged" in {e[0] for e in built}
    after = [eng.stats()[k] for k in keys]
    assert after[0] - warm[0] == len(built) >= 1
    assert after[1] - warm[1] == len(built)     # no persistent cache here
    assert after[2] - warm[2] == pytest.approx(sum(e[3] for e in built))


@pytest.mark.parametrize("chunk", [None, 4], ids=["unchunked", "chunked"])
@pytest.mark.parametrize("preempt", ["swap", "recompute"])
def test_ttft_is_the_sum_of_its_three_parts(nano_model, fake_clock, chunk,
                                            preempt, monkeypatch):
    """Per request, on the engine's clock: queue wait + prefill + first
    block == TTFT exactly, with a pool so small that rows are preempted."""
    cfg, params = nano_model
    real = engine_mod._device_get

    def slow_get(x):                    # the device takes its time
        fake_clock.advance(0.02)
        return real(x)

    monkeypatch.setattr(engine_mod, "_device_get", slow_get)
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=32, kv_block_tokens=4, prefill_chunk=chunk,
                       preempt=preempt,
                       kv_pool_bytes=_pool_bytes(cfg, 10),
                       prefix_cache=False, clock=fake_clock)
    m = eng.metrics
    per_request = []
    closing = m._on_first_token

    def spy(rt, now):
        closing(rt, now)
        per_request.append((rt.admit_t - rt.submit_t, m.prefill_s._ring[-1],
                            m.first_block_s._ring[-1], m.ttft_s._ring[-1]))

    m._on_first_token = spy
    prompts = [[7, 8, 9, 10, 11, 12, 13], [3, 1, 4, 1, 5], [2, 7, 1, 8, 2],
               [9, 9, 8, 8, 7, 7], [1, 2, 3], [4, 5, 6, 7, 8, 9]]
    for p in prompts:
        eng.submit(p, 12)
        fake_clock.advance(0.013)
    while eng.pending():
        fake_clock.advance(0.1)
        eng.step()
    s = eng.stats()
    assert s["preemptions"] >= 1
    assert len(per_request) == len(prompts) == s["ttft_s_count"] \
        == s["prefill_s_count"] == s["first_block_s_count"] \
        == s["queue_wait_s_count"]
    for q, p, f, t in per_request:
        assert q + p + f == pytest.approx(t, abs=1e-12)
        assert q >= 0 and p >= 0 and f >= 0
    assert sum(q for q, *_ in per_request) == pytest.approx(
        s["queue_wait_s_mean"] * s["queue_wait_s_count"])
    if chunk:                       # a prompt of two chunks spans steps
        assert max(p for _, p, _, _ in per_request) > 0
    assert min(f for _, _, f, _ in per_request) > 0


def test_tpot_median_is_the_cadence_not_zero_at_horizon_8(nano_model,
                                                          fake_clock):
    """A fused block of n tokens records gap/n for each: same count
    (tokens - 1) and sum as before, a p50 above 0."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       decode_horizon=8, clock=fake_clock)
    eng.submit([5, 6, 7], 33)
    t_first = t_last = None
    while eng.pending():
        fake_clock.advance(0.08)
        if eng.step():
            t_first = fake_clock() if t_first is None else t_first
            t_last = fake_clock()
    s = eng.stats()
    assert s["tpot_s_count"] == 32
    assert s["tpot_s_mean"] * 32 == pytest.approx(t_last - t_first)
    assert s["tpot_s_p50"] > 0
    assert s["tpot_s_p50"] == pytest.approx(0.08 / 8)


# -- step clocks (PR 54) -----------------------------------------------------

SEAMS = ("step_flush_s_total", "step_admit_s_total",
         "step_prefill_dispatch_s_total", "step_dispatch_s_total",
         "step_emit_s_total", "device_wait_s", "step_other_s_total")
CAUSES = ("retire", "admit", "chunk", "other")
ENGINE_OWN = SEAMS + (
    "step_s_total", "steps_stalled_total", "step_stalled_s_total",
    "step_stalled_device_wait_s_total", "device_starved_s_total",
    "device_starved_dispatches_total") + tuple(
        f"device_starved_{c}_s_total" for c in CAUSES)


class TickClock:
    """A clock on which every reading takes a millisecond: each seam of a
    step has a length without a real sleep."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.001
        return self.t

    def advance(self, dt):
        self.t += dt


def _slow_device(monkeypatch, clock, seconds=0.02):
    real = engine_mod._device_get

    def slow_get(x):
        clock.advance(seconds)
        return real(x)

    monkeypatch.setattr(engine_mod, "_device_get", slow_get)


def _starved(s):
    return {c: s[f"device_starved_{c}_s_total"] for c in CAUSES}


@pytest.mark.parametrize("metrics", [True, False], ids=["on", "off"])
def test_step_seams_sum_to_the_step(nano_model, monkeypatch, metrics):
    """Admissions, chunked prompts, run-ahead and a flush: the six seams,
    `device_wait_s` and the remainder are `step_s_total`, and the
    engine's own keys are there with the metrics plane off."""
    cfg, params = nano_model
    clock = TickClock()
    _slow_device(monkeypatch, clock)
    eng = DecodeEngine(params, cfg, batch_slots=3, max_len=64,
                       kv_block_tokens=4, prefill_chunk=4, decode_horizon=2,
                       prefix_cache=False, clock=clock,
                       enable_metrics=metrics)
    eng.submit(list(range(1, 11)), 24)          # three chunks
    outside = 0.0
    n = 0
    while eng.pending():
        if n == 8:                              # a free slot, blocks in
            eng.submit([3, 1, 4, 1, 5, 9, 2], 6)    # flight: a flush
        t = clock.t
        eng.step()
        outside += clock.t - t
        n += 1
    s = eng.stats()
    assert set(ENGINE_OWN) <= set(s)
    assert s["pipeline_flushes"] >= 1 and s["prefill_dispatches_ahead"] >= 1
    assert s["decode_dispatches_chained"] >= 1
    for k in SEAMS:
        assert s[k] > 0, k
    assert sum(s[k] for k in SEAMS) == pytest.approx(s["step_s_total"],
                                                     rel=1e-12)
    # the step's own two readings are a tick each: one falls inside
    assert outside - s["step_s_total"] == pytest.approx(0.001 * n)
    assert s["device_wait_s"] == pytest.approx(
        0.021 * s["device_waits"])
    assert sum(_starved(s).values()) == pytest.approx(
        s["device_starved_s_total"])
    assert s["steps_stalled_total"] == 0
    assert ("first_dispatch_s_count" in s) == metrics


def test_starved_seconds_after_a_retirement_with_a_newcomer(
        nano_model, fake_clock, monkeypatch):
    """The ring stops short of the block in which a budget ends while a
    request waits: from that block's pull to the newcomer's prefill the
    device has nothing, and the seconds go under `retire`; an engine run
    dry starves nobody however long it stands."""
    cfg, params = nano_model
    _slow_device(monkeypatch, fake_clock)
    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       decode_horizon=2, prefix_cache=False,
                       clock=fake_clock)
    eng.submit([5, 6, 7], 4)
    eng.submit([5, 6, 8], 4)
    while eng.pending():
        eng.step()
        fake_clock.advance(0.1)
    s = eng.stats()
    assert s["decode_dispatches_chained_queued"] >= 1
    assert _starved(s) == {"retire": pytest.approx(0.1), "admit": 0.0,
                           "chunk": 0.0, "other": 0.0}
    assert s["device_starved_dispatches_total"] == 1
    fake_clock.advance(30.0)                    # empty: nothing accrues
    eng.submit([5, 6, 9], 2)
    eng.run()
    assert eng.stats()["device_starved_s_total"] == pytest.approx(0.1)


def test_starved_seconds_after_an_arrival_into_a_free_slot(
        nano_model, fake_clock, monkeypatch):
    cfg, params = nano_model
    _slow_device(monkeypatch, fake_clock)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       decode_horizon=2, prefix_cache=False,
                       clock=fake_clock, trace=True)
    binding = eng._admit_rows_paged

    def slow_binding(admissions):               # between pull and prefill
        fake_clock.advance(0.05)
        binding(admissions)

    eng._admit_rows_paged = slow_binding
    eng.submit([5, 6, 7], 40)
    for _ in range(4):
        eng.step()
    assert len(eng._ring) == 1 and eng.stats()["device_starved_s_total"] == 0
    eng.submit([5, 6, 8], 4)                    # the flush, then the gate
    eng.step()
    s = eng.stats()
    assert s["pipeline_flushes"] == 1
    assert _starved(s) == {"retire": 0.0, "admit": pytest.approx(0.05),
                           "chunk": 0.0, "other": 0.0}
    # the span that ended the gap says what it followed
    after = [args["after"] for name, _, _, _, _, args in eng.trace.events()
             if name in ("dispatch", "prefill_dispatch")]
    assert after.count("admit") == 1 and set(after) == {"", "admit"}


def test_a_chunk_sent_ahead_is_in_flight_and_one_held_back_starves(
        nano_model, fake_clock, monkeypatch):
    cfg, params = nano_model
    _slow_device(monkeypatch, fake_clock)

    def drive(hold_back):
        eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                           kv_block_tokens=4, prefill_chunk=4,
                           decode_horizon=2, prefix_cache=False,
                           clock=fake_clock)
        chunks = eng._advance_prefills

        def slow_chunks(ahead=False):
            if not ahead:                       # the host takes its time
                fake_clock.advance(0.1)         # before the step's chunk
            if not (ahead and hold_back):
                chunks(ahead)

        eng._advance_prefills = slow_chunks
        eng.submit([5, 6, 7], 40)
        eng.step()
        eng.submit(list(range(1, 30)), 2)       # eight chunks beside a row
        for _ in range(4):
            eng.step()
        assert eng._row_prefill                 # still mid-prompt
        return eng.stats()

    s = drive(hold_back=False)
    assert s["prefill_dispatches_ahead"] >= 3
    # the newcomer's first chunk closes the only gap: the flush's
    assert _starved(s) == {"retire": 0.0, "admit": pytest.approx(0.1),
                           "chunk": 0.0, "other": 0.0}
    assert s["device_starved_dispatches_total"] == 1
    s = drive(hold_back=True)
    assert s["prefill_dispatches_ahead"] == 0
    assert _starved(s) == {"retire": 0.0, "admit": pytest.approx(0.1),
                           "chunk": pytest.approx(0.3), "other": 0.0}


def test_a_ring_of_one_block_starves_under_other(nano_model, fake_clock,
                                                 monkeypatch):
    cfg, params = nano_model
    _slow_device(monkeypatch, fake_clock)
    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       decode_horizon=2, pipeline_depth=1,
                       prefix_cache=False, clock=fake_clock)
    eng.submit([5, 6, 7], 8)
    while eng.pending():
        eng.step()
        fake_clock.advance(0.1)
    s = eng.stats()
    assert s["decode_dispatches"] == 4
    assert _starved(s) == {"retire": 0.0, "admit": 0.0, "chunk": 0.0,
                           "other": pytest.approx(0.3)}


@pytest.mark.parametrize("chunk", [None, 4], ids=["unchunked", "chunked"])
def test_first_block_is_the_sum_of_its_two_parts(nano_model, fake_clock,
                                                 chunk, monkeypatch):
    """Per request: decodable -> dispatch of the block that carries the
    first token -> the token on the host; rows are preempted on the way."""
    cfg, params = nano_model
    _slow_device(monkeypatch, fake_clock)
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=32,
                       kv_block_tokens=4, prefill_chunk=chunk,
                       preempt="swap", kv_pool_bytes=_pool_bytes(cfg, 10),
                       prefix_cache=False, clock=fake_clock)
    m = eng.metrics
    per_request = []
    closing = m._on_first_token

    def spy(rt, now):
        closing(rt, now)
        per_request.append((rt.admit_t - rt.submit_t,) + tuple(
            a._ring[-1] for a in (
                m.first_dispatch_s, m.first_return_s, m.first_block_s,
                m.prefill_s, m.ttft_s, m.first_blocks_ahead,
                m.first_block_horizon)))

    m._on_first_token = spy
    prompts = [[7, 8, 9, 10, 11, 12, 13], [3, 1, 4, 1, 5], [2, 7, 1, 8, 2],
               [9, 9, 8, 8, 7, 7], [1, 2, 3], [4, 5, 6, 7, 8, 9]]
    for p in prompts:
        eng.submit(p, 12)
        fake_clock.advance(0.013)
    while eng.pending():
        fake_clock.advance(0.1)
        eng.step()
    s = eng.stats()
    assert s["preemptions"] >= 1
    assert len(per_request) == len(prompts) == s["first_dispatch_s_count"] \
        == s["first_return_s_count"] == s["first_blocks_ahead_count"] \
        == s["first_block_horizon_count"]
    for q, d, r, f, p, t, ahead, horizon in per_request:
        assert d + r == pytest.approx(f, abs=1e-12)
        assert q + p + d + r == pytest.approx(t, abs=1e-12)
        assert d >= 0 and r >= 0.02             # the pull alone is 20 ms
        assert 0 <= ahead < eng.pipeline_depth and horizon >= 1
    for key in ("first_dispatch_s", "first_return_s"):
        assert {f"{key}_{f}" for f in ("count", "mean", "max", "p50", "p95",
                                       "p99")} <= set(s)
    assert s["first_dispatch_s_mean"] + s["first_return_s_mean"] \
        == pytest.approx(s["first_block_s_mean"])


def test_a_stalled_step_says_where_it_stood(nano_model, fake_clock,
                                            monkeypatch):
    cfg, params = nano_model
    real = engine_mod._device_get
    waits = iter([0.02, 0.02, 0.6] + [0.02] * 100)

    def get(x):
        fake_clock.advance(next(waits))
        return real(x)

    monkeypatch.setattr(engine_mod, "_device_get", get)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       decode_horizon=2, clock=fake_clock,
                       enable_metrics=False)
    assert engine_mod.STALL_STEP_S == 0.5
    eng.submit([5, 6, 7], 20)
    for _ in range(5):
        eng.step()
    s = eng.stats()
    assert s["steps_stalled_total"] == 1
    assert s["step_stalled_s_total"] == pytest.approx(0.6)
    assert s["step_stalled_device_wait_s_total"] == pytest.approx(0.6)
    binding = eng._admit_rows_paged

    def descheduled(admissions):                # the host, not the device
        fake_clock.advance(0.5)
        binding(admissions)

    eng._admit_rows_paged = descheduled
    eng.submit([5, 6, 8], 4)
    eng.step()
    s = eng.stats()
    assert s["steps_stalled_total"] == 2
    # that step: the flush's one pull, the gate, and no drain after it
    assert s["step_stalled_s_total"] == pytest.approx(0.6 + 0.52)
    assert s["step_stalled_device_wait_s_total"] == pytest.approx(0.62)
    eng.run()
    assert eng.stats()["steps_stalled_total"] == 2


@pytest.mark.parametrize("n", [1, 2, 8])
def test_a_block_is_one_weighted_observation(n, fake_clock):
    """`add(v, n)` and `observe(v, n=n)` are `n` single calls in every
    field: count, sum, max, percentiles, bucket counts."""
    from ray_tpu.models.engine_metrics import (LATENCY_BOUNDARIES_S,
                                               EngineMetrics, _Agg)
    from ray_tpu.util import metrics as um

    values = [0.0004, 0.03, 0.0125, 0.3, 0.0125, 40.0, 0.002]
    one, many = _Agg(), _Agg()
    h_one = um.Histogram("test_block_one", "", LATENCY_BOUNDARIES_S)
    h_many = um.Histogram("test_block_many", "", LATENCY_BOUNDARIES_S)
    for v in values:
        one.add(v, n)
        h_one.observe(v, n=n)
        for _ in range(n):
            many.add(v)
            h_many.observe(v)
    a, b = {}, {}
    one.fields("x", a)
    many.fields("x", b)
    assert a == pytest.approx(b, rel=1e-12) and a["x_count"] == 7 * n
    rows = {r["name"]: r for r in um.snapshots()
            if r["name"].startswith("test_block_")}
    for field in ("bucket_counts", "count"):
        assert rows["test_block_one"][field] \
            == rows["test_block_many"][field]
    assert rows["test_block_one"]["sum"] == pytest.approx(
        rows["test_block_many"]["sum"])
    # and through the engine's hook: a block of n tokens after the first
    m = EngineMetrics(engine_id=f"block-{n}", clock=fake_clock)
    m.on_submit(0)
    m.on_admit(0)
    m.on_tokens(0, 1)
    fake_clock.advance(0.08)
    m.on_tokens(0, n)
    s = m.stats()
    assert s["tpot_s_count"] == n and s["tpot_s_max"] == 0.08 / n
    assert s["tpot_s_mean"] * n == pytest.approx(0.08)
    assert s["tpot_s_p50"] == 0.08 / n
    (row,) = [r for r in um.snapshots() if r["name"] == "llm_engine_tpot_s"
              and r["tags"] == {"engine": f"block-{n}"}]
    assert row["count"] == n and sum(row["bucket_counts"]) == n
    assert row["sum"] == pytest.approx(0.08)


def test_a_kept_series_is_resolved_again_after_a_registry_reset():
    from ray_tpu.util import metrics as um

    c = um.Counter("test_kept_series", "", tag_keys=("engine",)) \
        .set_default_tags({"engine": "a"})
    c.inc()
    c.inc(2)
    c.inc(tags={"engine": "b"})
    um.reset_registry()
    c.inc(5)
    rows = [r for r in um.snapshots() if r["name"] == "test_kept_series"]
    assert [(r["tags"], r["value"]) for r in rows] == [({"engine": "a"}, 5)]


# -- scopes and kernel names in the compiled programs ------------------------

def _op_names(lowered):
    return set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))


def _scopes_in(names):
    """Scopes among the paths' components; the backward pass wraps a
    component in what made it: "transpose(jvp(mlp))" is `mlp`."""
    parts = {part for n in names for part in n.split("/")}
    parts |= {re.findall(r"\w+", p)[-1] for p in parts if p.endswith("))")
              or p.startswith("jvp(")}
    return parts & set(sn.SCOPES)


def test_decode_and_prefill_programs_carry_the_scopes(nano_model):
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32, kv_block_tokens=4)
    seen = {}

    def spy(name):
        fn = getattr(engine_mod, name)

        def wrapped(*a, **k):
            if name not in seen:
                seen[name] = _op_names(fn.lower(*a, **k))
            return fn(*a, **k)
        return wrapped

    mp = pytest.MonkeyPatch()
    try:
        for name in ("_decode_multi_paged", "_prefill_rows_paged"):
            mp.setattr(engine_mod, name, spy(name))
        eng.submit([5, 6, 7, 8, 9], 4)
        eng.run()
    finally:
        mp.undo()
    layer = {sn.NORM, sn.ATTN_QKV, sn.KV_WRITE, sn.ATTN_OUT, sn.MLP,
             sn.EMBED, sn.LM_HEAD}
    assert layer | {sn.SAMPLE, sn.PAGED_ATTENTION, sn.KV_GATHER} \
        <= _scopes_in(seen["_decode_multi_paged"])
    # prefill is the decode's layer core, a chunk wide: the same scopes,
    # and none of a dense cache row's
    assert layer | {sn.PAGED_ATTENTION, sn.KV_GATHER} \
        <= _scopes_in(seen["_prefill_rows_paged"])
    assert sn.CACHED_ATTENTION not in _scopes_in(
        seen["_prefill_rows_paged"])
    # the names of the jitted programs are what the readers match
    assert all(n.startswith("jit(_decode_multi_paged)/")
               for n in seen["_decode_multi_paged"] if "/" in n
               and n.startswith("jit("))


def test_train_step_carries_scopes_and_jax_marks_the_recompute():
    import optax

    from ray_tpu.models.llama import llama_loss, llama_param_specs
    from ray_tpu.models.training import make_sharded_train_step
    from ray_tpu.parallel.mesh import create_mesh

    cfg = LlamaConfig.nano(remat=True, remat_policy="full", loss_chunk=16,
                           max_seq_len=64)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    init_fn, step_fn = make_sharded_train_step(
        functools.partial(llama_loss, cfg=cfg), optax.adamw(1e-3),
        create_mesh({"fsdp": 1}, devices=jax.devices()[:1]),
        llama_param_specs(cfg))
    p, o = init_fn(params)
    names = _op_names(step_fn.lower(
        p, o, {"tokens": jnp.zeros((2, 65), jnp.int32)}))
    assert all(n.startswith("jit(step_fn)/") for n in names
               if n.startswith("jit("))
    assert {sn.EMBED, sn.NORM, sn.ATTN_QKV, sn.ATTENTION, sn.ATTN_OUT,
            sn.MLP, sn.LM_HEAD, sn.LOSS, sn.OPTIMIZER} <= _scopes_in(names)
    again = [n for n in names if "/rematted_computation/" in n]
    assert again and _scopes_in(again) >= {sn.MLP, sn.ATTN_QKV}
    # the backward pass wraps a scope in what made it
    assert any("transpose(jvp(norm))" in n for n in names)


@pytest.mark.parametrize("kernel", [sn.FLASH_FWD, sn.FLASH_BWD_DQ,
                                    sn.FLASH_BWD_DKV, sn.PAGED_KERNEL])
def test_every_pallas_kernel_has_its_name(kernel):
    """`pallas_call(name=)` puts the kernel's name into the lowered
    program (the scope of its call and the custom call's kernel name)."""
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.paged_attention_kernel import paged_attention_kernel

    if kernel == sn.PAGED_KERNEL:
        q = jnp.zeros((2, 1, 4, 16), jnp.float32)
        pool = jnp.zeros((2, 6, 4, 2 * 16), jnp.float32)
        text = jax.jit(functools.partial(
            paged_attention_kernel, layer=1, kv_valid_len=8,
            interpret=True)).lower(
                q, pool, pool, jnp.zeros((2, 2), jnp.int32),
                jnp.zeros((2, 1), jnp.int32)).as_text(debug_info=True)
    else:
        x = jnp.zeros((1, 2, 128, 16), jnp.float32)

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=64,
                                   block_k=64, interpret=True).sum()
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).as_text(debug_info=True)
    assert re.search(r'"[^"]*\b%s\b[^"]*"' % kernel, text), kernel

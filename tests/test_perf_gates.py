"""Committed perf gates — absolute floors under which a commit FAILS.

Round-4 verdict: "nothing in tests/ asserts absolute floors for
tasks/s, calls/s, put bandwidth, storm rate — one bad commit silently
erases round 4's headline wins." These gates commit the floors
(reference model: the nightly perf gates in
release/release_tests.yaml:1 over ray_perf.py microbenchmarks).

Floors vs judge-measured quiet-box medians (round 4 + round-5 storm
fix): tasks 8k/s vs 11.3k measured; sync actor calls 3k/s vs 4.45k;
put 4 GiB/s vs 6.3; actor storm 50/s vs ~123. Each gate takes the
median of 3 trials.

Ambient-load skip (same posture as the stress tier's budgets): a
loaded box cannot attest a floor, so each gate first waits briefly for
quiesce (not at all as an xdist worker, whose siblings are the load) and
SKIPS (visibly, with the load it saw) if the machine never settles — a
skip is "could not measure", never "passed".
"""

import os
import statistics
import time

import numpy as np
import pytest

import ray_tpu

LOAD_THRESHOLD = 2.5
QUIESCE_WAIT_S = 120.0


def _quiesce_or_skip():
    # An xdist worker's siblings ARE the load and run for as long as it
    # does: waiting buys nothing there, so it reads the load once.
    wait = 0.0 if "PYTEST_XDIST_WORKER" in os.environ else QUIESCE_WAIT_S
    deadline = time.monotonic() + wait
    while True:
        try:
            load = os.getloadavg()[0]
        except OSError:
            return
        if load < LOAD_THRESHOLD:
            return
        if time.monotonic() >= deadline:
            break
        time.sleep(5.0)
    pytest.skip(f"box never quiesced (1-min load {load:.1f} >= "
                f"{LOAD_THRESHOLD}); perf floors need a quiet box")


@pytest.fixture()
def gate_cluster():
    ctx = ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    yield ctx
    ray_tpu.shutdown()


def _median_rate(fn, units: float, trials: int = 3):
    rates = []
    for _ in range(trials):
        t0 = time.perf_counter()
        fn()
        rates.append(units / (time.perf_counter() - t0))
    return statistics.median(rates)


def _gate(measure, floor: float, what: str) -> None:
    """Assert a floor with ONE settle-and-retry: a previous test
    module's async teardown (dying workers) can depress the first
    measurement without registering on the 1-min loadavg the quiesce
    gate reads. A retry after settling is still a hard floor — two
    consecutive misses fail."""
    rate = measure()
    if rate < floor:
        time.sleep(20.0)
        _quiesce_or_skip()
        rate = measure()
    assert rate >= floor, f"{what} regressed: {rate:.1f} < {floor}"


def test_gate_task_throughput(gate_cluster):
    """Floor: >=8,000 tasks/s (judge-measured 11.3k quiet-box, r4)."""
    _quiesce_or_skip()

    @ray_tpu.remote
    def nop():
        return None

    ray_tpu.get([nop.remote() for _ in range(200)])  # warm workers
    n = 4_000
    _gate(lambda: _median_rate(
        lambda: ray_tpu.get([nop.remote() for _ in range(n)],
                            timeout=120), n),
        8_000, "task throughput (tasks/s)")


def test_gate_sync_actor_calls(gate_cluster):
    """Floor: >=3,000 sync actor calls/s (judge: 4.45k quiet-box)."""
    _quiesce_or_skip()

    @ray_tpu.remote
    class Echo:
        def m(self, x):
            return x

    a = Echo.remote()
    assert ray_tpu.get(a.m.remote(0), timeout=60) == 0  # creation done

    def run():
        for i in range(1_500):
            ray_tpu.get(a.m.remote(i))

    _gate(lambda: _median_rate(run, 1_500), 3_000,
          "sync actor calls (calls/s)")
    ray_tpu.kill(a)


def test_gate_put_bandwidth(gate_cluster):
    """Floor: >=4 GiB/s object-store put (judge: 6.3 GiB/s)."""
    _quiesce_or_skip()
    gib = 1024 ** 3
    arr = np.random.rand(gib // 8)  # 1 GiB

    # Hold exactly ONE ref: the default arena is 2 GiB, so each trial's
    # put releases the previous object to LRU eviction.
    holder = {}

    def run():
        holder["ref"] = ray_tpu.put(arr)

    _gate(lambda: _median_rate(run, 1.0), 4.0,
          "put bandwidth (GiB/s)")
    holder.clear()


def test_gate_actor_storm(gate_cluster):
    """Floor: >=50 actors/s creation storm — the round-3 done-line,
    crossed in round 5 (~123/s quiet-box after the fork-template
    runtime_env warm-up)."""
    _quiesce_or_skip()

    @ray_tpu.remote(num_cpus=0)
    class S:
        def m(self, x=None):
            return x

    time.sleep(6.0)  # prestart pool fill

    storm_n = 16

    def measure():
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            batch = [S.remote() for _ in range(storm_n)]
            ray_tpu.get([b.m.remote(1) for b in batch], timeout=120)
            rates.append(storm_n / (time.perf_counter() - t0))
            for b in batch:
                ray_tpu.kill(b)
            time.sleep(3.0)  # pool refill between trials
        return statistics.median(rates)

    _gate(measure, 50, "actor creation storm (actors/s)")


def test_gate_warm_admission_zero_copy_bytes():
    """Gate (r8, paged KV): a warm prefix admission moves ZERO
    device->device KV bytes — shared blocks are increfed into the new
    row's block table, never gathered. Counting, not timing, so it
    holds on any box: the gate fails if a future change dispatches
    any program for a warm admission but its suffix's prefill (or pays
    a CoW block on a non-aligned one)."""
    jax = pytest.importorskip("jax")
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    sys_p = list(range(1, 17))       # 4 full blocks at T=4
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       kv_block_tokens=4,
                       prefix_cache=True)
    eng.submit(sys_p + [50, 51], 4)  # cold: commits the chain
    eng.run()
    s0 = eng.stats()
    for i in range(3):               # warm admissions
        eng.submit(sys_p + [60 + i, 70 + i], 4)
    eng.run()
    s1 = eng.stats()
    assert s1["prefix_hits"] - s0["prefix_hits"] == 3
    assert s1["kv_blocks_shared"] - s0["kv_blocks_shared"] == 12
    assert s1["prefill_real_tokens"] - s0["prefill_real_tokens"] == 6, \
        "a warm admission prefills its 2-token suffix and nothing else"
    assert s1["kv_block_cows"] == s0["kv_block_cows"], \
        "non-aligned warm admissions must not pay copy-on-write"


def test_gate_warm_admission_zero_copy_bytes_quant():
    """Gate (kv quant): warm prefix admissions stay zero-copy with
    int8 KV storage. The scale slab is indexed by the SAME block ids
    as the pool, so a shared block shares its scales for free — a
    warm hit must still incref block-table entries, never gather
    pool bytes or scale rows."""
    jax = pytest.importorskip("jax")
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    sys_p = list(range(1, 17))       # 4 full blocks at T=4
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       kv_block_tokens=4,
                       prefix_cache=True, kv_quant="int8")
    eng.submit(sys_p + [50, 51], 4)  # cold: commits the chain
    eng.run()
    s0 = eng.stats()
    for i in range(3):               # warm admissions
        eng.submit(sys_p + [60 + i, 70 + i], 4)
    eng.run()
    s1 = eng.stats()
    assert s1["prefix_hits"] - s0["prefix_hits"] == 3
    assert s1["kv_blocks_shared"] - s0["kv_blocks_shared"] == 12
    assert s1["prefill_real_tokens"] - s0["prefill_real_tokens"] == 6, \
        "a warm admission prefills its 2-token suffix and nothing else"
    assert s1["kv_block_cows"] == s0["kv_block_cows"], \
        "non-aligned warm admissions must not pay copy-on-write"


def test_gate_null_tracer_zero_allocations_on_decode_path():
    """Gate (r9, tracing): with tracing OFF (the default NullEngineTracer)
    a decode churn allocates ZERO bytes inside engine_trace.py —
    the zero-cost-when-off contract. Counting allocations (tracemalloc
    filtered to the module), not timing, so it holds on any box: the
    gate fails if a call site ever builds an args dict or reads a
    clock before checking `trace.enabled`."""
    import tracemalloc

    jax = pytest.importorskip("jax")
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models import engine_trace
    from ray_tpu.models.engine import DecodeEngine

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32)
    assert eng.trace.enabled is False
    # The engine-lane helper is the one call site with no guard: with no
    # profiler session it must hand out a shared object, not build one.
    assert eng.trace.lane("dispatch", "dispatch", horizon=8, rows=2) \
        is eng.trace.lane("emit", "drain")
    eng.submit([5, 6, 7], 4)
    eng.run()                        # compile outside the window

    trace_filter = tracemalloc.Filter(
        True, engine_trace.__file__)
    tracemalloc.start()
    try:
        for i in range(3):
            eng.submit([5, 6, 7 + i], 4)
        eng.run()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces([trace_filter]).statistics("lineno")
    total = sum(s.size for s in stats)
    assert total == 0, (
        f"no-op tracer allocated {total} bytes on the decode path: "
        + "; ".join(str(s) for s in stats[:5]))


def test_gate_compile_ledger_silent_on_a_warmed_decode_loop():
    """Gate (PR 36, set-up accounting): the compile ledger is fed by
    `jax.monitoring`, and a `jit` call that is already compiled fires no
    event: over 200 warmed decode steps JAX calls no listener at all (so
    none of the ledger's) and not a byte is allocated inside
    compile_cache.py. It fails the day a JAX upgrade puts an event on the
    cached path, or the engine's step loop starts touching the ledger."""
    import tracemalloc

    jax = pytest.importorskip("jax")
    import jax.monitoring as monitoring
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine
    from ray_tpu.util import compile_cache

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=128,
                       decode_horizon=1)
    led = compile_cache.ledger()
    assert eng._compiles is led
    eng.submit([5, 6, 7], 100)
    eng.run()                        # compile outside the window
    calls = []

    def heard(event, *a, **k):       # beside the ledger's own listeners
        calls.append(event)

    monitoring.register_scalar_listener(heard)
    monitoring.register_event_listener(heard)
    monitoring.register_event_duration_secs_listener(heard)
    n, built = len(led.events()), led.builds
    tracemalloc.start()
    try:
        for i in range(200):
            if not eng.pending():    # the same shapes over again
                eng.submit([5, 6, 8 + i], 100)
            eng.step()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
        monitoring.unregister_scalar_listener(heard)
        monitoring.unregister_event_listener(heard)
        monitoring.unregister_event_duration_listener(heard)
    stats = snap.filter_traces([tracemalloc.Filter(
        True, compile_cache.__file__)]).statistics("lineno")
    total = sum(s.size for s in stats)
    assert eng.steps_total >= 200 and calls == []
    assert len(led.events()) == n and led.builds == built
    assert total == 0, (
        f"the compile ledger allocated {total} bytes on the decode path: "
        + "; ".join(str(s) for s in stats[:5]))


def test_gate_armed_idle_fault_injector_zero_allocations():
    """Gate (r13, fault injection): a FaultInjector ARMED on an engine
    but with nothing to inject (no script for this replica, no random
    rates) adds ZERO bytes of allocation inside fault_injection.py
    across a decode churn — the zero-cost-when-idle contract, held the
    same way as the null tracer's gate. Fails if the wrapped step ever
    does bookkeeping before checking the per-replica active flag."""
    import tracemalloc

    jax = pytest.importorskip("jax")
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models import fault_injection
    from ray_tpu.models.engine import DecodeEngine
    from ray_tpu.models.fault_injection import FaultInjector

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32)
    inj = FaultInjector(schedule={"other-replica": [(0, "kill")]})
    inj.arm(eng, "idle-replica")     # armed, but nothing can fire
    eng.submit([5, 6, 7], 4)
    eng.run()                        # compile outside the window

    trace_filter = tracemalloc.Filter(
        True, fault_injection.__file__)
    tracemalloc.start()
    try:
        for i in range(3):
            eng.submit([5, 6, 7 + i], 4)
        eng.run()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces([trace_filter]).statistics("lineno")
    total = sum(s.size for s in stats)
    assert total == 0, (
        f"idle armed injector allocated {total} bytes on the decode "
        "path: " + "; ".join(str(s) for s in stats[:5]))
    assert not inj.fired


def test_gate_tracer_ring_bounded_under_flood():
    """Gate (r9, tracing): 10k events through a small ring stay
    BOUNDED — capacity records live, the rest counted in
    events_dropped, chrome export sized to the ring. A tracer that
    grew without bound would turn a long serving run into an OOM."""
    from ray_tpu.models.engine_trace import EngineTracer

    cap = 256
    tr = EngineTracer(capacity=cap)
    n = 10_000
    for i in range(n):
        tr.span_since_mark("decode_block", i % 7, {"tokens": 1})
    assert len(tr) == cap
    assert tr.events_dropped == n - cap
    assert len(tr._buf) == cap       # storage itself never grew
    assert len(tr.chrome_events()) == cap
    # Bookkeeping dicts track live requests, not event volume.
    assert len(tr._req_mark) == 7 and len(tr._open) == 0


def test_gate_state_snapshot_bounded_allocations():
    """Gate (r11, state API): one FULL serving snapshot — engine rows,
    every in-flight request, KV pools, fleet summary — over a busy
    engine allocates a bounded, small number of live bytes inside
    serving.py. Counting bytes, not timing, so it holds on any box:
    the gate fails if a snapshot ever starts copying KV blocks,
    token lists, or device arrays instead of host-side counters."""
    import tracemalloc

    jax = pytest.importorskip("jax")
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine
    from ray_tpu.util.state import serving

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=32,
                       prefix_cache=True, kv_block_tokens=4)
    for i in range(8):
        eng.submit([5 + i, 6, 7, 8 + i], 16)
    eng.step()                       # genuinely busy: queue + slots
    serving.summarize_fleet()        # warm lazy imports outside window

    tracemalloc.start()
    try:
        held = (serving.list_engines(), serving.list_requests(),
                serving.list_kv_pools(), serving.summarize_fleet())
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces(
        [tracemalloc.Filter(True, serving.__file__)]).statistics(
            "lineno")
    total = sum(s.size for s in stats)
    assert held[1], "gate needs in-flight requests to be meaningful"
    assert total < 256 * 1024, (
        f"one serving snapshot holds {total} bytes live: "
        + "; ".join(str(s) for s in stats[:5]))
    eng.run()


def test_gate_metrics_history_bounded_allocations():
    """Gate (r11, state API): 10k samples through a 32-entry history
    ring retain O(capacity) live bytes inside metrics_history.py —
    the boundedness contract as a memory number, not an entry count
    (an entry that secretly accreted per-sample state would pass
    len() checks and still OOM a long-running server)."""
    import tracemalloc

    from ray_tpu.util import metrics_history as mh

    vals = {k: 1.0 for k in mh.DEFAULT_KEYS}
    warm = mh.MetricsHistory(capacity=32, cadence_s=0.0)
    for _ in range(100):
        warm.sample(vals)            # warm code paths outside window

    tracemalloc.start()
    try:
        h = mh.MetricsHistory(capacity=32, cadence_s=0.0)
        for _ in range(10_000):
            h.sample(vals)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces(
        [tracemalloc.Filter(True, mh.__file__)]).statistics("lineno")
    total = sum(s.size for s in stats)
    assert len(h) < 32 and h.compactions > 0
    assert total < 128 * 1024, (
        f"history ring holds {total} bytes live after 10k samples: "
        + "; ".join(str(s) for s in stats[:5]))


def test_gate_spec_off_zero_allocations_in_spec_path():
    """Gate (r12, speculative): an engine built WITHOUT draft_params
    pays nothing for the spec plane — a decode churn allocates ZERO
    bytes inside speculative.py (SpecStats/SpecMetrics never touched)
    and every dispatch takes the plain `_dispatch_decode` branch
    (spec_dispatches stays 0). Counting allocations, not timing, so it
    holds on any box: the gate fails if the spec seam ever builds
    per-round objects before checking `spec_enabled`."""
    import tracemalloc

    jax = pytest.importorskip("jax")
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models import speculative
    from ray_tpu.models.engine import DecodeEngine

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32)
    eng.submit([5, 6, 7], 4)
    eng.run()                        # compile outside the window

    tracemalloc.start()
    try:
        for i in range(3):
            eng.submit([5, 6, 7 + i], 4)
        eng.run()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces(
        [tracemalloc.Filter(True, speculative.__file__)]).statistics(
            "lineno")
    total = sum(s.size for s in stats)
    assert total == 0, (
        f"spec-off engine allocated {total} bytes in speculative.py: "
        + "; ".join(str(s) for s in stats[:5]))
    s = eng.stats()
    assert s["spec_dispatches"] == 0.0
    assert s["host_syncs_per_token"] <= 1.0, (
        "spec-off engine regressed host syncs per token")


def test_gate_spec_host_syncs_quartered():
    """Gate (r12, speculative): with a perfect draft at window=4 the
    engine advances (window+1) verified tokens per dispatch, so its
    blocking device->host pulls per token must be <= 1/4 of the H=1
    non-spec baseline (budget=20 is a multiple of window+1, so no
    round truncates). Counting syncs, not timing — holds on any box."""
    jax = pytest.importorskip("jax")
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 6, 7], [9, 8, 7, 6]]

    base = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                        decode_horizon=1)
    for p in prompts:
        base.submit(p, 20)
    base.run()
    base_spt = base.stats()["host_syncs_per_token"]

    spec = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                        draft_params=params, draft_cfg=cfg,
                        spec_window=4)
    for p in prompts:
        spec.submit(p, 20)
    spec.run()
    s = spec.stats()
    assert s["spec_acceptance_rate"] == 1.0, s["spec_acceptance_rate"]
    assert s["host_syncs_per_token"] <= base_spt / 4.0, (
        f"spec engine pays {s['host_syncs_per_token']:.3f} syncs/token "
        f"vs H=1 baseline {base_spt:.3f}; want <= baseline/4")


# ---------------------------------------------------------------------------
# runtime sanitizer gates (RAY_TPU_SANITIZE): zero retraces + zero
# unexpected device->host transfers on steady decode, per feature combo
# ---------------------------------------------------------------------------

# The sanitizer gates below count COMPILES and TRANSFERS, not time, so
# they need no quiesce and hold on any box. Contract: after a warmup
# that exercises the exact steady workload (two full passes — pass 1
# compiles the cold paths, pass 2 compiles warm-hit paths like the
# prefix cache's suffix-only prefill), an armed pass over the same workload must (a)
# never grow a fused entry point's compile cache, (b) never pull
# device->host outside the _device_get/_host_async choke points, and
# (c) still emit token streams identical to solo `generate`.

SANITIZER_COMBOS = {
    "plain": {},                    # blocks of 32: two a row
    "prefix": {"prefix_cache": True},
    "blocks4": {"kv_block_tokens": 4},
    "chunked_prefix": {"prefix_cache": True, "kv_block_tokens": 4,
                       "prefill_chunk": 3},
    "pipeline": {"pipeline_depth": 3},
    "spec": {"spec": True},
    "spec_blocks4": {"spec": True, "kv_block_tokens": 4},
    "tp": {"tp": 2},
    # Quantized-KV twins: the int8 pool + scale slab must introduce no
    # retraces and no stray pulls either. Token streams under quant are
    # tolerance-gated (test_engine_kv_quant), not solo-identical, so
    # the identity assert softens to budget-shape only for these.
    "quant": {"kv_quant": "int8"},
    "prefix_quant": {"prefix_cache": True, "kv_quant": "int8"},
    "spec_quant": {"spec": True, "kv_quant": "int8"},
    "tp_quant": {"tp": 2, "kv_quant": "int8", "kv_block_tokens": 4},
}

_SAN_PROMPTS = [[5, 6, 7], [9, 8, 7, 6, 5]]
_SAN_BUDGET = 10


@pytest.fixture(autouse=True)
def _disarm_leftover_sanitizer():
    """Never leak an armed sanitizer (process-global interposition)
    into other tests, even when an assertion fires mid-gate."""
    yield
    from ray_tpu._private import sanitize
    san = sanitize.active()
    if san is not None:
        san.disarm()


def _san_engine(params, cfg, combo):
    from ray_tpu.models.engine import DecodeEngine
    kw = dict(combo)
    if kw.pop("spec", False):
        kw.update(draft_params=params, draft_cfg=cfg, spec_window=4)
    return DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                        decode_horizon=4, **kw)


def _san_workload(eng):
    out = {}
    rids = [eng.submit(p, _SAN_BUDGET) for p in _SAN_PROMPTS]
    got = eng.run()
    for rid in rids:
        out[rid] = got[rid]
    return [out[r] for r in rids]


@pytest.mark.parametrize("combo", sorted(SANITIZER_COMBOS))
def test_gate_sanitizer_steady_decode(combo):
    """Gate: zero recompiles + zero unexpected transfers on steady
    decode, with sanitized output token-identical to solo generate."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.generate import generate
    from ray_tpu._private.sanitize import SanitizerError

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    eng = _san_engine(params, cfg, SANITIZER_COMBOS[combo])

    _san_workload(eng)           # pass 1: cold compiles (+ prefix commits)
    _san_workload(eng)           # pass 2: warm-hit paths compile
    san = eng.arm_sanitizer()
    try:
        emitted = _san_workload(eng)   # armed pass: must be all-cached
    except SanitizerError as exc:
        pytest.fail(f"[{combo}] unexpected device->host transfer on the "
                    f"steady decode path: {exc}")
    finally:
        eng.disarm_sanitizer()

    assert san.total_retraces() == 0, (
        f"[{combo}] steady-decode retraces: {san.retraces()}")
    assert san.unexpected_transfers == [], san.unexpected_transfers
    assert san.expected_pulls > 0, "armed pass should pull via _device_get"

    quant_on = "kv_quant" in SANITIZER_COMBOS[combo]
    for prompt, toks in zip(_SAN_PROMPTS, emitted):
        assert len(toks) == _SAN_BUDGET, (
            f"[{combo}] sanitized engine emitted {len(toks)} tokens, "
            f"wanted {_SAN_BUDGET}")
        if quant_on:
            # Quantized KV is tolerance-gated against bf16 elsewhere
            # (test_engine_kv_quant); solo identity is only promised
            # at quant-off.
            continue
        solo = np.asarray(generate(
            params, jnp.asarray([prompt], jnp.int32), cfg,
            max_new_tokens=_SAN_BUDGET))[0, len(prompt):].tolist()
        assert toks == solo[:len(toks)], (
            f"[{combo}] sanitized engine diverged from solo generate")


def test_gate_sanitizer_env_auto_arm(monkeypatch):
    """RAY_TPU_SANITIZE=1 builds the sanitizer at engine construction
    and auto-arms it after RAY_TPU_SANITIZE_WARMUP steps — no code
    changes needed to sanitize a deployment."""
    jax = pytest.importorskip("jax")
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu._private import sanitize

    monkeypatch.setenv("RAY_TPU_SANITIZE", "1")
    monkeypatch.setenv("RAY_TPU_SANITIZE_WARMUP", "3")
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    eng = _san_engine(params, cfg, {})
    assert eng.sanitizer is not None and not eng.sanitizer.armed
    eng.submit(_SAN_PROMPTS[0], 24)
    steps = 0
    while eng.pending():
        eng.step()
        steps += 1
        if steps <= 3:
            assert not eng.sanitizer.armed    # still warming up
    assert steps >= 4 and eng.sanitizer.armed  # armed mid-flight, no trips
    assert eng.sanitizer.unexpected_transfers == []
    stats = eng.sanitizer_stats()
    assert stats["expected_pulls"] > 0
    eng.disarm_sanitizer()
    assert sanitize.active() is None


def test_gate_sanitizer_catches_stray_pull_and_restores():
    """Negative control: while armed, a pull OUTSIDE _device_get raises
    SanitizerError (strict mode); disarm restores pristine behavior and
    the transfer-guard config."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu._private.sanitize import SanitizerError

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    eng = _san_engine(params, cfg, {})
    _san_workload(eng)
    eng.arm_sanitizer()
    try:
        with pytest.raises(SanitizerError):
            float(jnp.ones(()) * 3)            # stray implicit pull
        with pytest.raises(SanitizerError):
            jnp.arange(4).tolist()             # stray bulk pull
        with pytest.raises(SanitizerError):
            bool(jnp.ones(()) > 0)             # stray truthiness sync
    finally:
        eng.disarm_sanitizer()
    assert float(jnp.ones(()) * 3) == 3.0      # interposition removed
    assert jnp.arange(4).tolist() == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# Gate: multi-LoRA plane is free when unused
# ---------------------------------------------------------------------------

def test_gate_adapter_off_zero_allocations_in_adapter_path():
    """Gate (multi-LoRA): an engine built WITHOUT lora= pays nothing
    for the adapter plane — a decode churn allocates ZERO bytes inside
    adapter_pool.py (no AdapterPool, no per-round residency objects)
    and the adapter stats stay identically 0. Fails if any dispatch
    seam ever builds adapter state before checking `adapter_pool is
    None`."""
    import tracemalloc

    jax = pytest.importorskip("jax")
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models import adapter_pool
    from ray_tpu.models.engine import DecodeEngine

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32)
    eng.submit([5, 6, 7], 4)
    eng.run()                        # compile outside the window

    tracemalloc.start()
    try:
        for i in range(3):
            eng.submit([5, 6, 7 + i], 4)
        eng.run()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces(
        [tracemalloc.Filter(True, adapter_pool.__file__)]).statistics(
            "lineno")
    total = sum(s.size for s in stats)
    assert total == 0, (
        f"adapter-off engine allocated {total} bytes in adapter_pool.py: "
        + "; ".join(str(s) for s in stats[:5]))
    s = eng.stats()
    assert s["adapter_enabled"] == 0.0
    for k in ("adapter_lookups", "adapter_hits", "adapter_prefetches",
              "adapter_evictions", "adapter_prefetch_deferrals",
              "adapter_slots", "adapter_slots_resident",
              "adapter_slots_pinned"):
        assert s[k] == 0.0, f"{k} nonzero on an adapter-less engine"


def test_gate_adapter_enabled_base_traffic_zero_retrace():
    """Gate (multi-LoRA): an adapter-ENABLED engine serving ONLY
    adapter_id=None traffic recompiles nothing and leaks no transfers
    once warm — the slot-0 null adapter rides the same fused programs,
    so turning the feature on costs base traffic zero steady-state
    work. Output stays identical to solo generate (bit-identity vs a
    lora=None engine is test_engine_lora.py's job)."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from ray_tpu.models import LlamaConfig, LoraConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine
    from ray_tpu.models.generate import generate
    from ray_tpu._private.sanitize import SanitizerError

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       decode_horizon=4, lora=LoraConfig(rank=4),
                       max_live_adapters=2)

    _san_workload(eng)           # pass 1: cold compiles
    _san_workload(eng)           # pass 2: warm-hit paths
    san = eng.arm_sanitizer()
    try:
        emitted = _san_workload(eng)
    except SanitizerError as exc:
        pytest.fail("adapter-enabled engine pulled device->host on "
                    f"base-only traffic: {exc}")
    finally:
        eng.disarm_sanitizer()

    assert san.total_retraces() == 0, san.retraces()
    assert san.unexpected_transfers == [], san.unexpected_transfers
    for prompt, toks in zip(_SAN_PROMPTS, emitted):
        solo = np.asarray(generate(
            params, jnp.asarray([prompt], jnp.int32), cfg,
            max_new_tokens=_SAN_BUDGET))[0, len(prompt):].tolist()
        assert toks == solo
    s = eng.stats()
    assert s["adapter_enabled"] == 1.0
    assert s["adapter_lookups"] == 0.0

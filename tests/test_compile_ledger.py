"""The process-wide compile ledger (`ray_tpu/util/compile_cache.py`).

What JAX's events look like, so that each rule below can be read against
them (jax 0.9.0; `@jax.jit def my_prog(x): return inner_a(x) + inner_b(x)`
with two inner `jit`s, first call, a persistent cache that holds it):

    scalar   jaxpr_trace_duration           fun_name='my_prog'   (start)
    scalar   jaxpr_trace_duration           fun_name='inner_a'   (start)
    duration jaxpr_trace_duration           fun_name='matmul' ... 'inner_a'
    ...                                     the same for inner_b
    duration jaxpr_trace_duration           fun_name='my_prog'
    duration jaxpr_to_mlir_module_duration  fun_name='jit(my_prog)'
    event    /jax/compilation_cache/cache_hits
    duration /jax/compilation_cache/cache_retrieval_time_sec
    duration backend_compile_duration       fun_name='jit(my_prog)'

No engine and no model is built here: seconds in all.
"""

import os
import sys
import threading
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.util import compile_cache as cc  # noqa: E402
from ray_tpu.util.compile_cache import CompileLedger, ledger  # noqa: E402


def _named(name, inner=()):
    """A jitted program under a name no other test uses (the ledger is the
    process's and keyed by name), calling the `inner` jits if any."""
    def f(x):
        y = jnp.sin(x) @ x
        for g in inner:
            y = y + g(x)
        return y
    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def _written():
    """Records the process's ledger ever took: its ring keeps the newest
    4,096, and a worker that ran other modules first has long filled it, so
    a position in `events()` says nothing (`events()[n:]` is then empty)."""
    return len(ledger()) + ledger().events_dropped


def _since(mark):
    """The records written after `_written()` read `mark`."""
    new = _written() - mark
    return ledger().events()[-new:] if new else []


def _row(name):
    return next((r for r in ledger().report() if r["program"] == name),
                None)


@pytest.fixture()
def persistent_cache(tmp_path):
    """JAX's persistent cache in `tmp_path`, every entry kept; the
    process's own state (no cache: `tests/conftest.py`) restored after."""
    from jax.experimental.compilation_cache import compilation_cache as jcc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), 0.0, -1)):
        jax.config.update(k, v)
    jcc.reset_cache()
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    jcc.reset_cache()


CASES = {
    # case: (persistent cache, builds, hits, misses)
    "no_persistent_cache": (False, 1, 0, 1),
    "cache_first_build": (True, 1, 0, 1),
    "cache_second_build": (True, 2, 1, 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_builds_hits_and_misses_by_program(case, request):
    """A build is a miss (compiled) unless the cache's own event said hit
    inside it: with no persistent cache there is no such event at all. A
    hit's seconds go to `fetch_s`, a miss's to `compile_s`."""
    cached, builds, hits, misses = CASES[case]
    if cached:
        request.getfixturevalue("persistent_cache")
    name = f"ledger_probe_{case}"
    prog = _named(name)
    x = jnp.ones((16, 16))
    before = ledger().counters()
    for _ in range(builds):
        jax.clear_caches()        # the in-memory caches, not the directory
        prog(x).block_until_ready()
    prog(x).block_until_ready()   # already compiled: fires no event
    row = _row(name)
    assert (row["builds"], row["hits"], row["misses"]) == \
        (builds, hits, misses)
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0
    assert (row["fetch_s"] > 0) == bool(hits)
    mine = [e for e in ledger().events() if e[0] == name]
    assert [e[1] for e in mine] == ["trace", "lower", "compile"] + (
        ["trace", "lower", "fetch"] if hits else [])
    assert [e[4] for e in mine if e[4] is not None] == \
        [False] + [True] * hits
    assert all(a[2] <= b[2] for a, b in zip(mine, mine[1:]))    # stamps
    after = ledger().counters()
    assert after["compiles_total"] - before["compiles_total"] >= builds
    assert after["compile_cache_misses_total"] \
        - before["compile_cache_misses_total"] >= misses
    assert after["compile_s_total"] > before["compile_s_total"]


def test_a_program_with_inner_jits_counts_its_trace_once():
    """Tracing the outer program traces `sin`, `matmul` and both inner
    `jit`s first, each inside the outer's duration: only the top-level
    trace is kept, so the traced seconds cannot pass the call's."""
    inner = [_named("ledger_probe_inner_a"), _named("ledger_probe_inner_b")]
    prog = _named("ledger_probe_outer", inner)
    x = jnp.ones((16, 16))
    x.block_until_ready()
    n = _written()
    t0 = time.perf_counter()
    prog(x).block_until_ready()
    wall = time.perf_counter() - t0
    mine = _since(n)
    assert {e[0] for e in mine} == {"ledger_probe_outer"}
    assert [e[1] for e in mine] == ["trace", "lower", "compile"]
    assert _row("ledger_probe_inner_a") is None
    assert 0 < sum(e[3] for e in mine if e[1] == "trace") <= wall
    assert sum(e[3] for e in mine) <= wall
    # a stamp is the event's end on this clock, its start that less its
    # seconds (which JAX took on the wall clock: a millisecond of slack)
    assert all(t0 - 1e-3 <= e[2] - e[3] and e[2] <= t0 + wall for e in mine)


def _build(led, name, hit=False, seconds=0.5):
    """One backend build as JAX reports it, on the calling thread."""
    led._on_start(cc._BACKEND, 0.0, fun_name=f"jit({name})")
    if hit:
        led._on_event(cc._CACHE_HIT)
        led._on_duration(cc._CACHE_FETCH, seconds / 10)
    led._on_duration(cc._BACKEND, seconds, fun_name=f"jit({name})")


def test_the_ring_overwrites_its_oldest_and_counts_the_drop():
    led = CompileLedger(capacity=4)
    for i in range(6):
        _build(led, f"p{i}", hit=i % 2 == 1)
    assert len(led) == 4 and led.events_dropped == 2
    assert len(led._buf) == 4                 # storage itself never grew
    assert [e[0] for e in led.events()] == ["p2", "p3", "p4", "p5"]
    assert [e[1] for e in led.events()] == ["compile", "fetch"] * 2
    # the per-program table and the totals lose nothing to the ring
    assert len(led.report()) == 6
    assert led.counters() == {"compiles_total": 6.0,
                              "compile_cache_misses_total": 3.0,
                              "compile_s_total": 1.5}
    assert led.report()[0]["compile_s"] == 0.5       # slowest first
    with pytest.raises(ValueError):
        CompileLedger(capacity=0)


def _listeners_of(led):
    from jax._src import monitoring

    return [fn for fn in (monitoring.get_event_duration_listeners()
                          + monitoring.get_event_listeners()
                          + monitoring.get_scalar_listeners())
            if getattr(fn, "__self__", None) is led]


def test_install_is_idempotent():
    led = ledger()
    assert ledger() is led and len(_listeners_of(led)) == 3
    prog = _named("ledger_probe_once")
    prog(jnp.ones((16, 16))).block_until_ready()
    assert _row("ledger_probe_once")["builds"] == 1
    assert [e[1] for e in led.events() if e[0] == "ledger_probe_once"] \
        == ["trace", "lower", "compile"]


@pytest.mark.parametrize("env", ["JAX_COMPILATION_CACHE_DIR",
                                 "JAX_PLATFORMS"])
def test_enable_compile_cache_installs_before_it_returns_early(
        env, monkeypatch, tmp_path):
    """The chip machine sets JAX_COMPILATION_CACHE_DIR and every test
    JAX_PLATFORMS=cpu: `enable_compile_cache()` sets no directory then,
    and must have installed the ledger first."""
    import jax.monitoring as monitoring

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv(env, "cpu" if env == "JAX_PLATFORMS"
                       else str(tmp_path))
    monkeypatch.setattr(cc, "_LEDGER", None)      # a fresh process's state
    try:
        assert cc.enable_compile_cache() is None
        led = cc._LEDGER
        assert led is not None and len(_listeners_of(led)) == 3
    finally:                 # leave the process's one ledger as it was
        monitoring.unregister_scalar_listener(led._on_start)
        monitoring.unregister_event_listener(led._on_event)
        monitoring.unregister_event_duration_listener(led._on_duration)


def test_two_threads_building_at_once_keep_their_own_hits():
    """A's build hits the cache while B's, begun and ended on another
    thread in the middle of it, does not: hit or miss is the building
    thread's. Then a flood from more threads than cores loses no update."""
    led = CompileLedger()
    a_hit, b_done = threading.Event(), threading.Event()

    def build_a():
        led._on_start(cc._BACKEND, 0.0, fun_name="jit(a)")
        led._on_event(cc._CACHE_HIT)
        a_hit.set()
        assert b_done.wait(10)
        led._on_duration(cc._CACHE_FETCH, 0.25)
        led._on_duration(cc._BACKEND, 0.5, fun_name="jit(a)")

    def build_b():
        assert a_hit.wait(10)
        _build(led, "b")
        b_done.set()

    threads = [threading.Thread(target=f) for f in (build_a, build_b)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(20)
        assert not t.is_alive()
    rows = {r["program"]: r for r in led.report()}
    assert (rows["a"]["hits"], rows["a"]["misses"]) == (1, 0)
    assert (rows["b"]["hits"], rows["b"]["misses"]) == (0, 1)
    assert rows["a"]["fetch_s"] == 0.25 and rows["b"]["compile_s"] == 0.5

    workers, each = 4 * (os.cpu_count() or 2), 200
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lambda i=i: [
            _build(led, f"w{i % 3}", hit=j % 2 == 0) for j in range(each)])
            for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert led.builds == 2 + workers * each
    assert led.misses == 1 + workers * each // 2
    assert sum(r["builds"] for r in led.report()) == led.builds
    assert len(led) + led.events_dropped == led.builds

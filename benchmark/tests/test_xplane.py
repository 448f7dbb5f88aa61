"""The trace reduction on data/synthetic.xplane.pb, whose events are laid
out in make_synthetic_trace.py's docstring. Every expectation below is
worked out by hand from that layout (microseconds)."""

import os

import pytest

from benchmark.harness import common, xplane
from benchmark.tests import make_synthetic_trace

US = 1000  # ns


@pytest.fixture(scope="module")
def trace():
    return xplane.load(make_synthetic_trace.PATH)


@pytest.fixture(scope="module")
def window(trace):
    return xplane.span_window(trace.host, "bench.window")


def test_committed_file_is_what_the_maker_writes():
    with open(make_synthetic_trace.PATH, "rb") as f:
        assert f.read() == make_synthetic_trace.SPACE


def test_planes_and_window(trace, window):
    assert sorted(trace.devices) == [0, 1]
    assert window[1] - window[0] == 500 * US


def test_busy_union_and_idle_share(trace, window):
    ops0 = trace.devices[0]["XLA Ops"]
    # [0,150) u [200,260) u [300,450): overlapping ops count once
    assert xplane.busy_ns(ops0, window) == 360 * US
    assert xplane.busy_ns(trace.devices[1]["XLA Ops"], window) == 250 * US
    assert 1 - 360 / 500 == pytest.approx(0.28)
    # a window that cuts an op counts only the part inside
    cut = (window[0] + 50 * US, window[0] + 220 * US)
    assert xplane.busy_ns(ops0, cut) == (100 + 20) * US


def test_module_and_kernel_sums(trace, window):
    mods = trace.devices[0]["XLA Modules"]
    assert xplane.sum_matching(mods, r"decode_multi_paged", window) == \
        (150 * US, 1)
    assert xplane.sum_matching(mods, r"prefill_rows_paged", window) == \
        (250 * US, 1)
    ops = trace.devices[0]["XLA Ops"]
    assert xplane.sum_matching(ops, r"custom-call", window) == (60 * US, 1)
    assert xplane.sum_matching(ops, r"fusion", window) == (200 * US, 2)
    assert xplane.sum_matching(ops, r"no such op", window) == (0, 0)


def test_exposed_collective_time(trace, window):
    # chip 0: all-gather [80,150) is hidden under fusion.1 until 100,
    # all-reduce [400,450) runs alone: 50 + 50
    assert xplane.exposed_collective_ns(
        trace.devices[0]["XLA Ops"], window) == 100 * US
    # chip 1: all-gather [50,250) with nothing beside it
    assert xplane.exposed_collective_ns(
        trace.devices[1]["XLA Ops"], window) == 200 * US


def test_top_ops(trace, window):
    top = xplane.top_ops(trace.devices[0]["XLA Ops"], window, k=3)
    assert [n for n, _ in top] == ["fusion.1", "all-gather.2",
                                   "custom-call.3"]
    assert top[0][1] == pytest.approx(200e-6)


def test_idle_gaps_named_by_host_span(trace, window):
    names = ["engine.step", "submit", "idle_no_request"]
    gaps = dict(xplane.idle_gaps(trace.devices[0]["XLA Ops"], trace.host,
                                 window, names))
    # idle: [150,200) [260,300) [450,500); engine.step spans [0,180) and
    # [190,470); idle_no_request [470,500); [180,190) has no span
    assert gaps == pytest.approx({"engine.step": 100e-6,
                                  "idle_no_request": 30e-6,
                                  "uncovered": 10e-6})


def test_interval_arithmetic():
    assert xplane.merge([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert xplane.subtract([(0, 4)], []) == [(0, 4)]


def test_reduce_trace_on_the_synthetic_file(tmp_path):
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(make_synthetic_trace.SPACE)
    session = common.ProfilerSession(str(tmp_path))
    red = common.reduce_trace(session, ["engine.step", "idle_no_request"])
    assert red["window_s"] == pytest.approx(500e-6)
    assert red["busy_s"] == pytest.approx((360e-6 + 250e-6) / 2)
    assert red["idlest_chip"] == 1
    assert dict(red["breakdown"]["idle_gaps"]) == pytest.approx(
        {"engine.step": 220e-6, "idle_no_request": 30e-6})
    assert red["breakdown"]["device_ops"][0] == ["all-gather.2",
                                                 pytest.approx(200e-6)]


def test_layer_readers_on_the_synthetic_trace(tmp_path):
    from benchmark.harness import spec

    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(make_synthetic_trace.SPACE)
    red = common.reduce_trace(common.ProfilerSession(str(tmp_path)), [])
    read = lambda name, rec: spec.load_module(  # noqa: E731
        "layer_metrics", name).read(rec, red)
    # worst chip: 200 of 500 us exposed
    assert read("collective_exposed_pct", {}) == pytest.approx(40.0)
    # chip 1 is the idlest and has no modules line: nothing to read
    snaps = {"t0": {"decode_horizon_mean": 0, "decode_horizon_count": 0},
             "t1": {"decode_horizon_mean": 8, "decode_horizon_count": 3}}
    assert read("decode_step_device_ms", {"snaps": snaps}) is None
    red["idlest_chip"] = 0
    # 150 us of decode module over 24 tokens of horizon
    assert read("decode_step_device_ms", {"snaps": snaps}) == \
        pytest.approx(0.150 / 24)
    assert read("prefill_device_share_pct", {}) == \
        pytest.approx(100 * 250 / 360)
    assert read("decode_step_device_ms", {"snaps": {}}) is None


# -- a recorded trace ---------------------------------------------------------
# data/chat_excerpt.xplane.pb: the first 300 ms of the traced stretch of
# mistral7b-chat on one v5e chip (PR 23, 12 layers, 32 slots), cut and
# re-encoded by make_synthetic_trace.excerpt. It holds two prefills of one
# 512-token chunk and two one-token executions of the decode program.

EXCERPT = os.path.join(os.path.dirname(make_synthetic_trace.PATH),
                       "chat_excerpt.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(EXCERPT)


def test_recorded_modules_and_nesting(recorded):
    ops = recorded.devices[0]["XLA Ops"]
    mods = recorded.devices[0]["XLA Modules"]
    w = (0, 300_000_000)
    assert [m[0].split("(")[0] for m in sorted(mods, key=lambda e: e[1])] \
        == ["jit__prefill_rows_paged", "jit__decode_multi_paged"] * 2
    dec_ns, n = xplane.sum_matching(mods, "decode_multi_paged", w)
    assert n == 2 and dec_ns == 78903140 + 78913937
    # the ops line is nested: while loops span their bodies, so the plain
    # sum of durations is nearly twice the busy time, the leaves' is not
    busy = xplane.busy_ns(ops, w)
    assert sum(e[2] for e in ops) > 1.8 * busy
    leaf = sum(e[2] for e in xplane.leaves(ops))
    assert 0.95 * busy < leaf <= busy
    # busy time lies inside the modules' time
    assert busy <= sum(m[2] for m in mods)


def test_recorded_paged_kernel_is_found(recorded):
    ops = recorded.devices[0]["XLA Ops"]
    mods = recorded.devices[0]["XLA Modules"]
    ns, n = xplane.sum_within(ops, xplane.PALLAS_KERNEL, mods,
                              "decode_multi_paged", (0, 300_000_000))
    assert n == 2 * 12                  # one call a layer a token
    assert 2.8e6 < ns / n < 3.0e6       # 2.9 ms a call on the chip
    # no Pallas kernel runs in the prefill program
    assert xplane.sum_within(ops, xplane.PALLAS_KERNEL, mods,
                             "prefill_rows_paged", (0, 300_000_000))[1] == 0
    top = xplane.top_ops(ops, (0, 300_000_000), k=1)[0]
    assert top[0].endswith("tpu_custom_call") and "custom-call" in top[0]
    assert top[1] == pytest.approx(ns / 1e9)


def test_recorded_host_spans_are_on_the_device_clock(recorded):
    steps = sorted(e for e in recorded.host if e[0] == "engine.step")
    assert len(steps) == 2
    mods = sorted(recorded.devices[0]["XLA Modules"], key=lambda e: e[1])
    # the first step's span covers its prefill and decode executions
    s0 = steps[0]
    assert s0[1] <= mods[0][1] and mods[1][1] + mods[1][2] <= s0[1] + s0[2] \
        + 1_000_000

"""The step-clock readers (PR 54) on hand-made snapshots of
`engine.stats()`: the window's own share, a program from before the
counters (an older checkout as the parent), a window without a step or a
stall, and each reader against its entry in BENCHMARK.json."""

import pytest

from benchmark.harness import spec

SERVING = ["mistral7b-chat", "mistral7b-rollout", "olmoe-chat-short",
           "phi4flash-reason", "dsv32-longdoc", "qwen3next-longctx",
           "dots3-mixed-ctx"]
CLOSED = ["mistral7b-rollout", "phi4flash-reason", "dsv32-longdoc",
          "qwen3next-longctx", "dots3-mixed-ctx"]
CHAT = ["mistral7b-chat", "olmoe-chat-short"]
HOST = "engine step loop, host"
# name -> (unit, layer, moves, cells)
ENTRIES = {
    "step_self_ms": ("ms", HOST, "tpot_p95_ms", SERVING),
    "step_emit_ms": ("ms", HOST, "tpot_p95_ms", SERVING),
    "step_dispatch_ms": ("ms", HOST, "tpot_p95_ms", SERVING),
    "step_admit_ms": ("ms", "scheduler and admission", "ttft_p95_ms", CHAT),
    "device_starved_pct": ("%", HOST, "out_tokens_per_s", CLOSED),
    "starved_after_retire_pct": ("%", HOST, "out_tokens_per_s", CLOSED),
    "ttft_first_dispatch_mean_ms": ("ms", HOST, "ttft_p95_ms", CHAT),
    "ttft_first_return_mean_ms": ("ms", HOST, "ttft_p95_ms", CHAT),
    "first_token_blocks_ahead": ("count", HOST, "ttft_p95_ms", CHAT),
    "step_stall_s": ("s", HOST, "tpot_p95_ms", SERVING),
    "step_stall_device_wait_pct": ("%", HOST, "tpot_p95_ms", SERVING),
}


def read(name, snaps):
    return spec.load_module("layer_metrics", name).read({"snaps": snaps}, None)


def agg(key, count, mean):
    return {key + "_count": float(count), key + "_mean": float(mean)}


def snap(steps, up, wall, wait, emit=0.0, dispatch=0.0, prefill=0.0,
         admit=0.0, starved=0.0, retire=0.0, stalled=0.0, stalled_wait=0.0,
         first=(0, 0.0, 0.0, 0.0)):
    n, dispatch_s, return_s, ahead = first
    out = {"steps_total": float(steps), "uptime_s": up,
           "step_s_total": wall, "device_wait_s": wait,
           "step_emit_s_total": emit, "step_dispatch_s_total": dispatch,
           "step_prefill_dispatch_s_total": prefill,
           "step_admit_s_total": admit, "device_starved_s_total": starved,
           "device_starved_retire_s_total": retire,
           "step_stalled_s_total": stalled,
           "step_stalled_device_wait_s_total": stalled_wait}
    out.update(agg("first_dispatch_s", n, dispatch_s))
    out.update(agg("first_return_s", n, return_s))
    out.update(agg("first_blocks_ahead", n, ahead))
    out.update(agg("first_block_s", n, dispatch_s + return_s))
    return out


# the ramp: 100 steps; the window: 400 steps in 40 s of the engine's clock
W0 = snap(100, 20.0, 9.0, 8.5, emit=0.1, dispatch=0.2, prefill=0.1,
          admit=0.05, starved=0.3, retire=0.1, first=(10, 0.010, 0.050, 0.5))
W1 = snap(500, 60.0, 49.0, 46.5, emit=0.9, dispatch=1.0, prefill=0.5,
          admit=0.25, starved=1.5, retire=1.0, stalled=2.0, stalled_wait=1.5,
          first=(50, 0.030, 0.070, 0.9))
WINDOW = {
    "step_self_ms": (40.0 - 38.0) / 400 * 1e3,
    "step_emit_ms": 0.8 / 400 * 1e3,
    "step_dispatch_ms": (0.8 + 0.4) / 400 * 1e3,
    "step_admit_ms": 0.2 / 400 * 1e3,
    "device_starved_pct": 100 * 1.2 / 40.0,
    "starved_after_retire_pct": 100 * 0.9 / 1.2,
    "ttft_first_dispatch_mean_ms": (50 * 0.030 - 10 * 0.010) / 40 * 1e3,
    "ttft_first_return_mean_ms": (50 * 0.070 - 10 * 0.050) / 40 * 1e3,
    "first_token_blocks_ahead": (50 * 0.9 - 10 * 0.5) / 40,
    "step_stall_s": 2.0,
    "step_stall_device_wait_pct": 75.0,
}
PARENT = {"w0": {"steps_total": 100.0, "uptime_s": 20.0,
                 "device_wait_s": 8.5, **agg("first_block_s", 10, 0.06)},
          "w1": {"steps_total": 500.0, "uptime_s": 60.0,
                 "device_wait_s": 46.5, **agg("first_block_s", 50, 0.1)}}


def test_the_table_names_every_reader_this_pr_adds():
    assert set(ENTRIES) == set(WINDOW)
    assert len(ENTRIES) == 11


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reading_is_of_the_window_alone(name):
    assert read(name, {"w0": W0, "w1": W1}) == pytest.approx(WINDOW[name])


@pytest.mark.parametrize("name", sorted(ENTRIES))
@pytest.mark.parametrize("snaps", [{}, {"w0": W0}, PARENT],
                         ids=["no_snapshot", "no_closing_snapshot",
                              "no_counter"])
def test_none_when_there_is_nothing_to_read(name, snaps):
    assert read(name, snaps) is None


@pytest.mark.parametrize("name", ["step_self_ms", "step_emit_ms",
                                  "step_dispatch_ms", "step_admit_ms"])
def test_none_over_a_window_without_a_step(name):
    assert read(name, {"w0": W1, "w1": W1}) is None


def test_a_window_without_a_stall_reads_zero_seconds_and_a_zero_share():
    """A cell that lists a metric reports it in every traced run: with no
    stalled second the share is 0, not left out."""
    calm = dict(W1, step_stalled_s_total=0.0,
                step_stalled_device_wait_s_total=0.0)
    assert read("step_stall_s", {"w0": W0, "w1": calm}) == 0.0
    assert read("step_stall_device_wait_pct", {"w0": W0, "w1": calm}) == 0.0


def test_a_traced_run_reads_starved_seconds_up_to_the_trace():
    """The profiler's stop stands inside the window: what accrues between
    steps is read up to the snapshot taken as the profiler starts."""
    t0 = dict(W1, uptime_s=50.0, device_starved_s_total=0.9,
              device_starved_retire_s_total=0.4)
    stopped = dict(W1, uptime_s=66.0, device_starved_s_total=7.5)
    snaps = {"w0": W0, "t0": t0, "t1": t0, "w1": stopped}
    assert read("device_starved_pct", snaps) == pytest.approx(
        100 * 0.6 / 30.0)
    assert read("starved_after_retire_pct", snaps) == pytest.approx(50.0)
    # the per-step readers keep the window's two ends, as step_host_ms
    assert read("step_self_ms", snaps) == pytest.approx(
        WINDOW["step_self_ms"])


def test_nothing_starved_has_a_zero_share_after_a_retirement():
    fed = dict(W1, device_starved_s_total=0.3,
               device_starved_retire_s_total=0.1)
    assert read("device_starved_pct", {"w0": W0, "w1": fed}) == 0.0
    assert read("starved_after_retire_pct", {"w0": W0, "w1": fed}) == 0.0


def test_no_first_token_in_the_window_has_no_mean():
    still = dict(W1, **agg("first_dispatch_s", 10, 0.010),
                 **agg("first_return_s", 10, 0.050),
                 **agg("first_blocks_ahead", 10, 0.5))
    for name in ("ttft_first_dispatch_mean_ms", "ttft_first_return_mean_ms",
                 "first_token_blocks_ahead"):
        assert read(name, {"w0": W0, "w1": still}) is None


def test_the_first_block_is_its_two_halves():
    snaps = {"w0": W0, "w1": W1}
    whole = spec.load_module("layer_metrics", "ttft_first_block_mean_ms") \
        .read({"snaps": snaps}, None)
    assert read("ttft_first_dispatch_mean_ms", snaps) \
        + read("ttft_first_return_mean_ms", snaps) == pytest.approx(whole)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_entry_agrees_with_the_reader(name):
    unit, layer, moves, cells = ENTRIES[name]
    (entry,) = [m for m in spec.load_benchmark()["per_layer"]
                if m["name"] == name]
    mod = spec.load_module("layer_metrics", name)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"]) \
        == (layer, unit, "program_counter", moves)
    assert entry["better"] == "lower" and entry["workloads"] == cells
    known = {w["name"] for w in spec.load_benchmark()["workloads"]}
    assert set(cells) <= known


@pytest.mark.parametrize("cell", SERVING)
def test_every_serving_cell_loads_with_its_new_readers(cell):
    names = {m.name for m in spec.load_cell(cell).per_layer}
    assert {n for n, e in ENTRIES.items() if cell in e[3]} <= names
    assert not {n for n, e in ENTRIES.items() if cell not in e[3]} & names


def test_the_new_entries_are_the_last_of_per_layer():
    tail = [m["name"] for m in spec.load_benchmark()["per_layer"][-11:]]
    assert set(tail) == set(ENTRIES)
    assert "internlm2-train-fsdp4" not in {
        c for e in ENTRIES.values() for c in e[3]}

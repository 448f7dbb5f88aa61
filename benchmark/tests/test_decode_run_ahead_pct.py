"""The reader of `decode_run_ahead_pct` on hand-made snapshots of
`engine.stats()`, on a program from before the counter (an older
checkout as the parent), and against its entry in BENCHMARK.json."""

import pytest

from benchmark.harness import spec

NAME = "decode_run_ahead_pct"
CELLS = ["mistral7b-rollout", "phi4flash-reason", "dsv32-longdoc"]


def read(snaps):
    return spec.load_module("layer_metrics", NAME).read({"snaps": snaps}, None)


def snap(launched, chained, queued=0):
    return {"decode_dispatches": float(launched),
            "decode_dispatches_chained": float(chained),
            "decode_dispatches_chained_queued": float(queued),
            "steps_total": 7.0}


def test_share_is_of_the_window_alone():
    # the ramp launched 100 blocks and chained 90; the window 240, of
    # which 168 ran ahead
    assert read({"w0": snap(100, 90), "w1": snap(340, 258, 160)}) \
        == pytest.approx(70.0)


@pytest.mark.parametrize("snaps", [
    {},                                                  # no snapshot
    {"w0": {"decode_dispatches": 10.0, "steps_total": 1.0},
     "w1": {"decode_dispatches": 250.0, "steps_total": 9.0}},  # the parent
    {"w0": snap(10, 4), "w1": snap(10, 4)},              # nothing launched
], ids=["no_snapshot", "no_counter", "no_dispatch"])
def test_none_when_there_is_nothing_to_read(snaps):
    assert read(snaps) is None


def test_an_engine_that_never_runs_ahead_reads_zero():
    assert read({"w0": snap(100, 0), "w1": snap(340, 0)}) == 0.0


def test_entry_agrees_with_the_reader():
    entry = [m for m in spec.load_benchmark()["per_layer"]
             if m["name"] == NAME][-1]
    mod = spec.load_module("layer_metrics", NAME)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["better"] == "higher" and entry["workloads"] == CELLS
    for cell in CELLS:
        assert NAME in {m.name for m in spec.load_cell(cell).per_layer}

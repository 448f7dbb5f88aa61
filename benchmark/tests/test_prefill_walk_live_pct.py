"""The reader of `prefill_walk_live_pct` on hand-made snapshots of
`engine.stats()`, on a program from before the counter, and against its
entry in BENCHMARK.json."""

import pytest

from benchmark.harness import spec

NAME = "prefill_walk_live_pct"


def read(snaps):
    return spec.load_module("layer_metrics", NAME).read({"snaps": snaps}, None)


def snap(pages, entries):
    return {"prefill_walk_pages_total": float(pages),
            "prefill_table_entries_total": float(entries),
            "steps_total": 7.0}


def test_share_is_of_the_window_alone():
    # tables of 128 entries. Before the window ten 4-row dispatches walked
    # 25 %; inside it six one-row 512-token chunks at start 0 walk
    # 4 + 8 + 12 + 16 = 40 pages each: 240 of 768 = 31.25 %.
    w0 = snap(0.25 * 10 * 4 * 128, 10 * 4 * 128)
    w1 = snap(w0["prefill_walk_pages_total"] + 6 * 40,
              w0["prefill_table_entries_total"] + 6 * 128)
    assert read({"w0": w0, "w1": w1}) == pytest.approx(31.25)


def test_a_late_chunk_may_pass_the_tables_size():
    # a 512-token chunk at start 3,072: tiles walk 100 + 104 + 108 + 112
    w1 = snap(424, 128)
    assert read({"w0": snap(0, 0), "w1": w1}) == pytest.approx(331.25)


@pytest.mark.parametrize("snaps", [
    {},                                                  # no snapshot
    {"w0": {"steps_total": 1.0}, "w1": {"steps_total": 9.0}},   # old program
    {"w0": snap(10, 4096), "w1": snap(10, 4096)},        # nothing dispatched
], ids=["no_snapshot", "no_counter", "no_dispatch"])
def test_none_when_there_is_nothing_to_read(snaps):
    assert read(snaps) is None


def test_counter_new_since_the_windows_start():
    """`w0` taken by a program without the counter reads as zero."""
    assert read({"w0": {"steps_total": 1.0},
                 "w1": snap(32, 128)}) == pytest.approx(25.0)


def test_entry_agrees_with_the_reader():
    entry = [m for m in spec.load_benchmark()["per_layer"]
             if m["name"] == NAME][-1]
    mod = spec.load_module("layer_metrics", NAME)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["better"] == "lower"
    assert entry["workloads"] == ["mistral7b-chat", "olmoe-chat-short"]
    for cell in entry["workloads"]:
        assert NAME in {m.name for m in spec.load_cell(cell).per_layer}

"""The reader of `paged_walk_live_pct` on hand-made snapshots of
`engine.stats()`, on a program from before the counter, and against its
entry in BENCHMARK.json."""

import pytest

from benchmark.harness import spec

NAME = "paged_walk_live_pct"


def read(snaps):
    return spec.load_module("layer_metrics", NAME).read({"snaps": snaps}, None)


def snap(pages, entries):
    return {"paged_walk_pages_total": float(pages),
            "paged_walk_entries_total": float(entries), "steps_total": 7.0}


def test_share_is_of_the_window_alone():
    # 32 rows x 128 entries: 4,096 a token. Before the window 100 tokens
    # walked 40 %, inside it 50 tokens walk 6,144 + 18,432 = 12 %.
    w0 = snap(0.4 * 100 * 4096, 100 * 4096)
    w1 = snap(w0["paged_walk_pages_total"] + 24576, 150 * 4096)
    assert read({"w0": w0, "w1": w1}) == pytest.approx(12.0)


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
                 "w1": snap(1024, 4096)}) == pytest.approx(25.0)


def test_entry_agrees_with_the_reader():
    entry = [m for m in spec.load_benchmark()["per_layer"]
             if m["name"] == NAME][-1]
    mod = spec.load_module("layer_metrics", NAME)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["workloads"] == ["mistral7b-chat", "mistral7b-rollout"]
    for cell in entry["workloads"]:
        assert NAME in {m.name for m in spec.load_cell(cell).per_layer}

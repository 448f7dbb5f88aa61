"""The six `setup_*` readers (layer "set-up", PR 36) against a hand-made
compile ledger and `records`; the six entries in every cell; a CPU
rehearsal that lists them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import spec
from benchmark.layer_metrics import _setup_ledger as sl

NAMES = ("setup_programs", "setup_trace_lower_s",
         "setup_cache_miss_programs", "setup_compile_s",
         "setup_cache_fetch_s", "setup_other_s")
W0 = 100.0
# (program, kind, stamp = the event's end, seconds, hit)
EVENTS = [
    ("convert_element_type", "trace", 60.0, 0.25, None),
    ("convert_element_type", "lower", 60.5, 0.5, None),
    ("convert_element_type", "fetch", 61.0, 0.125, True),
    ("_prefill_rows_paged", "trace", 70.0, 2.0, None),
    ("_prefill_rows_paged", "lower", 72.0, 1.5, None),
    ("_prefill_rows_paged", "compile", 80.0, 7.5, False),
    ("_decode_multi_paged", "trace", 81.0, 1.0, None),
    ("_decode_multi_paged", "lower", 82.0, 0.75, None),
    ("_decode_multi_paged", "fetch", 83.0, 0.5, True),
    # from the window's start on: none of the set-up's
    ("_prefill_rows_paged", "trace", 110.0, 2.0, None),
    ("_prefill_rows_paged", "lower", 111.0, 1.0, None),
    ("_prefill_rows_paged", "compile", 120.0, 8.0, False),
    ("below_best", "compile", 160.0, 3.0, False),
]


class _Ledger:
    events_dropped = 0

    def events(self):
        return list(EVENTS)


class _Spans:
    by_name = {"engine.step": [(105.0, 0.5), (108.0, 13.0)],
               "submit": [(107.0, 0.001)]}


class _Session:
    def __init__(self, d):
        self.dir = os.path.join(d, "trace")


def _records(tmp_path=None):
    r = {"window": (W0, 145.0), "e2e": {"setup_s": 48.0}, "spans": _Spans(),
         "snaps": {"w0": {"compiles_total": 3.0},
                   "w1": {"compiles_total": 4.0}, "t0": {}}}
    if tmp_path is not None:
        r["session"] = _Session(str(tmp_path))
    return r


def _read(records):
    return {n: spec.load_module("layer_metrics", n).read(records, None)
            for n in NAMES}


def test_events_from_the_window_on_are_left_out_and_the_parts_add_up(
        monkeypatch, tmp_path):
    monkeypatch.setattr(sl, "ledger", _Ledger)
    got = _read(_records(tmp_path))
    assert got == {"setup_programs": 3, "setup_trace_lower_s": 6.0,
                   "setup_cache_miss_programs": 1, "setup_compile_s": 7.5,
                   "setup_cache_fetch_s": 0.625, "setup_other_s": 33.875}
    assert got["setup_trace_lower_s"] + got["setup_compile_s"] \
        + got["setup_cache_fetch_s"] + got["setup_other_s"] == 48.0
    # the table beside last_trace1.json: what compiled late, and where
    with open(tmp_path / "compile_ledger.json") as f:
        table = json.load(f)
    assert table["split"] == got and table["setup_s"] == 48.0
    assert table["engine_compiles_total"] == {"w0": 3.0, "w1": 4.0}
    # the process started at 100 - 48 = 52 on this clock; the first trace
    # began at 60 - 0.25
    assert table["first_build_after_s"] == 7.75
    rows = {r["program"]: r for r in table["programs"]}
    assert table["programs"][0]["program"] == "_prefill_rows_paged"
    assert rows["_prefill_rows_paged"] == {
        "program": "_prefill_rows_paged", "builds": 2, "trace_s": 4.0,
        "lower_s": 2.5, "compile_s": 15.5, "fetch_s": 0.0, "hits": 0,
        "misses": 2, "before_window_s": 11.0, "from_window_s": 11.0}
    assert rows["_decode_multi_paged"]["hits"] == 1
    late = table["from_window_start"]
    assert [e["program"] for e in late] == ["_prefill_rows_paged"] * 3 \
        + ["below_best"]
    assert [e["in_window"] for e in late] == [True, True, True, False]
    assert late[2]["harness_spans"] == ["engine.step"]    # 108 <= 120 <= 121
    assert late[2]["end_s_from_window"] == 20.0
    assert late[3]["harness_spans"] == []


def test_a_window_that_opens_before_anything_is_built_reads_zero(
        monkeypatch):
    monkeypatch.setattr(sl, "ledger", _Ledger)
    records = dict(_records(), window=(10.0, 55.0))
    got = _read(records)
    assert got == dict.fromkeys(NAMES[:5], 0) | {"setup_other_s": 48.0}


@pytest.mark.parametrize("why", ["no_ledger", "ring_overwrote"])
def test_a_parent_without_the_ledger_gives_none_for_all_six(why,
                                                             monkeypatch):
    """The parent commit has no `ledger` to import; a ring that dropped
    records has lost the oldest, the set-up's: nothing is reported."""
    if why == "no_ledger":
        monkeypatch.setattr(sl, "ledger", None)
    else:
        monkeypatch.setattr(sl, "ledger", type(
            "Dropped", (_Ledger,), {"events_dropped": 1}))
    assert _read(_records()) == dict.fromkeys(NAMES)


def test_every_cell_loads_with_the_six_entries():
    bench = spec.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    entries = [m for m in bench["per_layer"] if m["layer"] == "set-up"]
    assert [m["name"] for m in entries] == list(NAMES)
    assert bench["per_layer"][-6:] == entries        # appended, in order
    for m in entries:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["source"] == "program_counter"
        assert m["unit"] == ("s" if m["name"].endswith("_s") else "count")
        assert m["workloads"] == cells
    for name in cells:
        assert set(NAMES) <= {m.name for m in spec.load_cell(name).per_layer}


def test_a_cpu_rehearsal_lists_the_six():
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral7b-rollout", "--rehearse", "--trace", "1"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "ok" and last["correct"]
    assert set(NAMES) <= set(last["metrics_seen"])
    out = os.path.join(spec.BENCH_DIR, "out", "mistral7b-rollout",
                       "compile_ledger.json")
    with open(out) as f:
        table = json.load(f)
    split = table["split"]
    assert split["setup_programs"] == split["setup_cache_miss_programs"] > 0
    assert split["setup_cache_fetch_s"] == 0        # no persistent cache
    assert {"_decode_multi_paged", "_prefill_rows_paged"} <= {
        r["program"] for r in table["programs"] if r["before_window_s"] > 0}
    assert not [e for e in table["from_window_start"] if e["in_window"]]

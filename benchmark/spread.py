#!/usr/bin/env python3
"""Spreads of repeated runs, as the bounds in BENCHMARK.json are set from.

    python3 benchmark/spread.py <set1 dir> <set2 dir>

Each directory holds one file per run, the run's stdout (its last line the
result object). For every metric: each set's median and spread ((Q3 - Q1) /
median with statistics.quantiles(n=4)), the wider spread, five times it
(the bound to set, never under 1 %), and how far the second set's median
lies from the first's.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.harness.stats import iqr_share  # noqa: E402


def read_set(path: str) -> dict:
    out: dict = {}
    for f in sorted(glob.glob(os.path.join(path, "*"))):
        lines = [l for l in open(f).read().splitlines() if l.startswith("{")]
        if not lines:
            continue
        last = json.loads(lines[-1])
        if "metrics" not in last:
            continue
        for name, m in last["metrics"].items():
            out.setdefault(name, []).append(m["value"])
        out.setdefault("_correct", []).append(bool(last["correct"]))
        out.setdefault("_failed", []).append(last["failed"])
    return out


def main(argv) -> int:
    sets = [read_set(p) for p in argv[1:]]
    for name in sorted(k for k in sets[0] if not k.startswith("_")):
        row = {"metric": name}
        spreads = []
        for i, s in enumerate(sets, 1):
            v = s.get(name, [])
            row[f"set{i}"] = v
            if len(v) >= 2:
                row[f"median{i}"] = statistics.median(v)
                spreads.append(iqr_share(v))
                row[f"spread{i}"] = spreads[-1]
        if spreads:
            row["bound_5x_widest"] = max(0.01, 5 * max(spreads))
        if len(sets) > 1 and "median2" in row:
            row["median2_vs_1"] = row["median2"] / row["median1"] - 1
        print(json.dumps(row))
    print(json.dumps({"correct": [s.get("_correct") for s in sets],
                      "failed": [s.get("_failed") for s in sets]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

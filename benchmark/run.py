#!/usr/bin/env python3
"""One cell, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the contract's JSON object; earlier lines are
JSON notes (phases, medians, sample counts, generator lateness). With
--trace 0 the metrics are the cell's end-to-end metrics, with --trace 1
its per-layer metrics. `--rehearse` runs the cell's tiny twin on the CPU
for control flow and prints no metric. See benchmark/README.md.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark.harness import common, spec
    from benchmark.harness.common import say

    cell = spec.load_cell(args.workload)
    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        raise SystemExit("benchmark: the system under test (ray_tpu/) is "
                         "not in this checkout")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1:
            os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
                f" --xla_force_host_platform_device_count={cell.chips}"
    seconds = args.seconds if args.seconds is not None else cell.run_seconds
    out_dir = os.path.join(HERE, "out", cell.name)
    shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)

    import jax

    from ray_tpu.util.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # keep every program, also the ones that compile in under a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    say(workload=cell.name, seed=args.seed, seconds=seconds,
        trace=args.trace, rehearse=args.rehearse,
        compile_cache=cache or os.environ.get("JAX_COMPILATION_CACHE_DIR"))

    result = cell.driver.run_cell(cell, args.seed, seconds, bool(args.trace),
                                  args.rehearse, out_dir, say)
    records = result["records"]
    reduced = None
    if args.trace:
        try:
            reduced = common.reduce_trace(records["session"],
                                          records["span_names"])
            say(busy_s_by_chip=reduced["busy_s_by_chip"],
                window_s=reduced["window_s"])
        except RuntimeError as e:
            if not args.rehearse:      # a CPU trace has no device plane
                raise
            say(rehearsal_trace=str(e))
    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            reader = spec.load_module("layer_metrics", m.name)
            try:
                value = reader.read(records, reduced)
            except KeyError as e:       # no peaks for a CPU: rehearsal only
                if not args.rehearse:
                    raise
                say(rehearsal_reader=m.name, error=str(e))
                continue
            if value is not None:
                metrics[m.name] = {"value": float(value), "unit": m.unit}
    else:
        for m in cell.end_to_end:
            if m.name in result["e2e"]:
                metrics[m.name] = {"value": float(result["e2e"][m.name]),
                                   "unit": m.unit}
    device = dict(result["device"],
                  memory_peak_bytes=result["memory_peak_bytes"])
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = reduced["breakdown"]
    with open(os.path.join(out_dir, f"last_trace{args.trace}.json"),
              "w") as f:
        json.dump({"args": vars(args), "result": line,
                   "e2e": result["e2e"]}, f, default=float)
    if args.rehearse:
        say(rehearsal="ok", correct=result["correct"],
            metrics_seen=sorted(metrics))
        return 0 if result["correct"] else 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

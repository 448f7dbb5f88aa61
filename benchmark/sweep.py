#!/usr/bin/env python3
"""Find the knee of an open-loop cell once: one engine, one warm-up, then
the cell's traffic at each of several fixed rates, draining in between.

    python3 benchmark/sweep.py --workload mistral7b-chat --rates 2,3,4,5,6 --seconds 25

Prints one JSON line per rate: requests waiting for a slot in each
quarter of the window (a queue that grows is a rate above the knee), the
tails and the completed tokens per second. Not part of a check: the rate
a cell runs at is a number in its workload file (README.md).
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import common, spec, stats
    from benchmark.harness.common import say
    from benchmark.harness.drivers import serve_engine as drv

    cell = spec.load_cell(args.workload)
    device = common.require_device(cell.chips, rehearse=False)

    import jax

    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    watch = common.CompileWatch()
    engine, _, cfg, _ = drv.build_engine(cell, args.seed, False, watch, say)
    say(device=device, warm_programs=watch.total)
    for rate in [float(r) for r in args.rates.split(",")]:
        tparams = dict(cell.traffic["traffic"], rate_rps=rate)
        gen = cell.generator.generate(tparams, args.seed, args.seconds,
                                      cfg.vocab_size)
        run = drv.drive(engine, gen, args.seconds, common.Spans(), watch,
                        None, {}, 20.0, say)
        v = drv.judge(run)
        ok = v["ok"]
        ttft = [(r.t_first - r.due) * 1e3 for r in ok]
        tpot = [(r.t_last - r.t_first) / (r.n_out - 1) * 1e3
                for r in ok if r.n_out > 1]
        say(rate_rps=rate, counted=len(v["counted"]), ok=len(ok),
            unfinished_at_cap=len(v["failed"]),
            waiting_by_quarter=drv.waiting_by_quarter(run["waiting"],
                                                      args.seconds),
            ttft_p50_ms=stats.percentile(ttft, 50)[0] if ttft else None,
            ttft_p95_ms=stats.percentile(ttft, 95)[0] if ttft else None,
            tpot_p50_ms=stats.percentile(tpot, 50)[0] if tpot else None,
            tpot_p95_ms=stats.percentile(tpot, 95)[0] if tpot else None,
            out_tokens_per_s=run["out_tokens"] / args.seconds,
            compiles_in_window=watch.in_window,
            preemptions=run["snaps"]["w1"]["preemptions"])
        drv._run_dry(engine)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""`sweep.py` for a cell of any serving driver that exports `build_engine`,
`drive` and `judge` (the cell's own driver is used, as `spec` loads it):
one engine, one warm-up, then the cell's traffic at each of several fixed
rates, draining in between.

    python3 benchmark/sweep_model.py --workload olmoe-chat-short --rates 3,4,5,6,7 --seconds 25

One JSON line a rate: requests waiting for a slot in each quarter of the
window (a queue that grows is a rate above the knee), the tails, the
completed tokens per second and the pool's peak. Not part of a check.
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
    from benchmark.harness.drivers.serve_engine import (_run_dry,
                                                        waiting_by_quarter)

    cell = spec.load_cell(args.workload)
    drv = cell.driver
    device = common.require_device(cell.chips, rehearse=False)

    import jax

    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    watch = common.CompileWatch()
    engine, _, cfg, _ = drv.build_engine(cell, args.seed, False, watch, say)
    say(device=device, warm_programs=watch.total,
        setup_s=common.seconds_since_process_start(),
        memory_peak_bytes=common.memory_peak_bytes())
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
            waiting_by_quarter=waiting_by_quarter(run["waiting"],
                                                  args.seconds),
            ttft_p50_ms=stats.percentile(ttft, 50)[0] if ttft else None,
            ttft_p95_ms=stats.percentile(ttft, 95)[0] if ttft else None,
            tpot_p50_ms=stats.percentile(tpot, 50)[0] if tpot else None,
            tpot_p95_ms=stats.percentile(tpot, 95)[0] if tpot else None,
            out_tokens_per_s=run["out_tokens"] / args.seconds,
            kv_peak=run["kv_peak"], compiles_in_window=watch.in_window,
            preemptions=run["snaps"]["w1"]["preemptions"],
            memory_peak_bytes=common.memory_peak_bytes())
        _run_dry(engine)
    return 0


if __name__ == "__main__":
    sys.exit(main())

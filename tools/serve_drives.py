"""ONE engine, K drives of a serving cell's traffic in one process, with a
dump of every request and every `engine.step()` (PERF.md PR 30, third
session: a p95 over 72 requests that spreads from run to run is two or
three requests that flip; this shows which, and where the step's time
went).

    python tools/serve_drives.py --workload mistral7b-chat --seed N \
        --drives K --out DIR [--rehearse]          # from a tree's root
    python tools/serve_drives.py --read DIR        # what flipped, and why

A drive is the benchmark's own `drive()` (ramp, window, the cell's
schedule; the seed draws token ids only), so K drives are K samples of
the timing noise at a quarter of the chip time of K runs: the weights
and the warm-up are paid once. Each drive writes `drive_<seed>.json`:
requests (due, submitted, first and last token, relative to the window's
start) and steps (start, end, tokens returned, prefill dispatches,
blocks in flight after, rows mid-prefill, live rows, queue, CPU seconds
of the thread, and the parts of the step: flush, admission, prefill,
dispatch, run-ahead, the wait on the device, the replay, any garbage
collection). `--read` prints per drive the largest mean gaps and every
step that took 55 ms more than the median step of its kind, then the
requests whose mean gap moves most between drives. On the chip run it
through `chiprun` with `--out chiprun_out/<name>`; to compare two trees,
run it from each tree's root in one call.
"""
import argparse
import gc
import glob
import json
import os
import statistics
import sys
import time

PARTS = ("_flush_pipeline", "_admit_rows_paged", "_advance_prefills",
         "_dispatch_primary", "_top_up_pipeline", "_device_wait",
         "_emit_block")


def read(out: str) -> None:
    gaps = {}
    for path in sorted(glob.glob(os.path.join(out, "drive_*.json"))):
        with open(path) as f:
            d = json.load(f)
        reqs = [r for r in d["reqs"] if r["counted"] and r["n_out"] > 1]
        for r in reqs:
            r["gap"] = (r["t_last"] - r["t_first"]) / (r["n_out"] - 1) * 1e3
            gaps.setdefault(r["idx"], []).append(round(r["gap"], 2))
        reqs.sort(key=lambda r: -r["gap"])
        print(os.path.basename(path), "largest mean gaps (request, tokens "
              "out, ms):", [(r["idx"], r["olen"], round(r["gap"], 2))
                            for r in reqs[:8]])
        kinds = {}
        for s in d["steps"]:
            kinds.setdefault((s[3] > 0, s[5] > 0), []).append(s[1] - s[0])
        for s in d["steps"]:
            over = s[1] - s[0] - statistics.median(
                kinds[(s[3] > 0, s[5] > 0)])
            if over > 0.055:
                print(f"  step at {s[0]:.2f} s: {(s[1] - s[0]) * 1e3:.0f} ms"
                      f" ({over * 1e3:.0f} over its kind's median), "
                      f"{s[2]} tokens, {s[3]} prefill dispatches, cpu "
                      f"{s[8] * 1e3:.0f} ms; parts (ms):",
                      [(n, round(dt * 1e3, 1)) for n, _, dt in s[9]])
    print("requests whose mean gap moves most between drives:")
    for idx, v in sorted(gaps.items(),
                         key=lambda kv: min(kv[1]) - max(kv[1]))[:12]:
        print(" ", idx, v)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--read")
    ap.add_argument("--workload", default="mistral7b-chat")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drives", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--out")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.read:
        return read(args.read)
    if not args.out:
        ap.error("--out is required")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.getcwd())

    import jax

    from benchmark.harness import common, spec, stats
    from benchmark.harness.common import now, say
    from ray_tpu.util.compile_cache import enable_compile_cache

    cell = spec.load_cell(args.workload)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    drv = cell.driver
    os.makedirs(args.out, exist_ok=True)
    common.require_device(cell.chips, args.rehearse)
    watch = common.CompileWatch()
    tparams = dict(cell.traffic["traffic"])
    seconds = args.seconds
    if args.rehearse:
        tparams.update(cell.traffic["rehearsal"]["traffic"])
        seconds = cell.traffic["rehearsal"]["seconds"]
    engine, _, cfg, _ = drv.build_engine(cell, args.seed, args.rehearse,
                                         watch, say)
    steps, parts = [], []

    def timed(name):
        f = getattr(engine, name)

        def g(*a, **k):
            t = now()
            try:
                return f(*a, **k)
            finally:
                parts.append((name, t, now() - t))
        setattr(engine, name, g)

    for name in PARTS:
        timed(name)
    gc_t0 = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = now()
        else:
            parts.append((f"gc{info['generation']}", gc_t0[0],
                          now() - gc_t0[0]))

    gc.callbacks.append(on_gc)
    plain_step = engine.step

    def step(*a, **k):
        del parts[:]
        c0, t0, pd0 = time.thread_time(), now(), engine.prefill_dispatches
        out = plain_step(*a, **k)
        steps.append((t0, now(), sum(len(v) for v in out.values()),
                      engine.prefill_dispatches - pd0, len(engine._ring),
                      len(engine._row_prefill),
                      sum(r is not None for r in engine.row_req),
                      len(engine.scheduler), time.thread_time() - c0,
                      [(n, t - t0, dt) for n, t, dt in parts]))
        return out

    engine.step = step
    for i in range(args.drives):
        del steps[:]
        gen = cell.generator.generate(tparams, args.seed + i, seconds,
                                      cfg.vocab_size)
        gc.collect()
        gc.freeze()
        run = drv.drive(engine, gen, seconds, common.Spans(), watch, None,
                        {"trace_s": 3.0},
                        float(cell.traffic.get("finish_cap_s", 60)), say)
        verdict = drv.judge(run)
        ok, w0 = verdict["ok"], run["w0"]
        tpot = [(r.t_last - r.t_first) / (r.n_out - 1) * 1e3
                for r in ok if r.n_out > 1]
        ttft = [(r.t_first - r.due) * 1e3 for r in ok]
        say(drive=i, seed=args.seed + i, ok=len(ok),
            failed=len(verdict["failed"]),
            tpot_p95_ms=stats.percentile(tpot, 95)[0],
            ttft_p95_ms=stats.percentile(ttft, 95)[0],
            compiles_in_window=watch.in_window)

        def rel(t):
            return None if t is None else t - w0

        with open(os.path.join(args.out, f"drive_{args.seed + i}.json"),
                  "w") as f:
            json.dump({
                "reqs": [dict(idx=j, plen=len(r.prompt), olen=r.max_new,
                              counted=r.counted, due=rel(r.due),
                              t_submit=rel(r.t_submit),
                              t_first=rel(r.t_first), t_last=rel(r.t_last),
                              n_out=r.n_out)
                         for j, r in enumerate(run["reqs"])],
                "steps": [(a - w0, b - w0, *rest) for a, b, *rest in steps],
            }, f)
        while engine.pending():     # what the tail left in flight
            plain_step()
        for rid in list(engine.finished):
            engine.pop_result(rid)


if __name__ == "__main__":
    main()

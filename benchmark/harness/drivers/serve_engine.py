"""Driver `serve_engine`: one `DecodeEngine` on one chip under request
traffic, open or closed loop, timed on the benchmark's own clock.

One process, one thread: the loop submits what is due, calls
`engine.step()`, and stamps every token a step returns with the time the
step returned. The program sees prompts and `max_new_tokens`, nothing of
the schedule.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.harness import common, stats
from benchmark.harness.model import llama_config
from benchmark.harness.common import now

SPAN_NAMES = ["engine.step", "submit", "idle_no_request"]


class _Req:
    __slots__ = ("prompt", "max_new", "due", "counted", "rid", "t_submit",
                 "t_first", "t_last", "n_out", "tokens", "done", "error")

    def __init__(self, prompt, max_new, due=None, counted=False):
        self.prompt, self.max_new = prompt, max_new
        self.due, self.counted = due, counted
        self.rid = None
        self.t_submit = self.t_first = self.t_last = None
        self.n_out = 0
        self.tokens: Optional[List[int]] = None
        self.done = False
        self.error: Optional[str] = None


# -- warm-up -----------------------------------------------------------------

def _run_dry(engine) -> None:
    while engine.pending():
        engine.step()
    for rid in list(engine.finished):
        engine.pop_result(rid)


def warm_up(engine, opts: Dict[str, Any], groups: List[int], vocab: int
            ) -> int:
    """Run every program shape this configuration's traffic can reach,
    through submit/step alone. The shape set is the engine's own
    bucketing: a prefill program per (power-of-two group size, power-of-two
    chunk length <= prefill_chunk) and a decode program per power-of-two
    horizon <= decode_horizon. A group larger than max_prefills_per_step
    forms when earlier admissions are still mid-prompt, so it is built
    from waves whose prompts are one chunk longer each. Returns the number
    of warm-up requests."""
    chunk = int(opts["prefill_chunk"])
    mpps = int(opts["max_prefills_per_step"])
    horizon = int(opts.get("decode_horizon", 8))
    rng = np.random.default_rng(0)
    n = 0

    def submit(length, max_new=1):
        nonlocal n
        engine.submit(rng.integers(1, vocab, size=length).tolist(),
                      max_new_tokens=max_new)
        n += 1

    buckets = [1 << i for i in range(chunk.bit_length()) if 1 << i <= chunk]
    for group in groups:
        waves = max(1, group // mpps)
        for c in buckets:
            if (waves - 1) * chunk + c + 1 > engine.max_len:
                continue
            for w in range(waves):
                for _ in range(min(group, mpps)):
                    submit((waves - 1 - w) * chunk + c)
            _run_dry(engine)
    # decode horizons 8, 4, 2, 1 (a budget of 2H - 1), alone and with
    # every slot full, so both the first dispatch and the run-ahead one
    # of each horizon have run
    for rows in (1, engine.B):
        for _ in range(rows):
            submit(8, max_new=2 * horizon - 1)
        _run_dry(engine)
    return n


# -- the measured loop -------------------------------------------------------

def drive(engine, sched: dict, seconds: float, spans: common.Spans,
          watch: common.CompileWatch, session, topts: Dict[str, Any],
          cap_s: float, say) -> dict:
    reqs = [_Req(r["prompt"], r["max_new_tokens"], r.get("due_s"),
                 r.get("counted", False)) for r in sched["requests"]]
    closed = sched["kind"] == "closed"
    by_rid: Dict[int, _Req] = {}
    T0 = now()
    setup_s = common.seconds_since_process_start() + sched["ramp_s"]
    w0 = T0 + sched["ramp_s"]
    w1 = w0 + seconds
    if not closed:
        for r in reqs:
            r.due += w0
    trace_t0 = w1 - float(topts.get("trace_lead_s", 0)) \
        - float(topts["trace_s"]) if session else None
    nxt = 0
    in_flight = 0
    out_tokens = 0
    kv_peak = 0.0
    kv_tokens_traced = 0.0
    snaps: Dict[str, dict] = {}
    n_counted = sum(r.counted for r in reqs)
    counted_done = 0
    waiting: List[tuple] = []         # (s into the window, requests queued)
    next_tick = w0

    def submit(r: _Req) -> None:
        nonlocal in_flight
        with spans.span("submit"):
            try:
                r.rid = engine.submit(r.prompt.tolist(),
                                      max_new_tokens=r.max_new)
                by_rid[r.rid] = r
                in_flight += 1
            except Exception as e:      # rejected: counts as failed
                r.error = f"{type(e).__name__}: {e}"
                r.done = True
        r.t_submit = now()

    while True:
        t = now()
        if closed:
            while in_flight < sched["clients"] and nxt < len(reqs):
                submit(reqs[nxt])
                nxt += 1
        else:
            while nxt < len(reqs) and reqs[nxt].due <= t:
                submit(reqs[nxt])
                nxt += 1
        if "w0" not in snaps and t >= w0:
            snaps["w0"] = engine.stats()
            watch.armed = True
        if session is not None:
            if session.t_begin is None and t >= trace_t0:
                snaps["t0"] = engine.stats()
                session.start()
            elif session.active and t >= session.t_begin + topts["trace_s"]:
                snaps["t1"] = engine.stats()
                session.stop()
                say(trace_stop_s=now() - session.t_end)
        if w0 <= next_tick <= t < w1:
            waiting.append((t - w0, in_flight - int(
                engine.stats()["live_slots"])))
            next_tick += 0.5
        if t >= w1:
            if "w1" not in snaps:
                snaps["w1"] = engine.stats()
            if closed or counted_done >= n_counted or t >= w1 + cap_s:
                break
        if engine.pending():
            with spans.span("engine.step"):
                emitted = engine.step()
            t2 = now()
            traced = session is not None and session.active
            for rid, toks in emitted.items():
                r = by_rid[rid]
                k = len(toks)
                if not k:
                    continue
                if traced:
                    kv_tokens_traced += k * (len(r.prompt) + r.n_out + 1) \
                        + k * (k - 1) / 2
                if r.t_first is None:
                    r.t_first = t2
                r.t_last = t2
                r.n_out += k
                if w0 <= t2 < w1:
                    out_tokens += k
            if w0 <= t2 < w1:
                kv_peak = max(kv_peak, engine.kv_used_fraction())
            for rid in list(engine.finished):
                r = by_rid.pop(rid)
                shed = rid in engine.shed_ids
                r.tokens = engine.pop_result(rid)
                r.done = True
                if shed:
                    r.error = "shed"
                in_flight -= 1
                if r.counted:
                    counted_done += 1
        else:
            with spans.span("idle_no_request"):
                wait = reqs[nxt].due - now() \
                    if not closed and nxt < len(reqs) else 0.001
                time.sleep(max(0.0, min(wait, 0.005)))
    watch.armed = False
    if session is not None and session.active:       # cap hit mid-trace
        session.stop()
    return {"reqs": reqs, "w0": w0, "w1": w1, "out_tokens": out_tokens,
            "kv_peak": kv_peak, "kv_tokens_traced": kv_tokens_traced,
            "snaps": snaps, "closed": closed, "setup_s": setup_s,
            "waiting": waiting}


def waiting_by_quarter(waiting: List[tuple], seconds: float) -> List[float]:
    """Mean number of requests submitted and not yet in a slot, in each
    quarter of the window: a queue that grows through the window is a rate
    above the knee."""
    out = []
    for q in range(4):
        v = [n for t, n in waiting
             if q * seconds / 4 <= t < (q + 1) * seconds / 4]
        out.append(sum(v) / len(v) if v else 0.0)
    return out


def judge(run: dict) -> dict:
    """Counted requests, failures, and the per-request times."""
    w0, w1 = run["w0"], run["w1"]
    if run["closed"]:
        counted = [r for r in run["reqs"] if r.error or (
            r.done and r.t_last is not None and w0 <= r.t_last < w1)]
    else:
        counted = [r for r in run["reqs"] if r.counted]
    ok, failed = [], []
    for r in counted:
        if r.error or not r.done or r.tokens is None \
                or len(r.tokens) != r.max_new or r.n_out != r.max_new:
            failed.append(r)
        else:
            ok.append(r)
    return {"counted": counted, "ok": ok, "failed": failed}


def check_logits(params, model, ok: List[_Req], ccfg: Dict[str, Any],
                 seed: int, say) -> dict:
    """Teacher-forced greedy margin of a seeded sample against the plain
    float32 reference (benchmark/reference/llama_dense.py)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import llama_dense

    rng = np.random.default_rng([seed, 0xC0FFEE])
    fit = [r for r in ok
           if len(r.prompt) + r.max_new <= ccfg["reference_max_tokens"]]
    pick = [fit[i] for i in rng.permutation(len(fit))[:ccfg["sample"]]]
    pad_to = int(ccfg["pad_to"])

    below_best = jax.jit(lambda p, seq: llama_dense.below_best(p, seq, model))

    worst = 0.0
    for r in pick:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        n = len(seq)
        padded = np.zeros((-(-n // pad_to) * pad_to + 1,), np.int32)
        padded[:n] = seq          # causal: padding after n changes nothing
        m = np.asarray(below_best(params, jnp.asarray(padded)))
        m = m[len(r.prompt) - 1:n - 1]
        worst = max(worst, float(m.max()))
        say(reference_margin_max=float(m.max()),
            reference_margin_mean=float(m.mean()),
            prompt=len(r.prompt), generated=len(r.tokens))
    return {"sampled": len(pick), "margin_max": worst,
            "pass": len(pick) > 0 and worst <= ccfg["margin_tol"]}


def build_engine(cell, seed: int, rehearse: bool,
                 watch: common.CompileWatch, say):
    """Weights from the seed on the device, the engine as the
    configuration sets it, and every program shape warmed up. Returns
    (engine, params, LlamaConfig, model keys as run)."""
    import jax

    from ray_tpu.models import llama_init
    from ray_tpu.models.engine import DecodeEngine

    model = dict(cell.config)
    opts = dict(cell.config["engine"])
    if rehearse:
        model.update(cell.config["rehearsal"]["model"])
        opts.update(cell.config["rehearsal"]["engine"])
    warm_groups = opts.pop("warm_groups")
    cfg = llama_config(model, opts["max_len"],
                       activation_dtype=model["torch_dtype"],
                       param_dtype=model["torch_dtype"], remat=False)
    t = now()
    params = jax.jit(llama_init, static_argnums=1)(common.seed_key(seed), cfg)
    jax.block_until_ready(params)
    say(phase="weights", seconds=now() - t)
    engine = DecodeEngine(params, cfg, **opts)
    t = now()
    n_warm = warm_up(engine, opts, warm_groups, cfg.vocab_size)
    say(phase="warm_up", seconds=now() - t, requests=n_warm,
        programs=watch.total, compile_s=watch.seconds)
    return engine, params, cfg, model


def run_cell(cell, seed: int, seconds: float, trace: bool, rehearse: bool,
             out_dir: str, say) -> dict:
    device = common.require_device(cell.chips, rehearse)
    watch = common.CompileWatch()
    tparams = dict(cell.traffic["traffic"])
    ccfg = dict(cell.config["correct"])
    if rehearse:
        ccfg.update(cell.config["rehearsal"].get("correct", {}))
        tparams.update(cell.traffic["rehearsal"]["traffic"])
        seconds = cell.traffic["rehearsal"]["seconds"]
    engine, params, cfg, model = build_engine(cell, seed, rehearse, watch,
                                              say)
    gen = cell.generator.generate(tparams, seed, seconds, cfg.vocab_size)
    spans = common.Spans()
    session = common.ProfilerSession(out_dir + "/trace") if trace else None
    topts = cell.traffic.get("trace", {"trace_s": 3.0})
    if rehearse:
        topts = dict(topts, trace_s=min(topts["trace_s"], seconds / 2),
                     trace_lead_s=0)
    gc.collect()
    gc.freeze()
    run = drive(engine, gen, seconds, spans, watch, session, topts,
                float(cell.traffic.get("finish_cap_s", 60)), say)
    stats_end = engine.stats()
    verdict = judge(run)
    ok = verdict["ok"]
    w0, w1 = run["w0"], run["w1"]

    ttft = [(r.t_first - (r.t_submit if run["closed"] else r.due)) * 1e3
            for r in ok]
    tpot = [(r.t_last - r.t_first) / (r.n_out - 1) * 1e3
            for r in ok if r.n_out > 1]
    late = [(r.t_submit - r.due) * 1e3 for r in verdict["counted"]
            if r.due is not None and r.t_submit is not None]
    e2e: Dict[str, float] = {"setup_s": run["setup_s"]}
    if ttft:
        e2e["ttft_p95_ms"] = stats.percentile(ttft, 95)[0]
        say(ttft_p50_ms=stats.percentile(ttft, 50)[0],
            ttft_p95_ms=e2e["ttft_p95_ms"],
            ttft_p90_ms=stats.percentile(ttft, 90)[0],
            ttft_mean_ms=sum(ttft) / len(ttft), ttft_samples=len(ttft))
    if tpot:
        e2e["tpot_p95_ms"] = stats.percentile(tpot, 95)[0]
        say(tpot_p50_ms=stats.percentile(tpot, 50)[0],
            tpot_p95_ms=e2e["tpot_p95_ms"],
            tpot_mean_ms=sum(tpot) / len(tpot), tpot_samples=len(tpot))
    e2e["out_tokens_per_s"] = run["out_tokens"] / (w1 - w0)
    if late:
        say(generator_late_p50_ms=stats.percentile(late, 50)[0],
            generator_late_p95_ms=stats.percentile(late, 95)[0],
            generator_late_max_ms=max(late))
    say(waiting_by_quarter=waiting_by_quarter(run["waiting"], seconds))
    steps = spans.durations("engine.step", w0, w1)
    if steps:       # a far-off run shows here: all steps slow, or a few
        say(step_wall_p50_ms=stats.percentile(steps, 50)[0] * 1e3,
            step_wall_p95_ms=stats.percentile(steps, 95)[0] * 1e3,
            step_wall_max_ms=max(steps) * 1e3, steps=len(steps))
    say(counted=len(verdict["counted"]), ok=len(ok),
        failed=len(verdict["failed"]), out_tokens=run["out_tokens"],
        compiles_in_window=watch.in_window,
        queue_depth_end=stats_end.get("queue_depth"),
        preemptions=stats_end.get("preemptions"),
        errors=sorted({r.error for r in verdict["failed"] if r.error})[:3])

    mem_peak = common.memory_peak_bytes()
    del engine
    gc.collect()
    logit_check = check_logits(params, model, ok, ccfg, seed, say)
    correct = bool(logit_check["pass"] and not verdict["failed"]
                   and watch.in_window == 0 and len(ok) > 0)
    say(correct=correct, logit_check=logit_check)

    records = {
        "model": model, "device": device, "e2e": e2e, "spans": spans,
        "window": (w0, w1), "stats_end": stats_end, "snaps": run["snaps"],
        "kv_peak": run["kv_peak"],
        "kv_tokens_traced": run["kv_tokens_traced"],
        "session": session, "span_names": SPAN_NAMES,
    }
    return {"correct": correct, "attempted": len(verdict["counted"]),
            "failed": len(verdict["failed"]), "e2e": e2e,
            "records": records, "device": device,
            "memory_peak_bytes": mem_peak}

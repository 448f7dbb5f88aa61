"""Driver `serve_model`: one `DecodeEngine` on one chip under request
traffic, like `serve_engine`, for a configuration whose family
`serve_engine` cannot build: the program's config, its initialiser and the
plain reference are chosen by the configuration's `model_type`.

The measured loop, the warm-up and the verdict on requests ARE
`serve_engine`'s (`drive`, `warm_up`, `judge`, `waiting_by_quarter`), and
the records handed to the per-layer readers have the same keys, so every
serving reader works in a cell of this driver unchanged. What is this
file's own: `build_engine` (by family) and `check_logits` (the sparse
family's comparison knows that routing is discontinuous).

`build_engine`, `drive` and `judge` are exported for a rate sweep.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import common, stats
from benchmark.harness.common import now
from benchmark.harness.drivers.serve_engine import (   # noqa: F401
    SPAN_NAMES, drive, judge, waiting_by_quarter, warm_up)


def _olmoe(model: Dict[str, Any], max_len: int):
    import jax.numpy as jnp

    from benchmark.reference import olmoe_sparse
    from ray_tpu.models import MoeConfig, moe_init

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        model["torch_dtype"]]
    if model["hidden_size"] != model["num_attention_heads"] \
            * model["assumed"]["head_dim"]:
        raise ValueError("head_dim is not hidden_size / heads")
    cfg = MoeConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        ffn_dim=model["intermediate_size"],
        n_experts=model["num_experts"], top_k=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]), qk_norm=True,
        max_seq_len=max_len, rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=dt, param_dtype=dt,
        remat=False)
    return cfg, moe_init, olmoe_sparse


# model_type -> (published keys, max_len) -> (program config, initialiser,
# reference module). The dense families are `serve_engine`'s.
FAMILIES = {"olmoe": _olmoe}


def program_config(model: Dict[str, Any], max_len: int):
    """The configuration file's published keys as the program's config,
    its initialiser and its plain reference, by `model_type`."""
    family = FAMILIES.get(model.get("model_type"))
    if family is None:
        raise ValueError(
            f"driver serve_model builds {sorted(FAMILIES)}, not model_type "
            f"{model.get('model_type')!r} (dense families: serve_engine)")
    return family(model, max_len)


def build_engine(cell, seed: int, rehearse: bool,
                 watch: common.CompileWatch, say):
    """Weights from the seed on the device in one jitted program, the
    engine as the configuration sets it, and every program shape warmed
    up. Returns (engine, params, program config, model keys as run)."""
    import jax

    from ray_tpu.models.engine import DecodeEngine

    model = dict(cell.config)
    opts = dict(cell.config["engine"])
    if rehearse:
        model.update(cell.config["rehearsal"]["model"])
        opts.update(cell.config["rehearsal"]["engine"])
    warm_groups = opts.pop("warm_groups")
    cfg, init, _ = program_config(model, opts["max_len"])
    t = now()
    params = jax.jit(init, static_argnums=1)(common.seed_key(seed), cfg)
    jax.block_until_ready(params)
    say(phase="weights", seconds=now() - t)
    engine = DecodeEngine(params, cfg, **opts)
    t = now()
    n_warm = warm_up(engine, opts, warm_groups, cfg.vocab_size)
    say(phase="warm_up", seconds=now() - t, requests=n_warm,
        programs=watch.total, compile_s=watch.seconds)
    return engine, params, cfg, model


def margin_verdict(margins: List[np.ndarray], gaps: List[np.ndarray],
                   ccfg: Dict[str, Any]) -> dict:
    """The comparison that decides `correct`, on the teacher-forced margins
    of the sampled requests (one array a request, one entry a generated
    token) and the smallest router gap over the layers at each of those
    positions (`gaps`).

    A sparse model routes, and routing is discontinuous: where the 8th
    and 9th router probabilities lie closer than the program's precision
    moves them, it may take another 8th expert than the float32 reference
    and be right. So its margins are held to three limits (the configuration's
    `correct.derivation` says where each comes from): the mean over all
    positions <= `margin_mean_tol` (tight: an expert lost at EVERY
    position raises it severalfold, a flip here and there does not),
    the largest <= `margin_cap` (loose: what a few flips at one position
    can do, and far below what a wrong mask, norm or weight does), and
    the mean over the positions whose gap is at least `gap_clear` in every
    layer, where no flip is expected at all, <= `margin_clear_mean_tol`,
    judged when there are at least `min_clear` such positions."""
    if not margins:
        return {"sampled": 0, "pass": False}
    m = np.concatenate(margins)
    out = {"sampled": len(margins), "positions": int(m.size),
           "margin_max": float(m.max()), "margin_mean": float(m.mean())}
    g = np.concatenate(gaps)
    clear = g >= ccfg["gap_clear"]
    out.update(clear_positions=int(clear.sum()),
               gap_median=float(np.median(g)),
               margin_clear_mean=float(m[clear].mean()) if clear.any()
               else 0.0,
               margin_clear_max=float(m[clear].max()) if clear.any()
               else 0.0)
    out["pass"] = bool(
        out["margin_mean"] <= ccfg["margin_mean_tol"]
        and out["margin_max"] <= ccfg["margin_cap"]
        and (out["clear_positions"] < ccfg["min_clear"]
             or out["margin_clear_mean"] <= ccfg["margin_clear_mean_tol"]))
    return out


def check_logits(params, model, ok: List[Any], ccfg: Dict[str, Any],
                 seed: int, say) -> dict:
    """Teacher-forced greedy margins of a seeded sample of the finished
    requests against the family's plain float32 reference, judged by
    `margin_verdict`."""
    import jax
    import jax.numpy as jnp

    ref = program_config(model, ccfg["reference_max_tokens"])[2]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    fit = [r for r in ok
           if len(r.prompt) + r.max_new <= ccfg["reference_max_tokens"]]
    pick = [fit[i] for i in rng.permutation(len(fit))[:ccfg["sample"]]]
    pad_to = int(ccfg["pad_to"])
    score = jax.jit(lambda p, seq: ref.below_best_and_gaps(p, seq, model))

    margins, gaps = [], []
    for r in pick:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        n = len(seq)
        padded = np.zeros((-(-n // pad_to) * pad_to + 1,), np.int32)
        padded[:n] = seq          # causal: padding after n changes nothing
        m, g = score(params, jnp.asarray(padded))
        m = np.asarray(m)[len(r.prompt) - 1:n - 1]
        g = np.asarray(g)[len(r.prompt) - 1:n - 1]
        margins.append(m)
        gaps.append(g)
        say(reference_margin_max=float(m.max()),
            reference_margin_mean=float(m.mean()),
            router_gap_min=float(g.min()),
            router_gap_median=float(np.median(g)),
            prompt=len(r.prompt), generated=len(r.tokens))
    return margin_verdict(margins, gaps, ccfg)


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
    if steps:
        say(step_wall_p50_ms=stats.percentile(steps, 50)[0] * 1e3,
            step_wall_p95_ms=stats.percentile(steps, 95)[0] * 1e3,
            step_wall_max_ms=max(steps) * 1e3, steps=len(steps))
    say(counted=len(verdict["counted"]), ok=len(ok),
        failed=len(verdict["failed"]), out_tokens=run["out_tokens"],
        out_tokens_per_s=e2e["out_tokens_per_s"],
        compiles_in_window=watch.in_window, kv_peak=run["kv_peak"],
        queue_depth_end=stats_end.get("queue_depth"),
        preemptions=stats_end.get("preemptions"),
        errors=sorted({r.error for r in verdict["failed"] if r.error})[:3])
    snaps = run["snaps"]
    counted_rows = all(
        s.get("moe_rows_computed_total", 0.0)
        >= s.get("moe_assignments_total", 0.0)
        for s in list(snaps.values()) + [stats_end])
    say(moe={k: v for k, v in stats_end.items() if k.startswith("moe_")},
        rows_cover_assignments=counted_rows)

    mem_peak = common.memory_peak_bytes()
    del engine
    gc.collect()
    logit_check = check_logits(params, model, ok, ccfg, seed, say)
    correct = bool(logit_check["pass"] and not verdict["failed"]
                   and watch.in_window == 0 and len(ok) > 0
                   and counted_rows)
    say(correct=correct, logit_check=logit_check)

    records = {
        "model": model, "device": device, "e2e": e2e, "spans": spans,
        "window": (w0, w1), "stats_end": stats_end, "snaps": snaps,
        "kv_peak": run["kv_peak"],
        "kv_tokens_traced": run["kv_tokens_traced"],
        "session": session, "span_names": SPAN_NAMES,
    }
    return {"correct": correct, "attempted": len(verdict["counted"]),
            "failed": len(verdict["failed"]), "e2e": e2e,
            "records": records, "device": device,
            "memory_peak_bytes": mem_peak}

"""Driver `serve_hybrid`: one `DecodeEngine` on one chip under request
traffic, like `serve_engine` and `serve_model`, for a configuration whose
stack mixes layer kinds (`model_type` phi4flash: state-space, window,
full and shared-cache layers): the program's `HybridConfig`, its
initialiser and the plain reference are built here.

The measured loop, the warm-up and the verdict on requests ARE
`serve_engine`'s (`drive`, `warm_up`, `judge`, `waiting_by_quarter`), and
the records handed to the per-layer readers have the same keys, so every
serving reader works in a cell of this driver unchanged. What is this
file's own: `build_engine` (the config, and a warm-up of the prefill
programs this family has one more kind of: a chunk that is not a prompt's
last stops after the layers that see every token) and `check_logits`
(which requests are scored, and the two limits their margins are held to).

`build_engine`, `drive` and `judge` are exported for a sweep.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import common, stats
from benchmark.harness.common import now
from benchmark.harness.drivers.serve_engine import (   # noqa: F401
    SPAN_NAMES, _run_dry, drive, judge, waiting_by_quarter, warm_up)


def program_config(model: Dict[str, Any], max_len: int):
    """The configuration file's published keys (and its `assumed` ones) as
    the program's config, its initialiser and its plain reference."""
    import jax.numpy as jnp

    from benchmark.reference import phi4flash_hybrid
    try:
        from ray_tpu.models import HybridConfig, hybrid_init
    except ImportError:
        raise SystemExit("benchmark: this checkout's ray_tpu.models has no "
                         "HybridConfig: it cannot run a phi4flash "
                         "configuration")

    if model.get("model_type") != "phi4flash":
        raise ValueError(f"driver serve_hybrid builds model_type phi4flash, "
                         f"not {model.get('model_type')!r}")
    a = model["assumed"]
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        model["torch_dtype"]]
    if model["hidden_size"] != model["num_attention_heads"] * a["head_dim"]:
        raise ValueError("head_dim is not hidden_size / heads")
    cfg = HybridConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        ffn_dim=model["intermediate_size"],
        mb_per_layer=model["mb_per_layer"],
        sliding_window=model["sliding_window"],
        norm_eps=float(model["layer_norm_eps"]),
        d_state=a["mamba_d_state"], d_conv=a["mamba_d_conv"],
        expand=a["mamba_expand"], dt_rank=a["mamba_dt_rank"],
        max_seq_len=max_len, dtype=dt, param_dtype=dt)
    return cfg, hybrid_init, phi4flash_hybrid


def build_engine(cell, seed: int, rehearse: bool,
                 watch: common.CompileWatch, say):
    """Weights from the seed on the device in one jitted program, the
    engine as the configuration sets it, and every program shape warmed
    up. Returns (engine, params, program config, model keys as run)."""
    import jax

    model = dict(cell.config)
    opts = dict(cell.config["engine"])
    if rehearse:
        model.update(cell.config["rehearsal"]["model"])
        opts.update(cell.config["rehearsal"]["engine"])
    warm_groups = opts.pop("warm_groups")
    cfg, init, _ = program_config(model, opts["max_len"])
    from ray_tpu.models.engine import DecodeEngine
    # The seed's key as an `rbg` key: XLA's own bit generator, one op a
    # tensor where threefry is unrolled into each of the ~90 draws (23 s
    # of compile for this model's initialiser against 10, and the 3.85 B
    # draws themselves several times faster). Deterministic in the seed
    # on one kind of chip, which is what a run's inputs need.
    t = now()
    key4 = jax.numpy.tile(jax.random.key_data(common.seed_key(seed)), 2)
    make = jax.jit(lambda kd: init(
        jax.random.wrap_key_data(kd, impl="rbg"), cfg)).lower(key4).compile()
    t_run = now()
    params = make(key4)
    jax.block_until_ready(params)
    say(phase="weights", seconds=now() - t, compile_s=t_run - t)
    engine = DecodeEngine(params, cfg, **opts)
    t = now()
    n_warm = warm_up(engine, opts, warm_groups, cfg.vocab_size)
    # `warm_up` reaches a group's chunks through prompts of one chunk at
    # most while the group fits `max_prefills_per_step`: every chunk it
    # runs is a prompt's last. This family's other prefill program, the
    # chunk that is NOT the last, only comes at the full chunk length:
    # one more wave a group, of prompts one token longer than a chunk.
    chunk = int(opts["prefill_chunk"])
    rng = np.random.default_rng(1)
    for group in warm_groups:
        if group > int(opts["max_prefills_per_step"]) \
                or chunk + 2 > engine.max_len:
            continue
        for _ in range(group):
            engine.submit(rng.integers(1, cfg.vocab_size,
                                       size=chunk + 1).tolist(),
                          max_new_tokens=1)
            n_warm += 1
        _run_dry(engine)
    say(phase="warm_up", seconds=now() - t, requests=n_warm,
        programs=watch.total, compile_s=watch.seconds)
    return engine, params, cfg, model


def margin_verdict(margins: List[np.ndarray], ccfg: Dict[str, Any]) -> dict:
    """The comparison that decides `correct`, on the teacher-forced margins
    of the sampled requests (one array a request, one entry a generated
    token): the mean over all positions <= `margin_mean_tol` (what a lower
    precision or a slightly wrong state raises at every position) and the
    largest <= `margin_cap` (what a wrong mask, page or state does at one).
    The configuration's `correct.derivation` says where each comes from."""
    if not margins:
        return {"sampled": 0, "pass": False}
    m = np.concatenate(margins)
    out = {"sampled": len(margins), "positions": int(m.size),
           "margin_max": float(m.max()), "margin_mean": float(m.mean())}
    out["pass"] = bool(out["margin_mean"] <= ccfg["margin_mean_tol"]
                       and out["margin_max"] <= ccfg["margin_cap"])
    return out


def pick_sample(ok: List[Any], ccfg: Dict[str, Any], seed: int) -> List[Any]:
    """`sample` finished requests that fit the reference, seeded; at least
    `long_share` of them longer than `long_tokens` in all where the run
    has that many (rows past the window, over several chunks)."""
    rng = np.random.default_rng([seed, 0xC0FFEE])
    fit = [r for r in ok
           if len(r.prompt) + r.max_new <= ccfg["reference_max_tokens"]]
    fit = [fit[i] for i in rng.permutation(len(fit))]
    long = [r for r in fit
            if len(r.prompt) + r.max_new > ccfg["long_tokens"]]
    pick = long[:ccfg["long_share"]]
    taken = {id(r) for r in pick}
    pick += [r for r in fit
             if id(r) not in taken][:ccfg["sample"] - len(pick)]
    return pick


def check_logits(params, model, ok: List[Any], ccfg: Dict[str, Any],
                 seed: int, say) -> dict:
    """Teacher-forced greedy margins of a seeded sample of the finished
    requests against the plain float32 reference, judged by
    `margin_verdict`."""
    import jax
    import jax.numpy as jnp

    ref = program_config(model, ccfg["reference_max_tokens"])[2]
    pad_to = int(ccfg["pad_to"])
    score = jax.jit(lambda p, seq: ref.below_best(p, seq, model))
    margins = []
    for r in pick_sample(ok, ccfg, seed):
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        n = len(seq)
        padded = np.zeros((-(-n // pad_to) * pad_to + 1,), np.int32)
        padded[:n] = seq          # causal: padding after n changes nothing
        m = np.asarray(score(params, jnp.asarray(padded)))
        m = m[len(r.prompt) - 1:n - 1]
        margins.append(m)
        say(reference_margin_max=float(m.max()),
            reference_margin_mean=float(m.mean()),
            prompt=len(r.prompt), generated=len(r.tokens))
    return margin_verdict(margins, ccfg)


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
    e2e: Dict[str, float] = {"setup_s": run["setup_s"]}
    if ttft:
        e2e["ttft_p95_ms"] = stats.percentile(ttft, 95)[0]
        say(ttft_p50_ms=stats.percentile(ttft, 50)[0],
            ttft_p95_ms=e2e["ttft_p95_ms"], ttft_samples=len(ttft))
    if tpot:
        e2e["tpot_p95_ms"] = stats.percentile(tpot, 95)[0]
        say(tpot_p50_ms=stats.percentile(tpot, 50)[0],
            tpot_p95_ms=e2e["tpot_p95_ms"],
            tpot_mean_ms=sum(tpot) / len(tpot), tpot_samples=len(tpot))
    e2e["out_tokens_per_s"] = run["out_tokens"] / (w1 - w0)
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
    hybrid_keys = ("kv_walk_tokens_window_total", "kv_walk_tokens_full_total",
                   "window_blocks_freed_total", "window_pool_peak_blocks",
                   "window_pool_blocks_total", "ssm_state_resets_total",
                   "ssm_row_steps_total", "prefill_layer_tokens_total",
                   "prefill_layer_tokens_skipped_total")
    say(hybrid={k: stats_end.get(k) for k in hybrid_keys},
        longest_row=max((len(r.prompt) + r.n_out for r in ok), default=0))

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

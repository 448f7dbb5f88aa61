"""Driver `serve_mla`: one `DecodeEngine` on one chip under request
traffic, like `serve_engine` and `serve_hybrid`, for a configuration with
latent attention, a sparse-attention indexer and held experts
(`model_type` deepseek_v32): the program's `MlaConfig`, its initialiser
and the plain reference are built here.

The measured loop, the warm-up and the verdict on requests ARE
`serve_engine`'s (`drive`, `warm_up`, `judge`, `waiting_by_quarter`), and
the records handed to the per-layer readers have the same keys, so every
serving reader works in a cell of this driver unchanged. What is this
file's own: `program_config` (the published keys as the program's config,
the chip's share of experts and vocabulary), `pick_sample`
(`serve_hybrid`'s seeded requests and the longest one the reference
fits), `margin_verdict` (the mean and the 99th percentile, where
`serve_hybrid` caps the maximum) and `check_logits` (the float32 reference
a layer at a time from the host, and `select_overlap`: the share of the
slots the program's indexer chose that the reference's chose too).
`harness/controls_mla.py` puts wrong programs behind this driver.

`build_engine`, `drive` and `judge` are exported for a sweep.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import common, stats
from benchmark.harness.common import now
from benchmark.harness.drivers.serve_engine import (   # noqa: F401
    SPAN_NAMES, drive, judge, waiting_by_quarter, warm_up)
from benchmark.harness.drivers import serve_hybrid


def program_config(model: Dict[str, Any], max_len: int):
    """The configuration file's published keys as the program's config,
    its initialiser and its plain reference."""
    import jax.numpy as jnp

    from benchmark.reference import deepseek_v32_sparse
    try:
        from ray_tpu.models import MlaConfig, mla_init
    except ImportError:
        raise SystemExit("benchmark: this checkout's ray_tpu.models has no "
                         "MlaConfig: it cannot run a deepseek_v32 "
                         "configuration")

    if model.get("model_type") != "deepseek_v32":
        raise ValueError(f"driver serve_mla builds model_type deepseek_v32, "
                         f"not {model.get('model_type')!r}")
    if model["num_nextn_predict_layers"] or model["n_shared_experts"] != 1 \
            or model["scoring_func"] != "sigmoid" \
            or model["topk_method"] != "noaux_tc" \
            or model["hidden_act"] != "silu" or model["attention_bias"] \
            or model["tie_word_embeddings"] or model["moe_layer_freq"] != 1:
        raise ValueError("driver serve_mla: a key of the configuration "
                         "names a mechanism the program does not build")
    rs = model["rope_scaling"]
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        model["torch_dtype"]]
    held = model.get("held_experts")
    cfg = MlaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_dense_layers=model["first_k_dense_replace"],
        n_heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], ffn_dim=model["intermediate_size"],
        expert_dim=model["moe_intermediate_size"],
        n_experts=model["n_routed_experts"],
        n_shared_experts=model["n_shared_experts"],
        top_k=model["num_experts_per_tok"], n_group=model["n_group"],
        topk_group=model["topk_group"],
        norm_topk_prob=model["norm_topk_prob"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        held_experts=None if held is None else tuple(held),
        index_n_heads=model["index_n_heads"],
        index_head_dim=model["index_head_dim"],
        index_topk=model["index_topk"],
        norm_eps=float(model["rms_norm_eps"]),
        rope_theta=float(model["rope_theta"]),
        rope_scaling=(float(rs["factor"]),
                      int(rs["original_max_position_embeddings"]),
                      float(rs["beta_fast"]), float(rs["beta_slow"]),
                      float(rs["mscale"]), float(rs["mscale_all_dim"])),
        max_seq_len=max_len, dtype=dt, param_dtype=dt)
    return cfg, mla_init, deepseek_v32_sparse


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
    # an `rbg` key, as serve_hybrid: XLA's own bit generator, one op a
    # tensor, deterministic in the seed on one kind of chip
    t = now()
    key4 = jax.numpy.tile(jax.random.key_data(common.seed_key(seed)), 2)
    make = jax.jit(lambda kd: init(
        jax.random.wrap_key_data(kd, impl="rbg"), cfg)).lower(key4).compile()
    t_run = now()
    params = make(key4)
    jax.block_until_ready(params)
    say(phase="weights", seconds=now() - t, compile_s=t_run - t,
        parameters=cfg.num_params())
    engine = DecodeEngine(params, cfg, **opts)
    t = now()
    n_warm = warm_up(engine, opts, warm_groups, cfg.vocab_size)
    say(phase="warm_up", seconds=now() - t, requests=n_warm,
        programs=watch.total, compile_s=watch.seconds)
    return engine, params, cfg, model


def program_selection(params, cfg, seq) -> np.ndarray:
    """What the PROGRAM's indexer chose for every query of ``seq``, as a
    mask [layers, n, n] bool: the program's own stack, run teacher-forced
    over the sequence through a private pool."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mla

    n = len(seq)
    pad = -n % 1024          # whole tiles for the chunk's kernel
    toks = np.zeros((1, n + pad), np.int32)
    toks[0, :n] = seq        # causal: what follows n changes nothing
    cache = mla.init_cache(cfg, 1, n + pad)
    run = jax.jit(lambda p, toks, c, i, bt: mla.layers_paged(
        p, toks, c, i, bt, jnp.zeros((1,), jnp.int32), cfg,
        want_selection=True)[4])
    return np.asarray(run(params, jnp.asarray(toks), cache["c"],
                          cache["i"], cache["bt"]))[:, 0, :n, :n]


def select_overlap(chosen: np.ndarray, ref_masks, first: int) -> float:
    """The share of the slots the program chose (`program_selection`) for
    the queries past ``first`` = `index_topk` (where selection prunes)
    that the reference's float32 indexer chose too."""
    if chosen.shape[1] <= first:
        return float("nan")
    picked = chosen[:, first:]
    both = picked & np.asarray(ref_masks)[:, first:]
    return float(both.sum() / max(picked.sum(), 1))


def pick_sample(ok: List[Any], ccfg: Dict[str, Any], seed: int) -> List[Any]:
    """`serve_hybrid.pick_sample`'s seeded requests, all but one, and the
    LONGEST finished request that fits `far_max_tokens`: a context where
    selection prunes three quarters and more, the decode kernel walks
    dozens of pages and a prompt is many query blocks."""
    pick = serve_hybrid.pick_sample(
        ok, dict(ccfg, sample=ccfg["sample"] - 1), seed)
    far = [r for r in ok if r not in pick
           and len(r.prompt) + r.max_new <= ccfg["far_max_tokens"]]
    if far:
        pick.append(max(far, key=lambda r: len(r.prompt) + r.max_new))
    return pick


def margin_verdict(margins: List[np.ndarray], ccfg: Dict[str, Any]) -> dict:
    """The comparison that decides `correct`, on the teacher-forced margins
    of the sampled requests (one array a request, one entry a generated
    token): the mean over all positions <= `margin_mean_tol` and their
    99th percentile <= `margin_p99_cap`. Not the largest, which
    `serve_hybrid.margin_verdict` caps: selection is discrete, a swapped
    slot moves a whole softmax, and the maximum over a thousand positions
    of the RIGHT program has a long tail (reported, not judged). The
    configuration's `correct.derivation` says where each limit comes
    from."""
    if not margins:
        return {"sampled": 0, "pass": False}
    m = np.concatenate(margins)
    out = {"sampled": len(margins), "positions": int(m.size),
           "margin_max": float(m.max()), "margin_mean": float(m.mean()),
           "margin_p99": float(np.percentile(m, 99))}
    out["pass"] = bool(out["margin_mean"] <= ccfg["margin_mean_tol"]
                       and out["margin_p99"] <= ccfg["margin_p99_cap"])
    return out


def check_logits(params, model, cfg, ok: List[Any], ccfg: Dict[str, Any],
                 seed: int, say) -> dict:
    """Teacher-forced greedy margins of a sample of the finished requests
    (`pick_sample`) against the plain float32 reference, judged by
    `margin_verdict`; beside them `select_overlap` of the first sampled
    request. CONSUMES ``params``: once the program has said what it chose
    for that request, the layers' weights move to the host and the
    reference is handed a layer at a time, so that the bf16 weights (8.6
    GiB) do not sit beside a float32 layer and 10 k tokens of its
    activations (6.8 GiB, compiled for a described v5e)."""
    import jax

    ref = program_config(model, ccfg["far_max_tokens"])[2]
    held = cfg.held_experts
    sample = pick_sample(ok, ccfg, seed)
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
            for r in sample]
    chosen = program_selection(params, cfg, seqs[0][:-1]) if sample else None
    stacks = {k: params[k] for k in ("dense", "moe") if k in params}
    params = {**params, **jax.device_get(stacks)}
    jax.tree_util.tree_map(lambda x: x.delete(), stacks)
    margins, overlap = [], float("nan")
    for k, (r, seq) in enumerate(zip(sample, seqs)):
        note = {}
        if k == 0:    # the selection masks [layers, n, n] of this one only
            h, masks = ref.hidden(params, seq[:-1], model, held,
                                  want_selection=True)
            overlap = select_overlap(chosen, masks, cfg.index_topk)
            note["select_overlap"] = overlap
            del masks
        else:
            h = ref.hidden(params, seq[:-1], model, held)
        m = np.asarray(ref.head_margin(params, h, seq[1:]))
        m = m[len(r.prompt) - 1:]
        margins.append(m)
        del h
        say(reference_margin_max=float(m.max()),
            reference_margin_mean=float(m.mean()),
            reference_margin_p99=float(np.percentile(m, 99)),
            prompt=len(r.prompt), generated=len(r.tokens), **note)
    out = margin_verdict(margins, ccfg)
    if overlap == overlap:
        out["select_overlap"] = overlap
    return out


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

    tpot = [(r.t_last - r.t_first) / (r.n_out - 1) * 1e3
            for r in ok if r.n_out > 1]
    e2e: Dict[str, float] = {"setup_s": run["setup_s"]}
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
    in_window = {k: stats_end.get(k, 0) - run["snaps"]["w0"].get(k, 0)
                 for k in ("prefill_real_tokens", "tokens_out")} \
        if "w0" in run["snaps"] else {}
    say(counted=len(verdict["counted"]), ok=len(ok),
        failed=len(verdict["failed"]), out_tokens=run["out_tokens"],
        out_tokens_per_s=e2e["out_tokens_per_s"],
        prompt_tokens_per_s=in_window.get("prefill_real_tokens", 0)
        / (w1 - w0),
        compiles_in_window=watch.in_window, kv_peak=run["kv_peak"],
        queue_depth_end=stats_end.get("queue_depth"),
        preemptions=stats_end.get("preemptions"),
        errors=sorted({r.error for r in verdict["failed"] if r.error})[:3])
    mla_keys = ("indexer_tokens_scored_total",
                "indexer_tokens_selected_total", "moe_assignments_total",
                "moe_assignments_landed_total", "moe_rows_computed_total",
                "moe_decode_experts_hit_total",
                "moe_decode_layer_steps_total", "kv_bytes_per_token")
    say(mla={k: stats_end.get(k) for k in mla_keys},
        longest_row=max((len(r.prompt) + r.n_out for r in ok), default=0))

    mem_peak = common.memory_peak_bytes()
    del engine
    gc.collect()
    logit_check = check_logits(params, model, cfg, ok, ccfg, seed, say)
    del params          # its layers' device arrays are gone
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

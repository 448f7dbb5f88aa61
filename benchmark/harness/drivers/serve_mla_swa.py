"""Driver `serve_mla_swa`: one `DecodeEngine` on one chip under request
traffic, like `serve_mla`, for a configuration whose latent attention has
TWO geometries in one stack (`model_type` dots3_note: selected full layers
beside window layers of another head count and latent width, a head-wise
gate, rescaled latents, held experts): the program's `MlaConfig` with
`layer_types`, its initialiser and the plain reference are built here.

The measured loop, the warm-up and the verdict on requests ARE
`serve_engine`'s (`drive`, `warm_up`, `judge`, `waiting_by_quarter`), the
margins' verdict and `select_overlap` `serve_mla`'s, and the records
handed to the per-layer readers have the same keys, so every serving
reader works in a cell of this driver unchanged. What is this file's own:
`program_config` (the published keys as the program's config),
`pick_sample` (one finished request UNDER `short_tokens` in all, where
selection keeps every slot and the window alone prunes, beside
`serve_mla`'s seeded long ones and the longest the reference fits),
`program_selection` (the full layers' choice with the window plane
beside), `verdict` (three parts: every request against the reference as
it is, the short request alone, and a long request against the reference
handed the program's own selection) and `check_logits`. `harness/controls_mla_swa.py` puts wrong
programs behind this driver.

`build_engine`, `drive` and `judge` are exported for a sweep.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import common, stats
from benchmark.harness.common import now
from benchmark.harness.drivers import serve_mla
from benchmark.harness.drivers.serve_engine import (   # noqa: F401
    SPAN_NAMES, drive, judge, waiting_by_quarter, warm_up)
from benchmark.harness.drivers.serve_mla import (   # noqa: F401
    margin_verdict, select_overlap)

_KINDS = {"full_attention": "full", "sliding_attention": "window"}


def program_config(model: Dict[str, Any], max_len: int):
    """The configuration file's published keys as the program's config,
    its initialiser and its plain reference."""
    import jax.numpy as jnp

    from benchmark.reference import dots3_note
    try:
        from ray_tpu.models import MlaConfig, mla_init
        if "layer_types" not in MlaConfig.__dataclass_fields__:
            raise ImportError
    except ImportError:
        raise SystemExit("benchmark: this checkout's MlaConfig has no "
                         "window layers: it cannot run a dots3_note "
                         "configuration")

    if model.get("model_type") != "dots3_note":
        raise ValueError(f"driver serve_mla_swa builds model_type "
                         f"dots3_note, not {model.get('model_type')!r}")
    if model["n_shared_experts"] != 1 or model["scoring_func"] != "sigmoid" \
            or model["topk_method"] != "noaux_tc" \
            or model["hidden_act"] != "silu" or model["attention_bias"] \
            or model["tie_word_embeddings"] or model["moe_layer_freq"] != 1 \
            or model["rope_scaling"] \
            or model["attention_gate_type"] != "headwise" \
            or model["swa_attention_gate_type"] != "headwise" \
            or model["num_key_value_heads"] != model["num_attention_heads"] \
            or model["swa_num_key_value_heads"] \
            != model["swa_num_attention_heads"] \
            or len(model["layer_types"]) != model["num_hidden_layers"]:
        raise ValueError("driver serve_mla_swa: a key of the configuration "
                         "names a mechanism the program does not build")
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
        top_k=model["num_experts_per_tok"], n_group=1, topk_group=1,
        norm_topk_prob=model["norm_topk_prob"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        held_experts=None if held is None else tuple(held),
        index_n_heads=model["index_n_heads"],
        index_head_dim=model["index_head_dim"],
        index_topk=model["index_topk"],
        norm_eps=float(model["rms_norm_eps"]),
        rope_theta=float(model["rope_theta"]), rope_scaling=None,
        layer_types=tuple(_KINDS[k] for k in model["layer_types"]),
        sliding_window=model["sliding_window_size"],
        swa_n_heads=model["swa_num_attention_heads"],
        swa_q_lora_rank=model["swa_q_lora_rank"],
        swa_kv_lora_rank=model["swa_kv_lora_rank"],
        swa_qk_nope_head_dim=model["swa_qk_nope_head_dim"],
        swa_qk_rope_head_dim=model["swa_qk_rope_head_dim"],
        swa_v_head_dim=model["swa_v_head_dim"],
        swa_rope_theta=float(model["swa_rope_theta"]),
        attn_gate=True, lora_rescale=bool(model["apply_mla_qkv_lora_rescale"]),
        max_seq_len=max_len, dtype=dt, param_dtype=dt)
    return cfg, mla_init, dots3_note


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


def program_selection(params, cfg, seq, padded: int) -> np.ndarray:
    """What the PROGRAM's indexers chose for every query of ``seq``, as a
    mask [full layers, n, n] bool: the program's own stack, run
    teacher-forced over the sequence, filled up to ``padded`` tokens,
    through a private pool."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mla

    n = len(seq)
    toks = np.zeros((1, padded), np.int32)   # ONE length: one program
    toks[0, :n] = seq        # causal: what follows n changes nothing
    cache = mla.init_cache(cfg, 1, padded)
    run = jax.jit(lambda p, toks, c, i, w, bt: mla.layers_paged(
        p, toks, c, i, bt, jnp.zeros((1,), jnp.int32), cfg,
        state={"wlatent": w}, bt_w=bt, want_selection=True)[4])
    return np.asarray(run(params, jnp.asarray(toks), cache["c"], cache["i"],
                          cache["w"], cache["bt"]))[:, 0, :n, :n]


def pick_sample(ok: List[Any], ccfg: Dict[str, Any], seed: int) -> List[Any]:
    """`sample` finished requests: a seeded one UNDER `short_tokens` in
    all (prompt + answer: selection keeps every slot, the window alone
    prunes), `serve_mla.pick_sample`'s seeded ones past `long_tokens` (both
    prune) and the LONGEST one that fits `far_max_tokens`."""
    rng = np.random.default_rng([seed, 0x5407])
    short = [r for r in ok
             if len(r.prompt) + r.max_new < ccfg["short_tokens"]]
    pick = [short[int(rng.integers(len(short)))]] if short else []
    rest = [r for r in ok if not any(r is p for p in pick)]
    return pick + serve_mla.pick_sample(
        rest, dict(ccfg, sample=ccfg["sample"] - len(pick)), seed)


def margin_stats(m: np.ndarray) -> dict:
    return {"positions": int(m.size), "margin_max": float(m.max()),
            "margin_mean": float(m.mean()),
            "margin_p99": float(np.percentile(m, 99))}


def verdict(margins: List[np.ndarray], short, given, overlap: float,
            ccfg: Dict[str, Any]) -> dict:
    """The comparison that decides `correct` (the configuration's
    `correct.derivation` says where each limit comes from):

    - `serve_mla.margin_verdict` on every sampled request's margins
      against the reference AS IT IS (mean and 99th percentile of all
      positions): loose, because the few slots a bf16 indexer ranks
      otherwise than the float32 one move a peaked softmax;
    - ``short``, the margins of the request under `short_tokens` (no
      selection to differ on): their mean <= `short_mean_tol`;
    - ``given``, the watched long request's margins against the reference
      handed the PROGRAM's selection: mean <= `given_mean_tol`, and the
      share of that selection the reference's own indexer made too,
      ``overlap``, >= `select_overlap_min`.

    A part that has no request to judge (no short one finished) is left
    out and does not fail the run."""
    out = margin_verdict(margins, ccfg)
    if short is not None:
        out["short"] = dict(margin_stats(short), **{"pass": bool(
            short.mean() <= ccfg["short_mean_tol"])})
    if given is not None:
        out["given"] = dict(margin_stats(given), **{"pass": bool(
            given.mean() <= ccfg["given_mean_tol"]
            and overlap >= ccfg["select_overlap_min"])})
    if overlap == overlap:
        out["select_overlap"] = overlap
    out["pass"] = bool(out["pass"] and all(
        out[k]["pass"] for k in ("short", "given") if k in out))
    return out


def check_logits(params, model, cfg, ok: List[Any], ccfg: Dict[str, Any],
                 seed: int, say) -> dict:
    """Teacher-forced greedy margins of a sample of the finished requests
    (`pick_sample`) against the plain float32 reference, judged by
    `verdict`. The first request past `long_tokens` is WATCHED: the
    program says what its indexers chose for it (`program_selection`),
    `select_overlap` compares that with the reference's choice, and the
    reference scores it a second time with the program's selection in
    place of its own. CONSUMES ``params``: once the program has said what
    it chose, the layers' weights move to the host and the reference is
    handed a layer at a time."""
    import jax

    ref = program_config(model, ccfg["far_max_tokens"])[2]
    held = cfg.held_experts
    sample = pick_sample(ok, ccfg, seed)
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
            for r in sample]
    watched = next((k for k, s in enumerate(seqs)
                    if ccfg["long_tokens"] < len(s)
                    <= ccfg["reference_max_tokens"]), None)
    chosen = None if watched is None else program_selection(
        params, cfg, seqs[watched][:-1], ccfg["reference_max_tokens"])
    stacks = {k: params[k] for k in ("dense", "moe") if k in params}
    params = {**params, **jax.device_get(stacks)}
    jax.tree_util.tree_map(lambda x: x.delete(), stacks)
    margins, short, given, overlap = [], None, None, float("nan")

    # a sequence is filled up to one of THREE lengths (causal: what follows
    # its end changes nothing), so that the reference's layer programs are
    # compiled for those and found in the cache by every later run
    lengths = sorted(ccfg[k] for k in ("short_tokens", "reference_max_tokens",
                                       "far_max_tokens"))

    def filled(seq):
        n = len(seq) - 1
        out = np.zeros((next(t for t in lengths if t >= n),), np.int32)
        out[:n] = seq[:-1]
        return out, n

    def margin_of(h, r, seq):
        m = np.asarray(ref.head_margin(params, h[:len(seq) - 1], seq[1:]))
        return m[len(r.prompt) - 1:]

    for k, (r, seq) in enumerate(zip(sample, seqs)):
        note = {}
        toks, n = filled(seq)
        if k == watched:    # the selection masks [full layers, n, n]
            h, masks = ref.hidden(params, toks, model, held,
                                  want_selection=True)
            overlap = select_overlap(chosen, np.asarray(masks)[:, :n, :n],
                                     cfg.index_topk)
            del masks
            # the program's choice, the filler attending itself alone
            mine = np.broadcast_to(np.eye(len(toks), dtype=bool),
                                   (len(chosen), len(toks), len(toks))).copy()
            mine[:, :n, :n] = chosen
            given = margin_of(ref.hidden(params, toks, model, held,
                                         selection_given=mine), r, seq)
            del mine
            note = {"select_overlap": overlap,
                    "given_margin_mean": float(given.mean()),
                    "given_margin_p99": float(np.percentile(given, 99))}
        else:
            h = ref.hidden(params, toks, model, held)
        m = margin_of(h, r, seq)
        margins.append(m)
        if len(seq) < ccfg["short_tokens"] and short is None:
            short = m
        del h
        say(reference_margin_max=float(m.max()),
            reference_margin_mean=float(m.mean()),
            reference_margin_p99=float(np.percentile(m, 99)),
            prompt=len(r.prompt), generated=len(r.tokens), **note)
    return verdict(margins, short, given, overlap, ccfg)


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
        # where the longest step began: a run that held ONE step for
        # seconds (PERF.md PR 52) says when, for whoever looks for why
        at = max((d, s) for s, d in spans.by_name["engine.step"]
                 if w0 <= s < w1)[1] - w0
        say(step_wall_p50_ms=stats.percentile(steps, 50)[0] * 1e3,
            step_wall_p95_ms=stats.percentile(steps, 95)[0] * 1e3,
            step_wall_max_ms=max(steps) * 1e3, step_wall_max_at_s=at,
            steps_over_1s=sum(d > 1.0 for d in steps), steps=len(steps))
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
    # every assignment that LANDED on a held expert has a computed row
    # behind it (the routed ones that landed elsewhere are nobody's here)
    rows_cover = all(
        s.get("moe_rows_computed_total", 0.0)
        >= s.get("moe_assignments_landed_total", 0.0)
        for s in list(run["snaps"].values()) + [stats_end])
    keys = ("indexer_tokens_scored_total", "indexer_tokens_selected_total",
            "swa_window_rows_total", "swa_window_slots_total",
            "window_pool_peak_blocks", "window_pool_blocks_total",
            "window_blocks_freed_total", "moe_assignments_total",
            "moe_assignments_landed_total", "moe_rows_computed_total",
            "moe_decode_experts_hit_total", "moe_decode_layer_steps_total",
            "kv_bytes_per_token")
    say(mla_swa={k: stats_end.get(k) for k in keys},
        rows_cover_assignments=rows_cover,
        longest_row=max((len(r.prompt) + r.n_out for r in ok), default=0))

    mem_peak = common.memory_peak_bytes()
    del engine
    gc.collect()
    logit_check = check_logits(params, model, cfg, ok, ccfg, seed, say)
    del params          # its layers' device arrays are gone
    correct = bool(logit_check["pass"] and not verdict["failed"]
                   and watch.in_window == 0 and len(ok) > 0 and rows_cover)
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

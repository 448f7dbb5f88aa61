"""Driver `serve_gdn`: one `DecodeEngine` on one chip under request
traffic, like `serve_engine`, `serve_hybrid` and `serve_mla`, for a
configuration of gated delta-rule layers beside gated attention with held
experts in every layer (`model_type` qwen3_next): the program's
`GdnConfig`, its initialiser and the plain reference are built here.

The measured loop, the warm-up and the verdict on requests ARE
`serve_engine`'s (`drive`, `warm_up`, `judge`, `waiting_by_quarter`), and
the records handed to the per-layer readers have the same keys, so every
serving reader works in a cell of this driver unchanged. `pick_sample` and
`margin_verdict` are `serve_mla`'s (seeded requests past `long_tokens` and
the longest one the reference fits; the mean and the 99th percentile).
What is this file's own: `program_config` (the published keys as the
program's config, the chip's share of experts and vocabulary),
`admission_probes` (a few short requests through the warmed engine before
the schedule and again into the slots the window's requests leave, judged
beside the sample: what shows a slot's state at admission) and
`check_logits` (the float32 reference, which upcasts a layer and inside it
an expert at a time, over sequences padded to a few lengths).
`harness/controls_gdn.py` puts wrong programs behind this driver.

`build_engine`, `drive` and `judge` are exported for a sweep.
"""

from __future__ import annotations

import gc
from types import SimpleNamespace
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import common, stats
from benchmark.harness.common import now
from benchmark.harness.drivers.serve_engine import (   # noqa: F401
    SPAN_NAMES, drive, judge, waiting_by_quarter, warm_up)
from benchmark.harness.drivers.serve_mla import (   # noqa: F401
    margin_verdict, pick_sample)


def program_config(model: Dict[str, Any], max_len: int):
    """The configuration file's published keys as the program's config,
    its initialiser and its plain reference."""
    import jax.numpy as jnp

    from benchmark.reference import qwen3_next
    try:
        from ray_tpu.models import GdnConfig, gdn_init
    except ImportError:
        raise SystemExit("benchmark: this checkout's ray_tpu.models has no "
                         "GdnConfig: it cannot run a qwen3_next "
                         "configuration")

    if model.get("model_type") != "qwen3_next":
        raise ValueError(f"driver serve_gdn builds model_type qwen3_next, "
                         f"not {model.get('model_type')!r}")
    if model["decoder_sparse_step"] != 1 or model["mlp_only_layers"] \
            or model["hidden_act"] != "silu" or model["rope_scaling"] \
            or model["tie_word_embeddings"] or model["use_sliding_window"]:
        raise ValueError("driver serve_gdn: a key of the configuration "
                         "names a mechanism the program does not build")
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        model["torch_dtype"]]
    held = model.get("held_experts")
    cfg = GdnConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        full_attention_interval=model["full_attention_interval"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        partial_rotary_factor=float(model["partial_rotary_factor"]),
        rope_theta=float(model["rope_theta"]),
        key_heads=model["linear_num_key_heads"],
        value_heads=model["linear_num_value_heads"],
        key_head_dim=model["linear_key_head_dim"],
        value_head_dim=model["linear_value_head_dim"],
        conv_kernel=model["linear_conv_kernel_dim"],
        n_experts=model["num_experts"], top_k=model["num_experts_per_tok"],
        expert_dim=model["moe_intermediate_size"],
        shared_expert_dim=model["shared_expert_intermediate_size"],
        norm_topk_prob=model["norm_topk_prob"],
        held_experts=None if held is None else tuple(held),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=max_len, dtype=dt, param_dtype=dt)
    return cfg, gdn_init, qwen3_next


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
        model["engine"] = opts
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


def admission_probes(engine, ccfg: Dict[str, Any], vocab: int,
                     seed: int, after_window: bool = False) -> List[Any]:
    """Requests of `correct.probes` ``[prompt tokens, new tokens]``, put
    through the engine TWICE: warmed, before the schedule starts (set-up, a
    few steps), into slots whose last tenants the warm-up left; and again
    ``after_window``, into the slots the TIMED requests leave, with the
    other rows still decoding around them (the closed loop's queue is
    dropped first: what waits behind the window is nobody's, and a probe
    then takes the first slot a finishing row frees). What a judge of
    generated tokens needs to see a slot's state at ADMISSION: behind a
    prompt of 2,048 tokens and more nothing of it is left (each token's
    delta rule erases along its key: 2.5e-3 of a stale state's amplitude
    after 2,048 tokens at beta 0.5), so the window's own requests cannot
    tell a slot that was not zeroed from one that was; a probe of a few
    dozen tokens can, and one a chunk and a bit long shows a state not
    handed on whatever the last chunk's fill. Returns
    [`serve_engine`-like records with ``prompt`` and ``tokens``]."""
    rng = np.random.default_rng([seed, 0x9B0BE])
    if after_window:
        while len(engine.scheduler):
            engine.scheduler.pop()
    sent = []
    for n, new in ccfg.get("probes", ()):
        prompt = rng.integers(1, vocab, size=int(n)).astype(np.int32)
        sent.append((engine.submit(prompt.tolist(),
                                   max_new_tokens=int(new)), prompt))
    while not all(rid in engine.finished for rid, _ in sent):
        engine.step()
    return [SimpleNamespace(prompt=p, tokens=engine.pop_result(rid))
            for rid, p in sent]


def check_logits(params, model, cfg, ok: List[Any], ccfg: Dict[str, Any],
                 seed: int, say, probes=()) -> dict:
    """Teacher-forced greedy margins against the plain float32 reference,
    judged by `serve_mla.margin_verdict`: a sample of the finished requests
    (`serve_mla.pick_sample`), under the mean and the 99th percentile, and
    each set of `admission_probes` in ``probes`` ((name, records) pairs: the
    ones sent before the schedule, ``"probes"``, and the ones sent into the
    slots the window left, ``"probes_after"``), under a limit on its mean
    (``pass`` needs all; a set's verdict is under its name). A
    sequence is padded to a multiple of `pad_to` (causal: what follows its
    end changes nothing), so that the reference's two layer programs
    compile for a few lengths only."""
    import jax.numpy as jnp

    ref = program_config(model, ccfg["far_max_tokens"])[2]
    pad_to = int(ccfg["pad_to"])

    def margins_of(r, what):
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        n = len(seq)
        padded = np.zeros((-(-(n - 1) // pad_to) * pad_to,), np.int32)
        padded[:n - 1] = seq[:-1]
        h = ref.hidden(params, jnp.asarray(padded), model,
                       cfg.held_experts)[:n - 1]
        m = np.asarray(ref.head_margin(params, h, jnp.asarray(seq[1:])))
        m = m[len(r.prompt) - 1:]
        say(reference_margin_max=float(m.max()),
            reference_margin_mean=float(m.mean()),
            reference_margin_p99=float(np.percentile(m, 99)),
            prompt=len(r.prompt), generated=len(r.tokens), of=what)
        return m

    out = margin_verdict([margins_of(r, "sample")
                          for r in pick_sample(ok, ccfg, seed)], ccfg)
    for name, sent in probes:
        # a few dozen positions: their MEAN is judged, under a limit of its
        # own (`probe_mean_tol`); a 99th percentile of 64 values is their
        # second largest, and is reported only
        m = np.concatenate([margins_of(r, name) for r in sent])
        out[name] = {
            "sampled": len(sent), "positions": int(m.size),
            "margin_max": float(m.max()), "margin_mean": float(m.mean()),
            "margin_p99": float(np.percentile(m, 99)),
            "pass": bool(m.mean() <= ccfg["probe_mean_tol"])}
        out["pass"] = bool(out["pass"] and out[name]["pass"])
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
    probes = admission_probes(engine, ccfg, cfg.vocab_size, seed)
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
    gdn_keys = ("ssm_state_resets_total", "ssm_row_steps_total",
                "kv_walk_tokens_full_total", "moe_assignments_total",
                "moe_assignments_landed_total", "moe_rows_computed_total",
                "moe_decode_experts_hit_total",
                "moe_decode_layer_steps_total", "kv_bytes_per_token")
    say(gdn={k: stats_end.get(k) for k in gdn_keys},
        longest_row=max((len(r.prompt) + r.n_out for r in ok), default=0))

    probes_after = admission_probes(engine, ccfg, cfg.vocab_size, seed,
                                    after_window=True)
    mem_peak = common.memory_peak_bytes()
    del engine
    gc.collect()
    logit_check = check_logits(
        params, model, cfg, ok, ccfg, seed, say,
        (("probes", probes), ("probes_after", probes_after)))
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

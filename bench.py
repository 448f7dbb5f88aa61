"""Train-step presets and their MFU on one TPU chip.

What is left of the repo's first bench script: the two train presets
(`flagship_config`, `large_config`), the peak table and the timed
train loop (`_bench_config`) that `tools/remat_sweep.py`,
`tools/frontier_sweep.py` and `chip_smoke.py` import, and a `main()`
that prints ONE JSON line: the flagship 551M config's MFU with the
largest-fits-one-chip config (1.55B params, bf16 params/optimizer
state, remat) embedded as ``large_*`` fields, plus trial spread so load
contamination is visible. It refuses to run without a TPU. The judged
benchmark is `benchmark/` (`benchmark/README.md`); no ledger line comes
from this file.

Hardening (round-3 verdict: a single capture swung 2x under co-tenant
load): the bench quiesces on machine load before timing, runs 5 timed
trials per config, and reports the MEDIAN.
"""

import json
import os
import statistics
import time

# Dense bf16 peak FLOP/s of ONE chip, keyed by the `device_kind` JAX
# reports for it. A device that is not in the table is an error, never a
# default: an MFU against the wrong peak is a wrong number.
PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
}

TRIALS = 5
MAX_TRIALS = 7          # extend past TRIALS while spread stays high
SPREAD_TARGET_PCT = 20.0


def flagship_config():
    """551M flagship: the round-over-round comparable config."""
    from ray_tpu.models import LlamaConfig

    # remat_policy: saving the three FFN dot outputs (the FLOPs-heavy
    # 2/3 of each layer) skips their backward-pass recompute; measured
    # +2.2 MFU over full remat on this chip (tools/remat_sweep.py —
    # larger save sets OOM at this batch, smaller ones gain nothing).
    # flash 1024x1024 tiles: +~2 MFU over the 512 default at S=2048
    # (fewer per-block softmax rescales; swept in-model on this chip).
    return LlamaConfig(
        vocab_size=32000, dim=1536, n_layers=16, n_heads=12,
        n_kv_heads=12, ffn_dim=4096, max_seq_len=2048,
        remat=True, attn_impl="flash",
        remat_policy="save:ffn_gate+ffn_up+ffn_down",
        flash_block_q=1024, flash_block_k=1024)


def large_config():
    """Largest config that fits one 16 GiB chip (AOT-verified: 15.37 GiB
    with bf16 params + optimizer state, full remat — f32 AdamW for 1.55B
    needs 27 GiB and cannot fit; remat saves OOM at this frontier)."""
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=28, n_heads=16,
        n_kv_heads=16, ffn_dim=5504, max_seq_len=2048,
        remat=True, attn_impl="flash", param_dtype=jnp.bfloat16,
        flash_block_q=1024, flash_block_k=1024)


def _detect_peak() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise RuntimeError(
            f"no bf16 peak recorded for device_kind {kind!r}; add it to "
            "PEAK_BF16_FLOPS with its source before reporting an MFU")
    return PEAK_BF16_FLOPS[kind]


def _device_record() -> dict:
    """What every printed result names: the device it ran on."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _quiesce(max_wait_s: float = 90.0, threshold: float = 1.5) -> dict:
    """Wait (bounded) for ambient host load to settle before timing: the
    host CPU feeds the TPU, and co-tenant load halves measured MFU
    (round-3 verdict). Returns what the gate saw (initial/final load,
    seconds waited, whether it gave up) so round verdicts can tell a
    quiet run from a contaminated one."""
    t0 = time.monotonic()
    deadline = t0 + max_wait_s
    try:
        first = load = os.getloadavg()[0]
    except OSError:
        return {"load": 0.0, "load_initial": 0.0, "waited_s": 0.0,
                "settled": True}
    while load >= threshold and time.monotonic() < deadline:
        time.sleep(5.0)
        try:
            load = os.getloadavg()[0]
        except OSError:
            break
    return {"load": load, "load_initial": first,
            "waited_s": round(time.monotonic() - t0, 1),
            "settled": load < threshold}


def _bench_config(cfg, batch_size: int, seq_len: int, steps: int,
                  trials: int, devices, peak: float,
                  optimizer=None) -> dict:
    import jax
    import optax

    from ray_tpu.models import llama_init, llama_loss, llama_param_specs
    from ray_tpu.models.llama import llama_flops_per_token
    from ray_tpu.models.training import make_sharded_train_step
    from ray_tpu.parallel import create_mesh

    mesh = create_mesh({"dp": len(devices)}, devices)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    init_fn, step_fn = make_sharded_train_step(
        lambda p, b: llama_loss(p, b, cfg),
        optimizer or optax.adamw(3e-4, weight_decay=0.0),
        mesh, llama_param_specs(cfg))
    params, opt_state = init_fn(params)

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch_size, seq_len + 1), 0, cfg.vocab_size)
    batch = {"tokens": tokens}

    # compile + warmup (float() forces the device sync)
    params, opt_state, metrics = step_fn(params, opt_state, batch)
    loss_before = float(metrics["loss"])

    def one_trial():
        nonlocal params, opt_state, metrics
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        # float() is a device->host fetch of the last step's loss: the
        # timed region ends when the device has finished, not when the
        # dispatch returned.
        float(metrics["loss"])
        return batch_size * seq_len * steps / (time.perf_counter() - t0)

    def spread_pct(rs):
        return ((max(rs) - min(rs)) / max(rs) * 100.0) if max(rs) else 0.0

    rates = [one_trial() for _ in range(trials)]
    # Adaptive extension (round-4 verdict: 38-48% spread made round
    # medians robust only by luck): while the spread stays above target
    # and the budget allows, take more trials — the median over more
    # samples is what gets reported either way.
    while (trials > 1 and len(rates) < MAX_TRIALS
           and spread_pct(rates) > SPREAD_TARGET_PCT):
        rates.append(one_trial())
    # Execution sanity: training on a fixed batch must move the loss; a
    # degraded remote-execution path that no-ops steps would otherwise
    # report absurd throughput.
    loss_after = float(metrics["loss"])
    if loss_after == loss_before:
        raise RuntimeError(
            "benchmark steps did not execute (loss unchanged) — "
            "remote TPU path degraded; rerun")

    tokens_per_sec = statistics.median(rates)
    flops_per_token = llama_flops_per_token(cfg, seq_len)
    mfu = (tokens_per_sec * flops_per_token / len(devices)) / peak * 100.0
    return {
        "mfu": round(mfu, 2),
        "tokens_per_sec_per_chip": round(tokens_per_sec / len(devices)),
        "model_params": cfg.num_params(),
        "trial_spread_pct": round(spread_pct(rates), 2),
        "trials_taken": len(rates),
        "loss": loss_after,
    }


def main():
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(
            "bench.py times the train step on a TPU and found "
            f"{jax.default_backend()!r}; there is no CPU branch")
    peak = _detect_peak()
    gate = _quiesce()
    devices = jax.devices()[:1]
    base = _bench_config(flagship_config(), batch_size=8, seq_len=2048,
                         steps=20, trials=TRIALS, devices=devices,
                         peak=peak)
    large = _bench_config(large_config(), batch_size=4, seq_len=2048,
                          steps=10, trials=TRIALS, devices=devices,
                          peak=peak)
    out = {
        "metric": "llama_train_mfu_1chip",
        "value": base["mfu"],
        "unit": "%MFU",
        "vs_baseline": round(base["mfu"] / 40.0, 4),
        "tokens_per_sec_per_chip": base["tokens_per_sec_per_chip"],
        "model_params": base["model_params"],
        "trial_spread_pct": base["trial_spread_pct"],
        "trials_taken": base.get("trials_taken", 1),
        "host_load_at_start": round(gate["load"], 2),
        "load_gate": gate,
        "loss": base["loss"],
        "device": _device_record(),
    }
    for k, v in large.items():
        out[f"large_{k}"] = v
    print(json.dumps(out))


if __name__ == "__main__":
    main()

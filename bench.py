"""Headline benchmarks: Llama train-step MFU + LLM serving throughput
on one TPU chip.

Prints TWO JSON lines: first the SERVING block
(``llama_decode_tokens_per_sec_1chip`` — engine prefill and decode
tokens/s at 2-3 batch sizes plus DecodeEngine throughput under
mid-flight churn), then — LAST line, the driver's round-over-round
anchor — the train block: the flagship 551M-param config's MFU with
the second, largest-fits-one-chip config (1.55B params, bf16
params/optimizer state, remat) embedded as ``large_*`` fields, plus
trial spread so load contamination is visible.

Hardening (round-3 verdict: a single capture swung 2x under co-tenant
load): the bench quiesces on machine load before timing, runs 5 timed
trials per config, and reports the MEDIAN (two full runs agreed to
0.004% on a shared chip with ~50% per-trial spread).

North star (BASELINE.json): >=40% MFU — vs_baseline = MFU / 40%.
The reference publishes no training-throughput numbers (BASELINE.md), so
this benchmark IS the baseline being established. Model sizing targets a
single 16 GiB v5e chip; scale-out numbers come from the multi-host train
library, not this script.
"""

import json
import os
import statistics
import sys
import time

# The multichip serving section sweeps tensor-parallel degree; off-TPU
# that needs a forced multi-device CPU world, and the flag only takes
# effect if set before jax initializes (no-op for the TPU backend —
# it governs the HOST platform's device count only).
if "jax" not in sys.modules and "--xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        (os.environ.get("XLA_FLAGS", "") +
         " --xla_force_host_platform_device_count=8").strip())


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


def _bench_serving(cfg, *, batch_sizes, prompt_len: int,
                   new_tokens: int, trials: int,
                   horizons=(1, 4, 8)) -> dict:
    """Engine serving throughput on ONE chip: per batch size, the
    prefill rate (batched admission prefills, the engine's real
    admission path) and the steady-state fused-decode rate (every slot
    live, adaptive horizon), plus a HORIZON SWEEP (pinned H — H=1 is
    the historical one-dispatch-one-sync-per-token path, larger H
    amortizes both across the fused block; `host_syncs_per_token` is
    the direct evidence), mid-flight-churn throughput at
    decode_horizon 1 vs the default (queue deeper than slots, ragged
    budgets — slots are reused as rows finish mid-horizon), and a
    PIPELINE DEPTH SWEEP (d1 = synchronous, d2/d4 = async
    double-buffered run-ahead overlapping host replay with device
    compute) on both steady-state decode and the churn workload.
    Tokens/s are wall-clock host-inclusive numbers: this measures the
    serving engine, not the bare kernel."""
    import jax
    import numpy as np

    from ray_tpu.models import llama_init
    from ray_tpu.models.engine import DecodeEngine

    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    max_len = prompt_len + new_tokens + 1

    def prompts(n, length=prompt_len):
        return [rng.randint(1, cfg.vocab_size, size=length).tolist()
                for _ in range(n)]

    def make_engine(B, horizon=8, depth=2):
        return DecodeEngine(params, cfg, batch_slots=B, max_len=max_len,
                            decode_horizon=horizon,
                            pipeline_depth=depth,
                            enable_metrics=False)

    def spread_pct(rs):
        return ((max(rs) - min(rs)) / max(rs) * 100.0) if max(rs) else 0.0

    def drain(eng, horizon=None):
        """Drive to empty at a pinned (or adaptive) horizon; returns
        tokens emitted — a fused step emits up to H per row, so rates
        must count TOKENS, never steps x slots."""
        toks = 0
        while eng.pending():
            ev = eng.step(horizon=horizon)
            toks += sum(len(t) for t in ev.values())
        return toks

    per_batch = {}
    for B in batch_sizes:
        # warmup: compile this B's prefill bucket + fused decode
        # programs (adaptive drain touches H=1 and the full horizon)
        eng = make_engine(B)
        for p in prompts(B):
            eng.submit(p, new_tokens)
        drain(eng)

        pre_rates, dec_rates, spt = [], [], []
        for _ in range(trials):
            eng = make_engine(B)
            for p in prompts(B):
                eng.submit(p, new_tokens)
            t0 = time.perf_counter()
            eng.step(horizon=1)  # admits all B rows (batched prefill)
            t1 = time.perf_counter()
            toks = drain(eng)    # fused decode, all slots live
            t2 = time.perf_counter()
            pre_rates.append(B * prompt_len / (t1 - t0))
            if toks:
                dec_rates.append(toks / (t2 - t1))
            s = eng.stats()
            spt.append(s["host_syncs_per_token"])
        per_batch[f"b{B}"] = {
            "prefill_tokens_per_sec": round(
                statistics.median(pre_rates), 1),
            "decode_tokens_per_sec": round(
                statistics.median(dec_rates), 1),
            "host_syncs_per_token": round(statistics.median(spt), 4),
            "trial_spread_pct": round(spread_pct(dec_rates), 2),
            "trials_taken": len(dec_rates),
        }

    # Horizon sweep at the largest batch: same workload, pinned H.
    B = max(batch_sizes)
    horizon_sweep = {}
    for H in horizons:
        eng = make_engine(B, horizon=H)      # warmup: compile THIS H
        for p in prompts(B):
            eng.submit(p, new_tokens)
        eng.step(horizon=1)
        drain(eng, horizon=H)
        rates, spt = [], []
        for _ in range(trials):
            eng = make_engine(B, horizon=H)
            for p in prompts(B):
                eng.submit(p, new_tokens)
            eng.step(horizon=1)          # admission outside the clock
            t0 = time.perf_counter()
            toks = drain(eng, horizon=H)
            dt = time.perf_counter() - t0
            if toks:
                rates.append(toks / dt)
            spt.append(eng.stats()["host_syncs_per_token"])
        horizon_sweep[f"h{H}"] = {
            "decode_tokens_per_sec": round(statistics.median(rates), 1),
            "host_syncs_per_token": round(statistics.median(spt), 4),
            "trial_spread_pct": round(spread_pct(rates), 2),
        }

    # Churn: 3x oversubscribed queue, ragged budgets — requests join
    # and leave mid-flight, slots are reused, prefills interleave with
    # fused decode blocks. Run at decode_horizon=1 (the historical
    # per-step path) and the default horizon: the gap is the tentpole's
    # end-to-end win under realistic load.
    def churn(horizon, depth=2):
        rates = []
        for trial in range(trials + 1):     # +1 untimed warmup: churn
            eng = make_engine(B, horizon=horizon,   # hits prefill
                              depth=depth)
            total = 0                       # group sizes and capped
            for i, p in enumerate(prompts(3 * B)):  # horizons the
                n = new_tokens if i % 2 == 0 else max(2, new_tokens // 2)
                eng.submit(p, n)            # steady sweep never compiled
                total += n
            t0 = time.perf_counter()
            eng.run()
            if trial:
                rates.append(total / (time.perf_counter() - t0))
        return round(statistics.median(rates), 1)

    churn_h1 = churn(1)
    churn_h8 = churn(8)

    # Pipeline depth sweep at the default horizon: d1 is the
    # synchronous engine, d2/d4 run ahead — the device computes block
    # N+1 while the host replays block N off its async copy.
    # Steady-state decode is where run-ahead engages end-to-end;
    # churn (3x oversubscribed, admissions forcing flushes) shows the
    # overlap at least breaks even under realistic load.
    # depth_effective / overrun_tokens quantify how much run-ahead
    # actually happened and what it wasted.
    pipeline_sweep = {}
    for depth in (1, 2, 4):
        eng = make_engine(B, depth=depth)           # warmup this depth
        for p in prompts(B):
            eng.submit(p, new_tokens)
        drain(eng)
        rates = []
        eff = over = 0.0
        for _ in range(trials):
            eng = make_engine(B, depth=depth)
            for p in prompts(B):
                eng.submit(p, new_tokens)
            eng.step(horizon=1)          # admission outside the clock
            t0 = time.perf_counter()
            toks = drain(eng)
            dt = time.perf_counter() - t0
            if toks:
                rates.append(toks / dt)
            s = eng.stats()
            eff = s["pipeline_depth_effective"]
            over = s["pipeline_overrun_tokens"]
        pipeline_sweep[f"d{depth}"] = {
            "decode_tokens_per_sec": round(
                statistics.median(rates), 1),
            "churn_tokens_per_sec": churn(8, depth=depth),
            "pipeline_depth_effective": round(eff, 3),
            "pipeline_overrun_tokens": over,
            "trial_spread_pct": round(spread_pct(rates), 2),
        }

    biggest = per_batch[f"b{max(batch_sizes)}"]
    return {
        "metric": "llama_decode_tokens_per_sec_1chip",
        "value": biggest["decode_tokens_per_sec"],
        "unit": "tokens/s",
        "prefill_tokens_per_sec": biggest["prefill_tokens_per_sec"],
        "decode_tokens_per_sec": biggest["decode_tokens_per_sec"],
        "host_syncs_per_token": biggest["host_syncs_per_token"],
        "churn_tokens_per_sec": churn_h8,
        "churn_tokens_per_sec_h1": churn_h1,
        "churn_tokens_per_sec_h8": churn_h8,
        "horizon_sweep": horizon_sweep,
        "pipeline_sweep": pipeline_sweep,
        "batch_sizes": list(batch_sizes),
        "per_batch": per_batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "model_params": cfg.num_params(),
    }


def _bench_prefix(cfg, *, prefix_len: int, suffix_len: int,
                  batch_slots: int, n_requests: int, new_tokens: int,
                  trials: int, prefix_block: int = 32) -> dict:
    """Shared-prefix serving workload (the prefix-reuse tentpole's
    end-to-end number): every request = one shared `prefix_len`-token
    system prompt + a distinct `suffix_len`-token user suffix — the
    dominant production shape (vLLM/SGLang's motivating case).

    Reports (a) the WARM reuse fraction — after one priming request
    seeds the trie, what fraction of each admission's prompt tokens are
    COPIED from the pool instead of prefilled (the acceptance gate:
    >= 0.9 at prefix 512 / suffix <= 32); (b) the trie hit rate and
    prefill tokens/s SAVED during the churn run; and (c) churn
    tokens/s with the cache on vs off — same engine, same workload,
    the only difference is recomputing the shared prefix per request
    vs copying it."""
    import jax
    import numpy as np

    from ray_tpu.models import llama_init
    from ray_tpu.models.engine import DecodeEngine

    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(7)
    max_len = prefix_len + suffix_len + new_tokens + 1
    prefix = rng.randint(1, cfg.vocab_size, size=prefix_len).tolist()

    def reqs(n):
        return [prefix + rng.randint(1, cfg.vocab_size,
                                     size=suffix_len).tolist()
                for _ in range(n)]

    def make(cache_on):
        kw = dict(prefix_cache=True, prefix_block=prefix_block,
                  scheduler="prefix") if cache_on else {}
        return DecodeEngine(params, cfg, batch_slots=batch_slots,
                            max_len=max_len, enable_metrics=False, **kw)

    def spread_pct(rs):
        return ((max(rs) - min(rs)) / max(rs) * 100.0) if max(rs) else 0.0

    # Warm-reuse fraction: ONE priming request computes the shared
    # blocks (cold), then the burst is measured by counter deltas —
    # the steady state a long-running server sees.
    eng = make(True)
    eng.submit(reqs(1)[0], 4)
    eng.run()
    reused0 = eng.prefix_reused_tokens
    real0 = eng.prefill_real_tokens
    for p in reqs(n_requests):
        eng.submit(p, new_tokens)
    eng.run()
    reused = eng.prefix_reused_tokens - reused0
    real = eng.prefill_real_tokens - real0
    warm_frac = reused / (reused + real) if reused + real else 0.0

    # Churn: fresh engine per trial (trie starts empty — the first
    # request of each trial is the cold leader), ragged budgets,
    # queue deeper than slots. +1 untimed warmup trial compiles every
    # program (copy-in/out chain lengths, suffix prefill buckets).
    def churn(cache_on):
        rates, saved = [], []
        for trial in range(trials + 1):
            eng = make(cache_on)
            total = 0
            for i, p in enumerate(reqs(n_requests)):
                n = new_tokens if i % 2 == 0 else max(2, new_tokens // 2)
                eng.submit(p, n)
                total += n
            t0 = time.perf_counter()
            eng.run()
            dt = time.perf_counter() - t0
            if trial:
                rates.append(total / dt)
                saved.append(eng.prefix_reused_tokens / dt)
        stats = eng.stats()
        return rates, saved, stats

    off_rates, _, _ = churn(False)
    on_rates, on_saved, on_stats = churn(True)
    churn_off = statistics.median(off_rates)
    churn_on = statistics.median(on_rates)
    return {
        "prefix_len": prefix_len,
        "suffix_len": suffix_len,
        "n_requests": n_requests,
        "prefix_block": prefix_block,
        "warm_reused_token_frac": round(warm_frac, 4),
        "prefix_hit_rate": round(on_stats["prefix_hit_rate"], 4),
        "prefill_tokens_saved_per_sec": round(
            statistics.median(on_saved), 1),
        "churn_tokens_per_sec_cache_on": round(churn_on, 1),
        "churn_tokens_per_sec_cache_off": round(churn_off, 1),
        "churn_speedup": round(churn_on / churn_off, 3)
        if churn_off else 0.0,
        "trial_spread_pct": round(spread_pct(on_rates), 2),
    }


def _bench_paged(cfg, *, prefix_len: int, suffix_len: int,
                 batch_slots: int, n_requests: int, new_tokens: int,
                 trials: int, block_tokens: int = 16) -> dict:
    """Paged-KV serving workload (the block-pool tentpole's end-to-end
    number): the same shared-prefix churn as `_bench_prefix`, run
    through the paged engine, plus the two things paging buys that
    copy-in cannot:

    (a) WARM-ADMISSION LATENCY — after one priming request, each warm
        admission on the paged engine increfs its shared blocks (zero
        device bytes); the copy-in engine gathers them d2d. Reported
        as the median per-request wall time of a warm single-request
        submit+run on each engine, same prompts, same budgets.
    (b) PREEMPTION-PRESSURE THROUGHPUT — requests 4x the row slots,
        on a pool deliberately sized so the concurrent set cannot fit
        (~60% of peak demand): the engine must preempt-and-swap to
        finish, and the gate is that it FINISHES with tokens intact
        (identity is tested; here we report the tokens/s it sustains
        and the swap traffic it paid).

    `llama_decode_tokens_per_sec_paged` is the headline: churn
    tokens/s on the paged engine with the pool fitting the workload
    (preemption-free), directly comparable to the copy-in engine's
    churn number on the same traffic."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama_init
    from ray_tpu.models.engine import DecodeEngine
    from ray_tpu.models.prefix_cache import block_bytes

    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(7)
    max_len = prefix_len + suffix_len + new_tokens + 1
    # paged mode needs max_len % block_tokens == 0
    max_len = -(-max_len // block_tokens) * block_tokens
    prefix = rng.randint(1, cfg.vocab_size, size=prefix_len).tolist()
    bb = block_bytes(cfg.n_layers, block_tokens, cfg.n_kv_heads,
                     cfg.head_dim, jnp.dtype(cfg.dtype).itemsize)

    def reqs(n):
        return [prefix + rng.randint(1, cfg.vocab_size,
                                     size=suffix_len).tolist()
                for _ in range(n)]

    def make(paged, *, pool_blocks=None):
        kw = dict(prefix_cache=True, scheduler="prefix",
                  enable_metrics=False)
        if paged:
            kw.update(paged=True, kv_block_tokens=block_tokens)
            if pool_blocks is not None:
                kw.update(kv_pool_bytes=pool_blocks * bb)
        else:
            kw.update(prefix_block=block_tokens)
        return DecodeEngine(params, cfg, batch_slots=batch_slots,
                            max_len=max_len, **kw)

    # (a) warm-admission latency, paged (incref) vs copy-in (gather).
    def warm_lat(paged):
        eng = make(paged)
        eng.submit(reqs(1)[0], 4)
        eng.run()                      # prime + compile cold path
        lats = []
        for p in reqs(8):
            t0 = time.perf_counter()
            eng.submit(p, new_tokens)
            eng.run()
            lats.append(time.perf_counter() - t0)
        return statistics.median(lats[1:])  # [0] compiles warm path

    lat_paged = warm_lat(True)
    lat_copy = warm_lat(False)

    # Headline churn: preemption-free pool, queue 4x deeper than
    # slots, ragged budgets — same traffic the copy-in engine ran.
    def churn(pool_blocks):
        rates = []
        stats = {}
        for trial in range(trials + 1):
            eng = make(True, pool_blocks=pool_blocks)
            total = 0
            for i, p in enumerate(reqs(n_requests)):
                n = new_tokens if i % 2 == 0 else max(2, new_tokens // 2)
                eng.submit(p, n)
                total += n
            t0 = time.perf_counter()
            eng.run()
            dt = time.perf_counter() - t0
            if trial:
                rates.append(total / dt)
        stats = eng.stats()
        return statistics.median(rates), stats

    free_rate, free_stats = churn(None)

    # (b) preemption pressure: pool ~60% of the concurrent demand.
    per_row = -(-(prefix_len + suffix_len + new_tokens) // block_tokens)
    shared_blocks = prefix_len // block_tokens
    demand = shared_blocks + (per_row - shared_blocks) * batch_slots
    tight = max(per_row + 1, int(demand * 0.6))
    tight_rate, tight_stats = churn(tight)

    return {
        "metric": "llama_decode_tokens_per_sec_paged",
        "value": round(free_rate, 1),
        "unit": "tokens/s",
        "prefix_len": prefix_len,
        "suffix_len": suffix_len,
        "n_requests": n_requests,
        "block_tokens": block_tokens,
        "warm_admission_ms_paged": round(lat_paged * 1e3, 3),
        "warm_admission_ms_copy_in": round(lat_copy * 1e3, 3),
        "warm_admission_speedup": round(lat_copy / lat_paged, 3)
        if lat_paged else 0.0,
        "kv_blocks_shared": free_stats["kv_blocks_shared"],
        "kv_block_cows": free_stats["kv_block_cows"],
        "preemptions_free_pool": free_stats["preemptions"],
        "preempt_pressure_pool_blocks": tight,
        "preempt_pressure_tokens_per_sec": round(tight_rate, 1),
        "preempt_pressure_preemptions": tight_stats["preemptions"],
        "preempt_pressure_swap_out_bytes": tight_stats[
            "swap_out_bytes"],
        "preempt_throughput_frac": round(tight_rate / free_rate, 3)
        if free_rate else 0.0,
    }


def _bench_kv_quant(cfg, *, prompt_len: int, batch_slots: int,
                    n_requests: int, new_tokens: int, trials: int,
                    block_tokens: int = 16) -> dict:
    """Quantized-KV concurrency at fixed HBM (the int8/fp8 tentpole's
    end-to-end number): the SAME `kv_pool_bytes` budget buys a bf16,
    an int8, and an fp8-e4m3 pool; the headline
    `kv_quant_concurrency_ratio` is how many more requests' worth of
    blocks the int8 pool holds (scale slab included — ~1.9-2x, the
    "double the users per HBM byte" claim, gated in CI by
    tests/test_engine_kv_quant.py's tolerance check on the SAME
    comparison). Also reported:

    - decode tokens/s per mode on identical greedy traffic (the
      dequant-in-gather per-step price; microbench isolates the op),
    - the quant-on quality gate inline: greedy token-match fraction
      vs the bf16 engine on the same prompts,
    - preempt-swap traffic ratio on SAME-BLOCK-COUNT tight pools
      (quantized blocks spill quantized bytes + scales — ~half the
      bf16 swap bytes per preemption).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama_init
    from ray_tpu.models.engine import DecodeEngine
    from ray_tpu.models.prefix_cache import block_bytes

    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(11)
    T = block_tokens
    max_len = prompt_len + new_tokens + 1
    max_len = -(-max_len // T) * T
    per_row = max_len // T
    prompts = [rng.randint(1, cfg.vocab_size, size=prompt_len).tolist()
               for _ in range(n_requests)]
    bb_dense = block_bytes(cfg.n_layers, T, cfg.n_kv_heads,
                           cfg.head_dim, jnp.dtype(cfg.dtype).itemsize)
    bb_quant = block_bytes(cfg.n_layers, T, cfg.n_kv_heads,
                           cfg.head_dim, 1) \
        + 2 * cfg.n_layers * cfg.n_kv_heads * 4
    # Budget: exactly batch_slots rows' worth of bf16 blocks — the
    # fixed HBM everyone gets.
    budget = batch_slots * per_row * bb_dense

    def run(quant, *, pool_bytes=budget, preempt=None):
        kw = {} if preempt is None else {"preempt": preempt}
        eng = DecodeEngine(params, cfg, batch_slots=batch_slots,
                           max_len=max_len, paged=True,
                           kv_block_tokens=T, kv_pool_bytes=pool_bytes,
                           kv_quant=quant, enable_metrics=False, **kw)
        rates = []
        toks = None
        for trial in range(trials + 1):
            ids = [eng.submit(p, new_tokens) for p in prompts]
            t0 = time.perf_counter()
            out = eng.run()
            dt = time.perf_counter() - t0
            if trial:
                rates.append(n_requests * new_tokens / dt)
            toks = [out[i] for i in ids]
        return statistics.median(rates), toks, eng

    rate_bf, toks_bf, eng_bf = run(None)
    rate_i8, toks_i8, eng_i8 = run("int8")
    rate_f8, toks_f8, eng_f8 = run("fp8_e4m3")

    def conc(eng):
        return eng.kv_pool.blocks_total // per_row

    def match_frac(a, b):
        tot = sum(len(x) for x in a)
        hit = sum(int(x == y) for xs, ys in zip(a, b)
                  for x, y in zip(xs, ys))
        return hit / tot if tot else 0.0

    # Preempt-swap traffic: SAME BLOCK COUNT both modes (so the
    # preemption pattern matches), bytes differ by the quant layout.
    tight = max(per_row + 1, int(per_row * batch_slots * 0.6))
    _, _, eng_sw_bf = run(None, pool_bytes=tight * bb_dense,
                          preempt="swap")
    _, _, eng_sw_i8 = run("int8", pool_bytes=tight * bb_quant,
                          preempt="swap")
    sw_bf = eng_sw_bf.stats()
    sw_i8 = eng_sw_i8.stats()

    ratio = conc(eng_i8) / conc(eng_bf) if conc(eng_bf) else 0.0
    return {
        "metric": "kv_quant_concurrency_ratio",
        "value": round(ratio, 3),
        "unit": "x",
        "kv_pool_bytes": budget,
        "block_tokens": T,
        "bytes_per_block_bf16": eng_bf.kv_bytes_per_block,
        "bytes_per_block_int8": eng_i8.kv_bytes_per_block,
        "bytes_per_block_fp8": eng_f8.kv_bytes_per_block,
        "bytes_per_token_bf16": eng_bf.kv_bytes_per_token,
        "bytes_per_token_int8": eng_i8.kv_bytes_per_token,
        "concurrency_bf16": conc(eng_bf),
        "concurrency_int8": conc(eng_i8),
        "concurrency_fp8": conc(eng_f8),
        "kv_quant_concurrency_ratio_fp8": round(
            conc(eng_f8) / conc(eng_bf), 3) if conc(eng_bf) else 0.0,
        "decode_tokens_per_sec_bf16": round(rate_bf, 1),
        "decode_tokens_per_sec_int8": round(rate_i8, 1),
        "decode_tokens_per_sec_fp8": round(rate_f8, 1),
        "token_match_frac_int8": round(match_frac(toks_bf, toks_i8), 4),
        "token_match_frac_fp8": round(match_frac(toks_bf, toks_f8), 4),
        "swap_out_bytes_bf16": sw_bf["swap_out_bytes"],
        "swap_out_bytes_int8": sw_i8["swap_out_bytes"],
        "swap_preemptions_bf16": sw_bf["preemptions"],
        "swap_preemptions_int8": sw_i8["preemptions"],
        "swap_bytes_ratio_int8": round(
            sw_i8["swap_out_bytes"] / sw_bf["swap_out_bytes"], 3)
        if sw_bf["swap_out_bytes"] else 0.0,
    }


def _bench_fleet(cfg, *, n_groups: int, prefix_len: int,
                 suffix_len: int, n_requests: int, new_tokens: int,
                 batch_slots: int, replica_counts=(2, 4),
                 prefix_block: int = 16) -> dict:
    """Multi-replica churn (the fleet tentpole's end-to-end number):
    `n_groups` shared-prefix families (each: one `prefix_len`-token
    system prompt + distinct suffixes) arriving interleaved with mixed
    priority classes and a sliver of tight deadlines, served by 2 and
    4 `DecodeEngine` replicas behind `LLMFleet`.

    Each replica count runs TWICE — round-robin (stats-blind control)
    vs pow-2-choice + prefix affinity — on the identical arrival
    sequence. The affinity router should partition prefix groups
    across replicas (each group's blocks computed once, on one trie)
    while round-robin makes every replica recompute every group's
    prefix; the headline comparison is TTFT p95, with TPOT p95,
    shed-rate, and the prefill/reuse token counters as supporting
    evidence. Requests arrive a few per step (not all upfront) so the
    router sees live queue/occupancy/trie state, like a server
    would.

    The closing CHAOS arm reruns the churn at the top replica count
    with a scripted `FaultInjector` killing one replica mid-churn:
    recovery time, throughput dip vs the fault-free control, and the
    determinism checks (token-identical results, zero tokens lost)
    land under the ``chaos`` key."""
    import jax
    import numpy as np

    from ray_tpu.models import LLMFleet, llama_init
    from ray_tpu.models.engine import DecodeEngine

    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(11)
    max_len = prefix_len + suffix_len + new_tokens + 1
    prefixes = [rng.randint(1, cfg.vocab_size, size=prefix_len).tolist()
                for _ in range(n_groups)]
    # One fixed arrival sequence, group per request drawn at RANDOM
    # (seeded): a round-interleaved g = i % n_groups would let
    # round-robin partition groups perfectly by accident whenever
    # n_groups divides the replica count — the shuffle keeps the
    # control arm honest. Fields: (prompt, priority, deadline); every
    # 8th request carries a deadline so tight it sheds instead of
    # burning prefill (deadline_s=0 is the deterministic
    # dead-on-arrival case — shed-rate is exact, not racy, in the dry
    # run).
    arrivals = []
    for i in range(n_requests):
        g = int(rng.randint(n_groups))
        prompt = prefixes[g] + rng.randint(
            1, cfg.vocab_size, size=suffix_len).tolist()
        priority = 0 if i % 3 else 10
        deadline = 0.0 if i % 8 == 7 else None
        arrivals.append((prompt, priority, deadline))

    def run_one(router, n_replicas, trace=False, trace_path=None,
                probe_state=False):
        from ray_tpu.util import metrics_history as mh
        from ray_tpu.util.state import serving

        def factory(name):
            return DecodeEngine(params, cfg, batch_slots=batch_slots,
                                max_len=max_len, scheduler="priority",
                                prefix_cache=True,
                                prefix_block=prefix_block,
                                engine_id=name, trace=trace)
        fleet = LLMFleet(factory, initial_replicas=n_replicas,
                         router=router, trace=trace,
                         fleet_id=f"bench-{router}-{n_replicas}")
        probe_samples = []
        t0 = time.perf_counter()
        for i, (prompt, priority, deadline) in enumerate(arrivals):
            fleet.submit(prompt, new_tokens, priority=priority,
                         deadline_s=deadline)
            if i % 2 == 1:       # two arrivals per engine step
                fleet.step()
                if probe_state:
                    # One full status poll against the LIVE churn
                    # state: fleet rollup + forced history sample.
                    # Probed every step for statistics; the reported
                    # overhead uses the median probe cost against a
                    # 10 Hz poll period (see below).
                    p0 = time.perf_counter()
                    serving.summarize_fleet()
                    mh.sample_now(force=True)
                    probe_samples.append(time.perf_counter() - p0)
        fleet.run()
        wall = time.perf_counter() - t0
        if trace_path is not None:
            fleet.dump_trace(trace_path)
        s = fleet.stats()
        per = [r.engine.stats() for r in fleet.replicas]
        served = n_requests - int(s["requests_shed"])
        if probe_state:
            return {"wall_s": wall, "probe_samples": probe_samples}
        return {
            "router": router,
            "n_replicas": n_replicas,
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(served * new_tokens / wall, 1)
            if wall else 0.0,
            "ttft_p95_s": round(s["ttft_s_p95_max"], 4),
            "tpot_p95_s": round(s["tpot_s_p95_max"], 5),
            "shed_rate": round(s["requests_shed"] / n_requests, 4),
            "router_affinity_wins": int(s["router_affinity_wins"]),
            "prefill_real_tokens": int(sum(
                p["prefill_real_tokens"] for p in per)),
            "prefix_reused_tokens": int(sum(
                p["prefix_reused_tokens"] for p in per)),
        }

    # Untimed warmup per ROUTER: the two placements drive different
    # prefix-chain lengths through the copy programs (different XLA
    # shapes), so each router must compile its own set before its
    # measured run.
    run_one("round_robin", replica_counts[0])
    run_one("pow2_affinity", replica_counts[0])
    scenarios = []
    for n in replica_counts:
        for router in ("round_robin", "pow2_affinity"):
            scenarios.append(run_one(router, n))

    def pick(router, n):
        return next(sc for sc in scenarios
                    if sc["router"] == router and sc["n_replicas"] == n)

    n0 = replica_counts[0]
    rr, aff = pick("round_robin", n0), pick("pow2_affinity", n0)

    # Tracing tax on the identical churn: re-run the affinity arm with
    # the lifecycle tracer ON (compiled programs already warm) and dump
    # the chrome trace as the run's artifact — the request-level
    # timeline behind the aggregate numbers above
    # (tools/trace_report.py prints the breakdown).
    traced = run_one("pow2_affinity", n0, trace=True,
                     trace_path="BENCH_fleet.trace.json")
    trace_overhead = (traced["wall_s"] - aff["wall_s"]) \
        / aff["wall_s"] if aff["wall_s"] else 0.0

    # Observability tax on the identical churn: the affinity arm once
    # more with a full status poll (`summarize_fleet()` + forced
    # metrics-history sample) taken against the live mid-churn state
    # at every step. The reported fraction is the steady-state cost of
    # a 10 Hz status poller: median per-poll seconds over the 100 ms
    # poll period. Median, not sum — a single GC pause inside one
    # probe would otherwise dominate the dry run's tiny wall.
    # Target: < 1%.
    # Collect first: engines from the arms above die in reference
    # cycles, and until the GC runs they linger in the weak serving
    # registry — the probe would pay a stats sweep over every corpse.
    import gc
    gc.collect()
    probed = run_one("pow2_affinity", n0, probe_state=True)
    poll_period_s = 0.1
    state_overhead = (statistics.median(probed["probe_samples"])
                      / poll_period_s
                      if probed["probe_samples"] else 0.0)

    # Chaos arm: kill 1-of-N replicas mid-churn (scripted
    # FaultInjector) against a fault-free control of the IDENTICAL
    # fleet shape and arrival sequence. Reported numbers: recovery
    # time (kill detected -> every failed-over request finished),
    # throughput dip vs the control, and the zero-loss/token-identity
    # checks — all real on any backend; absolute tokens/s is not.
    from ray_tpu.models import FaultInjector

    n_chaos = replica_counts[-1]

    def run_chaos(inj, fleet_id):
        def factory(name):
            return DecodeEngine(params, cfg, batch_slots=batch_slots,
                                max_len=max_len, scheduler="priority",
                                prefix_cache=True,
                                prefix_block=prefix_block,
                                engine_id=name)
        fleet = LLMFleet(factory, initial_replicas=n_chaos,
                         router="pow2_affinity", fleet_id=fleet_id,
                         fault_injector=inj)
        kill_t = recover_t = None
        n_failed_over = 0

        def watch():
            nonlocal kill_t, recover_t, n_failed_over
            if kill_t is None and fleet.replicas_failed:
                kill_t = time.perf_counter()
                # Right after the failing step the retry queue holds
                # every reconstructed request (drain happens at the
                # NEXT step's start).
                n_failed_over = len(fleet._retry)
            elif kill_t is not None and recover_t is None and \
                    fleet.requests_recovered >= n_failed_over:
                recover_t = time.perf_counter()

        t0 = time.perf_counter()
        for i, (prompt, priority, deadline) in enumerate(arrivals):
            fleet.submit(prompt, new_tokens, priority=priority,
                         deadline_s=deadline)
            if i % 2 == 1:
                fleet.step()
                watch()
        while fleet.pending():
            fleet.step()
            watch()
        results = fleet.run()
        wall = time.perf_counter() - t0
        s = fleet.stats()
        served = n_requests - int(s["requests_shed"])
        return {
            "results": results, "wall_s": wall, "stats": s,
            "tokens_per_sec": served * new_tokens / wall
            if wall else 0.0,
            "recovery_s": (recover_t - kill_t)
            if kill_t is not None and recover_t is not None else None,
        }

    chaos_id = f"bench-chaos-{n_chaos}"
    control = run_chaos(None, f"bench-chaos-ctl-{n_chaos}")
    inj = FaultInjector(schedule={f"{chaos_id}-r0": [(2, "kill")]})
    chaos = run_chaos(inj, chaos_id)
    cs = chaos["stats"]
    chaos_block = {
        "n_replicas": n_chaos,
        "killed_replica": f"{chaos_id}-r0",
        "kill_fired": bool(inj.fired),
        "identical_to_fault_free": (
            chaos["results"] == control["results"]),
        "tokens_lost_to_failure": int(cs["tokens_lost_to_failure"]),
        "requests_recovered": int(cs["requests_recovered"]),
        "retries": int(cs["retries"]),
        "replicas_failed": int(cs["replicas_failed"]),
        "replicas_after": int(cs["replicas"]),
        "recovery_s": (round(chaos["recovery_s"], 4)
                       if chaos["recovery_s"] is not None else None),
        "wall_s": round(chaos["wall_s"], 3),
        "wall_fault_free_s": round(control["wall_s"], 3),
        "tokens_per_sec": round(chaos["tokens_per_sec"], 1),
        "tokens_per_sec_fault_free": round(
            control["tokens_per_sec"], 1),
        "throughput_dip_frac": round(
            1.0 - chaos["tokens_per_sec"] / control["tokens_per_sec"],
            4) if control["tokens_per_sec"] else 0.0,
    }

    return {
        "n_groups": n_groups,
        "prefix_len": prefix_len,
        "suffix_len": suffix_len,
        "n_requests": n_requests,
        "scenarios": scenarios,
        # Headline: affinity routing's TTFT p95 win over round-robin
        # at the base replica count (>1.0 = router earns its keep).
        "ttft_p95_rr_over_affinity": round(
            rr["ttft_p95_s"] / aff["ttft_p95_s"], 3)
        if aff["ttft_p95_s"] else 0.0,
        "prefill_saved_frac_vs_rr": round(
            1.0 - aff["prefill_real_tokens"]
            / rr["prefill_real_tokens"], 4)
        if rr["prefill_real_tokens"] else 0.0,
        "trace_overhead_frac": round(trace_overhead, 4),
        "trace_artifact": "BENCH_fleet.trace.json",
        "state_snapshot_overhead_frac": round(state_overhead, 4),
        "chaos": chaos_block,
    }


def _bench_disagg(cfg, *, prompt_len: int, new_tokens: int,
                  n_requests: int, batch_slots: int,
                  prefill_replicas: int = 2,
                  decode_replicas: int = 2,
                  block_tokens: int = 16,
                  tpot_idle_slack: float = 1.25,
                  ttft_slack: float = 1.1) -> dict:
    """Disaggregated prefill/decode fleet (the r13 tentpole's
    end-to-end number): the SAME churn arrival sequence — a few
    submits per step, so admissions land while earlier requests
    decode — served three ways:

    - ``colocated``: P+D replicas in one shared pool (the control):
      every replica interleaves chunked prefill with fused decode, so
      each admission stretches the inter-token gaps of whatever was
      decoding on that replica — the TPOT tail degrades with arrival
      rate;
    - ``disagg``: the same replica budget split P prefill / D decode
      with KV handed off at prefill completion. Decode replicas never
      run a prefill, so the TPOT tail is INDEPENDENT of admissions —
      that independence is the whole point of the split;
    - ``idle``: decode-class-sized colocated fleet with every request
      submitted before the first step and few enough to admit in one
      wave — quiet-decode TPOT, the floor the disagg arm is gated
      against.

    Headline: ``tpot_p95_colocated_over_disagg`` (>1.0 = the split
    shields decode; the control degrades while disagg holds) and
    ``tpot_p95_disagg_over_idle`` (~1.0 = decode under churn is as
    quiet as decode with admission idle). TTFT is measured at the
    BENCH level (submit wall-time -> first emission from fleet.step)
    identically for both churn arms so the ratio is apples-to-apples
    — fleet/engine TTFT windows differ between the two shapes. The
    closing CHAOS arm kills the first decode-class replica mid-churn:
    token-identity vs the fault-free disagg arm and
    ``tokens_lost_to_failure == 0`` are the gate. Ratios and gates are
    real on any backend; absolute tokens/s is not.

    ``tpot_idle_slack`` / ``ttft_slack`` set the gate thresholds. The
    defaults are the TPU targets; the CPU dry run passes looser values
    — there a fleet step costs as much as a whole nano prefill, so the
    handoff's fixed +1-step latency (noise at real model scale, where
    prefill dwarfs a decode step) and host co-tenant jitter both land
    squarely in the measured tails."""
    import jax
    import numpy as np

    from ray_tpu.models import FaultInjector, LLMFleet, llama_init
    from ray_tpu.models.engine import DecodeEngine

    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(13)
    max_len = prompt_len + new_tokens + 1
    max_len += (-max_len) % block_tokens    # paged rows span max_len
    n_total = prefill_replicas + decode_replicas
    arrivals = [rng.randint(1, cfg.vocab_size,
                            size=prompt_len).tolist()
                for _ in range(n_requests)]

    def factory(name):
        return DecodeEngine(params, cfg, batch_slots=batch_slots,
                            max_len=max_len, paged=True,
                            kv_block_tokens=block_tokens,
                            engine_id=name)

    def churn(fleet, prompts, upfront=False):
        """Drive the arrival sequence; returns wall, bench-side TTFT
        samples, per-fid results."""
        submit_t = {}
        ttft = []
        results = {}

        def drink(emissions):
            now = time.perf_counter()
            for fid, toks in emissions.items():
                if toks and fid in submit_t:
                    ttft.append(now - submit_t.pop(fid))

        t0 = time.perf_counter()
        if upfront:
            for p in prompts:
                submit_t[fleet.submit(p, new_tokens)] = \
                    time.perf_counter()
        else:
            for i, p in enumerate(prompts):
                submit_t[fleet.submit(p, new_tokens)] = \
                    time.perf_counter()
                if i % 2 == 1:      # two arrivals per engine step
                    drink(fleet.step())
        while fleet.pending():
            drink(fleet.step())
        for fid in list(fleet.finished):
            results[fid] = fleet.pop_result(fid)
        wall = time.perf_counter() - t0
        return wall, ttft, results

    def p95(xs):
        return sorted(xs)[max(0, int(0.95 * len(xs)) - 1)] if xs \
            else 0.0

    def colocated(n, fleet_id):
        return LLMFleet(factory, initial_replicas=n,
                        router="pow2_affinity", fleet_id=fleet_id)

    def disagg(fleet_id, inj=None):
        return LLMFleet(factory, disaggregated=True,
                        prefill_replicas=prefill_replicas,
                        decode_replicas=decode_replicas,
                        router="pow2_affinity", fleet_id=fleet_id,
                        fault_injector=inj)

    # Untimed warmup per fleet SHAPE (colocated and split place
    # different prefix-chain lengths -> different compiled programs).
    churn(colocated(n_total, "disagg-warm-co"), arrivals[:4])
    churn(disagg("disagg-warm-dis"), arrivals[:4])

    co_fleet = colocated(n_total, "disagg-co")
    co_wall, co_ttft, co_res = churn(co_fleet, arrivals)
    dis_fleet = disagg("disagg-dis")
    dis_wall, dis_ttft, dis_res = churn(dis_fleet, arrivals)
    ds = dis_fleet.stats()
    # Idle-admission floor: one admission wave (every slot filled
    # before step 1), then pure decode on the decode-class replica
    # budget — no mid-decode prefill by construction.
    idle_n = min(len(arrivals), decode_replicas * batch_slots)
    idle_fleet = colocated(decode_replicas, "disagg-idle")
    _, _, _ = churn(idle_fleet, arrivals[:idle_n], upfront=True)

    # TPOT p95 from the engines' own sliding windows: colocated takes
    # the worst replica; disagg takes the worst DECODE-class replica
    # (prefill-class windows are empty — those engines never decode).
    co_tpot = max(r.engine.stats()["tpot_s_p95"]
                  for r in co_fleet.replicas)
    dis_tpot = max(r.engine.stats()["tpot_s_p95"]
                   for r in dis_fleet.replicas
                   if r.replica_class == "decode")
    idle_tpot = max(r.engine.stats()["tpot_s_p95"]
                    for r in idle_fleet.replicas)

    # Chaos arm: identical disagg shape and arrivals, first
    # decode-class replica scripted dead mid-churn. The fault-free
    # disagg arm above IS the control (same fid->key derivation).
    chaos_id = "disagg-chaos"
    killed = f"{chaos_id}-r{prefill_replicas}"   # first decode-class
    inj = FaultInjector(schedule={killed: [(3, "kill")]})
    chaos_fleet = disagg(chaos_id, inj=inj)
    chaos_wall, _, chaos_res = churn(chaos_fleet, arrivals)
    cs = chaos_fleet.stats()

    return {
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "n_requests": n_requests,
        "prefill_replicas": prefill_replicas,
        "decode_replicas": decode_replicas,
        "colocated_replicas": n_total,
        "wall_colocated_s": round(co_wall, 3),
        "wall_disagg_s": round(dis_wall, 3),
        "tpot_p95_colocated_s": round(co_tpot, 5),
        "tpot_p95_disagg_s": round(dis_tpot, 5),
        "tpot_p95_idle_s": round(idle_tpot, 5),
        # Headline gate pair: the control degrades under churn while
        # the split holds decode at its idle-admission floor.
        "tpot_p95_colocated_over_disagg": round(
            co_tpot / dis_tpot, 3) if dis_tpot else 0.0,
        "tpot_p95_disagg_over_idle": round(
            dis_tpot / idle_tpot, 3) if idle_tpot else 0.0,
        "gate_decode_tpot_shielded": bool(
            dis_tpot and idle_tpot
            and dis_tpot <= idle_tpot * tpot_idle_slack
            and co_tpot >= dis_tpot),
        "ttft_p95_colocated_s": round(p95(co_ttft), 4),
        "ttft_p95_disagg_s": round(p95(dis_ttft), 4),
        "ttft_p95_disagg_over_colocated": round(
            p95(dis_ttft) / p95(co_ttft), 3) if p95(co_ttft) else 0.0,
        "gate_ttft_no_worse": bool(
            p95(co_ttft) and p95(dis_ttft) <= p95(co_ttft)
            * ttft_slack),
        "handoffs": int(ds["handoffs"]),
        "handoff_out_bytes": int(ds["handoff_out_bytes"]),
        "handoff_parked_end": int(ds["handoff_parked"]),
        "ttft_p95_fleet_window_s": round(ds["ttft_s_p95_fleet"], 4),
        "chaos": {
            "killed_replica": killed,
            "kill_fired": bool(inj.fired),
            "identical_to_fault_free": chaos_res == dis_res,
            "tokens_lost_to_failure": int(
                cs["tokens_lost_to_failure"]),
            "requests_recovered": int(cs["requests_recovered"]),
            "replicas_failed": int(cs["replicas_failed"]),
            "replicas_decode_after": int(cs["replicas_decode"]),
            "handoff_parked_end": int(cs["handoff_parked"]),
            "wall_s": round(chaos_wall, 3),
            "wall_fault_free_s": round(dis_wall, 3),
        },
        # Same submit order -> same fid -> same pinned sampling key in
        # both fleets: the dicts must agree entry-for-entry.
        "identical_colocated_vs_disagg": co_res == dis_res,
    }


def _bench_multichip_serving(cfg, *, tps=(1, 2, 4), prompt_len: int,
                             new_tokens: int, batch_slots: int,
                             trials: int) -> dict:
    """Tensor-parallel engine serving throughput (the sharded-engine
    tentpole's end-to-end number): the SAME workloads at tp degrees 1,
    2 and 4 — steady-state fused decode (every slot live) and
    mid-flight churn (3x oversubscribed queue, ragged budgets) —
    with `host_transfer_bytes_per_token` alongside each rate. The
    engine's single [H,B] device->host choke point is pinned fully
    replicated, so bytes/token must stay FLAT as tp grows (the
    acceptance gate); a sharded engine whose host traffic scaled with
    chip count would lose on the wire what it won in the matmuls.

    tp=1 runs the PLAIN engine (mesh=None) — the unsharded control
    arm, not a 1-device mesh — so the sweep prices the sharding
    machinery itself, not just the chip count. Degrees that need more
    devices than the backend exposes report a skip instead of dying
    (the 8-device virtual CPU world covers the full sweep off-TPU).

    `llama_decode_tokens_per_sec_multichip` is the rename-safe
    SUCCESSOR key to `llama_decode_tokens_per_sec_1chip`: the 1chip
    serving block and all its keys are untouched; this section nests
    under it as ``multichip``."""
    import jax
    import numpy as np

    from ray_tpu.models import llama_init
    from ray_tpu.models.engine import DecodeEngine

    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(3)
    max_len = prompt_len + new_tokens + 1
    n_dev = len(jax.devices())

    # One fixed arrival set shared by every tp degree and trial, so
    # the sweep compares mesh shapes — not workloads.
    decode_prompts = [
        rng.randint(1, cfg.vocab_size, size=prompt_len).tolist()
        for _ in range(batch_slots)]
    churn_prompts = [
        rng.randint(1, cfg.vocab_size, size=prompt_len).tolist()
        for _ in range(3 * batch_slots)]

    def make_engine(tp):
        kw = {} if tp == 1 else {"tp": tp}
        return DecodeEngine(params, cfg, batch_slots=batch_slots,
                            max_len=max_len, enable_metrics=False, **kw)

    def spread_pct(rs):
        return ((max(rs) - min(rs)) / max(rs) * 100.0) if max(rs) else 0.0

    def drain(eng):
        toks = 0
        while eng.pending():
            ev = eng.step()
            toks += sum(len(t) for t in ev.values())
        return toks

    per_tp = {}
    for tp in tps:
        if tp > n_dev:
            per_tp[f"tp{tp}"] = {
                "skipped": f"needs {tp} devices, backend has {n_dev}"}
            continue
        # warmup: compile this tp's sharded prefill + fused decode —
        # the exact admission + drain sequence the timed trials run,
        # so every horizon they touch is already compiled.
        eng = make_engine(tp)
        for p in decode_prompts:
            eng.submit(p, new_tokens)
        eng.step(horizon=1)
        drain(eng)

        dec_rates, bpt = [], []
        for _ in range(trials):
            eng = make_engine(tp)
            for p in decode_prompts:
                eng.submit(p, new_tokens)
            eng.step(horizon=1)          # admission outside the clock
            t0 = time.perf_counter()
            toks = drain(eng)
            dt = time.perf_counter() - t0
            if toks:
                dec_rates.append(toks / dt)
            bpt.append(eng.stats()["host_transfer_bytes_per_token"])

        churn_rates = []
        for trial in range(trials + 1):  # +1 untimed warmup: churn
            eng = make_engine(tp)        # hits capped horizons and
            total = 0                    # group sizes steady decode
            for i, p in enumerate(churn_prompts):   # never compiled
                n = new_tokens if i % 2 == 0 else max(2, new_tokens // 2)
                eng.submit(p, n)
                total += n
            t0 = time.perf_counter()
            eng.run()
            if trial:
                churn_rates.append(total / (time.perf_counter() - t0))

        per_tp[f"tp{tp}"] = {
            "decode_tokens_per_sec": round(
                statistics.median(dec_rates), 1),
            "churn_tokens_per_sec": round(
                statistics.median(churn_rates), 1),
            "host_transfer_bytes_per_token": round(
                statistics.median(bpt), 2),
            "trial_spread_pct": round(spread_pct(dec_rates), 2),
        }

    ran = [k for k in per_tp if "skipped" not in per_tp[k]]
    top = per_tp[ran[-1]] if ran else {}
    base_bpt = per_tp.get("tp1", {}).get("host_transfer_bytes_per_token")
    top_bpt = top.get("host_transfer_bytes_per_token")
    return {
        "metric": "llama_decode_tokens_per_sec_multichip",
        "value": top.get("decode_tokens_per_sec", 0.0),
        "unit": "tokens/s",
        "tp_degrees_run": [int(k[2:]) for k in ran],
        "per_tp": per_tp,
        # The choke-point gate: bytes/token at the deepest tp over
        # tp1 — ~1.0 means host traffic did NOT grow with chip count.
        "host_bytes_per_token_tp_ratio": round(top_bpt / base_bpt, 3)
        if base_bpt else 0.0,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "batch_slots": batch_slots,
        "model_params": cfg.num_params(),
    }


def _spec_model_pair(cfg, draft_layers: int = 1):
    """(target_params, draft_params, draft_cfg) for the speculative
    churn: both models are built EMBEDDING-PASSTHROUGH — every layer's
    output projections (`wo`, `w_down`) are zeroed, so the residual
    stream is exactly the last token's embedding, and the draft shares
    the target's tok_embed / final_norm / lm_head. The two models then
    argmax-agree on every position BY CONSTRUCTION (high-acceptance
    churn) while the draft runs `draft_layers` of the target's
    `n_layers` — and zeroed weights change nothing about matmul cost,
    so the measured work ratio is the real draft/target ratio."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_init

    def passthrough(params):
        layers = dict(params["layers"])
        layers["wo"] = jnp.zeros_like(layers["wo"])
        layers["w_down"] = jnp.zeros_like(layers["w_down"])
        return {**params, "layers": layers}

    target = passthrough(llama_init(jax.random.PRNGKey(0), cfg))
    draft_cfg = dataclasses.replace(cfg, n_layers=draft_layers)
    draft = passthrough(llama_init(jax.random.PRNGKey(1), draft_cfg))
    for k in ("tok_embed", "final_norm", "lm_head"):
        draft[k] = target[k]
    return target, draft, draft_cfg


def _bench_spec(cfg, *, batch_slots: int, n_requests: int,
                new_tokens: int, trials: int, windows=(0, 2, 4),
                draft_layers: int = 1, prompt_len: int = 8) -> dict:
    """Speculative-decoding churn (the spec tentpole's end-to-end
    number): the same ragged-budget churn at every draft window in
    `windows` — window 0 is the plain engine (identical workload, no
    draft plane), so `spec_speedup` is window-best tokens/s over
    window-0 tokens/s on the SAME box, same prompts, same budgets.
    The model pair is the high-acceptance construction from
    `_spec_model_pair`; acceptance and effective window come straight
    off `engine.stats()`. Output identity across windows is asserted
    here too — a speedup that changed tokens would be meaningless."""
    import jax  # noqa: F401  (model pair builds devices lazily)
    import numpy as np

    from ray_tpu.models.engine import DecodeEngine

    target, draft, draft_cfg = _spec_model_pair(
        cfg, draft_layers=draft_layers)
    rng = np.random.RandomState(11)
    max_len = prompt_len + new_tokens + max(windows) + 1
    prompts = [rng.randint(1, cfg.vocab_size,
                           size=prompt_len).tolist()
               for _ in range(n_requests)]
    budgets = [new_tokens if i % 2 == 0 else max(2, new_tokens // 2)
               for i in range(n_requests)]

    def spread_pct(rs):
        return ((max(rs) - min(rs)) / max(rs) * 100.0) if max(rs) else 0.0

    per_window, outputs = {}, {}
    for w in windows:
        kw = dict(draft_params=draft, draft_cfg=draft_cfg,
                  spec_window=w) if w else {}
        rates = []
        for trial in range(trials + 1):
            eng = DecodeEngine(target, cfg, batch_slots=batch_slots,
                               max_len=max_len, enable_metrics=False,
                               **kw)
            ids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
            t0 = time.perf_counter()
            out = eng.run()
            dt = time.perf_counter() - t0
            if trial:
                rates.append(sum(budgets) / dt)
        outputs[w] = [out[i] for i in ids]
        s = eng.stats()
        per_window[f"window{w}"] = {
            "churn_tokens_per_sec": round(statistics.median(rates), 1),
            "spec_acceptance_rate": round(s["spec_acceptance_rate"], 4),
            "spec_window_effective": round(s["spec_window_effective"],
                                           3),
            "spec_dispatches": int(s["spec_dispatches"]),
            "trial_spread_pct": round(spread_pct(rates), 2),
        }
    for w in windows:
        assert outputs[w] == outputs[windows[0]], \
            f"speculation changed tokens at window={w}"
    base = per_window[f"window{windows[0]}"]["churn_tokens_per_sec"]
    best_w = max(windows,
                 key=lambda w:
                 per_window[f"window{w}"]["churn_tokens_per_sec"])
    best = per_window[f"window{best_w}"]["churn_tokens_per_sec"]
    return {
        "metric": "llama_decode_tokens_per_sec_spec",
        "value": best,
        "unit": "tokens/s",
        "windows": list(windows),
        "per_window": per_window,
        "best_window": best_w,
        "spec_speedup": round(best / base, 3) if base else 0.0,
        "spec_acceptance_rate":
            per_window[f"window{best_w}"]["spec_acceptance_rate"],
        "draft_layers": draft_layers,
        "target_layers": cfg.n_layers,
        "n_requests": n_requests,
        "new_tokens": new_tokens,
        "batch_slots": batch_slots,
        "outputs_identical_across_windows": True,
    }


def _bench_lora(cfg, *, n_adapters: int, max_live: int,
                batch_slots: int, n_requests: int, new_tokens: int,
                trials: int, rank: int = 8, zipf_s: float = 1.1,
                prompt_len: int = 8) -> dict:
    """Multi-LoRA churn (the adapter-pool tentpole's end-to-end
    number): Zipf-distributed traffic over `n_adapters` fine-tunes
    through ONE engine whose HBM holds only `max_live` of them, vs the
    one-replica-per-adapter baseline — each adapter's requests on a
    dedicated merged-weight engine, run back to back (what a fleet
    without multi-LoRA must do on the same chip budget). The speedup
    comes from cross-adapter batching: the fused dispatch fills its
    slots from EVERY adapter's queue while the baseline's per-adapter
    engines decode their long tail at batch size ~1. Token identity
    between the two is asserted — a speedup that changed tokens would
    be meaningless. `adapter_hit_frac` and `prefetch_stall_frac`
    (admission deferrals per request) come straight off
    `engine.stats()` and size the residency knob: a hot Zipf head
    keeps the hit rate high even at max_live << n_adapters."""
    import jax
    import numpy as np

    from ray_tpu.models import (LoraConfig, llama_init, lora_init,
                                lora_merge)
    from ray_tpu.models.engine import DecodeEngine

    lcfg = LoraConfig(rank=rank)
    rng = np.random.RandomState(13)
    key = jax.random.PRNGKey(17)
    params = llama_init(jax.random.PRNGKey(0), cfg)

    def rand_lora(k):
        lp = lora_init(k, cfg, lcfg)
        leaves, tree = jax.tree_util.tree_flatten(lp)
        ks = jax.random.split(k, len(leaves))
        return jax.tree_util.tree_unflatten(tree, [
            jax.random.normal(kk, l.shape, l.dtype) * 0.02
            for kk, l in zip(ks, leaves)])

    keys = jax.random.split(key, n_adapters)
    loras = {f"ft{i}": rand_lora(keys[i]) for i in range(n_adapters)}

    # Zipf over adapter ranks: p(k) ~ 1/k^s — the classic multi-tenant
    # traffic shape (a hot head, a long cold tail).
    p = 1.0 / np.arange(1, n_adapters + 1) ** zipf_s
    p /= p.sum()
    aids = [f"ft{i}" for i in rng.choice(n_adapters, size=n_requests,
                                         p=p)]
    prompts = [rng.randint(1, cfg.vocab_size, size=prompt_len).tolist()
               for _ in range(n_requests)]
    max_len = prompt_len + new_tokens + 2

    def spread_pct(rs):
        return ((max(rs) - min(rs)) / max(rs) * 100.0) if max(rs) else 0.0

    # --- multi-LoRA engine: all adapters through one fused batch ----
    multi_rates, multi_out, stats = [], None, None
    for trial in range(trials + 1):
        eng = DecodeEngine(params, cfg, batch_slots=batch_slots,
                           max_len=max_len, enable_metrics=False,
                           lora=lcfg, max_live_adapters=max_live)
        for a, lp in loras.items():
            eng.register_adapter(a, lp)
        t0 = time.perf_counter()
        ids = [eng.submit(pr, new_tokens, adapter_id=a)
               for pr, a in zip(prompts, aids)]
        out = eng.run()
        dt = time.perf_counter() - t0
        if trial:
            multi_rates.append(n_requests * new_tokens / dt)
        multi_out = [out[i] for i in ids]
        stats = eng.stats()

    # --- baseline: one dedicated merged-weight engine per adapter ---
    merged = {a: lora_merge(params, lp, cfg, lcfg)
              for a, lp in loras.items()}
    groups = {}
    for i, a in enumerate(aids):
        groups.setdefault(a, []).append(i)
    base_engines = {a: DecodeEngine(merged[a], cfg,
                                    batch_slots=batch_slots,
                                    max_len=max_len,
                                    enable_metrics=False)
                    for a in groups}
    base_rates, base_out = [], [None] * n_requests
    for trial in range(trials + 1):
        dt = 0.0
        for a, rows in groups.items():
            eng = base_engines[a]
            t0 = time.perf_counter()
            ids = [eng.submit(prompts[i], new_tokens) for i in rows]
            out = eng.run()
            dt += time.perf_counter() - t0
            for i, rid in zip(rows, ids):
                base_out[i] = out[rid]
        if trial:
            base_rates.append(n_requests * new_tokens / dt)

    assert multi_out == base_out, \
        "multi-LoRA engine diverged from merged-weight baseline"
    multi = statistics.median(multi_rates)
    base = statistics.median(base_rates)
    lookups = max(stats["adapter_lookups"], 1.0)
    return {
        "metric": "llama_decode_tokens_per_sec_multilora",
        "value": round(multi, 1),
        "unit": "tokens/s",
        "baseline_one_engine_per_adapter_tokens_per_sec":
            round(base, 1),
        "multilora_speedup": round(multi / base, 3) if base else 0.0,
        "adapter_hit_frac": round(
            stats["adapter_hits"] / lookups, 4),
        "prefetch_stall_frac": round(
            stats["adapter_prefetch_deferrals"] / n_requests, 4),
        "adapter_evictions": int(stats["adapter_evictions"]),
        "n_adapters": n_adapters,
        "max_live_adapters": max_live,
        "adapters_touched": len(groups),
        "zipf_s": zipf_s,
        "rank": rank,
        "n_requests": n_requests,
        "new_tokens": new_tokens,
        "batch_slots": batch_slots,
        "trial_spread_pct": round(spread_pct(multi_rates), 2),
        "outputs_identical_to_baseline": True,
    }


def main():
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from ray_tpu.models import LlamaConfig

    on_tpu = jax.default_backend() == "tpu"
    # Off-TPU there is no chip and so no peak: the smoke branch's MFU
    # reads 0.0 (S1 removes that branch and its device-metric names).
    peak = _detect_peak() if on_tpu else float("inf")
    gate = _quiesce() if on_tpu else {"load": 0.0, "load_initial": 0.0,
                                      "waited_s": 0.0, "settled": True}

    if on_tpu:
        # No section is guarded: one that fails fails the run.
        devices = jax.devices()[:1]
        base = _bench_config(flagship_config(), batch_size=8, seq_len=2048,
                             steps=20, trials=TRIALS, devices=devices,
                             peak=peak)
        large = _bench_config(large_config(), batch_size=4, seq_len=2048,
                              steps=10, trials=TRIALS,
                              devices=devices, peak=peak)
        serving = _bench_serving(
            flagship_config(), batch_sizes=(1, 8, 16),
            prompt_len=512, new_tokens=64, trials=TRIALS)
        serving["prefix_cache"] = _bench_prefix(
            flagship_config(), prefix_len=512, suffix_len=32,
            batch_slots=8, n_requests=24, new_tokens=64,
            trials=TRIALS)
        serving["paged"] = _bench_paged(
            flagship_config(), prefix_len=512, suffix_len=32,
            batch_slots=8, n_requests=32, new_tokens=64,
            trials=TRIALS)
        serving["kv_quant"] = _bench_kv_quant(
            flagship_config(), prompt_len=128, batch_slots=8,
            n_requests=16, new_tokens=64, trials=TRIALS)
        serving["fleet"] = _bench_fleet(
            flagship_config(), n_groups=4, prefix_len=256,
            suffix_len=32, n_requests=48, new_tokens=32,
            batch_slots=4)
        serving["disagg"] = _bench_disagg(
            flagship_config(), prompt_len=256, new_tokens=64,
            n_requests=48, batch_slots=8, prefill_replicas=2,
            decode_replicas=2)
        serving["multichip"] = _bench_multichip_serving(
            flagship_config(), tps=(1, 2, 4), prompt_len=256,
            new_tokens=32, batch_slots=8, trials=TRIALS)
        serving["speculative"] = _bench_spec(
            flagship_config(), batch_slots=8, n_requests=16,
            new_tokens=64, trials=TRIALS)
        serving["multilora"] = _bench_lora(
            flagship_config(), n_adapters=32, max_live=8,
            batch_slots=8, n_requests=64, new_tokens=32,
            trials=TRIALS)
    else:  # smoke mode off-TPU
        # The module-top flag forces 8 virtual CPU devices for the tp
        # sweep; the train smoke stays single-device (its historical
        # shape — batch 4 doesn't divide a dp=8 mesh).
        devices = jax.devices()[:1]
        base = _bench_config(LlamaConfig.nano(), batch_size=4, seq_len=128,
                             steps=3, trials=1, devices=devices, peak=peak)
        large = {"skipped": "no TPU"}
        serving = _bench_serving(LlamaConfig.nano(), batch_sizes=(2, 4),
                                 prompt_len=16, new_tokens=8, trials=1)
        serving["dry_run"] = True
        # Shared-prefix workload, CPU dry run: the flagship shape (512
        # shared tokens) on the nano model — the reuse FRACTION and the
        # cache-on/off churn ratio are real on any backend.
        serving["prefix_cache"] = _bench_prefix(
            LlamaConfig.nano(max_seq_len=1024), prefix_len=512,
            suffix_len=16, batch_slots=4, n_requests=8, new_tokens=8,
            trials=1)
        # Paged-KV workload, CPU dry run: warm-admission latency ratio
        # (incref vs d2d gather), the zero-copy/CoW counters, and the
        # preemption-pressure throughput fraction are real on any
        # backend; absolute tokens/s is not.
        serving["paged"] = _bench_paged(
            LlamaConfig.nano(max_seq_len=1024), prefix_len=64,
            suffix_len=16, batch_slots=4, n_requests=16, new_tokens=8,
            trials=1, block_tokens=16)
        # Quantized-KV workload, CPU dry run: the concurrency ratio at
        # fixed kv_pool_bytes, the token-match quality gate, and the
        # swap-traffic ratio are layout facts — real on any backend;
        # absolute tokens/s is not.
        serving["kv_quant"] = _bench_kv_quant(
            LlamaConfig.nano(max_seq_len=256), prompt_len=16,
            batch_slots=4, n_requests=8, new_tokens=8, trials=1,
            block_tokens=8)
        # Fleet churn, CPU dry run: 2 and 4 replicas over shared-
        # prefix + mixed-priority traffic — the router comparison
        # (affinity vs round-robin TTFT p95) and the shed rate are
        # real on any backend; absolute tokens/s is not.
        serving["fleet"] = _bench_fleet(
            LlamaConfig.nano(max_seq_len=256), n_groups=4,
            prefix_len=192, suffix_len=8, n_requests=24, new_tokens=8,
            batch_slots=4)
        # Disaggregated prefill/decode churn, CPU dry run: the TPOT
        # shielding ratio (colocated control degrades under admission
        # churn while the decode class holds its idle-admission
        # floor), the bench-side TTFT ratio, the token-identity and
        # chaos zero-loss gates are real on any backend; absolute
        # tokens/s is not.
        serving["disagg"] = _bench_disagg(
            LlamaConfig.nano(max_seq_len=256), prompt_len=128,
            new_tokens=64, n_requests=24, batch_slots=12,
            prefill_replicas=3, decode_replicas=2, block_tokens=32,
            tpot_idle_slack=2.0, ttft_slack=1.5)
        # Tensor-parallel sweep, CPU dry run: tp in {1,2,4} over the
        # forced 8-device world — the bytes/token FLATNESS across tp
        # (the choke-point gate) is real on any backend; absolute
        # tokens/s is not.
        serving["multichip"] = _bench_multichip_serving(
            LlamaConfig.nano(), tps=(1, 2, 4), prompt_len=16,
            new_tokens=8, batch_slots=2, trials=1)
        # Speculative churn, CPU dry run: a 16-layer passthrough target
        # with a 1-layer draft — the speedup RATIO (same box, same
        # workload, window 0 vs best) and the acceptance rate are real
        # on any backend; absolute tokens/s is not. Budgets are
        # multiples of window+1 so no final round truncates acceptance.
        serving["speculative"] = _bench_spec(
            LlamaConfig.nano(n_layers=16, dim=128, ffn_dim=256),
            batch_slots=4, n_requests=8, new_tokens=60, trials=2)
        # Multi-LoRA churn, CPU dry run: Zipf traffic over 8 adapters
        # with residency for 3 — the adapter hit fraction, the
        # prefetch-stall fraction, and the baseline token-identity
        # check are real on any backend; the speedup ratio is NOT (on
        # a nano model the rank-r delta einsums rival the base matmuls
        # they ride on — the cross-adapter batching win needs real
        # model scale, where base FLOPs dwarf the delta's).
        serving["multilora"] = _bench_lora(
            LlamaConfig.nano(), n_adapters=8, max_live=3,
            batch_slots=4, n_requests=16, new_tokens=8, trials=1,
            rank=4)

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
        "backend": jax.default_backend(),
        "loss": base["loss"],
    }
    for k, v in large.items():
        out[f"large_{k}"] = v
    serving.setdefault("backend", jax.default_backend())
    serving["host_load_at_start"] = round(gate["load"], 2)
    # graftlint sweep over the serving tree: tracked scalar so a hot-path
    # violation regression shows up in the bench record, not just CI.
    from ray_tpu._private.lint import RULE_REGISTRY, lint_paths

    _lint_report = lint_paths(
        ["ray_tpu/models", "ray_tpu/serve", "ray_tpu/util"])
    serving["lint_violations_total"] = (
        len(_lint_report.open) + len(_lint_report.errors))
    # Per-rule open counts: a regression names its analyzer directly
    # (all zero on a clean tree, so the keys are stable).
    _by_rule = {}
    for _f in _lint_report.open:
        _by_rule[_f.rule] = _by_rule.get(_f.rule, 0) + 1
    for _rule in sorted(RULE_REGISTRY):
        serving[f"lint_open_{_rule.replace('-', '_')}"] = (
            _by_rule.get(_rule, 0))
    serving["device"] = out["device"] = _device_record()
    # Serving block on its own line; the train block stays the LAST
    # line (the driver's historical parse contract).
    print(json.dumps(serving))
    print(json.dumps(out))


if __name__ == "__main__":
    main()

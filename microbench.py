"""Core-runtime microbenchmarks (reference: python/ray/_private/ray_perf.py
+ release/microbenchmark/): task throughput, actor call latency, object
store put/get bandwidth. Prints one JSON line per metric.

Each metric is measured over several trials and reported as the MEDIAN:
this box runs co-tenant load (round-3 verdict: a single capture swung 2x
under background activity), so single-shot numbers are noise.

Run: python microbench.py [--quick]
"""

import json
import os
import statistics
import sys
import time

# The sharded-dispatch section sweeps tensor-parallel degree; off-TPU
# that needs a forced multi-device CPU world, set before jax initializes
# (if jax is already up with fewer devices the section skips tp=4).
if "jax" not in sys.modules and "--xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        (os.environ.get("XLA_FLAGS", "") +
         " --xla_force_host_platform_device_count=8").strip())

TRIALS = 3


def timed_median(fn, n, trials=TRIALS):
    rates = []
    for _ in range(trials):
        t0 = time.perf_counter()
        fn()
        rates.append(n / (time.perf_counter() - t0))
    return statistics.median(rates)


def _decode_dispatch_section(quick: bool) -> list:
    """Decode-step dispatch overhead for the fused serving engine
    (models/engine.py): per-step WALL time (engine.step: host
    bookkeeping + dispatch + the one [H, B] token-block transfer +
    replay) vs DEVICE time (the bare jitted _decode_multi program,
    chained through its donated buffers), plus transfers per token, at
    horizon 1 (the historical per-token cadence) and the default 8.
    wall - device is the per-step host tax the fused horizon amortizes.
    Runs anywhere — `JAX_PLATFORMS=cpu python microbench.py` included
    (nano model; the OVERHEAD is host-side and real on any backend)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine, _decode_multi

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    B, prompt_len, new_tokens = 4, 16, 16 if quick else 64
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, size=prompt_len).tolist()
               for _ in range(B)]
    max_len = prompt_len + new_tokens + 1
    results = []

    def fill(horizon):
        # pipeline_depth=1: this section measures the SYNCHRONOUS
        # per-step cost (dispatch + blocking pull + replay); the
        # pipelined overlap is measured by _dispatch_gap_section.
        eng = DecodeEngine(params, cfg, batch_slots=B, max_len=max_len,
                           decode_horizon=horizon, pipeline_depth=1,
                           enable_metrics=False)
        for p in prompts:
            eng.submit(p, new_tokens)
        eng.step(horizon=1)          # admit all rows (+1 token each)
        return eng

    for H in (1, 8):
        fill(H).run()                # warmup: compile prefill + this H

        # WALL: full engine steps, horizon pinned; count tokens (a
        # fused step emits up to H per row).
        wall_ms, toks, steps = [], 0, 0
        for _ in range(TRIALS):
            eng = fill(H)
            t0 = time.perf_counter()
            while eng.pending():
                ev = eng.step(horizon=H)
                steps += 1
                toks += sum(len(t) for t in ev.values())
            wall_ms.append((time.perf_counter() - t0) * 1000)
        n_steps = steps // TRIALS
        wall = statistics.median(wall_ms) / max(1, n_steps)
        syncs_per_tok = eng.stats()["host_syncs_per_token"]

        # DEVICE: the bare fused program, chained through its donated
        # cache/last_logits (no host replay, no block pull beyond the
        # final sync).
        eng = fill(H)
        dev_ms = []
        args = (jnp.asarray(eng.row_len),
                jnp.asarray(np.array([True] * B)),
                jnp.asarray(eng.row_budget + 10_000),
                jnp.asarray(eng._tok_idx), jnp.asarray(eng._row_keys))
        cache, last = eng.cache, eng._last_logits
        for _ in range(TRIALS):
            t0 = time.perf_counter()
            for _ in range(n_steps):
                toks_d, cache, last, *_rest = _decode_multi(
                    eng.params, cache, last, *args,
                    jnp.asarray(np.array([True] * B)), eng.temperature,
                    cfg, H, True, None, None, None)
            jax.block_until_ready(toks_d)
            dev_ms.append((time.perf_counter() - t0) * 1000 /
                          max(1, n_steps))
        dev = statistics.median(dev_ms)

        results.append((f"engine_decode_wall_ms_per_step_h{H}",
                        wall, "ms"))
        results.append((f"engine_decode_device_ms_per_step_h{H}",
                        dev, "ms"))
        results.append((f"engine_decode_host_overhead_ms_per_step_h{H}",
                        max(0.0, wall - dev), "ms"))
        results.append((f"engine_decode_transfers_per_token_h{H}",
                        syncs_per_tok, "syncs/token"))
    return results


def _spec_dispatch_section(quick: bool) -> list:
    """ONE speculative dispatch vs window+1 plain dispatches: the spec
    engine's whole round (draft scan of W proposals + one batched
    verify + on-device acceptance) is a single program launch emitting
    up to W+1 verified tokens per row, where the horizon-1 plain
    engine pays W+1 separate dispatch+drain round trips for the same
    tokens. Draft == target (perfect acceptance), so the token counts
    divide exactly and the per-token ratio isolates the dispatch
    amortization — the host-side overhead is real on any backend.
    pipeline_depth=1 on both engines: this measures the synchronous
    cost; run-ahead overlap is _dispatch_gap_section's job."""
    import jax  # noqa: F401
    import numpy as np

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    B, prompt_len, W = 4, 16, 4
    new_tokens = 20 if quick else 40     # multiples of W+1: no
    #                                      truncated final round
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, size=prompt_len).tolist()
               for _ in range(B)]
    max_len = prompt_len + new_tokens + W + 1

    def make(spec):
        kw = (dict(draft_params=params, draft_cfg=cfg, spec_window=W)
              if spec else dict(decode_horizon=1))
        eng = DecodeEngine(params, cfg, batch_slots=B, max_len=max_len,
                           pipeline_depth=1, enable_metrics=False,
                           **kw)
        for p in prompts:
            eng.submit(p, new_tokens)
        return eng

    per_tok = {}
    results = []
    for spec in (False, True):
        make(spec).run()                 # warmup: compile this path
        ms = []
        for _ in range(TRIALS):
            eng = make(spec)
            t0 = time.perf_counter()
            eng.run()
            ms.append((time.perf_counter() - t0) * 1000)
        med = statistics.median(ms)
        total = B * new_tokens
        per_tok[spec] = med / total
        s = eng.stats()
        if spec:
            disp = max(1, int(s["spec_dispatches"]))
            results.append((f"engine_spec_wall_ms_per_dispatch_w{W}",
                            med / disp, "ms"))
            results.append((f"engine_spec_tokens_per_dispatch_w{W}",
                            total / disp, "tokens"))
            results.append((f"engine_spec_acceptance_rate_w{W}",
                            s["spec_acceptance_rate"], "frac"))
            results.append((f"engine_spec_ms_per_token_w{W}",
                            per_tok[True], "ms"))
        else:
            results.append(("engine_plain_ms_per_token_h1",
                            per_tok[False], "ms"))
    results.append((f"engine_spec_dispatch_speedup_w{W}_vs_h1",
                    per_tok[False] / per_tok[True]
                    if per_tok[True] else 0.0, "x"))
    return results


def _sharded_dispatch_section(quick: bool) -> list:
    """Per-step cost of the TENSOR-PARALLEL engine vs the plain one:
    wall ms/step (engine.step over a tp mesh: host bookkeeping +
    sharded dispatch + the one replicated [H, B] token-block pull) and
    device ms/step (the bare jitted _decode_multi with the engine's
    NamedShardings, chained through its donated buffers) at tp=1 (the
    unsharded control) and tp=4, plus host bytes/token at each degree.
    The gate: the host-side numbers must NOT scale with chip count —
    the choke point stays one replicated block pull per fused step, so
    bytes/token is flat and wall - device stays the same host tax the
    plain engine pays. Runs anywhere (the module-top flag forces an
    8-device CPU world; skips tp=4 if the backend has fewer devices)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine, _decode_multi

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    B, prompt_len, H = 4, 16, 8
    new_tokens = 16 if quick else 64
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, size=prompt_len).tolist()
               for _ in range(B)]
    max_len = prompt_len + new_tokens + 1
    results = []

    def fill(tp):
        # pipeline_depth=1: the synchronous per-step cost is the
        # number under test (overlap is _dispatch_gap_section's job);
        # tp=1 is the PLAIN engine, not a 1-device mesh, so the sweep
        # prices the sharding machinery itself.
        kw = {} if tp == 1 else {"tp": tp}
        eng = DecodeEngine(params, cfg, batch_slots=B, max_len=max_len,
                           decode_horizon=H, pipeline_depth=1,
                           enable_metrics=False, **kw)
        for p in prompts:
            eng.submit(p, new_tokens)
        eng.step(horizon=1)          # admit all rows (+1 token each)
        return eng

    for tp in (1, 4):
        if tp > len(jax.devices()):
            continue
        fill(tp).run()               # warmup: compile prefill + decode

        wall_ms, toks, steps = [], 0, 0
        for _ in range(TRIALS):
            eng = fill(tp)
            t0 = time.perf_counter()
            while eng.pending():
                ev = eng.step(horizon=H)
                steps += 1
                toks += sum(len(t) for t in ev.values())
            wall_ms.append((time.perf_counter() - t0) * 1000)
        n_steps = steps // TRIALS
        wall = statistics.median(wall_ms) / max(1, n_steps)
        bytes_per_tok = eng.stats()["host_transfer_bytes_per_token"]

        # DEVICE: the bare fused program under this tp's shardings,
        # chained through its donated cache/last_logits.
        eng = fill(tp)
        dev_ms = []
        args = (jnp.asarray(eng.row_len),
                jnp.asarray(np.array([True] * B)),
                jnp.asarray(eng.row_budget + 10_000),
                jnp.asarray(eng._tok_idx), jnp.asarray(eng._row_keys))
        cache, last = eng.cache, eng._last_logits
        for _ in range(TRIALS):
            t0 = time.perf_counter()
            for _ in range(n_steps):
                toks_d, cache, last, *_rest = _decode_multi(
                    eng.params, cache, last, *args,
                    jnp.asarray(np.array([True] * B)), eng.temperature,
                    cfg, H, True, None, None, None,
                    shardings=eng._shardings)
            jax.block_until_ready(toks_d)
            dev_ms.append((time.perf_counter() - t0) * 1000 /
                          max(1, n_steps))
        dev = statistics.median(dev_ms)

        results.append((f"engine_sharded_wall_ms_per_step_tp{tp}",
                        wall, "ms"))
        results.append((f"engine_sharded_device_ms_per_step_tp{tp}",
                        dev, "ms"))
        results.append((f"engine_sharded_host_bytes_per_token_tp{tp}",
                        bytes_per_tok, "bytes/token"))
    return results


def _dispatch_gap_section(quick: bool) -> list:
    """Host gap between consecutive fused-decode DISPATCHES — the
    window in which the device has NOTHING queued and starves on host
    bookkeeping — sync (pipeline_depth=1) vs pipelined (depth=2), on a
    pure-decode workload (all slots admitted up front, queue empty).

    Measured from the engine's own host event stream: each blocking
    token-block pull (`_device_get`) that leaves ZERO dispatched
    programs in flight opens a starvation window, closed by the next
    `_decode_multi` launch. The synchronous loop opens one EVERY block
    (pull, then the whole O(H*B) replay, then dispatch — the device
    idles throughout); the pipelined loop dispatches step N+1 BEFORE
    pulling step N, so a pull almost never drains the device dry and
    the per-block gap collapses to ~0 (flush points are the residue).
    CPU dry-run capable: the gap is host-side wall time and the
    dispatch-before-pull inversion is real on any backend
    (`JAX_PLATFORMS=cpu python microbench.py`)."""
    import jax
    import numpy as np

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models import engine as engine_mod
    from ray_tpu.models.engine import DecodeEngine

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    B, prompt_len = 4, 16
    new_tokens = 32 if quick else 128
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, size=prompt_len).tolist()
               for _ in range(B)]
    max_len = prompt_len + new_tokens + 1

    def drive(depth):
        eng = DecodeEngine(params, cfg, batch_slots=B, max_len=max_len,
                           decode_horizon=8, pipeline_depth=depth,
                           enable_metrics=False)
        for p in prompts:
            eng.submit(p, new_tokens)
        eng.run()

    def starvation_gaps(events):
        """events: ("dispatch", t) at launch / ("get", t) at pull
        return. A pull that leaves in-flight == 0 starts a starvation
        window; the next dispatch ends it."""
        gaps, inflight, open_t = [], 0, None
        for kind, t in events:
            if kind == "dispatch":
                if open_t is not None:
                    gaps.append((t - open_t) * 1000)
                    open_t = None
                inflight += 1
            else:
                inflight -= 1
                if inflight == 0:
                    open_t = t
        return gaps

    results = []
    real_multi = engine_mod._decode_multi
    real_get = engine_mod._device_get
    for depth in (1, 2):
        drive(depth)                 # warmup: compile every program
        events = []

        def timed_multi(*a, **k):
            events.append(("dispatch", time.perf_counter()))
            return real_multi(*a, **k)

        def timed_get(x):
            out = real_get(x)
            events.append(("get", time.perf_counter()))
            return out

        engine_mod._decode_multi = timed_multi
        engine_mod._device_get = timed_get
        gaps = []
        try:
            for _ in range(TRIALS):
                events.clear()       # windows never span engines
                drive(depth)
                gaps.extend(starvation_gaps(events))
        finally:
            engine_mod._decode_multi = real_multi
            engine_mod._device_get = real_get
        # Mean, not median: the pipelined loop's distribution is mostly
        # exact zeros (pre-dispatched blocks) with a few flush-point
        # gaps — the mean keeps that residue visible instead of
        # reporting a flat 0.
        results.append((f"engine_dispatch_gap_ms_d{depth}",
                        statistics.fmean(gaps) if gaps else 0.0,
                        "ms"))
    return results


def _prefix_admission_section(quick: bool) -> list:
    """Admission cost with the shared-prefix KV cache
    (models/engine.py + models/prefix_cache.py): per prefix length,
    the wall ms and host syncs of admitting a request COLD (full
    prompt prefill, pool copy-out of the novel blocks) vs WARM (pool
    copy-in of the cached blocks + suffix-only prefill). The gap is
    what prefix reuse buys every repeat of a system prompt. Runs
    anywhere — the nano model makes the prefill cost small but the
    cold/warm ORDERING and the sync counts are real on any backend."""
    import jax
    import numpy as np

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine

    lens = (128,) if quick else (128, 512, 2048)
    suffix_len, new_tokens, T = 16, 4, 32
    results = []
    for P in lens:
        cfg = LlamaConfig.nano(max_seq_len=P + suffix_len + new_tokens + 8)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(P)
        prefix = rng.randint(1, cfg.vocab_size, size=P).tolist()

        def make():
            return DecodeEngine(params, cfg, batch_slots=2,
                                max_len=cfg.max_seq_len,
                                prefix_cache=True, prefix_block=T,
                                enable_metrics=False)

        def admit_once(eng):
            """Submit one prefix+fresh-suffix request, time its
            admission step, return (ms, host syncs)."""
            p = prefix + rng.randint(1, cfg.vocab_size,
                                     size=suffix_len).tolist()
            rid = eng.submit(p, new_tokens)
            syncs0 = eng.host_syncs
            t0 = time.perf_counter()
            eng.step(horizon=1)
            ms = (time.perf_counter() - t0) * 1000
            syncs = eng.host_syncs - syncs0
            while eng.pending():          # drain so the slot frees
                eng.step(horizon=1)
            eng.pop_result(rid)
            return ms, syncs

        admit_once(make())                # warmup eng: compile cold path
        warm_eng = make()
        admit_once(warm_eng)              # seed + compile warm path
        admit_once(warm_eng)

        cold_ms, warm_ms = [], []
        cold_syncs = warm_syncs = 0
        for _ in range(TRIALS):
            eng = make()                  # empty trie: first is cold
            ms, cold_syncs = admit_once(eng)
            cold_ms.append(ms)
            ms, warm_syncs = admit_once(eng)   # trie now holds prefix
            warm_ms.append(ms)
        results.append((f"engine_prefix_admission_cold_ms_p{P}",
                        statistics.median(cold_ms), "ms"))
        results.append((f"engine_prefix_admission_warm_ms_p{P}",
                        statistics.median(warm_ms), "ms"))
        results.append((f"engine_prefix_admission_cold_syncs_p{P}",
                        float(cold_syncs), "syncs"))
        results.append((f"engine_prefix_admission_warm_syncs_p{P}",
                        float(warm_syncs), "syncs"))
    return results


def _paged_gather_section(quick: bool) -> list:
    """Block-table-gather overhead of paged attention
    (ops/attention.py `paged_attention` vs the dense
    `_cached_attention` it must stay in op-for-op lockstep with): per
    max_len span, the wall ms of one fused decode-shaped attention
    over (a) a contiguous dense cache row and (b) the same K/V read
    through a per-row block table out of a 4x-oversized pool. The
    delta is the pure cost of the paged indirection — the price the
    engine pays per decode step for pool-bounded admission and
    zero-copy prefix shares. Runs anywhere: on CPU both lower to the
    same XLA reference einsums, so the gather overhead is the real
    quantity measured; Mosaic kernels change the constant, not the
    comparison's meaning."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import _cached_attention
    from ray_tpu.ops.attention import paged_attention

    B, H, KV, D, T = 8, 4, 2, 16, 16
    spans = (256,) if quick else (256, 1024)
    results = []
    for span in spans:
        MB = span // T
        NB = 4 * MB + 1                    # 4x oversized pool + null
        key = jax.random.PRNGKey(span)
        q = jax.random.normal(key, (B, 1, H, D), jnp.float32)
        dense_k = jax.random.normal(key, (B, span, KV, D), jnp.float32)
        dense_v = dense_k + 1.0
        # the engine's layout: [L, NB, T, KV*D], one layer here
        pool_k = jax.random.normal(key, (1, NB, T, KV * D), jnp.float32)
        pool_v = pool_k + 1.0
        # scattered tables: stride the pool so the gather is non-unit
        bt = (1 + (jnp.arange(B * MB) * 7) % (NB - 1)).reshape(B, MB)
        bt = bt.astype(jnp.int32)
        slots = jnp.full((B, 1), span - 1, jnp.int32)

        dense_fn = jax.jit(lambda q, k, v: _cached_attention(
            q, k, v, slots, span, None))
        paged_fn = jax.jit(lambda q, k, v: paged_attention(
            q, k, v, bt, slots, layer=0, kv_valid_len=span))
        dense_fn(q, dense_k, dense_v).block_until_ready()
        paged_fn(q, pool_k, pool_v).block_until_ready()

        def run(fn, *args):
            ts = []
            for _ in range(TRIALS):
                t0 = time.perf_counter()
                for _ in range(20):
                    out = fn(*args)
                out.block_until_ready()
                ts.append((time.perf_counter() - t0) / 20 * 1000)
            return statistics.median(ts)

        d_ms = run(dense_fn, q, dense_k, dense_v)
        p_ms = run(paged_fn, q, pool_k, pool_v)
        results.append((f"paged_attention_dense_ms_s{span}", d_ms,
                        "ms"))
        results.append((f"paged_attention_paged_ms_s{span}", p_ms,
                        "ms"))
        results.append((f"paged_attention_gather_overhead_pct_s{span}",
                        (p_ms - d_ms) / d_ms * 100.0 if d_ms else 0.0,
                        "%"))
    return results


def _kv_quant_gather_section(quick: bool) -> list:
    """Per-step cost of dequant-in-gather paged attention
    (ops/kv_quant.py + ops/attention.py): the same decode-shaped
    block-table attention as `_paged_gather_section`, read (a) from a
    dense f32 pool and (b) from an int8 pool with per-block scales
    dequantized INSIDE the gather. The delta is the pure price of the
    widening multiply the quantized plane pays per decode step — buying
    ~2x pool blocks per HBM byte (bench.py `kv_quant` section reports
    the concurrency side). Runs anywhere: both lower to the same XLA
    reference einsums off-TPU, so the dequant overhead measured is the
    real added op count, not a kernel artifact."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import paged_attention
    from ray_tpu.ops.kv_quant import (block_scale, quantize,
                                      resolve_kv_quant)

    B, H, KV, D, T = 8, 4, 2, 16, 16
    spans = (256,) if quick else (256, 1024)
    qspec = resolve_kv_quant("int8")
    results = []
    for span in spans:
        MB = span // T
        NB = 4 * MB + 1
        key = jax.random.PRNGKey(span)
        q = jax.random.normal(key, (B, 1, H, D), jnp.float32)
        pages_k = jax.random.normal(key, (1, NB, T, KV, D), jnp.float32)
        pages_v = pages_k + 1.0
        sk = block_scale(jnp.max(jnp.abs(pages_k), axis=(2, 4)), qspec)
        sv = block_scale(jnp.max(jnp.abs(pages_v), axis=(2, 4)), qspec)
        # the engine's layout: [L, NB, T, KV*D], scales [L, NB, KV]
        pool_k, pool_v, qk, qv = (
            x.reshape(1, NB, T, KV * D) for x in (
                pages_k, pages_v,
                quantize(pages_k, sk[:, :, None, :, None], qspec),
                quantize(pages_v, sv[:, :, None, :, None], qspec)))
        bt = (1 + (jnp.arange(B * MB) * 7) % (NB - 1)).reshape(B, MB)
        bt = bt.astype(jnp.int32)
        slots = jnp.full((B, 1), span - 1, jnp.int32)

        dense_fn = jax.jit(lambda q, k, v: paged_attention(
            q, k, v, bt, slots, layer=0, kv_valid_len=span))
        quant_fn = jax.jit(lambda q, k, v, sk, sv: paged_attention(
            q, k, v, bt, slots, layer=0, kv_valid_len=span, k_scale=sk,
            v_scale=sv))
        dense_fn(q, pool_k, pool_v).block_until_ready()
        quant_fn(q, qk, qv, sk, sv).block_until_ready()

        def run(fn, *args):
            ts = []
            for _ in range(TRIALS):
                t0 = time.perf_counter()
                for _ in range(20):
                    out = fn(*args)
                out.block_until_ready()
                ts.append((time.perf_counter() - t0) / 20 * 1000)
            return statistics.median(ts)

        d_ms = run(dense_fn, q, pool_k, pool_v)
        z_ms = run(quant_fn, q, qk, qv, sk, sv)
        results.append((f"paged_attention_dense_gather_ms_s{span}",
                        d_ms, "ms"))
        results.append((f"paged_attention_dequant_gather_ms_s{span}",
                        z_ms, "ms"))
        results.append((f"paged_attention_dequant_overhead_pct_s{span}",
                        (z_ms - d_ms) / d_ms * 100.0 if d_ms else 0.0,
                        "%"))
    return results


def _handoff_section(quick: bool) -> list:
    """Disaggregated handoff seam cost (models/engine.py
    `export_request` / `import_request` — the spill a prefill-class
    replica pays per finished prefill and the re-admission a
    decode-class replica pays per import): per prompt span, the wall
    ms to EXPORT (pow2-padded block gather + device->host pull + host
    staging), to IMPORT (re-submit + planting the paged swap pre-seed;
    no device work), and to ADMIT (the first decode step after the
    import: host->device scatter + decode dispatch), plus the payload
    bytes per request — dense f32 KV vs int8-quantized blocks. The
    quant plane moves ~4x fewer KV bytes (per-block scale rows ride
    along), which is the handoff-bandwidth side of the kv_quant
    trade. Runs anywhere: the staging copies and op counts are
    host-side and real on any backend."""
    import jax
    import numpy as np

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine

    spans = (128,) if quick else (128, 512, 2048)
    cfg = LlamaConfig.nano(max_seq_len=max(spans) + 64)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(17)
    results = []
    for span in spans:
        prompt = rng.randint(1, cfg.vocab_size, size=span).tolist()
        max_len = span + 16
        for quant in (None, "int8"):
            def make(name):
                return DecodeEngine(params, cfg, batch_slots=1,
                                    max_len=max_len, paged=True,
                                    kv_block_tokens=16,
                                    kv_quant=quant, engine_id=name)

            pre = make(f"hb-pre-{span}-{quant}")
            pre.prefill_only = True
            dec = make(f"hb-dec-{span}-{quant}")
            ex, im, ad = [], [], []

            def cycle(timed):
                rid = pre.submit(prompt, 4)
                while not pre.handoff_ready():
                    pre.step()
                t0 = time.perf_counter()
                h = pre.export_request(rid)
                t1 = time.perf_counter()
                dec.import_request(h)
                t2 = time.perf_counter()
                dec.step()          # admission: swap-in scatter
                t3 = time.perf_counter()
                dec.run()           # drain so the next cycle is clean
                if timed:
                    ex.append((t1 - t0) * 1000)
                    im.append((t2 - t1) * 1000)
                    ad.append((t3 - t2) * 1000)

            cycle(False)            # compile gather/scatter programs
            for _ in range(TRIALS):
                cycle(True)
            tag = "_int8" if quant else ""
            per_req_bytes = pre.handoff_out_bytes / (TRIALS + 1)
            results.append((f"handoff_export_ms_s{span}{tag}",
                            statistics.median(ex), "ms"))
            results.append((f"handoff_import_ms_s{span}{tag}",
                            statistics.median(im), "ms"))
            results.append((f"handoff_admit_ms_s{span}{tag}",
                            statistics.median(ad), "ms"))
            results.append((f"handoff_bytes_s{span}{tag}",
                            per_req_bytes, "bytes"))
    return results


def _fleet_router_section(quick: bool) -> list:
    """Per-decision cost of the fleet routers (models/fleet.py): the
    wall microseconds one `submit()` spends choosing a replica, per
    fleet size. The pow-2 + affinity router probes EVERY replica's
    prefix trie and stats plane per decision (peek-only host walks,
    zero device work), so its cost must stay trivially small next to
    a single prefill — this section is the guard. Round-robin is the
    floor (an index increment)."""
    import jax
    import numpy as np

    from ray_tpu.models import LLMFleet, LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine
    from ray_tpu.models.fleet import (PowerOfTwoAffinityRouter,
                                      RoundRobinRouter)

    cfg = LlamaConfig.nano(max_seq_len=256)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(3)
    sizes = (4,) if quick else (2, 4, 8)
    n_decisions = 50 if quick else 200
    prompt = rng.randint(1, cfg.vocab_size, size=96).tolist()

    results = []
    for n in sizes:
        for router_name, router in (
                ("round_robin", RoundRobinRouter()),
                ("pow2_affinity", PowerOfTwoAffinityRouter())):
            def factory(name):
                return DecodeEngine(params, cfg, batch_slots=2,
                                    max_len=cfg.max_seq_len,
                                    prefix_cache=True, prefix_block=16,
                                    enable_metrics=False)
            fleet = LLMFleet(factory, initial_replicas=n,
                             router=router,
                             fleet_id=f"mb-{router_name}-{n}")
            # Seed one replica's trie so the affinity probe walks a
            # non-trivial index (the expensive honest case).
            fleet.submit(prompt, 2)
            fleet.run()
            running = fleet._running()
            t0 = time.perf_counter()
            for _ in range(n_decisions):
                router.choose(running, prompt)
            us = (time.perf_counter() - t0) / n_decisions * 1e6
            results.append((
                f"fleet_router_{router_name}_decision_us_n{n}",
                us, "us"))
    return results


def _tracer_overhead_section(quick: bool) -> list:
    """Cost of the request-lifecycle tracer (models/engine_trace.py):
    raw event-emit throughput, and the engine-level tax — wall time of
    an identical decode churn with tracing OFF (the NullEngineTracer
    default), with the ring tracer ON, and the on/off overhead
    fraction. The zero-cost-when-off claim is the one that matters
    (every call site guards on `trace.enabled` before building args),
    so off-vs-baseline must be noise; on-vs-off bounds what turning a
    production engine's tracing on costs per token."""
    import jax
    import numpy as np

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine
    from ray_tpu.models.engine_trace import EngineTracer

    # Raw primitive cost: one span via the mark frontier (the decode
    # hot path's shape: span_since_mark with a small args dict).
    tracer = EngineTracer(capacity=1 << 14)
    n_ev = 20_000 if quick else 100_000
    tracer.mark(0)

    def emit():
        for _ in range(n_ev):
            tracer.span_since_mark("decode_block", 0,
                                   {"tokens": 1, "horizon": 8})

    results = [("tracer_span_emit_per_second",
                timed_median(emit, n_ev), "events/s")]

    cfg = LlamaConfig.nano(max_seq_len=256)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, size=24).tolist()
               for _ in range(8)]
    new_tokens = 8 if quick else 32

    def churn(trace):
        eng = DecodeEngine(params, cfg, batch_slots=4,
                           max_len=cfg.max_seq_len,
                           enable_metrics=False, trace=trace)
        for p in prompts:
            eng.submit(p, new_tokens)
        eng.run()         # compile warmup
        for p in prompts:
            eng.submit(p, new_tokens)
        t0 = time.perf_counter()
        eng.run()
        return time.perf_counter() - t0

    churn(False)          # shared jit cache warm
    n_tok = len(prompts) * new_tokens
    off = statistics.median([churn(False) for _ in range(TRIALS)])
    on = statistics.median([churn(True) for _ in range(TRIALS)])
    results.append(("tracer_off_decode_us_per_token",
                    off / n_tok * 1e6, "us"))
    results.append(("tracer_on_decode_us_per_token",
                    on / n_tok * 1e6, "us"))
    results.append(("tracer_overhead_frac",
                    (on - off) / off if off else 0.0, "frac"))
    return results


def _state_snapshot_section(quick: bool) -> list:
    """Cost of one serving state snapshot (util/state/serving.py) and
    one metrics-history sample (util/metrics_history.py) against a
    BUSY engine — queue + live slots + mid-prefill rows, the state a
    status poller actually reads. Calls/s for each query plus the
    per-poll microseconds of the full status-CLI read set; these are
    the numbers behind bench.py's `state_snapshot_overhead_frac`."""
    import gc

    import jax
    import numpy as np

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.engine import DecodeEngine
    from ray_tpu.util import metrics_history as mh
    from ray_tpu.util.state import serving

    gc.collect()                  # drop corpses from earlier sections
    cfg = LlamaConfig.nano(max_seq_len=256)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(7)
    eng = DecodeEngine(params, cfg, batch_slots=4,
                       max_len=cfg.max_seq_len, prefix_cache=True,
                       prefix_block=16)
    for _ in range(12):           # oversubscribed: queue stays deep
        eng.submit(rng.randint(1, cfg.vocab_size, size=24).tolist(),
                   64)
    eng.step()                    # live slots + queue, mid-churn

    n = 2_000 if quick else 10_000
    results = []
    for name, fn in [
        ("state_list_engines_per_second", serving.list_engines),
        ("state_list_requests_per_second", serving.list_requests),
        ("state_summarize_fleet_per_second", serving.summarize_fleet),
        ("metrics_history_sample_per_second",
         lambda: mh.sample_now(force=True)),
    ]:
        fn()                      # warm lazy paths outside the window
        results.append((name, timed_median(
            lambda: [fn() for _ in range(n)], n), "calls/s"))

    def poll():
        serving.summarize_fleet()
        mh.sample_now(force=True)

    rate = timed_median(lambda: [poll() for _ in range(n)], n)
    results.append(("state_full_poll_us", 1e6 / rate if rate else 0.0,
                    "us"))
    eng.run()
    return results


def _graft_lint_section(quick: bool) -> list:
    """Wall time of one full graftlint sweep (all eight analyzers,
    interprocedural summaries included, over the serving tree — the same
    work `test_graft_lint.py::test_tree_is_clean` does in tier-1 CI).
    Budget: < 4 s full-tree, so the gate stays cheap enough to run on
    every commit; also reports per-file microseconds and the open finding
    count (must be 0 — bench.py tracks it as `lint_violations_total`)."""
    from ray_tpu._private.lint import lint_paths

    paths = ["ray_tpu/models", "ray_tpu/serve", "ray_tpu/util"]
    lint_paths(paths)                       # warm import + glossary cache
    trials = 1 if quick else TRIALS
    times = []
    report = None
    for _ in range(trials):
        t0 = time.perf_counter()
        report = lint_paths(paths)
        times.append(time.perf_counter() - t0)
    sweep = statistics.median(times)
    return [
        ("lint_sweep_seconds", sweep, "s"),
        ("lint_us_per_file",
         sweep / max(report.files_scanned, 1) * 1e6, "us"),
        ("lint_violations_total", float(len(report.open)), "count"),
    ]


def main(quick: bool = False):
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import numpy as np

    import ray_tpu

    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}

    def emit(rows):
        # every line names the device it ran on: the engine sections
        # time whatever backend this is
        for name, value, unit in rows:
            print(json.dumps({"metric": name, "value": round(value, 4),
                              "unit": unit, "device": device}),
                  flush=True)

    scale = 0.1 if quick else 1.0
    # Print the serving-engine sections immediately: a later section
    # that fails ends the run, and their lines are already out.
    for section in (_graft_lint_section, _decode_dispatch_section,
                    _spec_dispatch_section, _sharded_dispatch_section,
                    _dispatch_gap_section, _prefix_admission_section,
                    _paged_gather_section, _kv_quant_gather_section,
                    _handoff_section, _fleet_router_section,
                    _tracer_overhead_section, _state_snapshot_section):
        emit(section(quick))
    results = []
    ray_tpu.init(num_cpus=4)

    # --- trivial task throughput (pipelined) ---
    @ray_tpu.remote
    def noop():
        return None

    n = int(3000 * scale)
    # Warm workers, leases, the fastlane channel, and the inline-exec
    # observation window; let store pre-population settle.
    ray_tpu.get([noop.remote() for _ in range(300)])
    time.sleep(1.0)

    def tasks():
        ray_tpu.get([noop.remote() for _ in range(n)])

    results.append(("tasks_per_second", timed_median(tasks, n), "tasks/s"))

    # --- single actor call latency / throughput ---
    @ray_tpu.remote
    class A:
        def m(self, x=None):
            return x

    a = A.remote()
    for _ in range(20):  # warm conn + fastlane channel
        ray_tpu.get(a.m.remote())
    n = int(2000 * scale)

    def actor_sync():
        for _ in range(n):
            ray_tpu.get(a.m.remote())

    rate = timed_median(actor_sync, n)
    results.append(("actor_calls_sync_per_second", rate, "calls/s"))
    results.append(("actor_call_latency_ms", 1000.0 / rate, "ms"))

    def actor_async():
        ray_tpu.get([a.m.remote() for _ in range(n)])

    results.append(("actor_calls_pipelined_per_second",
                    timed_median(actor_async, n), "calls/s"))

    # --- object store bandwidth (zero-copy numpy) ---
    mb = 64 if quick else 256
    arr = np.random.rand(mb * 1024 * 1024 // 8)

    put_rates, get_rates = [], []
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        ref = ray_tpu.put(arr)
        put_rates.append(mb / (time.perf_counter() - t0))
        t0 = time.perf_counter()
        out = ray_tpu.get(ref)
        get_rates.append(mb / (time.perf_counter() - t0))
        assert out.shape == arr.shape
        del out, ref
    results.append(("object_store_put_mb_per_second",
                    statistics.median(put_rates), "MiB/s"))
    results.append(("object_store_get_mb_per_second",
                    statistics.median(get_rates), "MiB/s"))

    # --- many small objects in one get ---
    n = int(1000 * scale)
    refs = [ray_tpu.put(i) for i in range(n)]

    def many_get():
        ray_tpu.get(refs)

    results.append(("small_objects_get_per_second",
                    timed_median(many_get, n), "objects/s"))

    # --- actor creation storm (warm pool) ---
    # Reference envelope row: actor creation throughput (BASELINE.md
    # 40k-actor scale / release scalability suite). A fresh cluster
    # sized to the storm keeps the prestart pool warm for all N, so the
    # metric isolates the creation pipeline (pipelined GCS registration
    # + lease + creation push + first call), not process cold start.
    ray_tpu.shutdown()
    storm_n = 4 if quick else 16
    ray_tpu.init(num_cpus=storm_n)

    @ray_tpu.remote
    class S:
        def m(self, x=None):
            return x

    time.sleep(2.0 if quick else 8.0)  # prestart pool fill

    storms = []
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        batch = [S.remote() for _ in range(storm_n)]
        ray_tpu.get([b.m.remote(1) for b in batch], timeout=120)
        storms.append(storm_n / (time.perf_counter() - t0))
        for b in batch:
            ray_tpu.kill(b)
        time.sleep(1.0 if quick else 4.0)  # pool refill between trials
    results.append(("actor_creation_storm_per_second",
                    statistics.median(storms), "actors/s"))

    emit(results)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)

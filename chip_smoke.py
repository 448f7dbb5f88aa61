#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py [--seed N]      one TPU chip: phases 1-3 below
    python chip_smoke.py --chips 4       one four-chip host: the sharded
                                         paths only, each against the same
                                         model on one device of that host

It drives the system's main paths once, through the entry points a user
calls, at the full width of a model the repo supports (depth cut, weights
random from ``--seed``), and checks what comes out by the repo's own
means. It measures nothing: the times it prints are there so a slow phase
is visible, not to be quoted as performance.

A chip belongs to one process at a time, and phase 3 needs a *worker*
process to hold it. So this parent never imports JAX: every phase runs
as a child process that owns the chip and gives it back on exit. A child
that exits non-zero, or exits without a report, fails the whole run;
nothing is caught and carried on from. Every child refuses to start
unless ``jax.devices()[0].platform == "tpu"`` — there is no CPU branch.

One chip (the default):
  1. serve   DecodeEngine at Llama-3-8B widths (dim 4096, 32 query / 8 KV
             heads of 128, FFN 14336, vocab 128,256; bf16 weights), depth
             cut from 32 layers to SERVE_LAYERS so the weights sit beside
             their KV on one 16 GB chip. A handful of requests of mixed
             prompt lengths go through submit/step/drain on the engine
             with a bf16 pool and with kv_quant="int8", then
             through a two-replica LLMFleet. Each is held to solo
             `generate` by the bounds written below.
  2. train   three steps of make_sharded_train_step on the 551M
             flagship_config() (flash attention fwd+bwd) at 8 x 2048 on a
             one-device mesh: the loss is finite and falls, and the
             lowered step contains the flash kernel.
  3. runtime ray_tpu.init() from a driver that never touches JAX: the
             node finds its own chip, a JaxTrainer worker leased to it
             runs a jitted step on the TPU, and a plain CPU task that
             imports JAX while that worker is alive sees only CPU devices.

``--chips 4`` runs none of those. It runs the phase-1 model under
DecodeEngine(tp=4) and the phase-2 model on a {"fsdp": 2, "tp": 2} mesh,
each against one device, and checks the arrays really span four devices.

The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
as the children reported the device. On any failure that line is not
printed and the exit code is non-zero.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# The whole run must end inside 1,200 s, compilation included (about 270 s
# cold on one v5e chip). A phase still running when this much has passed
# since the start is killed, and the run fails.
RUN_TIMEOUT_S = 1140

# ---- phase 1: what is served -------------------------------------------------
# Depth is the only cut: 8 of Llama-3-8B's 32 layers is 2.80 B parameters,
# 5.6 GB in bf16. Every width is the published one.
SERVE_LAYERS = 8
SERVE_MAX_LEN = 2048
SERVE_SLOTS = 8
# Mixed lengths, two over 1,024: with prefill_chunk=512 the long prompts
# take three chunked-prefill steps while the short ones already decode,
# and the prefill buckets 32 / 128 / 256 / 512 all run.
SERVE_PROMPT_LENS = (1100, 24, 520, 150, 1100, 150)
SERVE_PREFILL_CHUNK = 512
# 40 = five full decode blocks of the default horizon 8.
SERVE_NEW_TOKENS = 40
SERVE_KV_BLOCK_TOKENS = 32
# One KV pool is resident at a time. 1 GiB is 1,024 bf16 blocks of 32
# tokens (8 layers x 8 KV heads x 128 x K,V x 2 bytes = 32 KiB a token),
# twice what these requests can touch; the int8 pool gets the same bytes.
SERVE_KV_POOL_BYTES = 1 << 30

# What a pass requires of every serving variant, against solo `generate`
# on the same weights and prompt.
#
# Logits at the first generated position: the largest absolute
# difference over the vocabulary, over all requests, stays under
# LOGIT_ATOL. Activations are bf16 (8 significand bits, half-ulp 2^-9):
# ~4 roundings a layer over 8 layers random-walk to about 1 % of the
# residual stream, logits here are O(1) wide, and the worst of 128,256
# entries sits 4-5 sigma out — about 0.05 between two correct bf16
# evaluations that merely associate differently (the engine prefills in
# chunks into a 2,048-slot cache, solo `generate` in one piece into a
# cache of prompt + 40; tp=4 sums partial products four ways). Measured
# on the chip: 0.048 against solo, 0.071 tp=4 against one device.
# The bound is three times the estimate; a wrong mask, a wrong page or a
# lost scale moves logits by O(1).
LOGIT_ATOL = 0.15
# int8 KV: a chunked prompt's later chunks already read quantized keys
# and values. Per-block absmax steps are absmax/127, an rms error near
# ten times bf16's own rounding of the same element; through attention
# that about doubles the distance between bf16 evaluations (measured on
# the chip: 0.109), and so the bound.
LOGIT_ATOL_INT8 = 0.3
# Greedy tokens: EVERY generated token must be the reference's greedy
# choice given the same context, up to the logit tolerance. The
# reference (`forward_cached` over prompt + the variant's own tokens, the
# function solo `generate` is made of) scores each generated position;
# the token's logit may sit below the best one by at most twice the
# variant's logit bound — once for each of the two evaluations being
# compared. This is what checks the decode path (the Pallas kernel runs
# nowhere else), token by token, and a near-tie cannot fail it.
#
# The repo's own identity check — the free-running stream equal to solo
# `generate`'s, which holds exactly in f32 on the CPU — is run and its
# outcome printed (`identical_to_reference`, and `token_share`: the share of
# tokens generated before a request first leaves its solo stream). It is
# not part of the verdict: weights are random, the top two logits are
# often closer than LOGIT_ATOL, and one flip forfeits the rest of a
# request, so in bf16 on the chip it measures where the first near-tie
# fell, not whether the engine is right.
GREEDY_MARGIN_FACTOR = 2.0

# ---- phase 2: what is trained ------------------------------------------------
TRAIN_BATCH = 8
TRAIN_SEQ = 2048
TRAIN_STEPS = 3
# --chips 4: the {"fsdp": 2, "tp": 2} loss against the one-device loss at
# every step. Same f32 master weights and bf16 activations, reductions
# split four ways: agreement is to bf16 rounding of a loss near
# ln(32000) = 10.4, and Adam's normalised first steps keep it there.
TRAIN_MESH_LOSS_ATOL = 0.02
# --chips 4: no device may hold less than this share, or more than that
# share, of the sharded bytes (an even split is 0.25 each).
SHARD_SHARE_MIN, SHARD_SHARE_MAX = 0.15, 0.40


# =============================================================================
# Parent: runs children, never imports JAX
# =============================================================================

def _kill_tree(pid: int) -> None:
    """Stop a child and everything it started (the runtime's processes
    each lead their own session, so a process-group kill misses them)."""
    import psutil

    try:
        root = psutil.Process(pid)
    except psutil.NoSuchProcess:
        return
    procs = root.children(recursive=True) + [root]
    for p in procs:
        with contextlib.suppress(psutil.NoSuchProcess):
            p.kill()
    psutil.wait_procs(procs, timeout=10)


def _run_child(name: str, argv: list, timeout_s: float) -> dict:
    """Run one phase as a child; echo its stdout; return its report — the
    last stdout line, a JSON object. Raises if the child exits non-zero,
    is cut at the time limit, or leaves no report."""
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["PYTHONUNBUFFERED"] = "1"
    proc = subprocess.Popen(argv, cwd=HERE, env=env, text=True,
                            stdout=subprocess.PIPE)
    timed_out = threading.Event()

    def on_timeout():
        timed_out.set()
        _kill_tree(proc.pid)

    timer = threading.Timer(timeout_s, on_timeout)
    timer.start()
    last = ""
    try:
        for line in proc.stdout:
            print(line, end="", flush=True)
            if line.strip():
                last = line.strip()
        rc = proc.wait()
    finally:
        timer.cancel()
        _kill_tree(proc.pid)
    if timed_out.is_set():
        raise RuntimeError(f"phase {name}: no result in {timeout_s:.0f}s")
    if rc != 0:
        raise RuntimeError(f"phase {name}: exit code {rc}")
    try:
        report = json.loads(last)
    except json.JSONDecodeError:
        report = None
    if not isinstance(report, dict) or report.get("phase") != name:
        raise RuntimeError(f"phase {name}: exited 0 without a report")
    return report


def run_phases(commands: list, timeout_s: float = RUN_TIMEOUT_S) -> int:
    """Run ``[(name, argv), ...]`` one after another and print the
    verdict. Returns the exit code: 0 only if every phase reported
    ``"pass": true`` on one and the same TPU. Only the verdict carries
    the key ``ok``: the echoed lines of the phases never do, so the last
    line of a failed run cannot be read as a result."""
    device = None
    deadline = time.monotonic() + timeout_s
    try:
        for name, argv in commands:
            t0 = time.monotonic()
            report = _run_child(name, argv, max(deadline - t0, 1.0))
            print(f"chip_smoke: phase {name} took "
                  f"{time.monotonic() - t0:.1f}s", flush=True)
            if report.get("pass") is not True:
                raise RuntimeError(f"phase {name}: reported a failure")
            dev = report.get("device")
            if not isinstance(dev, dict) or dev.get("platform") != "tpu":
                raise RuntimeError(f"phase {name}: device {dev!r}")
            dev = {k: dev[k] for k in ("platform", "kind", "count")}
            if device is not None and dev != device:
                raise RuntimeError(
                    f"phase {name}: ran on {dev}, earlier phases on "
                    f"{device}")
            device = dev
    except RuntimeError as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _child_argv(name: str, seed: int) -> list:
    return [sys.executable, "-c",
            f"import chip_smoke; chip_smoke.child({name!r}, {seed})"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights and the prompts")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the tp=4 engine and the fsdp x tp train "
                         "step, each against one device")
    args = ap.parse_args(argv)
    names = ("serve", "train", "runtime") if args.chips == 1 \
        else ("serve_tp4", "train_mesh4")
    return run_phases([(n, _child_argv(n, args.seed)) for n in names])


# =============================================================================
# Children: each owns the chip for one phase
# =============================================================================

def _require_tpu(min_count: int = 1) -> dict:
    """The device as JAX reports it — or no run at all."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < min_count:
        raise SystemExit(
            f"chip_smoke: needs {min_count} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class _CompileMeter:
    """Seconds JAX spent in backend compiles (or fetching them from the
    persistent cache) and how many came from the cache, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @contextlib.contextmanager
    def window(self, out: dict):
        s0, h0 = self.seconds, self.hits
        yield
        out["compile_s"] = round(self.seconds - s0, 1)
        out["cache_hits"] = self.hits - h0


def _say(**fields) -> None:
    print(json.dumps(fields), flush=True)


@contextlib.contextmanager
def _decode_impl_probe(impls: dict):
    """While active, the first dispatch of the fused decode program is
    lowered as the engine calls it — same arguments, same mesh scope —
    and ``impls[program]`` records whether that program carries a Mosaic
    kernel. Read from the lowered program, not from an argument."""
    import ray_tpu.models.engine as engine_mod

    name = "_decode_multi_paged"
    real = getattr(engine_mod, name)

    def call(*a, **k):
        if name not in impls:
            text = real.lower(*a, **k).as_text()
            impls[name] = ("pallas" if "tpu_custom_call" in text
                           else "reference")
        return real(*a, **k)

    setattr(engine_mod, name, call)
    try:
        yield
    finally:
        setattr(engine_mod, name, real)


def _serve_config():
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    return dataclasses.replace(
        LlamaConfig.llama3_8b(), n_layers=SERVE_LAYERS,
        max_seq_len=SERVE_MAX_LEN, param_dtype=jnp.bfloat16)


def _seeded_model(seed: int, cfg, prompt_lens):
    import jax
    import numpy as np

    from ray_tpu.models import llama_init

    params = jax.jit(llama_init, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in prompt_lens]
    return params, prompts


def _reference_programs(cfg, n: int):
    """Two jitted programs over the repo's reference forward. ``solo``:
    prompt [1, P] -> (the logits solo `generate` emits its first token
    from, its n tokens). ``margins``: prompt + n tokens [1, P + n] ->
    for each of the n, how far its logit sits below the best logit at
    its position given everything before it (0 = the greedy choice)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import (forward_cached, generate,
                                         init_cache)

    @jax.jit
    def solo(params, prompt):
        plen = prompt.shape[1]
        # generate's own prefill: same cache length, same program
        logits, _ = forward_cached(
            params, prompt, init_cache(cfg, 1, plen + n), 0, cfg)
        toks = generate(params, prompt, cfg, max_new_tokens=n)
        return logits[0, -1], toks[0, plen:]

    @jax.jit
    def margins(params, seq):
        plen = seq.shape[1] - n
        logits, _ = forward_cached(
            params, seq, init_cache(cfg, 1, seq.shape[1]), 0, cfg)
        rows = logits[0, plen - 1:-1]                       # [n, vocab]
        chosen = jnp.take_along_axis(rows, seq[0, plen:, None], axis=1)
        return rows.max(axis=-1) - chosen[:, 0]

    return solo, margins


def _solo_reference(solo, params, prompts):
    """(tokens, logits) per prompt from solo `generate`."""
    import jax.numpy as jnp
    import numpy as np

    tokens, logits = [], []
    for p in prompts:
        lg, tk = solo(params, jnp.asarray([p], jnp.int32))
        logits.append(np.asarray(lg, np.float32))
        tokens.append(np.asarray(tk).tolist())
    return tokens, logits


def _greedy_margin(margins, params, prompts, tokens) -> float:
    """The worst of all generated tokens' margins (see `margins`)."""
    import jax.numpy as jnp

    return max(float(margins(params,
                             jnp.asarray([p + t], jnp.int32)).max())
               for p, t in zip(prompts, tokens))


def _first_logits(engine, prompts):
    """The logits each request's first token is sampled from. A request
    of one token freezes its row's `last_logits` there, and an empty
    FIFO engine gives request i row i — checked, not assumed: the frozen
    row's argmax must be the token the request emitted."""
    import numpy as np

    if len(prompts) > engine.B:
        raise ValueError("one probe row per prompt")
    ids = [engine.submit(p, max_new_tokens=1) for p in prompts]
    out = engine.run()
    rows = np.asarray(engine._last_logits[:len(prompts)], np.float32)
    for i, rid in enumerate(ids):
        if out[rid] != [int(rows[i].argmax())]:
            raise RuntimeError(
                f"row {i} does not hold request {rid}'s first logits")
    return list(rows)


def _serve_through(engine, prompts, new_tokens):
    """submit / step / drain; tokens in submission order."""
    ids = [engine.submit(p, max_new_tokens=new_tokens) for p in prompts]
    for _ in range(3):
        engine.step()
    out = engine.drain()
    return [out[rid] for rid in ids]


def _agreement(tokens, ref_tokens) -> float:
    """Share of tokens generated before each request first leaves its
    reference stream."""
    kept = total = 0
    for got, want in zip(tokens, ref_tokens):
        total += len(want)
        same = [a == b for a, b in zip(got, want)]
        kept += same.index(False) if False in same else len(want)
    return kept / total


def _judge(record, tokens, new_tokens, *, ref_tokens, greedy_margin,
           logits=None, ref_logits=None):
    """Fill a variant's record with its distances from the reference and
    its verdict under the bounds at the top of this file."""
    import numpy as np

    atol = LOGIT_ATOL_INT8 if record.get("kv_quant") else LOGIT_ATOL
    record["tokens"] = sum(len(t) for t in tokens)
    record["greedy_margin_max"] = round(greedy_margin, 5)
    record["greedy_margin_allowed"] = GREEDY_MARGIN_FACTOR * atol
    record["pass"] = bool(
        all(len(t) == new_tokens for t in tokens)
        and greedy_margin <= record["greedy_margin_allowed"])
    if logits is not None:
        finite = all(np.isfinite(x).all() for x in logits)
        record["logit_max_abs_diff"] = round(float(max(
            np.abs(a - b).max() for a, b in zip(logits, ref_logits))), 5)
        record["logit_atol"] = atol
        record["pass"] = bool(record["pass"] and finite and
                              record["logit_max_abs_diff"] <= atol)
    record["token_share"] = round(_agreement(tokens, ref_tokens), 4)
    record["identical_to_reference"] = tokens == ref_tokens
    return record


def _run_engine(record, params, cfg, prompts, new_tokens, meter, **kw):
    """Build one engine and serve the prompts: (engine, first logits,
    tokens). Fills the record with what the run showed of itself. The
    caller drops the engine — its KV goes with it — before the next."""
    from ray_tpu.models.engine import DecodeEngine

    impls: dict = {}
    t0 = time.monotonic()
    with meter.window(record), _decode_impl_probe(impls):
        engine = DecodeEngine(params, cfg, **kw)
        logits = _first_logits(engine, prompts)
        tokens = _serve_through(engine, prompts, new_tokens)
    (record["decode_program"], record["impl"]), = impls.items()
    record["kv_pool_blocks"] = engine.kv_pool.blocks_total
    record["kv_block_tokens"] = engine.kv_block_tokens
    record["seconds"] = round(time.monotonic() - t0, 1)
    return engine, logits, tokens


def phase_serve(seed: int, cfg=None, *, prompt_lens=SERVE_PROMPT_LENS,
                new_tokens=SERVE_NEW_TOKENS, slots=SERVE_SLOTS,
                chunk=SERVE_PREFILL_CHUNK,
                kv_block_tokens=SERVE_KV_BLOCK_TOKENS,
                kv_pool_bytes=SERVE_KV_POOL_BYTES) -> dict:
    device = _require_tpu()
    from ray_tpu.models.engine import DecodeEngine
    from ray_tpu.models.fleet import LLMFleet
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    meter = _CompileMeter()
    cfg = cfg or _serve_config()
    params, prompts = _seeded_model(seed, cfg, prompt_lens)
    _say(phase="serve", model="llama3_8b widths",
         depth_cut=f"{cfg.n_layers} of 32 layers",
         params_b=round(cfg.num_params() / 1e9, 2), prompt_lens=prompt_lens,
         new_tokens=new_tokens, seed=seed)
    solo, margins = _reference_programs(cfg, new_tokens)
    ref: dict = {"variant": "solo_generate"}
    with meter.window(ref):
        solo_tokens, solo_logits = _solo_reference(solo, params, prompts)
    _say(**ref)

    def judge(record, tokens, logits=None):
        return _judge(
            record, tokens, new_tokens, ref_tokens=solo_tokens,
            greedy_margin=_greedy_margin(margins, params, prompts, tokens),
            logits=logits, ref_logits=solo_logits)

    engine_kw = dict(batch_slots=slots, max_len=cfg.max_seq_len,
                     prefill_chunk=chunk, kv_block_tokens=kv_block_tokens,
                     kv_pool_bytes=kv_pool_bytes)
    records = []
    for name, kw in (("paged", engine_kw),
                     ("paged_int8", dict(engine_kw, kv_quant="int8"))):
        record = {"variant": name, "kv_quant": kw.get("kv_quant")}
        engine, logits, tokens = _run_engine(
            record, params, cfg, prompts, new_tokens, meter, **kw)
        del engine          # one KV pool resident at a time
        gc.collect()
        _say(**judge(record, tokens, logits))
        records.append(record)

    # Two replicas behind the router, sharing the one copy of the
    # weights; their step loop is the fleet's.
    record = {"variant": "fleet_2x_paged", "kv_quant": None}
    impls: dict = {}
    t0 = time.monotonic()
    with meter.window(record), _decode_impl_probe(impls):
        fleet = LLMFleet(
            lambda name: DecodeEngine(params, cfg, engine_id=name,
                                      **engine_kw),
            initial_replicas=2)
        fids = [fleet.submit(p, max_new_tokens=new_tokens)
                for p in prompts]
        while fleet.pending():
            fleet.step()
        tokens = [fleet.pop_result(f) for f in fids]
    (record["decode_program"], record["impl"]), = impls.items()
    record["routed"] = [r.routed for r in fleet.replicas]
    record["replicas_failed"] = fleet.replicas_failed
    record["retries"] = fleet.retries
    record["seconds"] = round(time.monotonic() - t0, 1)
    del fleet
    gc.collect()
    judge(record, tokens)
    record["pass"] = bool(record["pass"] and min(record["routed"]) > 0
                          and not record["replicas_failed"]
                          and not record["retries"])
    _say(**record)
    records.append(record)

    return {"phase": "serve", "pass": all(r["pass"] for r in records),
            "device": device,
            "identity_vs_solo": records[0]["identical_to_reference"],
            "paged_impl": records[0]["impl"],
            "compile_s": round(meter.seconds, 1),
            "cache_hits": meter.hits}


def _train_losses(cfg, mesh, tokens, steps, seed, record):
    """`steps` steps of the sharded train step on `mesh`; fills `record`
    with the losses, whether the lowered step carries the flash kernel,
    and how many devices the parameters span."""
    import jax
    import optax

    from ray_tpu.models import llama_init, llama_loss, llama_param_specs
    from ray_tpu.models.training import make_sharded_train_step

    init_fn, step_fn = make_sharded_train_step(
        lambda p, b: llama_loss(p, b, cfg),
        optax.adamw(3e-4, weight_decay=0.0), mesh, llama_param_specs(cfg))
    params, opt_state = init_fn(llama_init(jax.random.PRNGKey(seed), cfg))
    batch = {"tokens": tokens}
    record["flash_kernel_in_step"] = "tpu_custom_call" in step_fn.lower(
        params, opt_state, batch).as_text()
    record["param_devices"] = sorted({
        len(x.sharding.device_set) for x in jax.tree.leaves(params)})
    losses = []
    for _ in range(steps):
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    record["loss"] = [round(x, 5) for x in losses]
    del params, opt_state
    gc.collect()
    return losses


def _train_setup(seed, cfg, batch, seq):
    import jax

    from bench import flagship_config

    cfg = cfg or flagship_config()
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, seq + 1), 0, cfg.vocab_size)
    return cfg, tokens


def _loss_falls(losses) -> bool:
    import math

    return all(math.isfinite(x) for x in losses) and all(
        b < a for a, b in zip(losses, losses[1:]))


def phase_train(seed: int, cfg=None, *, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                steps=TRAIN_STEPS) -> dict:
    device = _require_tpu()
    import jax

    from ray_tpu.parallel import create_mesh
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    meter = _CompileMeter()
    cfg, tokens = _train_setup(seed, cfg, batch, seq)
    record = {"phase": "train", "model_params_m": round(
        cfg.num_params() / 1e6), "batch": [batch, seq], "seed": seed}
    t0 = time.monotonic()
    with meter.window(record):
        losses = _train_losses(cfg, create_mesh({"dp": 1},
                                                jax.devices()[:1]),
                               tokens, steps, seed, record)
    record["seconds"] = round(time.monotonic() - t0, 1)
    record["pass"] = bool(_loss_falls(losses)
                        and record["flash_kernel_in_step"])
    record["device"] = device
    return record


def phase_runtime(seed: int) -> dict:
    """The driver: it never imports JAX, so the chip is free for the
    worker the raylet leases it to."""
    import psutil

    import ray_tpu
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train import JaxConfig, JaxTrainer

    @ray_tpu.remote
    def cpu_task_devices():
        import jax

        return sorted({d.platform for d in jax.devices()})

    def train_loop(config):
        import jax
        import jax.numpy as jnp

        from ray_tpu import train

        dev = jax.devices()[0]
        x = jax.random.normal(jax.random.PRNGKey(config["seed"]),
                              (256, 512))

        def loss_fn(w):
            return jnp.mean((jnp.tanh(x @ w) - 1.0) ** 2)

        step = jax.jit(lambda w: (w - 0.5 * jax.grad(loss_fn)(w),
                                  loss_fn(w)))
        w, first = step(jnp.zeros((512, 128)))
        # this worker holds the chip now; ask what a plain task sees
        other = ray_tpu.get(cpu_task_devices.remote())
        w, second = step(w)
        train.report({
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
            "loss": [float(first), float(second)],
            "cpu_task_platforms": other})

    t0 = time.monotonic()
    record: dict = {"phase": "runtime"}
    me = psutil.Process()
    started: list = []
    try:
        ray_tpu.init()
        record["node_resources"] = ray_tpu.cluster_resources()
        with tempfile.TemporaryDirectory() as store:
            result = JaxTrainer(
                train_loop, train_loop_config={"seed": seed},
                jax_config=JaxConfig(jax_distributed=False),
                scaling_config=ScalingConfig(
                    num_workers=1, use_tpu=True, chips_per_worker=1),
                run_config=RunConfig(name="chip_smoke",
                                     storage_path=store)).fit()
        if result.error is not None:
            raise RuntimeError(f"trainer failed: {result.error}")
        record["worker"] = result.metrics
        started = me.children(recursive=True)
    finally:
        ray_tpu.shutdown()
    _, alive = psutil.wait_procs(started, timeout=15)
    record["processes_started"] = len(started)
    record["processes_left"] = len(alive)
    for p in alive:
        with contextlib.suppress(psutil.NoSuchProcess):
            p.kill()
    record["driver_imported_jax"] = "jax" in sys.modules
    worker = record["worker"]
    losses = worker["loss"]
    record["seconds"] = round(time.monotonic() - t0, 1)
    record["pass"] = bool(
        record["node_resources"].get("TPU") == 1.0
        and worker["platform"] == "tpu"
        and losses[1] < losses[0]
        and worker["cpu_task_platforms"] == ["cpu"]
        and not record["processes_left"]
        and not record["driver_imported_jax"])
    record["device"] = {k: worker[k] for k in ("platform", "kind", "count")}
    return record


# ---- --chips 4 ---------------------------------------------------------------

def _bytes_per_device(devices) -> list:
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def phase_serve_tp4(seed: int, cfg=None, *, tp=4,
                    prompt_lens=SERVE_PROMPT_LENS,
                    new_tokens=SERVE_NEW_TOKENS, slots=SERVE_SLOTS,
                    chunk=SERVE_PREFILL_CHUNK,
                    kv_block_tokens=SERVE_KV_BLOCK_TOKENS,
                    kv_pool_bytes=SERVE_KV_POOL_BYTES) -> dict:
    """The phase-1 model under DecodeEngine(tp=4) against the same
    engine on one device of the same host: the same bounds as phase 1,
    with the one-device engine's logits and tokens as the reference
    (four-way partial sums reorder bf16 additions, nothing more)."""
    device = _require_tpu(tp)
    import jax

    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    meter = _CompileMeter()
    cfg = cfg or _serve_config()
    params, prompts = _seeded_model(seed, cfg, prompt_lens)
    engine_kw = dict(batch_slots=slots, max_len=cfg.max_seq_len,
                     prefill_chunk=chunk, kv_block_tokens=kv_block_tokens,
                     kv_pool_bytes=kv_pool_bytes)
    _say(phase="serve_tp4", model="llama3_8b widths",
         depth_cut=f"{cfg.n_layers} of 32 layers", tp=tp, seed=seed)
    _, margins = _reference_programs(cfg, new_tokens)

    one: dict = {"variant": "paged_1_device", "kv_quant": None}
    engine, ref_logits, ref_tokens = _run_engine(
        one, params, cfg, prompts, new_tokens, meter, **engine_kw)
    del engine
    gc.collect()
    _say(**one)

    record: dict = {"variant": f"paged_tp{tp}", "kv_quant": None}
    engine, logits, tokens = _run_engine(
        record, params, cfg, prompts, new_tokens, meter, tp=tp, **engine_kw)
    _judge(record, tokens, new_tokens, ref_tokens=ref_tokens,
           greedy_margin=_greedy_margin(margins, params, prompts, tokens),
           logits=logits, ref_logits=ref_logits)
    # Only the sharded copy stays, so what each device holds is its own.
    del params
    gc.collect()
    state = (engine.params, engine._pool_k, engine._pool_v)
    sharded = sum(x.nbytes for x in jax.tree.leaves(state))
    per_device = _bytes_per_device(jax.devices()[:tp])
    record["spans_all_devices"] = all(
        len(x.sharding.device_set) == tp for x in jax.tree.leaves(state))
    record["device_bytes_share"] = [round(b / sharded, 3)
                                    for b in per_device]
    record["pass"] = bool(
        record["pass"] and record["spans_all_devices"]
        and all(SHARD_SHARE_MIN <= b / sharded <= SHARD_SHARE_MAX
                for b in per_device))
    _say(**record)
    return {"phase": "serve_tp4", "pass": record["pass"], "device": device,
            "compile_s": round(meter.seconds, 1),
            "cache_hits": meter.hits}


def phase_train_mesh4(seed: int, cfg=None, *, batch=TRAIN_BATCH,
                      seq=TRAIN_SEQ, steps=TRAIN_STEPS,
                      axes=(("fsdp", 2), ("tp", 2))) -> dict:
    """The phase-2 model on a {"fsdp": 2, "tp": 2} mesh against the
    one-device loss curve."""
    n = 1
    for _, size in axes:
        n *= size
    device = _require_tpu(n)
    import jax

    from ray_tpu.parallel import create_mesh
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    meter = _CompileMeter()
    cfg, tokens = _train_setup(seed, cfg, batch, seq)
    one: dict = {"variant": "train_1_device"}
    with meter.window(one):
        ref = _train_losses(cfg, create_mesh({"dp": 1}, jax.devices()[:1]),
                            tokens, steps, seed, one)
    _say(**one)
    record: dict = {"variant": "train_" + "x".join(
        f"{a}{s}" for a, s in axes)}
    with meter.window(record):
        got = _train_losses(cfg, create_mesh(dict(axes), jax.devices()[:n]),
                            tokens, steps, seed, record)
    record["loss_max_abs_diff"] = round(
        max(abs(a - b) for a, b in zip(got, ref)), 5)
    record["loss_atol"] = TRAIN_MESH_LOSS_ATOL
    record["pass"] = bool(
        _loss_falls(got) and record["flash_kernel_in_step"]
        and record["param_devices"] == [n]
        and record["loss_max_abs_diff"] <= TRAIN_MESH_LOSS_ATOL)
    _say(**record)
    return {"phase": "train_mesh4", "pass": record["pass"], "device": device,
            "compile_s": round(meter.seconds, 1),
            "cache_hits": meter.hits}


PHASES = {"serve": phase_serve, "train": phase_train,
          "runtime": phase_runtime, "serve_tp4": phase_serve_tp4,
          "train_mesh4": phase_train_mesh4}


def child(name: str, seed: int) -> None:
    """Entry point of a phase's process: the report is its last line."""
    _say(**PHASES[name](seed))


if __name__ == "__main__":
    sys.exit(main())

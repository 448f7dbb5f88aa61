"""Continuous-batching decode engine, TPU-first.

The reference has no serving engine for LLMs (Serve hosts arbitrary
torch callables; continuous batching lives outside it in vLLM-class
engines). Serving an LM is this framework's flagship deployment, so
slot-based continuous batching is first-class here, built the XLA way:

- ONE fused decode program for the whole engine: B fixed decode slots
  advance together, every row at its OWN cache offset (per-row scatter
  writes + per-row masks — no recompilation as requests come and go,
  no left-padding). H decode iterations run inside a single program
  (`_decode_multi_paged`: lax.scan + on-device sampling + per-row
  eos/budget freezing), so the host pays ONE dispatch and ONE
  device->host transfer per H tokens instead of a blocking sample per
  token — the vLLM/Orca lesson that the decode inner loop must be free
  of host synchronization, applied the XLA way.
- Admission is a per-length-bucket BATCHED prefill program
  (`_prefill_rows_paged`): all same-bucket admissions of a step write
  their prompts' K/V into their rows' pool blocks in one dispatch while
  the other rows' state rides along untouched (donated buffers, in-place
  in HBM). First tokens are sampled on device by the fused decode from
  the device-resident `last_logits` — admission costs zero host
  round-trips.
- K/V lives in ONE refcounted pool of fixed-size token blocks behind
  per-row block tables (models/block_pool.py): a finished row's blocks
  return to the pool at once, a warm prompt SHARES its cached prefix
  blocks (models/prefix_cache.py), and a row the pool cannot cover is
  preempted and swapped or recomputed. A finished row's slot is reused
  immediately: stale K/V need no clearing because every mask is
  `slot < row_len`. Rows finishing mid-horizon freeze on device
  (row_len stops, emits masked to -1) and are retired by the host
  replay of the token block.
- The decode loop is ASYNC double-buffered (`pipeline_depth`, default
  2): during pure-decode stretches (nothing mid-prefill, and either
  the queue empty or every slot taken by a row whose budget outlasts
  the blocks in flight: no admission can happen before they are
  drained) the engine keeps a bounded ring of fused steps in flight,
  chaining each run-ahead dispatch off the previous one's
  device-carried row state and issuing `copy_to_host_async` on every
  token block, so the host replays step N's tokens while the device
  computes step N+1. The ring is flushed before any admission/prefill
  (those mutate the donated pool from the host side), and run-ahead
  iterations on rows that finished mid-flight are masked on device and
  accounted as `pipeline_overrun_tokens`.
- SPECULATIVE decoding composes with all of the above
  (`draft_params=`/`draft_cfg=`/`spec_window=`): the engine keeps a
  second (draft) KV plane per slot — a second block pool — and each
  decode dispatch becomes ONE batched draft-propose / target-verify
  round (`_spec_round_paged`): the draft scans up to `spec_window`
  greedy proposals for every live row, one batched target pass
  verifies the [B, window+1] chunk, and per-row acceptance /
  correction / eos / budget freezing happens on device, so the host
  still sees a single [window+1, B] token block per dispatch (the
  -1-trailing-column emit contract is unchanged). Greedy
  rows stay token-identical to solo `generate(greedy=True)`; sampled
  rows fall back to the plain fused decode per-row via the decode-mode
  lane (`submit(..., greedy=...)`) — rejection sampling is follow-up
  work. Per-row draft widths adapt to the measured acceptance rate via
  `SchedulerPolicy.spec_window_hint` (the speculation analog of
  `horizon_hint`).

Consistency contract (tested): greedy engine output for every request
is token-identical to that request's solo `generate` run, regardless of
admission order, slot reuse, or which other requests share the batch —
and regardless of the SCHEDULER POLICY: scheduling (models/scheduler.py
— FIFO, priority classes, bounded-queue backpressure, per-step prefill
budget) only reorders admissions, never what an admitted row computes.

Telemetry (models/engine_metrics.py) timestamps every request through
queued → admitted → decoding → finished and exports queue-wait / TTFT /
TPOT / occupancy through the util.metrics Prometheus plane; `stats()`
snapshots it for the Serve path (serve.metrics.report_engine_stats).

Cites: reference Serve's dynamic batching seam
(python/ray/serve/batching.py:1) coalesces CALLS; this engine coalesces
DECODE STEPS — requests join and leave a running batch mid-flight.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu._private import sanitize as _sanitize
from ray_tpu.models.adapter_pool import AdapterPool
from ray_tpu.models.block_pool import BlockPool, zero_state_planes
from ray_tpu.models.engine_metrics import EngineMetrics, NullEngineMetrics
from ray_tpu.models.engine_trace import resolve_tracer
from ray_tpu.models.generate import (_check_sampling_knobs, _expert_stacks,
                                     _layer_body, lm_head, sample_rows)
from ray_tpu.models.llama import LlamaConfig, llama_param_specs
from ray_tpu.models.moe import (MoeConfig, held_grouped_prefill,
                                held_hit_kernel)
from ray_tpu.models.prefix_cache import PrefixCacheIndex
from ray_tpu.ops import scope_names as sn
from ray_tpu.ops.attention import paged_attention, spmd_mesh_scope
from ray_tpu.ops.kv_quant import (KVQuantSpec, paged_quant_write,
                                  resolve_kv_quant)
from ray_tpu.models.scheduler import (EngineDraining, EngineOverloaded,
                                      FIFOPolicy, SchedulerPolicy,
                                      SubmitTimeout, make_policy)
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.parallel.sharding import (DEFAULT_RULES, named_sharding,
                                       prune_rules_for_mesh,
                                       shard_pytree)
from ray_tpu.util.compile_cache import ledger as _compile_ledger

Params = Dict[str, Any]

# A step at least this long counts as STALLED (`steps_stalled_total`): a
# serving step takes 50-190 ms and the stalls met on the machine 0.33-4.5 s.
STALL_STEP_S = 0.5


def _pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << (n - 1).bit_length()


def _key_data(key) -> np.ndarray:
    """Raw uint32[2] bits of a PRNG key (legacy array or typed key).

    Cold path (submit-time key normalisation, 8 bytes): the typed-key
    branch still routes its pull through the `_device_get` choke point so
    the sanitizer sees an expected transfer and telemetry counts it."""
    try:
        return np.asarray(key, np.uint32).reshape(2)
    except (TypeError, ValueError):
        return np.asarray(_device_get(jax.random.key_data(key)),
                          np.uint32).reshape(2)


def _device_get(x) -> np.ndarray:
    """The engine's ONLY device->host transfer. Every blocking fetch in
    the serving loop funnels through here so (a) the engine can count
    host syncs for telemetry (`host_syncs_per_token`) and (b) tests can
    wrap it to GATE the transfer budget — the fused decode path must
    stay at one pull per horizon, and an accidental per-token sync
    reintroduction fails tests/test_engine_horizon.py. Under the async
    pipeline the pull is usually a no-op wait: the block's
    `copy_to_host_async` was issued at dispatch, one or more fused
    steps earlier (tests/test_engine_pipeline.py gates that the next
    dispatch is issued BEFORE this fetch). When a runtime sanitizer is
    armed (RAY_TPU_SANITIZE=1 / DecodeEngine(sanitize=...)) the pull is
    marked EXPECTED — any device->host sync outside this funnel trips
    the sanitizer's ArrayImpl interposition."""
    san = _sanitize.active()
    if san is not None:
        return san.expected_get(x)
    return np.asarray(x)


def _host_async(x) -> None:
    """Start the sanctioned async device->host copy for a dispatched token
    block (pairs with the `_device_get` wait in `_drain_one`). Mirrors
    `_device_get`'s sanitizer contract for the non-blocking half."""
    san = _sanitize.active()
    if san is not None:
        san.expected_copy_async(x)
        return
    try:
        x.copy_to_host_async()
    except AttributeError:
        pass                       # non-jax.Array backends (tests)


@dataclasses.dataclass(frozen=True)
class _EngineShardings:
    """NamedShardings the tensor-parallel engine threads through its
    compiled programs as a STATIC jit argument (NamedSharding is
    hashable, so each mesh compiles its own program set and the
    unsharded engine — shardings=None — compiles exactly what it did
    before).

    ``logits`` [B, vocab]             — vocab over "tp"
    ``pool``   the block pool [L, NB, T, KV*D] (what the decode kernel
               reads) — KV heads over "tp" when the model's n_kv_heads
               divides tp, replicated otherwise, so gathers and
               scatters stay chip-local: the merged lane axis is
               head-major, so splitting it over "tp" splits whole KV
               heads
    ``d_pool`` — the DRAFT model's pool, pruned against the draft
               config's own dims (a nano draft often can't split its kv
               heads over the same mesh the target can). None on
               non-speculative engines, so every existing program
               signature hashes exactly as before.
    ``scale``/``d_scale`` [L, NB, KV] — the quantized pool's per-block
               per-kv-head scale slabs, sharded by the SAME pruned KV
               rules as the pool they dequantize. None when kv_quant
               is off (again: identical hashes for existing engines).
    """

    logits: NamedSharding
    pool: NamedSharding
    d_pool: Optional[NamedSharding] = None
    scale: Optional[NamedSharding] = None
    d_scale: Optional[NamedSharding] = None

    @property
    def replicated(self) -> NamedSharding:
        """Fully-replicated sharding on the same mesh — the [H, B]
        token block is pinned to it so the single device->host transfer
        stays whole on every chip (no cross-chip fetch at drain)."""
        return NamedSharding(self.logits.mesh, P())


# ---------------------------------------------------------------------------
# Compiled programs
# ---------------------------------------------------------------------------

# An `MoeConfig` engine keeps its expert-layer counters on the device,
# int32 [4] that wrap: (live assignments, token-expert rows computed,
# experts hit in decode, expert-layer runs in decode). Prefill and
# decode programs add to them over LIVE rows only; a decode program
# also appends them to its token block as `_MOE_CTR_ROWS` more rows, so
# they reach the host in the pull that fetches the tokens anyway. A
# dense model passes None everywhere: no leaves, the same programs. A
# layer that holds a share of its experts (`held_experts`) counts one
# thing more, after those four: the live assignments that landed on an
# expert held here.
_MOE_CTR_ROWS = 4
_MOE_CTR_NAMES = ("moe_assignments_total", "moe_rows_computed_total",
                  "moe_decode_experts_hit_total",
                  "moe_decode_layer_steps_total",
                  "moe_assignments_landed_total")


def _moe_ctr_rows(cfg) -> int:
    """Counters an expert-layer config keeps on the device (0: none)."""
    if not hasattr(cfg, "n_experts"):
        return 0
    return _MOE_CTR_ROWS + (cfg.held_experts is not None)


def _moe_count(moe_ctr, layer_stats):
    """One decode iteration's per-layer counts [L, 3] into the [4]
    counters; the fourth goes up by L, the expert layers that ran (a
    fourth column, the assignments that landed here, is the fifth)."""
    s = layer_stats.sum(axis=0)
    runs = jnp.full((1,), layer_stats.shape[0], jnp.int32)
    if layer_stats.shape[1] == 3:
        return moe_ctr + jnp.concatenate([s, runs])
    return moe_ctr + jnp.concatenate([s[:3], runs, s[3:]])


def _append_moe_ctr(toks, moe_ctr):
    """[H, B] token block -> [H + len(moe_ctr), B]: counter i fills
    row H + i, so the drain's one transfer carries both."""
    return jnp.concatenate(
        [toks, jnp.broadcast_to(moe_ctr[:, None],
                                (moe_ctr.shape[0], toks.shape[1]))])


def _prefill_live(rows, last_idx, chunk: int):
    """[N, chunk] bool: the positions of a prefill group an `MoeConfig`
    engine's expert-layer counters count. Live are positions up to each
    row's last real token; a group's padding rows repeat the last
    admission verbatim and count once."""
    n = rows.shape[0]
    with jax.named_scope(sn.MOE_ROUTER):
        earlier = jnp.arange(n)[None, :] < jnp.arange(n)[:, None]
        repeat = jnp.any((rows[:, None] == rows[None, :]) & earlier,
                         axis=1)
        return (jnp.arange(chunk)[None, :] <= last_idx[:, None]) \
            & ~repeat[:, None]


def _spec_accept(chunk, proposals, ver, v_logits, last_logits, row_len,
                 active, budget, tok_idx, d_tok, row_greedy, w_row,
                 window: int, eos_id: Optional[int], max_len: int):
    """On-device acceptance/correction/freeze of a speculative round —
    the batched analog of the solo accept loop in models/speculative.py,
    fused so the host never sees logits.

    Per row: count the longest prefix of `proposals` matching the
    target's argmax continuation `ver` (capped at the row's adaptive
    width `w_row`; forced 0 on sampled rows — their lane emits just the
    t0 they sampled), emit `[t0, d_1..d_a, correction]` truncated by
    eos / budget / room exactly like `_decode_multi_paged`'s per-iteration
    masking, and carry the corrected `last_logits` so the next round's
    t0 is this round's on-device correction. Returns the -1-trailing
    [window+1, B] emit block plus the advanced carry, including the
    draft-lag lane: after a FULLY accepted round the draft has already
    consumed d_1..d_{W-1} and only owes d_W (lag 1, pending token
    `d_tok`); any rejection resets the draft frontier to the emitted
    history (lag 0)."""
    B = row_len.shape[0]
    bidx = jnp.arange(B)
    jW = jnp.arange(window)
    match = (proposals == ver[:, :window]) \
        & (jW[None, :] < w_row[:, None]) & row_greedy[:, None]
    acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    pos = jnp.arange(window + 1)
    valid = pos[None, :] <= acc[:, None]
    if eos_id is not None:
        # Keep the first eos, cut everything after it (mid-window eos).
        iseos = ((chunk == eos_id) & valid).astype(jnp.int32)
        valid = valid & ((jnp.cumsum(iseos, axis=1) - iseos) == 0)
    valid = valid & (pos[None, :] < budget[:, None]) & active[:, None]
    n = valid.sum(axis=1).astype(jnp.int32)
    emits = jnp.where(valid, chunk, -1).T            # [window+1, B]

    budget = budget - n
    tok_idx = tok_idx + n
    last_tok = chunk[bidx, jnp.maximum(n - 1, 0)]
    done_now = (budget <= 0) | (row_len + n >= max_len)
    if eos_id is not None:
        done_now = done_now | ((n >= 1) & (last_tok == eos_id))
    cont = active & ~done_now
    row_len = row_len + n * cont.astype(jnp.int32)
    sel = v_logits[bidx, jnp.maximum(n - 1, 0)]
    last_logits = jnp.where(cont[:, None], sel, last_logits)
    full = cont & (n == window + 1)
    d_tok = jnp.where(full, chunk[:, window], d_tok)
    d_lag = jnp.where(active, full.astype(jnp.int32), 0)
    return emits, last_logits, row_len, cont, budget, tok_idx, \
        d_lag, d_tok


# ---------------------------------------------------------------------------
# Compiled programs over the block pool
# ---------------------------------------------------------------------------
# The engine has NO per-slot cache: every request's K/V lives in
# fixed-size token blocks of ONE device pool [L, NB, T, KV*D] (the same
# pool the prefix cache commits into; a token's KV heads merged
# head-major into one lane axis, the layout the paged kernel reads
# pages in) and each program reaches it through the per-row block table
# bt [B, MB]. Every program takes the pool donated and updates it in
# place; none holds a second copy of it, and none builds a dense per-row
# view of it: prefill, decode, draft and verify are ONE layer core
# (`_layers_paged`), S tokens a row wide, that scatters a chunk's K/V
# into the blocks its slots fall in and attends through the table
# (`ops.attention.paged_attention`). MB * T == max_len is enforced at
# construction, so off the chip, where `paged_attention` gathers a
# layer's rows and runs `generate._cached_attention`'s exact op
# sequence on them, a row attends EXACTLY the cache solo `generate`
# keeps for it — which is what makes engine output bit-identical to
# solo `generate` (tests/test_engine_paged.py). Block id 0 is the
# reserved null block: unallocated table entries point at it, padded
# scatters dump garbage into it, and no mask ever admits it. (The
# `_paged` suffix of the jitted names dates from when a dense twin
# existed; the benchmark's trace readers match it.)


def _gather_pages(pools, ids):
    """``pool[:, ids]`` for each of ``pools`` (K and V), one page at a
    time: ``[L, NB, T, KV*D]`` and block ids of any shape give
    ``[L, *ids.shape, T, KV*D]``, each page a `dynamic_slice` of the pool
    copied into place. Written as the gather it is, the slice would be a
    whole page, 1024 or 2048 lanes wide, and the chip's compiler splits a
    gather of slices wider than 512 lanes by first slicing its OPERAND,
    the whole pool, into 512-lane pieces: a copy of the pool per call
    (4.5 ms an array in every prefill call at the benchmark's 2.75 GiB,
    PERF.md PR 27). The loop moves the pages and nothing else, and leaves
    the lane axis whole, so under a mesh each chip moves its own heads'
    lanes. One `while_loop` for both pools: a loop is traced and lowered
    in every prefill program, and set-up pays for it."""
    L, _, T, W = pools[0].shape
    flat = ids.reshape(-1)
    n = flat.shape[0]

    def body(carry):
        i, outs = carry
        return i + 1, tuple(
            jax.lax.dynamic_update_slice(
                out, jax.lax.dynamic_slice(pool, (0, flat[i], 0, 0),
                                           (L, 1, T, W)), (0, i, 0, 0))
            for pool, out in zip(pools, outs))

    _, outs = jax.lax.while_loop(
        lambda carry: carry[0] < n, body,
        (np.int32(0), tuple(jnp.zeros((L, n, T, W), pool.dtype)
                             for pool in pools)))
    return tuple(out.reshape(L, *ids.shape, T, W) for out in outs)


def _zero_pools(planes, n_blocks: int, block_tokens: int, dtype,
                quantized: bool, shardings: Optional[_EngineShardings]):
    """The zeroed (pool_k, pool_v, scale_k, scale_v) of the two
    `CachePlane`s behind one table: pools [L, NB, T, lanes] — K and V
    ``KV*D`` wide, the layout the decode kernel reads, one page one
    contiguous [T, KV*D] slab, heads merged head-major; or whatever two
    planes the config names — and, quantized,
    their f32 scale slabs [L, NB, KV] (else None). Zero scales: dequant
    of the zero-initialised pool (incl. the null block) is exactly 0.0
    everywhere. Under a mesh both are placed by ``shardings`` (the
    plane's own pool/scale in the primary slots)."""
    if len(planes) != 2:
        raise ValueError("the engine's programs carry two planes behind a "
                         f"row's table, the config names {len(planes)}")
    out = [jnp.zeros((pl.layers, n_blocks, block_tokens, pl.lanes), dtype)
           for pl in planes]
    if quantized:
        out += [jnp.zeros((pl.layers, n_blocks, pl.heads), jnp.float32)
                for pl in planes]
    else:
        out += [None, None]
    if shardings is not None:
        out = [x if x is None else jax.device_put(
            x, shardings.pool if i < 2 else shardings.scale)
            for i, x in enumerate(out)]
    return tuple(out)


def _pin_pools(shardings, pool_k, pool_v, scale_k, scale_v,
               draft: bool = False):
    """Donated pools (and a quantized pool's scale slabs) leave a
    program with the sharding they arrived in: under a mesh the KV
    write stays a chip-local scatter, each chip owning its heads' lanes.
    No-op without a mesh."""
    if shardings is None:
        return pool_k, pool_v, scale_k, scale_v
    pool_sh, scale_sh = (shardings.d_pool, shardings.d_scale) if draft \
        else (shardings.pool, shardings.scale)
    pin = jax.lax.with_sharding_constraint
    pool_k, pool_v = pin(pool_k, pool_sh), pin(pool_v, pool_sh)
    if scale_k is not None and scale_sh is not None:
        scale_k, scale_v = pin(scale_k, scale_sh), pin(scale_v, scale_sh)
    return pool_k, pool_v, scale_k, scale_v


@functools.partial(jax.jit, static_argnames=("cfg", "shardings",
                                             "qspec", "final"),
                   donate_argnames=("pool_k", "pool_v", "scale_k",
                                    "scale_v", "last_logits", "hyb"))
def _prefill_rows_paged(params: Params, prompts: jax.Array, pool_k,
                        pool_v, last_logits, bt: jax.Array,
                        rows: jax.Array, starts: jax.Array,
                        last_idx: jax.Array, cfg: LlamaConfig,
                        shardings: Optional[_EngineShardings] = None,
                        adapters: Optional[Params] = None,
                        row_slot: Optional[jax.Array] = None,
                        scale_k=None, scale_v=None,
                        qspec: Optional[KVQuantSpec] = None,
                        moe_ctr: Optional[jax.Array] = None,
                        hyb: Optional[Params] = None,
                        bt_w: Optional[jax.Array] = None,
                        final: bool = True):
    """Batched admission/continuation prefill: N same-bucket chunks
    [N, Cb] in ONE program, each row at its OWN offset ``starts[n]`` (0
    for a cold admission; the shared prefix length for a warm one; the
    chunk frontier for a chunked continuation). It is `_layers_paged`,
    the decode program's layer core, Cb tokens wide over the N
    admission rows: every layer scatters the chunk's K/V into the
    blocks its slots ``starts[n] + arange(Cb)`` fall in, in place in
    the donated pool, and attends through the block table. Nothing is
    gathered out of the pool and no block below ``starts`` is written:
    shared prefix blocks are only ever read. Each row's last-real-token
    hidden state goes through the final norm and `lm_head` alone and
    its logits are scattered into the engine's device-resident
    `last_logits` [B, vocab]. No logits ever cross to the host: the
    fused decode samples the first token on device, so an admission
    costs zero host round-trips.

    Cb may exceed a chunk's true length (length-bucketed serving):
    trailing filler tokens' K/V land at slots >= the true frontier (or,
    where the row's chain ends, in the null block), which every later
    mask excludes and the next write overwrites — only the position
    `last_idx` (true chunk length - 1) is read out, and only the FINAL
    chunk's scatter survives in `last_logits`. `rows` may contain
    duplicates (power-of-two group padding repeats the last admission
    verbatim): duplicate scatters write identical values.

    ``adapters``/``row_slot`` (the LoRA pool stacks + this chunk's slot
    lane [N]) thread to `_layer_body`'s per-row deltas; ``moe_ctr`` is
    an `MoeConfig` engine's expert-layer counters (`_moe_count`). Both
    default to None, which adds no pytree leaves: the same program.

    Quantized pools (``qspec`` + the f32 ``scale_k``/``scale_v`` slabs)
    write through `paged_quant_write`, as decode and verify do: the
    blocks the chunk touches are read, the chunk's REAL tokens laid in
    (filler past `last_idx` is left out and every slot at or beyond the
    true frontier zeroed, so neither filler nor a previous tenant's
    garbage reaches a block's absmax), requantized and written back.
    The chunk attends ITSELF as computed and what lies below it as the
    pool stores it (`paged_attention`'s ``own_kv``), which is what it
    saw in the dense view this program used to build: rounding reaches
    a token only through what it reads back from the pool. That takes
    the pure-lax lowering on the chip too (one layer's rows at a time);
    the kernel has no operand for the chunk's own K/V (ROADMAP S17).

    ``hyb`` is a family's own device state (window pools and recurrent
    state, donated like the pool; None adds no leaf) and ``bt_w`` the
    rows' window table; ``rows`` then also says which slot's recurrent
    state a chunk continues (zero where ``starts`` is 0) and stores.
    Where `prefill_layers` is not every layer, a chunk that is not a
    prompt's last (static ``final`` False) stops after those and leaves
    `last_logits` as it is; a last chunk's other layers and head run for
    the row's last real position alone (`hybrid.layers_paged`)."""
    n, s = prompts.shape
    h, pool_k, pool_v, scale_k, scale_v, moe_stats, hyb = _layers_paged(
        params, prompts, pool_k, pool_v, bt, starts, cfg,
        adapters=adapters, row_slot=row_slot, scale_k=scale_k,
        scale_v=scale_v, qspec=qspec,
        moe_live=None if moe_ctr is None
        else _prefill_live(rows, last_idx, s),
        n_valid=last_idx + 1, hyb=hyb, bt_w=bt_w, rows=rows,
        last_idx=last_idx, final=final)
    if moe_ctr is not None:
        seen = moe_stats.sum(axis=0)
        moe_ctr = moe_ctr.at[:2].add(seen[:2])
        if seen.shape[0] > 3:         # held experts: what landed here
            moe_ctr = moe_ctr.at[_MOE_CTR_ROWS:].add(seen[3:])
    if cfg.stack() is None:
        h = h[jnp.arange(n), last_idx][:, None]
    if final:
        # the final norm and lm_head see the ONE position a row is read at
        last = _lm_head(params, h, cfg)
        with jax.named_scope(sn.LM_HEAD):
            out_logits = last_logits.at[rows].set(last[:, 0])
    else:
        out_logits = last_logits
    pool_k, pool_v, scale_k, scale_v = _pin_pools(
        shardings, pool_k, pool_v, scale_k, scale_v)
    if shardings is not None:
        out_logits = jax.lax.with_sharding_constraint(
            out_logits, shardings.logits)
    return pool_k, pool_v, scale_k, scale_v, out_logits, moe_ctr, hyb


def _decode_layer_rows_paged(h, layer, li, kc, vc, bt, slots,
                             cfg: LlamaConfig, lora=None,
                             lora_slots=None,
                             qspec: Optional[KVQuantSpec] = None,
                             moe_live=None, n_valid=None, experts=None):
    """One decoder layer against the pool, S tokens a row (1 in the
    fused decode, the window in a speculative round, a chunk in
    prefill), each row writing at its own slots and attending its own
    prefix. All the per-layer
    math lives in generate.py's `_layer_body` (one source of truth with
    solo `generate`); only the cache write and the attention read
    differ. Row b's new K/V scatter into layer ``li`` of the WHOLE
    pool, at physical block ``bt[b, slot//T]`` and offset ``slot%T``,
    and attention reads back through `ops.attention.paged_attention`
    (the block-table gather + `_cached_attention`'s exact op sequence,
    or the kernel), which is handed the whole pool and ``li`` as well.
    The pool is the layer scan's carry: the scatter updates it in place
    and nothing of a layer's or the pool's size is sliced, relaid or
    restacked.

    Frontier blocks are always private to their row — a shared block
    is never a write target (full-prompt prefix hits copy-on-write
    their tail block at admission) — so the scatter triples are unique
    across live rows; retired/empty rows, and slots past a row's
    allocated chain (speculative overshoot, whose results the accept
    mask discards), scatter garbage into the null block.

    ``kc``/``vc`` are (pool ``[L, NB, T, KV*D]``, scales ``[L, NB, KV]``
    or None) pairs — `_layer_body` only ever touches them through the
    closures below. A quantized pool's write is `paged_quant_write`'s
    read-modify-write of the blocks the window touches (gather +
    dequant + token write + stale-slot zero + requant; ``n_valid`` [B],
    prefill's, says how many of the S tokens are real), and
    `paged_attention` gets the scales so dequant happens inside its
    gather."""
    B, S = slots.shape
    T = kc[0].shape[2]
    span = bt.shape[1] * T                 # == engine max_len

    if qspec is None:
        with jax.named_scope(sn.KV_WRITE):
            blk = bt[jnp.arange(B)[:, None], slots // T]    # [B, S]
            off = slots % T

        def write_kv(kc, vc, k, v):
            return tuple(
                (pool.at[li, blk, off].set(
                    x.reshape(B, S, -1).astype(pool.dtype)), None)
                for (pool, _), x in ((kc, k), (vc, v)))
    else:
        def write_kv(kc, vc, k, v):
            return tuple(
                paged_quant_write(pool, scales, li, bt, slots[:, 0], x,
                                  qspec, n_valid=n_valid)
                for (pool, scales), x in ((kc, k), (vc, v)))

    # a chunk's bucket filler queries nothing: its result is never read
    real = None if n_valid is None else \
        jnp.arange(S)[None, :] < n_valid[:, None]
    q_slots = slots if real is None else jnp.where(real, slots, -1)

    def attend(q, k, v, kc, vc):
        # A quantized pool's CHUNK attends itself as computed and only
        # what lies below it as stored, as it did through the dense view
        # (a cold prompt's first token is the dense-precision engine's);
        # a decode token or a window attends itself as stored.
        own = (k, v) if qspec is not None and n_valid is not None else None
        return paged_attention(q, kc[0], vc[0], bt, q_slots, layer=li,
                               kv_valid_len=span, k_scale=kc[1],
                               v_scale=vc[1], own_kv=own)

    # a chunk group's padding rows repeat another row: `moe_live` counts
    # them once, but their K/V land on their twin's, so they are READ
    return _layer_body(h, layer, kc, vc, slots, write_kv, slots, span,
                       cfg, attend=attend, lora=lora,
                       lora_slots=lora_slots, moe_live=moe_live,
                       experts=None if experts is None else (experts, li),
                       moe_read=real)


def _layers_paged(params: Params, toks: jax.Array, pool_k, pool_v,
                  bt, starts, cfg: LlamaConfig, adapters=None,
                  row_slot=None, scale_k=None, scale_v=None,
                  qspec: Optional[KVQuantSpec] = None,
                  moe_live=None, n_valid=None, hyb=None, bt_w=None,
                  live=None, rows=None, last_idx=None, final: bool = True):
    """The layer stack for ALL rows of ``toks`` [B, S]: feed each row's
    chunk at slots ``starts + arange(S)``, attending slots up to its
    own, and return the hidden states [B, S, d] ahead of the final norm
    (plus the pool and the expert layers' per-layer counts [L, 3] or
    None: see `_moe_count`). The fused decode (S = 1), the draft
    consume/scan steps, the target verify pass (S = the window) and
    prefill (S = the chunk's bucket, B = the admission group) are all
    this one shape family. Dead/frozen rows compute discarded garbage
    at their frontier slot — one past their real tokens, or the null
    block for empty rows — which every mask excludes and the next
    occupant's prefill overwrites.

    The layer scan takes ``(layer weights, layer index)`` as ``xs`` and
    CARRIES the pool (and a quantized pool's scale slabs) beside the
    activations: each layer writes its tokens into ``pool[li]`` in
    place and attends ``pool[li]`` where it lies. Plain function so
    `_decode_multi_paged`'s scan can inline it and keep the pool in its
    own carry.

    A family whose layers are not `generate._layer_body`'s runs its own
    stack (`block_pool.ServedConfig.stack`) over the same two pools, its
    state ``hyb`` (the seventh result; None where it has none) and the
    window table ``bt_w``, and is handed the rest as it came; the scan
    below is the dense and sparse families'."""
    own = cfg.stack()
    if own is not None:
        if hyb is not None and live is None:
            # a chunk's real tokens advance recurrent state, no filler
            live = jnp.arange(toks.shape[1])[None, :] < n_valid[:, None]
        h, pool_k, pool_v, moe_stats, hyb = own.layers_paged(
            params, toks, pool_k, pool_v, bt, starts, cfg, state=hyb,
            bt_w=bt_w, live=live, rows=rows, n_valid=n_valid,
            last_idx=last_idx, final=final, moe_live=moe_live)
        return h, pool_k, pool_v, scale_k, scale_v, moe_stats, hyb
    S = toks.shape[1]
    slots = starts[:, None] + jnp.arange(S)[None, :]
    with jax.named_scope(sn.EMBED):
        h = params["tok_embed"].astype(cfg.dtype)[toks]

    def body(carry, xs):
        h, kc, vc = carry
        layer, li = xs[:2]
        h, kc, vc, st = _decode_layer_rows_paged(
            h, layer, li, kc, vc, bt, slots, cfg,
            lora=xs[2] if adapters is not None else None,
            lora_slots=row_slot, qspec=qspec, moe_live=moe_live,
            n_valid=n_valid, experts=experts)
        return (h, kc, vc), st

    # an `MoeConfig`'s expert stacks stay out of the scan's slices where
    # a kernel reads them (`generate._expert_stacks`)
    layers, experts = _expert_stacks(params["layers"], cfg, toks.size)
    xs = (layers, jnp.arange(pool_k.shape[0]))
    if adapters is not None:
        xs = xs + (adapters,)
    (h, (pool_k, scale_k), (pool_v, scale_v)), moe_stats = jax.lax.scan(
        body, (h, (pool_k, scale_k), (pool_v, scale_v)), xs)
    return h, pool_k, pool_v, scale_k, scale_v, moe_stats, None


def _lm_head(params: Params, h: jax.Array, cfg: LlamaConfig):
    """Final norm and vocab projection: [B, S, d] -> f32 [B, S, vocab]."""
    own = cfg.stack()
    return (lm_head if own is None else own.lm_head)(params, h, cfg)


def _decode_core_paged(params: Params, toks: jax.Array, pool_k, pool_v,
                       bt, starts, cfg: LlamaConfig, **kw):
    """`_layers_paged` and `_lm_head` over every position: the
    [B, S, vocab] logits the decode, draft and verify steps sample
    from, beside `_layers_paged`'s other results."""
    h, *rest = _layers_paged(params, toks, pool_k, pool_v, bt, starts,
                             cfg, **kw)
    return (_lm_head(params, h, cfg), *rest)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "horizon", "greedy",
                                    "top_k", "top_p", "eos_id",
                                    "shardings", "qspec"),
                   donate_argnames=("pool_k", "pool_v", "scale_k",
                                    "scale_v", "last_logits", "hyb"))
def _decode_multi_paged(params: Params, pool_k, pool_v, bt,
                        last_logits, row_len, active, budget, tok_idx,
                        row_keys, row_greedy, temperature,
                        cfg: LlamaConfig,
                        horizon: int, greedy: bool,
                        top_k: Optional[int], top_p: Optional[float],
                        eos_id: Optional[int],
                        shardings: Optional[_EngineShardings] = None,
                        adapters: Optional[Params] = None,
                        row_slot: Optional[jax.Array] = None,
                        scale_k=None, scale_v=None,
                        qspec: Optional[KVQuantSpec] = None,
                        moe_ctr: Optional[jax.Array] = None,
                        hyb: Optional[Params] = None,
                        bt_w: Optional[jax.Array] = None):
    """Fuse `horizon` decode iterations into ONE program: a `lax.scan`
    whose body samples every row's next token ON DEVICE from the
    carried `last_logits` (greedy argmax, or per-row rng streams — see
    generate.sample_rows), feeds it through `_decode_core_paged`, and
    applies per-row eos/budget/room masking so rows that finish
    mid-horizon FREEZE: their row_len stops advancing, their
    `last_logits` stops updating, and their remaining emits are masked
    to -1. The host gets the whole [horizon, B] token block in a single
    transfer instead of one blocking sample per token.

    Per-iteration transition (bit-identical to the host replay in
    `DecodeEngine._emit_block`, which mirrors it without touching the
    device):
        tok      = sample(last_logits)          # emit if active
        budget  -= active;  tok_idx += active
        done     = budget <= 0 | row_len+1 >= max_len | tok == eos
        feed tok at slot row_len (all rows; frozen rows write garbage
        one slot past their content — masked everywhere, overwritten by
        the slot's next prefill)
        row_len += active & ~done;  last_logits updates where continuing

    Returns the token block and the FULL scan carry: the next horizon
    samples straight from the carried `last_logits`, and the async
    pipeline chains a run-ahead dispatch off the carried row state with
    no host synchronization between dispatches. `row_greedy` (bool [B])
    makes rows take the argmax in a program whose static `greedy` is
    False, so a mixed batch serves both modes in one program.

    The block table is a step invariant: the host grows it between
    dispatches, never inside one. The pool is ONE donated buffer: this
    scan carries it, the layer scan inside `_decode_core_paged` carries
    it again, each token's K/V is scattered into it in place and the
    kernel reads pages where they lie, so the program holds no second
    pool and moves nothing of a layer's size (tests/test_tpu_compile.py
    holds the compiled program to that). A quantized pool adds its
    scale slabs to the carry; qspec=None leaves every pytree and the
    traced program as they were. So does ``hyb`` None: a `HybridConfig`'s
    window pools and recurrent state ride the carry the same way, and
    only rows that go on (``cont``) advance their recurrent state, so a
    frozen, dead or mid-prefill row's state stays what it was."""
    max_len = bt.shape[1] * pool_k.shape[2]

    def body(carry, _):
        pool_k, pool_v, scale_k, scale_v, last_logits, row_len, \
            active, budget, tok_idx, moe_ctr, hyb = carry
        with jax.named_scope(sn.SAMPLE):
            tok = sample_rows(last_logits, row_keys, tok_idx,
                              greedy=greedy, temperature=temperature,
                              top_k=top_k, top_p=top_p)
            if not greedy:
                tok = jnp.where(
                    row_greedy,
                    jnp.argmax(last_logits, axis=-1).astype(tok.dtype),
                    tok)
            emit = jnp.where(active, tok, -1)
            live = active.astype(jnp.int32)
            budget = budget - live
            tok_idx = tok_idx + live
            done_now = (budget <= 0) | (row_len + 1 >= max_len)
            if eos_id is not None:
                done_now = done_now | (tok == eos_id)
            cont = active & ~done_now
        logits, pool_k, pool_v, scale_k, scale_v, moe_stats, hyb = \
            _decode_core_paged(
                params, tok[:, None], pool_k, pool_v, bt, row_len, cfg,
                adapters=adapters, row_slot=row_slot, scale_k=scale_k,
                scale_v=scale_v, qspec=qspec,
                moe_live=None if moe_ctr is None else cont[:, None],
                hyb=hyb, bt_w=bt_w,
                live=None if hyb is None else cont[:, None])
        logits = logits[:, 0]
        if moe_ctr is not None:
            moe_ctr = _moe_count(moe_ctr, moe_stats)
        with jax.named_scope(sn.SAMPLE):
            row_len = row_len + cont.astype(jnp.int32)
            last_logits = jnp.where(cont[:, None], logits, last_logits)
        # Pin the scan carry to the engine's layout every iteration:
        # the carried logits stay vocab-sharded beside the pool, so XLA
        # partitions attention heads and MLP width instead of
        # replicating the whole model.
        pool_k, pool_v, scale_k, scale_v = _pin_pools(
            shardings, pool_k, pool_v, scale_k, scale_v)
        if shardings is not None:
            last_logits = jax.lax.with_sharding_constraint(
                last_logits, shardings.logits)
        return (pool_k, pool_v, scale_k, scale_v, last_logits, row_len,
                cont, budget, tok_idx, moe_ctr, hyb), emit

    (pool_k, pool_v, scale_k, scale_v, last_logits, row_len, active,
     budget, tok_idx, moe_ctr, hyb), toks = jax.lax.scan(
            body, (pool_k, pool_v, scale_k, scale_v, last_logits,
                   row_len, active, budget, tok_idx, moe_ctr, hyb),
            None, length=horizon)
    if moe_ctr is not None:
        toks = _append_moe_ctr(toks, moe_ctr)
    if shardings is not None:
        # The [H, B] block is the ONE device->host transfer: keep it
        # fully replicated so the drain reads whole from any chip —
        # host-sync bytes stay 4*H*B regardless of tp degree.
        toks = jax.lax.with_sharding_constraint(
            toks, shardings.replicated)
    return (toks, pool_k, pool_v, scale_k, scale_v, last_logits,
            row_len, active, budget, tok_idx, moe_ctr, hyb)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "d_cfg", "window", "greedy",
                                    "top_k", "top_p", "eos_id",
                                    "shardings", "qspec"),
                   donate_argnames=("pool_k", "pool_v", "pool_dk",
                                    "pool_dv", "scale_k", "scale_v",
                                    "scale_dk", "scale_dv",
                                    "last_logits"))
def _spec_round_paged(params: Params, d_params: Params, pool_k, pool_v,
                      pool_dk, pool_dv, bt, bt_d, last_logits, row_len,
                      active, budget, tok_idx, d_lag, d_tok, row_keys,
                      row_greedy, w_row, temperature, cfg: LlamaConfig,
                      d_cfg: LlamaConfig, window: int, greedy: bool,
                      top_k: Optional[int], top_p: Optional[float],
                      eos_id: Optional[int],
                      shardings: Optional[_EngineShardings] = None,
                      scale_k=None, scale_v=None, scale_dk=None,
                      scale_dv=None,
                      qspec: Optional[KVQuantSpec] = None):
    """ONE batched draft-propose / target-verify round for every live
    row — the speculative replacement for a `_decode_multi_paged`
    dispatch. The target plane reaches its K/V through `bt`, the draft
    plane through its own private table `bt_d` (draft blocks are never
    shared — the trie only indexes the target pool).

    Round structure (greedy rows; sampled rows ride the same program
    with acceptance forced to 0, so they advance exactly one sampled
    token per round — their solo stream):

      t0        = argmax(last_logits)         # last round's correction
      draft     consumes its 2-wide catch-up chunk at `row_len - d_lag`
                (after a fully-accepted round the draft still owes its
                final proposal — carried in `d_tok` with `d_lag`=1 — so
                the chunk is always [pend, t0] and the program never
                recompiles on acceptance length), then scans
                `window - 1` more greedy proposals at row_len+1+j.
      verify    ONE target pass over [t0, d_1..d_W] at `row_len`.
      accept    `_spec_accept` on device; stale K/V from rejected
                candidates sits exactly where next round's writes land
                (write-before-attend, same argument as solo spec).

    Emitted tokens are ALWAYS the target's own argmax chain — a stale
    or cold draft plane can only shrink acceptance, never change
    output — which is what makes swap-in re-seeding and cold draft
    admissions safe. Returns the [window+1, B] -1-trailing emit block
    plus the full carry, so the async pipeline chains speculative
    run-ahead dispatches like plain ones. With kv_quant BOTH planes are
    quantized — each pool
    carries its own scale slab; a rejected window's stale K/V is
    zeroed out of the next overlapping write's absmax by
    `paged_quant_write`, so no-rollback cache discipline still holds."""
    B = row_len.shape[0]
    bidx = jnp.arange(B)
    W = window
    max_len = bt.shape[1] * pool_k.shape[2]

    with jax.named_scope(sn.SAMPLE):
        t_greedy = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        if greedy:
            t0 = t_greedy
        else:
            t_samp = sample_rows(last_logits, row_keys, tok_idx,
                                 greedy=False, temperature=temperature,
                                 top_k=top_k, top_p=top_p)
            t0 = jnp.where(row_greedy, t_greedy, t_samp)

    pend = jnp.where(d_lag == 1, d_tok, t0)
    chunk2 = jnp.stack([pend, t0], axis=1)
    d_logits, pool_dk, pool_dv, scale_dk, scale_dv, *_ = \
        _decode_core_paged(
            d_params, chunk2, pool_dk, pool_dv, bt_d, row_len - d_lag,
            d_cfg, scale_k=scale_dk, scale_v=scale_dv, qspec=qspec)
    first = jnp.argmax(d_logits[bidx, d_lag],
                       axis=-1).astype(jnp.int32)

    def dstep(carry, j):
        tok, pool_dk, pool_dv, scale_dk, scale_dv = carry
        lg, pool_dk, pool_dv, scale_dk, scale_dv, *_ = _decode_core_paged(
            d_params, tok[:, None], pool_dk, pool_dv, bt_d,
            row_len + 1 + j, d_cfg, scale_k=scale_dk, scale_v=scale_dv,
            qspec=qspec)
        nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
        return (nxt, pool_dk, pool_dv, scale_dk, scale_dv), tok

    (lastp, pool_dk, pool_dv, scale_dk, scale_dv), dtoks = jax.lax.scan(
        dstep, (first, pool_dk, pool_dv, scale_dk, scale_dv),
        jnp.arange(W - 1))
    proposals = jnp.concatenate([dtoks.T, lastp[:, None]], axis=1) \
        if W > 1 else lastp[:, None]

    chunk = jnp.concatenate([t0[:, None], proposals], axis=1)
    v_logits, pool_k, pool_v, scale_k, scale_v, *_ = _decode_core_paged(
        params, chunk, pool_k, pool_v, bt, row_len, cfg,
        scale_k=scale_k, scale_v=scale_v, qspec=qspec)
    ver = jnp.argmax(v_logits, axis=-1).astype(jnp.int32)

    with jax.named_scope(sn.SAMPLE):
        (emits, last_logits, row_len, active, budget, tok_idx, d_lag,
         d_tok) = _spec_accept(chunk, proposals, ver, v_logits,
                               last_logits, row_len, active, budget,
                               tok_idx, d_tok, row_greedy, w_row, W,
                               eos_id, max_len)
    pool_k, pool_v, scale_k, scale_v = _pin_pools(
        shardings, pool_k, pool_v, scale_k, scale_v)
    pool_dk, pool_dv, scale_dk, scale_dv = _pin_pools(
        shardings, pool_dk, pool_dv, scale_dk, scale_dv, draft=True)
    if shardings is not None:
        last_logits = jax.lax.with_sharding_constraint(
            last_logits, shardings.logits)
        emits = jax.lax.with_sharding_constraint(emits,
                                                 shardings.replicated)
    return (emits, pool_k, pool_v, pool_dk, pool_dv, scale_k, scale_v,
            scale_dk, scale_dv, last_logits, row_len, active, budget,
            tok_idx, d_lag, d_tok)


@functools.partial(jax.jit, static_argnames=("shardings",),
                   donate_argnames=("pool_k", "pool_v", "scale_k",
                                    "scale_v"))
def _cow_blocks(pool_k, pool_v, src: jax.Array, dst: jax.Array,
                shardings: Optional[_EngineShardings] = None,
                scale_k=None, scale_v=None):
    """Copy-on-write block duplication: ONE program copies every
    (src -> dst) pair of this admission round. Dispatched when a warm
    admission matched its FULL prompt — the tail block must still grow
    the row's generated tokens, so the row gets a private copy instead
    of a share (every non-tail matched block stays zero-copy). src/dst
    are power-of-two padded with (0, 0): null -> null, harmless. A
    quantized pool copies its per-block scales alongside — the copy is
    byte-exact, never a requantization."""
    with jax.named_scope(sn.KV_WRITE):
        k, v = _gather_pages((pool_k, pool_v), src)
        pool_k = pool_k.at[:, dst].set(k)
        pool_v = pool_v.at[:, dst].set(v)
        if scale_k is not None:
            scale_k = scale_k.at[:, dst].set(scale_k[:, src])
            scale_v = scale_v.at[:, dst].set(scale_v[:, src])
    return _pin_pools(shardings, pool_k, pool_v, scale_k, scale_v)


@functools.partial(jax.jit, static_argnames=("shardings",))
def _swap_out_gather(pool_k, pool_v, block_ids: jax.Array,
                     shardings: Optional[_EngineShardings] = None,
                     scale_k=None, scale_v=None):
    """Gather a preemption victim's blocks [L, n, T, KV*D] out of the
    pool into fresh buffers. The caller issues `copy_to_host_async` on
    the result and drops the device reference once the host copy
    lands, so the victim's HBM is actually reclaimed. block_ids is
    power-of-two padded with the null block (its garbage rides along
    and is scattered straight back at swap-in). A quantized pool ships
    the QUANTIZED bytes plus the [L, n, KV] scales — roughly half the
    bf16 swap traffic — and the round trip is byte-exact by
    construction (no dequantization happens on either leg)."""
    with jax.named_scope(sn.KV_GATHER):
        k, v = _gather_pages((pool_k, pool_v), block_ids)
        if scale_k is None:
            return k, v, None, None
        return k, v, scale_k[:, block_ids], scale_v[:, block_ids]


@functools.partial(jax.jit, static_argnames=("shardings",),
                   donate_argnames=("pool_k", "pool_v", "scale_k",
                                    "scale_v"))
def _swap_in_scatter(pool_k, pool_v, host_k, host_v,
                     block_ids: jax.Array,
                     shardings: Optional[_EngineShardings] = None,
                     scale_k=None, scale_v=None, host_sk=None,
                     host_sv=None):
    """Scatter a swapped-out request's host K/V into a freshly
    allocated block chain — the other half of preempt-and-swap. The
    new physical block ids need not match the old ones: the block
    table indirection is what makes the bytes land logically where
    they were. Quantized bytes + scales scatter back verbatim."""
    with jax.named_scope(sn.KV_WRITE):
        pool_k = pool_k.at[:, block_ids].set(host_k.astype(pool_k.dtype))
        pool_v = pool_v.at[:, block_ids].set(host_v.astype(pool_v.dtype))
        if scale_k is not None:
            scale_k = scale_k.at[:, block_ids].set(
                host_sk.astype(scale_k.dtype))
            scale_v = scale_v.at[:, block_ids].set(
                host_sv.astype(scale_v.dtype))
    return _pin_pools(shardings, pool_k, pool_v, scale_k, scale_v)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class _Request:
    __slots__ = ("req_id", "prompt", "max_new_tokens", "tokens", "done",
                 "priority", "seq", "rng", "deadline", "shed", "resume",
                 "greedy", "adapter_id", "handoff")

    def __init__(self, req_id: int, prompt: List[int],
                 max_new_tokens: int, priority: int = 0, seq: int = 0,
                 rng: Optional[np.ndarray] = None,
                 deadline: Optional[float] = None):
        self.req_id = req_id
        self.prompt = list(prompt)
        self.max_new_tokens = max_new_tokens
        self.tokens: List[int] = []
        self.done = False
        self.priority = priority    # lower = admitted first (priority policy)
        self.seq = seq              # submission order (FIFO tie-break)
        self.rng = rng              # [2] uint32 per-request key stream
        self.deadline = deadline    # absolute clock time; None = no SLO
        self.shed = False           # retired past-deadline, no prefill run
        self.resume = False         # preempted; re-queued to swap back in
        self.greedy = None          # per-request decode-mode override
        self.adapter_id = None      # LoRA adapter (None = base model)
        self.handoff = False        # imported from a prefill-class
        #                             replica, awaiting decode admission


class _PrefillState:
    """A slot row whose prompt suffix is still being written.

    ``pos`` is the row's prefill frontier: slots [0, pos) hold valid
    K/V (shared prefix + completed chunks). ``nodes`` are the PENDING
    trie nodes this row's prefill fills in place — each is committed as
    soon as the frontier covers its block.
    ``prompt`` is the token sequence being prefilled — the request's
    prompt, except for a preempt="recompute" re-admission, which
    replays prompt + already-emitted tokens (same K/V, recomputed)."""

    __slots__ = ("req", "pos", "nodes", "prompt")

    def __init__(self, req: _Request, pos: int, nodes: list,
                 prompt: Optional[List[int]] = None):
        self.req = req
        self.pos = pos
        self.nodes = nodes
        self.prompt = req.prompt if prompt is None else prompt


class _SwapState:
    """A preempted (or handed-off) request's spilled decode state.

    ``k``/``v`` are HOST copies of the victim's gathered blocks
    [L, nbp, T, KV*D] — `copy_to_host_async` overlaps the pull, and
    dropping the device reference is what actually returns the HBM.
    They are None under preempt="recompute", where re-admission
    re-prefills prompt + emitted tokens instead of scattering bytes
    back. ``row_len``/``tok_idx``/``budget``/``logits`` restore the
    row exactly where it froze; the token stream then continues
    bit-identically because `step_rng_key` depends only on the
    request's key and tok_idx — never on which row or which step."""

    __slots__ = ("k", "v", "n_blocks", "row_len", "tok_idx", "budget",
                 "logits", "sk", "sv")

    def __init__(self, k, v, n_blocks: int, row_len: int, tok_idx: int,
                 budget: int, logits, sk=None, sv=None):
        self.k = k
        self.v = v
        self.n_blocks = n_blocks
        self.row_len = row_len
        self.tok_idx = tok_idx
        self.budget = budget
        self.logits = logits
        # quantized pools spill their per-block scales alongside the
        # (quantized) bytes; None for an unquantized pool
        self.sk = sk
        self.sv = sv

    @property
    def nbytes(self) -> int:
        """Host bytes this state carries (0 under recompute)."""
        return sum(x.nbytes for x in (self.k, self.v, self.logits,
                                      self.sk, self.sv) if x is not None)


class _InflightStep:
    """One dispatched-but-not-yet-drained fused decode step.

    ``toks`` is the step's [H, B] device token block — its
    `copy_to_host_async` was issued at dispatch, so by the time the
    host drains it (one or more steps later) the bytes are already on
    their way or landed. ``chain`` is the dispatch's returned device
    row state (row_len, active, budget, tok_idx): the NEXT run-ahead
    dispatch consumes it directly, so queued steps never synchronize
    with the host. ``run_ahead`` marks steps dispatched before the
    host had replayed the previous block — only those can contain
    overrun iterations for rows that had already finished. ``seq`` is
    the step's place among ALL programs the engine dispatched, ``ahead``
    the blocks in flight in front of it then, ``t_dispatch`` the engine
    clock at its dispatch's return (a first token's path, `stats()`'s
    ``first_*`` aggregates)."""

    __slots__ = ("toks", "H", "rows", "run_ahead", "chain", "spec",
                 "w_max", "w_row", "seq", "ahead", "t_dispatch")

    def __init__(self, toks, H: int, rows: List[int], run_ahead: bool,
                 chain: tuple, spec: bool = False, w_max: int = 0,
                 w_row=None):
        self.toks = toks
        self.H = H
        self.rows = rows
        self.run_ahead = run_ahead
        self.chain = chain
        self.spec = spec            # speculative round: H == w_max + 1
        self.w_max = w_max          # dispatch draft width
        self.w_row = w_row          # per-row width snapshot [B] (np)
        self.seq = self.ahead = 0   # the three are stamped when the
        self.t_dispatch = 0.0       # dispatch returns


class DecodeEngine:
    """Slot-based continuous batching over one paged KV pool.

    `submit()` enqueues a request; `step()` admits queued requests into
    free slots (batched, same-bucket prefills share ONE program), then
    advances every live slot up to `decode_horizon` tokens with ONE
    fused device program and ONE device->host transfer (the [H, B]
    token block); `run()` drains everything. The horizon adapts each
    step via the scheduler's `horizon_hint`: 1 while queued requests
    could take a free slot next step (protect TTFT), the full
    `decode_horizon` once slots are saturated or the queue is empty
    (amortize dispatch overhead) — pass `step(horizon=...)` to pin it.

    Model families: ``cfg`` is a `LlamaConfig` (dense decoder) or an
    `MoeConfig` (sparse: `moe.moe_ffn_dropless` experts and, with
    `qk_norm`, OLMoE's q/k norm). The family is read in ONE place,
    `generate._layer_body`, so both are served by the same programs,
    scheduler, block pool, KV quantization, prefix cache, preemption and
    fleet; the KV side does not differ. An `MoeConfig` refuses what
    reads the dense feed-forward's names (`tp=`/`mesh=`, `lora=` targets
    on w_gate/w_up/w_down, a `draft_cfg=` of the other family) and adds
    four counters to `stats()` (`moe_assignments_total`,
    `moe_rows_computed_total`, `moe_decode_experts_hit_total`,
    `moe_decode_layer_steps_total`), counted on the device over live
    rows and carried to the host in the token block's own transfer.

    `pipeline_depth` (default 2) bounds the async ring of fused steps
    kept in flight during pure-decode stretches: step N+1 is dispatched
    BEFORE step N's token block is pulled to the host (the block's
    `copy_to_host_async` overlaps N+1's compute), chained through the
    device-carried row state, and the host drains/replays one step
    behind. The ring flushes whenever a queued request finds a free
    slot or a row is mid-chunked-prefill, so scheduling decisions
    always see fully-replayed host state; a queue behind FULL slots is
    no pending admission, and the ring runs ahead of every block in
    which no row's budget ends. Depth 1 is the synchronous engine.
    Output is token-identical at every depth.

    Greedy by default; sampling mode (greedy=False) applies the same
    temperature/top_k/top_p semantics as `generate`, with a PER-REQUEST
    key stream: request r's i-th token uses
    ``step_rng_key(r.rng, i)`` — exactly solo `generate`'s schedule —
    so sampled output, like greedy output, is token-identical to that
    request's solo run (pass ``submit(..., rng=...)`` to pin a stream;
    the default derives one from the engine rng and request id).

    bucket_lens=True rounds each admission's prefill to the next power
    of two, so a handful of XLA compiles (one per length bucket x
    power-of-two admission-group size) cover all traffic
    (`min_prefill_bucket` is the smallest bucket: a chunk of a few tokens
    costs a read of the weights whatever its width, so a floor of 32
    saves five programs a group size and no time a chunk); adaptive
    stepping rounds the horizon down to a power of two, so the fused
    decode program compiles at most log2(decode_horizon)+1 variants.

    Scheduling / admission control (models/scheduler.py):
      scheduler="fifo"|"priority"|SchedulerPolicy — which queued
        request takes the next freed slot (`submit(..., priority=)`
        orders the priority policy; lower admits first);
      max_queue + on_full ("reject"|"block") — bounded queue
        backpressure: reject raises EngineOverloaded, block drives
        step() until a queue slot frees;
      max_prefills_per_step — how many rows may prefill in a step, so
        that a burst of long prompts cannot starve in-flight decode
        rows. A row mid-prompt advances a chunk every step and counts:
        the gate admits only while fewer rows are mid-prompt, so a
        step's prefill is never a larger group than this (with prompts
        many chunks long the admissions alone would let it grow to
        every slot, 24 rows x 512 tokens in one program, and decode
        wait behind it);

    Tensor parallelism: ``tp=n`` (or a prebuilt ``mesh=`` with a "tp"
    axis) shards the model weights, the KV block pool and the fused
    programs' carried state across n chips via the
    model's logical axis rules — attention heads, MLP width and the
    vocab dimension split over ICI; KV heads split when ``n_kv_heads``
    divides tp and replicate otherwise (prune_rules_for_mesh). The
    host never notices: scheduling, chunked prefill, the async
    pipeline and the single [H, B] device->host block (kept fully
    replicated) are identical at every tp degree, and so is every
    emitted token (greedy and sampled) — gated by
    tests/test_engine_sharded.py.

    Telemetry: `self.metrics` (EngineMetrics) records queue-wait /
    TTFT / TPOT / occupancy through the util.metrics Prometheus plane;
    `stats()` returns the flat snapshot. enable_metrics=False swaps in
    a no-op recorder for benchmark inner loops.
    """

    def __init__(self, params: Params, cfg: LlamaConfig, *,
                 batch_slots: int = 8, max_len: Optional[int] = None,
                 greedy: bool = True, temperature: float = 1.0,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 bucket_lens: bool = True,
                 min_prefill_bucket: int = 1,
                 rng: Optional[jax.Array] = None,
                 scheduler: Union[str, SchedulerPolicy] = "fifo",
                 max_queue: Optional[int] = None,
                 on_full: str = "reject",
                 block_timeout_s: Optional[float] = None,
                 max_prefills_per_step: Optional[int] = None,
                 decode_horizon: int = 8,
                 pipeline_depth: int = 2,
                 prefix_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 paged: bool = True,
                 kv_block_tokens: int = 32,
                 kv_pool_bytes: Optional[int] = None,
                 kv_quant: Optional[str] = None,
                 preempt: str = "swap",
                 draft_params: Optional[Params] = None,
                 draft_cfg: Optional[LlamaConfig] = None,
                 spec_window: int = 4,
                 lora: Optional["LoraConfig"] = None,
                 max_live_adapters: int = 4,
                 mesh: Optional[Mesh] = None,
                 tp: Optional[int] = None,
                 sharding_rules=None,
                 engine_id: Optional[str] = None,
                 enable_metrics: bool = True,
                 trace=None,
                 sanitize=None,
                 clock: Callable[[], float] = time.monotonic):
        _check_sampling_knobs(greedy, top_k, top_p)
        if on_full not in ("reject", "block"):
            raise ValueError(f"on_full must be 'reject' or 'block', "
                             f"got {on_full!r}")
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if block_timeout_s is not None and block_timeout_s <= 0:
            raise ValueError("block_timeout_s must be > 0")
        if max_prefills_per_step is not None and max_prefills_per_step < 1:
            raise ValueError("max_prefills_per_step must be >= 1")
        if decode_horizon < 1:
            raise ValueError("decode_horizon must be >= 1")
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        if paged is not True:
            # The benchmark's configuration files still pass
            # `"paged": true`; the keyword goes when they drop it
            # (ROADMAP D16).
            raise ValueError(
                "paged=False: the dense KV path was removed in PR 29 — "
                "the block pool is the engine's one KV path; drop the "
                "keyword")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if min_prefill_bucket < 1 or \
                min_prefill_bucket & (min_prefill_bucket - 1):
            raise ValueError("min_prefill_bucket must be a power of two")
        if preempt not in ("swap", "recompute"):
            raise ValueError(f"preempt must be 'swap' or 'recompute', "
                             f"got {preempt!r}")
        if kv_block_tokens < 1:
            raise ValueError("kv_block_tokens must be >= 1")
        self.kv_quant_spec = resolve_kv_quant(kv_quant)
        self.kv_quant = kv_quant if self.kv_quant_spec is not None \
            else None
        if draft_params is not None:
            if draft_cfg is None:
                raise ValueError("draft_params needs draft_cfg")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target "
                    f"vocab {cfg.vocab_size}: speculative decoding "
                    "needs a shared tokenizer")
            if spec_window < 1:
                raise ValueError("spec_window must be >= 1")
        if lora is not None:
            if draft_params is not None:
                raise ValueError(
                    "lora= and draft_params= are mutually exclusive: "
                    "the speculative draft/verify programs do not "
                    "thread per-row adapter deltas (multi-LoRA "
                    "speculative decoding is follow-up work)")
            if max_live_adapters < 1:
                raise ValueError("max_live_adapters must be >= 1")
        # What a family cannot be served with is its config's answer
        # (`refusals`), raised in the order the family names it.
        asked = {"prefix_cache": prefix_cache,
                 "preempt_swap": preempt == "swap",
                 "draft": draft_params is not None or draft_cfg is not None,
                 "kv_quant": kv_quant is not None,
                 "lora": lora is not None,
                 "tp": tp is not None or mesh is not None}
        for option, why in cfg.refusals().items():
            if asked.get(option):
                raise ValueError(why)
        # A sparse model's two refusals that read an option's VALUE: LoRA
        # targets of the DENSE feed-forward, a draft of the other family.
        sparse = isinstance(cfg, MoeConfig)
        if sparse and lora is not None:
            ffn = sorted(set(lora.targets) & {"w_gate", "w_up", "w_down"})
            if ffn:
                raise ValueError(
                    f"lora= targets {ffn} name the dense feed-forward, "
                    "which an MoeConfig does not have (attention "
                    "targets wq/wk/wv/wo are served)")
        if draft_cfg is not None and \
                isinstance(draft_cfg, MoeConfig) != sparse:
            raise ValueError(
                "draft_cfg= is of another family than the target (one "
                "is an MoeConfig, one dense): speculative decoding "
                "serves a draft of the target's own family")
        self.params = params
        self.cfg = cfg
        self.B = batch_slots
        self.max_len = max_len or cfg.max_seq_len
        if self.max_len > cfg.max_seq_len:
            raise ValueError(f"max_len {self.max_len} exceeds "
                             f"max_seq_len {cfg.max_seq_len}")
        self.greedy = greedy
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_id = eos_id
        self.bucket_lens = bucket_lens
        self.min_prefill_bucket = min_prefill_bucket
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)

        self.scheduler = make_policy(scheduler)
        self.max_queue = max_queue
        self.on_full = on_full
        self.block_timeout_s = block_timeout_s
        self.max_prefills_per_step = max_prefills_per_step
        self.decode_horizon = decode_horizon
        self.pipeline_depth = pipeline_depth
        # One clock for telemetry AND deadline shedding — injectable so
        # hysteresis/expiry tests advance time without sleeping.
        self._clock = clock
        self.metrics = (EngineMetrics(engine_id=engine_id,
                                      batch_slots=self.B, clock=clock)
                        if enable_metrics else NullEngineMetrics())
        # Request-lifecycle tracer (engine_trace.py): `trace=` takes an
        # EngineTracer, True (build one), False (force off), or None —
        # defer to the RAY_TPU_TRACE env gate, else the no-op tracer.
        # Every hot-path call site guards on `self.trace.enabled`, so
        # the default costs one attribute read per seam.
        self.engine_id = engine_id or (self.metrics.engine_id
                                       if enable_metrics else "engine")
        self.trace = resolve_tracer(trace, engine_id=self.engine_id,
                                    clock=clock)
        # The PROCESS's compile ledger (util/compile_cache.py), installed
        # here at the latest so that every program this engine builds is
        # counted; `stats()` carries its three totals.
        self._compiles = _compile_ledger()
        # Runtime sanitizer (_private/sanitize.py): `sanitize=` takes a
        # Sanitizer, True (build a strict one), False (force off), or
        # None — defer to the RAY_TPU_SANITIZE env gate. When present it
        # auto-arms after RAY_TPU_SANITIZE_WARMUP steps (compiles are
        # expected during warmup); `arm_sanitizer()` arms it on demand.
        # The off path costs one module-global read in `_device_get`.
        self.sanitizer = _sanitize.resolve(sanitize)
        self._san_steps = 0
        self._san_warmup = _sanitize.warmup_steps()

        # Tensor parallelism (class docstring): `tp=n` builds a
        # {"tp": n} mesh over the first n visible devices; `mesh=`
        # hands over a prebuilt mesh carrying a "tp" axis.
        if tp is not None:
            if mesh is not None:
                raise ValueError("pass mesh= or tp=, not both")
            if tp < 1:
                raise ValueError("tp must be >= 1")
            devs = jax.devices()
            if tp > len(devs):
                raise ValueError(
                    f"tp={tp} exceeds the {len(devs)} visible "
                    "device(s); on CPU force a virtual world with "
                    "XLA_FLAGS=--xla_force_host_platform_device_count")
            mesh = create_mesh({"tp": tp}, devs[:tp])
        self.mesh = mesh
        if mesh is not None:
            if "tp" not in mesh.axis_names:
                raise ValueError(
                    "serving mesh needs a 'tp' axis, got axes "
                    f"{mesh.axis_names}")
            self.tp_degree = int(dict(mesh.shape)["tp"])
            dims = {"heads": cfg.n_heads, "qkv": cfg.n_heads,
                    "kv": cfg.n_kv_heads, "mlp": cfg.ffn_dim,
                    "vocab": cfg.vocab_size, "embed": cfg.dim,
                    "batch": self.B}
            base = dict(DEFAULT_RULES)
            base["kv"] = "tp"   # serving shards the KV-head axis; the
            #                     training table replicates it
            rules = (sharding_rules if sharding_rules is not None
                     else prune_rules_for_mesh(base, mesh, dims))
            self._rules = rules
            self.params = shard_pytree(
                params, llama_param_specs(cfg, rules), mesh)
            d_pool_sh = d_scale_sh = None
            self._d_shardings = None
            # the pool merges (kv, head_dim) into one head-major lane
            # axis
            pool_axes = ("layers", None, None, "kv")
            if draft_params is not None:
                # The draft shards over the SAME mesh, but its rules
                # prune against its OWN dims — a nano draft whose kv
                # heads don't divide tp replicates that axis while the
                # target still splits its.
                d_dims = {"heads": draft_cfg.n_heads,
                          "qkv": draft_cfg.n_heads,
                          "kv": draft_cfg.n_kv_heads,
                          "mlp": draft_cfg.ffn_dim,
                          "vocab": draft_cfg.vocab_size,
                          "embed": draft_cfg.dim, "batch": self.B}
                d_rules = prune_rules_for_mesh(dict(base), mesh, d_dims)
                draft_params = shard_pytree(
                    draft_params, llama_param_specs(draft_cfg, d_rules),
                    mesh)
                d_pool_sh = named_sharding(mesh, *pool_axes,
                                           rules=d_rules)
                if self.kv_quant_spec is not None:
                    d_scale_sh = named_sharding(
                        mesh, "layers", None, "kv", rules=d_rules)
                # A second shardings view with the DRAFT plane in the
                # primary slots, so `_prefill_rows_paged` runs
                # unchanged when seeding the draft pool.
                self._d_shardings = _EngineShardings(
                    logits=named_sharding(mesh, "batch", "vocab",
                                          rules=d_rules),
                    pool=d_pool_sh,
                    scale=d_scale_sh)
            scale_sh = None
            if self.kv_quant_spec is not None:
                # scale slab [L, NB, KV]: same pruned KV rules as the
                # pool it dequantizes, so gather stays chip-local
                scale_sh = named_sharding(mesh, "layers", None, "kv",
                                          rules=rules)
            self._shardings = _EngineShardings(
                logits=named_sharding(mesh, "batch", "vocab",
                                      rules=rules),
                pool=named_sharding(mesh, *pool_axes, rules=rules),
                d_pool=d_pool_sh, scale=scale_sh, d_scale=d_scale_sh)
        else:
            self.tp_degree = 1
            self._rules = None
            self._shardings = None
            self._d_shardings = None
        self.metrics.on_tp_degree(self.tp_degree)

        # Multi-LoRA serving plane (models/adapter_pool.py): device
        # stacks of up to max_live_adapters LoRA weight sets, one slot
        # lane mapping each batch row to its adapter (0 = base-only),
        # and a pending map carrying the slot reference taken at the
        # ADMISSION GATE to the row bind — the incref happens at the
        # gate, not at bind, so a later candidate's prefetch-commit in
        # the same admission round can never evict an adapter a
        # decision was already made against. lora=None engines carry
        # adapter_pool=None and every dispatch passes adapters=None
        # (zero extra pytree leaves -> byte-identical programs).
        self.lora_cfg = lora
        self.adapter_pool = None
        if lora is not None:
            self.adapter_pool = AdapterPool(
                cfg, lora, max_live_adapters=max_live_adapters,
                mesh=self.mesh, rules=self._rules,
                metrics=self.metrics, trace=self.trace)
            self.metrics.on_adapter_slots(max_live_adapters, 0, 0)
        self._row_slot = np.zeros((self.B,), np.int32)
        self._pending_slots: Dict[int, int] = {}
        self.adapter_deferrals = 0     # cold-adapter admission defers
        if self.adapter_pool is not None:
            attach = getattr(self.scheduler, "attach_adapter_probe",
                             None)
            if attach is not None:
                attach(self._adapter_probe)

        # Every row's K/V lives in pool blocks behind its block table
        # (state built below, after the row bookkeeping).
        self.preempt_mode = preempt
        self.kv_block_tokens = kv_block_tokens
        if self.max_len % kv_block_tokens:
            raise ValueError(
                f"max_len ({self.max_len}) must be divisible by "
                f"kv_block_tokens ({kv_block_tokens}): a row's block "
                "view must span exactly the cache solo `generate` "
                "keeps, so prefill on it is bit-identical")
        # Next-token logits per slot, DEVICE-resident: prefill scatters
        # into it, the fused decode samples from and re-carries it —
        # logits never cross the jit boundary to the host.
        self._last_logits = jnp.zeros((self.B, cfg.vocab_size),
                                      jnp.float32)
        if self._shardings is not None:
            self._last_logits = jax.device_put(self._last_logits,
                                               self._shardings.logits)
        # Expert-layer counters (see `_moe_count`): the device's wrapping
        # int32 [4], what the host last saw of them, and the unwrapped
        # totals `stats()` reports. None/zeros for a dense model.
        n_ctr = _moe_ctr_rows(cfg)
        self._moe_ctr = jnp.zeros((n_ctr,), jnp.int32) if n_ctr else None
        self._moe_seen = np.zeros((n_ctr,), np.uint32)
        self._moe_totals = np.zeros((n_ctr,), np.int64)
        self.row_len = np.zeros((self.B,), np.int32)   # written slots
        self.row_req: List[Optional[_Request]] = [None] * self.B
        self.row_budget = np.zeros((self.B,), np.int32)
        self._tok_idx = np.zeros((self.B,), np.int32)  # sampled so far
        self._row_keys = np.zeros((self.B, 2), np.uint32)
        self._base_key = _key_data(self._rng)
        self._next_id = 0
        self.results: Dict[int, _Request] = {}
        self.finished: set = set()      # done but not yet popped
        self.shed_ids: set = set()      # finished as past-deadline sheds
        self.requests_shed = 0          # plain int (enable_metrics=False)
        self.draining = False           # begin_drain(): no new submits
        self.halted = False             # halt(): state discarded (fleet
        #                                 failover abandoned this engine)
        # Dispatch/transfer accounting (plain ints so the benchmark's
        # enable_metrics=False engines still report them):
        self.decode_dispatches = 0     # fused decode program launches
        self.decode_dispatches_chained = 0         # ... made run-ahead
        self.decode_dispatches_chained_queued = 0  # ... a request queued
        self.moe_hit_kernel_decode_dispatches = 0  # fused decode blocks
        #    whose HELD experts went through `ops.hit_experts`'s kernel
        self.state_kernel_decode_dispatches = 0    # ... whose recurrent
        #    state was updated in place by the stack's own kernel
        self.prefill_dispatches = 0    # batched prefill launches
        self.prefill_dispatches_ahead = 0   # ... launched before the
        #                                step's decode block was pulled
        self.moe_grouped_prefill_dispatches = 0   # ... whose held experts
        #                    went through `ops.held_grouped_ffn`'s kernel
        self.host_syncs = 0            # device->host transfers
        self.device_waits = 0          # blocking pulls (`_device_wait`)
        self.device_wait_s = 0.0       # engine-clock seconds inside them
        # Step clocks (same discipline: plain floats on the engine's
        # clock, kept under enable_metrics=False). A step's wall time by
        # seam: each total is the seconds inside the `eng.*` lane of the
        # same name, read at the lane's two ends, so a difference of two
        # `stats()` snapshots lies beside a profiler trace of the same
        # stretch; with `device_wait_s`, and `step_other_s_total` the
        # remainder, they add up to `step_s_total`.
        self.step_s_total = 0.0        # inside `step()`, entry to exit
        self.step_flush_s_total = 0.0  # `pipeline_flush`, less the pulls
        #                                and replays inside it
        self.step_admit_s_total = 0.0  # `admit`: the gate and row binding
        self.step_prefill_dispatch_s_total = 0.0   # `advance_prefills`
        self.step_dispatch_s_total = 0.0   # `dispatch` / `spec_draft`
        self.step_emit_s_total = 0.0   # `emit`: the replay of a block
        # A step of STALL_STEP_S or more, and where it stood.
        self.steps_stalled_total = 0
        self.step_stalled_s_total = 0.0
        self.step_stalled_device_wait_s_total = 0.0
        # Seconds the device had nothing to run as far as the host knows
        # (`_idle_since`: the clock at which a pull returned with the
        # ring empty and no program dispatched after the pulled block),
        # closed by the next dispatch of any program and put down to ONE
        # cause (`_starved_after`).
        self.device_starved_s_total = 0.0
        self.device_starved_dispatches_total = 0
        self._starved_s = {"retire": 0.0, "admit": 0.0, "chunk": 0.0,
                           "other": 0.0}
        self._idle_since: Optional[float] = None
        # an open seam's first clock reading, and `device_wait_s` at the
        # step's entry: attributes, not locals (`_step_done` says why)
        self._t_step = self._t_admit = self._t_prefill = 0.0
        self._t_dispatch = 0.0
        self._waited_in = 0.0
        self._dispatch_seq = 0         # programs dispatched, all kinds
        self._retired_since_decode = False   # a row ended in a block
        #                    drained since the last decode dispatch
        self._admitted_since_dispatch = False    # the gate admitted
        #                    since the last dispatch of any program
        self.host_transfer_bytes = 0   # bytes those transfers moved
        self.tokens_out = 0            # tokens emitted, all requests
        # Prefill/prefix-reuse accounting (same plain-int discipline):
        self.prefill_real_tokens = 0   # true chunk tokens prefilled
        self.prefill_padded_tokens = 0  # bucket + pow2-group filler
        self.prefix_lookups = 0        # admissions probed in the trie
        self.prefix_hits = 0           # ... that matched >= 1 block
        self.prefix_reused_tokens = 0  # prompt tokens copied, not run
        self.prefix_evictions = 0      # LRU blocks recycled
        self.chunked_prefill_stalls = 0  # steps with a row mid-prefill
        # Block-pool plane (plain ints):
        self.kv_blocks_shared = 0      # warm-admission zero-copy shares
        self.kv_block_cows = 0         # tail blocks duplicated on write
        self.preemptions = 0           # rows evicted mid-decode
        self.swap_ins = 0              # preempted rows re-admitted
        self.swap_outs = 0             # swap-mode spills to host
        self.swap_in_bytes = 0         # host->device swap traffic
        self.swap_out_bytes = 0        # device->host swap traffic
        # What the paged kernel has to walk against what its table
        # holds, per fused decode dispatch (host estimate, see
        # `_count_paged_walk`).
        self.paged_walk_pages_total = 0    # live pages, active rows
        self.paged_walk_entries_total = 0  # B * MB per decode token
        # Rows (decode) or query tiles (prefill) the kernel walks, and
        # those whose first pages the row before them in the call
        # fetched: the kernel's rule, a row that walks hands on.
        self.paged_walk_rows_total = 0
        self.paged_walk_rows_chained_total = 0
        # Those whose call took the row's KV heads as one operand of a
        # step (`walk_shape`'s third word: every decode row of the served
        # families, no 128-row prefill tile); the rest loop over heads.
        self.paged_walk_rows_stacked_total = 0
        # The same for prefill dispatches (`_count_prefill_walk`).
        self.prefill_walk_pages_total = 0  # pages the chunks' tiles walk
        self.prefill_table_entries_total = 0   # n_pad * MB per dispatch
        # Disaggregated prefill/decode plane (plain ints; identically
        # zero on a colocated engine so fleet rollups sum blindly).
        # `prefill_only` is set by the fleet on prefill-class replicas:
        # step() then parks rows whose prefill frontier completed in
        # `_handoff_ready` instead of decoding them, and the fleet
        # export_request()s each one to a decode-class replica.
        self.prefill_only = False      # fleet-set replica-class switch
        self.replica_class = None      # "prefill" / "decode" / None
        self._handoff_ready: List[int] = []   # req_ids parked post-prefill
        self._handoff_ready_set: set = set()
        self.handoffs_out = 0          # requests exported post-prefill
        self.handoffs_in = 0           # requests imported for decode
        self.handoff_out_bytes = 0     # KV+logits bytes staged to host
        self.handoff_in_bytes = 0      # KV+logits bytes accepted
        # Async pipeline: dispatched-but-undrained fused steps, oldest
        # first. Same plain-int discipline for the counters so
        # enable_metrics=False benches still report the pipeline plane.
        self._ring: collections.deque = collections.deque()
        self.pipeline_flushes = 0      # forced full drains of the ring
        self.pipeline_overrun_tokens = 0  # masked run-ahead iterations
        self._pl_depth_sum = 0         # ring depth sampled at each drain
        self._pl_depth_n = 0

        # Chunked prefill: rows whose suffix is still being written,
        # row -> _PrefillState. A row in here is EXCLUDED from decode
        # (its last_logits are not final) and advances one chunk per
        # step via _advance_prefills().
        self.prefill_chunk = prefill_chunk
        self._row_prefill: Dict[int, _PrefillState] = {}
        # rows whose NEXT step's chunk is already dispatched (behind the
        # decode block the host was about to wait for): row -> its state
        self._chunk_ahead: Dict[int, _PrefillState] = {}

        # ONE refcounted block pool holds everything: live rows' K/V
        # behind their block tables, and — with `prefix_cache` — the
        # shared-prefix cache, a host-side radix index over committed
        # prompt blocks of the same pool, so a warm admission SHARES
        # blocks instead of copying them. `kv_pool_bytes` sizes it
        # (default: room for two full batches of max_len tokens) plus
        # the reserved null block 0.
        # What a token stores is the config's answer (`cache_planes`):
        # the two planes of table "full" are this pool's two arrays (K
        # and V; an `MlaConfig`'s latent and index planes, of unlike
        # widths and with no V), and a block's bytes are theirs. Planes
        # of table "window" get a pool of their own below.
        self._planes = tuple(pl for pl in cfg.cache_planes()
                             if pl.table == "full")
        planes_w = tuple(pl for pl in cfg.cache_planes()
                         if pl.table == "window")
        T = kv_block_tokens
        if self.kv_quant_spec is not None:
            # Quantized pool: 1-byte values + the per-block scale
            # slab's footprint (2 slabs x L x KV f32 scales per
            # block) — the ~2x concurrency-per-HBM-byte lever.
            pool_dtype = self.kv_quant_spec.dtype
            bb = sum(pl.block_bytes(T, self.kv_quant_spec.itemsize)
                     + pl.layers * pl.heads * 4
                     for pl in self._planes)
        else:
            pool_dtype = jnp.dtype(cfg.dtype)
            bb = sum(pl.block_bytes(T) for pl in self._planes)
        self.kv_bytes_per_block = float(bb)
        self.kv_bytes_per_token = bb / T
        if kv_pool_bytes is None:
            n_blocks = 1 + (2 * self.B * self.max_len) // T
        else:
            n_blocks = 1 + kv_pool_bytes // bb
        self._mb = self.max_len // T   # block-table width
        self.kv_pool = BlockPool(n_blocks)
        self._bt = np.zeros((self.B, self._mb), np.int32)
        self._row_blocks: List[List[int]] = [[] for _ in range(self.B)]
        self._swapped: Dict[int, _SwapState] = {}
        self._admit_seq = 0            # preemption recency order
        self._row_admit_seq = np.zeros((self.B,), np.int64)
        (self._pool_k, self._pool_v, self._scale_k,
         self._scale_v) = _zero_pools(
            self._planes, n_blocks, T, pool_dtype,
            self.kv_quant_spec is not None, shardings=self._shardings)
        # A family's other device state ``_hyb``, donated through every
        # program beside the pool: the RECURRENT state of every slot,
        # which is the config's answer as the pools are (`state_planes`:
        # a `HybridConfig`'s scan and conv state, a `GdnConfig`'s matrix
        # and conv state), zeroed for a row by its first chunk; and the
        # WINDOW planes where the config names any (a second `BlockPool`
        # and table over pools of their own geometry, under the planes'
        # names; a row holds only the blocks that intersect its last
        # `sliding_window` slots plus what it is about to write,
        # `_window_release`). None for a family with neither, which
        # passes no leaf of it to any program.
        self._state_planes = cfg.state_planes()
        # a chunk that is not a prompt's last stops after these layers
        self._prefill_stops_early = cfg.prefill_layers() < cfg.n_layers
        # an "index" plane: attention reads `index_topk` selected tokens
        self._selects = any(pl.name == "index" for pl in self._planes)
        hyb = zero_state_planes(self._state_planes, self.B)
        self.kv_pool_w: Optional[BlockPool] = None
        self.indexer_tokens_scored_total = 0   # token-layers, decode and
        self.indexer_tokens_selected_total = 0  # prefill (an MlaConfig)
        self.indexer_decode_tokens_scored_total = 0    # ... decode alone
        self.indexer_decode_tokens_selected_total = 0
        self.indexer_pages_walked_total = 0    # page-layers the selection
        self.indexer_pages_table_total = 0     # walks, of its tables'
        self.indexer_queries_total = 0         # query-layers, and those
        self.indexer_queries_unselected_total = 0  # with <= index_topk
        #                                            slots to see
        self.sparse_decode_pages_walked_total = 0  # page-layers a decode
        self.sparse_decode_pages_table_total = 0   # token's attention
        #                               walks in its full layers, of the
        #                               table entries of the rows that ask
        self.kv_walk_tokens_window_total = 0   # token-layers decode asks
        self.kv_walk_tokens_full_total = 0     # ... per READER of the pool
        self.window_blocks_freed_total = 0     # released behind the window
        self.window_pool_peak_blocks = 0
        # a selecting family's window layers (an `MlaConfig` with
        # `layer_types`), a decode dispatch: the rows that asked and the
        # slots one window layer reads for them, min(len, window) each
        self.swa_window_rows_total = 0
        self.swa_window_slots_total = 0
        # (recurrent state of any kind: `engine_metrics` says which)
        self.ssm_state_resets_total = 0        # admissions from zero state
        self.ssm_row_steps_total = 0           # live rows x decode tokens
        self.prefill_layer_tokens_total = 0    # token-layers of a dense stack
        self.prefill_layer_tokens_skipped_total = 0   # ... not run (YOCO)
        if planes_w:
            W = cfg.sliding_window
            chunk = min(prefill_chunk or self.max_len, self.max_len)
            mid = min(self.B, 2 * (max_prefills_per_step or self.B))
            # a decoding row: the window, misaligned, and the horizons in
            # flight; a row mid-prefill its chunk more
            n_blocks_w = 1 + self.B * (-(-W // T) + 3) \
                + mid * (-(-chunk // T) + 1)
            self.kv_pool_w = BlockPool(n_blocks_w, label="window_kv")
            self._bt_w = np.zeros((self.B, self._mb), np.int32)
            self._row_blocks_w: List[List[int]] = [
                [] for _ in range(self.B)]
            self._w_lo = np.zeros((self.B,), np.int64)  # first held block
            # a pool a plane, each of its own geometry: a K and a V plane
            # (a `HybridConfig`), one latent plane (an `MlaConfig`)
            hyb.update((pl.name, jnp.zeros(
                (pl.layers, n_blocks_w, T, pl.lanes), jnp.dtype(cfg.dtype)))
                for pl in planes_w)
        self._hyb: Optional[Params] = hyb or None
        self._prefix: Optional[PrefixCacheIndex] = None
        if prefix_cache:
            self._prefix = PrefixCacheIndex(
                block_tokens=T, on_evict=self._on_prefix_evict,
                pool=self.kv_pool)
            attach = getattr(self.scheduler, "attach_prefix_probe", None)
            if attach is not None:
                attach(self._prefix_probe)

        # Speculative plane: the DRAFT model's KV lives in a second
        # per-slot plane — its own private block pool + table (draft
        # blocks are never shared or tried; sized so every slot can
        # hold a full row, the draft allocator can never run dry).
        # Host lanes mirror the device's draft-lag trick and feed the
        # adaptive per-row window from a sliding acceptance history.
        self.spec_enabled = draft_params is not None
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        self.spec_window = spec_window
        self.spec_dispatches = 0       # speculative program launches
        self.spec_rounds = 0           # per-row rounds replayed
        self.spec_proposed = 0         # draft tokens proposed (w_row)
        self.spec_accepted = 0         # draft tokens emitted
        self.spec_wasted = 0           # dispatch-width slots rejected
        self.spec_prefill_dispatches = 0   # draft-plane seeding programs
        self.spec_metrics = None
        if self.spec_enabled:
            if draft_cfg.max_seq_len < self.max_len:
                raise ValueError(
                    f"draft max_seq_len {draft_cfg.max_seq_len} < "
                    f"engine max_len {self.max_len}")
            self._d_lag = np.zeros((self.B,), np.int32)
            self._d_tok = np.zeros((self.B,), np.int32)
            self._spec_hist: List[collections.deque] = [
                collections.deque(maxlen=16) for _ in range(self.B)]
            self._d_last_logits = jnp.zeros(
                (self.B, draft_cfg.vocab_size), jnp.float32)
            if self._d_shardings is not None:
                self._d_last_logits = jax.device_put(
                    self._d_last_logits, self._d_shardings.logits)
            n_blocks_d = 1 + self.B * self._mb
            self.kv_pool_d = BlockPool(n_blocks_d, label="draft_kv")
            self._bt_d = np.zeros((self.B, self._mb), np.int32)
            self._row_blocks_d: List[List[int]] = [
                [] for _ in range(self.B)]
            (self._pool_dk, self._pool_dv, self._scale_dk,
             self._scale_dv) = _zero_pools(
                draft_cfg.cache_planes(), n_blocks_d, T,
                self.kv_quant_spec.dtype if self.kv_quant_spec is not None
                else jnp.dtype(draft_cfg.dtype),
                self.kv_quant_spec is not None,
                shardings=self._d_shardings)
            if enable_metrics:
                # llm_spec_* Prometheus counters share the engine's
                # tag, so fleet dashboards can join the spec plane onto
                # the engine's other series (satellite: telemetry
                # routed through the engine identity).
                from ray_tpu.models.speculative import SpecMetrics
                self.spec_metrics = SpecMetrics(spec_id=self.engine_id)
        # Per-row decode-mode lane: True = argmax, False = sampled.
        # Defaults to the engine-wide mode; submit(greedy=...) overrides
        # per request at bind time. Retirement resets to the default so
        # the all-greedy fast path recompiles nothing.
        self._row_greedy = np.full((self.B,), bool(greedy), bool)

        # Serving-state plane: wall-clock birth + a step counter that
        # survives enable_metrics=False (the metrics `steps` field
        # vanishes with NullEngineMetrics), then a WEAK registration in
        # the process-local state API so `ray_tpu.util.state`
        # list_engines()/list_requests() can find this engine without
        # holding it alive.
        self._start_t = clock()
        self.steps_total = 0
        from ray_tpu.util.state.serving import register_engine
        register_engine(self)
        if self.preempt_mode == "swap":
            self._warm_swap()

    def _warm_swap(self) -> None:
        """Run preempt-and-swap's device programs once for every chain
        length (a power of two of blocks; here all the null block, whose
        contents nothing reads), so that the FIRST preemption compiles
        nothing. It comes when the pool has just run dry under a burst,
        the worst moment to hold `step()` for a compile, and a shape the
        benchmark's warm-up never reaches (PERF.md PR 34: a 15 s stall in
        front of an open loop). The gather, the scatter, and the two small
        programs that move a row's logits out and back."""
        kw = dict(shardings=self._shardings, scale_k=self._scale_k,
                  scale_v=self._scale_v)
        n = 1
        while n <= _pow2(self._mb):
            bids = jnp.asarray(np.zeros((n,), np.int32))
            host = [None if x is None else jnp.asarray(
                np.zeros(x.shape, x.dtype)) for x in _swap_out_gather(
                    self._pool_k, self._pool_v, bids, **kw)]
            (self._pool_k, self._pool_v, self._scale_k,
             self._scale_v) = _swap_in_scatter(
                self._pool_k, self._pool_v, host[0], host[1], bids,
                host_sk=host[2], host_sv=host[3], **kw)
            kw.update(scale_k=self._scale_k, scale_v=self._scale_v)
            n *= 2
        self._set_row_logits(0, np.asarray(self._last_logits[0]))

    def _set_row_logits(self, row: int, logits: np.ndarray) -> None:
        self._last_logits = self._last_logits.at[row].set(
            jnp.asarray(logits))
        if self._shardings is not None:
            self._last_logits = jax.device_put(self._last_logits,
                                               self._shardings.logits)

    # -- public API --------------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int = 32,
               priority: int = 0,
               rng: Optional[jax.Array] = None,
               deadline_s: Optional[float] = None,
               greedy: Optional[bool] = None,
               resume_tokens: Optional[List[int]] = None,
               adapter_id: Optional[str] = None) -> int:
        """Enqueue a request; returns its id (see `results`).

        ``priority`` (lower = sooner) orders admission under the
        priority policy; the FIFO policy ignores it. With a bounded
        queue (max_queue), a full queue either raises EngineOverloaded
        (on_full="reject") or drives the engine until a queue slot
        frees (on_full="block"). ``rng`` pins this request's sampling
        key stream (greedy=False engines): with the same key, the
        request's sampled tokens equal solo
        ``generate(..., rng=rng)``; by default a distinct stream is
        derived from the engine rng and request id.

        ``greedy`` overrides the engine-wide decode mode for THIS
        request (the per-row decode-mode lane): on a speculative
        engine, greedy rows ride the draft/verify fast path while
        sampled rows fall back to one plain sampled token per round —
        their streams are unchanged vs a non-speculative engine
        (rejection sampling for speculative sampled rows is follow-up
        work). ``None`` (default) inherits the engine mode.

        ``deadline_s`` is the request's admission SLO: a latency budget
        (seconds from now, on the engine clock) within which prefill
        must START. A request still queued when its deadline passes is
        SHED — retired with zero tokens, ``shed_ids`` membership, and
        the ``requests_shed`` counter — instead of burning prefill
        compute no caller is waiting for; requests already admitted
        always run to completion (killing mid-decode would waste the
        prefill already paid). ``deadline_s <= 0`` sheds immediately
        (reject-before-prefill). After ``begin_drain()`` submit raises
        EngineDraining — a draining replica finishes what it holds but
        takes nothing new.

        ``resume_tokens`` is the fleet-failover resume path: tokens
        this request ALREADY emitted on a replica that died. Admission
        replays prompt + resume_tokens as the prefill (recompute — the
        same discipline as preempt="recompute"), starts the
        budget and sampling-stream index at len(resume_tokens), and
        the request's final ``tokens`` list is resume_tokens plus
        everything decoded here — bit-identical to a run that never
        failed, because `step_rng_key(rng, i)` depends only on the
        request key and the token index, never on the engine, row, or
        step that samples it. Resumed requests are exempt from
        deadline shedding (they were admitted once already) and their
        replay is NOT registered in the prefix trie (emitted tokens
        are not a shareable prompt). Pass the SAME ``rng`` as the
        original submission — sampled identity is the caller's key
        discipline (the fleet pins one key per request for exactly
        this reason).

        ``adapter_id`` routes this request through a registered LoRA
        adapter (see `register_adapter`): its rows decode with that
        adapter's low-rank delta fused into the SAME batched program
        as every other row — heterogeneous-adapter batches are the
        point. A cold adapter defers the request at the admission gate
        while its weights prefetch host->device; None (default) is the
        base model, bit-identical to an engine without lora=."""
        if adapter_id is not None:
            if self.adapter_pool is None:
                raise ValueError(
                    "adapter_id= needs an engine built with lora= "
                    "(a LoraConfig enabling the multi-LoRA plane)")
            if not self.adapter_pool.registered(adapter_id):
                raise KeyError(
                    f"unknown adapter_id {adapter_id!r}: call "
                    "register_adapter first")
        if self.draining:
            raise EngineDraining(
                "engine is draining (begin_drain was called): it will "
                "finish in-flight work but accepts no new requests")
        # Normalise to plain ints: device arrays make unusable
        # prefix-trie keys (unhashable) and unreliable equality checks.
        prompt = [int(t) for t in prompt]
        if not len(prompt):
            raise ValueError("empty prompt: need at least one token "
                             "(prepend a BOS token)")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds engine max_len "
                f"{self.max_len}")
        if (self.spec_enabled and len(prompt) + max_new_tokens
                + self.spec_window > self.max_len):
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) + spec_window "
                f"({self.spec_window}) exceeds engine max_len "
                f"{self.max_len}: the verify chunk writes up to "
                "spec_window slots past the last emitted token, so "
                "speculative engines need that margin")
        resume = None
        if resume_tokens:
            resume = [int(t) for t in resume_tokens]
            if len(resume) >= max_new_tokens:
                raise ValueError(
                    f"resume_tokens ({len(resume)}) must be shorter "
                    f"than max_new_tokens ({max_new_tokens}): a "
                    "completed request has nothing to resume")
            if deadline_s is not None:
                raise ValueError(
                    "resume_tokens and deadline_s are mutually "
                    "exclusive: a resumed request was admitted once "
                    "and is exempt from deadline shedding")
        # A request must fit the pool ALONE in the worst case (every
        # other row preempted, every cold prefix block evicted) or it
        # could never complete.
        T = self.kv_block_tokens
        need = -(-(len(prompt) + max_new_tokens) // T)
        if need > self.kv_pool.blocks_total:
            raise ValueError(
                f"request needs {need} KV blocks ({len(prompt)} "
                f"prompt + {max_new_tokens} new tokens at "
                f"{T} tokens/block) but the pool holds only "
                f"{self.kv_pool.blocks_total}; raise "
                "kv_pool_bytes or shrink the request")
        deadline = (None if deadline_s is None
                    else self._clock() + deadline_s)
        if deadline is not None and self._clock() >= deadline:
            # Dead on arrival: shed before the bounded-queue check —
            # it will never occupy a queue slot, let alone a prefill.
            req = _Request(self._next_id, prompt, max_new_tokens,
                           priority=priority, seq=self._next_id,
                           rng=None if rng is None else _key_data(rng),
                           deadline=deadline)
            req.greedy = greedy
            req.adapter_id = adapter_id
            self._next_id += 1
            self.results[req.req_id] = req
            self.metrics.on_submit(req.req_id)
            if self.trace.enabled:
                self.trace.instant(
                    "submit", req.req_id,
                    {"prompt_tokens": len(prompt),
                     "max_new_tokens": max_new_tokens,
                     "priority": priority})
                self.trace.open("queue_wait", req.req_id)
            self._shed(req)
            return req.req_id
        if self.max_queue is not None and \
                len(self.scheduler) >= self.max_queue:
            if self.on_full == "reject":
                self.metrics.on_reject()
                raise EngineOverloaded(
                    f"queue full ({self.max_queue} queued requests); "
                    f"shed load or use on_full='block'")
            t_block = self._clock()
            while len(self.scheduler) >= self.max_queue:
                if self.block_timeout_s is not None and \
                        self._clock() - t_block >= self.block_timeout_s:
                    self.metrics.on_reject()
                    raise SubmitTimeout(
                        f"queue still full ({self.max_queue} queued "
                        f"requests) after blocking "
                        f"{self.block_timeout_s}s: the engine made no "
                        "room — wedged, or hopelessly oversubscribed")
                self.step()   # admissions + finishes drain the queue
        req = _Request(self._next_id, prompt, max_new_tokens,
                       priority=priority, seq=self._next_id,
                       rng=None if rng is None else _key_data(rng),
                       deadline=deadline)
        req.greedy = greedy
        req.adapter_id = adapter_id
        if resume is not None:
            # Fleet failover resume: the request continues, not
            # restarts — admission replays prompt + these tokens and
            # the sampling stream picks up at token len(resume).
            req.tokens = resume
            req.resume = True
            # Ride the recompute swap-in path: a k=None ledger entry
            # makes `_admit_rows_paged` replay prompt + tokens exactly
            # like a preempted row.
            self._swapped[req.req_id] = _SwapState(
                None, None, 0, 0, len(resume),
                max_new_tokens - len(resume), None)
        self._next_id += 1
        self.scheduler.push(req)
        self.results[req.req_id] = req
        self.metrics.on_submit(req.req_id)
        self.metrics.observe_queue_depth(len(self.scheduler))
        if self.trace.enabled:
            self.trace.instant(
                "submit", req.req_id,
                {"prompt_tokens": len(prompt),
                 "max_new_tokens": max_new_tokens,
                 "priority": priority})
            self.trace.open("queue_wait", req.req_id)
        return req.req_id

    def pending(self) -> bool:
        return bool(len(self.scheduler)) or any(
            r is not None for r in self.row_req)

    # -- multi-LoRA adapter table ------------------------------------------

    def register_adapter(self, adapter_id: str, lora_params: Params
                         ) -> None:
        """Admit a LoRA adapter's weights (a `lora_init`-shaped tree)
        to the engine's host-side adapter table. HBM is untouched
        until traffic warms the adapter through the prefetch path."""
        if self.adapter_pool is None:
            raise ValueError(
                "register_adapter needs an engine built with lora=")
        self.adapter_pool.register(adapter_id, lora_params)

    def unregister_adapter(self, adapter_id: str) -> bool:
        """Drop an adapter (deferred until its last live row retires
        if currently pinned; returns False then, True when immediate).
        Requests still QUEUED for it must not outlive the
        registration — the admission gate raises on unknown ids."""
        if self.adapter_pool is None:
            return True
        return self.adapter_pool.unregister(adapter_id)

    def adapter_resident(self, adapter_id: str) -> bool:
        """True when the adapter currently occupies an HBM slot — the
        fleet router's residency-affinity probe."""
        return (self.adapter_pool is not None
                and self.adapter_pool.resident(adapter_id))

    def _adapter_probe(self, adapter_id: Optional[str]
                       ) -> Tuple[bool, bool]:
        """(resident, fetching) for the adapter-affinity scheduler."""
        if adapter_id is None or self.adapter_pool is None:
            return True, False
        return (self.adapter_pool.resident(adapter_id),
                self.adapter_pool.fetching(adapter_id))

    # The fused entry points whose compile caches the sanitizer audits:
    # any growth after arm() is a steady-state retrace regression.
    _SANITIZER_JIT_ENTRY_POINTS = (
        "_prefill_rows_paged", "_decode_multi_paged", "_spec_round_paged",
        "_cow_blocks", "_swap_out_gather", "_swap_in_scatter")

    def arm_sanitizer(self):
        """Snapshot the jit caches and arm the runtime sanitizer: from
        this call on, any recompile of a fused entry point or any
        device->host pull outside `_device_get`/`_host_async` is a
        violation (raised in strict mode, tallied otherwise). Builds a
        strict sanitizer on the fly if the engine was constructed
        without one. Perf gates call this after warmup; under
        RAY_TPU_SANITIZE=1 it fires automatically after
        RAY_TPU_SANITIZE_WARMUP (default 8) steps."""
        if self.sanitizer is None:
            self.sanitizer = _sanitize.Sanitizer(label=self.engine_id)
        for name in self._SANITIZER_JIT_ENTRY_POINTS:
            self.sanitizer.watch(name, globals().get(name))
        if self.adapter_pool is not None:
            from ray_tpu.models import adapter_pool as _adapter_pool
            self.sanitizer.watch("_adapter_commit",
                                 _adapter_pool._adapter_commit)
        self.sanitizer.arm()
        return self.sanitizer

    def disarm_sanitizer(self) -> None:
        """Restore the un-sanitized fast path (interposition off)."""
        if self.sanitizer is not None:
            self.sanitizer.disarm()

    def sanitizer_stats(self) -> Dict[str, Any]:
        """Snapshot of the sanitizer plane; {} when sanitizing is off."""
        if self.sanitizer is None:
            return {}
        return self.sanitizer.stats()

    def step(self, horizon: Optional[int] = None) -> Dict[int, List[int]]:
        """Admit queued requests into free slots (at most
        max_prefills_per_step of them less the rows still mid-prompt,
        same-bucket admissions batched into one prefill program each),
        then advance every live slot up
        to `horizon` tokens in ONE fused device program with ONE
        device->host transfer. Returns {req_id: [tokens]} emitted this
        step — up to `horizon` per request; a request that finishes
        mid-horizon (budget/eos/room) is frozen on device and retired
        here, and its slot admits a newcomer next step.

        ``horizon=None`` (the default) adapts: the scheduler's
        `horizon_hint` picks 1 while a queued request could take a free
        slot next step, else `decode_horizon`, capped at the largest
        remaining budget (no trailing iterations run fully frozen) and
        rounded down to a power of two (bounded compile count).

        With `pipeline_depth >= 2` and a pure-decode stretch (nothing
        mid-prefill, and no admission possible before the blocks in
        flight are drained: the queue is empty, or every slot is taken
        and no row's budget ends inside them), the step dispatches
        ahead: it tops the in-flight ring up to `pipeline_depth` fused
        steps (each chained off the previous one's device row state)
        BEFORE pulling the oldest step's token block, so the device
        computes step N+1 while the host replays step N. Per-call emissions are identical
        to the synchronous engine: each call still drains exactly one
        block, whose horizon follows the same budget arithmetic. A call
        that finds an admission while blocks are in flight drains THOSE
        (the flush), dispatches the prefill and the next block, and
        returns: tokens are handed over when their block has run, never
        a prefill and a block later.

        Rows mid-prompt take one chunk a step, and the chunk of the
        NEXT step goes out as soon as this step's decode block is
        dispatched (`_advance_prefills(ahead=True)`): the device runs it
        while the host waits for the block, replays it, gates and
        dispatches, and the order of programs on the device is the
        synchronous engine's.

        A slot freed by BUDGET admits its newcomer in the step the
        synchronous engine would: nothing is dispatched ahead of a
        block in which a row is known to end. A row that ends by EOS
        inside block N while N+1 is already in flight frees its slot
        one block (at most `decode_horizon` tokens) later, queue or no
        queue: the host cannot know an EOS before it has the block."""
        if horizon is not None and horizon < 1:
            raise ValueError("horizon must be >= 1")
        self._t_step = self._clock()
        self._waited_in = self.device_wait_s
        self.steps_total += 1
        if self.sanitizer is not None and not self.sanitizer.armed:
            self._san_steps += 1
            if self._san_steps > self._san_warmup:
                self.arm_sanitizer()
        emitted: Dict[int, List[int]] = {}
        # Flush the pipeline before any admission / prefill: those
        # paths mutate the pool from the host side and
        # read row/slot state, so every in-flight run-ahead block must
        # be replayed first (freed slots, retired requests) for the
        # admission decision to see true state. A queue behind full
        # slots is no admission: the gate below skips every taken row.
        if self._ring and self._batch_may_change():
            self._flush_pipeline(emitted)
        # Tokens a flush drained are in hand NOW: this call dispatches
        # what follows (the admissions' prefill, the next decode block)
        # and returns them, instead of holding them until that block
        # has run too; the next call drains it (and runs ahead of it:
        # a second newcomer then waits for one block, not for two).
        flushed = bool(emitted)
        with self.trace.lane("admit", "admit") as admit:
            self._t_admit = self._clock()
            # rows mid-prompt prefill a chunk this step too, and so did
            # a row that left its prompt in the chunk sent ahead for
            # this step
            budget = (self.max_prefills_per_step or self.B) \
                - len(self._row_prefill) \
                - sum(r not in self._row_prefill
                      for r in self._chunk_ahead)
            admissions: List[Tuple[int, _Request]] = []
            begin = getattr(self.scheduler, "begin_admission_round", None)
            if begin is not None:
                begin()
            # Commit any landed adapter prefetches before gating: the
            # commit donates the stacks, so it must never race an
            # in-flight dispatch — with the ring empty (flushed above
            # whenever a queued request had a free slot) nothing on
            # device still reads the old stack buffers.
            if self.adapter_pool is not None and not self._ring:
                self.adapter_pool.drain_prefetches()
            deferred = False
            for row in range(self.B):
                if budget <= 0 or deferred:
                    break
                if self.row_req[row] is not None:
                    continue
                req = None
                while len(self.scheduler):
                    cand = self.scheduler.pop()
                    if cand is None:
                        deferred = True  # prefix policy deferred the queue
                        break
                    if cand.deadline is not None and \
                            self._clock() >= cand.deadline and \
                            not cand.resume:
                        # Expired mid-queue: shed at the admission gate —
                        # the last moment before prefill compute would be
                        # committed to a request nobody is waiting for.
                        # A PREEMPTED request is exempt: it was already
                        # admitted once, and admitted requests run to
                        # completion.
                        self._shed(cand)
                        continue
                    if not self._fits_now(cand):
                        # No room even counting evictable cold prefix
                        # blocks: capacity, not order, is the constraint —
                        # stop admitting this step and retry when decode
                        # retirements free blocks.
                        self._requeue_front(cand)
                        deferred = True
                        break
                    if cand.adapter_id is not None:
                        # Adapter residency gate: acquire the slot HERE
                        # (refcount taken) so nothing admitted later this
                        # round can evict it; a cold adapter starts its
                        # async prefetch and the request waits at the
                        # queue front instead of stalling the step.
                        slot = self.adapter_pool.alloc(cand.adapter_id)
                        if slot is None:
                            self.adapter_pool.prefetch(cand.adapter_id)
                            self._requeue_front(cand)
                            self.adapter_deferrals += 1
                            self.metrics.on_adapter_defer()
                            deferred = True
                            break
                        self._pending_slots[cand.req_id] = slot
                    req = cand
                    break
                if req is None:
                    continue       # queue drained to empty (or deferred)
                admissions.append((row, req))
                budget -= 1
            if deferred and self.trace.enabled:
                self.trace.instant("admission_defer", lane="events",
                                   args={"queued": len(self.scheduler)})
            admit.note(admitted=len(admissions))
            if admissions:
                self._admitted_since_dispatch = True
                self._admit_rows_paged(admissions)
            self.step_admit_s_total += self._clock() - self._t_admit
        self._advance_prefills()

        live = [b for b in range(self.B) if self.row_req[b] is not None]
        if not live:
            if self._ring:             # defensive: never strand blocks
                self._flush_pipeline(emitted)
            return self._step_done(emitted)
        # Rows mid-chunked-prefill are NOT decodable: their last_logits
        # still hold an intermediate chunk's scatter. They ride along
        # frozen (active=False) and take their next chunk next step.
        decodable = [b for b in live if b not in self._row_prefill]
        if self.prefill_only:
            # Prefill-class replica (disaggregated fleet): a row whose
            # prefill frontier just completed holds final last_logits
            # and tok_idx=0 — exactly a preemption-at-first-token
            # state. Park it for export_request() instead of decoding;
            # the fleet hands it to a decode-class replica. Never
            # dispatch a decode program here, so the ring stays empty
            # and export never races an in-flight block.
            for b in decodable:
                rid = self.row_req[b].req_id
                if rid not in self._handoff_ready_set:
                    self._handoff_ready_set.add(rid)
                    self._handoff_ready.append(rid)
                    if self.trace.enabled:
                        self.trace.instant(
                            "handoff_ready", lane="events",
                            args={"req": rid,
                                  "prompt_tokens": int(self.row_len[b])})
            self.metrics.on_step(len(live), len(self.scheduler), 0)
            return self._step_done(emitted)
        if len(decodable) < len(live):
            self.chunked_prefill_stalls += 1
            self.metrics.on_prefill_stall()
        if not decodable:
            self.metrics.on_step(len(live), len(self.scheduler), 0)
            return self._step_done(emitted)

        if not self._ring:
            decodable = self._dispatch_primary(decodable, live, horizon)
        if not flushed:
            self._top_up_pipeline(decodable, horizon)
        # A block is in flight that this call (below) or the next one
        # (its flush) waits for: the rows still mid-prompt take the
        # NEXT step's chunk now (the prompt is known), so the device
        # has it to run while the host replays the block, gates and
        # dispatches.
        self._advance_prefills(ahead=True)
        if not flushed:
            self._drain_one(emitted)
        # End of stream: every request retired, but run-ahead blocks
        # may remain (all-masked overrun). Drain them now so pending()
        # reads true and the ring never outlives its requests.
        if self._ring and not any(r is not None for r in self.row_req):
            self._flush_pipeline(emitted)
        n_tokens = sum(len(t) for t in emitted.values())
        self.tokens_out += n_tokens
        self.metrics.on_step(
            sum(r is not None for r in self.row_req),
            len(self.scheduler), n_tokens)
        self.metrics.on_kv_pool(self.kv_pool.blocks_total,
                                self.kv_pool.blocks_in_use,
                                self.kv_pool.free_blocks,
                                bytes_per_token=self.kv_bytes_per_token)
        return self._step_done(emitted)

    def _step_done(self, emitted: Dict[int, List[int]]
                   ) -> Dict[int, List[int]]:
        """Every return of `step()`: its wall time, a stall, and an engine
        left empty. Called AT the returns and not around the body, and
        `step()` keeps its stamps on the engine (`_t_step`, `_waited_in`,
        `_t_admit`; `_advance_prefills` its `_t_prefill`, a decode dispatch
        its `_t_dispatch`) and not in
        locals: a frame between `step` and a jitted call, and every local
        added to a frame that stands on the stack at one, lengthened the
        LOWERING of the Mistral prefill programs on the chip (6.7-7.0 s ->
        8.4-8.7 with five locals, 20.9 s of tracing and lowering for 14.3
        with the frame; PERF.md sections 6-7, PR 54)."""
        wall = self._clock() - self._t_step
        self.step_s_total += wall
        if wall >= STALL_STEP_S:
            self.steps_stalled_total += 1
            self.step_stalled_s_total += wall
            self.step_stalled_device_wait_s_total += \
                self.device_wait_s - self._waited_in
        if self._idle_since is not None and not len(self.scheduler) \
                and not any(r is not None for r in self.row_req):
            self._idle_since = None    # an empty engine starves nobody
        return emitted

    # -- async pipeline ----------------------------------------------------

    def _dispatch_primary(self, decodable: List[int], live: List[int],
                          horizon: Optional[int]) -> List[int]:
        """Launch the step's PRIMARY dispatch (ring empty, host state
        fully replayed): a speculative draft/verify round when the
        engine has a draft plane and at least one decodable greedy row
        with budget to speculate into, else the plain fused horizon.
        Mid-chunked-prefill steps always take the plain H=1 path — the
        chunk cadence outranks speculation depth. Returns the possibly
        narrowed decodable set (block reservation may preempt)."""
        if self.spec_enabled and len(decodable) == len(live):
            W, w_row = self._spec_plan(decodable)
            if W:
                decodable, Hr = self._reserve_decode_blocks(
                    decodable, W + 1)
                if Hr < W + 1:
                    # Pool too tight to cover the verify chunk even
                    # after preemption: decode plainly at whatever
                    # horizon the reservation could hold.
                    self._dispatch_decode(Hr, decodable, chain=None)
                    return decodable
                self._dispatch_spec(W, w_row, decodable, chain=None)
                return decodable
        H = horizon
        if H is None:
            free = self.B - len(live)
            H = self.scheduler.horizon_hint(
                free_slots=free, max_horizon=self.decode_horizon)
            if len(decodable) < len(live):
                H = 1      # keep the chunk cadence: a mid-prefill
                #            row must not wait a long horizon for
                #            its next chunk (bounded TTFT)
            # Cap at the largest remaining row budget (no trailing
            # iterations with every row frozen), rounded DOWN to a
            # power of two: the fused program recompiles per
            # distinct H, so adaptive serving touches at most
            # log2(horizon)+1 programs instead of one per budget
            # remainder.
            H = min(H, int(self.row_budget[decodable].max()))
            H = 1 << max(0, H.bit_length() - 1)
        # Grow every decodable row's chain to cover the horizon,
        # preempting victims if the pool runs dry — admission capacity
        # is pool bytes, not slots, so over-admission is resolved here,
        # not refused there.
        decodable, H = self._reserve_decode_blocks(decodable, H)
        self._dispatch_decode(H, decodable, chain=None)
        return decodable

    def _spec_plan(self, decodable: List[int]):
        """Pick this dispatch's draft width. Each greedy decodable
        row's sliding acceptance window (last 16 rounds) feeds
        `SchedulerPolicy.spec_window_hint`; the dispatch width W is the
        max hint rounded UP to a power of two (bounded compile count,
        like the horizon), capped at `spec_window`, and each row keeps
        its own hint as a traced acceptance cap (`w_row`) — a shrinking
        row narrows its drafting without recompiling anything. Returns
        (0, None) to decline speculation: no decodable greedy row, or
        every greedy row down to its last budgeted token (a plain step
        emits the same single token with a cheaper program)."""
        greedy_rows = [b for b in decodable if self._row_greedy[b]]
        if not greedy_rows:
            return 0, None
        if int(self.row_budget[greedy_rows].max()) <= 1:
            return 0, None
        rates: List[Optional[float]] = []
        for b in greedy_rows:
            prop = sum(p for p, _ in self._spec_hist[b])
            acc = sum(a for _, a in self._spec_hist[b])
            rates.append(acc / prop if prop else None)
        hints = self.scheduler.spec_window_hint(
            rates=rates, spec_window=self.spec_window)
        w_row = np.ones((self.B,), np.int32)
        wmax = 1
        for b, w in zip(greedy_rows, hints):
            w = max(1, min(int(w), self.spec_window))
            w_row[b] = w
            wmax = max(wmax, w)
        return min(self.spec_window, _pow2(wmax)), w_row

    def _dispatch_spec(self, W: int, w_row: np.ndarray,
                       rows: List[int],
                       chain: Optional[tuple]) -> None:
        """Launch ONE speculative draft/verify round — the spec twin of
        `_dispatch_decode`, same async contract: emit block's
        `copy_to_host_async` issued immediately, full device carry
        (including the draft-lag lane) stored for run-ahead chaining,
        ONE host pull later at drain. The ring entry's H is W+1 (the
        emit block height and the pessimistic in-flight token count)."""
        # The draft scan and verify pass live inside ONE fused program,
        # so the dispatch seam carries the spec_draft span (proposal
        # width known here) and the drain seam carries spec_verify
        # (acceptance known there).
        with self.trace.lane("spec_draft", "dispatch", window=W,
                             proposed=int(w_row[rows].sum()),
                             rows=len(rows),
                             run_ahead=chain is not None,
                             after=self._starved_after()):
            self._t_dispatch = self._clock()
            args = chain if chain is not None else (
                *self._row_state(), jnp.asarray(self._d_lag),
                jnp.asarray(self._d_tok))
            rg = jnp.asarray(self._row_greedy)
            all_greedy = bool(self._row_greedy.all())
            wr = jnp.asarray(w_row)
            bt_dev = self._table_snapshot(self._bt)
            btd_dev = self._table_snapshot(self._bt_d)
            with spmd_mesh_scope(self.mesh):
                (toks, self._pool_k, self._pool_v, self._pool_dk,
                 self._pool_dv, self._scale_k, self._scale_v,
                 self._scale_dk, self._scale_dv, self._last_logits, rl,
                 ac, bu, ti, dl, dt) = _spec_round_paged(
                    self.params, self.draft_params, self._pool_k,
                    self._pool_v, self._pool_dk, self._pool_dv, bt_dev,
                    btd_dev, self._last_logits, *args,
                    jnp.asarray(self._row_keys), rg, wr,
                    self.temperature, self.cfg, self.draft_cfg, W,
                    all_greedy, self.top_k, self.top_p, self.eos_id,
                    shardings=self._shardings,
                    scale_k=self._scale_k, scale_v=self._scale_v,
                    scale_dk=self._scale_dk, scale_dv=self._scale_dv,
                    qspec=self.kv_quant_spec)
            _host_async(toks)
            self._ring.append(_InflightStep(
                toks, W + 1, list(rows), run_ahead=chain is not None,
                chain=(rl, ac, bu, ti, dl, dt), spec=True, w_max=W,
                w_row=np.array(w_row, np.int32)))
            self._count_decode_dispatch(chain)
            self.spec_dispatches += 1
            self.metrics.on_dispatch(W + 1, host_syncs=0)
            self._decode_dispatched()

    def _dispatch_decode(self, H: int, rows: List[int],
                         chain: Optional[tuple]) -> None:
        """Launch ONE fused decode step without waiting on anything:
        from replayed host state after a flush (`chain=None`), or
        chained off the previous in-flight dispatch's device-carried
        row state (run-ahead). The token block's `copy_to_host_async`
        is issued immediately, so the transfer overlaps the device
        computing the block — and any queued successors."""
        with self.trace.lane("dispatch", "dispatch", horizon=H,
                             rows=len(rows),
                             run_ahead=chain is not None,
                             after=self._starved_after()):
            self._t_dispatch = self._clock()
            args = chain if chain is not None else self._row_state()
            # The static greedy flag is the all-greedy fast path: without
            # per-request overrides it equals the engine-wide mode exactly
            # (the lane resets to the default at retirement), so existing
            # engines compile the same two programs they always did.
            rg = jnp.asarray(self._row_greedy)
            all_greedy = bool(self._row_greedy.all())
            # Multi-LoRA lane: the pool stacks + the [B] slot lane ride
            # every dispatch (slot 0 = zero null adapter, so base-only
            # rows are untouched); adapter_pool=None passes None/None —
            # no extra pytree leaves, the exact pre-LoRA programs.
            if self.adapter_pool is not None:
                adapters = self.adapter_pool.stacks
                row_slot = jnp.asarray(self._row_slot)
            else:
                adapters = row_slot = None
            self._count_paged_walk(H, rows)
            bt_dev = self._table_snapshot(self._bt)
            btw_dev = self._table_snapshot(self._bt_w) \
                if self.kv_pool_w is not None else None
            # the scope only matters while the program traces: under
            # a tp mesh paged_attention must not pick a Mosaic kernel
            with spmd_mesh_scope(self.mesh):
                (toks, self._pool_k, self._pool_v, self._scale_k,
                 self._scale_v, self._last_logits,
                 rl, ac, bu, ti,
                 self._moe_ctr, self._hyb) = _decode_multi_paged(
                    self.params, self._pool_k, self._pool_v, bt_dev,
                    self._last_logits, *args,
                    jnp.asarray(self._row_keys), rg, self.temperature,
                    self.cfg, H, all_greedy, self.top_k, self.top_p,
                    self.eos_id, shardings=self._shardings,
                    adapters=adapters, row_slot=row_slot,
                    scale_k=self._scale_k, scale_v=self._scale_v,
                    qspec=self.kv_quant_spec, moe_ctr=self._moe_ctr,
                    hyb=self._hyb, bt_w=btw_dev)
            _host_async(toks)
            self._ring.append(_InflightStep(toks, H, list(rows),
                                            run_ahead=chain is not None,
                                            chain=(rl, ac, bu, ti)))
            self._count_decode_dispatch(chain)
            self.moe_hit_kernel_decode_dispatches += \
                held_hit_kernel(self.cfg, self.B)
            # a stack that has a kernel for its one-token update says
            # when a decode program takes it (`state_step_kernel(cfg)`)
            in_place = getattr(self.cfg.stack(), "state_step_kernel", None)
            self.state_kernel_decode_dispatches += \
                bool(in_place and in_place(self.cfg))
            self.metrics.on_dispatch(H, host_syncs=0)
            self._decode_dispatched()

    def _starved_after(self) -> str:
        """The ONE cause of the gap the next dispatch closes, "" with no
        gap open (`_idle_since`): ``retire`` a row ended in a block
        drained since the last decode dispatch, its slot has a newcomer
        or not (ROADMAP S14 b's case: the ring stops short of a block in
        which a budget ends); ``admit`` none did and the gate has just
        admitted (an arrival into a free slot); ``chunk`` a row is
        mid-prompt; ``other`` the rest (a ring held to one block, a pool
        too dry to run ahead). What the `dispatch`, `spec_draft` and
        `prefill_dispatch` lanes carry as ``after=``."""
        if self._idle_since is None:
            return ""
        if self._retired_since_decode:
            return "retire"
        if self._admitted_since_dispatch:
            return "admit"
        return "chunk" if self._row_prefill else "other"

    def _dispatched(self, after: str, now: Optional[float] = None) -> None:
        """One program of any kind is handed to the device: the device
        has something to run again, and the seconds it had nothing
        (`after`, from `_starved_after` before the launch) go under
        their cause."""
        self._dispatch_seq += 1
        self._admitted_since_dispatch = False
        if after:
            gap = (self._clock() if now is None else now) \
                - self._idle_since
            self._idle_since = None
            self.device_starved_s_total += gap
            self._starved_s[after] += gap
            self.device_starved_dispatches_total += 1

    def _decode_dispatched(self) -> None:
        """The end of a `dispatch` / `spec_draft` lane opened at
        `_t_dispatch`, its block the ring's newest: ONE clock read is the
        seam's end, the block's dispatch time and the end of a starved
        gap."""
        entry = self._ring[-1]
        now = entry.t_dispatch = self._clock()
        self.step_dispatch_s_total += now - self._t_dispatch
        after = self._starved_after()
        self._retired_since_decode = False
        self._dispatched(after, now)
        entry.seq = self._dispatch_seq
        entry.ahead = len(self._ring) - 1

    def _count_decode_dispatch(self, chain: Optional[tuple]) -> None:
        """One fused decode (or speculative) launch; a chained one ran
        ahead of a block the host had not pulled, with or without a
        request waiting in the queue."""
        self.decode_dispatches += 1
        if chain is not None:
            self.decode_dispatches_chained += 1
            if len(self.scheduler):
                self.decode_dispatches_chained_queued += 1

    def _row_state(self) -> tuple:
        """(row_len, active, budget, tok_idx) from replayed host state:
        what a dispatch after a flush starts from. Rows mid-prefill
        ride along frozen."""
        active = np.array([self.row_req[b] is not None
                           and b not in self._row_prefill
                           for b in range(self.B)])
        return (jnp.asarray(self.row_len), jnp.asarray(active),
                jnp.asarray(self.row_budget), jnp.asarray(self._tok_idx))

    def _table_snapshot(self, bt: np.ndarray) -> jax.Array:
        """A block table as of this dispatch: jnp.asarray copies it to
        the device, so host-side growth between chained dispatches only
        reaches FUTURE dispatches (in-flight steps never read past the
        coverage they were reserved)."""
        bt_dev = jnp.asarray(bt)
        if self._shardings is not None:
            bt_dev = jax.device_put(bt_dev, self._shardings.replicated)
        return bt_dev

    def _count_paged_walk(self, H: int, rows: List[int]) -> None:
        """Account one fused decode dispatch of `H` tokens over the
        active `rows`: token h of row b queries slot ``row_len[b] +
        inflight + h``, so the kernel walks that slot's page and every
        page before it, of the ``B * MB`` entries the table holds.
        From the host's replayed `row_len` plus what the ring already
        carries — pessimistic like the block reservation: a row that
        finishes mid-flight freezes on device and walks less."""
        T = self.kv_block_tokens
        inflight = sum(e.H for e in self._ring)
        slots = (self.row_len[rows] + inflight)[:, None] + np.arange(H)
        self.paged_walk_pages_total += int(
            np.minimum(slots // T + 1, self._mb).sum())
        self.paged_walk_entries_total += H * self.B * self._mb
        # every slot of the grid has a query slot, a dead row's too: all
        # B rows walk and all but a call's first find their pages coming
        self.paged_walk_rows_total += H * self.B
        self.paged_walk_rows_chained_total += H * (self.B - 1)
        if self._selects:       # its attention is not this kernel's
            self._count_selection(slots.reshape(-1, 1), decode=True)
            self._count_sparse_decode(slots.reshape(-1))
            if self.kv_pool_w is not None:
                self.swa_window_rows_total += slots.size
                self.swa_window_slots_total += int(np.minimum(
                    slots + 1, self.cfg.sliding_window).sum())
        else:
            self.paged_walk_rows_stacked_total += \
                H * self.B * self._walk_shape(1)[2]
        if self._state_planes:
            # tokens the kernel is asked to read, a token-layer each: the
            # full layers' cache once a READER (a `HybridConfig`'s one
            # full layer and every cross-attention layer, each of a
            # `GdnConfig`'s attention layers), a window layer's at most
            # the window
            cfg = self.cfg
            self.kv_walk_tokens_full_total += int((slots + 1).sum()) \
                * cfg.full_cache_readers
            if self.kv_pool_w is not None:
                self.kv_walk_tokens_window_total += int(np.minimum(
                    slots + 1, cfg.sliding_window).sum()) \
                    * cfg.n_window_layers
            self.ssm_row_steps_total += H * len(rows)

    def _count_selection(self, q_slots: np.ndarray,
                         decode: bool = False) -> None:
        """Account the queries of one dispatch of a config that selects
        (an `MlaConfig`), ``q_slots`` [rows, queries a call] their slots
        (-1: bucket filler): a query at slot t sees t + 1 tokens, the
        indexer scores them all and attention reads `index_topk` of them
        at most, in every layer that selects (all but its window
        layers). And what the selection WALKS of the rows' tables
        (`ops.indexer_select`, whose arithmetic this asks): a block of
        a row's queries the pages up to its last slot's, none while
        that is below `index_topk` (such a query has no choice to make:
        it keeps what it sees); the lax form, which takes the calls the
        kernel has no tile for and every call off the chip, reads the
        whole table, and is counted as the kernel where it stands in
        for it."""
        from ray_tpu.ops import indexer_select as isel

        cfg, layers = self.cfg, self.cfg.n_select_layers
        live = q_slots[q_slots >= 0] + 1
        scored = int(live.sum()) * layers
        selected = int(np.minimum(live, cfg.index_topk).sum()) * layers
        self.indexer_tokens_scored_total += scored
        self.indexer_tokens_selected_total += selected
        if decode:
            self.indexer_decode_tokens_scored_total += scored
            self.indexer_decode_tokens_selected_total += selected
        rows, n = q_slots.shape
        tq = isel.query_tile(n)
        last = q_slots.reshape(rows, -1, tq or n).max(axis=2)
        walked = isel.pages_walked(last, cfg.index_topk,
                                   self.kv_block_tokens, self._mb) \
            if tq else np.full_like(last, self._mb)
        self.indexer_pages_walked_total += int(walked.sum()) * layers
        self.indexer_pages_table_total += last.size * self._mb * layers
        self.indexer_queries_total += live.size * layers
        self.indexer_queries_unselected_total += int(
            (live <= cfg.index_topk).sum()) * layers

    def _count_sparse_decode(self, q_slots: np.ndarray) -> None:
        """Account the attention of one decode dispatch of a config that
        selects, ``q_slots`` [row-tokens] the queries' slots (-1: a row
        that asks nothing, which walks and counts nothing): in every
        layer that selects, a token's attention walks the pages up to its
        slot's (`ops.sparse_latent_attention`, whose trip counts come
        from the same arithmetic) of the ``MB`` table entries a grid step
        each used to visit."""
        from ray_tpu.ops import sparse_latent_attention as sla

        layers = self.cfg.n_select_layers
        walked = sla.pages_walked(q_slots, self.kv_block_tokens, self._mb)
        self.sparse_decode_pages_walked_total += int(walked.sum()) * layers
        self.sparse_decode_pages_table_total += \
            int((q_slots >= 0).sum()) * self._mb * layers

    def _count_prefill_walk(self, starts: np.ndarray,
                            last_idx: np.ndarray, bucket: int) -> None:
        """Account one prefill dispatch of ``len(starts)`` (padded) rows
        x ``bucket`` tokens: the kernel takes a row's chunk one query
        tile at a time (`walk_shape`), and a tile walks the pages up to
        the one that holds its last REAL token's slot (a tile of bucket
        filler alone walks none), where the dense view this replaced
        covered all ``MB`` table entries of every row: pages asked for
        per table entry, which passes 1 for a late chunk of a long
        prompt (each of its tiles walks most of the row). The host's
        estimate; nothing is counted where no tile walks anything by
        design, the pure-lax lowering a tp mesh or a quantized pool's
        chunk takes (off the chip that lowering stands in for the
        kernel, and is counted)."""
        if self._selects:
            at = np.arange(bucket)[None, :]
            self._count_selection(np.where(at <= last_idx[:, None],
                                           starts[:, None] + at, -1))
            return
        if self.kv_quant_spec is not None or self.kv_pool_w is not None \
                or (self.mesh is not None and self.mesh.size > 1):
            return
        T = self.kv_block_tokens
        _, tq, stacked = self._walk_shape(bucket)
        first = np.arange(0, bucket, tq)                    # [tiles]
        top = np.minimum(first + tq - 1, last_idx[:, None])
        pages = np.minimum((starts[:, None] + top) // T + 1, self._mb)
        walks = first <= last_idx[:, None]                  # [rows, tiles]
        self.prefill_walk_pages_total += int(pages[walks].sum())
        self.prefill_table_entries_total += len(starts) * self._mb
        # the call's grid is the tiles row by row: one that walks finds
        # its first pages fetched if the tile before it walks too
        walks = walks.reshape(-1)
        self.paged_walk_rows_total += int(walks.sum())
        self.paged_walk_rows_chained_total += int(
            (walks[1:] & walks[:-1]).sum())
        self.paged_walk_rows_stacked_total += int(walks.sum()) * stacked

    def _walk_shape(self, n_slots: int):
        """What the paged kernel makes of a call of ``n_slots`` queries
        a row (`walk_shape`: pages a step, query tile, heads stacked),
        from the same static shapes it reads."""
        from ray_tpu.ops.paged_attention_kernel import walk_shape

        cfg = self.cfg
        return walk_shape(n_slots, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, self.kv_block_tokens, self._mb,
                          self._pool_k.dtype.itemsize)

    def _top_up_pipeline(self, rows: List[int],
                         horizon: Optional[int]) -> None:
        """Run ahead: keep up to `pipeline_depth` fused steps in flight
        while the engine is in a pure-decode stretch (no admission
        could change the batch, no row mid-prefill). Each queued step
        chains the previous dispatch's device row state, so no host
        sync happens between dispatches. Horizons are chosen from host
        budgets minus everything already in flight — pessimistic, so a
        queued step is never provably all-frozen; rows that finish
        mid-flight still mask their tail iterations on device
        (`pipeline_overrun_tokens`).

        With requests queued (behind full slots: `_batch_may_change`
        ruled out a free one) the ring stops short of a block in which a
        row's budget ends: draining that block frees a slot, and the newcomer
        must not wait a block it need not. Blocks that retire nobody by
        budget free no slot (short of an EOS), so the gate after them
        would admit nothing and dispatching ahead of them loses
        nothing."""
        if self.pipeline_depth < 2 or self._batch_may_change():
            return
        queued = self.scheduler.admissions_pending()
        while len(self._ring) < self.pipeline_depth:
            last = self._ring[-1]
            inflight = sum(e.H for e in self._ring)
            rem = int(self.row_budget[rows].max()) - inflight
            if rem <= 0:
                break              # every further iteration would be
                #                    overrun — nothing left to compute
            if queued and int(self.row_budget[rows].min()) <= inflight:
                break              # a slot frees inside what is in
                #                    flight: the next step admits
            if last.spec:
                # Chain another speculative round at the SAME widths:
                # the adaptive window can only move once the host has
                # replayed acceptance, and an unchanged (W, w_row)
                # keeps the chained dispatch on the compiled program.
                # H accounting is pessimistic (every round could emit
                # w_max+1), same discipline as plain run-ahead.
                if not self._ensure_decode_blocks(
                        rows, last.w_max + 1, inflight):
                    break
                self._dispatch_spec(last.w_max, last.w_row, rows,
                                    chain=last.chain)
                continue
            if horizon is not None:
                Hn = horizon
            else:
                Hn = self.scheduler.horizon_hint(
                    free_slots=self.B - sum(r is not None
                                            for r in self.row_req),
                    max_horizon=self.decode_horizon)
                Hn = min(Hn, rem)
                Hn = 1 << max(0, Hn.bit_length() - 1)
            if not self._ensure_decode_blocks(rows, Hn, inflight):
                # Pool dry: no run-ahead. Preemption needs replayed
                # host state, so it only runs on the primary dispatch
                # path once the ring empties.
                break
            self._dispatch_decode(Hn, rows,
                                  chain=self._ring[-1].chain)

    def _batch_may_change(self) -> bool:
        """Could the next admission gate or chunk cadence change the
        batch: a row is mid-prompt (or left its prompt in a chunk sent
        ahead, after the block in flight was dispatched: `_chunk_ahead`
        holds it until the next step's chunks), or a request is queued
        AND a slot is free for it. A queue behind full slots is not a
        pending admission (the gate skips every taken row), which is
        what lets a saturated engine run ahead; `admissions_pending()`
        keeps its meaning, something is queued."""
        return bool(self._row_prefill) or bool(self._chunk_ahead) or (
            self.scheduler.admissions_pending()
            and any(r is None for r in self.row_req))

    def _drain_one(self, emitted: Dict[int, List[int]]) -> None:
        """Pull the OLDEST in-flight token block to the host (its async
        copy has been in progress since dispatch) and replay it. With
        the ring topped up first, the device is already computing the
        next step(s) while this replay runs — the overlap that hides
        the host bookkeeping."""
        tr = self.trace
        entry = self._ring.popleft()
        depth = len(self._ring) + 1    # steps in flight at this drain
        self._pl_depth_sum += depth
        self._pl_depth_n += 1
        with tr.lane("host_drain", "drain", horizon=entry.H,
                     depth=depth) as drain:
            t0 = tr.now() if tr.enabled else 0.0
            block = self._device_wait(entry.toks, entry.seq)
            self.host_syncs += 1
            nbytes = int(getattr(block, "nbytes", block.size * 4))
            drain.note(bytes=nbytes)
            self.host_transfer_bytes += nbytes
            self.metrics.on_host_sync(nbytes=nbytes)
            if self._moe_ctr is not None and not entry.spec:
                # the device's wrapping counters ride the block's last
                # rows: unwrap them into the host's totals
                seen = block[entry.H:, 0].astype(np.uint32)
                self._moe_totals += (seen - self._moe_seen).astype(
                    np.int64)
                self._moe_seen = seen
                block = block[:entry.H]
            with tr.lane("emit", "drain"):
                t_emit = self._clock()
                self.metrics.on_block(entry.t_dispatch, entry.ahead,
                                      entry.H)
                sp_rounds, sp_prop, sp_acc = self._emit_block(
                    block, entry, emitted)
                self.step_emit_s_total += self._clock() - t_emit
            self.metrics.on_pipeline_drain(depth, len(self._ring))
            if entry.spec and sp_rounds:
                self.metrics.on_spec_round(sp_rounds, sp_prop, sp_acc)
                if self.spec_metrics is not None:
                    from ray_tpu.models.speculative import SpecStats
                    self.spec_metrics.observe(SpecStats(
                        rounds=sp_rounds, proposed=sp_prop,
                        accepted=sp_acc))
            if entry.spec and tr.enabled:
                # The draft scan and verify pass live inside ONE fused
                # program, so acceptance is only knowable here at
                # drain: spec_draft marks the dispatch seam,
                # spec_verify the drain seam where the accept counts
                # land.
                tr.add("spec_verify", t0, tr.now() - t0, lane="drain",
                       args={"window": entry.w_max, "rounds": sp_rounds,
                             "proposed": sp_prop, "accepted": sp_acc})

    def _device_wait(self, x, seq: Optional[int] = None) -> np.ndarray:
        """`_device_get` from inside the serving loop, with the time the
        host stood blocked in it kept (`device_wait_s`, `device_waits`)
        and shown as its own span: what is left of a step's wall time
        is the host's own work. `seq` is the pulled program's place
        among the dispatches (None: the last one). With the ring empty
        and nothing dispatched after it, the device has nothing to run
        from the pull's return on (`_idle_since`); a program sent after
        it (a chunk sent ahead) counts as in flight until the next
        pull."""
        with self.trace.lane("device_wait", "drain"):
            t = self._clock()
            out = _device_get(x)
            now = self._clock()
            self.device_wait_s += now - t
        self.device_waits += 1
        self._idle_since = now if not self._ring and (
            seq is None or seq == self._dispatch_seq) else None
        return out

    def _flush_pipeline(self, emitted: Dict[int, List[int]]) -> None:
        """Drain EVERY in-flight step. Called before any admission /
        prefill, and at end of stream — the points where
        host state must be fully caught up with the device."""
        if not self._ring:
            return
        self.pipeline_flushes += 1
        self.metrics.on_pipeline_flush()
        with self.trace.lane("pipeline_flush", "drain",
                             steps=len(self._ring)):
            t0 = self._clock()
            inside = self.device_wait_s + self.step_emit_s_total
            while self._ring:
                self._drain_one(emitted)
            self.step_flush_s_total += self._clock() - t0 - (
                self.device_wait_s + self.step_emit_s_total - inside)

    def stats(self) -> Dict[str, float]:
        """Flat numeric telemetry snapshot (EngineMetrics.stats) plus
        the engine's instantaneous queue/slot state — safe to publish
        as gauges (serve.metrics.report_engine_stats)."""
        out = self.metrics.stats()
        out["queue_depth"] = float(len(self.scheduler))
        out["live_slots"] = float(
            sum(r is not None for r in self.row_req))
        out["slot_occupancy"] = out["live_slots"] / self.B
        # Fleet plane: the router scores replicas on these three plus
        # the TTFT/TPOT percentiles from EngineMetrics.stats().
        out["requests_shed"] = float(self.requests_shed)
        out["pending_prefill_tokens"] = float(
            self.pending_prefill_tokens())
        out["draining"] = 1.0 if self.draining else 0.0
        # Engine lifetime on the injectable clock + the plain-int step
        # counter (the metrics-plane `steps` field disappears under
        # enable_metrics=False; these two never do).
        out["uptime_s"] = max(0.0, self._clock() - self._start_t)
        out["steps_total"] = float(self.steps_total)
        # Engine-level dispatch accounting (kept even when metrics are
        # disabled — benchmarks read these to report syncs per token).
        # Every derived ratio guards its denominator: a fresh engine
        # (no token emitted, no prefill run) reports 0.0, never NaN.
        def _ratio(num: float, den: float) -> float:
            return num / den if den else 0.0

        out["decode_dispatches"] = float(self.decode_dispatches)
        # of those, dispatched ahead of a block not yet pulled; and of
        # THOSE, while a request waited in the queue behind full slots
        out["decode_dispatches_chained"] = float(
            self.decode_dispatches_chained)
        out["decode_dispatches_chained_queued"] = float(
            self.decode_dispatches_chained_queued)
        out["prefill_dispatches"] = float(self.prefill_dispatches)
        out["prefill_dispatches_ahead"] = float(
            self.prefill_dispatches_ahead)
        out["moe_grouped_prefill_dispatches_total"] = float(
            self.moe_grouped_prefill_dispatches)
        out["moe_hit_kernel_decode_dispatches_total"] = float(
            self.moe_hit_kernel_decode_dispatches)
        out["state_kernel_decode_dispatches_total"] = float(
            self.state_kernel_decode_dispatches)
        out["host_syncs"] = float(self.host_syncs)
        out["host_syncs_per_token"] = _ratio(self.host_syncs,
                                             self.tokens_out)
        # Seconds the host stood blocked on the device: a step's wall
        # time less this is the host's own work.
        out["device_waits"] = float(self.device_waits)
        out["device_wait_s"] = float(self.device_wait_s)
        # Step clocks: a step's wall time by seam (the six below,
        # `device_wait_s` and the remainder add up to `step_s_total`
        # while every pull happens inside a step; a handoff export
        # pulls outside one), steps of STALL_STEP_S or more and their
        # seconds inside `_device_get`, and the seconds the device had
        # nothing to run as far as the host knows, by cause.
        seams = 0.0
        for name in ("step_flush_s_total", "step_admit_s_total",
                     "step_prefill_dispatch_s_total",
                     "step_dispatch_s_total", "step_emit_s_total"):
            out[name] = float(getattr(self, name))
            seams += out[name]
        out["step_s_total"] = float(self.step_s_total)
        out["step_other_s_total"] = \
            self.step_s_total - seams - self.device_wait_s
        out["steps_stalled_total"] = float(self.steps_stalled_total)
        out["step_stalled_s_total"] = float(self.step_stalled_s_total)
        out["step_stalled_device_wait_s_total"] = float(
            self.step_stalled_device_wait_s_total)
        out["device_starved_s_total"] = float(self.device_starved_s_total)
        out["device_starved_dispatches_total"] = float(
            self.device_starved_dispatches_total)
        for cause, seconds in self._starved_s.items():
            out[f"device_starved_{cause}_s_total"] = seconds
        # Compile plane: programs JAX built since the PROCESS started
        # (every engine of a process reports the same three; a rollup
        # takes them once). A warmed engine holds them still: one that
        # rises met a shape its warm-up missed (docs/serving.md).
        out.update(self._compiles.counters())
        # Tensor-parallel plane: tp_degree is 1 for an unsharded
        # engine; transfer bytes count the [H, B] token blocks pulled
        # at drain — the replicated choke point, so bytes/token must
        # NOT grow with tp degree (tests/test_engine_sharded.py gates
        # this).
        out["tp_degree"] = float(self.tp_degree)
        out["host_transfer_bytes"] = float(self.host_transfer_bytes)
        out["host_transfer_bytes_per_token"] = _ratio(
            self.host_transfer_bytes, self.tokens_out)
        out["dispatches_per_token"] = _ratio(self.decode_dispatches,
                                             self.tokens_out)
        # Prefill efficiency: real suffix tokens vs bucket/pow2 filler.
        out["prefill_real_tokens"] = float(self.prefill_real_tokens)
        out["prefill_padded_tokens"] = float(self.prefill_padded_tokens)
        out["prefill_padding_waste_frac"] = _ratio(
            self.prefill_padded_tokens,
            self.prefill_real_tokens + self.prefill_padded_tokens)
        # Prefix-reuse plane: reused = prompt tokens whose blocks were
        # SHARED from the pool; recomputed (= prefill_real_tokens) =
        # prompt tokens the prefill actually ran.
        out["prefix_lookups"] = float(self.prefix_lookups)
        out["prefix_hits"] = float(self.prefix_hits)
        out["prefix_hit_rate"] = _ratio(self.prefix_hits,
                                        self.prefix_lookups)
        out["prefix_reused_tokens"] = float(self.prefix_reused_tokens)
        out["prefix_reused_frac"] = _ratio(
            self.prefix_reused_tokens,
            self.prefix_reused_tokens + self.prefill_real_tokens)
        out["prefix_evictions"] = float(self.prefix_evictions)
        out["chunked_prefill_stalls"] = float(self.chunked_prefill_stalls)
        # Async-pipeline plane. depth_effective is the mean number of
        # fused steps in flight at each drain (1.0 = synchronous; ->
        # pipeline_depth when run-ahead is sustained); host_lag_steps
        # is the instantaneous ring length (dispatched, not yet
        # replayed); overrun tokens are masked device iterations run
        # ahead for rows that had already finished. Fresh engine: all
        # 0.0 (the _ratio guard).
        out["pipeline_depth"] = float(self.pipeline_depth)
        out["pipeline_depth_effective"] = _ratio(self._pl_depth_sum,
                                                 self._pl_depth_n)
        out["pipeline_flushes"] = float(self.pipeline_flushes)
        out["pipeline_overrun_tokens"] = float(
            self.pipeline_overrun_tokens)
        out["host_lag_steps"] = float(len(self._ring))
        if self._prefix is not None:
            out["prefix_blocks_in_use"] = float(self._prefix.blocks_in_use)
            out["prefix_blocks_total"] = float(self._prefix.blocks_total)
        # Block-pool plane: zero-copy sharing, CoW, preempt-and-swap.
        out["kv_blocks_shared"] = float(self.kv_blocks_shared)
        out["kv_block_cows"] = float(self.kv_block_cows)
        out["preemptions"] = float(self.preemptions)
        out["swap_ins"] = float(self.swap_ins)
        out["swap_outs"] = float(self.swap_outs)
        out["swap_in_bytes"] = float(self.swap_in_bytes)
        out["swap_out_bytes"] = float(self.swap_out_bytes)
        out["kv_used_fraction"] = self.kv_used_fraction()
        out["paged_walk_pages_total"] = float(self.paged_walk_pages_total)
        out["paged_walk_entries_total"] = float(
            self.paged_walk_entries_total)
        out["paged_walk_rows_total"] = float(self.paged_walk_rows_total)
        out["paged_walk_rows_chained_total"] = float(
            self.paged_walk_rows_chained_total)
        out["paged_walk_rows_stacked_total"] = float(
            self.paged_walk_rows_stacked_total)
        out["prefill_walk_pages_total"] = float(
            self.prefill_walk_pages_total)
        out["prefill_table_entries_total"] = float(
            self.prefill_table_entries_total)
        # Expert-layer plane (an `MoeConfig`; identically 0.0 for a dense
        # model): counted on the device over live rows, as of the last
        # token block drained. Speculative rounds are not counted.
        # `moe_assignments_landed_total`: those of them that landed on an
        # expert HELD here (`held_experts`; all of them where all are).
        totals = list(self._moe_totals) + [0] * len(_MOE_CTR_NAMES)
        for name, n in zip(_MOE_CTR_NAMES, totals):
            out[name] = float(n)
        if len(self._moe_totals) <= _MOE_CTR_ROWS:
            out["moe_assignments_landed_total"] = \
                out["moe_assignments_total"]
        # Selection plane (an `MlaConfig`; identically 0.0 otherwise):
        # token-layers the indexer scored and those it selected for
        # attention, host estimates at dispatch like the paged-walk ones.
        out["indexer_tokens_scored_total"] = float(
            self.indexer_tokens_scored_total)
        out["indexer_tokens_selected_total"] = float(
            self.indexer_tokens_selected_total)
        out["indexer_decode_tokens_scored_total"] = float(
            self.indexer_decode_tokens_scored_total)
        out["indexer_decode_tokens_selected_total"] = float(
            self.indexer_decode_tokens_selected_total)
        for name in ("indexer_pages_walked_total",
                     "indexer_pages_table_total", "indexer_queries_total",
                     "indexer_queries_unselected_total",
                     "sparse_decode_pages_walked_total",
                     "sparse_decode_pages_table_total"):
            out[name] = float(getattr(self, name))
        # Recurrent-state and window planes (a `HybridConfig`; the
        # `ssm_*` and `kv_walk_tokens_full_total` also a `GdnConfig`;
        # identically 0.0 otherwise): host estimates at dispatch, like
        # the paged-walk ones.
        for name in ("kv_walk_tokens_window_total",
                     "kv_walk_tokens_full_total",
                     "swa_window_rows_total", "swa_window_slots_total",
                     "window_blocks_freed_total", "window_pool_peak_blocks",
                     "ssm_state_resets_total", "ssm_row_steps_total",
                     "prefill_layer_tokens_total",
                     "prefill_layer_tokens_skipped_total"):
            out[name] = float(getattr(self, name))
        out["window_pool_blocks_total"] = float(
            self.kv_pool_w.blocks_total if self.kv_pool_w else 0)
        out["window_pool_blocks_in_use"] = float(
            self.kv_pool_w.blocks_in_use if self.kv_pool_w else 0)
        # Disaggregated-handoff plane: identically 0.0 on a colocated
        # engine (prefill_only never set, import never called) so
        # fleet rollups sum blindly.
        out["prefill_only"] = 1.0 if self.prefill_only else 0.0
        out["handoffs_out"] = float(self.handoffs_out)
        out["handoffs_in"] = float(self.handoffs_in)
        out["handoff_out_bytes"] = float(self.handoff_out_bytes)
        out["handoff_in_bytes"] = float(self.handoff_in_bytes)
        out["requests_handoff_ready"] = float(len(self._handoff_ready))
        # Quantized-KV plane: bytes/token is the concurrency lever the
        # fleet watches (see docs/serving.md); quant_enabled is 0.0 on
        # an unquantized engine.
        out["kv_quant_enabled"] = 1.0 if self.kv_quant else 0.0
        out["kv_bytes_per_token"] = float(self.kv_bytes_per_token)
        out["kv_bytes_per_block"] = float(self.kv_bytes_per_block)
        pool = self.kv_pool
        out["kv_pool_blocks_total"] = float(pool.blocks_total)
        out["kv_pool_blocks_in_use"] = float(pool.blocks_in_use)
        out["kv_pool_blocks_free"] = float(pool.free_blocks)
        out["kv_pool_occupancy"] = _ratio(pool.blocks_in_use,
                                          pool.blocks_total)
        out["kv_free_blocks"] = float(self.kv_free_blocks())
        out["requests_swapped"] = float(len(self._swapped))
        # Speculative plane: identically 0.0 with spec off, so fleet
        # rollups sum/weight them without mode checks. acceptance_rate
        # is accepted/proposed over the engine's lifetime;
        # window_effective is the mean per-round draft width the
        # adaptive policy actually dispatched (proposed/rounds).
        out["spec_enabled"] = 1.0 if self.spec_enabled else 0.0
        out["spec_window"] = float(self.spec_window
                                   if self.spec_enabled else 0)
        out["spec_dispatches"] = float(self.spec_dispatches)
        out["spec_rounds"] = float(self.spec_rounds)
        out["spec_proposed"] = float(self.spec_proposed)
        out["spec_accepted"] = float(self.spec_accepted)
        out["spec_acceptance_rate"] = _ratio(self.spec_accepted,
                                             self.spec_proposed)
        out["spec_window_effective"] = _ratio(self.spec_proposed,
                                              self.spec_rounds)
        out["spec_draft_tokens_wasted"] = float(self.spec_wasted)
        out["spec_prefill_dispatches"] = float(
            self.spec_prefill_dispatches)
        if self.spec_enabled:
            out["spec_kv_pool_blocks_in_use"] = float(
                self.kv_pool_d.blocks_in_use)
        # Multi-LoRA plane: identically 0.0 with no adapter pool, so
        # fleet rollups (and the perf gate's zero check) need no mode
        # branch. Pool fields come from AdapterPool.stats().
        out["adapter_enabled"] = 1.0 if self.adapter_pool else 0.0
        out["adapter_prefetch_deferrals"] = float(self.adapter_deferrals)
        if self.adapter_pool is not None:
            out.update(self.adapter_pool.stats())
        else:
            out.update({
                "adapters_registered": 0.0, "adapter_slots": 0.0,
                "adapter_slots_resident": 0.0,
                "adapter_slots_pinned": 0.0, "adapter_lookups": 0.0,
                "adapter_hits": 0.0, "adapter_hit_rate": 0.0,
                "adapter_prefetches": 0.0, "adapter_evictions": 0.0,
            })
        return out

    def run(self) -> Dict[int, List[int]]:
        """Drain queue + slots; returns {req_id: generated tokens} for
        every finished request and POPS them from the engine (a
        long-running server that never popped would leak one _Request
        per call served)."""
        while self.pending():
            self.step()
        return {rid: self.pop_result(rid) for rid in list(self.finished)}

    def dump_trace(self, path: Optional[str] = None) -> List[dict]:
        """chrome://tracing export of this engine's request-lifecycle
        spans (pid = engine_id, tid = one lane per request plus
        `engine:dispatch` / `engine:drain` step lanes). Writes JSON to
        `path` (falling back to the RAY_TPU_TRACE dump path) and
        returns the event list — empty with tracing off."""
        return self.trace.dump(path, pid=self.engine_id)

    def pop_result(self, req_id: int) -> List[int]:
        """Remove a FINISHED request from the engine and return its
        generated tokens. Long-running callers driving step() directly
        must pop each request as it finishes (see `finished`). A shed
        request pops an empty list — check `shed_ids` BEFORE popping
        to distinguish a shed from a zero-token finish."""
        if req_id not in self.finished:
            raise KeyError(f"request {req_id} unknown or not finished")
        self.finished.discard(req_id)
        self.shed_ids.discard(req_id)
        return self.results.pop(req_id).tokens

    # -- fleet integration: drain hook + router load probes ----------------

    def begin_drain(self) -> None:
        """Stop accepting new requests; everything already submitted
        (queued or in-flight) still runs to completion. This is the
        flush-before-removal half of fleet scale-down: the fleet stops
        routing to a DRAINING replica, keeps stepping it until
        `pending()` reads False, then removes it — so an admitted
        token is never lost to a scale decision. Idempotent."""
        if self.trace.enabled and not self.draining:
            self.trace.instant("drain", lane="events",
                               args={"queued": len(self.scheduler)})
        self.draining = True

    def drain(self) -> Dict[int, List[int]]:
        """`begin_drain()` + run to empty: flushes the async pipeline,
        finishes every queued/in-flight request, and returns
        {req_id: tokens} for all of them (popping, like `run()`)."""
        self.begin_drain()
        return self.run()

    def halt(self) -> None:
        """Abandon this engine's work WITHOUT completing it — the
        fleet's failure path (the opposite of drain's flush-before-
        removal). Discards the async pipeline ring (in-flight device
        steps are never replayed), releases every live row's KV
        blocks (refcount hygiene: trie-shared blocks survive through
        the trie's own references, private blocks free), drops the
        swap ledger and the queue, and refuses new submits. Host-side
        request bookkeeping (`results`: prompt, emitted tokens,
        priority) is deliberately KEPT — it is what the fleet
        reconstructs failover resubmissions from. Idempotent; never
        raises (the engine may be arbitrarily broken when called)."""
        if self.halted:
            return
        self.halted = True
        self.draining = True
        if self.trace.enabled:
            self.trace.instant(
                "halt", lane="events",
                args={"queued": len(self.scheduler),
                      "live_rows": sum(r is not None
                                       for r in self.row_req),
                      "inflight_steps": len(self._ring)})
        self._ring.clear()
        self._idle_since = None
        self._row_prefill.clear()
        self._chunk_ahead.clear()
        for row in range(self.B):
            try:
                self._release_row_blocks(row)
            except Exception:
                pass
            if self._row_slot[row] and self.adapter_pool is not None:
                try:
                    self.adapter_pool.decref(int(self._row_slot[row]))
                except Exception:
                    pass
            self._row_slot[row] = 0
            self.row_req[row] = None
            self.row_len[row] = 0
            self.row_budget[row] = 0
            self._tok_idx[row] = 0
        if self.adapter_pool is not None:
            for slot in self._pending_slots.values():
                try:
                    self.adapter_pool.decref(slot)
                except Exception:
                    pass
        self._pending_slots.clear()
        self._handoff_ready.clear()
        self._handoff_ready_set.clear()
        self._swapped.clear()
        # Drop the queue wholesale (a fresh empty policy, not N pops:
        # a deferring policy could legally return None forever once
        # its probe's world is gone). The queued _Request objects stay
        # reachable through `results` for failover reconstruction.
        self.scheduler = FIFOPolicy()

    def pending_prefill_tokens(self) -> int:
        """Prompt tokens this engine has accepted but not yet
        prefilled: every queued request's full prompt plus the
        uncovered suffix of every row mid-chunked-prefill. A pure host
        count (zero device syncs) — the fleet router's per-replica
        cost signal: a replica may show free slots yet owe seconds of
        prefill to requests ahead of the newcomer."""
        n = sum(len(st.prompt) - st.pos
                for st in self._row_prefill.values())
        queued = getattr(self.scheduler, "queued_requests", None)
        if queued is not None:
            try:
                for r in queued():
                    swap = self._swapped.get(r.req_id)
                    if swap is not None and swap.k is not None:
                        continue   # swap-in is a scatter, no prefill owed
                    if swap is not None:
                        n += len(r.prompt) + len(r.tokens)  # replay
                        continue
                    n += len(r.prompt)
            except NotImplementedError:
                pass     # custom policy without the probe: slots-only
        return n

    def kv_free_blocks(self) -> int:
        """KV blocks an admission could claim right now: free +
        evictable cold prefix blocks. Pure host arithmetic, zero
        device syncs."""
        n = self.kv_pool.free_blocks
        if self._prefix is not None:
            n += self._prefix.evictable_blocks()
        return n

    def kv_used_fraction(self) -> float:
        """Unreclaimable KV pressure in [0, 1] — the fleet router's
        occupancy signal: the fraction of pool blocks neither free nor
        evictable-cold; the fuller of the two pools where there is a
        window pool, which a row needs both of."""
        used = max(0.0, 1.0 - self.kv_free_blocks()
                   / self.kv_pool.blocks_total)
        if self.kv_pool_w is not None:
            used = max(used, self.kv_pool_w.blocks_in_use
                       / self.kv_pool_w.blocks_total)
        return used

    def prefix_match_tokens(self, prompt: List[int]) -> int:
        """Prompt tokens this engine could SHARE from its prefix cache
        instead of prefilling, right now (0 without a prefix cache).
        A pure host trie walk with peek=True: probing every replica
        per routing decision must not perturb any replica's LRU
        recency — only the replica that WINS the request touches its
        trie (at admission)."""
        if self._prefix is None:
            return 0
        ids, _ = self._prefix.match(prompt, peek=True)
        return len(ids) * self.kv_block_tokens

    # -- internals ---------------------------------------------------------

    def _bucket(self, n: int) -> int:
        if not self.bucket_lens:
            return n
        return min(max(1 << (n - 1).bit_length(), self.min_prefill_bucket),
                   self.max_len)

    def _req_key(self, req: _Request) -> np.ndarray:
        """Per-request sampling stream: the submitted key verbatim, or
        a distinct stream mixed host-side from the engine key and the
        request id (no device dispatch per admission)."""
        if req.rng is not None:
            return req.rng
        mix0 = (req.req_id * 0x9E3779B9 + 1) & 0xFFFFFFFF
        mix1 = (req.req_id * 0x85EBCA6B + 1) & 0xFFFFFFFF
        return np.array([int(self._base_key[0]) ^ mix0,
                         int(self._base_key[1]) ^ mix1], np.uint32)

    def _shed(self, req: _Request) -> None:
        """Retire a past-deadline request WITHOUT admitting it: no
        slot, no prefill, no tokens. It lands in `finished` (and
        `shed_ids`) like a normal completion so callers polling
        finished/pop_result need no special path."""
        req.done = True
        req.shed = True
        self.finished.add(req.req_id)
        self.shed_ids.add(req.req_id)
        self.requests_shed += 1
        self.metrics.on_shed(req.req_id)
        if self.trace.enabled:
            self.trace.close("queue_wait", req.req_id, {"shed": True})
            self.trace.finish(req.req_id, {"shed": True}, name="shed")

    def _on_prefix_evict(self, n: int) -> None:
        self.prefix_evictions += n
        self.metrics.on_prefix_evictions(n)

    def _prefix_probe(self, prompt) -> Tuple[int, Optional[tuple],
                                             bool]:
        """(matched_tokens, prefix_group_key, next_block_pending) for
        the prefix-affinity scheduler — a pure host trie walk, zero
        device dispatches. The group key (the prompt's first block) is
        None for prompts too short to ever share a block."""
        ids, pending = self._prefix.match(prompt)
        T = self.kv_block_tokens
        key = tuple(prompt[:T]) if len(prompt) > T else None
        return len(ids) * T, key, pending

    # -- admission, block accounting, preempt-and-swap ---------------------

    def _admit_rows_paged(
            self, admissions: List[Tuple[int, _Request]]) -> None:
        """Bind this step's admissions to their rows and start their
        prefills: each request gets a BLOCK CHAIN. With the prefix
        cache on, each admission first probes the trie: a warm prompt's
        matched blocks are shared by incref — zero bytes move — and
        only the suffix is prefilled. A FULL-prompt match keeps all but
        the tail block shared and copies the tail once (copy-on-write:
        the row's first generated token must extend it). Novel prompt
        blocks are freshly allocated and registered PENDING in the trie
        (the row's prefill writes them in place — commit needs no
        copy). The actual prefill work — whole suffix, or
        `prefill_chunk`-sized pieces across steps — runs in
        `_advance_prefills`. First tokens are NOT sampled here: each
        row's last-prompt logits stay on device in `_last_logits` and
        the fused decode samples them — admission costs zero host
        round-trips."""
        T = self.kv_block_tokens
        cow_pairs: List[Tuple[int, int]] = []
        draft_seeds: List[Tuple[int, List[int]]] = []
        for row, req in admissions:
            self.metrics.on_admit(req.req_id)
            swap = self._swapped.pop(req.req_id, None)
            if swap is not None:
                if not self._swap_in_row(row, req, swap):
                    # The admission gate's estimate went stale (an
                    # earlier admission this step took the headroom):
                    # requeue; the slot stays empty this round.
                    self._swapped[req.req_id] = swap
                    self._drop_pending_slot(req)
                    self._requeue_front(req)
                elif self.spec_enabled:
                    # The swap ledger never carries the draft plane:
                    # re-seed it from prompt + emitted tokens (the
                    # exact sequence the target's restored K/V
                    # encodes), so acceptance recovers immediately.
                    draft_seeds.append(
                        (row, list(req.prompt) + list(req.tokens)))
                continue
            if self.trace.enabled:
                self.trace.close("queue_wait", req.req_id)
                self.trace.instant("admit", req.req_id, {"row": row})
            start = 0
            shared: List[int] = []
            cow_src: Optional[int] = None
            nodes: list = []
            # Adapter rows BYPASS the prefix trie entirely: their K/V
            # depends on the adapter's deltas, so a block produced
            # under adapter X must never be matched by (or registered
            # for) a request under adapter Y or the base model.
            if self._prefix is not None and req.adapter_id is None:
                ids, _ = self._prefix.match(req.prompt, allow_full=True)
                self.prefix_lookups += 1
                if ids and len(ids) * T == len(req.prompt):
                    # Full-prompt hit: share every block but the tail,
                    # which the row must grow — that one is duplicated
                    # by `_cow_blocks` (the round's single batched
                    # copy) and the prefill recomputes ONLY the last
                    # prompt token to land its true next-token logits.
                    cow_src = int(ids[-1])
                    shared = [int(i) for i in ids[:-1]]
                    start = len(req.prompt) - 1
                elif ids:
                    shared = [int(i) for i in ids]
                    start = len(shared) * T
            n_total = -(-len(req.prompt) // T)
            # Pin the shared blocks FIRST: holding the row's reference
            # means the eviction fallback inside _pool_alloc can never
            # recycle them out from under this admission.
            self.kv_pool.incref(shared)
            new_ids = self._pool_alloc(n_total - len(shared))
            if new_ids is None:
                self.kv_pool.decref(shared)
                self._drop_pending_slot(req)
                if self.trace.enabled:
                    # Back to the queue: re-open queue_wait so the
                    # retry wait stays a span, not a trace gap.
                    self.trace.open("queue_wait", req.req_id)
                self._requeue_front(req)
                continue
            if cow_src is not None:
                cow_pairs.append((cow_src, new_ids[0]))
                self.kv_block_cows += 1
                self.metrics.on_kv_cow()
            chain = shared + new_ids
            if self._prefix is not None and req.adapter_id is None:
                hit = bool(shared) or cow_src is not None
                if hit:
                    self.prefix_hits += 1
                self.prefix_reused_tokens += start
                self.kv_blocks_shared += len(shared)
                if shared:
                    self.metrics.on_kv_shared(len(shared))
                self.metrics.on_prefix(hit=hit, reused_tokens=start)
                if self.trace.enabled:
                    self.trace.instant(
                        "prefix_match", req.req_id,
                        {"hit": hit, "matched_tokens": start,
                         "shared_blocks": len(shared),
                         "cow": cow_src is not None})
                nodes = self._prefix.register(req.prompt, chain)
            self._bind_row(row, req, chain, start)
            self._row_prefill[row] = _PrefillState(req, start, nodes)
            if self.spec_enabled:
                draft_seeds.append((row, list(req.prompt)))
        if cow_pairs:
            n = len(cow_pairs)
            n_pad = _pow2(n)
            src = np.zeros((n_pad,), np.int32)   # pad = null block:
            dst = np.zeros((n_pad,), np.int32)   # 0 -> 0 is a no-op
            for i, (s, d) in enumerate(cow_pairs):
                src[i] = s
                dst[i] = d
            (self._pool_k, self._pool_v, self._scale_k,
             self._scale_v) = _cow_blocks(
                self._pool_k, self._pool_v, jnp.asarray(src),
                jnp.asarray(dst), shardings=self._shardings,
                scale_k=self._scale_k, scale_v=self._scale_v)
            self._dispatched(self._starved_after())
        self._seed_draft_rows(draft_seeds)

    def _seed_draft_rows(
            self, seeds: List[Tuple[int, List[int]]]) -> None:
        """Seed the DRAFT KV plane for freshly (re)bound rows: one
        full-sequence draft prefill per length bucket, piggybacked on
        the admission step (the draft is cheap enough that chunking it
        buys nothing — the target's chunked prefill still paces TTFT).
        Each seeded row also resets its draft-lag lane and acceptance
        history. A failed draft-chain alloc skips the seed: a cold
        draft only lowers acceptance, never changes emitted tokens."""
        if not self.spec_enabled or not seeds:
            return
        T = self.kv_block_tokens
        groups: Dict[int, List[Tuple[int, List[int]]]] = {}
        for row, toks in seeds:
            self._d_lag[row] = 0
            self._d_tok[row] = 0
            self._spec_hist[row].clear()
            if not toks:
                continue
            if not self._ensure_draft_blocks(row, -(-len(toks) // T)):
                continue
            Cb = min(self._bucket(len(toks)), self.max_len)
            groups.setdefault(Cb, []).append((row, toks))
        for Cb in sorted(groups):
            grp = groups[Cb]
            n = len(grp)
            with self.trace.lane("spec_draft_prefill", "dispatch",
                                 bucket=Cb, rows=n):
                n_pad = _pow2(n)
                prompts = np.zeros((n_pad, Cb), np.int32)
                rows = np.zeros((n_pad,), np.int32)
                starts = np.zeros((n_pad,), np.int32)
                last_idx = np.zeros((n_pad,), np.int32)
                for i, (row, toks) in enumerate(grp):
                    prompts[i, :len(toks)] = toks
                    rows[i] = row
                    last_idx[i] = len(toks) - 1
                prompts[n:] = prompts[n - 1]    # filler: repeat last row —
                rows[n:] = rows[n - 1]          # duplicate scatters write
                last_idx[n:] = last_idx[n - 1]  # identical values
                bt_grp = self._bt_d[rows]
                # as in `_dispatch_decode`: under a tp mesh the traced
                # program must not pick a Mosaic kernel
                with spmd_mesh_scope(self.mesh):
                    (self._pool_dk, self._pool_dv, self._scale_dk,
                     self._scale_dv,
                     self._d_last_logits, _, _) = _prefill_rows_paged(
                        self.draft_params, jnp.asarray(prompts),
                        self._pool_dk, self._pool_dv,
                        self._d_last_logits, jnp.asarray(bt_grp),
                        jnp.asarray(rows), jnp.asarray(starts),
                        jnp.asarray(last_idx), self.draft_cfg,
                        shardings=self._d_shardings,
                        scale_k=self._scale_dk, scale_v=self._scale_dv,
                        qspec=self.kv_quant_spec)
                self.spec_prefill_dispatches += 1
                self._dispatched(self._starved_after())

    def _bind_row(self, row: int, req: _Request, chain: List[int],
                  start: int) -> None:
        """Point a slot row at its block chain and reset its decode
        state (budget/tok_idx overridden after the call by the swap-in
        path, which restores rather than restarts)."""
        self._row_blocks[row] = list(chain)
        self._bt[row, :] = 0
        self._bt[row, :len(chain)] = chain
        if self.kv_pool_w is not None:
            # the window chain is grown chunk by chunk (`_window_cover`);
            # the slot's recurrent state is zeroed by the first chunk
            assert not self._row_blocks_w[row]
            self._bt_w[row, :] = 0
            self._w_lo[row] = 0
        self.row_req[row] = req
        self.row_len[row] = start
        self.row_budget[row] = req.max_new_tokens
        self._tok_idx[row] = 0
        self._row_keys[row] = self._req_key(req)
        self._row_greedy[row] = (self.greedy if req.greedy is None
                                 else bool(req.greedy))
        self._row_slot[row] = self._pending_slots.pop(req.req_id, 0)
        self._row_admit_seq[row] = self._admit_seq
        self._admit_seq += 1

    def _requeue_front(self, req: _Request) -> None:
        pf = getattr(self.scheduler, "push_front", None)
        (pf if pf is not None else self.scheduler.push)(req)
        self.metrics.observe_queue_depth(len(self.scheduler))

    def _drop_pending_slot(self, req: _Request) -> None:
        """Return the adapter-slot reference the admission gate took
        for a request that is being requeued AFTER the gate (stale
        capacity estimate, swap-in failure). The request re-allocs —
        re-increfs — at the gate on its next admission round, so the
        pending reference must be dropped here or the slot leaks a
        count and can never evict."""
        slot = self._pending_slots.pop(req.req_id, 0)
        if slot and self.adapter_pool is not None:
            self.adapter_pool.decref(slot)

    def _pool_alloc(self, n: int) -> Optional[List[int]]:
        """n fresh blocks, evicting cold committed prefix blocks
        LRU-first when the free list runs short (the trie's eviction
        honors refcounts: a block any row still shares is never a
        victim). None when nothing more can be evicted — the caller
        preempts a row or defers the admission."""
        if n <= 0:
            return []
        ids = self.kv_pool.alloc(n)
        while ids is None:
            if self._prefix is None or not self._prefix.evict_one():
                return None
            ids = self.kv_pool.alloc(n)
        return ids

    def _ensure_decode_blocks(self, rows: List[int], H: int,
                              inflight: int) -> bool:
        """Grow each row's chain to cover ``row_len + inflight + H``
        slots (capped at the row's own completion point — prompt +
        budget — and at max_len). Growth appends to the host block
        table only; in-flight dispatches hold their own device
        snapshot. False when the pool (plus evictable prefix blocks)
        cannot cover it; rows already grown keep their blocks — no
        leak, the retry after preemption re-walks them as no-ops."""
        T = self.kv_block_tokens
        for b in rows:
            req = self.row_req[b]
            lim = min(len(req.prompt) + req.max_new_tokens,
                      self.max_len)
            need_slots = min(int(self.row_len[b]) + inflight + H, lim)
            nb = -(-need_slots // T)
            have = len(self._row_blocks[b])
            if nb > have:
                got = self._pool_alloc(nb - have)
                if got is None:
                    return False
                self._row_blocks[b].extend(got)
                self._bt[b, have:have + len(got)] = got
            if self.spec_enabled and not self._ensure_draft_blocks(b, nb):
                return False
            if self.kv_pool_w is not None:
                # every dispatch still to come queries at or past the
                # host's replayed row_len
                self._window_release(b, int(self.row_len[b]))
                if not self._window_cover(b, need_slots):
                    return False
        return True

    # -- the window plane (`kv_pool_w`: a `HybridConfig`'s) -----------------

    def _window_cover(self, b: int, upto: int) -> bool:
        """Grow row ``b``'s WINDOW chain to hold slots below ``upto``.
        The chain is the row's logical blocks ``[_w_lo, _w_lo + len)``;
        entries before it were released and point at the null block.
        False when the window pool cannot cover it (the caller preempts,
        or leaves the row's chunk for a later step)."""
        T = self.kv_block_tokens
        have = int(self._w_lo[b]) + len(self._row_blocks_w[b])
        nb = -(-upto // T)
        if nb > have:
            got = self.kv_pool_w.alloc(nb - have)
            if got is None:
                return False
            self._row_blocks_w[b].extend(got)
            self._bt_w[b, have:nb] = got
            self.window_pool_peak_blocks = max(
                self.window_pool_peak_blocks, self.kv_pool_w.blocks_in_use)
        return True

    def _window_release(self, b: int, next_q: int) -> None:
        """Release row ``b``'s window blocks that lie WHOLLY behind the
        window of every query still to come, the earliest at slot
        ``next_q``: a query at t sees ``t - W < s <= t``, so block j is
        dead once ``(j + 1) * T <= next_q - W + 1``. A step already in
        flight reads them through its own table snapshot, and the device
        runs it before any later program can write them for another
        row."""
        keep = max(0, next_q - self.cfg.sliding_window + 1) \
            // self.kv_block_tokens
        lo = int(self._w_lo[b])
        n = min(keep - lo, len(self._row_blocks_w[b]))
        if n <= 0:
            return
        self.kv_pool_w.decref(self._row_blocks_w[b][:n])
        del self._row_blocks_w[b][:n]
        self._bt_w[b, lo:lo + n] = 0
        self._w_lo[b] = lo + n
        self.window_blocks_freed_total += n

    def _ensure_draft_blocks(self, b: int, nb: int) -> bool:
        """Grow row ``b``'s DRAFT chain to ``nb`` blocks. The draft
        pool is sized so every slot can hold a full-length chain, so
        this cannot fail for live rows in steady state; False is
        returned defensively (the caller treats it like target-pool
        exhaustion). Draft coverage is a performance nicety, not a
        correctness requirement: an overshooting draft write past the
        chain lands in table entry 0 — the null block — whose garbage
        is never attended (``kv_valid_len`` masks it), and a garbage
        draft only lowers acceptance, never changes emitted tokens."""
        have = len(self._row_blocks_d[b])
        if nb <= have:
            return True
        got = self.kv_pool_d.alloc(nb - have)
        if got is None:
            return False
        self._row_blocks_d[b].extend(got)
        self._bt_d[b, have:have + len(got)] = got
        return True

    def _reserve_decode_blocks(self, decodable: List[int],
                               H: int) -> Tuple[List[int], int]:
        """Make the coming fused step safe: every decodable row must
        own the blocks its next H tokens will write. When the pool
        runs dry, PREEMPT victims (newest admission first — oldest
        rows are closest to finishing and have the most sunk compute)
        until the survivors fit. Only called with the pipeline ring
        empty: preemption reads host row state, which must be fully
        replayed."""
        decodable = list(decodable)
        while not self._ensure_decode_blocks(decodable, H, 0):
            if len(decodable) <= 1:
                if H > 1:
                    H = 1      # shrink the horizon before giving up
                    continue
                raise RuntimeError(
                    "KV pool exhausted with a single decodable "
                    "row at horizon 1 — kv_pool_bytes is too small "
                    "for this request shape (mid-prefill rows may be "
                    "holding the remainder)")
            victim = self._choose_victim(decodable)
            self._preempt_row(victim)
            decodable.remove(victim)
        return decodable, H

    def _choose_victim(self, rows: List[int]) -> int:
        """Which decodable row to preempt. Rows are offered to the
        scheduler's `choose_victim` hook oldest-admission-first; the
        default (and every built-in policy) takes the LAST-admitted
        row — LIFO preemption, the vLLM discipline that protects sunk
        compute."""
        ordered = sorted(rows, key=lambda b: self._row_admit_seq[b])
        hook = getattr(self.scheduler, "choose_victim", None)
        if hook is not None:
            return hook(ordered, self.row_req)
        return ordered[-1]

    def _spill_row(self, row: int) -> _SwapState:
        """Gather a row's blocks (quantized bytes and their scale rows
        verbatim) and its last logits to the host: the state a swap-in
        or a handoff import scatters back. `copy_to_host_async` on all
        of them first, so the pulls overlap."""
        ids = self._row_blocks[row]
        n = len(ids)
        bids = np.zeros((_pow2(max(1, n)),), np.int32)
        bids[:n] = ids                 # pad = null block
        k, v, sk, sv = _swap_out_gather(
            self._pool_k, self._pool_v, jnp.asarray(bids),
            shardings=self._shardings, scale_k=self._scale_k,
            scale_v=self._scale_v)
        self._dispatched(self._starved_after())
        parts = [k, v, self._last_logits[row], sk, sv]
        for x in parts:
            if x is not None:
                _host_async(x)
        k, v, lg, sk, sv = (None if x is None else self._device_wait(x)
                            for x in parts)
        return _SwapState(k, v, n, int(self.row_len[row]),
                          int(self._tok_idx[row]),
                          int(self.row_budget[row]), lg, sk=sk, sv=sv)

    def _preempt_row(self, row: int) -> None:
        """Evict a live decodable row mid-decode. swap mode gathers
        its blocks into fresh buffers, starts `copy_to_host_async`,
        and frees the blocks once the host copy lands — HBM is
        reclaimed, and re-admission scatters the bytes back into
        whatever physical blocks are free then (the block table makes
        them logically identical). recompute mode just drops the
        blocks and replays prompt + emitted tokens at re-admission.
        Either way the request returns to the FRONT of the queue with
        `resume` set: its deadline no longer applies (it was admitted
        once) and the prefix-affinity policy skips its probe."""
        assert not self._ring, "preemption needs a drained pipeline"
        req = self.row_req[row]
        ids = self._row_blocks[row]
        if self.preempt_mode == "swap":
            swap = self._spill_row(row)
            self.swap_outs += 1
            self.swap_out_bytes += swap.nbytes
            self.metrics.on_swap_out(swap.nbytes)
        else:
            swap = _SwapState(
                None, None, len(ids), int(self.row_len[row]),
                int(self._tok_idx[row]), int(self.row_budget[row]),
                None)
        self._swapped[req.req_id] = swap
        self._release_row_blocks(row)
        if self._row_slot[row]:
            # The row's adapter reference dies with the row; the gate
            # re-allocs (and may have to re-prefetch) at re-admission.
            self.adapter_pool.decref(int(self._row_slot[row]))
            self._row_slot[row] = 0
        self.row_req[row] = None
        self.row_len[row] = 0
        self.row_budget[row] = 0
        self._tok_idx[row] = 0
        self.preemptions += 1
        self.metrics.on_preempt()
        if self.trace.enabled:
            self.trace.span_since_mark(
                "preempt_swap_out", req.req_id,
                {"mode": self.preempt_mode, "blocks": len(ids),
                 "bytes": swap.nbytes})
        req.resume = True
        self._requeue_front(req)

    def _swap_in_row(self, row: int, req: _Request,
                     swap: _SwapState) -> bool:
        """Re-admit a preempted request. swap mode scatters its host
        K/V into a fresh chain and restores the row EXACTLY where it
        froze — decodable this very step, no prefill. recompute mode
        re-prefills prompt + emitted tokens (mathematically the same
        K/V) and continues the token stream at the saved tok_idx.
        False if the pool cannot cover it right now (caller requeues)."""
        T = self.kv_block_tokens
        if swap.k is None:
            replay = list(req.prompt) + list(req.tokens)
            ids = self._pool_alloc(-(-len(replay) // T))
            if ids is None:
                return False
            self._bind_row(row, req, ids, 0)
            self.row_budget[row] = req.max_new_tokens - len(req.tokens)
            self._tok_idx[row] = len(req.tokens)
            # No trie registration: emitted tokens are not a shared
            # prompt, and the prompt's own blocks were registered (and
            # possibly still live) on first admission.
            self._row_prefill[row] = _PrefillState(req, 0, [],
                                                   prompt=replay)
            self.swap_ins += 1
            if self.trace.enabled:
                self.trace.span_since_mark(
                    "swap_in", req.req_id,
                    {"mode": "recompute",
                     "replay_tokens": len(replay)})
            return True
        ids = self._pool_alloc(swap.n_blocks)
        if ids is None:
            return False
        nbp = _pow2(max(1, swap.n_blocks))
        bids = np.zeros((nbp,), np.int32)      # pad = null block: the
        bids[:swap.n_blocks] = ids             # gather's padding lands
        #                                        back where it came from
        (self._pool_k, self._pool_v, self._scale_k,
         self._scale_v) = _swap_in_scatter(
            self._pool_k, self._pool_v, jnp.asarray(swap.k),
            jnp.asarray(swap.v), jnp.asarray(bids),
            shardings=self._shardings, scale_k=self._scale_k,
            scale_v=self._scale_v,
            host_sk=None if swap.sk is None else jnp.asarray(swap.sk),
            host_sv=None if swap.sv is None else jnp.asarray(swap.sv))
        self._dispatched(self._starved_after())
        self._set_row_logits(row, swap.logits)
        self._bind_row(row, req, ids, swap.row_len)
        self.row_budget[row] = swap.budget
        self._tok_idx[row] = swap.tok_idx
        nbytes = swap.nbytes
        self.swap_ins += 1
        self.swap_in_bytes += nbytes
        self.metrics.on_swap_in(nbytes)
        self.metrics.on_decodable(req.req_id)
        if self.trace.enabled:
            self.trace.span_since_mark(
                "swap_in", req.req_id,
                {"mode": "swap", "bytes": nbytes,
                 "blocks": swap.n_blocks})
        return True

    # -- disaggregated prefill/decode handoff ------------------------------

    def handoff_ready(self) -> List[int]:
        """Request ids parked post-prefill on a prefill-only engine,
        oldest first — each is waiting for the fleet to
        `export_request` it to a decode-class replica. Always empty on
        a colocated engine."""
        return list(self._handoff_ready)

    def export_request(self, req_id: int) -> dict:
        """Extract a request whose prefill frontier has completed —
        the engine half of the disaggregated prefill→decode handoff.

        The request must be bound to a live row that is NOT
        mid-chunked-prefill, with the async pipeline empty (on a
        prefill-only engine the ring is always empty: it never
        dispatches a decode program). The row's KV blocks are gathered
        to host via the preempt-and-swap `_swap_out_gather` path —
        quantized bytes plus their scale rows move verbatim — together
        with the row's last-prompt-token logits. The row's blocks are
        decref'd, its adapter pin released, and the request leaves this
        engine
        entirely (`results` included): it now lives wherever
        `import_request` lands it.

        Token identity holds because a completed prefill IS a
        preemption at tok_idx=0: the first decode token is sampled
        from the carried logits with `step_rng_key(rng, 0)`, exactly
        what this engine would have done next."""
        self._refuse_handoff("export_request")
        row = None
        for b in range(self.B):
            r = self.row_req[b]
            if r is not None and r.req_id == req_id:
                row = b
                break
        if row is None:
            raise RuntimeError(
                f"export_request: request {req_id} is not bound to a "
                "row (still queued, already finished, or unknown)")
        if row in self._row_prefill:
            raise RuntimeError(
                f"export_request: request {req_id} is still "
                "mid-chunked-prefill; export only after its frontier "
                "completes (see handoff_ready())")
        if self._ring:
            raise RuntimeError(
                "export_request needs a drained pipeline (in-flight "
                "fused decode blocks still reference row state); "
                "step() flushes before admissions — export between "
                "steps")
        # Drained-ring dominator for the row-state writes below (the
        # raise above enforces it with a typed error; flush-order
        # wants the guard in assert form).
        assert not self._ring
        req = self.row_req[row]
        st = self._spill_row(row)
        nbytes = st.nbytes
        kv = {"k": st.k, "v": st.v, "sk": st.sk, "sv": st.sv,
              "n_blocks": st.n_blocks, "row_len": st.row_len,
              "tok_idx": st.tok_idx, "budget": st.budget,
              "logits": st.logits,
              "block_tokens": self.kv_block_tokens,
              "quant": self.kv_quant,
              "pool_shape": self._kv_geometry}
        self._release_row_blocks(row)
        if self._row_slot[row]:
            # The exporting row's adapter pin dies here; the importing
            # engine's admission gate re-pins (and prefetches a cold
            # adapter) on its own pool.
            self.adapter_pool.decref(int(self._row_slot[row]))
            self._row_slot[row] = 0
        handoff = {"req_id": req.req_id,
                   "prompt": list(req.prompt),
                   "max_new_tokens": req.max_new_tokens,
                   "priority": req.priority,
                   "greedy": req.greedy,
                   "rng": req.rng,
                   "adapter_id": req.adapter_id,
                   "tokens": list(req.tokens),
                   "kv": kv}
        self.row_req[row] = None
        self.row_len[row] = 0
        self.row_budget[row] = 0
        self._tok_idx[row] = 0
        self.results.pop(req.req_id, None)
        if req.req_id in self._handoff_ready_set:
            self._handoff_ready_set.discard(req.req_id)
            self._handoff_ready.remove(req.req_id)
        self.handoffs_out += 1
        self.handoff_out_bytes += nbytes
        self.metrics.on_handoff_out(req.req_id, nbytes)
        if self.trace.enabled:
            self.trace.span_since_mark(
                "handoff_export", req.req_id,
                {"bytes": nbytes,
                 "blocks": kv["n_blocks"],
                 "tokens": len(req.tokens)})
        return handoff

    def import_request(self, handoff: dict) -> int:
        """Admit a request exported from another engine — the decode
        half of the handoff. Re-submits it under THIS engine's queue
        discipline (same rng key, greedy mode, priority, adapter), and
        when the exported KV payload is compatible with this engine's
        pool (same block size, same quantization, same KV geometry)
        pre-seeds the swap ledger with it: admission then scatters the
        bytes back via `_swap_in_scatter` and the row is decodable
        immediately — no re-prefill. Incompatible or missing payloads
        fall back to recompute (prompt + any emitted
        tokens replay), which is slower but bit-identical. Returns the
        request id on this engine."""
        self._refuse_handoff("import_request")
        kv = handoff.get("kv")
        toks = handoff.get("tokens") or []
        rng = handoff.get("rng")
        rid = self.submit(
            handoff["prompt"], handoff["max_new_tokens"],
            priority=handoff.get("priority", 0),
            rng=rng,
            greedy=handoff.get("greedy"),
            resume_tokens=toks or None,
            adapter_id=handoff.get("adapter_id"))
        req = self.results[rid]
        req.handoff = True
        compatible = (
            kv is not None
            and kv["block_tokens"] == self.kv_block_tokens
            and kv["quant"] == self.kv_quant
            and kv["pool_shape"] == self._kv_geometry)
        if compatible:
            # Pre-seed the swap ledger with the exported bytes: the
            # recompute entry submit() may have planted (resume path)
            # is replaced by the byte-carrying state, and
            # `_admit_rows_paged` scatters it back like any preempted
            # row returning home.
            self._swapped[rid] = _SwapState(
                kv["k"], kv["v"], kv["n_blocks"], kv["row_len"],
                kv["tok_idx"], kv["budget"], kv["logits"],
                sk=kv["sk"], sv=kv["sv"])
            req.resume = True
            nbytes = self._swapped[rid].nbytes
        else:
            nbytes = 0
        self.handoffs_in += 1
        self.handoff_in_bytes += nbytes
        self.metrics.on_handoff_in(nbytes)
        if self.trace.enabled:
            self.trace.span_since_mark(
                "handoff_import", rid,
                {"bytes": nbytes, "mode":
                 "swap" if compatible else "recompute"})
        return rid

    def _refuse_handoff(self, what: str) -> None:
        why = self.cfg.refusals().get("handoff")
        if why is not None:
            raise ValueError(why.format(what))

    @property
    def _kv_geometry(self) -> Tuple[Tuple[str, int, int], ...]:
        """(name, layers, lanes) of each plane behind a row's table
        (`cache_planes`): what a handoff's payload ``[L, n, T, lanes]``
        must agree on."""
        return tuple((pl.name, pl.layers, pl.lanes) for pl in self._planes)

    def _release_row_blocks(self, row: int) -> None:
        """Drop the row's reference on its chain (trie-shared blocks
        survive via the trie's own reference) and point the table back
        at the null block."""
        ids = self._row_blocks[row]
        if ids:
            self.kv_pool.decref(ids)
        self._row_blocks[row] = []
        self._bt[row, :] = 0
        if self.kv_pool_w is not None:
            if self._row_blocks_w[row]:
                self.kv_pool_w.decref(self._row_blocks_w[row])
            self._row_blocks_w[row] = []
            self._bt_w[row, :] = 0
            self._w_lo[row] = 0
        if self.spec_enabled:
            # Draft chains are private (never trie-shared), so decref
            # frees them outright; the plane is re-seeded from scratch
            # at (re-)admission.
            d_ids = self._row_blocks_d[row]
            if d_ids:
                self.kv_pool_d.decref(d_ids)
            self._row_blocks_d[row] = []
            self._bt_d[row, :] = 0

    def _fits_now(self, req: _Request) -> bool:
        """Admission gate: would this request's NEW blocks fit the
        pool right now, counting evictable cold trie blocks as
        reclaimable? Pure host probe (peek=True) — deferring an
        admission must not perturb LRU recency. An optimistic stale
        answer is safe: `_admit_rows_paged` re-checks and requeues."""
        T = self.kv_block_tokens
        swap = self._swapped.get(req.req_id)
        if swap is not None:
            if swap.k is not None:
                need = swap.n_blocks
            else:
                need = -(-(len(req.prompt) + len(req.tokens)) // T)
        else:
            need = -(-len(req.prompt) // T)
            # Adapter rows take no prefix credit: they bypass the trie.
            if self._prefix is not None and req.adapter_id is None:
                ids, _ = self._prefix.match(req.prompt, peek=True,
                                            allow_full=True)
                if ids and len(ids) * T == len(req.prompt):
                    need -= len(ids) - 1   # tail block is CoW'd
                else:
                    need -= len(ids)
        if self.kv_pool_w is not None:
            # the window plane must hold the first chunk at least
            first = min(len(req.prompt), self.prefill_chunk or self.max_len)
            if -(-first // T) > self.kv_pool_w.free_blocks:
                return False
        return need <= self.kv_free_blocks()

    def _commit_covered(self, row: int, st: _PrefillState) -> None:
        """The row's prefill writes the trie's blocks DIRECTLY (they
        ARE the row's chain), so a pending block the frontier has
        covered just commits — zero copy dispatches — and from the next
        admission round on `match` hands it to warm requests."""
        T = self.kv_block_tokens
        while st.nodes and (st.nodes[0][0] + 1) * T <= st.pos:
            _, node = st.nodes.pop(0)
            self._prefix.commit(node)

    def _advance_prefills(self, ahead: bool = False) -> None:
        """Advance every mid-prefill row by one chunk (the whole
        remaining suffix when `prefill_chunk` is None), same-bucket
        chunks batched into ONE `_prefill_rows_paged` program. A row
        whose frontier reaches its prompt length leaves `_row_prefill`
        and is decodable THIS step (its last chunk scattered the true
        last-prompt logits). Pending prefix blocks are committed as the
        frontier passes them.

        ``ahead``: the call a step makes once its decode block is
        dispatched, before it (or the next step's flush) waits for the
        block. It dispatches the chunk these rows would take at
        the start of the NEXT step (a chunk needs nothing the block
        returns: the prompt is known, the row's blocks are its own), and
        the next step's call skips them: a row still takes one chunk a
        step, in the same order on the device behind the same block,
        and the device has the chunk to run while the host replays the
        block, gates and dispatches. A newcomer's first chunk is then a
        program of its own behind it. Not with an adapter pool (a
        landed prefetch donates the stacks at the gate, which an
        in-flight chunk still reads) nor a draft plane."""
        if ahead:
            if self.adapter_pool is not None or self.spec_enabled:
                return
            todo = dict(self._row_prefill)
        else:
            todo = {row: st for row, st in self._row_prefill.items()
                    if self._chunk_ahead.get(row) is not st}
            self._chunk_ahead = {}
        if not todo:
            return
        with self.trace.lane("advance_prefills", "dispatch",
                             rows=len(todo), ahead=ahead):
            self._t_prefill = self._clock()
            # A group is one program: the chunks of one bucket and, where
            # prefill stops early (a `HybridConfig`), of one kind, a
            # prompt's last chunk or not (the others are always "last":
            # one program a bucket).
            groups: Dict[Tuple[int, bool],
                         List[Tuple[int, _PrefillState, int]]] = {}
            for row, st in todo.items():
                C = len(st.prompt) - st.pos
                if self.prefill_chunk is not None:
                    C = min(C, self.prefill_chunk)
                # Bucket the chunk, capped so the scatter never runs past
                # max_len (starts differ per row; the cap is per-row).
                Cb = min(self._bucket(C), self.max_len - st.pos)
                if self.kv_pool_w is not None \
                        and not self._window_cover(row, st.pos + C):
                    continue   # the window pool is dry: next step
                final = not self._prefill_stops_early \
                    or st.pos + C >= len(st.prompt)
                groups.setdefault((Cb, final), []).append((row, st, C))
            if self.kv_pool_w is not None and not groups and not ahead \
                    and len(todo) == len(self._row_prefill) \
                    == sum(r is not None for r in self.row_req):
                raise RuntimeError(
                    "window pool exhausted with every live row mid-"
                    "prefill: nothing can free a block (batch_slots or "
                    "prefill_chunk too large for it)")
            for Cb, final in sorted(groups):
                grp = groups[Cb, final]
                n = len(grp)
                with self.trace.lane("prefill_dispatch", "dispatch",
                                     bucket=Cb, rows=n,
                                     after=self._starved_after()) as span:
                    n_pad = _pow2(n)
                    prompts = np.zeros((n_pad, Cb), np.int32)
                    rows = np.zeros((n_pad,), np.int32)
                    starts = np.zeros((n_pad,), np.int32)
                    last_idx = np.zeros((n_pad,), np.int32)
                    real = 0
                    for i, (row, st, C) in enumerate(grp):
                        prompts[i, :C] = st.prompt[st.pos:st.pos + C]
                        rows[i] = row
                        starts[i] = st.pos
                        last_idx[i] = C - 1
                        real += C
                    prompts[n:] = prompts[n - 1]    # filler: repeat last row —
                    rows[n:] = rows[n - 1]          # duplicate scatters write
                    starts[n:] = starts[n - 1]      # identical values
                    last_idx[n:] = last_idx[n - 1]
                    # Per-chunk adapter-slot lane gathered from the engine's
                    # [B] lane (filler rows repeat the last real row, so the
                    # gather stays well-defined).
                    if self.adapter_pool is not None:
                        adapters = self.adapter_pool.stacks
                        row_slot = jnp.asarray(self._row_slot[rows])
                    else:
                        adapters = row_slot = None
                    bt_grp = self._bt[rows]            # [n_pad, MB]
                    btw_grp = None
                    if self._state_planes:
                        self.ssm_state_resets_total += sum(
                            st.pos == 0 for _, st, _ in grp)
                    if self.kv_pool_w is not None:
                        btw_grp = jnp.asarray(self._bt_w[rows])
                    if self._prefill_stops_early:
                        skipped = self.cfg.n_layers \
                            - self.cfg.prefill_layers()
                        self.prefill_layer_tokens_total += \
                            real * self.cfg.n_layers
                        self.prefill_layer_tokens_skipped_total += \
                            (real - n * final) * skipped
                    self._count_prefill_walk(starts, last_idx, Cb)
                    # the scope only matters while the program traces:
                    # under a tp mesh paged_attention must not pick a
                    # Mosaic kernel (GSPMD cannot partition one)
                    with spmd_mesh_scope(self.mesh):
                        (self._pool_k, self._pool_v, self._scale_k,
                         self._scale_v, self._last_logits,
                         self._moe_ctr, self._hyb) = _prefill_rows_paged(
                            self.params, jnp.asarray(prompts),
                            self._pool_k, self._pool_v,
                            self._last_logits, jnp.asarray(bt_grp),
                            jnp.asarray(rows), jnp.asarray(starts),
                            jnp.asarray(last_idx), self.cfg,
                            shardings=self._shardings,
                            adapters=adapters, row_slot=row_slot,
                            scale_k=self._scale_k,
                            scale_v=self._scale_v,
                            qspec=self.kv_quant_spec,
                            moe_ctr=self._moe_ctr, hyb=self._hyb,
                            bt_w=btw_grp, final=final)  # graftlint: disable=jit-hygiene -- a bool, part of the group's key: two programs a bucket at most, and only for a HybridConfig
                    self.prefill_dispatches += 1
                    self.prefill_dispatches_ahead += ahead
                    self.moe_grouped_prefill_dispatches += \
                        held_grouped_prefill(self.cfg, n_pad * Cb)
                    padded = n_pad * Cb - real
                    self.prefill_real_tokens += real
                    self.prefill_padded_tokens += padded
                    self.metrics.on_prefill_batch(real, padded)
                    span.note(real=real, padded=padded)
                    self._dispatched(self._starved_after())
            done_rows = []
            for grp in groups.values():
                for row, st, C in grp:
                    if ahead:
                        self._chunk_ahead[row] = st
                    st.pos += C
                    self.row_len[row] = st.pos
                    if self.trace.enabled:
                        self.trace.span_since_mark(
                            "prefill_chunk", st.req.req_id,
                            {"pos": st.pos, "tokens": C,
                             "prompt_tokens": len(st.prompt)})
                    if self._prefix is not None:
                        self._commit_covered(row, st)
                    if self.kv_pool_w is not None:
                        self._window_release(row, st.pos)
                    if st.pos >= len(st.prompt):
                        done_rows.append(row)
            for row in done_rows:
                st = self._row_prefill.pop(row)
                self.metrics.on_decodable(st.req.req_id)
            self.step_prefill_dispatch_s_total += \
                self._clock() - self._t_prefill

    def _emit_block(self, block: np.ndarray, entry: _InflightStep,
                    emitted: Dict[int, List[int]]
                    ) -> Tuple[int, int, int]:
        """VECTORIZED host replay of one [H, B] token block: mirrors
        `_decode_multi_paged`'s per-iteration transition without touching the
        device, but in one numpy slice + one arithmetic pass per ROW
        instead of a Python iteration per token.

        The device masks every emit after a row freezes to -1, and
        `active` only ever transitions True->False inside a block, so
        each column is a prefix of real tokens followed by -1s: the
        count of != -1 entries IS the number of emitted tokens, and
        replaying the transition once with that count is bit-identical
        to replaying it token by token —
            budget   -= count;  tok_idx += count
            done      = budget <= 0
                        | row_len + count >= max_len   (room check at
                          the LAST emitted token's pre-advance row_len)
                        | last_tok == eos
            row_len  += count if continuing (a finishing row's state is
                        reset on retirement, so its advance is moot)
        Emission order is unchanged from the scalar loop: every live
        row emits from iteration 0, so `emitted` insertion order — and
        therefore retire-on-eos ordering — is identical.

        Rows found already retired (`row_req is None`) only occur in
        run-ahead blocks dispatched before the host replayed the
        retiring block; their columns are all-masked on device and
        accounted as `pipeline_overrun_tokens`.

        Returns `(rounds, proposed, accepted)` speculative accounting
        for this block — all zero for a plain decode block — so
        `_drain_one` can feed SpecMetrics and the `spec_verify` span
        without rescanning the columns. For a spec block each live
        greedy row is one ROUND: it proposed `w_row[b]` draft tokens
        and had `count - 1` of them accepted (the +1 is the verify
        pass's own token, which is free). The host `_d_lag/_d_tok`
        lanes mirror the device's draft-lag carry: a fully-accepted
        round leaves the last accepted token un-fed to the DRAFT
        (lag 1), anything else leaves the draft exactly at the
        frontier (lag 0)."""
        tr = self.trace
        sp_rounds = sp_prop = sp_acc = 0
        for b in entry.rows:
            req = self.row_req[b]
            if req is None:
                if entry.run_ahead:
                    self.pipeline_overrun_tokens += entry.H
                    self.metrics.on_pipeline_overrun(entry.H)
                continue
            col = block[:, b]
            count = int((col != -1).sum())
            if count == 0:
                continue
            toks = col[:count].tolist()
            req.tokens.extend(toks)
            emitted.setdefault(req.req_id, []).extend(toks)
            self.metrics.on_tokens(req.req_id, count)
            if tr.enabled:
                tr.span_since_mark(
                    "decode_block", req.req_id,
                    {"tokens": count, "horizon": entry.H,
                     "batch": len(entry.rows)})
            if entry.spec and self._row_greedy[b]:
                proposed_b = int(entry.w_row[b])
                accepted_b = count - 1
                sp_rounds += 1
                sp_prop += proposed_b
                sp_acc += accepted_b
                self.spec_rounds += 1
                self.spec_proposed += proposed_b
                self.spec_accepted += accepted_b
                self.spec_wasted += proposed_b - accepted_b
                self._spec_hist[b].append((proposed_b, accepted_b))
            self.row_budget[b] -= count
            self._tok_idx[b] += count
            out_of_room = self.row_len[b] + count >= self.max_len
            if (self.row_budget[b] <= 0 or out_of_room
                    or (self.eos_id is not None
                        and toks[-1] == self.eos_id)):
                req.done = True
                self._retired_since_decode = True
                self.finished.add(req.req_id)
                self.metrics.on_finish(req.req_id)
                if tr.enabled:
                    tr.finish(req.req_id,
                              {"tokens": len(req.tokens)})
                self.row_req[b] = None
                self.row_len[b] = 0      # slot free for the next prefill
                self.row_budget[b] = 0
                self._tok_idx[b] = 0
                # Lane reset: the slot's next tenant starts from the
                # engine default, so an override-free engine keeps its
                # all-greedy fast path (one static compile).
                self._row_greedy[b] = bool(self.greedy)
                if self.spec_enabled:
                    self._d_lag[b] = 0
                    self._d_tok[b] = 0
                # Blocks the trie shares stay resident (its ref);
                # everything else returns to the pool NOW — this is
                # what lets admission capacity track finished tokens
                # instead of max-live slots.
                self._release_row_blocks(b)
                if self._row_slot[b]:
                    # Retirement drops the row's adapter pin; a
                    # refcount-0 slot stays RESIDENT (LRU) so the next
                    # same-adapter request is a hit, it just becomes
                    # evictable.
                    self.adapter_pool.decref(int(self._row_slot[b]))
                    self._row_slot[b] = 0
            else:
                self.row_len[b] += count  # the fed tokens took their slots
                if entry.spec:
                    full = count == entry.H
                    self._d_lag[b] = 1 if full else 0
                    self._d_tok[b] = int(toks[-1]) if full else 0
        return sp_rounds, sp_prop, sp_acc

"""Attention dispatch + pure-JAX reference implementation.

`attention` picks the best implementation for the current backend:
Pallas flash attention on TPU, an XLA-fused reference elsewhere (CPU
tests run on the reference path; the Pallas kernel is also unit-tested in
interpret mode against it).

SPMD: Mosaic kernels cannot be auto-partitioned by GSPMD, so under a
multi-device mesh the flash kernel is wrapped in a `shard_map` over the
batch/head axes (sequence stays whole per shard — sp uses the dedicated
ring/ulysses paths). The active mesh reaches this dispatch through a
trace-time context (`spmd_mesh_scope`) set by make_sharded_train_step.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import scope_names as sn

_SPMD_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_spmd_mesh", default=None)


@contextlib.contextmanager
def spmd_mesh_scope(mesh):
    """Announce the mesh a jitted program is being traced for, so kernel
    dispatch can pick SPMD-safe forms. Trace-time only — no runtime
    effect."""
    token = _SPMD_MESH.set(mesh)
    try:
        yield
    finally:
        _SPMD_MESH.reset(token)


def _in_manual_region() -> bool:
    """True inside a shard_map body (axes already manual there)."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def _flash_spmd_spec(q_shape, kv_shape, mesh):
    """PartitionSpec over (batch, heads) for a [B,H,S,D] flash call, or
    None when no mesh axis can be used (run unwrapped)."""
    from jax.sharding import PartitionSpec as P

    b_axes = tuple(a for a in ("dcn", "dp", "fsdp")
                   if mesh.shape.get(a, 1) > 1)
    if b_axes and q_shape[0] % math.prod(mesh.shape[a] for a in b_axes):
        b_axes = ()
    tp = mesh.shape.get("tp", 1)
    h_axes = ("tp",) if tp > 1 and q_shape[1] % tp == 0 and \
        kv_shape[1] % tp == 0 else ()
    if not b_axes and not h_axes:
        return None
    return P(b_axes or None, h_axes or None, None, None)


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B, Hkv, S, D] -> [B, Hkv*n_rep, S, D] for grouped-query attention."""
    if n_rep == 1:
        return k
    b, hkv, s, d = k.shape
    k = jnp.broadcast_to(k[:, :, None], (b, hkv, n_rep, s, d))
    return k.reshape(b, hkv * n_rep, s, d)


def mha_reference(q: jax.Array,
                  k: jax.Array,
                  v: jax.Array,
                  *,
                  causal: bool = True,
                  sm_scale: Optional[float] = None,
                  segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Stable-softmax attention. q: [B,H,Sq,D]; k,v: [B,Hkv,Sk,D].

    Computes in float32 regardless of input dtype (bf16 inputs hit the MXU
    via preferred_element_type), returns q.dtype.
    """
    *_, h, sq, d = q.shape
    hkv = k.shape[1]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    sk = k.shape[2]
    scale = sm_scale if sm_scale is not None else d ** -0.5

    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = None
    if causal:
        qi = jnp.arange(sq)[:, None] + (sk - sq)  # allow kv prefix (decode)
        ki = jnp.arange(sk)[None, :]
        mask = qi >= ki
    if segment_ids is not None:
        seg = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        mask = seg if mask is None else (mask[None, None] & seg)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if mask is not None:
        # A row with NO unmasked column attends to nothing: define its
        # output (and gradient) as zero, not softmax's accidental
        # uniform distribution over -inf logits. Matches the Pallas
        # kernels' semantics.
        row_live = mask.any(-1, keepdims=True)
        probs = jnp.where(row_live, probs, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def pool_kv_heads(pool, q) -> int:
    """KV heads of a ``[L, NB, T, KV*D]`` pool, read against the
    ``[B, S, H, D]`` queries that attend it."""
    H, D = q.shape[2:]
    if pool.ndim != 4 or pool.shape[3] % D:
        raise ValueError(f"pool {pool.shape} is not [L, NB, T, KV*{D}]")
    KV = pool.shape[3] // D
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    return KV


def paged_attention(q: jax.Array,
                    k_pool: jax.Array,
                    v_pool: jax.Array,
                    block_tables: jax.Array,
                    q_slots: jax.Array,
                    *,
                    layer,
                    kv_valid_len,
                    sm_scale: Optional[float] = None,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    own_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                    window: Optional[int] = None,
                    impl: str = "auto") -> jax.Array:
    """Attention over PAGED K/V: each query row reads its keys/values
    through a per-row block table instead of a contiguous cache row —
    the vLLM/PagedAttention access pattern, serving the DecodeEngine's
    one-pool-many-requests memory plane.

      q            [B, S, H, D]   queries (S=1 fused decode; the window
                                  of a speculative round; a prefill
                                  chunk, B the admission group)
      k/v_pool     [L, NB, T, KV*D] the shared block pool, WHOLE, as
                                  the engine stores and carries it: L
                                  layers of NB blocks of T tokens, a
                                  token's KV heads merged head-major
                                  into one lane axis; block 0 is the
                                  reserved null block
      block_tables [B, MB]        row b's logical block p covers cache
                                  slots [p*T, (p+1)*T); unallocated
                                  entries point at block 0
      q_slots      [B, S]         the cache slot each query occupies
                                  (-1: bucket filler that is asked
                                  nothing; its result is garbage or 0)
      layer        scalar         which layer of the pool to attend
                                  (traced: the engine's layer scan
                                  passes its index). Pages are read
                                  ``pool[layer, block]`` where they lie;
                                  no layer is ever sliced out
      kv_valid_len scalar         slots >= this are masked (the
                                  engine's max_len)
      k/v_scale    [L, NB, KV]    per-block per-kv-head f32 dequant
                                  scales when the pool is quantized
                                  (int8/fp8 — see ops/kv_quant.py);
                                  None for a dense-precision pool
      own_kv       2 x [B, S, KV, D] the queries' own keys and values as
                                  computed, attended at ``q_slots`` in
                                  place of what the pool holds there (a
                                  QUANTIZED pool's prefill: a chunk
                                  attends itself exact and only what
                                  lies below it as stored). Pure-lax
                                  path only
      window       static int     a query at slot t sees slots
                                  ``t - window < s <= t`` only (a
                                  sliding-window layer). Table entries
                                  wholly behind every query's window are
                                  never read, so the engine may have
                                  freed their blocks; None: causal over
                                  everything

    Semantics are EXACTLY the dense path's `_cached_attention` (see
    models/generate.py) evaluated on the gathered view: causal mask
    ``slot <= q_slot`` plus the valid-length cap, -1e30 fill, f32
    softmax. The two must stay in lockstep op-for-op — the paged
    engine's token-identity to the dense engine and to solo `generate`
    (tests/test_engine_paged.py) rests on it. Positions gathered from
    unallocated/garbage block entries are always masked: exp(-1e30 -
    max) underflows to exactly 0.0, so any finite garbage contributes
    exactly nothing. With scales, dequantization happens INSIDE the
    gather (the pool itself stays quantized; only the per-row view is
    widened, to f32, and XLA fuses it into the einsums).

    ``impl`` mirrors `attention`'s dispatch seam. "reference" is the
    pure-lax lowering above; "flash" routes to the Pallas/Mosaic kernel
    in ops/paged_attention_kernel.py that walks the block table
    block-by-block with an online-softmax inner loop — gather + dequant
    + attend fused, no materialized [B, MB*T, KV, D] view (off-TPU the
    kernel runs in interpret mode, which is how it is unit-tested
    against this reference). "auto" resolves to "flash" on TPU and
    "reference" elsewhere, same policy as `attention` — except while a
    program is traced for a multi-device mesh (`spmd_mesh_scope`, which
    the tp engine announces): GSPMD cannot partition a Mosaic kernel and
    the paged kernel has no shard_map form, so "auto" stays on the
    pure-lax path there."""
    if impl not in ("auto", "flash", "reference"):
        raise ValueError(f"impl must be auto|flash|reference, got {impl!r}")
    B, S, H, D = q.shape
    T = k_pool.shape[2]
    KV = pool_kv_heads(k_pool, q)
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if impl == "auto":
        mesh = _SPMD_MESH.get()
        impl = "flash" if own_kv is None \
            and jax.default_backend() == "tpu" and (
                mesh is None or mesh.size == 1) else "reference"
    if impl == "flash":
        from ray_tpu.ops.paged_attention_kernel import paged_attention_kernel

        if own_kv is not None:
            raise ValueError("the kernel reads pages only: own_kv needs "
                             "the reference path")

        return paged_attention_kernel(
            q, k_pool, v_pool, block_tables, q_slots, layer=layer,
            kv_valid_len=kv_valid_len, sm_scale=sm_scale,
            k_scale=k_scale, v_scale=v_scale, window=window)
    # Gather the per-row dense view straight out of the whole pool:
    # [B, MB, T, KV*D] -> [B, MB*T, KV, D] (logical slot p*T + t of row
    # b is block_tables[b, p] slot t, so the reshape restores contiguous
    # slot order per row; the lane axis splits head-major).
    with jax.named_scope(sn.KV_GATHER):
        MB = block_tables.shape[1]
        k = k_pool[layer, block_tables].reshape(B, MB, T, KV, D)
        v = v_pool[layer, block_tables].reshape(B, MB, T, KV, D)
        if k_scale is not None:
            # dequant-in-gather, in f32
            k = k.astype(jnp.float32) \
                * k_scale[layer, block_tables][:, :, None, :, None]
            v = v.astype(jnp.float32) \
                * v_scale[layer, block_tables][:, :, None, :, None]
        span = MB * T
        k = k.reshape(B, span, KV, D)
        v = v.reshape(B, span, KV, D)
        if own_kv is not None:
            # filler queries (slot -1) lay nothing over the view
            at = (jnp.arange(B)[:, None],
                  jnp.where(q_slots >= 0, q_slots, span))
            k = k.at[at].set(own_kv[0].astype(k.dtype), mode="drop")
            v = v.at[at].set(own_kv[1].astype(v.dtype), mode="drop")
        # -- lockstep with generate._cached_attention from here on --
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)                 # [B, span, H, D]
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (sm_scale if sm_scale is not None else D ** -0.5)
    slots = jnp.arange(span)
    mask = (slots[None, None, None, :] <= q_slots[:, None, :, None]) \
        & (slots[None, None, None, :] < kv_valid_len)
    if window is not None:
        mask = mask & (slots[None, None, None, :]
                       > q_slots[:, None, :, None] - window)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def attention(q: jax.Array,
              k: jax.Array,
              v: jax.Array,
              *,
              causal: bool = True,
              sm_scale: Optional[float] = None,
              impl: str = "auto",
              block_q: Optional[int] = None,
              block_k: Optional[int] = None) -> jax.Array:
    """Dispatch: impl in {'auto', 'flash', 'reference'}. block_q/block_k
    override the flash kernel's tile sizes (None = kernel default);
    ignored on the reference path."""
    for nm, b in (("block_q", block_q), ("block_k", block_k)):
        if b is not None and b <= 0:
            raise ValueError(f"{nm} must be positive, got {b}")
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    if impl == "flash":
        from ray_tpu.ops.flash_attention import flash_attention

        if sm_scale is None:
            sm_scale = q.shape[-1] ** -0.5
        # only forward explicit overrides; defaulting stays with the
        # kernel's own signature
        blocks = {k_: v_ for k_, v_ in
                  (("block_q", block_q), ("block_k", block_k))
                  if v_ is not None}
        mesh = _SPMD_MESH.get()
        if mesh is not None and not _in_manual_region():
            spec = _flash_spmd_spec(q.shape, k.shape, mesh)
            if spec is not None:
                from jax import shard_map

                fn = functools.partial(flash_attention, causal=causal,
                                       sm_scale=sm_scale, **blocks)
                return shard_map(fn, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False)(q, k, v)
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               **blocks)
    return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)

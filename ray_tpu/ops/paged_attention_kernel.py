"""Fused block-walking paged decode-attention kernel (Pallas/Mosaic).

The pure-lax reference in `ops/attention.py:paged_attention` gathers a
per-row dense view ``[B, MB*T, KV, D]`` and lets XLA fuse it — correct,
but the gathered view is materialization pressure exactly proportional
to the block-table span. This kernel instead walks each row's block
table block-by-block in VMEM with a flash-style online-softmax inner
loop: the physical page for grid step ``j`` is resolved through a
scalar-prefetched block table inside the BlockSpec index map, so page
gather + (optional int8/fp8) dequantization + attend are fused and no
dense view ever exists.

Grid is ``(B, MB)`` with the block-walk axis innermost and marked
"arbitrary" (the online-softmax recurrence is sequential). Each step
fetches ONE whole page over all KV heads — the pool is viewed as
``[NB, T, KV*D]`` (a free reshape), so the block's last two dims are the
array's own and every head is a lane-aligned ``[T, D]`` slice of it —
and the KV heads are walked inside the body; a page is read once per
row whatever the GQA group size. Queries arrive regrouped as
``[B, KV, S*G, D]`` so one KV head's query group is one matmul operand.
Scratch is the usual flash trio per KV head — f32 accumulator
``[KV, S*G, D]`` plus running max/sum ``[KV, S*G, 1]`` — carried across
the walk and finalized on the last block. Masked positions follow the
reference exactly: causal ``slot <= q_slot`` plus the ``kv_valid_len``
cap, fully-masked rows produce 0.

Quantized pages are widened to the query dtype (exact for int8 and
fp8-e4m3 into bf16 or f32) and the per-block per-head scale multiplies
the ``[S*G, T]`` scores and the ``[S*G, D]`` page contribution instead
of the ``[T, D]`` page: same product, rounded in a different order than
the reference's dequantize-then-matmul. The scales of a row's MB pages
are gathered outside the kernel into one ``[1, MB*KV]`` SMEM block per
row and read as scalars — Mosaic has no broadcast of a ``(1, 1)`` vector
over both sublanes and lanes, and a ``(1, 1)`` block of the ``[NB, KV]``
slab is not a legal block.

The layout compiles for a described ``v5e:2x2`` device at Llama-3-8B
widths (tests/test_tpu_compile.py) and runs on the chip in
``chip_smoke.py``'s paged variants; the value sweeps against the
pure-lax reference run in interpret mode
(tests/test_engine_kv_quant.py). On a TPU `impl="auto"` routes here;
elsewhere it stays on the reference path and this kernel runs only when
asked for explicitly (then in interpret mode). What it has NOT had yet
is a timing: the walk still visits all ``MB`` table entries whatever
the row's length, and a ``T=16`` page is a small DMA per grid step
(ROADMAP S2).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scope_names as sn

_NEG_INF = -1e30

__all__ = ["paged_attention_kernel"]


def _kernel(bt_ref, lim_ref, *refs, sm_scale, n_kv, head_dim, has_scale):
    """One (b, j) grid step: fold page j of row b into every KV head's
    online softmax. Scalar-prefetch refs: ``bt_ref`` [B*MB] flat block
    table (also consumed by the BlockSpec index maps), ``lim_ref`` [1]
    the valid-length cap."""
    if has_scale:
        (qs_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref,
         m_ref, l_ref) = refs
    else:
        qs_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    rows = q_ref.shape[2]                               # S * G
    t = k_ref.shape[1]
    slot = j * t + jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
    mask = (slot <= qs_ref[0]) & (slot < lim_ref[0])    # qs: [S*G, 1]
    for kv in range(n_kv):
        q = q_ref[0, kv]                                # [S*G, D]
        lanes = slice(kv * head_dim, (kv + 1) * head_dim)
        k = k_ref[0, :, lanes].astype(q.dtype)          # [T, D]
        v = v_ref[0, :, lanes].astype(q.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if has_scale:
            s = s * ks_ref[0, 0, j * n_kv + kv]
        s = s * sm_scale
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[kv]                              # [S*G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # explicit zero (not just exp underflow): a fully-masked block
        # with m still at -inf would otherwise yield exp(0) == 1 per
        # position
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[kv] = l_ref[kv] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if has_scale:
            pv = pv * vs_ref[0, 0, j * n_kv + kv]
        acc_ref[kv] = acc_ref[kv] * alpha + pv
        m_ref[kv] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        row_live = m_ref[...] > _NEG_INF / 2
        o_ref[0] = jnp.where(row_live, acc_ref[...] / l,
                             0.0).astype(o_ref.dtype)


def paged_attention_kernel(q: jax.Array,
                           k_pages: jax.Array,
                           v_pages: jax.Array,
                           block_tables: jax.Array,
                           q_slots: jax.Array,
                           *,
                           kv_valid_len,
                           sm_scale: Optional[float] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Same contract as `ops.attention.paged_attention` (reference
    impl), fused. ``interpret=None`` resolves to True off-TPU."""
    B, S, H, D = q.shape
    NB, T, KV, _ = k_pages.shape
    MB = block_tables.shape[1]
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    g = H // KV
    rows = S * g
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    has_scale = k_scale is not None

    # query row r = s*g + i of KV head kv is query s, head kv*g + i
    qg = q.reshape(B, S, KV, g, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, rows, D)
    qs = jnp.repeat(q_slots.astype(jnp.int32), g, axis=1)[..., None]
    bt = block_tables.astype(jnp.int32).reshape(-1)
    lim = jnp.asarray(kv_valid_len, jnp.int32).reshape(1)

    def row_map(b, j, *_):
        return (b, 0, 0)

    def q_map(b, j, *_):
        return (b, 0, 0, 0)

    def page_map(b, j, bt_ref, *_):
        return (bt_ref[b * MB + j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, rows, 1), row_map),           # q slots
        pl.BlockSpec((1, KV, rows, D), q_map),         # q
        pl.BlockSpec((1, T, KV * D), page_map),        # k page
        pl.BlockSpec((1, T, KV * D), page_map),        # v page
    ]
    with jax.named_scope(sn.KV_GATHER):
        args = [qs, qg, k_pages.reshape(NB, T, KV * D),
                v_pages.reshape(NB, T, KV * D)]
        if has_scale:
            in_specs += [pl.BlockSpec((1, 1, MB * KV), row_map,
                                      memory_space=pltpu.SMEM)] * 2
            args += [s.astype(jnp.float32)[block_tables]
                     .reshape(B, 1, MB * KV) for s in (k_scale, v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, rows, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((KV, rows, D), jnp.float32),
            pltpu.VMEM((KV, rows, 1), jnp.float32),
            pltpu.VMEM((KV, rows, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, sm_scale=sm_scale if sm_scale is not None else D ** -0.5,
        n_kv=KV, head_dim=D, has_scale=has_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=sn.PAGED_KERNEL,
    )(bt, lim, *args)
    return out.reshape(B, KV, S, g, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, D)

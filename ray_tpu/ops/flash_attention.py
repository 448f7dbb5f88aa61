"""Pallas TPU flash attention (forward AND backward kernels).

Online-softmax tiling keeps the working set in VMEM and the score matmuls
on the MXU; the kv-block grid axis iterates fastest so the (m, l, acc)
scratch accumulators persist across kv blocks for a fixed q block.
Backward is flash-style recompute in Pallas under `jax.custom_vjp`:
_bwd_dq_kernel (kv innermost) and _bwd_dkv_kernel (q innermost) re-derive
p from the saved row logsumexp.

Semantics match `ray_tpu.ops.attention.mha_reference` exactly, including
the kv-prefix causal offset when Sq != Sk (decode) and GQA. Sequence
lengths that don't divide the block size are zero-padded; padded kv
columns are masked by global index, padded q rows are sliced off.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scope_names as sn

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _attn_mask(i, j, block_q, block_k, q_offset, sk_orig, causal):
    """Single source of truth for the fwd AND bwd score mask (they must
    agree exactly or the backward's recomputed softmax diverges)."""
    qi = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    ki = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = ki < sk_orig  # zero-padded kv columns
    if causal:
        mask = mask & (qi >= ki)
    return mask


def _block_contributes(i, j, block_q, block_k, q_offset, causal):
    """Causal block skip: kv block j contributes iff its first kv index
    <= the global position of q block i's last row."""
    if not causal:
        return True
    return j * block_k <= q_offset + i * block_q + block_q - 1


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                q_offset: int, sk_orig: int):
    """q_offset = sk_orig - sq_orig (kv-prefix shift for decode);
    sk_orig masks zero-padded kv columns."""
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block (fastest)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    should_compute = _block_contributes(i, j, block_q, block_k, q_offset,
                                        causal)

    @pl.when(should_compute)
    def _body():
        # Matmul inputs keep their storage dtype: bf16 activations hit
        # the MXU's native bf16xbf16->f32 path (upcasting to f32 first
        # would force multi-pass f32 matmuls at a fraction of peak);
        # softmax statistics stay f32 via preferred_element_type.
        q = q_ref[0, 0]  # [bq, d]
        k = k_ref[0, 0]  # [bk, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        mask = _attn_mask(i, j, block_q, block_k, q_offset, sk_orig,
                          causal)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:]                      # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        # l == 0 for zero-padded q rows (sliced off by the caller).
        # m == -inf marks FULLY-MASKED rows (decode with Sq > Sk): they
        # attend to nothing and must output exactly zero — without this,
        # p = exp(-inf - -inf) = 1 leaks uniform weights into acc.
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        row_live = m_ref[:] > _NEG_INF / 2
        o_ref[0, 0] = jnp.where(row_live, acc_ref[:] / l,
                                0.0).astype(o_ref.dtype)


def _fwd_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                    l_ref, **kw):
    """Forward that also writes the row logsumexp (for the Pallas
    backward): lse = m + log(l)."""
    _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, **kw)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == nk - 1)
    def _write_lse():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        lse_ref[0, 0] = m_ref[:] + jnp.log(l)  # [bq, 1]


def _pad_seq(x, block):
    s = x.shape[2]
    pad = (-s) % block
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               with_lse=False):
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    g = h // hkv
    block_q = min(block_q, max(sq, 1))
    block_k = min(block_k, max(sk, 1))
    qp, kp, vp = _pad_seq(q, block_q), _pad_seq(k, block_k), _pad_seq(v,
                                                                      block_k)
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    grid = (b, h, sq_p // block_q, sk_p // block_k)

    kernel_fn = _fwd_kernel_lse if with_lse else _fwd_kernel
    kernel = functools.partial(
        kernel_fn, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k,
        q_offset=sk - sq, sk_orig=sk)
    out_specs = pl.BlockSpec((1, 1, block_q, d),
                             lambda b_, h_, i, j: (b_, h_, i, 0))
    out_shape = jax.ShapeDtypeStruct(qp.shape, q.dtype)
    if with_lse:
        # [B,H,Sq,1] keeps the last-two block dims TPU-tileable
        # ((block_q, 1) with 1 == full trailing dim).
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, block_q, 1),
                                  lambda b_, h_, i, j: (b_, h_, i, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b, h, sq_p, 1), jnp.float32)]
    result = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j, g=g: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j, g=g: (b_, h_ // g, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=sn.FLASH_FWD,
    )(qp, kp, vp)
    if with_lse:
        out, lse = result
        out = out[:, :, :sq] if sq_p != sq else out
        lse = lse[:, :, :sq] if sq_p != sq else lse
        return out, lse
    out = result
    return out[:, :, :sq] if sq_p != sq else out


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, sm_scale, causal, block_q,
                   block_k, q_offset, sk_orig):
    """dq for one q block, accumulated over kv blocks (innermost axis).
    ds = p * (dO v^T - delta) * scale; dq += ds k."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    should = _block_contributes(i, j, block_q, block_k, q_offset, causal)

    @pl.when(should)
    def _body():
        # Storage-dtype matmul inputs (native bf16 MXU path; f32 stats).
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                     # [bq, 1]
        delta = delta_ref[0, 0]                 # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        mask = _attn_mask(i, j, block_q, block_k, q_offset, sk_orig,
                          causal)
        s = jnp.where(mask, s, _NEG_INF)
        # Fully-masked rows (decode with Sq > Sk, or padded rows) have
        # lse ~ -inf: their softmax is empty, p must be 0 — not
        # exp(-inf - -inf).
        p = jnp.where(lse <= _NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _fin():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                    block_q, block_k, q_offset, sk_orig):
    """dk/dv for one kv block (per q head — GQA groups reduced outside),
    accumulated over q blocks (innermost axis)."""
    j = pl.program_id(2)  # kv block
    i = pl.program_id(3)  # q block (innermost)
    nq = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    should = _block_contributes(i, j, block_q, block_k, q_offset, causal)

    @pl.when(should)
    def _body():
        # Storage-dtype matmul inputs (native bf16 MXU path; f32 stats).
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                     # [bq, 1]
        delta = delta_ref[0, 0]                 # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        mask = _attn_mask(i, j, block_q, block_k, q_offset, sk_orig,
                          causal)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.where(lse <= _NEG_INF / 2, 0.0,
                      jnp.exp(s - lse))         # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale        # [bq, bk]
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _fin():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, sm_scale, causal, block_q, block_k,
               interpret, delta=None, grad_dtype=None):
    """grad_dtype overrides the dq/dk/dv output dtype (ring attention
    accumulates per-shard partials in f32); delta may be precomputed by
    callers that invoke this once per kv shard."""
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    grp = h // hkv
    block_q = min(block_q, max(sq, 1))
    block_k = min(block_k, max(sk, 1))
    dq_dtype = grad_dtype or q.dtype
    dk_dtype = grad_dtype or k.dtype
    dv_dtype = grad_dtype or v.dtype

    if delta is None:
        # delta = rowsum(dO * O) — cheap, fused by XLA. [B,H,Sq,1] layout
        # keeps the Pallas row blocks TPU-tileable.
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)  # [B,H,Sq,1]

    qp = _pad_seq(q, block_q)
    gp = _pad_seq(g, block_q)
    kp, vp = _pad_seq(k, block_k), _pad_seq(v, block_k)
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    pad_q = sq_p - sq
    if pad_q:
        # Padded q rows get lse=0 and delta=0. Their p is NOT zero (for
        # unmasked columns p = exp(s-0)), but every contribution is
        # multiplied by do=0 (gp zero-padded) and delta=0, so dk/dv/dq
        # stay exact — do not stop zero-padding gp.
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q), (0, 0)))

    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, q_offset=sk - sq, sk_orig=sk)

    # --- dq: grid (b, h, nq, nk), kv innermost (axis2=q, axis3=kv) ---
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(b, h, sq_p // block_q, sk_p // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j, g_=grp:
                         (b_, h_ // g_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j, g_=grp:
                         (b_, h_ // g_, j, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, dq_dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=sn.FLASH_BWD_DQ,
    )(qp, kp, vp, gp, lse, delta)

    # --- dk/dv: grid (b, h, nk, nq), q innermost (axis2=kv, axis3=q);
    # per-q-head then group reduce (GQA) ---
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(b, h, sk_p // block_k, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, j, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j, i, g_=grp:
                         (b_, h_ // g_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j, i, g_=grp:
                         (b_, h_ // g_, j, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, j, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, j, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, j, i: (b_, h_, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j, i: (b_, h_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk_p, d), dk_dtype),
            jax.ShapeDtypeStruct((b, h, sk_p, d), dv_dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=sn.FLASH_BWD_DKV,
    )(qp, kp, vp, gp, lse, delta)

    dq = dq[:, :, :sq] if sq_p != sq else dq
    dk_h = dk_h[:, :, :sk] if sk_p != sk else dk_h
    dv_h = dv_h[:, :, :sk] if sk_p != sk else dv_h
    if grp > 1:
        dk = dk_h.reshape(b, hkv, grp, sk, d).sum(axis=2).astype(dk_dtype)
        dv = dv_h.reshape(b, hkv, grp, sk, d).sum(axis=2).astype(dv_dtype)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)


# What a rematerialised layer keeps of this kernel: its output and the
# compact [B, H, Sq] row logsumexp, tagged in `_flash_vjp_fwd`. A
# `jax.checkpoint` whose policy saves these two names never re-runs the
# forward kernel in its backward pass (models/llama.py:_layer_checkpoint).
# Outside `jax.checkpoint` a name lowers to nothing.
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                          interpret, with_lse=True)
    # The TAGGED `out` is the primal output too: a recompute of what
    # consumes the attention output then reads the saved array instead of
    # asking the kernel for it. The kernel's [B, H, Sq, 1] statistic is
    # kept without its trailing axis, which a tiled layout would pad
    # 128-fold once stacked over the layers.
    out_name, lse_name = FLASH_RESIDUAL_NAMES
    out = checkpoint_name(out, out_name)
    lse = checkpoint_name(lse[..., 0], lse_name)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, interpret,
                   residuals, g):
    q, k, v, out, lse = residuals
    return _flash_bwd(q, k, v, out, lse[..., None], g, sm_scale, causal,
                      block_q, block_k, interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q: jax.Array,
                    k: jax.Array,
                    v: jax.Array,
                    *,
                    causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: [B,H,Sq,D]; k,v: [B,Hkv,Sk,D] (GQA when Hkv < H). -> [B,H,Sq,D]."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash(q, k, v, float(sm_scale), bool(causal),
                  int(block_q), int(block_k), bool(interpret))

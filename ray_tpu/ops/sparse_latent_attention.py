"""Attention of a prefill chunk over a latent cache under a per-query
selection (Pallas/Mosaic): the absorbed form of latent attention, dense
over a row's live keys with the indexer's choice as a mask.

A chunk's 512 queries each choose their own 2,048 slots. Gathering them
costs a copy of ``queries x 2,048`` latent rows a layer (1.3 GB a 512-token
chunk; 45 % of a prefill program's time on a v5e, PERF.md PR 33), where
the chunk's queries between them read a row's live keys, each key once a
query tile. So the chunk attends its row's keys DENSELY, flash-style, and
the selection comes in as an additive mask: scores of slots a query did
not choose (or may not see) never reach its softmax. The MXU does up to
``live / 2,048`` times the selected form's operations; the keys are read
where they lie, once a tile, and no score leaves VMEM.

    q     [B, H, S, W]     absorbed queries ``[q' | q_rope | 0]``, laid
                           out like a latent row (W lanes, 640)
    lat   [B, span, W]     the row's latent rows in slot order (the block
                           table's pages side by side)
    bias  [B, S, span] f32 0 where query s attends slot t, <= -1e29 where
                           it does not (not chosen, not yet written, past
                           the row)
    n_live [B, S / tq]     key tiles each query tile has to visit: tiles
                           past it are skipped (their bias is all mask)

Returns ``sum_t p_t c_t`` [B, H, S, rc]: the softmax-weighted sum of the
first ``rc`` lanes (the latent; the rotary key is not a value). A query
with nothing to attend (bucket filler) gets 0.

Grid ``(B, S/tq, H/hb, span/tk)``, the key axis innermost: a step folds
one key tile [tk, W] into the online softmax of ``hb`` heads x ``tq``
queries, a head at a time (``[tq, W] x [W, tk]``, then ``[tq, tk] x
[tk, rc]``). The key tile's index is clamped to the last live one, so
dead steps fetch nothing new.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scope_names as sn

_NEG = -1e30
_MASKED = -1e29           # a bias at or under this is "does not attend"
_VMEM_LIMIT_BYTES = 48 << 20

__all__ = ["sparse_latent_attention", "sparse_latent_attention_reference",
           "sparse_latent_decode"]

_lax = jax.lax


def _row_reduce(reduce, x):
    return _lax.broadcast_in_dim(reduce(x, (1,)), (x.shape[0], 1), (0,))


def _start(acc_ref, m_ref, l_ref):
    acc_ref[...] = _lax.full(acc_ref.shape, 0.0, jnp.float32)
    m_ref[...] = _lax.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = _lax.full(l_ref.shape, 0.0, jnp.float32)


def _fold(q, k, seen, i, acc_ref, m_ref, l_ref, rc, sm_scale):
    """One key tile ``k`` [tk, W] into the online softmax of the queries
    ``q`` [rows, W] whose state is slot ``i`` of the scratch (`...`: the
    scratch whole); ``seen`` [rows, tk] says which keys each query
    attends."""
    v = k[:, :rc]
    s = _lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    s = _lax.select(seen, _lax.mul(s, np.float32(sm_scale)),
                    _lax.full_like(s, _NEG))
    m_prev = m_ref[i]
    m_new = _lax.max(m_prev, _row_reduce(_lax.reduce_max, s))
    # explicit zero: a tile all masked with m still at -1e30 would
    # otherwise give exp(0) = 1 a slot
    p = _lax.select(seen, _lax.exp(_lax.sub(s, m_new)),
                    _lax.full_like(s, 0.0))
    alpha = _lax.exp(_lax.sub(m_prev, m_new))
    l_ref[i] = _lax.add(_lax.mul(l_ref[i], alpha),
                        _row_reduce(_lax.reduce_sum, p))
    pv = _lax.dot_general(_lax.convert_element_type(p, v.dtype), v,
                          (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    acc_ref[i] = _lax.add(_lax.mul(acc_ref[i], alpha), pv)
    m_ref[i] = m_new


def _finish(o_ref, acc_ref, l_ref):
    l = l_ref[...]
    l = _lax.select(_lax.eq(l, np.float32(0.0)), _lax.full_like(l, 1.0), l)
    o_ref[0] = _lax.convert_element_type(_lax.div(acc_ref[...], l),
                                         o_ref.dtype)


def _kernel(nl_ref, q_ref, k_ref, b_ref, o_ref, acc_ref, m_ref, l_ref, *,
            hb, rc, sm_scale, n_qt, n_kb):
    b, qt, kb = pl.program_id(0), pl.program_id(1), pl.program_id(3)

    @pl.when(kb == 0)
    def _():
        _start(acc_ref, m_ref, l_ref)

    @pl.when(kb < nl_ref[b * n_qt + qt])
    def _():
        k = k_ref[0]                                     # [tk, W]
        seen = _lax.gt(b_ref[0], np.float32(_MASKED))    # [tq, tk]
        for i in range(hb):
            _fold(q_ref[0, i], k, seen, i, acc_ref, m_ref, l_ref, rc,
                  sm_scale)

    @pl.when(kb == n_kb - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


def _decode_kernel(bt_ref, nl_ref, li_ref, q_ref, k_ref, b_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, rc, sm_scale, n_kb):
    """Grid step (row b, page kb): fold one PAGE of the latent plane, read
    where it lies through the block table, into the row's one query (all
    heads: the heads are the kernel's rows, and share the row's mask)."""
    del bt_ref, li_ref                     # the index maps read them
    b, kb = pl.program_id(0), pl.program_id(1)

    @pl.when(kb == 0)
    def _():
        _start(acc_ref, m_ref, l_ref)

    @pl.when(kb < nl_ref[b])
    def _():
        q = q_ref[0]                                     # [H, W]
        k = k_ref[0, 0]                                  # [T, W]
        bias = _lax.broadcast_in_dim(b_ref[0], (q.shape[0], k.shape[0]),
                                     (0, 1))
        _fold(q, k, _lax.gt(bias, np.float32(_MASKED)), ..., acc_ref,
              m_ref, l_ref, rc, sm_scale)

    @pl.when(kb == n_kb - 1)
    def _():
        _finish(o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("rc", "sm_scale", "interpret"))
def sparse_latent_decode(q, pool, block_tables, bias, q_slots, layer, *,
                         rc: int, sm_scale: float, interpret: bool):
    """A decode token's attention under its selection, straight from the
    paged latent plane: ``q`` [B, H, W] (one query a row), ``pool``
    [L, NB, T, W] whole, ``block_tables`` [B, MB], ``bias`` [B, span] f32
    (0 on the chosen slots, <= -1e29 elsewhere), ``q_slots`` [B] the
    query's slot (-1: a row that asks nothing), ``layer`` the plane's
    layer (traced). Returns [B, H, rc]. Nothing is gathered: a grid step
    reads one page where it lies, pages past a row's last slot are
    skipped, and a page none of whose slots was chosen costs its read."""
    B, H, W = q.shape
    T, MB = pool.shape[2], block_tables.shape[1]
    n_live = jnp.clip(q_slots.astype(jnp.int32) // T + 1, 0, MB)

    def last_live(b, kb, nl):
        return jnp.minimum(kb, jnp.maximum(nl[b] - 1, 0))

    def q_map(b, kb, bt, nl, li):
        return (b, 0, 0)

    def k_map(b, kb, bt, nl, li):
        return (li[0], bt[b * MB + last_live(b, kb, nl)], 0, 0)

    def b_map(b, kb, bt, nl, li):
        return (b, 0, last_live(b, kb, nl))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, MB),
        in_specs=[pl.BlockSpec((1, H, W), q_map),
                  pl.BlockSpec((1, 1, T, W), k_map),
                  pl.BlockSpec((1, 1, T), b_map)],
        out_specs=pl.BlockSpec((1, H, rc), q_map),
        scratch_shapes=[pltpu.VMEM((H, rc), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)])
    kernel = functools.partial(_decode_kernel, rc=rc, sm_scale=sm_scale,
                               n_kb=MB)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rc), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=sn.SPARSE_LATENT_DECODE_KERNEL,
    )(block_tables.reshape(-1).astype(jnp.int32), n_live,
      jnp.asarray(layer, jnp.int32).reshape(1), q, pool,
      bias.reshape(B, 1, -1))


def _tile(n: int, want: int) -> int:
    """The largest divisor of n that is <= want and a multiple of 8 (n
    itself when n <= want)."""
    if n <= want:
        return n
    for t in range(want, 7, -8):
        if n % t == 0:
            return t
    return n


@functools.partial(jax.jit, static_argnames=("rc", "sm_scale", "interpret",
                                             "tq", "tk", "hb"))
def _call(q, lat, bias, n_live, *, rc, sm_scale, interpret, tq, tk, hb):
    B, H, S, W = q.shape
    span = lat.shape[1]
    n_qt, n_kb = S // tq, span // tk

    def q_map(b, qt, h, kb, nl):
        return (b, h, qt, 0)

    def k_map(b, qt, h, kb, nl):
        return (b, jnp.minimum(kb, jnp.maximum(nl[b * n_qt + qt] - 1, 0)),
                0)

    def b_map(b, qt, h, kb, nl):
        return (b, qt,
                jnp.minimum(kb, jnp.maximum(nl[b * n_qt + qt] - 1, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_qt, H // hb, n_kb),
        in_specs=[pl.BlockSpec((1, hb, tq, W), q_map),
                  pl.BlockSpec((1, tk, W), k_map),
                  pl.BlockSpec((1, tq, tk), b_map)],
        out_specs=pl.BlockSpec((1, hb, tq, rc), q_map),
        scratch_shapes=[pltpu.VMEM((hb, tq, rc), jnp.float32),
                        pltpu.VMEM((hb, tq, 1), jnp.float32),
                        pltpu.VMEM((hb, tq, 1), jnp.float32)])
    kernel = functools.partial(_kernel, hb=hb, rc=rc, sm_scale=sm_scale,
                               n_qt=n_qt, n_kb=n_kb)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, rc), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret, name=sn.SPARSE_LATENT_KERNEL,
    )(n_live.reshape(-1).astype(jnp.int32), q, lat, bias)


def sparse_latent_attention(q, lat, bias, q_slots, *, rc: int,
                            sm_scale: float,
                            interpret: Optional[bool] = None,
                            tq: int = 128, tk: int = 1024, hb: int = 8):
    """See the module docstring. ``q_slots`` [B, S] (-1: filler) says how
    far each query tile has to walk: key tiles wholly past a tile's last
    slot are skipped."""
    B, H, S, W = q.shape
    span = lat.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tq, tk, hb = _tile(S, tq), _tile(span, tk), _tile(H, hb)
    if S % tq or span % tk or H % hb:
        raise ValueError("sparse_latent_attention: tiles must divide "
                         f"queries {S}, span {span} and heads {H}")
    last = jnp.max(q_slots.reshape(B, S // tq, tq).astype(jnp.int32), axis=2)
    n_live = jnp.clip(last // tk + 1, 0, span // tk)
    return _call(q, lat, bias, n_live, rc=rc, sm_scale=float(sm_scale),
                 interpret=bool(interpret), tq=tq, tk=tk, hb=hb)


def sparse_latent_attention_reference(q, lat, bias, *, rc: int,
                                      sm_scale: float):
    """The same numbers in plain `jax.numpy` (off the chip, and what the
    kernel is tested against)."""
    s = jnp.einsum("bhsw,btw->bhst", q, lat,
                   preferred_element_type=jnp.float32) * sm_scale
    seen = (bias > _MASKED)[:, None]
    s = jnp.where(seen, s, _NEG)
    p = jnp.where(seen, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    denom = p.sum(-1, keepdims=True)
    p = (p / jnp.where(denom == 0.0, 1.0, denom)).astype(lat.dtype)
    return jnp.einsum("bhst,btc->bhsc", p, lat[..., :rc],
                      preferred_element_type=jnp.float32).astype(q.dtype)

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

A DECODE token (`sparse_latent_decode`: one query a row, all its heads
the kernel's rows under the row's one mask) reads nothing side by side:
the latent plane ``[L, NB, T, W]`` stays in HBM and the grid is the ROWS.
A row's walk over its pages is an in-kernel loop whose trip count is the
row's LIVE pages (`pages_walked`: the page of the query's slot and every
page before it; the engine's counters ask the same function), in steps
of `pages_per_step` pages: each page ``pool[layer, bt[b, p]]`` one
``make_async_copy`` into one slot of a double buffer, the next step's
copies started before this step's are waited for, and a landed step
folded into the row's online softmax as ONE key tile of ``P * T`` keys
(the softmax's state is read and written once a step, not once a page,
and the two matmuls are long enough to fill the MXU: 0.46 us a page
against 0.90 a page at a time, 0.40 of it the page's HBM time; PERF.md
PR 55). A walk that ends in a step's first half folds that half alone.
The rows of a call are ONE such pipeline, in order (the grid axis is
``"arbitrary"``): a row's last step starts step 0 of the next row that
walks, so no row but a call's first stands still for its first copy, and
a step's buffer slot is its place in the call's walk, not the row's. A
row that asks nothing (slot -1) makes zero trips and writes zeros; a
table entry past a row's last page costs nothing, where it was a grid
step of its own until PR 55 (72 a row in one serving cell, 132 in
another, a quarter and a tenth of them live). The row's mask arrives
whole, ``[1, MB * T]`` float32 a row, as the grid's own block.
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
# A step of a decode row's walk: the bytes of pages one slot of its double
# buffer holds
_STEP_BYTES = 2560 << 10

__all__ = ["sparse_latent_attention", "sparse_latent_attention_reference",
           "sparse_latent_decode", "pages_walked", "pages_per_step"]

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


def pages_walked(q_slots, block_tokens: int, max_blocks: int):
    """How many leading table entries a decode token's attention walks,
    from its slot (-1: a row that asks nothing): the page that holds the
    slot and every page before it. Plain arithmetic: the engine's
    counters ask with numpy arrays, the kernel's caller with traced
    ones."""
    return (q_slots // block_tokens + 1).clip(0, max_blocks)


def pages_per_step(block_tokens: int, lanes: int, itemsize: int,
                   max_blocks: int) -> int:
    """Pages a step of a row's walk fetches, and folds as ONE key tile: as
    many as fill `_STEP_BYTES` of one slot of the double buffer (8 pages
    of 256 slots x 640 bf16 lanes, 4 of the window layers' 1,152) and
    leave no step hanging over the table's end: the table's last step is
    whole, or a half that the half fold covers (132 entries by 8: 4)."""
    fit = max(1, _STEP_BYTES // (block_tokens * lanes * itemsize))
    return max(p for p in range(1, min(fit, max_blocks) + 1)
               if max_blocks % p in (0, p // 2))


def _decode_kernel(bt_ref, n_ref, g0_ref, nxt_ref, li_ref, q_ref, b_ref,
                   pool_hbm, o_ref, k_buf, sem, acc_ref, m_ref, l_ref, *,
                   rc, sm_scale, pages_per_step):
    """Grid step (row b): the row's one query (all heads: the heads are
    the kernel's rows, and share the row's mask) over the row's LIVE
    pages, read where they lie in the latent plane through the block
    table. Scalar-prefetch refs: ``bt_ref`` [B * MB] the flat table,
    ``n_ref`` [B] each row's live pages (`pages_walked`), ``g0_ref`` [B]
    the walk steps of the rows before it (a step's buffer slot is its
    place in the CALL's walk, odd or even), ``nxt_ref`` [B] the next row
    that walks at all (B: none), ``li_ref`` [1] the plane's layer."""
    P = pages_per_step
    T = k_buf.shape[1] // P
    MB = b_ref.shape[2] // T
    H = q_ref.shape[1]
    i32 = np.int32
    b = pl.program_id(0)
    layer = li_ref[0]
    n, g0 = n_ref[b], g0_ref[b]
    n_steps = _lax.div(_lax.add(n, i32(P - 1)), i32(P))
    # the row whose first step this row's last step fetches
    n_rows = pl.num_programs(0)
    nxt = _lax.min(nxt_ref[b], _lax.sub(n_rows, i32(1)))
    next_n = _lax.select(_lax.lt(nxt_ref[b], n_rows), n_ref[nxt], i32(0))

    def each_page(row, step, n, slot, act):
        """``act`` on the copy of every page of ``row``'s step ``step``
        that lies inside its walk of ``n`` pages; returns how many."""
        first = _lax.mul(step, i32(P))
        entry = _lax.add(_lax.mul(row, i32(MB)), first)

        def one(p, carry):
            blk = bt_ref[_lax.add(entry, p)]
            at = pl.ds(pl.multiple_of(_lax.mul(p, i32(T)), T), T)
            act(pltpu.make_async_copy(pool_hbm.at[layer, blk],
                                      k_buf.at[slot, at], sem.at[slot]))
            return carry
        live = _lax.clamp(i32(0), _lax.sub(n, first), i32(P))
        _lax.fori_loop(0, live, one, 0)
        return live

    def start(row, step, n, slot):
        each_page(row, step, n, slot, lambda copy: copy.start())

    _start(acc_ref, m_ref, l_ref)

    @pl.when(_lax.eq(b, i32(0)))
    def _():
        # a page of a step past the walk is not fetched and what the
        # buffer holds there is masked, but has to be finite
        k_buf[...] = _lax.full(k_buf.shape, 0, k_buf.dtype)

    # step 0 is in flight (the row before started it) unless this is the
    # call's first row that walks
    start(b, i32(0), _lax.select(_lax.eq(g0, i32(0)), n, i32(0)), i32(0))

    def fold_pages(slot, first, pages):
        """The first ``pages`` pages of the step in ``slot`` as ONE key
        tile into the row's softmax; a slot past the walk is masked
        whatever the row's mask says of it."""
        lanes = pages * T
        at = pl.ds(pl.multiple_of(_lax.mul(first, i32(T)), T), lanes)
        walked = _lax.lt(_lax.broadcasted_iota(jnp.int32, (1, lanes), 1),
                         _lax.mul(_lax.sub(n, first), i32(T)))
        bias = _lax.select(walked, b_ref[0, :, at],
                           _lax.full((1, lanes), _NEG, jnp.float32))
        seen = _lax.gt(_lax.broadcast_in_dim(bias, (H, lanes), (0, 1)),
                       np.float32(_MASKED))
        _fold(q_ref[0], k_buf[slot, :lanes, :], seen, ..., acc_ref, m_ref,
              l_ref, rc, sm_scale)

    def step_body(i, carry):
        slot = _lax.rem(_lax.add(g0, i), i32(2))
        ahead = _lax.add(i, i32(1))
        more = _lax.lt(ahead, n_steps)
        # the next step's copies before this step's are waited for: the
        # row's own, or step 0 of the row after it
        start(_lax.select(more, b, nxt), _lax.select(more, ahead, i32(0)),
              _lax.select(more, n, next_n), _lax.sub(i32(1), slot))
        live = each_page(b, i, n, slot, lambda copy: copy.wait())
        first = _lax.mul(i, i32(P))
        # a walk that ends in a step's first half folds that half alone
        # (a row of 4 live pages would else pay for 8)
        half = P // 2

        @pl.when(_lax.gt(live, i32(half)))
        def _():
            fold_pages(slot, first, P)

        if half:
            @pl.when(_lax.le(live, i32(half)))
            def _():
                fold_pages(slot, first, half)
        return carry

    _lax.fori_loop(0, n_steps, step_body, 0)
    _finish(o_ref, acc_ref, l_ref)


def sparse_latent_decode(q, pool, block_tables, bias, q_slots, layer, *,
                         rc: int, sm_scale: float, interpret: bool):
    """A decode token's attention under its selection, straight from the
    paged latent plane: ``q`` [B, H, W] (one query a row), ``pool``
    [L, NB, T, W] whole, ``block_tables`` [B, MB], ``bias`` [B, span] f32
    (0 on the chosen slots, <= -1e29 elsewhere), ``q_slots`` [B] the
    query's slot (-1: a row that asks nothing), ``layer`` the plane's
    layer (traced). Returns [B, H, rc]. Nothing is gathered: a row walks
    the pages up to its slot's where they lie (`pages_walked`), a row
    that asks nothing walks none and gets 0, and a page none of whose
    slots was chosen costs its read."""
    T, W = pool.shape[2:]
    return _decode(q, pool, block_tables, bias, q_slots, layer, rc=rc,
                   sm_scale=float(sm_scale), interpret=bool(interpret),
                   pps=pages_per_step(T, W, pool.dtype.itemsize,
                                      block_tables.shape[1]))


@functools.partial(jax.jit, static_argnames=("rc", "sm_scale", "interpret",
                                             "pps"))
def _decode(q, pool, block_tables, bias, q_slots, layer, *, rc, sm_scale,
            interpret, pps):
    B, H, W = q.shape
    T, MB = pool.shape[2], block_tables.shape[1]
    n = pages_walked(q_slots.astype(jnp.int32), T, MB)
    steps = (n + (pps - 1)) // pps
    # the rows of a call are one pipeline: where a row's steps lie in it,
    # and which row walks next
    walks = jnp.where(steps > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jnp.append(_lax.cummin(walks, reverse=True)[1:], jnp.int32(B))

    def row(b, *_):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(B,),
        in_specs=[pl.BlockSpec((1, H, W), row),                 # the query
                  pl.BlockSpec((1, 1, MB * T), row),            # its mask
                  pl.BlockSpec(memory_space=pl.ANY)],           # the plane
        out_specs=pl.BlockSpec((1, H, rc), row),
        scratch_shapes=[pltpu.VMEM((2, pps * T, W), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((H, rc), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)])
    kernel = functools.partial(_decode_kernel, rc=rc, sm_scale=sm_scale,
                               pages_per_step=pps)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rc), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=sn.SPARSE_LATENT_DECODE_KERNEL,
    )(block_tables.reshape(-1).astype(jnp.int32), n,
      jnp.cumsum(steps) - steps, nxt,
      jnp.asarray(layer, jnp.int32).reshape(1), q,
      bias.reshape(B, 1, MB * T), pool)


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

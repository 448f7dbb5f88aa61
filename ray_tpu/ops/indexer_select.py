"""Sparse attention's selection for the queries of a paged row
(Pallas/Mosaic): the indexer's scores and the exact top-k of them as a
mask, at the cost of the slots a query can SEE.

`mla.select_mask`, the lax form, gathers a row's whole block table out of
the index plane, scores every slot of it and counts its k-th value over
the table's width 32 times: 553 MB of keys copied a layer a decode token
at 64 rows x 132 entries of 256, where the rows' live slots were a tenth
of that, and as much again for every 16 queries of a prefill chunk
(PERF.md PR 53). Here one grid step is one BLOCK of a row's queries (a
decode token; `tq` = 16 queries of a chunk) and does four things:

1. **Walks the row's live index pages and no others**, where they lie in
   the plane ``[L, NB, T, D]`` through the block table. The trip count is
   scalar-prefetched (`pages_walked`: the page of the block's last slot
   and every page before it) and the walk an in-kernel loop of
   ``cdiv(n, pps)`` steps of ``pps`` pages (2,048 keys), each page one
   ``make_async_copy`` into one slot of a double buffer, the next step's
   copies started before this step's are waited for. The grid's steps
   are ONE such pipeline, in order: after its last scoring step a block
   starts step 0 of the block after it, which lands behind the counting
   below, so no block but a call's first stands still for its first
   copy (both grid axes are ``"arbitrary"``).
2. **Scores a page as the lax form does**: ``relu(q^I [H*tq, D] x k^I
   [D, T])`` with the operands as stored (bf16) and float32
   accumulation, times the heads' weights in float32 and summed over
   the heads, -inf where ``s > t`` or the query is filler (slot -1). The
   queries of a block lie head-major (row ``h * tq + s``), so the heads'
   sum is a sum of whole vector registers; a decode token's product
   takes its walk's whole step (``[H, pps * T]``) and sums over its
   sublanes. The weights arrive spread over one lane tile. The scores
   stay in VMEM, as order-preserving int32 keys (`_ordered`), a walk's
   step side by side (a decode token's: stacked over the sublanes its one
   query leaves empty).
3. **Finds each query's k-th largest score over the walked lanes only**,
   by `mla.kth_largest`'s own method (the largest key that at least k
   entries reach, a bit at a time from the top: 32 counts, each a pass
   over the walk's steps), and emits the SAME mask: 0 on every slot above
   the k-th value and, of the slots at it, on the lowest ones while
   there is room; -1e30 elsewhere. The lanes past the walk are filled
   with ``s <= t``.
4. **Does nothing where there is nothing to choose**: a block whose last
   slot is below ``topk`` has at most ``topk`` slots to see, keeps them
   all, and walks ZERO pages: the trip count's zero case, whose fill is
   the whole answer.

No `pool[layer, block_tables]` gather exists and no score passes through
HBM. Float32 sums the heads in another order than XLA's fusion, so a
slot AT the k-th value's edge can swap with its neighbour (and the keys
tell -0.0 from 0.0 where the lax form's last compare does not);
everything else is the lax form's mask, which is what runs off the chip
and what this is tested against (tests/test_mla_serving.py, interpret
mode; tests/test_tpu_compile.py compiles it for a described v5e). On a
v5e (PERF.md PR 53, `tools/select_alone.py`): the masks of a 4,608-token
request equal the lax form's on every slot; a decode call of 64 rows of
a 132-entry table 0.21 ms where no row walks, 0.43 at 4,096 tokens a
row, 0.81 at 16,384, against 3.34 for the lax form at any length; a 4 x
512 prefill call 0.59 / 2.45 / 6.70 ms at start 1,024 / 4,096 / 16,384
against 13.58.
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
# A walk's step fetches as many pages as make 2,048 keys: 512 KiB a
# buffer slot at 128 bf16 lanes, several copies in flight where one page
# (64 KiB) would leave a decode row waiting on each.
_KEYS_PER_STEP = 2048
# Queries a grid step of a chunk: a block's [H * tq, T] float32 products
# are 1 MiB at 64 heads and pages of 256, its keys [tq, span] 2 MiB at a
# table of 33,792 slots.
_QUERY_TILE = 16
_VMEM_LIMIT_BYTES = 32 << 20
_SIGN = np.int32(-2 ** 31)
_MAGNITUDE = np.int32(2 ** 31 - 1)
# int32 keys (`_ordered`): of a lane no page fills, below every candidate
# of the counts; and of -inf, a slot a query does not see
_BELOW_ALL = int(_SIGN)
_UNSEEN = int(np.float32(-np.inf).view(np.int32) ^ _MAGNITUDE)

__all__ = ["indexer_select", "pages_walked", "query_tile"]

_lax = jax.lax


def query_tile(n_queries: int) -> Optional[int]:
    """Queries a grid step takes of rows of ``n_queries``: one (a decode
    token), else 16 or 8, whichever divides them (a block's queries are
    whole sublane tiles); None where neither does (the lax form's)."""
    if n_queries == 1:
        return 1
    for tq in (_QUERY_TILE, 8):
        if n_queries % tq == 0:
            return tq
    return None


def pages_walked(last_slot, topk: int, block_tokens: int, max_blocks: int):
    """How many leading table entries a block of queries walks, from its
    LAST slot (-1: filler alone): the page that holds it and every page
    before it; none while it is below ``topk``, where a query keeps every
    slot it sees. Plain arithmetic: the engine's counters ask with numpy
    arrays, the kernel's caller with traced ones."""
    span = block_tokens * max_blocks
    n = (last_slot // block_tokens + 1).clip(0, max_blocks)
    return n * (last_slot >= min(topk, span))


def _ordered(bits):
    """A float32's bits (int32) as an int32 that orders as the float
    does (negative floats: every bit but the sign flipped), and back:
    the map is its own inverse."""
    return _lax.select(_lax.ge(bits, np.int32(0)), bits,
                       _lax.bitwise_xor(bits, _MAGNITUDE))


def _lanes(x, shape):
    """[rows, 1] spread over ``shape``'s lanes."""
    return _lax.broadcast_in_dim(x, shape, (0, 1))


def _row_sum(x):
    return _lax.broadcast_in_dim(_lax.reduce_sum(x, (1,)),
                                 (x.shape[0], 1), (0,))


def _kernel(bt_ref, n_ref, lay_ref, qs_ref, w_ref, q_ref, pool_hbm, o_ref,
            k_buf, sem, key_ref, *, topk, tq, n_heads,
            block_tokens, pages_per_step, max_blocks, n_blocks,
            scores_only):
    """Grid step (row b, query block j). Scalar-prefetch refs: ``bt_ref``
    [B * MB] the flat block table, ``n_ref`` [B * n_blocks] each block's
    trip count (`pages_walked`), ``lay_ref`` [1] the plane's layer."""
    T, P, MB = block_tokens, pages_per_step, max_blocks
    f32, i32 = jnp.float32, jnp.int32
    b, j = pl.program_id(0), pl.program_id(1)
    idx = _lax.add(_lax.mul(b, np.int32(n_blocks)), j)
    last = _lax.sub(_lax.mul(pl.num_programs(0), np.int32(n_blocks)),
                    np.int32(1))
    layer = lay_ref[0]
    n = n_ref[idx]
    n_steps = _lax.div(_lax.add(n, np.int32(P - 1)), np.int32(P))
    row0 = _lax.mul(b, np.int32(MB))
    # the grid step after this one, whose step 0 this one fetches
    nxt = _lax.min(_lax.add(idx, np.int32(1)), last)
    next_n = _lax.select(_lax.lt(idx, last), n_ref[nxt], np.int32(0))
    next_row0 = _lax.mul(_lax.div(nxt, np.int32(n_blocks)), np.int32(MB))

    def live_in(step, n=n):
        """How many of the step's pages lie inside the walk."""
        return _lax.clamp(np.int32(0),
                          _lax.sub(n, _lax.mul(step, np.int32(P))),
                          np.int32(P))

    def page_at(p):
        return pl.ds(pl.multiple_of(_lax.mul(p, np.int32(T)), T), T)

    def each_page(entry, n_pages, slot, act):
        def one(p, carry):
            blk = bt_ref[_lax.add(entry, p)]
            act(pltpu.make_async_copy(pool_hbm.at[layer, blk],
                                      k_buf.at[slot, page_at(p)],
                                      sem.at[slot]))
            return carry
        _lax.fori_loop(0, n_pages, one, 0)

    def start(entry, n_pages, slot):
        each_page(entry, n_pages, slot, lambda copy: copy.start())

    def wait(entry, n_pages, slot):
        each_page(entry, n_pages, slot, lambda copy: copy.wait())

    # step 0 is in flight (the grid step before started it) unless this
    # is the call's first
    start(row0, _lax.select(_lax.eq(idx, np.int32(0)), live_in(0),
                            np.int32(0)), 0)

    # The keys are kept, and COUNTED, a walk's step at a time (a slab):
    # a chunk's ``P`` pages side by side over the lanes ([tq, P * T]), a
    # decode token's stacked over the sublanes its one query leaves
    # empty ([P up to 8s, T], page p of a step in row p). A product
    # takes ``pm`` pages: one of a chunk's ([H * tq, T] float32 is 1
    # MiB), a token's whole step ([H, P * T]: one product and one pass
    # of the vector unit where eight would each wait for their own).
    pm = P if tq == 1 else 1
    width = pm * T
    slab = (key_ref.shape[0], T if tq == 1 else P * T)
    wide = (tq, P * T)                  # a step's lanes (the fill's unit)
    wide_lane = _lax.broadcasted_iota(i32, wide, 1)
    wide_slot = _lanes(qs_ref[0], wide)
    lane, q_slot = (wide_lane, wide_slot) if tq == 1 else (
        _lax.broadcasted_iota(i32, (tq, T), 1), _lanes(qs_ref[0], (tq, T)))
    # the heads' weights come spread over one lane tile
    weights = _lax.concatenate([w_ref[0]] * (width // w_ref.shape[2]), 1)

    def slab_at(g):
        return pl.ds(pl.multiple_of(_lax.mul(g, np.int32(slab[1])),
                                    slab[1]), slab[1])

    def keys_of(page):
        """Where page ``page``'s keys [tq, T] lie in `key_ref`."""
        if tq > 1:
            return slice(None), page_at(page)
        return (pl.ds(_lax.rem(page, np.int32(P)), 1),
                page_at(_lax.div(page, np.int32(P))))

    # lanes of the last slab that no page fills hold a key below every
    # candidate (a token's are written with its step's)
    if tq > 1:
        @pl.when(_lax.gt(n, np.int32(0)))
        def _():
            key_ref[:, slab_at(_lax.sub(n_steps, np.int32(1)))] = \
                _lax.full(slab, _BELOW_ALL, i32)

    def step_body(i, carry):
        slot = _lax.rem(i, np.int32(2))
        ahead = _lax.add(i, np.int32(1))
        start(_lax.add(row0, _lax.mul(ahead, np.int32(P))), live_in(ahead),
              _lax.sub(np.int32(1), slot))
        first = _lax.mul(i, np.int32(P))
        wait(_lax.add(row0, first), live_in(i), slot)

        def score(g, carry):
            p = _lax.mul(g, np.int32(pm))
            page = _lax.add(first, p)
            keys = k_buf[slot, pl.ds(pl.multiple_of(
                _lax.mul(p, np.int32(T)), T), width), :]
            dots = _lax.dot_general(q_ref[0], keys, (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32)
            part = _lax.mul(_lax.max(dots, np.float32(0.0)), weights)
            if tq == 1:
                s = _lax.broadcast_in_dim(_lax.reduce_sum(part, (0,)),
                                          (1, width), (1,))
            else:
                s = _lax.reduce_sum(
                    _lax.reshape(part, (n_heads, tq, width)), (0,))
            at = _lax.add(lane, _lax.mul(page, np.int32(T)))
            s = _lax.select(_lax.le(at, q_slot), s,
                            _lax.full_like(s, -np.inf))
            key = _ordered(_lax.bitcast_convert_type(s, i32))
            if tq > 1:
                key_ref[keys_of(page)] = key
                return carry
            # (a page of the step past the walk was not fetched: what the
            # buffer holds there is stale)
            key = _lax.select(_lax.lt(at, _lax.mul(n, np.int32(T))), key,
                              _lax.full_like(key, _BELOW_ALL))
            for r in range(slab[0]):
                key_ref[r:r + 1, slab_at(i)] = _lax.slice_in_dim(
                    key, r * T, (r + 1) * T, axis=1) if r < P \
                    else _lax.full((1, T), _BELOW_ALL, i32)
            return carry

        _lax.fori_loop(
            0, _lax.div(_lax.add(live_in(i), np.int32(pm - 1)),
                        np.int32(pm)), score, 0)
        return carry

    _lax.fori_loop(0, n_steps, step_body, 0)
    # every copy of this block has landed: the next block's first pages
    # come in behind the counting
    start(next_row0, live_in(0, next_n), 0)

    # Past the walk a query keeps what it sees: nothing or, where there
    # was no walk at all, every slot up to its own. Written ``P`` pages
    # at a time from the walk's last whole step on (the table's last
    # such stretch may lap the one before it); the walked pages' own
    # answer comes after and lies over it.
    def fill(g, carry):
        first = _lax.min(_lax.mul(g, np.int32(P)), np.int32(MB - P))
        at = pl.ds(pl.multiple_of(_lax.mul(first, np.int32(T)), T), P * T)
        if scores_only:
            o_ref[0, :, at] = _lax.full(wide, -np.inf, f32)
        else:
            sees = _lax.le(_lax.add(wide_lane, _lax.mul(first, np.int32(T))),
                           wide_slot)
            o_ref[0, :, at] = _lax.select(sees, _lax.full(wide, 0.0, f32),
                                          _lax.full(wide, _NEG, f32))
        return carry
    _lax.fori_loop(_lax.div(n, np.int32(P)), np.int32(-(-MB // P)), fill, 0)

    def over_pages(fn, init):
        return _lax.fori_loop(0, n, fn, init)

    if scores_only:
        # the tests' view: what the mask would be taken from
        def emit_scores(page, carry):
            o_ref[0, :, page_at(page)] = _lax.bitcast_convert_type(
                _ordered(key_ref[keys_of(page)]), f32)
            return carry
        over_pages(emit_scores, 0)
        return

    def per_query(x):
        """[tq, 1] spread over a slab's lanes (a token's over its
        sublanes too)."""
        return _lax.broadcast_in_dim(x, slab, (0, 1))

    def count(pred):
        """[tq, 1] f32: how many walked lanes of each query hold."""
        def one(g, acc):
            return _lax.add(acc, _lax.select(
                pred(key_ref[:, slab_at(g)]), _lax.full(slab, 1.0, f32),
                _lax.full(slab, 0.0, f32)))
        hits = _row_sum(_lax.fori_loop(0, n_steps, one,
                                       _lax.full(slab, 0.0, f32)))
        if tq == 1:
            hits = _lax.broadcast_in_dim(_lax.reduce_sum(hits, (0,)),
                                         (1, 1), (1,))
        return hits

    k = np.float32(topk)

    def bit(i, t):
        # ``t``: the unsigned key so far, as int32 bits; the keys here
        # order as SIGNED ints, which is that order with the top bit
        # flipped
        cand = _lax.bitwise_or(
            t, _lax.shift_left(np.int32(1), _lax.sub(np.int32(31), i)))
        at_least = per_query(_lax.bitwise_xor(cand, _SIGN))
        enough = _lax.ge(count(lambda key: _lax.ge(key, at_least)), k)
        return _lax.select(enough, cand, t)

    @pl.when(_lax.gt(n, np.int32(0)))
    def _():
        t = _lax.bitwise_xor(
            _lax.fori_loop(0, 32, bit, _lax.full((tq, 1), 0, i32)), _SIGN)
        # a query with fewer than k slots to see ends at -inf's key: every
        # slot it sees is above it
        kth_c, floor_c = per_query(t), _lax.full(slab, _UNSEEN, i32)
        room = _lax.sub(k, count(lambda key: _lax.gt(key, kth_c)))
        ties = count(lambda key: _lax.bitwise_and(
            _lax.eq(key, kth_c), _lax.gt(key, floor_c)))
        crowded = _lax.reduce_max(_lax.sub(ties, room), (0, 1))
        kth, floor = _lanes(t, (tq, T)), _lax.full((tq, T), _UNSEEN, i32)
        zero = _lax.full((tq, T), 0.0, f32)
        neg = _lax.full((tq, T), _NEG, f32)

        @pl.when(_lax.le(crowded, np.float32(0.0)))
        def _():
            # every slot at the k-th value has room: the mask is a compare
            def emit(page, carry):
                key = key_ref[keys_of(page)]
                take = _lax.bitwise_and(_lax.ge(key, kth),
                                        _lax.gt(key, floor))
                o_ref[0, :, page_at(page)] = _lax.select(take, zero, neg)
                return carry
            over_pages(emit, 0)

        @pl.when(_lax.gt(crowded, np.float32(0.0)))
        def _():
            # more slots at the k-th value than room: the lowest ones, by
            # a running count of them (within a page: a product with the
            # upper triangle, exact in float32)
            upper = _lax.convert_element_type(
                _lax.le(_lax.broadcasted_iota(i32, (T, T), 0),
                        _lax.broadcasted_iota(i32, (T, T), 1)), jnp.bfloat16)

            def emit(page, before):
                key = key_ref[keys_of(page)]
                at = _lax.bitwise_and(_lax.eq(key, kth), _lax.gt(key, floor))
                ones = _lax.select(at, _lax.full((tq, T), 1.0, f32), zero)
                upto = _lax.add(_lanes(before, (tq, T)), _lax.dot_general(
                    _lax.convert_element_type(ones, jnp.bfloat16), upper,
                    (((1,), (0,)), ((), ())), preferred_element_type=f32))
                take = _lax.bitwise_or(
                    _lax.gt(key, kth),
                    _lax.bitwise_and(at,
                                     _lax.le(upto, _lanes(room, (tq, T)))))
                o_ref[0, :, page_at(page)] = _lax.select(take, zero, neg)
                return _lax.add(before, _row_sum(ones))
            over_pages(emit, _lax.full((tq, 1), 0.0, f32))


def indexer_select(qi, wt, q_slots, pool, block_tables, layer, *, topk: int,
                   interpret: Optional[bool] = None,
                   scores_only: bool = False):
    """The selection's mask for queries [B, S] of paged rows: ``qi``
    [B, S, H, D] the indexer's queries, ``wt`` [B, S, H] float32 its
    heads' weights, ``q_slots`` [B, S] each query's slot (-1: filler,
    which chooses nothing), ``pool`` [L, NB, T, D] the index plane WHOLE,
    ``block_tables`` [B, MB], ``layer`` the plane's layer (traced).
    Returns the additive bias [B, S, MB * T] float32 `mla.select_mask`
    returns: 0 on the ``min(t + 1, topk)`` slots a query chose, -1e30
    elsewhere. ``S`` is 1 or a multiple of 8 (`query_tile`).
    ``scores_only`` (the tests' view of step 2) returns the float32
    scores of the walked lanes instead, -inf elsewhere."""
    B, S, H, D = qi.shape
    T, MB = pool.shape[2], block_tables.shape[1]
    tq = query_tile(S)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if tq is None or (T % 128 and not interpret):
        raise ValueError("indexer_select: one query a row or a multiple "
                         f"of 8, pages of whole lane tiles (got {S}, {T})")
    if isinstance(layer, (int, np.integer)):    # (no scalar on the device)
        layer = np.int32(layer)
    return _select(qi, wt, q_slots.astype(jnp.int32), pool,
                   block_tables.astype(jnp.int32), layer,
                   topk=min(topk, T * MB), tq=tq, interpret=bool(interpret),
                   scores_only=scores_only)


@functools.partial(jax.jit, static_argnames=("topk", "tq", "interpret",
                                             "scores_only"))
def _select(qi, wt, q_slots, pool, block_tables, layer, *, topk, tq,
            interpret, scores_only):
    B, S, H, D = qi.shape
    T, MB = pool.shape[2], block_tables.shape[1]
    nb = S // tq
    pps = max(1, min(_KEYS_PER_STEP // T, MB))
    n = pages_walked(jnp.max(q_slots.reshape(B, nb, tq), axis=2), topk, T,
                     MB)
    # a block's queries head-major: row h * tq + s of its [H * tq, D],
    # and their heads' weights in that order, each over one lane tile (a
    # column [H * tq, 1] would be stored as wide and arrive unspread)
    q = qi.reshape(B, nb, tq, H, D).swapaxes(2, 3).reshape(B, nb * H * tq, D)
    w = jnp.broadcast_to(
        wt.astype(jnp.float32).reshape(B, nb, tq, H).swapaxes(2, 3)
        .reshape(B, nb * H * tq, 1), (B, nb * H * tq, min(T, 128)))

    def block(b, j, *_):
        return (b, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, nb),
        in_specs=[pl.BlockSpec((1, tq, 1), block),              # q slots
                  pl.BlockSpec((1, H * tq, w.shape[2]), block),  # weights
                  pl.BlockSpec((1, H * tq, D), block),          # queries
                  pl.BlockSpec(memory_space=pl.ANY)],           # the plane
        out_specs=pl.BlockSpec((1, tq, MB * T), block),
        scratch_shapes=[pltpu.VMEM((2, pps * T, D), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        # the keys, a slab of pages at a time
                        pltpu.VMEM((-(-pps // 8) * 8, -(-MB // pps) * T)
                                   if tq == 1 else
                                   (tq, -(-MB // pps) * pps * T),
                                   jnp.int32)])
    kernel = functools.partial(
        _kernel, topk=topk, tq=tq, n_heads=H, block_tokens=T,
        pages_per_step=pps, max_blocks=MB, n_blocks=nb,
        scores_only=scores_only)
    # the out block twice and the keys (a [1, span] row is held as 8
    # sublanes), the weights twice, the page buffers, a page's products
    # twice, the queries twice
    vmem = (3 * max(tq, 8) * MB * T * 4 + 2 * H * tq * 128 * 4
            + 2 * pps * T * D * pool.dtype.itemsize + 2 * H * tq * T * 4
            + 2 * H * tq * D * qi.dtype.itemsize)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, MB * T), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES if vmem > (8 << 20)
            else None),
        interpret=interpret, name=sn.INDEXER_SELECT_KERNEL,
    )(block_tables.reshape(-1), n.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q_slots[..., None], w, q,
      pool)

"""Fused block-walking paged attention kernel (Pallas/Mosaic): decode
tokens, speculative windows and prefill chunks.

The pure-lax reference in `ops/attention.py:paged_attention` gathers a
per-row dense view ``[B, MB*T, KV, D]`` and lets XLA fuse it — correct,
but the gathered view is materialization pressure exactly proportional
to the block-table span. This kernel instead walks each row's block
table in VMEM with a flash-style online-softmax loop: page gather +
(optional int8/fp8) dequantization + attend are fused, no dense view
ever exists, and **the walk is as long as what is live**: a page ``j``
with ``j*T > max_s q_slots[b, s]`` or ``j*T >= kv_valid_len`` is masked
for every query of the row, the pages that are not form a prefix of the
table, and `live_pages` hands the kernel that prefix's length as a
scalar-prefetched trip count. Table entries past it are never read,
fetched or folded (the engine's decode step has a quarter of its
``B*MB`` entries live at most; walking all of them was 44 % of a decode
token, PERF.md PR 25).

Grid is ``(B,)``: one grid step per ROW, and a row is all the query
slots of an engine row while they are few (a decode token, a
speculative window: one call over all rows, as before prefill came
here), or one TILE of a prefill chunk's queries: ``tq`` query tokens
(`walk_shape`: as many as keep the tile's f32 accumulator within 2 MiB
and a head's scores within 1 MiB, 128 tokens at Mistral's 8 KV heads x
4, 256 at OLMoE's 16 x 1, so a 512-token chunk is 4 or 2 tiles).
Causality makes a tile's walk its own: ``n_live`` is `live_pages` of
the tile's slots, so the first tile of a chunk at ``start`` walks
``start/T + tq/T`` pages and not the row's, and a tile whose queries
are all bucket filler (slot -1) walks none (PERF.md PR 30: prefill
attended a dense ``[rows, max_len]`` view before). All tiles of all
rows go in one call, rows in eights (`paged_attention_kernel` says what
that buys).

The pool stays in HBM (``pl.ANY``) WHOLE, every layer of it, in the
layout the engine stores: ``[L, NB, T, KV*D]``, so one page is one
contiguous ``[T, KV*D]`` slab holding every KV head as a lane-aligned
``[T, D]`` slice, and a page is read once per tile whatever the GQA
group size. The layer to read is one more scalar-prefetched operand and
a page is addressed ``pool.at[layer, block]``: nothing pool-sized or
layer-sized is sliced, reshaped or copied on the way in (the layer scan
used to slice a layer out and relay it for every layer and token: four
fifths of a decode token, PERF.md PR 27). Inside the grid step a
``fori_loop`` of ``cdiv(n_live, P)`` compute steps runs, each over
``P`` pages (``P*T`` = 512 keys, or what fits 1 MiB a buffer slot),
which the kernel fetches itself: one ``make_async_copy`` per live page
through the scalar-prefetched block table into one slot of a double
buffer, the next step's copies started into the other slot before this
step's are waited for. **The rows of a call are ONE such pipeline, in
grid order**: at a row's last step "the next step" is step 0 of the row
after it, read from the scalar-prefetched refs at ``b + 1``, so a row
finds its first pages in flight and does not start them itself (PERF.md
PR 38; before, every row stood still for its first copy). The slot a row
starts in and whether the row before it ran a step (a row that walks
nothing runs no step and fetches for nobody: the row after it starts its
own copies, as the first row of a call does) are two words of SMEM
scratch that a row leaves for the next, so the grid axis is
``"arbitrary"``: the rows of a call are ordered. Nothing else passes
from row to row: a row's output is, bit for bit, what it is in a call of
its own. A page of a tile's last step that lies past ``n_live`` is
not fetched, by whichever row started the step; its place in the V
buffer is zeroed first, so stale VMEM (``0 * NaN``) cannot reach the
accumulator, and stale K only reaches scores the mask replaces. A tile
with ``n_live == 0`` walks nothing and returns 0.

Queries arrive regrouped as ``[B, KV, S*G, D]``. While all the query
rows of a grid step, every head's, are within the MXU's 128 (`walk_shape`:
a decode token, a speculative window of a few slots) **a step takes the
row's KV heads as ONE operand**: the queries are laid out once a grid
step, in VMEM, as a block-diagonal ``Qd [KV*S*G, KV*D]`` (head ``kv``'s
rows hold their query in lanes ``kv*D:(kv+1)*D``, zeros elsewhere), so
``Qd x K^T`` against the step's ``[P*T, KV*D]`` slab is every head's
scores at once (a zero times a key adds an exact 0.0 to the f32 sum),
ONE mask, max, exp and sum serve all the rows, and ``p x V`` lands in an
f32 accumulator ``[KV*S*G, KV*D]`` of which a row's own head's ``D``
lanes are its answer (the other lanes are finite products nobody reads);
the diagonal blocks are cut out once, at the grid step's end
(`_own_lanes`), into the ``[KV, S*G, D]`` output block. Two matmuls and
one softmax a step, where until PR 51 a decode row ran ``KV``
straight-line bodies of 1-4 query rows each (8 half-empty ``[4, 512]``
score tiles at Mistral's shape; 16 of one row in eight at OLMoE's). A
prefill tile (128 query rows a head and more) fills the MXU a head:
there, and for a speculative window too wide to stack, the heads are a
``fori_loop`` over one KV head's ``[S*G, D]`` operand against its ``D``
lanes of the slab, with the usual flash trio per KV head (f32 accumulator
``[KV, tq*G, D]`` plus running max/sum ``[KV, tq*G, 1]``). Either way the
trio is set at the top of the grid step and finalized at its end. Masked
positions follow the reference exactly: causal ``slot <= q_slot`` plus the
``kv_valid_len`` cap, fully-masked rows produce 0. A fully masked step
leaves the state untouched (``p = 0``, ``alpha = 1``), so stopping at
``n_live`` gives the bits of a walk over all ``MB`` entries (`_walk`,
which the tests call both ways); against a walk of one page a step the
sums are associated differently, within the parity tolerances.

Quantized pages are widened to the query dtype (exact for int8 and
fp8-e4m3 into bf16 or f32) and the per-block per-head scales multiply
the ``[tq*G, P*T]`` scores (K) and probabilities (V) column-wise
instead of the pages: same product, rounded in a different order than
the reference's dequantize-then-matmul (the stacked body spreads each
head's column scales over that head's rows by selects: a quantized pool
takes the same one body a step). The scales of a row's MB pages
are gathered outside the kernel into one ``[1, MB*KV]`` SMEM block per
row (under the ``kv_gather`` scope) and read as scalars; `_page_scales`
spreads a step's ``P`` of them over its key columns by selects — Mosaic
has no gather and no broadcast of a ``(1, 1)`` vector over both
sublanes and lanes.

The layout compiles for a described ``v5e:2x2`` device at Llama-3-8B
widths and at the benchmark's shapes, block tokens 16-128, bf16 / int8 /
fp8, one and four query slots, and so do the whole fused decode program
and the prefill program around it, held there to moving nothing of the
pool's size and to building no view of the table
(tests/test_tpu_compile.py); it runs on the chip in ``chip_smoke.py``'s
paged variants; the value sweeps against the pure-lax reference run in
interpret mode (tests/test_engine_kv_quant.py, on a pool whose layers
hold different data; tests/test_paged_kernel_stacked.py, the cells' head
layouts stacked and looped). On a TPU `impl="auto"` routes here;
elsewhere it stays on the reference path and this kernel runs only when
asked for explicitly (then in interpret mode). Timed on a v5e (PERF.md
PR 25, PR 27, PR 30, PR 38): 0.28 ms a decode call at the benchmark's
shape where the full walk took 2.92, and the same through the whole
pool's ref as through a layer's view. Until PR 38 a row stood still for
its first copy, about 3 us; with the rows one pipeline the decode
PROGRAM, timed alone, lost 1.5-2.1 us a live row of a call (Mistral, 32
rows x 12 layers: 9.41 -> 8.83 ms a token at 256 tokens a row, 11.85 ->
11.05 at 1,024; Phi-4-mini-flash, 64 rows x 16 calls: 24.04 -> 21.87)
and 0.6 us a dead row (one short copy); a call's first row, and a row
behind one that walks nothing, still wait. **What a step costs (PR 51,
`tools/decode_alone.py`, one v5e, the decode program alone):** with the
heads as straight-line bodies one more 512-key step of a Mistral row
cost 3.86 us against 2.56 of HBM time at 819 GB/s (32 rows, between 9
and 33 pages a row); 3.23 with ONE body of the eight (what the copies
and one chain leave), 5.08 for the same keys in two steps of 256 (1.2 us
a step that no byte explains: eight dependent chains and 2 x pps copies
issued and waited for in scalar loops). Stacked it costs **3.51**, and a
row's only step 1.26 us less than before (8.83 -> 8.35 ms a token at 9
pages a row, 11.05 -> 10.37 at 33); a 384-key step of a Phi-4-mini-flash
row 2.72 -> **2.43** against 2.40 of HBM time (64 rows x 16 calls: 21.86
-> 18.47 ms a token at 1,024 tokens a row, 20.01 -> 16.81 at 512);
qwen3next's two heads of 256 equal either way (12.50 ms a token at 1,024,
a step of two 256-token pages 1.43 us against 1.28). ROADMAP S2 keeps
what is left of a Mistral step: the 2 x 16 copies a step from the scalar
core. A
512-token chunk at start 0 0.15 ms a layer, at start 2,560 0.44.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scope_names as sn
from ray_tpu.ops.attention import pool_kv_heads

_NEG_INF = -1e30
# A compute step folds as many pages as make 512 keys, or as fit 1 MiB
# per buffer slot (two slots each for K and V: 4 MiB of VMEM at most).
_KEYS_PER_STEP = 512
_STEP_BYTES = 1 << 20
# A grid step holds one TILE of a row's queries: as many query tokens as
# keep the f32 accumulator [KV, tq*G, D] within 2 MiB and one KV head's
# f32 scores [tq*G, keys per step] within 1 MiB.
_TILE_ACC_BYTES = 2 << 20
_TILE_SCORE_BYTES = 1 << 20
# The trio, the page buffers and the q and out blocks of a 512-row tile
# come to 18 MiB, above the compiler's default of 16 for a kernel: such
# a call asks for 32. A call that fits the default asks for nothing:
# what a kernel may take, XLA cannot give to the program around it, and
# the decode program keeps a layer-stacked weight in VMEM across its
# scan (32 MiB for a decode row's kernel cost a decode token 1.4 % on a
# v5e: `wk` came from HBM again, PERF.md PR 30).
_VMEM_DEFAULT_BYTES = 16 << 20
_VMEM_LIMIT_BYTES = 32 << 20
# While ALL the query rows of a grid step, every KV head's, are no more
# than the MXU's 128, the step takes the row's KV heads as one operand
# (`_kernel`, the stacked body): the zeros of the block-diagonal query
# then ride passes the array makes anyway. Past that they would cost
# passes of their own, a head's two matmuls fill the array alone, and the
# heads are a `fori_loop` of the kernel (a prefill tile, a wide
# speculative window).
_STACK_ROWS = 128
# Rows (tiles) go into a call in eights (`paged_attention_kernel`).
_ROWS_PER_CALL = 8

__all__ = ["paged_attention_kernel"]


def _scalar_i32(x):
    """A scalar operand as int32 WITHOUT touching the device when it is
    static. `jnp.asarray(4096)` puts a scalar on the chip even under a
    trace, and a traced program that uses it reads it BACK to make it a
    literal: a read that queues behind whatever the chip is running.
    While the engine warms up it is running the program dispatched just
    before, so every trace of this kernel stood still for 0.2-2 s
    (PERF.md PR 30: 17 s of a 38 s warm-up)."""
    if isinstance(x, (int, np.integer)):
        return np.int32(x)
    return jnp.asarray(x, jnp.int32)


def live_pages(q_slots, kv_valid_len, block_tokens: int,
               max_blocks: int) -> jax.Array:
    """[B] int32: how many leading block-table entries of each row hold
    a slot some query of the row may see. Page ``j`` is fully masked for
    every query of row ``b`` when ``j * T > max_s q_slots[b, s]`` or
    ``j * T >= kv_valid_len``, and the pages that are not are a prefix
    of the table."""
    by_slot = jnp.max(q_slots.astype(jnp.int32), axis=1) // block_tokens + 1
    by_len = (_scalar_i32(kv_valid_len) + (block_tokens - 1)) \
        // block_tokens
    return jnp.clip(jnp.minimum(by_slot, by_len), 0, max_blocks)


def first_page(q_slots, window: int, block_tokens: int, n_live) -> jax.Array:
    """[B] int32: the first block-table entry of each row that holds a
    slot some query of a sliding-window row may see. A query at slot t
    sees ``t - window < s <= t``, so every page before the one that holds
    ``min_s q_slots[b, s] - window + 1`` is masked for all of them
    (filler queries, slot -1, ask nothing and are left out of the
    minimum). Never past ``n_live``: a row that walks nothing starts
    where it ends."""
    qs = q_slots.astype(jnp.int32)
    lo = jnp.min(jnp.where(qs >= 0, qs, jnp.iinfo(jnp.int32).max), axis=1)
    first = jnp.maximum(lo - (window - 1), 0) // block_tokens
    return jnp.minimum(first, n_live).astype(jnp.int32)


def walk_shape(n_slots: int, n_heads: int, n_kv: int, head_dim: int,
               block_tokens: int, max_blocks: int,
               itemsize: int) -> Tuple[int, int, bool]:
    """(pages a compute step folds, query tokens a grid step holds,
    whether a step takes the row's KV heads as one operand), from the
    static shapes alone. A step folds as many pages as make
    `_KEYS_PER_STEP` keys or fit `_STEP_BYTES`. A grid step holds all
    ``n_slots`` queries of a row while they fit the tile's budgets (a
    decode token, a speculative window), else the largest power of two
    that does (a prefill chunk: 128 tokens x 4 heads a group at 8 KV
    heads of 128, 256 x 1 at 16). The heads are stacked while the grid
    step's query rows, all heads', are within `_STACK_ROWS` and the
    stacked accumulator ``[rows, KV*D]`` and scores ``[rows, keys]`` are
    within the same two budgets. The engine's walk counters ask here too
    (`DecodeEngine._count_paged_walk`, `_count_prefill_walk`)."""
    page_bytes = block_tokens * n_kv * head_dim * itemsize
    pps = max(1, min(_KEYS_PER_STEP // block_tokens,
                     _STEP_BYTES // page_bytes, max_blocks))
    group = n_heads // n_kv
    keys = pps * block_tokens
    fit = min(_TILE_ACC_BYTES // (n_heads * head_dim * 4),
              _TILE_SCORE_BYTES // (group * keys * 4))
    tq = n_slots if n_slots <= fit \
        else max(8, 1 << (max(fit, 1).bit_length() - 1))
    rows = tq * n_heads
    stacked = (rows <= _STACK_ROWS
               and rows * n_kv * head_dim * 4 <= _TILE_ACC_BYTES
               and rows * keys * 4 <= _TILE_SCORE_BYTES)
    return pps, tq, stacked


# The kernel's body is written in `jax.lax` primitives, with no `jnp`
# function and no operator on a traced value. Each of those is a jitted
# helper, and under a kernel's trace every use of one is traced anew: on
# the benchmark's host that was 0.45 s a kernel, 20 s of an engine's
# warm-up over its 44 kernels (PERF.md PR 30). `lax` binds the primitive
# and nothing else, and the jaxpr is the same.
_lax = jax.lax


def _i32(x):
    return np.int32(x) if isinstance(x, int) else x


def _add(a, b):
    return _lax.add(_i32(a), _i32(b))


def _mul(a, b):
    return _lax.mul(_i32(a), _i32(b))


def _select(pred, x, other):
    """``where(pred, x, other)`` for a scalar or same-shape ``other``."""
    if not hasattr(other, "shape"):
        other = _lax.full_like(x, other)
    return _lax.select(pred, x, other)


def _row_reduce(reduce, x):
    """``reduce(x, axis=-1, keepdims=True)`` of a [rows, cols] value."""
    return _lax.broadcast_in_dim(reduce(x, (1,)), (x.shape[0], 1), (0,))


def _kernel(bt_ref, lim_ref, nl_ref, lay_ref, *refs, sm_scale, n_kv,
            head_dim, block_tokens, pages_per_step, max_blocks, has_scale,
            stacked, window=None):
    """Grid step ``b``: walk row b's live pages, ``pages_per_step`` at a
    time, folding each step into every KV head's online softmax (a row
    is a decode row's query slots, or one query tile of a prefill
    chunk): all heads in one body of two matmuls and one softmax when
    ``stacked`` (`walk_shape`), else a loop over the heads.
    Scalar-prefetch refs: ``bt_ref`` [B*MB] flat block table,
    ``lim_ref`` [1] the valid-length cap, ``nl_ref`` [B] live pages per
    row (the walk's end), ``lay_ref`` [1] the pool's layer and, for a
    sliding-window layer (``window``), one more: [B] the page each row's
    walk STARTS at (`first_page`); keys at or behind ``q_slot - window``
    are masked, inside the first page too. The last scratch ref,
    ``chain_ref`` (SMEM [2]), is what row b - 1 left for this one: the
    buffer slot its last step fetched into (this row's step 0 runs
    there) and whether it ran a step at all."""
    if window is not None:
        fp_ref, *refs = refs
    *refs, chain_ref = refs
    if stacked:
        *refs, qd_ref, slot_ref = refs
    if has_scale:
        (qs_ref, q_ref, k_hbm, v_hbm, ks_ref, vs_ref, o_ref, k_buf, v_buf,
         sem, acc_ref, m_ref, l_ref) = refs
    else:
        (qs_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, acc_ref,
         m_ref, l_ref) = refs
    b = pl.program_id(0)
    last_row = _lax.sub(pl.num_programs(0), np.int32(1))
    t, pps = block_tokens, pages_per_step
    span = pps * t                                      # keys per step
    layer = lay_ref[0]
    rows = q_ref.shape[2]                               # tq * G
    n_rows = n_kv * rows if stacked else rows           # of a head body

    def walk_of(r):
        """Row r's walk: the flat table index of its first page, how
        many pages, and the page of the row's table it starts at (None
        without a window: 0)."""
        row0, n_live = _mul(r, max_blocks), nl_ref[r]
        if window is None:
            return row0, n_live, None
        # the walk is the pages [first, n_live): everything below counts
        # from the row's first live page
        first = fp_ref[r]
        return _add(row0, first), _lax.sub(n_live, first), first

    row0, n_live, first = walk_of(b)
    n_steps = _lax.div(_add(n_live, pps - 1), np.int32(pps))
    # the row after this one, whose step 0 this row's last step fetches;
    # the last row of the call fetches for nobody
    next_row0, next_live, _ = walk_of(_lax.min(_add(b, 1), last_row))
    next_live = _select(_lax.lt(b, last_row), next_live, 0)
    # what the row before left: the buffer slot this row starts in, and
    # whether it ran a step (then this row's step 0 is in flight there)
    slot0 = _select(_lax.eq(b, np.int32(0)), np.int32(0), chain_ref[0])
    chained = _lax.bitwise_and(_lax.gt(b, np.int32(0)),
                               _lax.eq(chain_ref[1], np.int32(1)))

    def live_in(step, n_live=n_live):
        """How many of the step's pages lie inside the live prefix."""
        return _lax.min(np.int32(pps), _lax.sub(n_live, _mul(step, pps)))

    def page_at(p):
        return pl.ds(pl.multiple_of(_mul(p, t), t), t)

    def page_copies(entry, buf, p):
        """The K and V copy into buffer `buf` of the p-th page of the
        step whose first table entry is `entry` (a start and its wait
        build the same descriptor, be they one row's or two)."""
        blk = bt_ref[_add(entry, p)]
        dst = page_at(p)
        return (pltpu.make_async_copy(k_hbm.at[layer, blk],
                                      k_buf.at[buf, dst], sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, blk],
                                      v_buf.at[buf, dst], sem.at[1, buf]))

    def each_page(entry, n_pages, buf, act):
        def one(p, carry):
            for copy in page_copies(entry, buf, p):
                act(copy)
            return carry
        _lax.fori_loop(0, n_pages, one, 0)

    def start(entry, n_pages, buf):
        each_page(entry, n_pages, buf, lambda copy: copy.start())

    def wait(entry, n_pages, buf):
        each_page(entry, n_pages, buf, lambda copy: copy.wait())

    start(row0, _select(chained, np.int32(0), live_in(0)), slot0)
    acc_ref[...] = _lax.full(acc_ref.shape, 0.0, jnp.float32)
    m_ref[...] = _lax.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = _lax.full(l_ref.shape, 0.0, jnp.float32)
    if stacked:
        # The row's queries as ONE block-diagonal operand [KV*rows, KV*D]:
        # head kv's rows hold their query in that head's D lanes and
        # zeros elsewhere, so one product against the step's [keys, KV*D]
        # slab is every head's scores (a zero times a key adds an exact
        # 0.0 to the f32 sum). Laid out through the zeroed accumulator,
        # whose 32-bit rows take a store at any sublane; the query slots
        # repeat over the heads beside it.
        for kv in range(n_kv):
            at = pl.ds(kv * rows, rows)
            acc_ref[at, pl.ds(kv * head_dim, head_dim)] = \
                _lax.convert_element_type(q_ref[0, kv], jnp.float32)
            slot_ref[at, :] = qs_ref[0]
        qd_ref[...] = _lax.convert_element_type(acc_ref[...], qd_ref.dtype)
        acc_ref[...] = _lax.full(acc_ref.shape, 0.0, jnp.float32)
        q_slot = slot_ref[...]                          # [KV*tq*G, 1]
    else:
        q_slot = qs_ref[0]                              # [tq*G, 1]

    col = _lax.broadcasted_iota(jnp.int32, (n_rows, span), 1)
    page_of_col = _lax.div(
        _lax.broadcasted_iota(jnp.int32, (1, span), 1), np.int32(t))
    lim = lim_ref[0]
    zero_page = _lax.full((t, v_buf.shape[2]), 0, v_buf.dtype)

    def step_body(i, carry):
        buf = _lax.rem(_add(slot0, i), np.int32(2))
        # the copies of the step after this one, into the other slot:
        # the row's own next step or, at its last, step 0 of the row
        # after it, which then finds them in flight
        ahead = _add(i, 1)
        at_last = _lax.eq(ahead, n_steps)
        start(_select(at_last, next_row0, _add(row0, _mul(ahead, pps))),
              _select(at_last, live_in(0, next_live), live_in(ahead)),
              _lax.sub(np.int32(1), buf))
        wait(_add(row0, _mul(i, pps)), live_in(i), buf)
        # A page of the last step past the row's live prefix was not
        # fetched: what the V buffer holds there is stale, and 0 * NaN
        # would reach the accumulator through the matmul. (Stale K only
        # reaches scores the mask replaces.)
        def zero(p, carry):
            v_buf[buf, page_at(p), :] = zero_page
            return carry
        _lax.fori_loop(live_in(i), pps, zero, 0)

        slot = _add(_mul(i, span), col)
        if window is not None:
            slot = _add(slot, _mul(first, t))
        mask = _lax.bitwise_and(_lax.le(slot, q_slot), _lax.lt(slot, lim))
        if window is not None:
            mask = _lax.bitwise_and(
                mask, _lax.gt(slot, _lax.sub(q_slot, np.int32(window))))

        def scales(ref, kv):
            page0 = _mul(i, pps) if window is None \
                else _add(first, _mul(i, pps))
            return _page_scales(ref, page0, kv, page_of_col, n_kv, pps,
                                max_blocks)

        def fold(at, q, k, v, ks, vs):
            """One online-softmax update of the trio's rows `at` with
            the step's keys: ``q`` [rows, C] against ``k``, ``v``
            [keys, C] (C a head's lanes, or all heads')."""
            k = _lax.convert_element_type(k, q.dtype)
            v = _lax.convert_element_type(v, q.dtype)
            s = _lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            if has_scale:
                s = _lax.mul(s, ks)
            s = _lax.mul(s, np.float32(sm_scale))
            s = _select(mask, s, _NEG_INF)
            m_prev = m_ref[at]                          # [rows, 1]
            m_new = _lax.max(m_prev, _row_reduce(_lax.reduce_max, s))
            # explicit zero (not just exp underflow): a fully-masked
            # step with m still at -inf would otherwise yield
            # exp(0) == 1 per position
            p = _select(mask, _lax.exp(_lax.sub(s, m_new)), 0.0)
            alpha = _lax.exp(_lax.sub(m_prev, m_new))
            l_ref[at] = _lax.add(_lax.mul(l_ref[at], alpha),
                                 _row_reduce(_lax.reduce_sum, p))
            if has_scale:
                p = _lax.mul(p, vs)
            pv = _lax.dot_general(_lax.convert_element_type(p, v.dtype), v,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
            acc_ref[at] = _lax.add(_lax.mul(acc_ref[at], alpha), pv)
            m_ref[at] = m_new

        def head(kv, carry):
            lanes = pl.ds(pl.multiple_of(_mul(kv, head_dim), head_dim),
                          head_dim)
            ks, vs = (scales(r, kv) for r in (ks_ref, vs_ref)) \
                if has_scale else (None, None)
            fold(kv, q_ref[0, kv], k_buf[buf, :, lanes],
                 v_buf[buf, :, lanes], ks, vs)
            return carry

        if not stacked:
            _lax.fori_loop(0, n_kv, head, 0)
            return carry
        ks = vs = None
        if has_scale:
            # a head's column scales, spread over that head's rows
            head_of_row = _lax.div(
                _lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0),
                np.int32(rows))

            def of_rows(ref):
                out = _lax.full((n_rows, span), 0.0, jnp.float32)
                for kv in range(n_kv):
                    out = _select(
                        _lax.broadcast_in_dim(
                            _lax.eq(head_of_row, np.int32(kv)), out.shape,
                            (0, 1)),
                        _lax.broadcast_in_dim(scales(ref, kv), out.shape,
                                              (0, 1)), out)
                return out
            ks, vs = of_rows(ks_ref), of_rows(vs_ref)
        # every lane of a row's accumulator takes p x V; the row's own
        # head's D lanes are its answer (cut out at the grid step's end),
        # the others finite products nobody reads
        fold(Ellipsis, qd_ref[...], k_buf[buf], v_buf[buf], ks, vs)
        return carry

    _lax.fori_loop(0, n_steps, step_body, 0)
    # a row that ran no step left nothing in flight and the slot as it was
    chain_ref[0] = _lax.rem(_add(slot0, n_steps), np.int32(2))
    chain_ref[1] = _lax.convert_element_type(
        _lax.gt(n_steps, np.int32(0)), jnp.int32)

    l = l_ref[...]
    l = _select(_lax.eq(l, np.float32(0.0)), _lax.full_like(l, 1.0), l)
    row_live = _lax.broadcast_in_dim(
        _lax.gt(m_ref[...], np.float32(_NEG_INF / 2)), acc_ref.shape,
        tuple(range(acc_ref.ndim)))
    out = _select(row_live, _lax.div(acc_ref[...], l), 0.0)
    if not stacked:
        o_ref[0] = _lax.convert_element_type(out, o_ref.dtype)
        return
    for kv in range(n_kv):
        o_ref[0, kv] = _lax.convert_element_type(
            _own_lanes(out, kv, rows, head_dim), o_ref.dtype)


def _own_lanes(out, kv, rows, head_dim):
    """Head ``kv``'s answer in the stacked ``[KV*rows, KV*D]`` result:
    its rows, and of their lanes its own head's ``D`` (the diagonal
    block; the rest of a row is p x the other heads' values)."""
    return _lax.slice(out, (kv * rows, kv * head_dim),
                      ((kv + 1) * rows, (kv + 1) * head_dim))


def _page_scales(scale_ref, first_page, kv, page_of_col, n_kv,
                 pages_per_step, max_blocks):
    """[1, pps*T] f32: the dequantization scale of KV head ``kv`` for
    each key column of the step that starts at page ``first_page``, from
    the row's ``[1, MB*KV]`` SMEM block. Built from scalars by selects:
    Mosaic has no gather."""
    out = _lax.full(page_of_col.shape, 0.0, jnp.float32)
    for p in range(pages_per_step):
        # the last step may reach past the table; such a column is masked
        page = _lax.min(_add(first_page, p), np.int32(max_blocks - 1))
        scale = scale_ref[0, 0, _add(_mul(page, n_kv), kv)]
        out = _select(_lax.eq(page_of_col, np.int32(p)),
                      _lax.broadcast_in_dim(scale, out.shape, ()), out)
    return out


def paged_attention_kernel(q: jax.Array,
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
                           window: Optional[int] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Same contract as `ops.attention.paged_attention` (reference
    impl), fused. ``interpret=None`` resolves to True off-TPU.

    The grid runs over ROWS OF ONE TILE: a decode token's or a
    speculative window's rows as they come, a prefill chunk cut into its
    query tiles (`walk_shape`), each a row of its own with the chunk
    row's table. Rows go into the call in eights (a filler row sits at
    slot -1 and walks nothing) and `_walk` is jitted, so an engine's ~40
    prefill programs (group sizes x length buckets) trace the kernel
    once for each tile size and count of eights, about ten times between
    them and not once each, and its decode programs once. On the
    benchmark's host a trace of this kernel costs 0.15-0.5 s, a whole
    prefill program without it 0.08 (PERF.md PR 30).
    """
    B, S, H, D = q.shape
    T, MB = k_pool.shape[2], block_tables.shape[1]
    KV = pool_kv_heads(k_pool, q)
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    pps, tq, stacked = walk_shape(S, H, KV, D, T, MB,
                                  k_pool.dtype.itemsize)
    walk = functools.partial(
        _walk, k_pool=k_pool, v_pool=v_pool, k_scale=k_scale,
        v_scale=v_scale, layer=_scalar_i32(layer),
        kv_valid_len=_scalar_i32(kv_valid_len), n_live=None,
        sm_scale=sm_scale if sm_scale is not None else D ** -0.5,
        interpret=interpret, pps=pps, stacked=stacked, window=window)
    q_slots = q_slots.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)
    tiles = -(-S // tq)
    if tiles * tq != S:
        # the last tile's filler queries sit at the row's last slot
        # (they see what it sees, and are cut off below)
        fill = ((0, 0), (0, tiles * tq - S))
        q = jnp.pad(q, fill + ((0, 0), (0, 0)))
        q_slots = jnp.pad(q_slots, fill, mode="edge")
    if tiles > 1:
        q = q.reshape(B * tiles, tq, H, D)
        q_slots = q_slots.reshape(B * tiles, tq)
        block_tables = jnp.repeat(block_tables, tiles, axis=0)
    pad = -(B * tiles) % _ROWS_PER_CALL
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
        block_tables = jnp.pad(block_tables, ((0, pad), (0, 0)))
        q_slots = jnp.pad(q_slots, ((0, pad), (0, 0)), constant_values=-1)
    out = walk(q, block_tables, q_slots)[:B * tiles]
    return out.reshape(B, tiles * tq, H, D)[:, :S]


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret", "pps",
                                             "stacked", "window"))
def _walk(q, block_tables, q_slots, *, n_live, k_pool, v_pool, k_scale,
          v_scale, layer, kv_valid_len, sm_scale, interpret, pps,
          stacked, window=None):
    """The kernel call: rows of ``S`` query slots, ``pps`` pages a
    compute step, the KV heads of a step ``stacked`` or a loop
    (`walk_shape` says which). ``n_live`` [B] is each row's trip count:
    None takes `live_pages`; a walk of all ``MB`` entries gives the same
    bits (a fully masked step leaves the softmax state untouched), which
    is what the tests hold it to."""
    B, S, H, D = q.shape
    T = k_pool.shape[2]
    KV = pool_kv_heads(k_pool, q)
    MB = block_tables.shape[1]
    g = H // KV
    rows = S * g
    has_scale = k_scale is not None
    if n_live is None:
        n_live = live_pages(q_slots, kv_valid_len, T, MB)

    # query row r = s*g + i of KV head kv is query s, head kv*g + i
    qg = q.reshape(B, S, KV, g, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, rows, D)
    qs = jnp.repeat(q_slots, g, axis=1)[..., None]

    def row_map(b, *_):
        return (b, 0, 0)

    def q_map(b, *_):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, rows, 1), row_map),           # q slots
        pl.BlockSpec((1, KV, rows, D), q_map),         # q
        pl.BlockSpec(memory_space=pl.ANY),             # k pool, in HBM
        pl.BlockSpec(memory_space=pl.ANY),             # v pool
    ]
    args = [qs, qg, k_pool, v_pool]
    if has_scale:
        in_specs += [pl.BlockSpec((1, 1, MB * KV), row_map,
                                  memory_space=pltpu.SMEM)] * 2
        with jax.named_scope(sn.KV_GATHER):
            args += [s[layer, block_tables].astype(jnp.float32)
                     .reshape(B, 1, MB * KV) for s in (k_scale, v_scale)]

    prefetch = [block_tables.reshape(-1), kv_valid_len.reshape(1),
                n_live.astype(jnp.int32).reshape(-1), layer.reshape(1)]
    if window is not None:
        prefetch.append(first_page(q_slots, window, T, prefetch[2]))
    # the flash trio: a head's [rows, D] accumulator and [rows, 1] max
    # and sum, KV of them; or, the heads stacked, all heads' rows by all
    # heads' lanes, with the block-diagonal query and its rows' slots
    trio = (KV * rows, KV * D) if stacked else (KV, rows, D)
    scratch_shapes = [
        pltpu.VMEM((2, pps * T, KV * D), k_pool.dtype),
        pltpu.VMEM((2, pps * T, KV * D), v_pool.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM(trio, jnp.float32),
        pltpu.VMEM(trio[:-1] + (1,), jnp.float32),
        pltpu.VMEM(trio[:-1] + (1,), jnp.float32),
    ]
    if stacked:
        scratch_shapes += [pltpu.VMEM(trio, q.dtype),
                           pltpu.VMEM((KV * rows, 1), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, rows, D), q_map),
        scratch_shapes=scratch_shapes + [pltpu.SMEM((2,), jnp.int32)],
    )
    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, n_kv=KV, head_dim=D, block_tokens=T,
        pages_per_step=pps, max_blocks=MB, has_scale=has_scale,
        stacked=stacked, window=window)
    # What the call holds in VMEM, by hand: page buffers; the trio (m
    # and l a lane tile wide) and, stacked, the query beside it; the q
    # and out blocks, double-buffered; a body's scores, probabilities
    # and mask. Mosaic's own temporaries are not in it, so the call asks
    # for more from half the default on.
    body_rows = KV * rows if stacked else rows
    vmem = (4 * pps * T * KV * D * k_pool.dtype.itemsize
            + math.prod(trio) * 4 + KV * rows * 2 * 128 * 4
            + stacked * math.prod(trio) * q.dtype.itemsize
            + 4 * KV * rows * D * q.dtype.itemsize
            + 3 * body_rows * pps * T * 4)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES
            if vmem > _VMEM_DEFAULT_BYTES // 2 else None),
        interpret=interpret,
        name=sn.PAGED_KERNEL,
    )(*prefetch, *args)
    return out.reshape(B, KV, S, g, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, D)

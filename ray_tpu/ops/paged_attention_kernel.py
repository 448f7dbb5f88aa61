"""Fused block-walking paged decode-attention kernel (Pallas/Mosaic).

The pure-lax reference in `ops/attention.py:paged_attention` gathers a
per-row dense view ``[B, MB*T, KV, D]`` and lets XLA fuse it — correct,
but the gathered view is materialization pressure exactly proportional
to the block-table span. This kernel instead walks each row's block
table in VMEM with a flash-style online-softmax loop: page gather +
(optional int8/fp8) dequantization + attend are fused, no dense view
ever exists, and **the walk is as long as the row**: a page ``j`` with
``j*T > max_s q_slots[b, s]`` or ``j*T >= kv_valid_len`` is masked for
every query of the row, the pages that are not form a prefix of the
table, and `live_pages` hands the kernel that prefix's length per row
as a scalar-prefetched ``n_live[b]``. Table entries past it are never
read, fetched or folded (the engine's decode step has a quarter of its
``B*MB`` entries live at most; walking all of them was 44 % of a decode
token, PERF.md PR 25).

Grid is ``(B,)``: one grid step per row. The pool stays in HBM
(``pl.ANY``) WHOLE, every layer of it, in the layout the engine stores:
``[L, NB, T, KV*D]``, so one page is one contiguous ``[T, KV*D]`` slab
holding every KV head as a lane-aligned ``[T, D]`` slice, and a page is
read once per row whatever the GQA group size. The layer to read is one
more scalar-prefetched operand and a page is addressed
``pool.at[layer, block]``: nothing pool-sized or layer-sized is sliced,
reshaped or copied on the way in (the layer scan used to slice a layer
out and relay it for every layer and token: four fifths of a decode
token, PERF.md PR 27). Inside the grid step a ``fori_loop`` of
``cdiv(n_live[b], P)`` compute steps runs, each over ``P`` pages
(``P*T`` = 512 keys, or what fits 1 MiB a buffer slot), which the kernel
fetches itself: one ``make_async_copy`` per live page through the
scalar-prefetched block table into slot ``i % 2`` of a double buffer,
step ``i+1``'s copies started before step ``i``'s are waited for. A
page of a row's last step that lies past ``n_live[b]`` is not fetched;
its place in the V buffer is zeroed first, so stale VMEM (``0 * NaN``)
cannot reach the accumulator, and stale K only reaches scores the mask
replaces. A row with ``n_live == 0`` walks nothing and returns 0.

Queries arrive regrouped as ``[B, KV, S*G, D]`` so one KV head's query
group is one matmul operand against the step's ``[P*T, D]`` keys.
Scratch is the usual flash trio per KV head — f32 accumulator
``[KV, S*G, D]`` plus running max/sum ``[KV, S*G, 1]`` — set at the top
of the grid step and finalized at its end. Masked positions follow the
reference exactly: causal ``slot <= q_slot`` plus the ``kv_valid_len``
cap, fully-masked rows produce 0. A fully masked step leaves the state
untouched (``p = 0``, ``alpha = 1``), so stopping at ``n_live`` gives
the bits of a walk over all ``MB`` entries (`_walk`, which the tests
call both ways); against a walk of one page a step the sums are
associated differently, within the parity tolerances.

Quantized pages are widened to the query dtype (exact for int8 and
fp8-e4m3 into bf16 or f32) and the per-block per-head scales multiply
the ``[S*G, P*T]`` scores (K) and probabilities (V) column-wise instead
of the pages: same product, rounded in a different order than the
reference's dequantize-then-matmul. The scales of a row's MB pages are
gathered outside the kernel into one ``[1, MB*KV]`` SMEM block per row
(under the ``kv_gather`` scope) and read as scalars; `_page_scales`
spreads a step's ``P`` of them over its key columns by selects — Mosaic
has no gather and no broadcast of a ``(1, 1)`` vector over both
sublanes and lanes.

The layout compiles for a described ``v5e:2x2`` device at Llama-3-8B
widths and at the benchmark's shapes, block tokens 16-128, bf16 / int8 /
fp8, one and four query slots, and so does the whole fused decode
program around it, held there to moving nothing of the pool's size
(tests/test_tpu_compile.py); it runs on the chip in ``chip_smoke.py``'s
paged variants; the value sweeps against the pure-lax reference, on a
pool of three layers that hold different data, run in interpret mode
(tests/test_engine_kv_quant.py). On a TPU `impl="auto"` routes here;
elsewhere it stays on the reference path and this kernel runs only when
asked for explicitly (then in interpret mode). Timed on a v5e (PERF.md
PR 25, PR 27): 0.28 ms a call at the benchmark's shape where the full
walk took 2.92, and the same through the whole pool's ref as through a
layer's view; a row costs about 3 us before its first page (the first
copy is not overlapped with the row before), a 512-key step about 3.4 us
against 2.6 of HBM time (ROADMAP S2 keeps what is left).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scope_names as sn
from ray_tpu.ops.attention import pool_kv_heads

_NEG_INF = -1e30
# A compute step folds as many pages as make 512 keys, or as fit 1 MiB
# per buffer slot (two slots each for K and V: 4 MiB of VMEM at most).
_KEYS_PER_STEP = 512
_STEP_BYTES = 1 << 20

__all__ = ["paged_attention_kernel"]


def live_pages(q_slots, kv_valid_len, block_tokens: int,
               max_blocks: int) -> jax.Array:
    """[B] int32: how many leading block-table entries of each row hold
    a slot some query of the row may see. Page ``j`` is fully masked for
    every query of row ``b`` when ``j * T > max_s q_slots[b, s]`` or
    ``j * T >= kv_valid_len``, and the pages that are not are a prefix
    of the table."""
    by_slot = jnp.max(q_slots.astype(jnp.int32), axis=1) // block_tokens + 1
    by_len = (jnp.asarray(kv_valid_len, jnp.int32) + block_tokens - 1) \
        // block_tokens
    return jnp.clip(jnp.minimum(by_slot, by_len), 0, max_blocks)


def _kernel(bt_ref, lim_ref, nl_ref, lay_ref, *refs, sm_scale, n_kv,
            head_dim, block_tokens, pages_per_step, max_blocks, has_scale):
    """Grid step ``b``: walk row b's live pages, ``pages_per_step`` at a
    time, folding each step into every KV head's online softmax.
    Scalar-prefetch refs: ``bt_ref`` [B*MB] flat block table,
    ``lim_ref`` [1] the valid-length cap, ``nl_ref`` [B] live pages per
    row (the walk's trip count), ``lay_ref`` [1] the pool's layer."""
    if has_scale:
        (qs_ref, q_ref, k_hbm, v_hbm, ks_ref, vs_ref, o_ref, k_buf, v_buf,
         sem, acc_ref, m_ref, l_ref) = refs
    else:
        (qs_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, acc_ref,
         m_ref, l_ref) = refs
    b = pl.program_id(0)
    t, pps = block_tokens, pages_per_step
    span = pps * t                                      # keys per step
    n_live = nl_ref[b]
    layer = lay_ref[0]
    n_steps = (n_live + pps - 1) // pps
    rows = q_ref.shape[2]                               # S * G

    def live_in(step):
        """How many of the step's pages lie inside the live prefix."""
        return jnp.minimum(pps, n_live - step * pps)

    def page_copies(step, buf, p):
        """The K and V copy of the step's p-th page into buffer `buf`
        (a start and its wait build the same descriptor)."""
        blk = bt_ref[b * max_blocks + step * pps + p]
        dst = pl.ds(pl.multiple_of(p * t, t), t)
        return (pltpu.make_async_copy(k_hbm.at[layer, blk],
                                      k_buf.at[buf, dst], sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, blk],
                                      v_buf.at[buf, dst], sem.at[1, buf]))

    def each_live_page(step, buf, act):
        def one(p, carry):
            for copy in page_copies(step, buf, p):
                act(copy)
            return carry
        jax.lax.fori_loop(0, live_in(step), one, 0)

    def start(step, buf):
        each_live_page(step, buf, lambda copy: copy.start())

    def wait(step, buf):
        each_live_page(step, buf, lambda copy: copy.wait())

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    start(0, 0)

    col = jax.lax.broadcasted_iota(jnp.int32, (rows, span), 1)
    page_of_col = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1) // t
    q_slot = qs_ref[0]                                  # [S*G, 1]
    lim = lim_ref[0]

    def step_body(i, carry):
        buf = i % 2

        start(i + 1, 1 - buf)       # past the last step: no live page
        wait(i, buf)
        # A page of the last step past the row's live prefix was not
        # fetched: what the V buffer holds there is stale, and 0 * NaN
        # would reach the accumulator through the matmul. (Stale K only
        # reaches scores the mask replaces.)
        def zero(p, carry):
            v_buf[buf, pl.ds(pl.multiple_of(p * t, t), t), :] = jnp.zeros(
                (t, v_buf.shape[2]), v_buf.dtype)
            return carry
        jax.lax.fori_loop(live_in(i), pps, zero, 0)

        slot = i * span + col
        mask = (slot <= q_slot) & (slot < lim)
        for kv in range(n_kv):
            q = q_ref[0, kv]                            # [S*G, D]
            lanes = slice(kv * head_dim, (kv + 1) * head_dim)
            k = k_buf[buf, :, lanes].astype(q.dtype)    # [pps*T, D]
            v = v_buf[buf, :, lanes].astype(q.dtype)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if has_scale:
                ks, vs = (_page_scales(r, i * pps, kv, page_of_col,
                                       n_kv, pps, max_blocks)
                          for r in (ks_ref, vs_ref))
                s = s * ks
            s = s * sm_scale
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[kv]                          # [S*G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # explicit zero (not just exp underflow): a fully-masked
            # step with m still at -inf would otherwise yield
            # exp(0) == 1 per position
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[kv] = l_ref[kv] * alpha + jnp.sum(p, axis=-1,
                                                    keepdims=True)
            if has_scale:
                p = p * vs
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[kv] = acc_ref[kv] * alpha + pv
            m_ref[kv] = m_new
        return carry

    jax.lax.fori_loop(0, n_steps, step_body, 0)

    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)
    row_live = m_ref[...] > _NEG_INF / 2
    o_ref[0] = jnp.where(row_live, acc_ref[...] / l, 0.0).astype(o_ref.dtype)


def _page_scales(scale_ref, first_page, kv, page_of_col, n_kv,
                 pages_per_step, max_blocks):
    """[1, pps*T] f32: the dequantization scale of KV head ``kv`` for
    each key column of the step that starts at page ``first_page``, from
    the row's ``[1, MB*KV]`` SMEM block. Built from scalars by selects:
    Mosaic has no gather."""
    out = jnp.zeros(page_of_col.shape, jnp.float32)
    for p in range(pages_per_step):
        # the last step may reach past the table; such a column is masked
        page = jnp.minimum(first_page + p, max_blocks - 1)
        out = jnp.where(page_of_col == p,
                        scale_ref[0, 0, page * n_kv + kv], out)
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
                           interpret: Optional[bool] = None) -> jax.Array:
    """Same contract as `ops.attention.paged_attention` (reference
    impl), fused. ``interpret=None`` resolves to True off-TPU."""
    T, MB = k_pool.shape[2], block_tables.shape[1]
    pool_kv_heads(k_pool, q)
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_live = live_pages(q_slots, kv_valid_len, T, MB)
    return _walk(q, k_pool, v_pool, block_tables, q_slots, n_live,
                 layer=layer, kv_valid_len=kv_valid_len,
                 sm_scale=sm_scale, k_scale=k_scale, v_scale=v_scale,
                 interpret=interpret)


def _walk(q, k_pool, v_pool, block_tables, q_slots, n_live, *, layer,
          kv_valid_len, sm_scale, k_scale, v_scale, interpret):
    """The kernel call, with each row's trip count ``n_live`` [B] given:
    `paged_attention_kernel` passes `live_pages`; a walk of all ``MB``
    entries gives the same bits (a fully masked step leaves the softmax
    state untouched), which is what the tests hold it to."""
    B, S, H, D = q.shape
    T = k_pool.shape[2]
    KV = pool_kv_heads(k_pool, q)
    MB = block_tables.shape[1]
    g = H // KV
    rows = S * g
    has_scale = k_scale is not None
    page_bytes = T * KV * D * k_pool.dtype.itemsize
    pps = max(1, min(_KEYS_PER_STEP // T, _STEP_BYTES // page_bytes, MB))

    # query row r = s*g + i of KV head kv is query s, head kv*g + i
    qg = q.reshape(B, S, KV, g, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, rows, D)
    qs = jnp.repeat(q_slots.astype(jnp.int32), g, axis=1)[..., None]
    bt = block_tables.astype(jnp.int32).reshape(-1)
    lim = jnp.asarray(kv_valid_len, jnp.int32).reshape(1)

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

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, rows, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, pps * T, KV * D), k_pool.dtype),
            pltpu.VMEM((2, pps * T, KV * D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((KV, rows, D), jnp.float32),
            pltpu.VMEM((KV, rows, 1), jnp.float32),
            pltpu.VMEM((KV, rows, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, sm_scale=sm_scale if sm_scale is not None else D ** -0.5,
        n_kv=KV, head_dim=D, block_tokens=T, pages_per_step=pps,
        max_blocks=MB, has_scale=has_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=sn.PAGED_KERNEL,
    )(bt, lim, n_live.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *args)
    return out.reshape(B, KV, S, g, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, D)

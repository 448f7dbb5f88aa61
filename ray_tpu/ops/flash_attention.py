"""Pallas TPU flash attention (forward AND backward kernels).

Online-softmax tiling keeps the working set in VMEM and the score matmuls
on the MXU; the kv block of a pair changes fastest so the (m, l, acc)
scratch accumulators persist across kv blocks for a fixed q block.
Backward is flash-style recompute in Pallas under `jax.custom_vjp`:
_bwd_dq_kernel (kv innermost) and _bwd_dkv_kernel (q innermost) re-derive
p from the saved row logsumexp.

A kernel's grid is `(b, h, pairs)`: the (q block, kv block) pairs of ONE
static schedule (`pair_schedule`), built in Python from the shapes and the
`causal` flag and handed to the kernel as a scalar-prefetch operand. The
index maps read a pair's block numbers from it, so a block the causal mask
rules out is neither a grid step nor a fetch, and the mask is built only in
a pair the diagonal (or kv padding) touches.

Semantics match `ray_tpu.ops.attention.mha_reference` exactly, including
the kv-prefix causal offset when Sq != Sk (decode) and GQA. Sequence
lengths that don't divide the block size are zero-padded; padded kv
columns are masked by global index, padded q rows are sliced off.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scope_names as sn

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _mask_rule(qi, ki, sk_orig, causal, kv_padded=True):
    """Which (global q row, global kv column) a score keeps. The ONE rule:
    `_attn_mask` evaluates it on a pair's iotas, `pair_schedule` at a
    pair's corner. `kv_padded` False (no zero-padded kv column exists)
    leaves the column compare out: it is true by construction."""
    mask = None
    if kv_padded:
        mask = ki < sk_orig  # zero-padded kv columns
    if causal:
        mask = (qi >= ki) if mask is None else mask & (qi >= ki)
    return mask


def _attn_mask(i, j, block_q, block_k, q_offset, sk_orig, causal,
               kv_padded=True):
    """Single source of truth for the fwd AND bwd score mask (they must
    agree exactly or the backward's recomputed softmax diverges)."""
    qi = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    ki = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return _mask_rule(qi, ki, sk_orig, causal, kv_padded)


def _block_contributes(i, j, block_q, block_k, q_offset, causal):
    """Causal block skip: kv block j contributes iff its first kv index
    <= the global position of q block i's last row."""
    if not causal:
        return True
    return j * block_k <= q_offset + i * block_q + block_q - 1


# Rows of a schedule's `pairs` operand, and what a pair's KIND says.
_OUTER, _INNER, _FIRST, _LAST, _KIND = range(5)
_DEAD, _INTERIOR, _MASKED = range(3)


@dataclasses.dataclass(frozen=True, eq=False)
class PairSchedule:
    """The (outer block, inner block) pairs one kernel steps through, in
    order: a (batch, head)'s whole grid. `first` / `last` bracket an outer
    block's run (init / write its accumulators); `masked` says the pair
    needs `_attn_mask` (the diagonal crosses it, or it holds padded kv
    columns); `dead` marks the one pair an outer block with NO live inner
    block keeps so that it is initialised and written (zeros): rows before
    every key when sq > sk."""
    pairs: np.ndarray           # int32 [5, pairs]: rows `_OUTER`..`_KIND`,
    #                             the kernels' scalar-prefetch operand
    rectangle: int              # pairs of the full nq x nk grid
    kv_padded: bool             # a zero-padded kv column exists
    # The grid axes behind (batch, head): `(pairs,)`, or, where the pairs
    # ARE the rectangle in row-major order, its `(outer, inner)` blocks, so
    # that an index map is the grid index and reads no table (a table read
    # costs a step 0.06-0.09 us, PERF.md PR 50; nothing is skipped to pay
    # for it).
    grid: Tuple[int, ...]

    outer = property(lambda self: self.pairs[_OUTER])
    inner = property(lambda self: self.pairs[_INNER])
    first = property(lambda self: self.pairs[_FIRST] == 1)
    last = property(lambda self: self.pairs[_LAST] == 1)
    masked = property(lambda self: self.pairs[_KIND] == _MASKED)
    dead = property(lambda self: self.pairs[_KIND] == _DEAD)

    def counts(self) -> Dict[str, int]:
        return {"live": int((~self.dead).sum()),
                "masked": int(self.masked.sum()),
                "rectangle": self.rectangle}


@functools.lru_cache(maxsize=None)
def pair_schedule(kernel: str, sq: int, sk: int, block_q: int, block_k: int,
                  causal: bool) -> PairSchedule:
    """The static schedule of `kernel` (a `scope_names.FLASH_*`) for q of
    `sq` rows against `sk` keys at these tiles (already clipped to the
    lengths): every pair `_block_contributes` accepts, once, inner block
    fastest. The forward and dq kernels run q blocks outermost, the dk/dv
    kernel kv blocks: the order every output accumulates in is the full
    rectangle's with its skipped steps left out."""
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    q_offset = sk - sq
    kv_padded = nk * block_k != sk
    q_outer = kernel != sn.FLASH_BWD_DKV
    rows = []
    for o in range(nq if q_outer else nk):
        run = []
        for n in range(nk if q_outer else nq):
            i, j = (o, n) if q_outer else (n, o)
            if not _block_contributes(i, j, block_q, block_k, q_offset,
                                      causal):
                continue
            # The rule is monotone: if the pair's first row keeps its last
            # column, every score of the pair is kept.
            whole = _mask_rule(q_offset + i * block_q,
                               j * block_k + block_k - 1, sk, causal)
            run.append([o, n, 0, 0, _INTERIOR if whole else _MASKED])
        run = run or [[o, 0, 0, 0, _DEAD]]
        run[0][_FIRST] = run[-1][_LAST] = 1
        rows += run
    pairs = np.asarray(rows, np.int32).T
    whole = len(rows) == nq * nk and _DEAD not in pairs[_KIND]
    return PairSchedule(pairs, rectangle=nq * nk, kv_padded=kv_padded,
                        grid=((nq, nk) if q_outer else (nk, nq)) if whole
                        else (len(rows),))


_PAIR_COUNTERS = {}


def _count_pairs(kernel: str, sched: PairSchedule, programs: int) -> None:
    """`flash_pairs_{live,masked,rectangle}_total`, labelled by kernel:
    what a call built here steps through and what the full grid would have
    held, counted once where the call is BUILT (at trace time). Imported
    here: a process that builds no flash call never loads the registry."""
    from ray_tpu.util.metrics import Counter

    for what, n in sched.counts().items():
        name = f"flash_pairs_{what}_total"
        if name not in _PAIR_COUNTERS:
            _PAIR_COUNTERS[name] = Counter(
                name, f"(q block, kv block) pairs of flash calls built: "
                f"{what}", tag_keys=("kernel",))
        if n:
            _PAIR_COUNTERS[name].inc(n * programs, tags={"kernel": kernel})


def _pair(pairs_ref, rectangular):
    """This grid step's (outer block, inner block, first, last, kind)."""
    p = pl.program_id(2)
    if rectangular:     # `PairSchedule.grid`: axes (outer, inner)
        p = p * pl.num_programs(3) + pl.program_id(3)
    return tuple(pairs_ref[r, p] for r in range(5))


def _per_kind(kind, kinds, body):
    """`body(masked)` for an interior pair and again, with the mask, for a
    masked one, each only if the schedule holds such a pair (`kinds`); a
    dead pair runs neither."""
    for this in (_INTERIOR, _MASKED):
        if this in kinds:
            pl.when(kind == this)(functools.partial(body, this == _MASKED))


def _fwd_kernel(pairs_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                l_ref, *, sm_scale: float, kinds, rectangular, **mask_kw):
    """One (q block, kv block) pair of `pair_schedule`, kv fastest.
    `mask_kw` is `_attn_mask`'s: q_offset = sk_orig - sq_orig (kv-prefix
    shift for decode); sk_orig masks zero-padded kv columns."""
    i, j, first, last, kind = _pair(pairs_ref, rectangular)

    @pl.when(first == 1)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _body(masked):
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
        if masked:
            s = jnp.where(_attn_mask(i, j, **mask_kw), s, _NEG_INF)
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

    _per_kind(kind, kinds, _body)

    @pl.when(last == 1)
    def _finalize():
        # l == 0 for zero-padded q rows (sliced off by the caller).
        # m == -inf marks FULLY-MASKED rows (decode with Sq > Sk): they
        # attend to nothing and must output exactly zero — without this,
        # p = exp(-inf - -inf) = 1 leaks uniform weights into acc.
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        row_live = m_ref[:] > _NEG_INF / 2
        o_ref[0, 0] = jnp.where(row_live, acc_ref[:] / l,
                                0.0).astype(o_ref.dtype)


def _fwd_kernel_lse(pairs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                    m_ref, l_ref, **kw):
    """Forward that also writes the row logsumexp (for the Pallas
    backward): lse = m + log(l)."""
    _fwd_kernel(pairs_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                **kw)

    @pl.when(_pair(pairs_ref, kw["rectangular"])[_LAST] == 1)
    def _write_lse():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        lse_ref[0, 0] = m_ref[:] + jnp.log(l)  # [bq, 1]


def _pad_seq(x, block):
    s = x.shape[2]
    pad = (-s) % block
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def _scheduled_call(kernel_fn, name, programs, sq, sk, block_q, block_k,
                    causal, sm_scale, *, in_specs, out_specs, out_shape,
                    scratch_shapes, interpret):
    """`pallas_call` of `kernel_fn` as kernel `name` over grid (b, h,
    *`sched.grid`) of its `pair_schedule`, the schedule its one
    scalar-prefetch operand. The kernel's static arguments: `_attn_mask`'s,
    and the pair kinds the schedule holds (a body is emitted only for a
    kind that occurs)."""
    sched = pair_schedule(name, sq, sk, block_q, block_k, causal)
    _count_pairs(name, sched, programs[0] * programs[1])
    kernel = functools.partial(
        kernel_fn, sm_scale=sm_scale,
        kinds=frozenset(sched.pairs[_KIND].tolist()),
        rectangular=len(sched.grid) == 2, causal=causal, block_q=block_q,
        block_k=block_k, q_offset=sk - sq, sk_orig=sk,
        kv_padded=sched.kv_padded)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(*programs, *sched.grid),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (1 + len(sched.grid))
            + ("arbitrary",)),
        interpret=interpret,
        name=name,
    )
    return functools.partial(call, jnp.asarray(sched.pairs))


def _block_specs(block_q, block_k, d, grp, q_row):
    """(q-side spec of a given width, kv-side spec of q head h's KV head,
    kv-side spec a q head) for a grid (b, h, *`sched.grid`) whose pairs
    name the q block in row `q_row` of the schedule and the kv block in
    the other."""
    kv_row = _INNER if q_row == _OUTER else _OUTER

    def block(row, *at):
        """The pair's block number: `at` is the grid position behind
        (batch, head), then the schedule."""
        *ids, pairs = at
        return ids[row] if len(ids) == 2 else pairs[row, ids[0]]

    def q_side(width):
        return pl.BlockSpec(
            (1, 1, block_q, width),
            lambda b_, h_, *at: (b_, h_, block(q_row, *at), 0))

    kv_side = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda b_, h_, *at: (b_, h_ // grp, block(kv_row, *at), 0))
    kv_side_per_head = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda b_, h_, *at: (b_, h_, block(kv_row, *at), 0))
    return q_side, kv_side, kv_side_per_head


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
    sq_p = qp.shape[2]
    q_side, kv_side, _ = _block_specs(block_q, block_k, d, g, _OUTER)
    out_specs = q_side(d)
    out_shape = jax.ShapeDtypeStruct(qp.shape, q.dtype)
    if with_lse:
        # [B,H,Sq,1] keeps the last-two block dims TPU-tileable
        # ((block_q, 1) with 1 == full trailing dim).
        out_specs = [out_specs, q_side(1)]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b, h, sq_p, 1), jnp.float32)]
    result = _scheduled_call(
        _fwd_kernel_lse if with_lse else _fwd_kernel, sn.FLASH_FWD, (b, h),
        sq, sk, block_q, block_k, causal, sm_scale,
        in_specs=[q_side(d), kv_side, kv_side],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    if with_lse:
        out, lse = result
        out = out[:, :, :sq] if sq_p != sq else out
        lse = lse[:, :, :sq] if sq_p != sq else lse
        return out, lse
    out = result
    return out[:, :, :sq] if sq_p != sq else out


def _bwd_dq_kernel(pairs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, acc_ref, *, sm_scale, kinds,
                   rectangular, **mask_kw):
    """dq for one q block, accumulated over its kv blocks (the pair's
    inner block). ds = p * (dO v^T - delta) * scale; dq += ds k."""
    i, j, first, last, kind = _pair(pairs_ref, rectangular)

    @pl.when(first == 1)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _body(masked):
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
        if masked:
            s = jnp.where(_attn_mask(i, j, **mask_kw), s, _NEG_INF)
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

    _per_kind(kind, kinds, _body)

    @pl.when(last == 1)
    def _fin():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(pairs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale,
                    kinds, rectangular, **mask_kw):
    """dk/dv for one kv block (per q head — GQA groups reduced outside),
    accumulated over its q blocks (the pair's inner block)."""
    j, i, first, last, kind = _pair(pairs_ref, rectangular)  # kv outermost

    @pl.when(first == 1)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _body(masked):
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
        if masked:
            s = jnp.where(_attn_mask(i, j, **mask_kw), s, _NEG_INF)
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

    _per_kind(kind, kinds, _body)

    @pl.when(last == 1)
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

    # --- dq: q blocks outermost, a q block's kv blocks innermost ---
    schedule = (sq, sk, block_q, block_k, causal, sm_scale)
    q_side, kv_side, _ = _block_specs(block_q, block_k, d, grp, _OUTER)
    dq = _scheduled_call(
        _bwd_dq_kernel, sn.FLASH_BWD_DQ, (b, h), *schedule,
        in_specs=[q_side(d), kv_side, kv_side, q_side(d), q_side(1),
                  q_side(1)],
        out_specs=q_side(d),
        out_shape=jax.ShapeDtypeStruct(qp.shape, dq_dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, gp, lse, delta)

    # --- dk/dv: kv blocks outermost, a kv block's q blocks innermost;
    # per-q-head then group reduce (GQA) ---
    q_side, kv_side, kv_out = _block_specs(block_q, block_k, d, grp, _INNER)
    dk_h, dv_h = _scheduled_call(
        _bwd_dkv_kernel, sn.FLASH_BWD_DKV, (b, h), *schedule,
        in_specs=[q_side(d), kv_side, kv_side, q_side(d), q_side(1),
                  q_side(1)],
        out_specs=[kv_out, kv_out],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk_p, d), dk_dtype),
            jax.ShapeDtypeStruct((b, h, sk_p, d), dv_dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
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

"""The gated delta rule (Gated DeltaNet's recurrence), in the forms a
serving engine needs.

A head keeps a matrix state ``S`` ``[dk, dv]`` float32. A token with key
``k`` and query ``q`` (both L2-normalised, ``q`` scaled), value ``v``,
log-decay ``g <= 0`` and write strength ``beta`` in (0, 1) does

    S <- exp(g) S;   d = beta (v - S^T k);   S <- S + k d^T;   o = S^T q

`delta_step` is that, for one token a row (a decode step): two reductions
over the state as it was (``S^T k`` and ``S^T q``; ``o`` follows from them
without a second look at the new state: ``o = exp(g) S^T q + (k . q) d``)
and one pass that writes the new state. It is the plain form: what the
CPU runs, what a one-token chunk of a prefill group takes, what the kernel
is tested against.

`delta_step_plane` is the same update as ONE pass, in place on the state
plane ``[layers, slots, H, dk, dv]`` (Pallas/Mosaic), for a decode token on
the chip: a grid step brings one live row's block of heads into VMEM once,
takes both reductions from it there and writes ``exp(g) S + k d^T`` back
to the same place (the plane is an aliased input and output), where XLA's
`delta_step` on ``plane[layer]`` reads a row's state twice and puts the
layer back through a slice (PERF.md PR 49). The work is bytes: a row's
2 MiB read and written once a layer is the floor.

`delta_chunks` is the chunkwise form for a prefill chunk (the published
algorithm: Yang et al., "Gated Delta Networks", and the WY representation
of "Parallelizing Linear Transformers with the Delta Rule"). With ``G_i``
the running sum of ``g`` inside a chunk of `CHUNK` tokens and

    A[i, j] = beta_i (k_i . k_j) exp(G_i - G_j),  j < i   (else 0)
    T = (I + A)^-1
    U = T (beta v),   W = T (beta k exp(G))

a chunk that starts from state ``S`` has, for all its tokens at once,

    V' = U - W S                                  (the d of every token)
    O  = (q exp(G)) S + tril(q k^T exp(G_i - G_j)) V'
    S <- exp(G_last) S + (k exp(G_last - G_i))^T V'

so the state moves through HBM once a chunk and not once a token, and all
that is sequential is a scan over chunks. ``T`` is the inverse of a unit
lower-triangular matrix: it is built by halves (`_unit_lower_inverse`:
``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``, six levels for
64), which is forward substitution in blocks and as stable, where the
series ``I - A + A^2 - ...`` cancels catastrophically once keys repeat.

A position that is not ``live`` (bucket filler behind a chunk's real
tokens, a frozen row) has ``g = 0`` and ``beta = 0``: it writes nothing
and decays nothing, so the state after the chunk is the state after its
live prefix.

Everything the state touches is float32; the matmuls take their operands
in ``dtype`` (the model's, bf16 on the chip) and accumulate in float32,
the triangular inverse runs at the highest precision.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scope_names as sn

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def l2norm(x, eps: float = 1e-6):
    """x / sqrt(sum(x^2) + eps) over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_step(state, q, k, v, g, beta, live):
    """One token a row. ``state`` [B, H, dk, dv] float32; ``q``, ``k``
    [B, H, dk] and ``v`` [B, H, dv] float32 (q, k normalised, q scaled);
    ``g``, ``beta`` [B, H] float32; ``live`` [B] bool: only live rows
    advance. Returns (o [B, H, dv] float32, the new state)."""
    with jax.named_scope(sn.GDN_STEP):
        decay = jnp.exp(g)[..., None]                          # [B, H, 1]
        # both reductions read the state as it was (multiply and add, not
        # a matmul of one row: exact float32, and one fused read)
        ks = jnp.sum(k[..., :, None] * state, axis=-2)
        qs = jnp.sum(q[..., :, None] * state, axis=-2)
        d = beta[..., None] * (v - decay * ks)
        o = decay * qs + jnp.sum(k * q, axis=-1, keepdims=True) * d
        new = decay[..., None] * state + k[..., :, None] * d[..., None, :]
        return o, jnp.where(live[:, None, None, None], new, state)


# Heads of one row a grid step of `delta_step_plane`. The step's state
# block goes in and out, each double-buffered: 4 x heads x dk x dv x 4 B,
# 8 MiB at the published 32 heads of 128 x 128, inside the default scoped
# 16 MiB (no `vmem_limit_bytes`: PERF.md PR 30). Measured on a v5e, 64
# live rows, one layer of a 6-layer plane (PR 49, PERF.md section 6), ms a
# layer-call: 8 heads a step 0.502, 16 0.449, 32 0.438, so a whole row is
# asked for (XLA's `delta_step` on the layer's slice: 0.609). The DMAs
# bound it: the same blocks copied in and out with no arithmetic take
# 0.418, which is what this HBM gives a read and a write side by side
# (727 GB/s reading alone, 627 writing alone, 0.399 one after the other).
# The VPU's work a head (two sums over dk, the columns' lane broadcasts,
# the rank-one update) just hides under the head's DMA: a third sum a
# head (k . q taken in the kernel) cost 16 %.
_STEP_HEADS = 32
# Heads a trip of the kernel's loop over a block's heads (the body is
# traced once a trip's heads: 32 heads unrolled cost the cell 0.9 s of
# tracing and lowering in its four decode programs). Mosaic unrolls a loop
# whole or not at all, so a trip's heads are written out: the same call,
# ms a layer-call, 1 head a trip 0.513 (the VPU's chain a head is exposed),
# 2 0.453, 4 0.440, 8 0.438, all 32 unrolled 0.440.
_TRIP_HEADS = 8
_VMEM_BUDGET = 16 * 2 ** 20


def delta_step_heads(heads: int, dk: int, dv: int) -> Optional[int]:
    """The heads a grid step of `delta_step_plane` takes: the largest
    divisor of ``heads`` up to `_STEP_HEADS` that is whole tiles of the
    per-head rows (a multiple of 8 sublanes, or all the heads) and whose
    blocks fit the default scoped VMEM, or None where a head's ``dk x dv``
    is not whole float32 tiles or nothing fits."""
    if dk % 8 or dv % 128:
        return None
    for hb in range(min(_STEP_HEADS, heads), 0, -1):
        if heads % hb or (hb % 8 and hb != heads):
            continue
        held = (4 * hb * dk * dv * 4              # state in and out, twice
                + 2 * dk * (2 * heads + -2 * heads % 128) * 4     # columns
                + 2 * 2 * (hb + -hb % 8) * dv * 4     # v in, o out
                + 3 * _TRIP_HEADS * dk * dv * 4)  # a trip's temporaries
        if held <= _VMEM_BUDGET:
            return hb
    return None


def live_rows(live):
    """``live`` [B] bool -> (the live rows' ids first, in order, [B] int32;
    how many, int32): what `delta_step_plane`'s grid walks. Ids past the
    count are 0. A one-hot select and a sum: no sort, no scatter."""
    b = live.shape[0]
    place = jnp.cumsum(live, dtype=jnp.int32) - 1
    at = live[None, :] & (place[None, :] == jnp.arange(b)[:, None])
    ids = jnp.sum(jnp.where(at, jnp.arange(b, dtype=jnp.int32)[None, :], 0),
                  axis=1)
    return ids, live.sum(dtype=jnp.int32)


def _step_kernel(layer_ref, rows_ref, n_ref, c_ref, s_ref, col_ref, v_ref,
                 out_ref, o_ref, *, hb: int, heads: int):
    del layer_ref                          # the index maps read it
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    lanes = col_ref.shape[2]

    # no live row: the one block every step maps to goes back as it came
    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _():
        out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    def head(hl, scalars):
        """Head ``hl`` of this step's block (a traced index: the loop's body
        is traced once). What meets the state's SUBLANE axis is a column
        ``[dk, 1]`` that broadcasts along the lanes: a head's key and query
        lie a lane a head in ``col`` [dk, 2 H ..], and a lane roll by the
        head's index brings them to lanes 0 and H. What scales a row
        ``[1, dv]`` or the whole block is a scalar from SMEM."""
        h = j * hb + hl
        s = s_ref[0, 0, hl]                                   # [dk, dv]
        decay, beta, kq = (c_ref[scalars + r * heads + h] for r in range(3))
        cols = pltpu.roll(col_ref[0], lanes - h, 1)
        kc, qc = cols[:, 0:1], cols[:, heads:heads + 1]       # [dk, 1]
        # both reductions over dk from the block as it lies in VMEM
        ks = jnp.sum(kc * s, axis=0, keepdims=True)           # [1, dv]
        qs = jnp.sum(qc * s, axis=0, keepdims=True)
        d = beta * (v_ref[0, pl.ds(hl, 1), :] - decay * ks)
        o_ref[0, pl.ds(hl, 1), :] = decay * qs + kq * d
        out_ref[0, 0, hl] = decay * s + kc * d

    # a step past the live rows holds the last live block and does nothing
    @pl.when(i < n)
    def _():
        scalars = rows_ref[i] * (3 * heads)
        trip = math.gcd(hb, _TRIP_HEADS)

        def some(t, carry):
            for u in range(trip):
                head(t * trip + u, scalars)
            return carry

        jax.lax.fori_loop(0, hb // trip, some, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "hb"))
def _step_call(plane, layer, rows, n_live, q, k, v, g, beta, *,
               interpret: bool, hb: int):
    _, b, hv, dk, dv = plane.shape
    n_blocks = hv // hb
    f32 = jnp.float32
    # a head's three scalars: exp(g), beta, k . q, taken here as
    # `delta_step` takes them (the kernel's VPU has no room for a third
    # sum a head: PERF.md PR 49), prefetched into SMEM: [B, 3, H] flat
    c = jnp.stack([jnp.exp(g), beta, jnp.sum(k * q, axis=-1)],
                  axis=1).astype(f32).reshape(-1)
    # a head's key and query as columns, a lane a head: [B, dk, 2 H] padded
    # to whole lane tiles, 64 KiB a row at the published widths (``[..,
    # dk, 1]`` in HBM would be padded 128-fold by the tiling)
    col = jnp.concatenate([jnp.swapaxes(k.astype(f32), 1, 2),
                           jnp.swapaxes(q.astype(f32), 1, 2)], axis=2)
    col = jnp.pad(col, ((0, 0), (0, 0), (0, -2 * hv % 128)))

    def row(i, rows, n):
        return rows[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]

    def block(i, j, n):
        return jnp.where(i < n[0], j, n_blocks - 1)

    def state_map(i, j, layer, rows, n, c):
        return (layer[0], row(i, rows, n), block(i, j, n), 0, 0)

    def row_map(i, j, layer, rows, n, c):
        return (row(i, rows, n), 0, 0)

    def heads_map(i, j, layer, rows, n, c):
        return (row(i, rows, n), block(i, j, n), 0)

    state_spec = pl.BlockSpec((1, 1, hb, dk, dv), state_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(b, n_blocks),
        in_specs=[state_spec,
                  pl.BlockSpec((1, dk, col.shape[2]), row_map),
                  pl.BlockSpec((1, hb, dv), heads_map)],
        out_specs=[state_spec, pl.BlockSpec((1, hb, dv), heads_map)])
    new, o = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, heads=hv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(plane.shape, f32),
                   jax.ShapeDtypeStruct((b, hv, dv), f32)],
        # operand 4 (after the four prefetched into SMEM) is the plane, and
        # it is output 0: updated where it lies
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name=sn.DELTA_STEP_KERNEL,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      jnp.reshape(n_live, (1,)).astype(jnp.int32), c, plane, col,
      v.astype(f32))
    return o, new


def delta_step_plane(plane, layer, q, k, v, g, beta, live, *, walk=None,
                     interpret: Optional[bool] = None,
                     hb: Optional[int] = None):
    """`delta_step` on ``plane[layer]``, in place. ``plane`` [L, B, H, dk,
    dv] float32, the state of all layers and slots (inside a program that
    owns it the result takes its place: no copy); ``layer`` int32; ``q``,
    ``k``, ``v``, ``g``, ``beta``, ``live`` as `delta_step` takes them;
    ``walk`` is `live_rows(live)`, for a caller whose layers share it.
    Returns (o [B, H, dv] float32, the plane). Only the live rows' blocks
    of layer ``layer`` are read or written: everything else of the plane,
    a dead row's state included, stays bit for bit; a dead row's ``o`` is
    0 (`delta_step` reads its state for one; nothing reads it).

    Grid ``(B, H / hb)`` over the live rows first: step (i, j) holds the
    block ``(layer, rows[i], j)``; steps past the count map to the LAST
    live block, row and head block both, so they fetch nothing, write
    nothing and the block the last live step wrote goes back once, as
    written. Float32 throughout, multiplies and adds on the VPU (no MXU
    product: its operands would be rounded or its latency paid a head):
    against `delta_step` only the order of the two sums over ``dk``
    differs. ``interpret=None`` resolves to True off the TPU; ``hb`` None
    is `delta_step_heads`'s."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, _, hv, dk, dv = plane.shape
    hb = hb or delta_step_heads(hv, dk, dv)
    with jax.named_scope(sn.GDN_STEP):
        rows, n_live = live_rows(live) if walk is None else walk
        o, new = _step_call(plane, layer, rows, n_live, q, k, v, g, beta,
                            interpret=bool(interpret), hb=hb)
        return jnp.where(live[:, None, None], o, 0.0), new


def _unit_lower_inverse(a):
    """(I + a)^-1 for ``a`` [..., n, n] STRICTLY lower triangular, n a
    power of two, by halves from 1 x 1 blocks up, on whole n x n matrices
    (blocks of 1, 2 or 4 as arrays of their own would leave a tile nearly
    empty). ``x`` holds the inverses of the diagonal blocks of size b and
    zeros elsewhere; ``low`` is ``a`` inside the lower-left block of each
    PAIR of them, and ``x - x low x`` fills exactly those blocks with
    ``-Q^-1 R P^-1``."""
    n = a.shape[-1]
    i = jnp.arange(n)
    x = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
    b = 1
    while b < n:
        pair = (i[:, None] // (2 * b)) == (i[None, :] // (2 * b))
        low = jnp.where(pair & ((i[:, None] // b) % 2 == 1)
                        & ((i[None, :] // b) % 2 == 0), a, 0.0)
        x = x - jnp.einsum("...ij,...jk,...kl->...il", x, low, x,
                           precision=_HI)
        b *= 2
    return x


def delta_chunks(state, q, k, v, g, beta, live, dtype):
    """A chunk of ``S`` tokens a row, `CHUNK` at a time. ``state``
    [B, H, dk, dv] float32; ``q``, ``k`` [B, S, H, dk], ``v`` [B, S, H, dv]
    float32; ``g``, ``beta`` [B, S, H] float32; ``live`` [B, S] bool, a
    prefix of each row. Returns (o [B, S, H, dv] float32, the state after
    each row's live tokens)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    pad = -S % CHUNK
    with jax.named_scope(sn.GDN_CHUNK):
        lv = live[..., None]
        g = jnp.where(lv, g, 0.0)
        beta = jnp.where(lv, beta, 0.0)
        if pad:
            q, k, v, g, beta = (
                jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                for x in (q, k, v, g, beta))
        n = (S + pad) // CHUNK

        def chunked(x):                 # [B, n*C, H, ...] -> [n, B, H, C, ...]
            x = x.reshape(B, n, CHUNK, *x.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

        q, k, v, g, beta = (chunked(x) for x in (q, k, v, g, beta))
        G = jnp.cumsum(g, axis=-1)                             # [n,B,H,C]
        tri = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
        # exp(G_i - G_j) where j <= i, 0 above the diagonal
        decay = jnp.exp(jnp.where(tri, G[..., :, None] - G[..., None, :],
                                  -jnp.inf))
        kb = k * beta[..., None]
        kk = jnp.einsum("...ik,...jk->...ij", kb, k, precision=_HI)
        a = jnp.where(jnp.tril(tri, -1), kk * decay, 0.0)
        t = _unit_lower_inverse(a)
        u = jnp.einsum("...ij,...jv->...iv", t, v * beta[..., None],
                       precision=_HI)
        w = jnp.einsum("...ij,...jk->...ik", t, kb * jnp.exp(G)[..., None],
                       precision=_HI)
        qk = jnp.einsum("...ik,...jk->...ij", q.astype(dtype),
                        k.astype(dtype), preferred_element_type=f32) * decay
        q_in = (q * jnp.exp(G)[..., None]).astype(dtype)
        g_last = G[..., -1]                                    # [n,B,H]
        k_out = (k * jnp.exp(g_last[..., None] - G)[..., None]).astype(dtype)

        def step(s, xs):
            u_c, w_c, qk_c, q_c, k_c, gl = xs
            sd = s.astype(dtype)
            vn = u_c - jnp.einsum("bhck,bhkv->bhcv", w_c.astype(dtype), sd,
                                  preferred_element_type=f32)
            o = jnp.einsum("bhck,bhkv->bhcv", q_c, sd,
                           preferred_element_type=f32) \
                + jnp.einsum("bhij,bhjv->bhiv", qk_c.astype(dtype),
                             vn.astype(dtype), preferred_element_type=f32)
            s = jnp.exp(gl)[..., None, None] * s \
                + jnp.einsum("bhck,bhcv->bhkv", k_c, vn.astype(dtype),
                             preferred_element_type=f32)
            return s, o

        state, o = jax.lax.scan(step, state,
                                (u, w, qk, q_in, k_out, g_last))
        # [n, B, H, C, dv] -> [B, n*C, H, dv]
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(
            B, n * CHUNK, H, dv)
        return o[:, :S], state

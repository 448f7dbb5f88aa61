"""The sorted form's expert matmuls for a layer that HOLDS a share of the
experts (a prefill chunk), as ONE grouped gated FFN whose grid visits only
the groups that hold rows (Pallas/Mosaic).

The rows that landed on the held experts arrive sorted by expert, so an
expert's rows are one contiguous range. XLA's `ragged_dot` is handed the
stacks of ALL layers (so that no layer's share is sliced out and copied)
and walks every group of them, three times a layer; at 128 held groups of
1,024 that walk, not the weights' bytes, is what a call costs (PERF.md PR
45). Here the schedule of VISITS is worked out ahead of the call and
scalar-prefetched: a visit is one (group, row tile) pair that has rows, in
group order, so

- a group of another layer, or a held group without rows, is never a grid
  step and nothing of it is fetched;
- consecutive visits of one group (a group across a row-tile boundary)
  name the same weight blocks, which the pipeline does not fetch again:
  every matrix that is read is read once;
- consecutive visits of one row tile (several small groups in it) keep the
  tile's rows and its result in VMEM; a tile past the rows that landed is
  never visited.

    xs     [c, d]           the window's rows, sorted by expert
    sched                   `visit_schedule(starts, sizes, c)`
    first  int32            this layer's first group in the stacks
    w1/w3  [N, d, f], w2 [N, f, d]   the stacks of ALL layers, whole

Returns ``down_g(silu(gate_g x) * up_g x)`` for every row of a group, [c,
d] in xs' dtype; rows of no group are zero where their tile was visited
and unspecified where it was not (the caller adds neither). Products take
bf16 operands and accumulate in float32; the gate and up results are not
rounded before ``silu(gate) * up``.

Grid ``(visits, f / tf)``, both sequential: a step reads one f-tile of one
group's three matrices (3 x d x tf values) and adds ``act @ w2`` for the
group's rows of the tile, masked, to a float32 accumulator a tile.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scope_names as sn

__all__ = ["held_grouped_ffn", "held_grouped_ffn_reference",
           "held_grouped_tiles", "visit_schedule", "ROW_TILE"]

# Rows a visit: the MXU's height. A weight tile handed to the MXU costs its
# 128 cycles whether 40 rows stream past it or 128, and a group's 6 MiB take
# 7.7 us to arrive where 128 rows take 4.1 to multiply (Qwen3-Next's widths
# on a v5e): taller tiles turn the kernel compute-bound on masked rows.
ROW_TILE = 128
# What a step may hold in VMEM: the default scoped 16 MiB, never more
# (asking for more takes room XLA gives the program around the call:
# PERF.md PR 30). Qwen3-Next's whole expert a step (tf = f = 512) counts
# 15.75 MiB below and compiles; on a v5e, ms a layer-call of 5,120 landed
# rows (167 visits), tf 128 / 256 / 512: 1.52 / 1.51 / 1.33, where three
# `ragged_dot` take 2.82 and the 805 MB take 0.98 (PR 45). An f-tile
# narrower than f reads a group's matrices again on its second visit.
_VMEM_BUDGET = 16 * 2 ** 20


class Schedule(NamedTuple):
    group: jax.Array      # [V] int32: a visit's group (0 .. eh - 1)
    tile: jax.Array       # [V] int32: a visit's row tile
    lo: jax.Array         # [eh] int32: a group's first row
    hi: jax.Array         # [eh] int32: one past its last
    n: jax.Array          # int32: live visits (those before it)


def held_grouped_tiles(d: int, f: int, dtype) -> Optional[Tuple[int, int]]:
    """(rows a visit, values of f a step) for experts of ``d x f``, or None
    where no f-tile leaves a step's working set inside the budget (the rows
    and the result of a visit are whole rows of d: at d 7,168 they and three
    128-wide weight tiles, double-buffered, pass 16 MiB)."""
    isz = jnp.dtype(dtype).itemsize
    tm = ROW_TILE
    # f itself (always a legal block), then the multiples of 128 under it
    for tf in [f] + list(range((f - 1) // 128 * 128, 0, -128)):
        if f % tf:
            continue
        held = (2 * 3 * d * tf * isz          # weight blocks, two buffers
                + 2 * 2 * tm * d * isz        # rows in, result out
                + tm * d * 4                  # accumulator
                + 3 * tm * tf * 4)            # gate, up, act
        if held <= _VMEM_BUDGET:
            return tm, tf
    return None


def visit_schedule(starts, sizes, c: int, tm: int = ROW_TILE) -> Schedule:
    """The visits of a window of ``c`` rows whose group j is rows
    ``starts[j] .. starts[j] + sizes[j] - 1`` (ascending, disjoint): every
    (group, row tile) pair that has rows, by group then tile. At most
    ``c / tm + groups`` of them; the entries past `n` repeat the last live
    one, so they name the blocks that are already there."""
    eh = sizes.shape[0]
    n_tiles = -(-c // tm)
    v_max = n_tiles + eh
    lo = starts.astype(jnp.int32)
    hi = lo + sizes.astype(jnp.int32)
    t0 = lo // tm
    nt = jnp.where(hi > lo, (hi - 1) // tm - t0 + 1, 0)
    ends = jnp.cumsum(nt)
    n = ends[-1]
    v = jnp.arange(v_max, dtype=jnp.int32)
    v = jnp.minimum(v, jnp.maximum(n - 1, 0))
    group = jnp.minimum(
        jnp.sum(ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        eh - 1)
    tile = jnp.clip(t0[group] + v - (ends[group] - nt[group]), 0,
                    n_tiles - 1)
    return Schedule(group, tile.astype(jnp.int32), lo, hi,
                    n.astype(jnp.int32))


def _kernel(grp_ref, tile_ref, lo_ref, hi_ref, meta_ref,
            x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_ref):
    v, j = pl.program_id(0), pl.program_id(1)
    tm = x_ref.shape[0]

    @pl.when(v < meta_ref[0])
    def _():
        g, t = grp_ref[v], tile_ref[v]
        fresh = (v == 0) | (t != tile_ref[jnp.maximum(v - 1, 0)])

        @pl.when(fresh & (j == 0))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        x = x_ref[...]
        gate = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= lo_ref[g]) & (row < hi_ref[g])
        act = jnp.where(mine, gate * jax.nn.sigmoid(gate) * up, 0.0)
        acc_ref[...] += jnp.dot(act.astype(x.dtype), w2_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "tm", "tf"))
def _call(xs, sched, first, w1, w3, w2, *, interpret: bool, tm: int,
          tf: int):
    c, d = xs.shape
    f = w1.shape[2]
    n_t = f // tf
    pad = -c % tm
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    v_max = sched.group.shape[0]

    def f_tile(v, j, meta):
        # a dead visit names the last live step's blocks: nothing moves
        return jnp.where(v < meta[0], j, n_t - 1)

    def rows_map(v, j, grp, tile, lo, hi, meta):
        return (tile[v], 0)

    def up_map(v, j, grp, tile, lo, hi, meta):
        return (meta[1] + grp[v], 0, f_tile(v, j, meta))

    def down_map(v, j, grp, tile, lo, hi, meta):
        return (meta[1] + grp[v], f_tile(v, j, meta), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(v_max, n_t),
        in_specs=[pl.BlockSpec((tm, d), rows_map),
                  pl.BlockSpec((1, d, tf), up_map),
                  pl.BlockSpec((1, d, tf), up_map),
                  pl.BlockSpec((1, tf, d), down_map)],
        out_specs=pl.BlockSpec((tm, d), rows_map),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)])
    meta = jnp.stack([sched.n, jnp.asarray(first, jnp.int32)])
    out = pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c + pad, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name=sn.HELD_GROUPED_KERNEL,
    )(sched.group, sched.tile, sched.lo, sched.hi, meta, xs, w1, w3, w2)
    return out[:c] if pad else out


def held_grouped_ffn(xs, sched: Schedule, first, w1, w3, w2, *,
                     interpret: Optional[bool] = None,
                     tf: Optional[int] = None):
    """See the module docstring. ``interpret=None`` resolves to True off
    the TPU; ``tf`` overrides `held_grouped_tiles`' choice (a test's)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tm = ROW_TILE
    if tf is None:
        tm, tf = held_grouped_tiles(xs.shape[1], w1.shape[2], xs.dtype)
    return _call(xs, sched, first, w1, w3, w2, interpret=bool(interpret),
                 tm=tm, tf=tf)


def held_grouped_ffn_reference(xs, starts, sizes, first, w1, w3, w2):
    """The same rows in plain `lax`: a loop over this layer's groups, each
    multiplying every row and keeping its own. Rows of no group are zero."""
    row = jnp.arange(xs.shape[0])[:, None]

    def one(j, out):
        e = first + j
        gate = jnp.dot(xs, w1[e], preferred_element_type=jnp.float32)
        up = jnp.dot(xs, w3[e], preferred_element_type=jnp.float32)
        act = (gate * jax.nn.sigmoid(gate) * up).astype(xs.dtype)
        ys = jnp.dot(act, w2[e], preferred_element_type=jnp.float32)
        mine = (row >= starts[j]) & (row < starts[j] + sizes[j])
        return jnp.where(mine, ys, out)

    return jax.lax.fori_loop(
        0, sizes.shape[0], one,
        jnp.zeros(xs.shape, jnp.float32)).astype(xs.dtype)

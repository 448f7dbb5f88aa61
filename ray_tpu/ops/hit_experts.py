"""The expert layer of a few tokens (a decode step), reading only the
experts some live row chose (Pallas/Mosaic): a grouped gated FFN whose
groups are the HIT experts, every one of them multiplying all the rows.

At decode a layer's experts are read whatever the routing once a handful
of rows spread over them, so XLA's every-row x every-expert einsum is the
right form for a full batch (it streams the 805 MB of an OLMoE layer at 91
% of a v5e's HBM peak). With 8 of 32 slots live a third of those bytes
belong to experts no live row chose (PERF.md PR 34). Here the ids of the
hit experts, compacted to the front, are scalar-prefetched and the weight
blocks' index maps read them: the pipeline fetches hit expert i + 1 while
i multiplies, and an expert nobody chose is never touched.

    x     [G, d]            the rows (dead ones too: their weights are 0)
    cw    [E, G] f32        entry i's weight for each row: the combine
                            column of expert ``ids[i]`` (0: not chosen)
    ids   [E] int32         the hit experts' rows in the stacks, first;
                            entries from ``n_hit`` on are not read
    n_hit int32             how many entries are live
    w1/w3 [N, d, f], w2 [N, f, d]   the stacks: ALL layers' experts, so
                            that no layer's experts are sliced out of the
                            layer scan's operand and copied for the call

Returns ``sum_i cw[i, g] * down_i(silu(gate_i x_g) * up_i x_g)`` as float32
[G, d]. Grid ``(E entries, f / tf)``: a step reads one f-tile of one
expert's three matrices (3 x d x tf values) and adds its part to the
result, which stays resident in VMEM across the whole grid. Entries past
``n_hit`` map to the block the last live step used, so they fetch nothing,
and skip their compute.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scope_names as sn

__all__ = ["hit_experts_ffn", "hit_experts_ffn_reference",
           "hit_experts_tile"]

# Values of f a step: 3 x d x tf x 2 B = 6 MiB at d 2048, 12 double
# buffered, inside the default scoped VMEM (asking for more takes room XLA
# gives the program around the call: PERF.md PR 30). On a v5e at OLMoE's
# widths, 12 layers, 42 experts hit (PR 34), ms: tf 128 9.40, 256 9.47,
# 512 8.63 (90 % of the HBM peak; fewer, larger steps).
_TF = 512
_ROWS_ALIGN = 16          # a bf16 tile's sublanes
# What a step may hold in VMEM: the default scoped 16 MiB, never more (as
# `ops.held_grouped_ffn`'s budget: PERF.md PR 30).
_VMEM_BUDGET = 16 * 2 ** 20


def _kernel(ids_ref, nh_ref, x_ref, cw_ref, w1_ref, w3_ref, w2_ref, o_ref):
    del ids_ref                            # the index maps read it
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(i < nh_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
        act = gate * jax.nn.sigmoid(gate) * up * cw_ref[0]
        o_ref[...] += jnp.dot(act.astype(x.dtype), w2_ref[0],
                              preferred_element_type=jnp.float32)


def _tiles(f: int, want: int) -> list:
    """The multiples of 128 that divide f and are <= want, largest first;
    f itself where there is none."""
    return [t for t in range(min(want, f) // 128 * 128, 0, -128)
            if f % t == 0] or [f]


def hit_experts_tile(rows: int, d: int, f: int, dtype) -> Optional[int]:
    """The values of f a step for ``rows`` rows and experts of ``d x f``:
    the largest tile up to `_TF` whose step fits the budget, or None where
    none does. All the rows and their float32 result are whole rows of d
    and stay in VMEM beside three weight blocks, double-buffered: OLMoE's
    and Qwen3-Next's 2,048 x 1,024 and x 512 take 512 at up to 128 rows
    (one step an expert of the latter); DeepSeek-V3.2's 7,168 x 2,048
    takes 128 (5.25 MiB a step, 16 an expert) at up to 64 rows and nothing
    at 128. Counted so that what it names Mosaic compiles for a v5e
    (`tests/test_tpu_compile.py`)."""
    isz = jnp.dtype(dtype).itemsize
    gp = rows + -rows % _ROWS_ALIGN
    for tf in _tiles(f, _TF):
        held = (2 * 3 * d * tf * isz          # weight blocks, two buffers
                + 2 * gp * d * (isz + 4)      # rows in, result out
                + 2 * gp * 128 * 4            # a weight a row, a lane used
                + 3 * gp * tf * 4)            # gate, up, act
        if held <= _VMEM_BUDGET:
            return tf
    return None


@functools.partial(jax.jit, static_argnames=("interpret", "tf"))
def _call(x, cw, ids, n_hit, w1, w3, w2, *, interpret: bool, tf: int):
    g, d = x.shape
    e = ids.shape[0]
    f = w1.shape[2]
    n_t = f // tf
    pad = -g % _ROWS_ALIGN
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        cw = jnp.pad(cw, ((0, 0), (0, pad)))
    gp = g + pad

    def entry(i, nh):
        return jnp.minimum(i, jnp.maximum(nh[0] - 1, 0))

    def tile(i, j, nh):
        return jnp.where(i < nh[0], j, n_t - 1)

    def rows_map(i, j, ids, nh):
        return (0, 0)

    def cw_map(i, j, ids, nh):
        return (entry(i, nh), 0, 0)

    def up_map(i, j, ids, nh):
        return (ids[entry(i, nh)], 0, tile(i, j, nh))

    def down_map(i, j, ids, nh):
        return (ids[entry(i, nh)], tile(i, j, nh), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(e, n_t),
        in_specs=[pl.BlockSpec((gp, d), rows_map),
                  pl.BlockSpec((1, gp, 1), cw_map),
                  pl.BlockSpec((1, d, tf), up_map),
                  pl.BlockSpec((1, d, tf), up_map),
                  pl.BlockSpec((1, tf, d), down_map)],
        out_specs=pl.BlockSpec((gp, d), rows_map))
    out = pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((gp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name=sn.HIT_EXPERTS_KERNEL,
    )(ids.astype(jnp.int32), jnp.reshape(n_hit, (1,)).astype(jnp.int32),
      x, cw.astype(jnp.float32)[..., None], w1, w3, w2)
    return out[:g]


def hit_experts_ffn(x, cw, ids, n_hit, w1, w3, w2, *,
                    interpret: Optional[bool] = None,
                    tf: Optional[int] = None):
    """See the module docstring. ``interpret=None`` resolves to True off
    the TPU; ``tf`` None is `_TF` (`hit_experts_tile` names the one that
    fits)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _call(x, cw, ids, n_hit, w1, w3, w2, interpret=bool(interpret),
                 tf=_tiles(w1.shape[2], tf or _TF)[0])


def hit_experts_ffn_reference(x, cw, ids, n_hit, w1, w3, w2):
    """The same sum in plain `lax`: a loop over the live entries."""
    def one(i, out):
        e = ids[i]
        gate = jnp.dot(x, w1[e], preferred_element_type=jnp.float32)
        up = jnp.dot(x, w3[e], preferred_element_type=jnp.float32)
        act = gate * jax.nn.sigmoid(gate) * up * cw[i][:, None]
        return out + jnp.dot(act.astype(x.dtype), w2[e],
                             preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, n_hit, one,
                             jnp.zeros(x.shape, jnp.float32))

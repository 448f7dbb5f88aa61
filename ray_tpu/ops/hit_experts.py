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

__all__ = ["hit_experts_ffn", "hit_experts_ffn_reference"]

# Values of f a step: 3 x d x tf x 2 B = 6 MiB at d 2048, 12 double
# buffered, inside the default scoped VMEM (asking for more takes room XLA
# gives the program around the call: PERF.md PR 30). On a v5e at OLMoE's
# widths, 12 layers, 42 experts hit (PR 34), ms: tf 128 9.40, 256 9.47,
# 512 8.63 (90 % of the HBM peak; fewer, larger steps).
_TF = 512
_ROWS_ALIGN = 16          # a bf16 tile's sublanes


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


def _tile(f: int, want: int) -> int:
    """The largest multiple of 128 that divides f and is <= want; f itself
    where there is none."""
    for t in range(min(want, f) // 128 * 128, 0, -128):
        if f % t == 0:
            return t
    return f


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
                    interpret: Optional[bool] = None, tf: int = _TF):
    """See the module docstring. ``interpret=None`` resolves to True off
    the TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _call(x, cw, ids, n_hit, w1, w3, w2, interpret=bool(interpret),
                 tf=_tile(w1.shape[2], tf))


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

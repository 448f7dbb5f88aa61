"""Sharded train-step builder.

Produces the jitted SPMD training step the reference leaves to torch user
code (python/ray/train/torch/train_loop_utils.py:158 `prepare_model`): the
whole step — fwd, bwd, optimizer — is ONE compiled XLA program over the
mesh; XLA inserts all collectives (gradient reduce over dp/fsdp, weight
all-gathers for fsdp, tp reductions) from the sharding annotations.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops import scope_names as sn
from ray_tpu.parallel.sharding import logical_to_mesh, LogicalAxisRules

Pytree = Any


def batch_sharding_fn(mesh: Mesh,
                      batch_logical: Tuple[Optional[str], ...],
                      rules: Optional[LogicalAxisRules] = None):
    """Rank-adaptive batch-leaf sharding: batch_logical is truncated /
    None-padded to each leaf's rank (labels are rank-1, tokens rank-2,
    images rank-4 — all shard their leading batch axis, trailing axes
    replicate unless batch_logical names them). Shared by every
    train-step builder (full fine-tune, LoRA)."""
    def shard_for(x: jax.Array) -> NamedSharding:
        logical = tuple(batch_logical[:x.ndim]) + \
            (None,) * max(0, x.ndim - len(batch_logical))
        return NamedSharding(mesh, logical_to_mesh(logical, rules))
    return shard_for


def make_sharded_train_step(
    loss_fn: Callable[[Pytree, Dict[str, jax.Array]], jax.Array],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    param_specs: Pytree,
    batch_logical: Tuple[Optional[str], ...] = ("batch", None),
    rules: Optional[LogicalAxisRules] = None,
    donate: bool = True,
):
    """Returns (init_fn, step_fn).

    init_fn(params) -> (sharded_params, sharded_opt_state): device_puts the
    param tree per `param_specs`; optimizer state inherits its params'
    sharding via GSPMD propagation through a jitted `optimizer.init`.

    step_fn(params, opt_state, batch) -> (params, opt_state, metrics).
    Shardings are inferred from the committed inputs; params/opt_state
    buffers are donated so the step is in-place in HBM.
    """
    param_shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs,
        is_leaf=lambda x: isinstance(x, P))
    _batch_sharding_for = batch_sharding_fn(mesh, batch_logical, rules)

    def init_fn(params):
        params = jax.tree_util.tree_map(
            jax.device_put, params, param_shardings)
        opt_state = jax.jit(optimizer.init)(params)
        return params, opt_state

    @functools.partial(jax.jit, donate_argnums=(0, 1) if donate else ())
    def step_fn(params, opt_state, batch):
        from ray_tpu.ops.attention import spmd_mesh_scope

        # Trace-time mesh announcement: kernel dispatch (Pallas flash
        # attention) picks shard_map-wrapped forms that GSPMD can't
        # auto-partition.
        with spmd_mesh_scope(mesh):
            batch = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, _batch_sharding_for(x)), batch)
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            with jax.named_scope(sn.OPTIMIZER):
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
                metrics = {"loss": loss,
                           "grad_norm": optax.global_norm(grads)}
            return params, opt_state, metrics

    return init_fn, step_fn

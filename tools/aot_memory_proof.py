"""AOT memory proof: compile the FULL Llama-2-7B sharded train step
against a REAL v5e-64 TPU topology description and verify it fits
per-chip HBM.

The north star (BASELINE.json) is Llama-2-7B fine-tune at >=40% MFU on a
v5e-64 slice (16 GiB HBM/chip). Real 64-chip hardware is not needed:
`jax.experimental.topologies.get_topology_desc("tpu", "v5e:8x8")` plus
AOT lower+compile produces the actual TPU executable and its HLO memory
analysis (argument/temp sizes per chip) — the same buffer assignment the
chips would run, including remat and fsdp all-gather scheduling.

Usage:  python tools/aot_memory_proof.py [--out AOT_7B_PROOF.json]
The driver-visible artifact is committed at the repo root.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys

N_DEVICES = 64
HBM_PER_CHIP = 16 * 1024 ** 3        # v5e: 16 GiB
PEAK_BF16_FLOPS = 197e12             # v5e: 197 TFLOP/s bf16
# bench.py single-chip result for the 551M flagship (54.54% with the
# named remat policy save:ffn_* + 1024x1024 flash tiles). The record it
# came from, BENCH_r05.json, was removed: the number is an old claim
# from an installation that is gone, and the projection below inherits
# that until S1's benchmark re-takes it.
MEASURED_MFU = 0.5454

# Mesh: pure fsdp over the slice — params + optimizer state shard 64
# ways; batch (one sequence per chip) shards over the same axis.
MESH = {"fsdp": 64}
SEQ_LEN = 4096
BATCH_PER_CHIP = 1


def main() -> None:
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "AOT_7B_PROOF.json"))
    p.add_argument("--topology", default="v5e:8x8")
    args = p.parse_args()
    report = aot_body(topology=args.topology)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps({"per_chip_hbm_gib": report["per_chip_hbm_gib"],
                      "fits_16gib": report["fits_16gib"],
                      "projected_tokens_per_sec_per_chip":
                      report["projected_tokens_per_sec_per_chip"]}))


def aot_body(mesh_sizes: dict = None, cfg=None,
             batch_per_chip: int = BATCH_PER_CHIP,
             seq_len: int = SEQ_LEN, topology: str = "v5e:8x8") -> dict:
    """AOT-compile the sharded 7B train step against a TPU topology
    description; return per-chip memory stats + throughput projection."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from ray_tpu.models import (LlamaConfig, llama_loss, llama_param_specs)
    from ray_tpu.models.training import make_sharded_train_step
    from ray_tpu.parallel.mesh import AXIS_ORDER
    from ray_tpu.parallel.sharding import logical_to_mesh

    mesh_sizes = dict(mesh_sizes or MESH)
    cfg = cfg or LlamaConfig.llama2_7b()  # true 7B: 32L x 4096d, remat on
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    n_devices = math.prod(mesh_sizes.values())
    assert len(topo.devices) == n_devices, (
        f"topology {topology} has {len(topo.devices)} devices, mesh "
        f"wants {n_devices}")
    names = tuple(a for a in AXIS_ORDER if mesh_sizes.get(a, 1) >= 1)
    shape = tuple(mesh_sizes.get(a, 1) for a in names)
    mesh = Mesh(np.asarray(topo.devices).reshape(shape), names)
    specs = llama_param_specs(cfg)

    init_fn, step_fn = make_sharded_train_step(
        lambda p, b: llama_loss(p, b, cfg), optax.adamw(1e-4), mesh, specs)

    # Abstract trees only — no 28 GB of host arrays.
    from jax.sharding import NamedSharding

    def abstract_params():
        from ray_tpu.models import llama_init

        shapes = jax.eval_shape(
            lambda k: llama_init(k, cfg), jax.random.PRNGKey(0))
        return jax.tree_util.tree_map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
            shapes, specs,
            is_leaf=lambda x: not isinstance(x, dict))

    params_abs = abstract_params()
    opt_abs = jax.eval_shape(lambda p: optax.adamw(1e-4).init(p),
                             params_abs)
    global_batch = batch_per_chip * n_devices
    batch_abs = {"tokens": jax.ShapeDtypeStruct(
        (global_batch, seq_len), jnp.int32,
        sharding=NamedSharding(mesh, logical_to_mesh(("batch", None))))}

    lowered = step_fn.lower(params_abs, opt_abs, batch_abs)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()

    # Donated params/opt alias their outputs, so per-chip residency is
    # arguments (params + opt + batch shards) + temporaries.
    arg_b = int(mem.argument_size_in_bytes)
    tmp_b = int(mem.temp_size_in_bytes)
    out_b = int(mem.output_size_in_bytes)
    alias_b = int(getattr(mem, "alias_size_in_bytes", 0))
    per_chip = arg_b + tmp_b

    n_params = sum(
        math.prod(l.shape) for l in jax.tree_util.tree_leaves(params_abs))
    # Per-token train FLOPs: 6*N matmul + attention 12*L*d*s correction.
    flops_per_token = 6 * n_params + \
        12 * cfg.n_layers * cfg.dim * seq_len
    projected = MEASURED_MFU * PEAK_BF16_FLOPS / flops_per_token

    return {
        "model": "llama2_7b",
        "topology": topology,
        "n_params": int(n_params),
        "mesh": mesh_sizes,
        "seq_len": seq_len,
        "global_batch": global_batch,
        "remat": cfg.remat,
        "remat_policy": cfg.remat_policy,
        "argument_bytes_per_chip": arg_b,
        "temp_bytes_per_chip": tmp_b,
        "output_bytes_per_chip": out_b,
        "alias_bytes_per_chip": alias_b,
        "per_chip_hbm_bytes": per_chip,
        "per_chip_hbm_gib": round(per_chip / 1024 ** 3, 3),
        "hbm_per_chip_gib": HBM_PER_CHIP / 1024 ** 3,
        "fits_16gib": per_chip <= HBM_PER_CHIP,
        "measured_single_chip_mfu": MEASURED_MFU,
        "mfu_source": ("551M flagship (named remat policy "
                       "save:ffn_gate+ffn_up+ffn_down, 1024x1024 flash "
                       "tiles): an old claim, its record BENCH_r05.json "
                       "removed with the installation it was taken on"),
        "peak_bf16_flops": PEAK_BF16_FLOPS,
        "flops_per_token": int(flops_per_token),
        "projected_tokens_per_sec_per_chip": round(projected, 1),
    }


if __name__ == "__main__":
    main()

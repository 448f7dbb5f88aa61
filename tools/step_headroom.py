"""How much device memory the train cell's step leaves free ON THE CHIP.

    python tools/step_headroom.py <tree root> <tag> [start_mib] [step_mib] [rehearse]

builds `internlm2-train-fsdp4`'s step from THAT tree (weights, AdamW state
and placement as the benchmark's driver makes them), runs it, then holds
`start_mib` MiB of ballast on every chip and adds `step_mib` more after
each step that still runs, until the loader refuses the program. Prints
`HEADROOM {json}` lines: what `memory_analysis()` says of the compiled
step, every ballast size tried, the loader's own words at the failure.

Why it exists (PERF.md section 6, PR 32): `argument_size + temp_size` of
`memory_analysis()` counts every scan-stacked residual twice and overstated
this step by 3.2 GiB; what the chip reserves is the arguments plus one
heap, `peak_memory_in_bytes`, and only the chip can say so
(`memory_stats()["peak_bytes_in_use"]` counts live arrays, not workspace).
On the chip: parent, then change, in one `chiprun --chips 4` call;
`rehearse` runs the cell's rehearsal size on four CPU devices."""
import json
import sys
import time

root, tag = sys.argv[1], sys.argv[2]
start = int(sys.argv[3]) if len(sys.argv) > 3 else 0
inc = int(sys.argv[4]) if len(sys.argv) > 4 else 128
REHEARSE = len(sys.argv) > 5
sys.path.insert(0, root)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmark.harness import spec  # noqa: E402
from benchmark.harness.model import llama_config  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models import llama_init, llama_loss, llama_param_specs  # noqa: E402
from ray_tpu.models.training import (batch_sharding_fn,  # noqa: E402
                                     make_sharded_train_step)
from ray_tpu.parallel import create_mesh  # noqa: E402

assert llama.__file__.startswith(root + "/ray_tpu"), llama.__file__


def say(**kw):
    print("HEADROOM " + json.dumps(dict(tag=tag, **kw)), flush=True)


cell = spec.load_cell("internlm2-train-fsdp4")
model, topts = dict(cell.config), dict(cell.config["train"])
traffic = dict(cell.traffic["traffic"])
if REHEARSE:
    model.update(cell.config["rehearsal"]["model"])
    topts.update(cell.config["rehearsal"]["train"])
    traffic.update(cell.traffic["rehearsal"]["traffic"])
B, S = traffic["batch"], traffic["seq_len"]
cfg = llama_config(
    model, S, activation_dtype=topts["activation_dtype"],
    param_dtype=topts["param_dtype"], remat=topts["remat"],
    remat_policy=topts["remat_policy"], attn_impl=topts["attn_impl"],
    loss_chunk=topts.get("loss_chunk"))
mesh = create_mesh(dict(topts["mesh"]), jax.devices()[:cell.chips])
specs = llama_param_specs(cfg)
shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, P))
params = jax.jit(lambda k: llama_init(k, cfg), out_shardings=shardings)(
    jax.random.key(7, impl="rbg"))
opt = optax.adamw(topts["optimizer"]["lr"],
                  weight_decay=topts["optimizer"]["weight_decay"])
by_shape = {(x.shape, x.dtype): x.sharding for x in jax.tree.leaves(params)}
opt_state = jax.jit(opt.init, out_shardings=jax.tree.map(
    lambda a: by_shape.get((a.shape, a.dtype), NamedSharding(mesh, P())),
    jax.eval_shape(opt.init, params)))(params)
_, step_fn = make_sharded_train_step(
    lambda p, b: llama_loss(p, b, cfg), opt, mesh, specs)
tokens = np.random.RandomState(0).randint(
    0, cfg.vocab_size, (B, S + 1)).astype(np.int32)
batch = {"tokens": jax.device_put(
    tokens, batch_sharding_fn(mesh, ("batch", None))(tokens))}

t = time.time()
compiled = step_fn.lower(params, opt_state, batch).compile()
m = compiled.memory_analysis()
say(compile_s=time.time() - t, argument=m.argument_size_in_bytes,
    temp=m.temp_size_in_bytes, peak=m.peak_memory_in_bytes,
    limit=(jax.devices()[0].memory_stats() or {}).get("bytes_limit"))

ballast, held = [], 0


def hold(mib):
    """`mib` MiB more on EVERY chip."""
    a = jax.jit(lambda: jnp.zeros((cell.chips, mib, 1 << 20), jnp.uint8),
                out_shardings=NamedSharding(mesh, P("fsdp")))()
    ballast.append(jax.block_until_ready(a))


try:
    if start:
        hold(start)
        held = start
    while held < (3 * inc if REHEARSE else 16384):
        t = time.time()
        params, opt_state, met = compiled(params, opt_state, batch)
        say(held_mib=held, ran=True, loss=float(met["loss"]),
            step_s=time.time() - t)
        hold(inc)
        held += inc
except Exception as e:  # noqa: BLE001 — the loader's refusal is the reading
    say(held_mib=held, ran=False, error=str(e)[:600])
say(ran_beside_mib=max(held - inc, 0), refused_beside_mib=held)

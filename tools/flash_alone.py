"""The three flash kernels ALONE at a train cell's shape, for comparing two
trees on one chip (beside `tools/decode_alone.py` / `prefill_alone.py`: the
train cell's trace gives the kernels' time inside a step, not what a grid
step costs).

    python tools/flash_alone.py <tree root> <tag> [--cell NAME]
        [--shape B,H,HKV,S,D] [--block N] [--rehearse]

imports `ray_tpu.ops.flash_attention` from THAT tree (run it from the
tree's root), builds q `[B,H,S,D]` and k, v `[B,HKV,S,D]` in bf16 (default:
a chip's share of the cell's batch, the cell's heads and sequence:
`[4,16,4096,128]` / 8 KV heads for `internlm2-train-fsdp4`) and runs, for
`causal` True and False, three programs: the forward with its logsumexp
(what a train step's forward calls), `_flash_bwd` keeping only dq, and
`_flash_bwd` keeping only dk/dv (the other kernel is dead code to XLA), each
3 x 20 times on the device's queue (async dispatch, one wait at the end),
then 10 times under the profiler. Prints `FLASH_AB {json}`: ms a program
(host clock; dk/dv's holds the GQA group sum), ms a KERNEL call (the
`tpu_custom_call` events inside each program: the number to read), the
pairs of a (batch, head) by `_block_contributes` and, where the tree has a
schedule, its counts, a digest of every program's results (two trees that
compute the same bits print the same), and from the kernel times

    L = t_full / rectangle            a live grid step, us
    D = (t_causal - live / rectangle * t_full) / (rectangle - live)

a (batch, head): what a step the mask rules out costs on a tree whose grid
is the rectangle (PERF.md PR 50); on a tree whose grid is the schedule D is
what a causal call takes over (or under) its share of the full one, a dead
pair. On the chip: parent, change, change, parent in one `chiprun` call;
`--rehearse` runs a small shape through the interpreter on the CPU (counts,
never a time)."""
import argparse
import hashlib
import importlib
import json
import os
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("root")
ap.add_argument("tag")
ap.add_argument("--cell", default="internlm2-train-fsdp4")
ap.add_argument("--shape", default="")
ap.add_argument("--block", type=int, default=0)
ap.add_argument("--rehearse", action="store_true")
a = ap.parse_args()
sys.path.insert(0, a.root)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import spec, xplane  # noqa: E402

# `ray_tpu.ops.flash_attention` the attribute is the function of that name
F = importlib.import_module("ray_tpu.ops.flash_attention")

assert F.__file__.startswith(a.root + "/ray_tpu"), F.__file__
if a.shape:
    B, H, HKV, S, D = (int(x) for x in a.shape.split(","))
elif a.rehearse:
    B, H, HKV, S, D = 1, 4, 2, 256, 32
else:
    cell = spec.load_cell(a.cell)
    B = cell.traffic["traffic"]["batch"] // cell.chips
    S = cell.traffic["traffic"]["seq_len"]
    H = cell.config["num_attention_heads"]
    HKV = cell.config["num_key_value_heads"]
    D = cell.config["head_dim"]
bq = bk = a.block or (64 if a.rehearse else F.DEFAULT_BLOCK_Q)
interpret = jax.default_backend() != "tpu"
assert a.rehearse or not interpret, "no chip: a time comes from the chip"
CALLS, REPS, TRACED = (1, 1, 1) if a.rehearse else (20, 3, 10)
scale = D ** -0.5

ks = jax.random.split(jax.random.PRNGKey(50), 4)
q = jax.random.normal(ks[0], (B, H, S, D), jnp.bfloat16)
k = jax.random.normal(ks[1], (B, HKV, S, D), jnp.bfloat16)
v = jax.random.normal(ks[2], (B, HKV, S, D), jnp.bfloat16)
g = jax.random.normal(ks[3], (B, H, S, D), jnp.bfloat16)


def programs(causal):
    def fwd(q, k, v):
        return F._flash_fwd(q, k, v, scale, causal, bq, bk, interpret,
                            with_lse=True)

    def bwd(q, k, v, out, lse, g, delta):
        return F._flash_bwd(q, k, v, out, lse, g, scale, causal, bq, bk,
                            interpret, delta=delta)

    def dq(*xs):
        return bwd(*xs)[0]

    def dkv(*xs):
        return bwd(*xs)[1:]

    tag = "causal" if causal else "full"
    for f in (fwd, dq, dkv):    # the trace names a module by its function
        f.__name__ = f"flash_alone_{f.__name__}_{tag}"
    return jax.jit(fwd), jax.jit(dq), jax.jit(dkv)


nq, nk = -(-S // bq), -(-S // bk)
live = sum(bool(F._block_contributes(i, j, bq, bk, 0, True))
           for i in range(nq) for j in range(nk))
out = {"tag": a.tag, "device": str(jax.devices()[0]),
       "shape": [B, H, HKV, S, D], "block": [bq, bk],
       "pairs_by_block_contributes": {"live": live, "rectangle": nq * nk}}
if hasattr(F, "pair_schedule"):
    out["schedule"] = {
        f"{kern}_{'causal' if c else 'full'}": F.pair_schedule(
            kern, S, S, bq, bk, c).counts()
        for kern in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
        for c in (True, False)}

calls = {}
for causal in (True, False):
    fwd, dq, dkv = programs(causal)
    o, lse = fwd(q, k, v)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    bwd_args = (q, k, v, o, lse, g, delta)
    for name, fn, args in (("fwd", fwd, (q, k, v)), ("dq", dq, bwd_args),
                           ("dkv", dkv, bwd_args)):
        calls[f"{name}_{'causal' if causal else 'full'}"] = (fn, args)

digests = out["sha256_of_results"] = {}
for name, (fn, args) in calls.items():
    for _ in range(3):
        r = fn(*args)
    jax.block_until_ready(r)
    digests[name] = hashlib.sha256(b"".join(
        np.asarray(x).tobytes() for x in jax.tree.leaves(r))
    ).hexdigest()[:16]
    reps = []
    for _ in range(REPS):
        t = time.perf_counter()
        for _ in range(CALLS):
            r = fn(*args)
        jax.block_until_ready(r)
        reps.append((time.perf_counter() - t) / CALLS * 1e3)
    out[f"ms_per_program_{name}"] = reps

trace_dir = os.path.join("chiprun_out", "flash_alone", a.tag)
with jax.profiler.trace(trace_dir):
    for name, (fn, args) in calls.items():
        for _ in range(TRACED):
            r = fn(*args)
        jax.block_until_ready(r)
trace = xplane.load(xplane.find_xplane(trace_dir), host_names=())
everything = (0, 1 << 62)
kernel_ms, kernel_events = {}, {}
for lines in trace.devices.values():
    for name in calls:
        ns, n = xplane.sum_within(
            lines.get(xplane.OPS_LINE, []), xplane.PALLAS_KERNEL,
            lines.get(xplane.MODULES_LINE, []), f"flash_alone_{name}",
            everything)
        if n:
            kernel_ms[name] = ns / n / 1e6
            kernel_events[name] = n     # TRACED: ONE kernel a program
if kernel_ms:
    out["ms_per_kernel_call"] = kernel_ms
    out["kernel_events_traced"] = kernel_events
    dead = nq * nk - live
    for name in ("fwd", "dq", "dkv"):
        t_c, t_f = kernel_ms[f"{name}_causal"], kernel_ms[f"{name}_full"]
        out[f"L_us_{name}"] = t_f / (nq * nk) / (B * H) * 1e3
        out[f"D_us_{name}"] = ((t_c - live / (nq * nk) * t_f) / dead
                               / (B * H) * 1e3) if dead else None
else:
    out["ms_per_kernel_call"] = "not measured: the trace has no device"
print("FLASH_AB " + json.dumps(out), flush=True)

"""A decode token's latent attention ALONE on one chip: the kernel
`ops.sparse_latent_attention.sparse_latent_decode` of a tree at its three
callers' shapes (PERF.md PR 55), for comparing two trees in one call.

    python tools/sparse_decode_alone.py <tree root> <tag>
        [--shape dsv32,dots3,window] [--len L[,L...]] [--rehearse]

draws a latent plane, queries and a selection from a seed, gives row b a
shuffled set of blocks and prints `SPARSE_DECODE_AB {json}`:

- `max_err_<shape>`: the largest difference from the plain form on the
  gathered view, over the first rows at mixed lengths (bf16 operands);
- `us_per_row_<shape>_L<len>`: microseconds a row of one call with every
  row at slot ``len - 1`` (`mixed`: lengths spread from 256 to the table's
  end; `dead`: every other row asks nothing), the least of three timings
  of 10 programs of 8 dependent calls each on the device's queue;
- a window shape's rows sit at the slots a 513-token window gives them in
  a table of 4 pages (3 or 4 of them live, the first masked in part).

`--rehearse` runs tiny shapes on the CPU, the kernel in interpret mode."""
import argparse
import json
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("root")
ap.add_argument("tag")
ap.add_argument("--shape", default="dsv32,dots3,window")
ap.add_argument("--len", default="1024,4096,16384")
ap.add_argument("--rehearse", action="store_true")
a = ap.parse_args()
sys.path.insert(0, a.root)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.ops import sparse_latent_attention as sla  # noqa: E402

assert sla.__file__.startswith(a.root + "/ray_tpu"), sla.__file__
# rows, heads, lanes, latent, table entries, slots a page, the selection
SHAPES = {"dsv32": (24, 128, 640, 512, 72, 256, 2048),
          "dots3": (64, 128, 640, 512, 132, 256, 2048),
          "window": (64, 64, 1152, 1024, 4, 256, 513)}
if a.rehearse:
    SHAPES = {"dsv32": (3, 8, 128, 96, 6, 32, 64),
              "window": (4, 4, 256, 192, 4, 32, 65)}
CALLS, REPS = (2, 2) if a.rehearse else (10, 8)
out = {"tag": a.tag, "device": str(jax.devices()[0])}


def ms_a_call(fn, *args):
    jax.block_until_ready(fn(*args))
    reps = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(CALLS):
            r = fn(*args)
        jax.block_until_ready(r)
        reps.append((time.perf_counter() - t) / CALLS * 1e3)
    return min(reps)


for name in a.shape.split(","):
    if name not in SHAPES:
        continue
    B, H, W, rc, MB, T, keep = SHAPES[name]
    span = MB * T
    rng = np.random.default_rng(55)
    key = iter(jax.random.split(jax.random.PRNGKey(55), 8))
    pool = jax.random.normal(next(key), (1, 1 + B * MB, T, W), jnp.bfloat16)
    bt = jnp.asarray(1 + rng.permutation(B * MB).reshape(B, MB), jnp.int32)
    q = jax.random.normal(next(key), (B, H, W), jnp.bfloat16)
    window = name == "window"

    def selection(slots):
        """The mask of rows at ``slots`` (-1: asks nothing): the window's
        last `keep` slots, else `keep` of the seen slots at random."""
        at = np.arange(span)[None, :]
        s = np.asarray(slots)[:, None]
        if window:
            take = (at <= s) & (s - at < keep)
        else:
            take = (at <= s) & ((rng.random((B, span)) * (s + 1) < keep)
                                | (at == s))
        return jnp.asarray(np.where(take, 0.0, -1e30), jnp.float32)

    @jax.jit
    def kernel(q, pool, bt, bias, slots):
        return sla.sparse_latent_decode(q, pool, bt, bias, slots,
                                        jnp.int32(0), rc=rc, sm_scale=0.05,
                                        interpret=a.rehearse)

    @jax.jit
    def repeated(q, pool, bt, bias, slots):
        """`REPS` calls in one program, each waiting for the one before it
        (a call of 24 short rows is shorter than the host's dispatch)."""
        def again(_, q):
            o = kernel(q, pool, bt, bias, slots)
            return q + (o[:, :, :1] * 0).astype(q.dtype)
        return jax.lax.fori_loop(0, REPS, again, q)

    @jax.jit
    def plain(q, pool, bt, bias):
        lat = pool[0, bt].reshape(bt.shape[0], span, W)
        return sla.sparse_latent_attention_reference(
            q[:, :, None], lat, bias[:, None], rc=rc, sm_scale=0.05)[:, :, 0]

    if window:      # a window's rows: the query's slot in page 2 or 3
        cases = [("mixed", 2 * T + rng.integers(0, 2 * T, B) - 1)]
    else:
        mixed = np.geomspace(T, span, B).astype(np.int64) - 1
        cases = [("mixed", mixed)] + [
            (f"L{n}", np.full(B, min(n, span) - 1))
            for n in ([40, 150] if a.rehearse else
                      [int(x) for x in a.len.split(",")])] + [
            ("dead", np.where(np.arange(B) % 2, -1, mixed))]
    for case, slots in cases:
        bias = selection(slots)
        slots = jnp.asarray(slots, jnp.int32)
        if case in ("mixed", "dead"):
            got = np.asarray(kernel(q, pool, bt, bias, slots), np.float32)
            rows = slice(0, B, max(1, B // 8))
            want = np.asarray(plain(q[rows], pool, bt[rows], bias[rows]),
                              np.float32)
            out[f"max_err_{name}_{case}"] = float(
                np.abs(got[rows] - want).max())
            assert not got[np.asarray(slots) < 0].any()
        out[f"us_per_row_{name}_{case}"] = 1e3 * ms_a_call(
            repeated, q, pool, bt, bias, slots) / (B * REPS)
    del pool
print("SPARSE_DECODE_AB " + json.dumps(out), flush=True)

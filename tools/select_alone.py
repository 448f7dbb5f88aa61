"""The selection ALONE on one chip: the kernel `ops.indexer_select` beside
the lax form `mla.select_mask`, at a serving cell's indexer geometry and
table (PERF.md PR 53).

    python tools/select_alone.py <tree root> [--cell NAME] [--len L[,L...]]
        [--rehearse]

draws an index plane, queries and head weights from a seed (normal
values: a seeded model's are projections of the same), gives row b the
blocks ``1 + b * MB ..`` and prints `SELECT_AB {json}`:

- `differ_share`: for ONE request of nine 512-token chunks (4,608 tokens),
  the share of (query, slot) pairs on which the two masks differ, and
  whether every query chose exactly ``min(t + 1, index_topk)`` slots;
- `decode_ms_L<len>`: ms a call of each form for all the cell's rows one
  query each at slot ``len - 1`` (`mixed`: lengths spread from 256 to the
  table's end, half under `index_topk`);
- `prefill_ms_S<start>`: ms a call of each form for 4 rows x 512 queries
  at ``start``.

`--rehearse` runs tiny shapes on the CPU, the kernel in interpret mode."""
import argparse
import json
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("root")
ap.add_argument("--cell", default="dots3-mixed-ctx")
ap.add_argument("--len", default="1024,4096,16384")
ap.add_argument("--rehearse", action="store_true")
a = ap.parse_args()
sys.path.insert(0, a.root)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import spec  # noqa: E402
from ray_tpu.models import mla  # noqa: E402
from ray_tpu.ops import indexer_select as isel  # noqa: E402

assert mla.__file__.startswith(a.root + "/ray_tpu"), mla.__file__
cell = spec.load_cell(a.cell)
model, opts = dict(cell.config), dict(cell.config["engine"])
if a.rehearse:
    H, D, K, T, B, MB, chunk, calls = 4, 32, 64, 32, 4, 8, 16, 2
    lens = [48, 200]
else:
    H, D, K = (model[k] for k in ("index_n_heads", "index_head_dim",
                                  "index_topk"))
    T, B, chunk, calls = opts["kv_block_tokens"], opts["batch_slots"], 512, 20
    MB = -(-opts["max_len"] // T)
    lens = [int(x) for x in a.len.split(",")]
cfg = mla.MlaConfig.nano_mla(index_n_heads=H, index_head_dim=D,
                             index_topk=K, qk_rope_head_dim=8)
span = T * MB
key = iter(jax.random.split(jax.random.PRNGKey(53), 64))
pool = jax.random.normal(next(key), (1, 1 + B * MB, T, D), jnp.bfloat16)
bt = 1 + jnp.arange(B * MB, dtype=jnp.int32).reshape(B, MB)
out = {"cell": a.cell, "device": str(jax.devices()[0]),
       "geometry": [H, D, K, T, B, MB]}


def draw(rows, n):
    return (jax.random.normal(next(key), (rows, n, H, D), jnp.bfloat16),
            jax.random.normal(next(key), (rows, n, H), jnp.float32)
            * np.float32((H * D) ** -0.5))


@jax.jit
def lax_form(qi, wt, q_slots, pool, bt):
    return mla._by_query_blocks(
        lambda qi, wt, qs: mla.select_mask(qi, wt, qs, pool, bt, 0, cfg),
        q_slots.shape[1], qi, wt, q_slots)


@jax.jit
def kernel(qi, wt, q_slots, pool, bt):
    return isel.indexer_select(qi, wt, q_slots, pool, bt, 0, topk=K,
                               interpret=a.rehearse)


def ms_a_call(fn, *args):
    jax.block_until_ready(fn(*args))
    reps = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(calls):
            r = fn(*args)
        jax.block_until_ready(r)
        reps.append((time.perf_counter() - t) / calls * 1e3)
    return min(reps)


# one request's chunks, both forms, slot by slot
differ = pairs = 0
exact = True
for start in range(0, 9 * chunk, chunk):
    qi, wt = draw(1, chunk)
    qs = start + jnp.arange(chunk, dtype=jnp.int32)[None]
    got = np.asarray(kernel(qi, wt, qs, pool, bt[:1])) == 0
    want = np.asarray(lax_form(qi, wt, qs, pool, bt[:1])) == 0
    differ += int((got != want).sum())
    pairs += int(np.asarray(qs + 1).sum())
    exact &= bool((got.sum(-1) == np.minimum(np.asarray(qs) + 1, K)).all())
out["differ_share"] = differ / pairs
out["differ_slots"], out["chose_min_t1_k"] = differ, exact

mixed = np.geomspace(256, span, B).astype(np.int64)
for name, row_len in [(f"L{n}", np.full(B, min(n, span))) for n in lens] \
        + [("mixed", mixed)]:
    qi, wt = draw(B, 1)
    qs = jnp.asarray(row_len - 1, jnp.int32)[:, None]
    out[f"decode_ms_{name}"] = {
        "kernel": ms_a_call(kernel, qi, wt, qs, pool, bt),
        "lax": ms_a_call(lax_form, qi, wt, qs, pool, bt)}
for start in lens:
    rows = min(4, B)
    qi, wt = draw(rows, chunk)
    qs = min(start, span - chunk) \
        + jnp.tile(jnp.arange(chunk, dtype=jnp.int32), (rows, 1))
    out[f"prefill_ms_S{start}"] = {
        "kernel": ms_a_call(kernel, qi, wt, qs, pool, bt[:rows]),
        "lax": ms_a_call(lax_form, qi, wt, qs, pool, bt[:rows])}
print("SELECT_AB " + json.dumps(out), flush=True)

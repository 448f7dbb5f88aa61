"""The prefill program ALONE on fixed rows, for comparing two trees on one
chip (beside `tools/decode_alone.py`: a traced benchmark run's prefill
share moves with the chunks its slice holds, and a kernel that is equal
alone may still cost the program around it, PERF.md PR 30).

    python tools/prefill_alone.py <tree root> <tag> [--cell NAME]
        [--rows N[,N...]] [--start S[,S...]] [--rehearse]

builds the engine of a serving cell of driver `serve_gdn`, `serve_mla` or
`serve_model` (default `qwen3next-longctx`) from THAT tree (run it from the
tree's root), brings N rows (default 1,2,4) through prompts of S + one chunk
tokens (S a multiple of `prefill_chunk`; default 0 and 4096) so that their
blocks exist, then calls `_prefill_rows_paged` for the chunk at S of those
rows 3 x 20 times on the SAME rows and times it on the device's queue
(async dispatch, one wait at the end). Prints `PREFILL_AB {json}`: ms a
program. On the chip: parent, change, change, parent in one `chiprun`
call; `--rehearse` runs the cell's rehearsal size on the CPU."""
import argparse
import importlib
import json
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("root")
ap.add_argument("tag")
ap.add_argument("--cell", default="qwen3next-longctx")
ap.add_argument("--rows", default="1,2,4")
ap.add_argument("--start", default="")
ap.add_argument("--rehearse", action="store_true")
a = ap.parse_args()
sys.path.insert(0, a.root)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import common, spec  # noqa: E402
from ray_tpu.models import engine as E  # noqa: E402

assert E.__file__.startswith(a.root + "/ray_tpu"), E.__file__
cell = spec.load_cell(a.cell)
model = dict(cell.config)
opts = dict(cell.config["engine"])
if a.rehearse:
    model.update(cell.config["rehearsal"]["model"])
    opts.update(cell.config["rehearsal"]["engine"])
opts.pop("warm_groups")
driver = importlib.import_module(
    "benchmark.harness.drivers." + cell.config["driver"])
cfg, init, _ = driver.program_config(model, opts["max_len"])
if cell.config["driver"] == "serve_model":
    params = jax.jit(init, static_argnums=1)(common.seed_key(7), cfg)
else:   # an `rbg` key, as those drivers draw their weights with
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(common.seed_key(7)), 2), impl="rbg")
    params = jax.jit(init, static_argnums=1)(key, cfg)
jax.block_until_ready(params)
chunk = opts["prefill_chunk"]
CALLS = 2 if a.rehearse else 20
starts_at = [int(x) for x in a.start.split(",") if x] or (
    [0, 2 * chunk] if a.rehearse else [0, 4096])
out = {"tag": a.tag, "cell": a.cell, "device": str(jax.devices()[0]),
       "chunk": chunk}
for S in starts_at:
    assert S % chunk == 0, (S, chunk)
    for n in [int(x) for x in a.rows.split(",")]:
        eng = E.DecodeEngine(params, cfg, **opts)
        rng = np.random.default_rng(S + n)
        for _ in range(n):
            eng.submit(rng.integers(1, cfg.vocab_size,
                                    size=S + chunk).tolist(),
                       max_new_tokens=64)
        while eng._row_prefill or sum(
                r is not None for r in eng.row_req) < n:
            eng.step()
        eng._flush_pipeline({})
        rows = np.asarray([b for b in range(eng.B)
                           if eng.row_req[b] is not None], np.int32)
        assert len(rows) == n, rows
        n_pad = 1 << (n - 1).bit_length()
        rows = np.concatenate([rows, np.repeat(rows[-1:], n_pad - n)])
        prompts = jnp.asarray(rng.integers(
            1, cfg.vocab_size, size=(n_pad, chunk)).astype(np.int32))
        fixed = dict(
            bt=jnp.asarray(eng._bt[rows]), rows=jnp.asarray(rows),
            starts=jnp.full((n_pad,), S, jnp.int32),
            last_idx=jnp.full((n_pad,), chunk - 1, jnp.int32), cfg=eng.cfg,
            shardings=eng._shardings, qspec=eng.kv_quant_spec,
            moe_ctr=eng._moe_ctr,
            bt_w=jnp.asarray(eng._bt_w[rows])
            if eng.kv_pool_w is not None else None)
        state = (eng._pool_k, eng._pool_v, eng._scale_k, eng._scale_v,
                 eng._last_logits, eng._hyb)

        def call(pk, pv, sk, sv, ll, hyb):
            r = E._prefill_rows_paged(
                eng.params, prompts, pk, pv, ll, scale_k=sk, scale_v=sv,
                hyb=hyb, **fixed)
            return r[0], r[1], r[2], r[3], r[4], r[6]

        for _ in range(3):
            state = call(*state)
        jax.block_until_ready(state[4])
        reps = []
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(CALLS):
                state = call(*state)
            jax.block_until_ready(state[4])
            reps.append((time.perf_counter() - t) / CALLS * 1e3)
        out[f"ms_per_program_start{S}_rows{n}"] = reps
        del eng, state
print("PREFILL_AB " + json.dumps(out), flush=True)

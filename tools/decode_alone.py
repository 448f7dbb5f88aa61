"""The fused decode program ALONE at fixed row lengths, for comparing two
trees on one chip (PERF.md PR 30: a kernel that is equal alone may still
cost the program around it, and a traced benchmark run's
`decode_step_device_ms` moves with the row lengths its slice holds).

    python tools/decode_alone.py <tree root> <tag> [--cell NAME]
        [--live N[,N...]] [--len L[,L...]] [--rehearse]

builds the engine of a serving cell of driver `serve_engine`,
`serve_model`, `serve_hybrid`, `serve_gdn`, `serve_mla` or `serve_mla_swa`
(default `mistral7b-rollout`) from THAT tree (run it from the tree's root),
brings N rows of L tokens into decode (N: all the slots by default; the other
slots stay dead) for every N and L, then calls `_decode_multi_paged` (the
cell's `decode_horizon`, 8 where it names none) 3 x 40 times on the SAME
row state and times it on the device's queue (async dispatch, one wait at
the end).
Prints `DECODE_AB {json}`: ms a token, the pages the paged kernel walks a
token in full steps' worth (pages over `walk_shape`'s pages a step, summed
over every layer that calls it: a last step of one page is a sixteenth of
a 16-page step), for an expert-layer model the experts hit a layer-step,
and, where two lengths differ in pages, `us_per_step`: what one more FULL
step of keys costs the program, the difference between the longest and
the shortest length over the difference in steps' worth. On the chip: parent, change, change, parent in
one `chiprun` call; `--rehearse` runs the cell's rehearsal size on the CPU."""
import argparse
import importlib
import json
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("root")
ap.add_argument("tag")
ap.add_argument("--cell", default="mistral7b-rollout")
ap.add_argument("--live", default="")
ap.add_argument("--len", default="")
ap.add_argument("--rehearse", action="store_true")
a = ap.parse_args()
sys.path.insert(0, a.root)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import common, spec  # noqa: E402
from benchmark.harness.drivers import serve_model  # noqa: E402
from benchmark.harness.model import llama_config  # noqa: E402
from ray_tpu.models import engine as E  # noqa: E402
from ray_tpu.models import llama_init  # noqa: E402
from ray_tpu.ops.paged_attention_kernel import walk_shape  # noqa: E402

assert E.__file__.startswith(a.root + "/ray_tpu"), E.__file__
cell = spec.load_cell(a.cell)
model = dict(cell.config)
opts = dict(cell.config["engine"])
if a.rehearse:
    model.update(cell.config["rehearsal"]["model"])
    opts.update(cell.config["rehearsal"]["engine"])
opts.pop("warm_groups")
driver = cell.config.get("driver")
own = driver in ("serve_hybrid", "serve_gdn", "serve_mla",
                 "serve_mla_swa")
if own:     # a family that brings its own stack, and its own driver
    cfg, init, _ = importlib.import_module(
        "benchmark.harness.drivers." + driver).program_config(
        model, opts["max_len"])
elif model.get("model_type") in serve_model.FAMILIES:
    cfg, init, _ = serve_model.program_config(model, opts["max_len"])
else:
    cfg, init = llama_config(model, opts["max_len"],
                             activation_dtype=model["torch_dtype"],
                             param_dtype=model["torch_dtype"],
                             remat=False), llama_init
# an `rbg` key as those drivers draw their weights with (threefry
# compiles 40 s longer for the hybrid model's initialiser, PERF.md PR 31)
key = jax.random.wrap_key_data(
    jnp.tile(jax.random.key_data(common.seed_key(7)), 2), impl="rbg") \
    if own else common.seed_key(7)
params = jax.jit(init, static_argnums=1)(key, cfg)
jax.block_until_ready(params)
H, CALLS = opts.get("decode_horizon", 8), (2 if a.rehearse else 40)
lens = [int(x) for x in a.len.split(",") if x] or (
    [16, 24] if a.rehearse else [512, 1024])
lives = [int(x) for x in a.live.split(",") if x] or [opts["batch_slots"]]
out = {"tag": a.tag, "cell": a.cell, "device": str(jax.devices()[0]),
       "horizon": H}


def kernel_steps(eng, row_len):
    """Full steps' worth of pages the paged kernel walks for a decode
    token of these rows, the mean over the block's H tokens: a layer
    that reads the full cache walks the pages up to the query's slot, a
    window layer those from its window's first page."""
    cfg, T = eng.cfg, eng.kv_block_tokens
    if not hasattr(cfg, "n_kv_heads"):      # an `MlaConfig`: the paged
        return 0.0                          # kernel is not its attention
    pps = walk_shape(1, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, T,
                     eng._mb, eng._pool_k.dtype.itemsize)[0]
    slots = row_len[:, None] + np.arange(H)[None, :]
    pages = (slots // T + 1) \
        * getattr(cfg, "full_cache_readers", cfg.n_layers)
    if eng.kv_pool_w is not None:
        first = np.maximum(slots - (cfg.sliding_window - 1), 0) // T
        pages = pages + (slots // T + 1 - first) * cfg.n_window_layers
    return float(pages.sum()) / (pps * H)


for L in lens:
    for n_live in lives:
        eng = E.DecodeEngine(params, cfg, **opts)
        rng = np.random.default_rng(L)
        for _ in range(n_live):
            eng.submit(rng.integers(1, cfg.vocab_size, size=L).tolist(),
                       max_new_tokens=(90 if a.rehearse else 1500))
        rows = []
        while len(rows) < n_live:
            eng.step()
            rows = [b for b in range(eng.B) if eng.row_req[b] is not None
                    and b not in eng._row_prefill]
        eng._flush_pipeline({})
        assert eng._ensure_decode_blocks(rows, H, 0)
        args = eng._row_state()
        bt_dev = eng._table_snapshot(eng._bt)
        # a hybrid engine's second table, and its recurrent state, which
        # the program is given to keep (donated) like the pools
        btw_dev = eng._table_snapshot(eng._bt_w) \
            if eng.kv_pool_w is not None else None
        fixed = (jnp.asarray(eng._row_keys), jnp.asarray(eng._row_greedy),
                 eng.temperature, eng.cfg, H, bool(eng._row_greedy.all()),
                 eng.top_k, eng.top_p, eng.eos_id)
        pk, pv, ll, ctr, hyb = eng._pool_k, eng._pool_v, eng._last_logits, \
            eng._moe_ctr, eng._hyb

        def call(pk, pv, ll, hyb):
            r = E._decode_multi_paged(eng.params, pk, pv, bt_dev, ll, *args,
                                      *fixed, moe_ctr=ctr, hyb=hyb,
                                      bt_w=btw_dev)
            return r[1], r[2], r[5], r[11], r[10]

        for _ in range(3):
            pk, pv, ll, hyb, seen = call(pk, pv, ll, hyb)
        jax.block_until_ready(ll)
        reps = []
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(CALLS):
                pk, pv, ll, hyb, seen = call(pk, pv, ll, hyb)
            jax.block_until_ready(ll)
            reps.append((time.perf_counter() - t) / (CALLS * H) * 1e3)
        key = f"L{L}_live{n_live}"
        out[f"ms_per_token_{key}"] = reps
        out[f"row_len_{key}"] = [int(eng.row_len[rows].min()),
                                 int(eng.row_len[rows].max())]
        out[f"kernel_steps_{key}"] = kernel_steps(eng, eng.row_len[rows])
        if seen is not None:    # the last call's counts: hit / layer-steps
            d = np.asarray(seen) - np.asarray(ctr)
            out[f"experts_hit_{key}"] = float(d[2]) / float(d[3])
        del eng, pk, pv, ll, hyb
for n_live in lives:
    lo, hi = (f"L{L}_live{n_live}" for L in (min(lens), max(lens)))
    more = out[f"kernel_steps_{hi}"] - out[f"kernel_steps_{lo}"]
    if more > 0:
        out[f"us_per_step_live{n_live}"] = 1e3 * (
            min(out[f"ms_per_token_{hi}"])
            - min(out[f"ms_per_token_{lo}"])) / more
print("DECODE_AB " + json.dumps(out), flush=True)

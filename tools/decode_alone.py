"""The fused decode program ALONE at fixed row lengths, for comparing two
trees on one chip (PERF.md PR 30: a kernel that is equal alone may still
cost the program around it, and a traced benchmark run's
`decode_step_device_ms` moves with the row lengths its slice holds).

    python tools/decode_alone.py <tree root> <tag> [rehearse]

builds the `mistral7b-rollout` cell's engine from THAT tree (run it from
the tree's root), brings 32 rows of L tokens into decode for L in 512,
1,024, then calls `_decode_multi_paged` (horizon 8) 3 x 40 times on the
SAME row state and times it on the device's queue (async dispatch, one
wait at the end). Prints `DECODE_AB {json}`: ms a token. On the chip:
parent, change, change, parent in one `chiprun` call; `rehearse` runs the
cell's rehearsal size on the CPU."""
import json
import sys
import time

root, tag = sys.argv[1], sys.argv[2]
sys.path.insert(0, root)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import common, spec  # noqa: E402
from benchmark.harness.model import llama_config  # noqa: E402
from ray_tpu.models import engine as E  # noqa: E402
from ray_tpu.models import llama_init  # noqa: E402

assert E.__file__.startswith(root + "/ray_tpu"), E.__file__
REHEARSE = len(sys.argv) > 3
cell = spec.load_cell("mistral7b-rollout")
model = dict(cell.config)
opts = dict(cell.config["engine"])
if REHEARSE:
    model.update(cell.config["rehearsal"]["model"])
    opts.update(cell.config["rehearsal"]["engine"])
opts.pop("warm_groups")
cfg = llama_config(model, opts["max_len"],
                   activation_dtype=model["torch_dtype"],
                   param_dtype=model["torch_dtype"], remat=False)
params = jax.jit(llama_init, static_argnums=1)(common.seed_key(7), cfg)
jax.block_until_ready(params)
H, CALLS = 8, (2 if REHEARSE else 40)
out = {"tag": tag, "device": str(jax.devices()[0])}
for L in ((16, 24) if REHEARSE else (512, 1024)):
    eng = E.DecodeEngine(params, cfg, **opts)
    rng = np.random.default_rng(L)
    for _ in range(eng.B):
        eng.submit(rng.integers(1, cfg.vocab_size, size=L).tolist(),
                   max_new_tokens=(90 if REHEARSE else 1500))
    while not all(eng.row_req[b] is not None and b not in eng._row_prefill
                  for b in range(eng.B)):
        eng.step()
    eng._flush_pipeline({})
    rows = list(range(eng.B))
    assert eng._ensure_decode_blocks(rows, H, 0)
    args = eng._row_state()
    bt_dev = eng._table_snapshot(eng._bt)
    fixed = (jnp.asarray(eng._row_keys), jnp.asarray(eng._row_greedy),
             eng.temperature, eng.cfg, H, bool(eng._row_greedy.all()),
             eng.top_k, eng.top_p, eng.eos_id)
    pk, pv, ll, ctr = eng._pool_k, eng._pool_v, eng._last_logits, \
        eng._moe_ctr

    def call(pk, pv, ll):
        r = E._decode_multi_paged(eng.params, pk, pv, bt_dev, ll, *args,
                                  *fixed, moe_ctr=ctr)
        return r[1], r[2], r[5]

    for _ in range(3):
        pk, pv, ll = call(pk, pv, ll)
    jax.block_until_ready(ll)
    reps = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(CALLS):
            pk, pv, ll = call(pk, pv, ll)
        jax.block_until_ready(ll)
        reps.append((time.perf_counter() - t) / (CALLS * H) * 1e3)
    out[f"ms_per_token_L{L}"] = reps
    out[f"row_len_L{L}"] = [int(eng.row_len.min()), int(eng.row_len.max())]
    del eng, pk, pv, ll
print("DECODE_AB " + json.dumps(out), flush=True)

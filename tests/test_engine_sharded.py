"""Tensor-parallel DecodeEngine over an ICI mesh (ray_tpu/models/engine.py).

`DecodeEngine(tp=n)` shards the model weights, the KV block pool
and the fused decode scan state across n devices via
the model's logical axis rules (heads/mlp/vocab over "tp"; KV heads
when divisible). These tests run on the conftest-forced 8-device
virtual CPU mesh (see the note next to FakeClock in conftest.py) and
pin the contract:

- output is TOKEN-IDENTICAL to the single-chip engine and to solo
  `generate` at every tp degree, greedy and sampled, with and without
  the prefix cache and the async pipeline — sharding is a pure
  throughput/capacity optimization;
- the single [H, B] device->host choke point survives: one transfer
  per drained horizon, and transfer bytes per token do NOT grow with
  tp (the block is pinned replicated);
- prefix-cache eviction pressure and mid-flight drains behave exactly
  as on one chip (same evictions, same tokens);
- the tp/mesh knobs validate, and the tp plane reaches stats() and
  the metrics registry.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, llama_init  # noqa: E402
from ray_tpu.models.engine import DecodeEngine  # noqa: E402
from ray_tpu.models.generate import generate  # noqa: E402

TP_DEGREES = (1, 2, 4)


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(n, cfg, seed=7, lo=3, hi=9):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size,
                        size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


def _req_keys(n, seed=0):
    return [jax.random.PRNGKey(1000 + seed * 100 + i) for i in range(n)]


def _solo(params, cfg, prompt, n, mode, rng=None):
    out = np.asarray(generate(params, jnp.asarray([prompt], jnp.int32),
                              cfg, max_new_tokens=n, rng=rng, **mode))
    return out[0, len(prompt):].tolist()


def _run(params, cfg, prompts, budgets, tp, *, eng_kw=None, keys=None):
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64, tp=tp,
                       **(eng_kw or {}))
    ids = [eng.submit(p, n, rng=None if keys is None else keys[i])
           for i, (p, n) in enumerate(zip(prompts, budgets))]
    out = eng.run()
    return [out[r] for r in ids], eng


# ---------------------------------------------------------------------------
# Token identity: tp x sampling mode x prefix cache x pipeline depth
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    {"greedy": True},
    {"greedy": False, "temperature": 0.9, "top_k": 5},
], ids=["greedy", "top_k"])
@pytest.mark.parametrize("features", [
    {"pipeline_depth": 1},
    {"pipeline_depth": 2},
    {"prefix_cache": True, "kv_block_tokens": 4, "pipeline_depth": 1},
    {"prefix_cache": True, "kv_block_tokens": 4, "pipeline_depth": 2},
], ids=["plain_d1", "plain_d2", "prefix_d1", "prefix_d2"])
def test_sharded_token_identity_matrix(nano_model, mode, features):
    """Every tp degree produces the SAME tokens as solo `generate`
    (the gold contract every engine feature is already held to) and as
    the tp=1 engine on the same workload. Shared-prefix prompts drive
    the trie under the prefix variants; 5 requests through 2 slots
    churn admissions so slot reuse crosses sharded prefills."""
    cfg, params = nano_model
    base = _prompts(5, cfg)
    shared = list(range(3, 11))      # 2 full prefix blocks at T=4
    prompts = [shared + p for p in base[:2]] + base[2:]
    budgets = [7, 4, 9, 5, 6]
    keys = None if mode["greedy"] else _req_keys(len(prompts))
    ref = [_solo(params, cfg, p, n, mode,
                 rng=None if keys is None else keys[i])
           for i, (p, n) in enumerate(zip(prompts, budgets))]
    got1 = None
    for tp in TP_DEGREES:
        got, eng = _run(params, cfg, prompts, budgets, tp,
                        eng_kw={**mode, **features}, keys=keys)
        assert got == ref, f"tp={tp} diverged from solo generate"
        if got1 is None:
            got1 = got
        assert got == got1, f"tp={tp} diverged from tp=1 engine"
        s = eng.stats()
        assert s["tp_degree"] == float(tp)
        # The choke point survived: one transfer per drained block.
        assert s["decode_dispatches"] == s["host_syncs"]
        assert s["host_lag_steps"] == 0.0


def test_sharded_chunked_prefill_identity(nano_model):
    """Chunked prefill (multi-step suffix writes + mid-prefill frozen
    rows) is tp-blind: same tokens at every degree."""
    cfg, params = nano_model
    prompts = _prompts(4, cfg, seed=31, lo=6, hi=14)
    budgets = [5, 7, 4, 6]
    kw = {"prefill_chunk": 3, "prefix_cache": True, "kv_block_tokens": 4}
    ref, _ = _run(params, cfg, prompts, budgets, 1, eng_kw=kw)
    for tp in (2, 4):
        got, _ = _run(params, cfg, prompts, budgets, tp, eng_kw=kw)
        assert got == ref, f"tp={tp} diverged under chunked prefill"


# ---------------------------------------------------------------------------
# Prefix-cache pressure and mid-flight drain, sharded
# ---------------------------------------------------------------------------

def test_sharded_identity_under_eviction_pressure(nano_model):
    """A prefix pool too small for the working set (constant LRU
    eviction + re-prefill through the SHARDED copy-in/copy-out
    programs) must not perturb output: the host trie never sees the
    mesh, so eviction decisions — and tokens — match one chip
    exactly."""
    from ray_tpu.models.prefix_cache import block_bytes

    cfg, params = nano_model
    rng = np.random.RandomState(3)
    bb = block_bytes(cfg.n_layers, 4, cfg.n_kv_heads, cfg.head_dim, 4)
    prompts = []
    for i in range(3):
        pref = rng.randint(1, cfg.vocab_size, size=8).tolist()
        prompts += [pref + [30 + i], pref + [40 + i]]
    budgets = [5] * 6
    kw = {"prefix_cache": True, "kv_block_tokens": 4,
          "kv_pool_bytes": 4 * bb, "pipeline_depth": 2}
    ref, eng1 = _run(params, cfg, prompts, budgets, 1, eng_kw=kw)
    assert eng1.stats()["prefix_evictions"] > 0   # pressure was real
    for tp in (2, 4):
        got, eng = _run(params, cfg, prompts, budgets, tp, eng_kw=kw)
        assert got == ref
        assert eng.stats()["prefix_evictions"] == \
            eng1.stats()["prefix_evictions"]


def test_sharded_mid_flight_drain(nano_model):
    """begin_drain() with run-ahead blocks in flight on a sharded
    engine: in-flight requests finish with exactly their solo tokens,
    nothing new admits, and the ring fully drains (no stranded sharded
    buffers)."""
    cfg, params = nano_model
    from ray_tpu.models.scheduler import EngineDraining

    prompts = _prompts(3, cfg, seed=5)
    ref = [_solo(params, cfg, p, 12, {"greedy": True})
           for p in prompts[:2]]
    for tp in (2, 4):
        eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                           tp=tp, pipeline_depth=2, decode_horizon=4)
        a = eng.submit(prompts[0], 12)
        b = eng.submit(prompts[1], 12)
        eng.step()                       # pure decode: ring tops up
        assert eng.stats()["host_lag_steps"] >= 1.0
        out = eng.drain()
        with pytest.raises(EngineDraining):
            eng.submit(prompts[2], 4)
        assert out[a] == ref[0] and out[b] == ref[1]
        assert not eng.pending()
        assert eng.stats()["host_lag_steps"] == 0.0


# ---------------------------------------------------------------------------
# Choke point: host-transfer bytes must not scale with tp
# ---------------------------------------------------------------------------

def test_host_transfer_bytes_flat_across_tp(nano_model):
    """The [H, B] token block is pinned replicated, so the bytes each
    drain pulls are IDENTICAL at tp=1 and tp=4 — the device->host
    choke point does not multiply with chip count."""
    cfg, params = nano_model
    prompts = _prompts(4, cfg, seed=41)
    budgets = [6, 8, 5, 7]
    per_tp = {}
    for tp in (1, 4):
        _, eng = _run(params, cfg, prompts, budgets, tp,
                      eng_kw={"pipeline_depth": 2})
        s = eng.stats()
        assert s["host_transfer_bytes"] > 0
        per_tp[tp] = (s["host_transfer_bytes"], s["host_syncs"])
    assert per_tp[4][0] == per_tp[1][0], (
        "host-transfer bytes grew with tp degree: "
        f"tp1={per_tp[1][0]} tp4={per_tp[4][0]}")
    assert per_tp[4][1] == per_tp[1][1]


# ---------------------------------------------------------------------------
# Knobs, mesh= path, stats plane
# ---------------------------------------------------------------------------

def test_mesh_knob_and_validation(nano_model, tp_mesh):
    """mesh= accepts a prebuilt {"tp": n} mesh (the fixture factory);
    bad combinations fail eagerly at construction."""
    cfg, params = nano_model
    mesh = tp_mesh(2)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       mesh=mesh)
    assert eng.tp_degree == 2
    p = [5, 6, 7]
    rid = eng.submit(p, 4)
    assert eng.run()[rid] == _solo(params, cfg, p, 4, {"greedy": True})

    with pytest.raises(ValueError, match="not both"):
        DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                     mesh=mesh, tp=2)
    with pytest.raises(ValueError, match="tp must be >= 1"):
        DecodeEngine(params, cfg, batch_slots=2, max_len=64, tp=0)
    with pytest.raises(ValueError, match="exceeds"):
        DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                     tp=len(jax.devices()) + 1)
    # create_mesh always carries every named axis (size 1), so a
    # tp-less mesh only arises hand-built — still validated eagerly.
    from jax.sharding import Mesh
    dp_mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    with pytest.raises(ValueError, match="'tp' axis"):
        DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                     mesh=dp_mesh)


def test_kv_rule_degrades_by_divisibility(nano_model):
    """nano has n_kv_heads=2: tp=2 shards the KV pool's head lanes;
    tp=4 can't divide it, so KV replicates while heads (4) and vocab
    (256) still shard — prune_rules_for_mesh per-axis divisibility."""
    cfg, params = nano_model
    e2 = DecodeEngine(params, cfg, batch_slots=2, max_len=64, tp=2,
                      enable_metrics=False)
    assert e2._rules["kv"] == "tp"
    assert e2._pool_k.sharding.spec[3] == "tp"
    assert e2._pool_k.sharding.shard_shape(e2._pool_k.shape)[3] == \
        cfg.head_dim                       # one KV head's lanes a chip
    e4 = DecodeEngine(params, cfg, batch_slots=2, max_len=64, tp=4,
                      enable_metrics=False)
    assert e4._rules["kv"] is None
    assert e4._rules["heads"] == "tp"
    assert e4._rules["vocab"] == "tp"
    assert e4._pool_k.sharding.spec[3] is None
    # Weights really shard: a head-axis param's per-chip slice shrinks.
    wq4 = e4.params["layers"]["wq"]
    assert wq4.sharding.shard_shape(wq4.shape)[2] == cfg.n_heads // 4


def test_tp_plane_reaches_stats_and_registry(nano_model):
    """tp_degree and host-transfer bytes flow through stats() and the
    llm_engine_* registry like every other engine series."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64, tp=2,
                       engine_id="sharded-metrics-test")
    for p in _prompts(2, cfg, seed=23):
        eng.submit(p, 5)
    eng.run()
    s = eng.stats()
    assert s["tp_degree"] == 2.0
    assert s["host_transfer_bytes"] > 0
    assert s["host_transfer_bytes_per_token"] > 0

    from ray_tpu._private import metrics as _impl

    rows = [r for r in _impl.snapshots()
            if r["tags"].get("engine") == "sharded-metrics-test"]
    by_name = {r["name"]: r for r in rows}
    assert by_name["llm_engine_tp_degree"]["value"] == 2.0
    assert by_name["llm_engine_host_transfer_bytes_total"]["value"] \
        == s["host_transfer_bytes"]


def test_sharded_int8_pool_identity(nano_model):
    """A quantized pool under a mesh: the scale slabs shard by KV head
    like the pages they scale, so tp=2 emits exactly what the
    one-device int8 engine emits, prefix sharing and chunking on."""
    cfg, params = nano_model
    shared = list(range(3, 11))
    a, b = (shared + p for p in _prompts(2, cfg, seed=43))
    prompts = [a] + _prompts(2, cfg, seed=44) + [b]   # b admits warm
    budgets = [6, 8, 5, 7]
    kw = {"kv_quant": "int8", "kv_block_tokens": 4,
          "prefix_cache": True, "prefill_chunk": 3}
    ref, _ = _run(params, cfg, prompts, budgets, 1, eng_kw=kw)
    got, eng = _run(params, cfg, prompts, budgets, 2, eng_kw=kw)
    assert got == ref
    assert eng._scale_k.sharding.spec[2] == "tp"
    assert eng.stats()["kv_blocks_shared"] >= 2


def test_no_program_of_a_tp_engine_picks_the_mosaic_kernel(nano_model,
                                                           monkeypatch):
    """GSPMD cannot partition a Mosaic kernel, so while a tp engine's
    programs are traced `paged_attention(impl="auto")` must stay on the
    pure-lax path even where the backend is a TPU. Every dispatch that
    traces one announces the mesh (`spmd_mesh_scope`): the decode
    always did; prefill attends through the block table since PR 30 and
    forgot to, which only a four-chip run showed (`chip_smoke.py
    --chips 4`: "Mosaic kernels cannot be automatically partitioned").
    Here the backend is made to answer "tpu" and the kernel to refuse:
    a prefill, a chunked continuation and decode steps all pass."""
    from ray_tpu.ops import paged_attention_kernel as pak

    def refuse(*a, **k):
        raise AssertionError("a tp program reached the Mosaic kernel")

    monkeypatch.setattr(pak, "paged_attention_kernel", refuse)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params = nano_model
    prompts = _prompts(3, cfg, seed=13, lo=5, hi=20)
    toks, eng = _run(params, cfg, prompts, [4, 3, 5], 2,
                     eng_kw={"prefill_chunk": 8})
    assert [len(t) for t in toks] == [4, 3, 5]
    assert eng.stats()["prefill_dispatches"] >= 3

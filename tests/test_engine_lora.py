"""Multi-LoRA serving (ray_tpu/models/adapter_pool.py + engine lora=).

Gold contract, extending the engine suite's: every adapter row of a
MIXED heterogeneous-adapter batch is token-identical to a solo
`generate` run on that adapter's `lora_merge`d weights — greedy and
sampled — while base-only rows stay bit-identical to a lora=None
engine. One fused dispatch serves all rows; residency (LRU eviction +
async prefetch), preemption, paged KV, prefix caching, pipelining and
tensor parallelism change WHERE adapter weights live and WHEN rows
run, never what a row computes.

Adapters here are randomized (lora_init's b=0 start would make every
"adapter" an alias of the base model and the identity checks
vacuous).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (LlamaConfig, LoraConfig, llama_init,
                            lora_init, lora_merge, lora_stack_specs)
from ray_tpu.models.adapter_pool import AdapterPool
from ray_tpu.models.engine import DecodeEngine
from ray_tpu.models.fleet import LLMFleet
from ray_tpu.models.generate import generate
from ray_tpu.models.prefix_cache import block_bytes
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.parallel.sharding import DEFAULT_RULES, prune_rules_for_mesh

T = 4                                   # kv_block_tokens under test


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


LCFG = LoraConfig(rank=4, alpha=8.0)


def _rand_lora(cfg, seed, scale=0.05):
    """A non-trivial adapter: both a AND b randomized (b=0 from
    lora_init is the identity adapter — useless for identity tests)."""
    lp = lora_init(jax.random.PRNGKey(seed), cfg, LCFG)
    leaves, tree = jax.tree_util.tree_flatten(lp)
    key = jax.random.PRNGKey(seed + 999)
    out = []
    for leaf in leaves:
        key, sub = jax.random.split(key)
        out.append(jax.random.normal(sub, leaf.shape, leaf.dtype) * scale)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module")
def adapters(nano_model):
    cfg, params = nano_model
    loras = {f"ad{i}": _rand_lora(cfg, 10 + i) for i in range(3)}
    merged = {a: lora_merge(params, lp, cfg, LCFG)
              for a, lp in loras.items()}
    return loras, merged


def _solo(params, cfg, prompt, n, mode=None, rng=None):
    out = np.asarray(generate(params, jnp.asarray([prompt], jnp.int32),
                              cfg, max_new_tokens=n, rng=rng,
                              **(mode or {})))
    return out[0, len(prompt):].tolist()


def _req_keys(n, seed=0):
    return [jax.random.PRNGKey(1000 + seed * 100 + i) for i in range(n)]


def _pool_bytes(cfg, n_blocks):
    return n_blocks * block_bytes(cfg.n_layers, T, cfg.n_kv_heads,
                                  cfg.head_dim,
                                  jnp.dtype(cfg.dtype).itemsize)


# ---------------------------------------------------------------------------
# Token identity: mixed-adapter batch x sampling x engine feature matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    {"greedy": True},
    {"greedy": False, "temperature": 0.9, "top_k": 5},
], ids=["greedy", "top_k"])
@pytest.mark.parametrize("features", [
    {},
    {"kv_block_tokens": T, "prefix_cache": True},
    {"kv_block_tokens": T, "prefix_cache": True,
     "pipeline_depth": 2},
    {"tp": 2},
], ids=["blocks32", "prefix", "prefix_pipeline", "tp2"])
def test_mixed_adapter_identity_matrix(nano_model, adapters, mode,
                                       features):
    """Three distinct adapters + base-only rows through ONE engine with
    residency for only TWO (max_live_adapters=2 < 3 registered): the
    run is forced through at least one LRU eviction and prefetch
    round-trip, and every row still equals its solo merged-weight
    reference. Shared-prefix prompts drive the trie under the prefix
    variants — adapter rows must bypass it (adapter-dependent K/V
    never crosses adapters), base rows may hit it."""
    cfg, params = nano_model
    loras, merged = adapters
    shared = list(range(3, 11))
    prompts = [shared + [1, 2, 3, 4], shared + [5, 6, 7],
               [9, 10, 11, 12, 13], [3, 1, 4], shared + [2, 2]]
    aids = ["ad0", "ad1", None, "ad2", "ad0"]
    budgets = [7, 4, 9, 5, 6]
    keys = None if mode["greedy"] else _req_keys(len(prompts))
    rng_kw = {} if mode["greedy"] else {"rng": jax.random.PRNGKey(7)}

    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       lora=LCFG, max_live_adapters=2,
                       **mode, **rng_kw, **features)
    for a, lp in loras.items():
        eng.register_adapter(a, lp)
    ids = [eng.submit(p, n, adapter_id=a,
                      rng=None if keys is None else keys[i])
           for i, (p, n, a) in enumerate(zip(prompts, budgets, aids))]
    out = eng.run()

    for i, (rid, p, n, a) in enumerate(zip(ids, prompts, budgets, aids)):
        ref = _solo(params if a is None else merged[a], cfg, p, n, mode,
                    rng=None if keys is None else keys[i])
        assert out[rid] == ref, f"adapter {a} diverged from merged solo"

    s = eng.stats()
    assert s["adapter_evictions"] >= 1.0, "residency never cycled"
    assert s["adapter_prefetches"] >= 3.0
    assert s["adapter_hits"] >= 1.0
    # every slot reference returned: nothing pinned after drain
    assert not any(eng.adapter_pool._refs), eng.adapter_pool._refs
    assert not eng._pending_slots


def test_preempt_swap_identity_with_adapters(nano_model, adapters):
    """Paged pool sized for 2 of 4 in-flight adapter rows: preemption
    swaps rows (and their slot pins) out and back in; tokens stay
    identical and every adapter slot reference drains — a preempted
    row must decref on swap-out and re-acquire at re-admission, or
    the pool leaks pins and eviction wedges."""
    cfg, params = nano_model
    loras, merged = adapters
    prompts = [[7, 8, 9, 10, 11], [3, 1, 4, 1, 5],
               [2, 7, 1, 8, 2], [9, 9, 8, 8, 7]]
    aids = ["ad0", "ad1", None, "ad2"]
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=64,
                       kv_block_tokens=T,
                       kv_pool_bytes=_pool_bytes(cfg, 10),
                       prefix_cache=False, greedy=True,
                       lora=LCFG, max_live_adapters=2)
    for a, lp in loras.items():
        eng.register_adapter(a, lp)
    ids = [eng.submit(p, 12, adapter_id=a)
           for p, a in zip(prompts, aids)]
    out = eng.run()

    for rid, p, a in zip(ids, prompts, aids):
        ref = _solo(params if a is None else merged[a], cfg, p, 12,
                    {"greedy": True})
        assert out[rid] == ref, f"adapter {a} diverged across swap"
    assert eng.stats()["preemptions"] >= 1.0
    assert not any(eng.adapter_pool._refs), eng.adapter_pool._refs


def test_base_only_rows_bit_identical_to_plain_engine(nano_model):
    """An adapter-ENABLED engine serving only adapter_id=None requests
    emits the same tokens as a lora=None engine: slot-0 (null adapter)
    deltas are exact zeros, not epsilon noise."""
    cfg, params = nano_model
    prompts = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2]]
    plain = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                         greedy=True)
    p_ids = [plain.submit(p, 5) for p in prompts]
    p_out = plain.run()

    lora_eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                            greedy=True, lora=LCFG, max_live_adapters=2)
    l_ids = [lora_eng.submit(p, 5) for p in prompts]
    l_out = lora_eng.run()

    assert [p_out[i] for i in p_ids] == [l_out[i] for i in l_ids]
    s = lora_eng.stats()
    assert s["adapter_lookups"] == 0.0
    assert s["adapter_prefetches"] == 0.0


# ---------------------------------------------------------------------------
# Residency: cold-adapter defer, eviction under pressure, pinning
# ---------------------------------------------------------------------------

def test_cold_adapter_prefetch_then_defer_then_decode(nano_model,
                                                      adapters):
    """A cold adapter's first admission attempt kicks off an async
    prefetch and defers the request (counted) instead of blocking the
    step; once the stage commits, the request decodes normally."""
    cfg, params = nano_model
    loras, merged = adapters
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       greedy=True, scheduler="adapter",
                       lora=LCFG, max_live_adapters=2)
    eng.register_adapter("ad0", loras["ad0"])
    rid_cold = eng.submit([1, 2, 3], 4, adapter_id="ad0")
    rid_base = eng.submit([4, 5], 4)
    out = eng.run()
    assert out[rid_cold] == _solo(merged["ad0"], cfg, [1, 2, 3], 4,
                                  {"greedy": True})
    assert out[rid_base] == _solo(params, cfg, [4, 5], 4,
                                  {"greedy": True})
    s = eng.stats()
    assert s["adapter_prefetch_deferrals"] >= 1.0
    assert s["adapter_prefetches"] == 1.0


def test_pool_pinned_adapter_never_evicted(nano_model, adapters):
    """Direct pool contract: with max_live_adapters=2, a slot held by
    alloc (refcount > 0) survives any amount of churn — eviction only
    ever takes refcount-0 LRU residents — and unregistering a pinned
    adapter defers until the last reference drops."""
    cfg, _ = nano_model
    loras, _m = adapters
    pool = AdapterPool(cfg, LCFG, max_live_adapters=2)
    for a, lp in loras.items():
        pool.register(a, lp)

    pool.prefetch("ad0")
    pool.drain_prefetches()
    slot = pool.alloc("ad0")
    assert slot is not None and pool._refs[slot] == 1

    # churn the other two through the single remaining slot
    for aid in ("ad1", "ad2", "ad1", "ad2"):
        if not pool.resident(aid):
            pool.prefetch(aid)
            pool.drain_prefetches()
        assert pool.resident("ad0"), "pinned adapter evicted"
    assert pool.evictions >= 3

    # deferred unregister: pinned now, gone at last decref
    assert pool.unregister("ad0") is False
    assert pool.registered("ad0")
    pool.decref(slot)
    assert not pool.registered("ad0")
    assert not pool.resident("ad0")

    # with the pin gone, the slot is reclaimable again
    pool.prefetch("ad1")
    pool.drain_prefetches()
    assert pool.resident("ad1")


def test_pool_alloc_unknown_adapter_raises(nano_model):
    cfg, _ = nano_model
    pool = AdapterPool(cfg, LCFG, max_live_adapters=2)
    with pytest.raises(KeyError):
        pool.alloc("never-registered")
    assert pool.alloc(None) == 0        # null adapter, never refcounted
    pool.decref(0)                      # no-op, not an underflow


def test_engine_submit_unknown_adapter_raises(nano_model):
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       lora=LCFG)
    with pytest.raises(KeyError):
        eng.submit([1, 2], 2, adapter_id="nope")
    plain = DecodeEngine(params, cfg, batch_slots=2, max_len=32)
    with pytest.raises(ValueError):
        plain.submit([1, 2], 2, adapter_id="any")


# ---------------------------------------------------------------------------
# Sharding: adapter stacks follow the PRUNED base rules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tp", [1, 2, 4])
def test_lora_stack_specs_prune_parity(nano_model, tp):
    """Satellite gate: the adapter stacks' sharded axes degrade to
    replicated EXACTLY when the base weight's axis does. nano's
    n_kv_heads=2 shards wk/wv over tp=2 but must replicate at tp=4
    (uneven split) — the b-stack fan-out spec must flip with it, and
    the rank/slot axes always replicate."""
    cfg, _ = nano_model
    devs = jax.devices()
    assert len(devs) >= 4, "conftest must force 8 host devices"
    mesh = create_mesh({"tp": tp}, devs[:tp])
    dims = {"heads": cfg.n_heads, "qkv": cfg.n_heads,
            "kv": cfg.n_kv_heads, "mlp": cfg.ffn_dim,
            "vocab": cfg.vocab_size, "embed": cfg.dim, "batch": 2}
    base = dict(DEFAULT_RULES)
    base["kv"] = "tp"
    rules = prune_rules_for_mesh(base, mesh, dims)
    specs = lora_stack_specs(cfg, LCFG, rules)

    for name, ab in specs.items():
        # slot + rank axes: never sharded
        assert ab["a"][1] is None and ab["a"][3] is None
        assert ab["b"][1] is None and ab["b"][2] is None
    kv_sharded = rules["kv"] == "tp"
    assert kv_sharded == (cfg.n_kv_heads % tp == 0 and tp > 1)
    for name in ("wk", "wv"):
        want = "tp" if kv_sharded else None
        assert specs[name]["b"][3] == want, (
            f"{name} b-stack fan-out spec diverged from pruned base "
            f"kv rule at tp={tp}")
    heads_sharded = rules["heads"] == "tp"
    assert specs["wo"]["a"][2] == ("tp" if heads_sharded else None)


def test_sharded_engine_stacks_match_specs(nano_model, adapters):
    """The live engine's device stacks carry the pruned specs (tp=2:
    wk b-stack sharded; tp=4 would replicate) — proving the pool
    plumbed the engine's OWN rule table, not a fresh unpruned one."""
    cfg, params = nano_model
    loras, merged = adapters
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32, tp=2,
                       greedy=True, lora=LCFG, max_live_adapters=2)
    eng.register_adapter("ad0", loras["ad0"])
    rid = eng.submit([5, 6, 7], 4, adapter_id="ad0")
    out = eng.run()
    assert out[rid] == _solo(merged["ad0"], cfg, [5, 6, 7], 4,
                             {"greedy": True})
    def norm(spec):                      # P drops trailing Nones
        t = tuple(spec)
        while t and t[-1] is None:
            t = t[:-1]
        return t

    specs = lora_stack_specs(cfg, LCFG, eng._rules)
    for name, ab in eng.adapter_pool.stacks.items():
        assert norm(ab["a"].sharding.spec) == norm(specs[name]["a"])
        assert norm(ab["b"].sharding.spec) == norm(specs[name]["b"])


# ---------------------------------------------------------------------------
# Sanitizer: multi-adapter churn is retrace-free and transfer-clean
# ---------------------------------------------------------------------------

def test_sanitizer_clean_on_multi_adapter_churn(nano_model, adapters):
    """Armed run over adapter churn (hits, misses, prefetch commits,
    evictions): 0 retraces, 0 unexpected device->host transfers. The
    commit scatter takes its slot as a TRACED scalar — a static slot
    would recompile per slot and fail here."""
    from ray_tpu._private.sanitize import SanitizerError

    cfg, params = nano_model
    loras, _merged = adapters
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       greedy=True, lora=LCFG, max_live_adapters=2)
    for a, lp in loras.items():
        eng.register_adapter(a, lp)
    prompts = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2]] * 2
    churn_aids = ["ad0", "ad1", "ad2", None, "ad0", "ad2"]

    def churn():
        ids = [eng.submit(p, 4, adapter_id=a)
               for p, a in zip(prompts, churn_aids)]
        out = eng.run()
        return [out[r] for r in ids]

    churn()                      # cold compiles + first commits
    churn()                      # warm-hit paths
    san = eng.arm_sanitizer()
    try:
        churn()
    except SanitizerError as exc:
        pytest.fail(f"unexpected transfer on adapter churn: {exc}")
    finally:
        eng.disarm_sanitizer()
    assert san.total_retraces() == 0, san.retraces()
    assert san.unexpected_transfers == [], san.unexpected_transfers
    assert eng.adapter_pool.evictions >= 1


# ---------------------------------------------------------------------------
# Fleet: adapter-affinity routing + registry replay
# ---------------------------------------------------------------------------

def test_fleet_adapter_affinity_routing_identity(nano_model, adapters):
    """pow2_affinity steers repeat-adapter traffic to replicas already
    holding the adapter (router_adapter_wins > 0) unless overloaded —
    and every request still matches its merged-weight solo run."""
    cfg, params = nano_model
    loras, merged = adapters

    def factory(name):
        return DecodeEngine(params, cfg, engine_id=name, batch_slots=2,
                            max_len=32, greedy=True, lora=LCFG,
                            max_live_adapters=2)

    fleet = LLMFleet(factory, initial_replicas=2,
                     router="pow2_affinity", fleet_id="lora-affinity")
    for a, lp in loras.items():
        fleet.register_adapter(a, lp)
    assert sorted(fleet.adapter_ids()) == ["ad0", "ad1", "ad2"]

    prompts = [[5, 6, 7], [9, 8, 7], [1, 2, 3], [4, 5, 6],
               [7, 8, 9], [2, 2, 2]]
    aids = ["ad0", "ad1", "ad0", "ad1", "ad0", None]
    fids = []
    for p, a in zip(prompts, aids):
        fids.append(fleet.submit(p, 4, adapter_id=a))
        fleet.step()              # interleave so residency forms
    out = fleet.run()

    for fid, p, a in zip(fids, prompts, aids):
        ref = _solo(params if a is None else merged[a], cfg, p, 4,
                    {"greedy": True})
        assert out[fid] == ref, f"fleet adapter {a} diverged"
    s = fleet.stats()
    assert s["router_adapter_wins"] >= 1.0
    assert s["adapter_hit_rate"] > 0.0

    with pytest.raises(KeyError):
        fleet.submit([1, 2], 2, adapter_id="never-registered")


def test_fleet_add_replica_replays_adapter_registry(nano_model,
                                                    adapters):
    """A replica joining AFTER registration still serves every
    registered adapter: the fleet replays its adapter table onto the
    newcomer's pool."""
    cfg, params = nano_model
    loras, merged = adapters

    def factory(name):
        return DecodeEngine(params, cfg, engine_id=name, batch_slots=2,
                            max_len=32, greedy=True, lora=LCFG,
                            max_live_adapters=2)

    fleet = LLMFleet(factory, initial_replicas=1,
                     router="round_robin", fleet_id="lora-replay")
    fleet.register_adapter("ad0", loras["ad0"])
    fleet.add_replica()
    for rep in fleet.replicas:
        assert "ad0" in rep.engine.adapter_pool.adapter_ids()
    fid = fleet.submit([5, 6, 7], 4, adapter_id="ad0")
    out = fleet.run()
    assert out[fid] == _solo(merged["ad0"], cfg, [5, 6, 7], 4,
                             {"greedy": True})
    fleet.unregister_adapter("ad0")
    assert fleet.adapter_ids() == []


# ---------------------------------------------------------------------------
# Serve seam: model_id resolution + multiplex eviction callback
# ---------------------------------------------------------------------------

def test_llm_server_model_id_resolution(nano_model, adapters):
    """LLMFleetServer.generate(model_id=...) resolves through the
    registered-adapter table; unknown ids raise instead of silently
    serving base-model tokens; omitted model_id means base."""
    from ray_tpu.serve.llm import LLMFleetServer

    cfg, params = nano_model
    loras, merged = adapters

    def factory(name):
        return DecodeEngine(params, cfg, engine_id=name, batch_slots=2,
                            max_len=32, greedy=True, lora=LCFG,
                            max_live_adapters=2)

    srv = LLMFleetServer(factory, initial_replicas=1,
                         report_stats=False, fleet_id="lora-serve")
    srv.register_model("ft-a", loras["ad0"])
    assert srv.model_ids() == ["ft-a"]

    r = srv.generate([5, 6, 7], max_new_tokens=4, model_id="ft-a")
    assert r["tokens"][3:] == _solo(merged["ad0"], cfg, [5, 6, 7], 4,
                                    {"greedy": True})
    base = srv.generate([5, 6, 7], max_new_tokens=4)
    assert base["tokens"][3:] == _solo(params, cfg, [5, 6, 7], 4,
                                       {"greedy": True})
    with pytest.raises(KeyError):
        srv.generate([1, 2], max_new_tokens=2, model_id="nope")

    srv.unregister_model("ft-a")
    assert srv.model_ids() == []


def test_multiplex_on_evict_callback(nano_model, adapters):
    """serve.multiplexed(on_evict=...) fires for every LRU drop — the
    seam that lets the wrapper call LLMFleetServer.unregister_model so
    the multiplex cache and adapter pools agree — and a raising
    callback never fails the request that triggered eviction."""
    from ray_tpu.serve.multiplex import multiplexed

    evicted = []

    @multiplexed(max_num_models_per_replica=1,
                 on_evict=lambda mid, m: evicted.append((mid, m)))
    async def load(model_id):
        return model_id.upper()

    async def drive():
        assert await load("a") == "A"
        assert await load("b") == "B"       # evicts a
        assert await load("a") == "A"       # reload; evicts b
        return True

    assert asyncio.run(drive())
    assert evicted == [("a", "A"), ("b", "B")]

    boom = []

    @multiplexed(max_num_models_per_replica=1,
                 on_evict=lambda mid, m: boom.append(mid) or 1 / 0)
    async def load2(model_id):
        return model_id

    async def drive2():
        await load2("x")
        return await load2("y")             # eviction callback raises

    assert asyncio.run(drive2()) == "y"
    assert boom == ["x"]

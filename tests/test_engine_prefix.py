"""Prefix-reuse KV cache + chunked prefill (ray_tpu/models/engine.py,
models/prefix_cache.py, scheduler.PrefixAffinityPolicy).

Contract under test, extending the engine gold contract: with the
shared-prefix cache ON — warm admissions sharing cached K/V blocks and
prefilling only their suffix, chunked prefill interleaving with decode,
LRU eviction under pool pressure, prefix-affinity admission deferral —
every request's output stays token-identical to its solo `generate`
run, greedy and sampled. Plus the efficiency gates: a 100%-hit
admission runs ZERO full-prompt prefill tokens (suffix only), and the
padding-waste / prefix-reuse / stall telemetry lands in both stats()
and the Prometheus registry. Satellites: derived stats ratios are
0.0 (never NaN) on a fresh engine; speculative SpecStats publish
through the same util.metrics plane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, llama_init
from ray_tpu.models.engine import DecodeEngine
from ray_tpu.models.engine_metrics import EngineMetrics
from ray_tpu.models.block_pool import BlockPool
from ray_tpu.models.generate import generate
from ray_tpu.models.prefix_cache import PrefixCacheIndex, block_bytes
from ray_tpu.models.scheduler import PrefixAffinityPolicy, make_policy


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _solo(params, cfg, prompt, n, **kw):
    out = np.asarray(generate(params, jnp.asarray([prompt], jnp.int32),
                              cfg, max_new_tokens=n, **kw))
    return out[0, len(prompt):].tolist()


PREFIX = [7, 3, 9, 1, 4, 4, 2, 8, 5, 6, 1, 2]        # 3 blocks of 4
SUFFIXES = [[11, 12], [13, 14], [15, 16], [17, 18], [19, 20]]

SAMPLING_MODES = {
    "greedy": {},
    "top_k": {"greedy": False, "temperature": 0.9, "top_k": 8},
    "top_p": {"greedy": False, "temperature": 1.1, "top_p": 0.9},
}


# ---------------------------------------------------------------------------
# Token identity: shared prefix x sampling x chunking x cache on/off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", list(SAMPLING_MODES))
@pytest.mark.parametrize("chunked", [False, True],
                         ids=["unchunked", "chunked"])
def test_prefix_identity_matrix(nano_model, mode, chunked):
    """Five requests sharing a system-prompt prefix, more requests than
    slots, prefix-affinity scheduling, cache ON (+ chunked prefill):
    every request matches its solo run exactly — warm admissions'
    shared K/V and suffix-only prefill change no token."""
    cfg, params = nano_model
    kw = SAMPLING_MODES[mode]
    prompts = [PREFIX + s for s in SUFFIXES]
    budgets = [4, 6, 3, 5, 4]
    keys = [jax.random.PRNGKey(300 + i) for i in range(len(prompts))]

    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       prefix_cache=True, kv_block_tokens=4,
                       scheduler="prefix",
                       prefill_chunk=4 if chunked else None, **kw)
    ids = [eng.submit(p, n, rng=k)
           for p, n, k in zip(prompts, budgets, keys)]
    out = eng.run()
    for rid, p, n, k in zip(ids, prompts, budgets, keys):
        want = _solo(params, cfg, p, n, rng=k, **kw)
        assert out[rid] == want, f"req {rid} mode={mode}"
    s = eng.stats()
    assert s["prefix_hits"] >= 1          # later admissions ran warm
    assert s["prefix_reused_tokens"] >= 12


def test_chunked_identity_without_prefix_cache(nano_model):
    """prefill_chunk is independent of the prefix cache: chunked-only
    engines (cache off) also stay token-identical."""
    cfg, params = nano_model
    prompts = [PREFIX + s for s in SUFFIXES[:3]]
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       prefill_chunk=4)
    ids = [eng.submit(p, 4) for p in prompts]
    out = eng.run()
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, 4)
    assert eng.stats()["chunked_prefill_stalls"] >= 1


def test_prefix_identity_under_eviction_pressure(nano_model):
    """A pool too small for the working set: LRU eviction recycles
    blocks while requests stream through — still token-identical, and
    evictions actually happened (the pressure was real)."""
    cfg, params = nano_model
    # 6 usable blocks, 3 of them under the live row; 4 distinct
    # prefixes x 2 blocks = 8 -> eviction.
    L, _, _, KV, D = (2, 0, 0, cfg.n_kv_heads, cfg.head_dim)
    bb = block_bytes(cfg.n_layers, 4, KV, D, 4)
    prompts = []
    rng = np.random.RandomState(3)
    for i in range(4):
        pref = rng.randint(1, cfg.vocab_size, size=8).tolist()
        prompts += [pref + [30 + i], pref + [40 + i]]
    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       prefix_cache=True, kv_block_tokens=4,
                       kv_pool_bytes=6 * bb)
    ids = [eng.submit(p, 3) for p in prompts]
    out = eng.run()
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, 3)
    s = eng.stats()
    assert s["prefix_evictions"] > 0
    assert s["prefix_blocks_total"] == 6.0
    assert s["prefix_blocks_in_use"] <= 6.0


# ---------------------------------------------------------------------------
# Efficiency gates
# ---------------------------------------------------------------------------

def test_warm_admission_runs_zero_full_prompt_prefill(nano_model):
    """THE reuse gate: after one cold request seeds the trie, a
    same-prefix admission (100% hit: every full block cached) prefills
    ONLY its 1-token suffix — prefill_real_tokens moves by exactly 1,
    reused tokens by the whole matched prefix."""
    cfg, params = nano_model
    prefix = list(range(1, 17))                       # 4 blocks of 4
    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       prefix_cache=True, kv_block_tokens=4)
    r0 = eng.submit(prefix + [21], 3)
    out0 = eng.run()
    assert out0[r0] == _solo(params, cfg, prefix + [21], 3)
    real0, reused0 = eng.prefill_real_tokens, eng.prefix_reused_tokens

    r1 = eng.submit(prefix + [22], 3)
    out = eng.run()
    assert out[r1] == _solo(params, cfg, prefix + [22], 3)
    assert eng.prefill_real_tokens - real0 == 1       # suffix only
    assert eng.prefix_reused_tokens - reused0 == 16   # whole prefix
    s = eng.stats()
    assert s["prefix_hit_rate"] == 0.5                # 1 of 2 lookups
    assert s["kv_blocks_shared"] == 4.0               # shared, not copied


def test_chunked_prefill_interleaves_with_decode(nano_model):
    """While a long prompt advances chunk-by-chunk, the already-live
    row keeps emitting tokens every step (bounded TPOT — the point of
    chunked prefill), and the stall counter records the overlap."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       prefill_chunk=4)
    ra = eng.submit([5, 6, 7], 12)
    eng.step()                            # A admitted, decoding
    long_prompt = list(range(1, 14))      # 13 tokens -> 4 chunks
    rb = eng.submit(long_prompt, 3)
    a_tokens_during_prefill = 0
    while rb not in eng.finished and ra not in eng.finished:
        ev = eng.step()
        a_tokens_during_prefill += len(ev.get(ra, []))
    out = eng.run()                       # pops every finished request
    assert out[ra] == _solo(params, cfg, [5, 6, 7], 12)
    assert out[rb] == _solo(params, cfg, long_prompt, 3)
    assert a_tokens_during_prefill >= 2   # A progressed during B's prefill
    assert eng.chunked_prefill_stalls >= 2


def test_prefill_padding_waste_metric(nano_model):
    """A 3-wide same-bucket admission group pads to 4 rows: the filler
    row's tokens are counted and surfaced as
    prefill_padding_waste_frac (satellite: padded-row accounting)."""
    cfg, params = nano_model
    prompts = [[5, 6, 7], [9, 8, 7], [1, 2, 3]]       # one bucket, n=3
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=32)
    for p in prompts:
        eng.submit(p, 3)
    eng.run()
    s = eng.stats()
    # bucket(3)=4 wide, group padded 3->4 rows: real 3*3=9, padded
    # 4*4-9=7.
    assert s["prefill_real_tokens"] == 9.0
    assert s["prefill_padded_tokens"] == 7.0
    assert s["prefill_padding_waste_frac"] == pytest.approx(7 / 16)


# ---------------------------------------------------------------------------
# Prefix-affinity scheduling
# ---------------------------------------------------------------------------

def test_prefix_policy_defers_followers_then_admits_warm(nano_model):
    """Burst of 3 same-prefix requests into 3 free slots: the policy
    admits ONE cold leader the first step (same-group followers defer
    rather than recompute the prefix in parallel), then both followers
    admit WARM next step."""
    cfg, params = nano_model
    prompts = [PREFIX[:8] + [s] for s in (31, 32, 33)]
    eng = DecodeEngine(params, cfg, batch_slots=3, max_len=32,
                       prefix_cache=True, kv_block_tokens=4,
                       scheduler="prefix")
    ids = [eng.submit(p, 4) for p in prompts]
    eng.step()
    assert sum(r is not None for r in eng.row_req) == 1   # leader only
    assert len(eng.scheduler) == 2                        # followers wait
    eng.step()                     # both followers admitted, WARM
    assert len(eng.scheduler) == 0
    assert eng.prefix_hits == 2
    assert eng.prefix_reused_tokens == 16                 # 2 x 8
    out = eng.run()
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, 4)


def test_prefix_policy_without_probe_is_fifo():
    """Outside a prefix-cache engine the policy degrades to FIFO, and
    make_policy resolves the "prefix" name."""
    pol = make_policy("prefix")
    assert isinstance(pol, PrefixAffinityPolicy)
    reqs = [type("R", (), {"req_id": i, "prompt": [i]})() for i in range(3)]
    for r in reqs:
        pol.push(r)
    assert [pol.pop().req_id for _ in range(3)] == [0, 1, 2]


def test_prefix_policy_pop_returns_none_when_all_deferred():
    """Every queued request deferred (same cold group) -> pop() is None
    after the leader, and the engine's admission loop must cope."""
    pol = PrefixAffinityPolicy()
    pol.attach_prefix_probe(lambda prompt: (0, ("g",), False))
    reqs = [type("R", (), {"req_id": i, "prompt": [1, 2]})()
            for i in range(3)]
    for r in reqs:
        pol.push(r)
    pol.begin_admission_round()
    assert pol.pop().req_id == 0          # cold leader
    assert pol.pop() is None              # followers defer
    assert len(pol) == 2
    pol.begin_admission_round()           # new round, still cold probe
    assert pol.pop().req_id == 1


# ---------------------------------------------------------------------------
# PrefixCacheIndex unit behavior
# ---------------------------------------------------------------------------

def test_prefix_index_match_register_commit_evict():
    pool = BlockPool(4)                    # 3 usable
    idx = PrefixCacheIndex(block_tokens=4, pool=pool)
    p = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    assert idx.match(p) == ([], False)
    row = pool.alloc(2)                    # the row's own chain
    created = idx.register(p, row)         # 2 full blocks
    assert [j for j, _ in created] == [0, 1]
    assert all(not n.committed for _, n in created)
    assert [pool.ref(b) for b in row] == [2, 2]   # row + trie
    assert idx.match(p) == ([], True)      # pending, not matched
    for _, n in created:
        idx.commit(n)
    ids, pending = idx.match(p)
    assert ids == row and not pending
    assert 0 not in ids                    # null block reserved
    # Matched length never covers the whole prompt: a block-aligned
    # prompt leaves its final block unusable (the vLLM rule) unless the
    # caller can copy-on-write it.
    ids8, _ = idx.match([1, 2, 3, 4, 5, 6, 7, 8])
    assert len(ids8) == 1
    assert len(idx.match([1, 2, 3, 4, 5, 6, 7, 8], allow_full=True)[0]) == 2
    # A position already in the trie keeps its block: only the new
    # tail is registered.
    row2 = pool.alloc(1)
    c2 = idx.register([1, 2, 3, 4, 9, 9, 9, 9], [row[0]] + row2)
    assert [j for j, _ in c2] == [1]
    for _, n in c2:
        idx.commit(n)
    assert idx.blocks_in_use == 3 and idx.blocks_total == 3
    # Shared with a live row: nothing is evictable. Once the rows let
    # go, the LRU committed LEAF goes first and its block is free.
    assert not idx.evict_one() and idx.evictable_blocks() == 0
    pool.decref(row + row2)
    assert idx.evictable_blocks() == 3     # the cascade, not the leaves
    assert idx.evict_one() and idx.evictions == 1
    assert idx.blocks_in_use == 2 and pool.free_blocks == 1
    assert idx.match(p)[0] == [row[0]]     # p's tail was the LRU leaf


def test_prefix_index_validation():
    with pytest.raises(ValueError, match="block_tokens"):
        PrefixCacheIndex(block_tokens=0, pool=BlockPool(4))


# ---------------------------------------------------------------------------
# Stats edge cases (satellite: derived ratios on a fresh engine)
# ---------------------------------------------------------------------------

def test_stats_ratios_are_zero_before_any_token(nano_model):
    """Before any token/prefill, every derived ratio is 0.0 — never
    NaN/ZeroDivisionError — with metrics enabled AND disabled, and on
    a bare EngineMetrics."""
    cfg, params = nano_model
    for enable in (True, False):
        eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                           prefix_cache=True, kv_block_tokens=4,
                           enable_metrics=enable)
        s = eng.stats()
        for key in ("host_syncs_per_token", "dispatches_per_token",
                    "prefill_padding_waste_frac", "prefix_hit_rate",
                    "prefix_reused_frac"):
            assert s[key] == 0.0, (enable, key, s[key])
    m = EngineMetrics(engine_id="fresh-ratio-engine")
    ms = m.stats()
    assert ms["host_syncs_per_token"] == 0.0
    assert ms["dispatches_per_token"] == 0.0
    assert ms["prefix_hit_rate"] == 0.0
    assert ms["prefill_padding_waste_frac"] == 0.0


# ---------------------------------------------------------------------------
# Prometheus plane
# ---------------------------------------------------------------------------

def test_prefix_metrics_reach_prometheus_registry(nano_model):
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       prefix_cache=True, kv_block_tokens=4,
                       engine_id="prefix-metrics-engine")
    prefix = list(range(1, 13))
    for s in (21, 22, 23):
        eng.submit(prefix + [s], 3)
    eng.run()
    s = eng.stats()
    assert s["prefix_lookups"] == 3.0
    assert s["prefix_hits"] >= 1.0

    from ray_tpu._private import metrics as _impl

    rows = {r["name"]: r for r in _impl.snapshots()
            if r["tags"].get("engine") == "prefix-metrics-engine"}
    assert rows["llm_engine_prefix_lookups_total"]["value"] == \
        s["prefix_lookups"]
    assert rows["llm_engine_prefix_hits_total"]["value"] == \
        s["prefix_hits"]
    assert rows["llm_engine_prefix_reused_tokens_total"]["value"] == \
        s["prefix_reused_tokens"]
    assert rows["llm_engine_prefill_tokens_total"]["value"] == \
        s["prefill_real_tokens"]


def test_spec_stats_reach_prometheus_registry():
    """Satellite: speculative.SpecStats ride the util.metrics plane
    like engine telemetry."""
    from ray_tpu.models.speculative import (SpecMetrics,
                                            speculative_generate)

    target_cfg = LlamaConfig.nano()
    draft_cfg = LlamaConfig.nano(n_layers=1)
    target = llama_init(jax.random.PRNGKey(0), target_cfg)
    draft = llama_init(jax.random.PRNGKey(1), draft_cfg)

    sm = SpecMetrics(spec_id="spec-plane-test")
    assert sm.stats()["acceptance_rate"] == 0.0       # fresh: 0, not NaN
    prompt = jnp.asarray([[5, 6, 7]], jnp.int32)
    _, stats = speculative_generate(target, target_cfg, draft, draft_cfg,
                                    prompt, max_new_tokens=8, window=2,
                                    metrics=sm)
    snap = sm.stats()
    assert snap["calls"] == 1.0
    assert snap["rounds"] == stats.rounds
    assert snap["proposed"] == stats.proposed
    assert snap["accepted"] == stats.accepted
    assert 0.0 <= snap["acceptance_rate"] <= 1.0

    from ray_tpu._private import metrics as _impl

    rows = {r["name"]: r for r in _impl.snapshots()
            if r["tags"].get("spec") == "spec-plane-test"}
    assert rows["llm_spec_calls_total"]["value"] == 1
    assert rows["llm_spec_rounds_total"]["value"] == stats.rounds
    assert rows["llm_spec_proposed_total"]["value"] == stats.proposed
    assert rows["llm_spec_acceptance_rate"]["value"] == \
        pytest.approx(stats.acceptance_rate)

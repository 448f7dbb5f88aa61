"""Paged KV memory (ray_tpu/models/engine.py).

The engine stores every request's K/V in blocks of one shared
refcounted pool (`models/block_pool.py`) behind a per-request block
table, not in a private [max_len] cache row per slot. The gold
contract is THE thing this file pins:

- TOKEN IDENTITY. Engine output == solo `generate`, greedy and
  sampled, at blocks of 4 and of 32 tokens, under the prefix cache,
  chunked prefill, the async pipeline, tensor parallelism, and
  preemption. `paged_attention` is solo generate's `_cached_attention`
  evaluated on the block-table gather (the engine enforces
  max_len % block_tokens == 0 so the gathered view has exactly the
  shape of the cache `generate` keeps), so the identity holds
  bit-for-bit, not just approximately.
- ZERO-COPY warm admission. A prefix-cache hit increfs the matched
  blocks into the new request's table — no gather,
  no device bytes moved. Only a FULL-prompt hit pays one
  copy-on-write block (the new row must extend the shared tail).
- PREEMPT-AND-SWAP. When the pool runs dry mid-decode the engine
  evicts the newest row (LIFO), spills its blocks to host (or drops
  them for preempt="recompute"), and later swaps back in and
  continues — with identical tokens, because the per-token rng key
  depends only on (request key, token index).
- CAPACITY. Admission is bounded by pool blocks, not row slots: a
  pool sized for B rows of max_len runs 2B+ concurrent requests when
  their actual lengths need less than max_len each.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, llama_init  # noqa: E402
from ray_tpu.models.block_pool import BlockPool  # noqa: E402
from ray_tpu.models.engine import DecodeEngine  # noqa: E402
from ray_tpu.models.generate import generate  # noqa: E402
from ray_tpu.models.prefix_cache import (  # noqa: E402
    PrefixCacheIndex, block_bytes)

T = 4           # kv_block_tokens under test
MAX_LEN = 32


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
    return [jax.random.PRNGKey(2000 + seed * 100 + i) for i in range(n)]


def _solo(params, cfg, prompt, n, mode=None, rng=None):
    out = np.asarray(generate(params, jnp.asarray([prompt], jnp.int32),
                              cfg, max_new_tokens=n, rng=rng,
                              **(mode or {})))
    return out[0, len(prompt):].tolist()


def _run(params, cfg, prompts, budgets, *, eng_kw=None, keys=None,
         slots=2):
    eng = DecodeEngine(params, cfg, batch_slots=slots, max_len=MAX_LEN,
                       **(eng_kw or {}))
    ids = [eng.submit(p, n, rng=None if keys is None else keys[i])
           for i, (p, n) in enumerate(zip(prompts, budgets))]
    out = eng.run()
    return [out[r] for r in ids], eng


def _pool_bytes(cfg, n_blocks):
    """Bytes buying exactly `n_blocks` usable pool blocks at T."""
    return n_blocks * block_bytes(cfg.n_layers, T, cfg.n_kv_heads,
                                  cfg.head_dim,
                                  jnp.dtype(cfg.dtype).itemsize)


# ---------------------------------------------------------------------------
# Token identity: paged x sampling x engine feature matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    {"greedy": True},
    {"greedy": False, "temperature": 0.9, "top_k": 5},
], ids=["greedy", "top_k"])
@pytest.mark.parametrize("features", [
    {},
    {"prefix_cache": True},
    {"prefix_cache": True, "pipeline_depth": 2},
    {"prefill_chunk": 3, "prefix_cache": True},
    {"tp": 2, "prefix_cache": True},
], ids=["plain", "prefix", "prefix_pipeline", "chunked", "tp2"])
def test_paged_token_identity_matrix(nano_model, mode, features):
    _paged_token_identity(nano_model, mode, features)


@pytest.mark.parametrize("features", [
    {},
    {"prefix_cache": True, "pipeline_depth": 2},
    {"prefill_chunk": 3, "prefix_cache": True},
], ids=["plain", "prefix_pipeline", "chunked"])
def test_paged_token_identity_matrix_olmoe(nano_olmoe, features):
    """The sparse family (experts, q/k norm), greedy: the seam touches
    nothing on the KV side, so the same identities hold."""
    _paged_token_identity(nano_olmoe, {"greedy": True}, features)


def _paged_token_identity(nano_model, mode, features):
    """Engine == solo generate across the feature matrix, at one
    32-token block a row (the default) and at blocks of 4. Shared-prefix
    prompts drive refcounted block sharing under the prefix variants; 5
    requests through 2 slots churn admissions so block alloc/free
    crosses slot reuse."""
    cfg, params = nano_model
    base = _prompts(5, cfg)
    shared = list(range(3, 11))      # 2 full blocks at T=4
    prompts = [shared + p for p in base[:2]] + base[2:]
    budgets = [7, 4, 9, 5, 6]
    keys = None if mode["greedy"] else _req_keys(len(prompts))
    rng_kw = {} if mode["greedy"] else {"rng": jax.random.PRNGKey(7)}
    ref = [_solo(params, cfg, p, n, mode,
                 rng=None if keys is None else keys[i])
           for i, (p, n) in enumerate(zip(prompts, budgets))]

    for block_tokens in (MAX_LEN, T):
        got, eng = _run(params, cfg, prompts, budgets,
                        eng_kw={**mode, **rng_kw, **features,
                                "kv_block_tokens": block_tokens},
                        keys=keys)
        assert got == ref, \
            f"blocks of {block_tokens}: diverged from solo generate"
        # every retired row returned its blocks: only trie-held stay
        assert eng.kv_pool.blocks_in_use == \
            (eng._prefix.blocks_in_use if eng._prefix else 0)


def test_paged_rejects_misaligned_block_size(nano_model):
    cfg, params = nano_model
    with pytest.raises(ValueError, match="divisible"):
        DecodeEngine(params, cfg, batch_slots=2, max_len=30,
                     kv_block_tokens=T)
    with pytest.raises(ValueError, match="preempt"):
        DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                     kv_block_tokens=T, preempt="drop")


# ---------------------------------------------------------------------------
# Zero-copy prefix sharing + copy-on-write
# ---------------------------------------------------------------------------

def test_warm_admission_is_zero_copy(nano_model):
    """A warm admission SHARES committed blocks by incref: no program
    runs for it but the suffix's prefill."""
    cfg, params = nano_model
    sys_p = list(range(1, 13))       # 3 full blocks at T=4
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       kv_block_tokens=T,
                       prefix_cache=True)
    a = eng.submit(sys_p + [50, 51], 4)
    out = eng.run()
    assert out[a] == _solo(params, cfg, sys_p + [50, 51], 4)
    s0 = eng.stats()

    b = eng.submit(sys_p + [60, 61, 62], 4)     # warm: 3 shared blocks
    out = eng.run()
    assert out[b] == _solo(params, cfg, sys_p + [60, 61, 62], 4)
    s1 = eng.stats()
    assert s1["prefix_hits"] - s0["prefix_hits"] == 1
    assert s1["kv_blocks_shared"] - s0["kv_blocks_shared"] == 3
    # THE gate: the one program of the warm admission is its suffix's
    assert s1["prefill_dispatches"] - s0["prefill_dispatches"] == 1
    assert s1["prefill_real_tokens"] - s0["prefill_real_tokens"] == 3
    # non-aligned suffix -> frontier block is fresh, no CoW either
    assert s1["kv_block_cows"] == s0["kv_block_cows"]
    # reused tokens flow into the shared prefix accounting
    assert s1["prefix_reused_tokens"] - s0["prefix_reused_tokens"] == 12


def test_full_prompt_hit_pays_one_cow_block(nano_model):
    """A prompt that IS a committed chain would share its own write
    frontier; the engine copies exactly the tail block (CoW) and
    shares the rest."""
    cfg, params = nano_model
    sys_p = list(range(1, 13))       # exactly 3 blocks
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       kv_block_tokens=T,
                       prefix_cache=True)
    a = eng.submit(sys_p, 4)
    eng.run()
    s0 = eng.stats()
    b = eng.submit(sys_p, 4)         # full-prompt hit
    out = eng.run()
    assert out[b] == _solo(params, cfg, sys_p, 4)
    s1 = eng.stats()
    assert s1["kv_block_cows"] - s0["kv_block_cows"] == 1
    assert s1["kv_blocks_shared"] - s0["kv_blocks_shared"] == 2


# ---------------------------------------------------------------------------
# The fused decode writes in place: its triples and nothing else
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", [None, "int8"], ids=["dense", "int8"])
def test_fused_decode_touches_only_what_it_writes(nano_model, monkeypatch,
                                                  quant):
    """The pool is ONE buffer the decode program updates in place, so
    what a dispatch does NOT write must come out bit for bit: every
    `_decode_multi_paged` dispatch of a run with shared prefix blocks,
    rows of different lengths and rows that retire mid-horizon is
    compared, K and V (and a quantized pool's scales), against the pool
    that went in. Allowed to differ: ``[layer, bt[b, slot // T],
    slot % T]`` for the horizon's slots of each row (a quantized pool
    requantizes that row's whole frontier block) and the null block,
    which retired rows write. The slots the active rows advanced over
    did change, in every layer."""
    import ray_tpu.models.engine as engine_mod

    cfg, params = nano_model
    real = engine_mod._decode_multi_paged
    seen = []

    def spy(params, pool_k, pool_v, bt, last_logits, row_len, *a, **kw):
        before = [np.array(x) for x in (pool_k, pool_v)]
        s_before = [None if kw.get(n) is None else np.array(kw[n])
                    for n in ("scale_k", "scale_v")]
        rl, bt_np = np.array(row_len), np.array(bt)
        out = real(params, pool_k, pool_v, bt, last_logits, row_len, *a,
                   **kw)
        seen.append((before, [np.asarray(out[1]), np.asarray(out[2])],
                     s_before, [None if x is None else np.array(x)
                                for x in (out[3], out[4])], bt_np, rl,
                     np.asarray(out[6]), out[0].shape[0]))   # toks [H, B]
        return out

    monkeypatch.setattr(engine_mod, "_decode_multi_paged", spy)
    sys_p = list(range(1, 13))                   # 3 full shared blocks
    eng = DecodeEngine(params, cfg, batch_slots=3, max_len=MAX_LEN,
                       kv_block_tokens=T, prefix_cache=True,
                       decode_horizon=4, kv_quant=quant)
    eng.submit(sys_p + [50, 51], 2)
    eng.run()                                    # commits the prefix
    for prompt, n in ((sys_p + [60, 61, 62], 9), (sys_p + [70], 3),
                      ([9, 8, 7, 6, 5], 6)):
        eng.submit(prompt, n)
    eng.run()
    assert eng.stats()["kv_blocks_shared"] >= 6
    assert len(seen) >= 3
    L, NB = seen[0][0][0].shape[:2]
    advanced = 0
    for before, after, s_before, s_after, bt, rl, rl_out, H in seen:
        allowed = np.zeros((NB, T), bool)
        allowed[0] = True                        # the null block
        for b in range(bt.shape[0]):
            for slot in range(rl[b], min(rl[b] + H, MAX_LEN)):
                blk = bt[b, slot // T]
                if quant is None:
                    allowed[blk, slot % T] = True
                else:
                    allowed[blk] = True
        for old, new in zip(before, after):
            same = (old.view(np.uint8) == new.view(np.uint8)) \
                .reshape(L, NB, T, -1).all(axis=-1)
            assert (same | allowed[None]).all()
            for b in range(bt.shape[0]):
                for slot in range(rl[b], rl_out[b]):
                    assert not same[:, bt[b, slot // T], slot % T].any()
                    advanced += 1
        for old, new in zip(s_before, s_after):
            if old is not None:
                same = (old == new).all(axis=-1)                # [L, NB]
                assert (same | allowed.any(axis=1)[None]).all()
    assert advanced >= 2 * (9 + 3 + 6 - 3)       # K and V, both checked


@pytest.mark.parametrize("quant", [None, "int8"], ids=["dense", "int8"])
def test_prefill_touches_only_what_it_writes(nano_model, monkeypatch,
                                             quant):
    """Prefill writes its chunk's blocks in place and nothing else:
    every `_prefill_rows_paged` dispatch of a run with shared prefix
    blocks, chunked continuations, bucket filler and a padded group is
    compared, K and V (and a quantized pool's scales), against the pool
    that went in. Allowed to differ: ``[layer, bt[n, slot // T],
    slot % T]`` for the chunk's slots ``starts[n] .. starts[n] + Cb``
    (a quantized pool rewrites the blocks those slots fall in, and the
    one behind them) and the null block. Stronger than what the
    whole-view write-back of before PR 30 could promise (shared blocks
    rewritten with the bytes they held): a block that lies wholly below
    a row's ``starts`` is never a write target, so shared prefix blocks
    are only ever read. The real tokens' slots did change, in every
    layer."""
    import ray_tpu.models.engine as engine_mod

    cfg, params = nano_model
    real = engine_mod._prefill_rows_paged
    seen = []

    def spy(params, prompts, pool_k, pool_v, last_logits, bt, rows,
            starts, last_idx, *a, **kw):
        before = [np.array(x) for x in (pool_k, pool_v)]
        s_before = [None if kw.get(n) is None else np.array(kw[n])
                    for n in ("scale_k", "scale_v")]
        out = real(params, prompts, pool_k, pool_v, last_logits, bt, rows,
                   starts, last_idx, *a, **kw)
        seen.append((before, [np.asarray(out[0]), np.asarray(out[1])],
                     s_before, [None if x is None else np.array(x)
                                for x in (out[2], out[3])], np.array(bt),
                     np.array(starts), np.array(last_idx),
                     prompts.shape[1]))
        return out

    monkeypatch.setattr(engine_mod, "_prefill_rows_paged", spy)
    sys_p = list(range(1, 13))                   # 3 full shared blocks
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN,
                       kv_block_tokens=T, prefix_cache=True,
                       prefill_chunk=8, kv_quant=quant)
    eng.submit(sys_p + [50, 51], 2)
    eng.run()                                    # commits the prefix
    for prompt, n in ((sys_p + list(range(60, 71)), 2), (sys_p + [70], 2),
                      (list(range(100, 119)), 2)):
        eng.submit(prompt, n)
    eng.run()
    assert eng.stats()["kv_blocks_shared"] >= 6
    assert any(starts.min() >= 12 for *_, starts, _, _ in seen)   # warm
    assert any(len(starts) > len(set(starts.tolist()))
               or len(starts) > 1 for *_, starts, _, _ in seen)
    L, NB = seen[0][0][0].shape[:2]
    written = 0
    for before, after, s_before, s_after, bt, starts, last_idx, Cb in seen:
        allowed = np.zeros((NB, T), bool)
        allowed[0] = True                        # the null block
        below = np.zeros((NB,), bool)            # wholly below a start
        for n in range(bt.shape[0]):
            below[bt[n, :starts[n] // T]] = True
            for slot in range(starts[n], min(starts[n] + Cb, MAX_LEN)):
                if quant is None:
                    allowed[bt[n, slot // T], slot % T] = True
                else:
                    allowed[bt[n, slot // T]] = True
            if quant is not None:
                nxt = (starts[n] + Cb - 1) // T + 1
                if nxt < bt.shape[1]:
                    allowed[bt[n, nxt]] = True
        below[0] = False
        assert not (allowed.any(axis=1) & below).any()
        for old, new in zip(before, after):
            same = (old.view(np.uint8) == new.view(np.uint8)) \
                .reshape(L, NB, T, -1).all(axis=-1)
            assert (same | allowed[None]).all()
            assert same[:, below].all()
            for n in range(bt.shape[0]):
                for slot in range(starts[n], starts[n] + last_idx[n] + 1):
                    assert not same[:, bt[n, slot // T], slot % T].any()
                    written += 1
        for old, new in zip(s_before, s_after):
            if old is not None:
                same = (old == new).all(axis=-1)                # [L, NB]
                assert (same | allowed.any(axis=1)[None]).all()
                assert same[:, below].all()
    assert written >= 2 * (14 + 11 + 1 + 19)    # K and V, both checked


def test_prefill_walk_counters_match_the_hand_count(nano_model,
                                                    monkeypatch):
    """`prefill_walk_pages_total / prefill_table_entries_total` is the
    share of its rows' table entries a prefill dispatch asks the kernel
    to walk. A dispatch of ``n_pad`` rows counts ``n_pad * MB`` entries
    (what the dense view covered); a row's chunk goes tile by tile
    (`walk_shape`: the tile's budget is set to 8 query tokens here), and
    a tile walks the pages up to its last REAL token's slot, none if it
    holds bucket filler alone."""
    from ray_tpu.ops import paged_attention_kernel as pak

    cfg, params = nano_model
    monkeypatch.setattr(pak, "_TILE_ACC_BYTES",
                        8 * cfg.n_heads * cfg.head_dim * 4)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       kv_block_tokens=T, prefill_chunk=16)
    MB = MAX_LEN // T
    rng = np.random.RandomState(3)
    # 27 tokens: a chunk of 16 at 0 (tiles 0-7 and 8-15), then 11 in a
    # bucket of 16 at 16 (tile 16-23 whole; tile 24-31 holds real tokens
    # up to slot 26)
    eng.submit(rng.randint(1, cfg.vocab_size, size=27).tolist(), 2)
    eng.run()
    s = eng.stats()
    assert s["prefill_dispatches"] == 2
    assert s["prefill_walk_pages_total"] == \
        (7 // T + 1) + (15 // T + 1) + (23 // T + 1) + (26 // T + 1)
    assert s["prefill_table_entries_total"] == 2 * MB
    # 18 tokens unchunked: a bucket of 32 whose last tile (slots 24-31)
    # is filler alone and whose third holds real tokens up to slot 17
    whole = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                         kv_block_tokens=T)
    whole.submit(rng.randint(1, cfg.vocab_size, size=18).tolist(), 1)
    whole.run()
    assert whole.stats()["prefill_walk_pages_total"] == \
        (7 // T + 1) + (15 // T + 1) + (17 // T + 1) + 0
    # a group of three pads to four rows: the padding row counts again
    for n in (3, 3, 3):
        eng.submit(rng.randint(1, cfg.vocab_size, size=n).tolist(), 1)
    before = s
    eng.run()
    s = eng.stats()
    assert s["prefill_table_entries_total"] \
        - before["prefill_table_entries_total"] <= 4 * MB
    assert s["prefill_walk_pages_total"] > before["prefill_walk_pages_total"]
    # a quantized pool's chunk takes the pure-lax lowering by design (it
    # attends itself exact): no tile walks anything, nothing is counted
    quant = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                         kv_block_tokens=T, kv_quant="int8")
    quant.submit(rng.randint(1, cfg.vocab_size, size=9).tolist(), 1)
    quant.run()
    s = quant.stats()
    assert s["prefill_dispatches"] == 1
    assert s["prefill_table_entries_total"] == 0


# ---------------------------------------------------------------------------
# Preempt-and-swap
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    {"greedy": True},
    {"greedy": False, "temperature": 0.9, "top_k": 5},
], ids=["greedy", "top_k"])
def test_preempt_and_swap_round_trip_identity(nano_model, mode):
    _preempt_and_swap_round_trip(nano_model, mode)


def test_preempt_and_swap_round_trip_identity_olmoe(nano_olmoe):
    _preempt_and_swap_round_trip(nano_olmoe, {"greedy": True})


def _preempt_and_swap_round_trip(nano_model, mode):
    """Pool sized for 2 of 4 in-flight requests: decode growth must
    preempt rows (swap out to host), requeue them, swap back in, and
    finish with tokens identical to solo generate. The per-token rng
    key depends only on (request key, token index), so a sampled row
    resumes bit-identically too."""
    cfg, params = nano_model
    prompts = [[7, 8, 9, 10, 11], [3, 1, 4, 1, 5],
               [2, 7, 1, 8, 2], [9, 9, 8, 8, 7]]
    M = 12                           # each row needs 5 blocks at T=4
    keys = None if mode["greedy"] else _req_keys(len(prompts), seed=3)
    rng_kw = {} if mode["greedy"] else {"rng": jax.random.PRNGKey(7)}
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN,
                       kv_block_tokens=T,
                       kv_pool_bytes=_pool_bytes(cfg, 10),
                       prefix_cache=False, **mode, **rng_kw)
    assert eng.kv_pool.blocks_total == 10
    ids = [eng.submit(p, M, rng=None if keys is None else keys[i])
           for i, p in enumerate(prompts)]
    out = eng.run()
    for i, (rid, p) in enumerate(zip(ids, prompts)):
        want = _solo(params, cfg, p, M, mode,
                     rng=None if keys is None else keys[i])
        assert out[rid] == want, f"req {rid} diverged across swap"
    s = eng.stats()
    assert s["preemptions"] >= 1
    assert s["swap_outs"] == s["preemptions"]
    assert s["swap_ins"] == s["swap_outs"]
    assert s["swap_out_bytes"] > 0 and s["swap_in_bytes"] > 0
    assert s["requests_swapped"] == 0.0          # all restored
    assert eng.kv_pool.blocks_in_use == 0        # all returned


def test_the_first_preemption_compiles_nothing(nano_model):
    """A swap engine runs its gather and scatter once a chain length (a
    power of two of blocks) when it is built, so the first preemption,
    which comes when the pool has just run dry, finds them compiled: the
    two programs' caches do not grow while rows are swapped out and in."""
    from ray_tpu.models import engine as E

    cfg, params = nano_model
    jax.clear_caches()
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN,
                       kv_block_tokens=T,
                       kv_pool_bytes=_pool_bytes(cfg, 10),
                       prefix_cache=False, greedy=True)
    sizes = (E._swap_out_gather._cache_size(),
             E._swap_in_scatter._cache_size())
    assert min(sizes) >= (MAX_LEN // T).bit_length()
    for p in ([7, 8, 9, 10, 11], [3, 1, 4, 1, 5], [2, 7, 1, 8, 2],
              [9, 9, 8, 8, 7]):
        eng.submit(p, 12)
    eng.run()
    assert eng.stats()["swap_ins"] >= 1
    assert sizes == (E._swap_out_gather._cache_size(),
                     E._swap_in_scatter._cache_size())


@pytest.mark.parametrize("family", ["llama", "olmoe"])
def test_preempt_recompute_identity(nano_model, nano_olmoe, family):
    """preempt="recompute" drops the victim's blocks and replays
    prompt+emitted through prefill on re-admission — same tokens,
    zero swap traffic (greedy: prefill recomputes the same K/V the
    decode originally wrote)."""
    cfg, params = nano_olmoe if family == "olmoe" else nano_model
    prompts = [[7, 8, 9, 10, 11], [3, 1, 4, 1, 5],
               [2, 7, 1, 8, 2], [9, 9, 8, 8, 7]]
    M = 12
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN,
                       kv_block_tokens=T,
                       preempt="recompute",
                       kv_pool_bytes=_pool_bytes(cfg, 10),
                       prefix_cache=False)
    ids = [eng.submit(p, M) for p in prompts]
    out = eng.run()
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, M)
    s = eng.stats()
    assert s["preemptions"] >= 1
    assert s["swap_out_bytes"] == 0.0 and s["swap_in_bytes"] == 0.0


def test_tight_pool_with_shared_prefix_trie_terminates(nano_model):
    """Regression: the admission gate must count CASCADE-evictable
    trie chains as capacity. A cold shared-prefix chain pins interior
    blocks that are not instantaneously-evictable leaves; if
    `_fits_now` only counts the leaves, a preempted request 'never
    fits' and step() livelocks doing nothing. Pool of 7 blocks, rows
    needing 6 (4 of them a shared trie chain): the engine must evict
    through the chain, preempt-and-swap, and finish every request
    with solo-identical tokens in bounded steps."""
    cfg, params = nano_model
    shared = list(range(1, 13))      # 3 full blocks at T=4
    rng = np.random.RandomState(5)
    prompts = [shared + rng.randint(1, cfg.vocab_size,
                                    size=3).tolist()
               for _ in range(6)]
    M = 6                            # each row: ceil(21/4) = 6 blocks
    eng = DecodeEngine(params, cfg, batch_slots=3, max_len=MAX_LEN,
                       kv_block_tokens=T,
                       kv_pool_bytes=_pool_bytes(cfg, 7),
                       prefix_cache=True)
    ids = [eng.submit(p, M) for p in prompts]
    steps = 0
    while eng.pending():
        eng.step()
        steps += 1
        assert steps < 500, "paged admission gate livelocked"
    out = {r: eng.pop_result(r) for r in list(eng.finished)}
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, M)


def test_preempt_and_swap_under_tp(nano_model):
    """Swap-out gathers and swap-in scatters cross a tp=2 sharded
    pool; tokens stay identical to solo generate."""
    cfg, params = nano_model
    prompts = [[7, 8, 9, 10, 11], [3, 1, 4, 1, 5],
               [2, 7, 1, 8, 2], [9, 9, 8, 8, 7]]
    M = 12
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN,
                       tp=2, kv_block_tokens=T,
                       kv_pool_bytes=_pool_bytes(cfg, 10),
                       prefix_cache=False)
    ids = [eng.submit(p, M) for p in prompts]
    out = eng.run()
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, M)
    assert eng.stats()["preemptions"] >= 1


# ---------------------------------------------------------------------------
# Capacity: pool-bounded admission beats slot-bounded admission
# ---------------------------------------------------------------------------

def test_paged_runs_2x_row_concurrency_on_same_budget(nano_model):
    """The capacity acceptance: on a pool holding 2 rows of max_len
    (2 * max_len tokens of K/V), the engine runs 4+ CONCURRENT requests — their actual footprints are
    small, and admission charges blocks, not a max_len-sized slot —
    with every token still identical to solo generate."""
    cfg, params = nano_model
    n_full_rows = 2
    pool_blocks = n_full_rows * (MAX_LEN // T)       # 16 blocks
    prompts = _prompts(6, cfg, seed=11, lo=3, hi=7)
    budgets = [5] * len(prompts)     # ceil((~6+5)/4) <= 3 blocks/row

    eng = DecodeEngine(params, cfg, batch_slots=2 * n_full_rows,
                       max_len=MAX_LEN, kv_block_tokens=T,
                       kv_pool_bytes=_pool_bytes(cfg, pool_blocks),
                       prefix_cache=False)
    assert eng.kv_pool.blocks_total == pool_blocks
    ids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    eng.step()
    live = sum(r is not None for r in eng.row_req)
    assert live >= 2 * n_full_rows, \
        f"only {live} live rows on a {n_full_rows}-full-row budget"
    out = eng.run()
    for rid, p, n in zip(ids, prompts, budgets):
        assert out[rid] == _solo(params, cfg, p, n)


def test_submit_rejects_request_larger_than_pool(nano_model):
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       kv_block_tokens=T,
                       kv_pool_bytes=_pool_bytes(cfg, 3),
                       prefix_cache=False)
    with pytest.raises(ValueError, match="pool"):
        eng.submit(list(range(1, 9)), 12)    # needs 5 > 3 blocks


# ---------------------------------------------------------------------------
# Refcount safety: shared blocks never evicted while referenced
# ---------------------------------------------------------------------------

def test_referenced_blocks_never_evicted_property():
    """Property test over the BlockPool + PrefixCacheIndex pair: drive
    random register/match/incref/decref/evict traffic and assert the
    trie never evicts a block some live row still references, and
    refcounts never go negative or leak."""
    rng = np.random.RandomState(0)
    pool = BlockPool(24)
    idx = PrefixCacheIndex(block_tokens=4, pool=pool)
    live = []                        # simulated rows: lists of bids

    def rand_prompt():
        n_blocks = rng.randint(1, 4)
        return rng.randint(1, 50, size=4 * n_blocks).tolist()

    for _ in range(300):
        op = rng.randint(4)
        if op == 0 and pool.free_blocks >= 3:         # admit a row
            prompt = rand_prompt()
            need = len(prompt) // 4
            ids, _pending = idx.match(prompt, allow_full=True)
            shared = ids[:need]
            pool.incref(shared)
            fresh = pool.alloc(need - len(shared))
            if fresh is None:
                pool.decref(shared)
                continue
            chain = shared + fresh
            for _, node in idx.register(prompt, chain):
                idx.commit(node)
            live.append(chain)
        elif op == 1 and live:                        # retire a row
            row = live.pop(rng.randint(len(live)))
            pool.decref(row)
        elif op == 2:                                 # memory pressure
            idx.evict_one()
        else:                                         # audit
            held = set(b for row in live for b in row)
            for b in held:
                assert pool.ref(b) >= 1, \
                    f"block {b} referenced by a live row but free"
    # teardown: retiring every row and draining the trie frees all
    for row in live:
        pool.decref(row)
    while idx.evict_one():
        pass
    assert pool.blocks_in_use == 0
    assert pool.free_blocks == pool.blocks_total


def test_block_pool_basics():
    pool = BlockPool(8)              # 7 usable; block 0 reserved
    assert pool.blocks_total == 7
    ids = pool.alloc(3)
    assert ids is not None and 0 not in ids
    assert pool.alloc(5) is None     # all-or-nothing
    assert pool.alloc(4) is not None
    assert pool.free_blocks == 0
    pool.incref(ids)
    assert pool.decref(ids) == []    # still referenced
    assert sorted(pool.decref(ids)) == sorted(ids)
    with pytest.raises(ValueError):
        pool.incref(ids)             # free blocks can't be ref'd
    with pytest.raises(ValueError):
        BlockPool(1)

"""Quantized paged KV storage (ray_tpu/ops/kv_quant.py + engine
`kv_quant=`) and the fused paged-attention kernel
(ray_tpu/ops/paged_attention_kernel.py).

Two distinct contracts, tested separately because they have different
strengths:

- QUANT OFF IS FREE. `kv_quant=None` (the default) traces the exact
  programs the engine traced before this feature existed — token
  streams stay BIT-IDENTICAL to solo `generate` across the whole
  feature matrix (paged x prefix x pipeline x spec x tp2 x
  preemption). Any "if quant" leak into the quant-off trace breaks
  this file first.
- QUANT ON IS TOLERANCE-GATED. int8/fp8 storage rounds the KV bytes,
  so token streams may diverge from bf16 after enough steps; the gate
  is a greedy token-match FRACTION against the dense-precision run
  plus an op-level logit error bound — not identity. What IS exact
  under quant: swap round-trips (quantized bytes + scales move
  verbatim), recompute preemption (requantizing an f32 dequantized
  view with a recomputed scale lands on identical bytes), and CoW
  tails (block copies are byte copies). Those paths assert full token
  identity against an unpreempted run of the SAME quant mode.
- The Pallas kernel (impl="flash") is validated off-TPU in interpret
  mode against the pure-lax reference over a shape sweep including
  GQA, ragged valid lengths, and quantized pools. Both read the pool
  as the engine stores it, ``[L, NB, T, KV*D]`` with the layer as an
  argument: every case here attends the middle layer of three that
  hold different data (`_pool`), and the ragged sweep attends each.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, llama_init  # noqa: E402
from ray_tpu.models.engine import DecodeEngine  # noqa: E402
from ray_tpu.models.generate import generate  # noqa: E402
from ray_tpu.models.prefix_cache import block_bytes  # noqa: E402
from ray_tpu.ops.attention import paged_attention  # noqa: E402
from ray_tpu.ops.kv_quant import (  # noqa: E402
    block_scale, dequantize, paged_quant_write, quantize,
    resolve_kv_quant)

T = 4
MAX_LEN = 32
LAYER = 1           # the layer of `_pool`'s three that holds the data


def _pool(x, layer=LAYER, n_layers=3):
    """One layer's pages ``[NB, T, KV, D]`` (or scales ``[NB, KV]``) ->
    the engine's ``[L, NB, T, KV*D]`` pool (``[L, NB, KV]`` slab) with
    ``x`` as layer ``layer`` and the other layers holding other data
    (the blocks rolled), so that reading the wrong layer shows. None
    stays None."""
    if x is None:
        return None
    flat = x.reshape(*x.shape[:2], -1) if x.ndim == 4 else x
    return jnp.stack([jnp.roll(flat, i - layer, axis=0)
                      for i in range(n_layers)])


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(n, cfg, seed=11, lo=3, hi=9):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size,
                        size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


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


def _quant_pool_bytes(cfg, n_blocks, qspec_name="int8"):
    """Bytes buying exactly `n_blocks` usable QUANTIZED pool blocks:
    1-byte payload plus the two [KV] f32 scale rows per layer."""
    bb = block_bytes(cfg.n_layers, T, cfg.n_kv_heads, cfg.head_dim, 1)
    bb += 2 * cfg.n_layers * cfg.n_kv_heads * 4
    return n_blocks * bb


# ---------------------------------------------------------------------------
# Quant OFF: bit-identity across the feature matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("features", [
    {},
    {"prefix_cache": True},
    {"prefix_cache": True, "pipeline_depth": 2},
    {"tp": 2},
    {"spec": True},
], ids=["plain", "prefix", "prefix_pipeline", "tp2", "spec"])
def test_quant_off_bit_identity_matrix(nano_model, features):
    """kv_quant=None engines are the pre-quant engines: token streams
    match solo `generate` exactly, with the quant knob passed
    EXPLICITLY so the None path is exercised on purpose."""
    cfg, params = nano_model
    kw = dict(features)
    if kw.pop("spec", False):
        kw.update(draft_params=params, draft_cfg=cfg, spec_window=4)
    prompts = _prompts(4, cfg)
    budgets = [7, 4, 6, 5]
    ref = [_solo(params, cfg, p, n)
           for p, n in zip(prompts, budgets)]
    got, eng = _run(params, cfg, prompts, budgets,
                    eng_kw={**kw, "kv_block_tokens": T,
                            "kv_quant": None})
    assert got == ref, "quant-off paged engine diverged from solo"
    s = eng.stats()
    assert s["kv_quant_enabled"] == 0.0
    # quant-off byte accounting reports the dense dtype cost
    itemsize = jnp.dtype(cfg.dtype).itemsize
    assert s["kv_bytes_per_token"] == pytest.approx(
        2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * itemsize)


def test_quant_off_preemption_identity(nano_model):
    """Quant-off preempt-and-swap keeps the r8 identity contract."""
    cfg, params = nano_model
    prompts = [[7, 8, 9, 10, 11], [3, 1, 4, 1, 5],
               [2, 7, 1, 8, 2], [9, 9, 8, 8, 7]]
    M = 12
    dense_bb = block_bytes(cfg.n_layers, T, cfg.n_kv_heads,
                           cfg.head_dim, jnp.dtype(cfg.dtype).itemsize)
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN,
                       kv_block_tokens=T, kv_quant=None,
                       kv_pool_bytes=10 * dense_bb, prefix_cache=False)
    ids = [eng.submit(p, M) for p in prompts]
    out = eng.run()
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, M)
    assert eng.stats()["preemptions"] >= 1


# ---------------------------------------------------------------------------
# Quant ON: tolerance gate vs dense precision
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", ["int8", "fp8_e4m3"])
@pytest.mark.parametrize("mode", [
    {"greedy": True},
    {"greedy": False, "temperature": 0.9, "top_k": 5},
], ids=["greedy", "top_k"])
def test_quant_on_token_tolerance_gate(nano_model, quant, mode):
    """Quantized decode tracks the dense-precision engine: the
    elementwise token-match fraction across the workload must clear a
    floor. Divergence compounds (one different token reroutes the
    rest of that stream), so the floor is deliberately below the
    typical per-token agreement — it catches a broken quant path
    (garbage scales, stale-slot bleed), not rounding."""
    cfg, params = nano_model
    prompts = _prompts(4, cfg, seed=5)
    budgets = [8, 8, 8, 8]
    keys = (None if mode["greedy"]
            else [jax.random.PRNGKey(3000 + i)
                  for i in range(len(prompts))])
    rng_kw = {} if mode["greedy"] else {"rng": jax.random.PRNGKey(7)}
    base_kw = {**mode, **rng_kw, "kv_block_tokens": T}
    dense, _ = _run(params, cfg, prompts, budgets,
                    eng_kw=base_kw, keys=keys)
    qtoks, eng = _run(params, cfg, prompts, budgets,
                      eng_kw={**base_kw, "kv_quant": quant}, keys=keys)
    total = sum(budgets)
    match = sum(int(a == b)
                for dt, qt in zip(dense, qtoks)
                for a, b in zip(dt, qt))
    assert all(len(t) == n for t, n in zip(qtoks, budgets))
    assert match / total >= 0.5, (
        f"{quant} matched only {match}/{total} tokens vs dense "
        "precision — quantized KV path is broken, not just rounding")
    s = eng.stats()
    assert s["kv_quant_enabled"] == 1.0
    assert 0 < s["kv_bytes_per_token"] < \
        2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * \
        jnp.dtype(cfg.dtype).itemsize


@pytest.mark.parametrize("quant", ["int8", "fp8_e4m3"])
def test_quant_prefill_chunk_attends_itself_exact(nano_model, quant):
    """A prefill chunk of a quantized pool attends its OWN keys and
    values as computed, and only what lies below it as the pool stores
    it (`paged_attention`'s ``own_kv``): a cold prompt that fits one
    chunk gives the dense-precision engine's first token, bit for bit
    in its logits. Rounding starts with the second token, which reads
    the prompt back from the pool."""
    cfg, params = nano_model
    prompts = _prompts(4, cfg, seed=9)
    kw = {"kv_block_tokens": T}
    dense, d_eng = _run(params, cfg, prompts[:2], [1, 1], eng_kw=kw)
    qtoks, q_eng = _run(params, cfg, prompts[:2], [1, 1],
                        eng_kw={**kw, "kv_quant": quant})
    assert qtoks == dense
    assert jnp.array_equal(q_eng._last_logits, d_eng._last_logits)
    # ... and the pool holds the chunk quantized all the same
    assert q_eng._pool_k.dtype == resolve_kv_quant(quant).dtype
    assert float(jnp.abs(q_eng._scale_k).max()) > 0


@pytest.mark.parametrize("quant", ["int8", "fp8_e4m3"])
def test_quant_logit_error_bound(quant):
    """Op-level bound: attention over a quantized pool stays within a
    small max-abs-err of attention over the f32 original. Per-block
    per-head absmax scaling bounds elementwise KV error by
    amax/(2*qmax) (int8) and softmax averaging keeps the output error
    the same order."""
    qspec = resolve_kv_quant(quant)
    rng = np.random.RandomState(0)
    B, MB, NB, TT, KV, D, H = 2, 4, 9, 8, 2, 16, 4
    kf = jnp.asarray(rng.randn(NB, TT, KV, D), jnp.float32)
    vf = jnp.asarray(rng.randn(NB, TT, KV, D), jnp.float32)
    amax_k = jnp.max(jnp.abs(kf), axis=(1, 3))
    amax_v = jnp.max(jnp.abs(vf), axis=(1, 3))
    sk, sv = block_scale(amax_k, qspec), block_scale(amax_v, qspec)
    kq = quantize(kf, sk[:, None, :, None], qspec)
    vq = quantize(vf, sv[:, None, :, None], qspec)
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
    bt = jnp.asarray(rng.randint(1, NB, size=(B, MB)), jnp.int32)
    q_slots = jnp.asarray([[MB * TT - 1]] * B, jnp.int32)
    exact = paged_attention(q, _pool(kf), _pool(vf), bt, q_slots,
                            layer=LAYER, kv_valid_len=MB * TT,
                            impl="reference")
    approx = paged_attention(q, _pool(kq), _pool(vq), bt, q_slots,
                             layer=LAYER, kv_valid_len=MB * TT,
                             k_scale=_pool(sk), v_scale=_pool(sv),
                             impl="reference")
    err = float(jnp.max(jnp.abs(exact - approx)))
    assert err < 0.05, f"{quant} attention max-abs-err {err}"


# ---------------------------------------------------------------------------
# Quant ON: exact paths — swap round trip, recompute, CoW
# ---------------------------------------------------------------------------

def test_quant_swap_round_trip_exact(nano_model):
    """Preempt-and-swap under int8 moves the quantized bytes AND the
    scale rows host-and-back verbatim, so a preempted run emits
    tokens IDENTICAL to an unpreempted run of the same quant mode."""
    cfg, params = nano_model
    prompts = [[7, 8, 9, 10, 11], [3, 1, 4, 1, 5],
               [2, 7, 1, 8, 2], [9, 9, 8, 8, 7]]
    M = 12
    ample = DecodeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN,
                         kv_block_tokens=T,
                         kv_quant="int8", prefix_cache=False)
    ids = [ample.submit(p, M) for p in prompts]
    want = ample.run()
    want = [want[r] for r in ids]

    tight = DecodeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN,
                         kv_block_tokens=T,
                         kv_quant="int8", prefix_cache=False,
                         kv_pool_bytes=_quant_pool_bytes(cfg, 10))
    assert tight.kv_pool.blocks_total == 10
    ids = [tight.submit(p, M) for p in prompts]
    out = tight.run()
    assert [out[r] for r in ids] == want, \
        "int8 tokens changed across a swap round trip"
    s = tight.stats()
    assert s["preemptions"] >= 1
    assert s["swap_out_bytes"] > 0 and s["swap_in_bytes"] > 0
    # swapped bytes include the f32 scale rows for the moved blocks,
    # and the payload is 1 byte/elem — far below the dense dtype cost
    assert s["swap_out_bytes"] == s["swap_in_bytes"]


def test_quant_recompute_preemption_exact(nano_model):
    """preempt="recompute" under int8: replaying prompt+emitted through
    the quantized prefill lands on the same bytes (the dequantized
    view is f32 end-to-end, so requantizing with a recomputed scale is
    byte-stable) — tokens match the unpreempted int8 run exactly."""
    cfg, params = nano_model
    prompts = [[7, 8, 9, 10, 11], [3, 1, 4, 1, 5],
               [2, 7, 1, 8, 2], [9, 9, 8, 8, 7]]
    M = 12
    ample = DecodeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN,
                         kv_block_tokens=T,
                         kv_quant="int8", prefix_cache=False)
    ids = [ample.submit(p, M) for p in prompts]
    want = ample.run()
    want = [want[r] for r in ids]

    rec = DecodeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN,
                       kv_block_tokens=T, kv_quant="int8",
                       preempt="recompute", prefix_cache=False,
                       kv_pool_bytes=_quant_pool_bytes(cfg, 10))
    ids = [rec.submit(p, M) for p in prompts]
    out = rec.run()
    assert [out[r] for r in ids] == want, \
        "int8 recompute preemption is not byte-stable"
    s = rec.stats()
    assert s["preemptions"] >= 1
    assert s["swap_out_bytes"] == 0.0 and s["swap_in_bytes"] == 0.0


def test_quant_cow_on_shared_tail_exact(nano_model):
    """A full-prompt prefix hit on a QUANTIZED chain pays exactly one
    CoW block — the copy moves quantized bytes plus the scale rows, so
    the warm request's tokens equal the cold request's."""
    cfg, params = nano_model
    sys_p = list(range(1, 13))       # exactly 3 blocks at T=4
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       kv_block_tokens=T, kv_quant="int8",
                       prefix_cache=True)
    a = eng.submit(sys_p, 4)
    out = eng.run()
    cold = out[a]
    s0 = eng.stats()
    b = eng.submit(sys_p, 4)         # full-prompt hit -> CoW tail
    out = eng.run()
    assert out[b] == cold, "CoW'd quantized tail changed the tokens"
    s1 = eng.stats()
    assert s1["kv_block_cows"] - s0["kv_block_cows"] == 1
    assert s1["kv_blocks_shared"] - s0["kv_blocks_shared"] == 2


# ---------------------------------------------------------------------------
# ops/kv_quant.py unit coverage
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", ["int8", "fp8_e4m3"])
def test_requantize_is_byte_stable(quant):
    """The preemption-recompute keystone: dequantize -> recompute scale
    -> requantize reproduces the original bytes exactly."""
    qspec = resolve_kv_quant(quant)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(5, 8, 2, 16), jnp.float32)
    s = block_scale(jnp.max(jnp.abs(x), axis=(1, 3)), qspec)
    q1 = quantize(x, s[:, None, :, None], qspec)
    deq = dequantize(q1, s[:, None, :, None])
    s2 = block_scale(jnp.max(jnp.abs(deq), axis=(1, 3)), qspec)
    q2 = quantize(deq, s2[:, None, :, None], qspec)
    assert jnp.array_equal(
        q1.view(jnp.uint8), q2.view(jnp.uint8)), \
        f"{quant} requantization is not byte-stable"


def test_paged_quant_write_matches_dense_write():
    """paged_quant_write through a block table lands the same values
    (up to quantization) a dense slot-write would, and zeroes stale
    slots at-and-past the write frontier so garbage can't coarsen a
    later block's scale."""
    qspec = resolve_kv_quant("int8")
    rng = np.random.RandomState(2)
    NB, TT, KV, D, B, S = 7, 4, 2, 8, 2, 6
    pool = jnp.zeros((3, NB, TT, KV * D), qspec.dtype)
    slab = jnp.zeros((3, NB, KV), jnp.float32)
    bt = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    vals = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    start = jnp.asarray([1, 3], jnp.int32)
    pool, slab = paged_quant_write(pool, slab, LAYER, bt, start, vals,
                                   qspec)
    # the write touched its layer alone
    for other in (0, 2):
        assert not jnp.any(pool[other].view(jnp.uint8))
        assert not jnp.any(slab[other])
    pages, scales = pool[LAYER].reshape(NB, TT, KV, D), slab[LAYER]
    for b in range(B):
        for s_i in range(S):
            pos = int(start[b]) + s_i
            blk, off = bt[b, pos // TT], pos % TT
            got = dequantize(pages[blk, off], scales[blk][:, None])
            ref = vals[b, s_i]
            tol = jnp.max(jnp.abs(ref)) / qspec.qmax + 1e-6
            assert float(jnp.max(jnp.abs(got - ref))) <= float(tol), \
                f"row {b} slot {pos} dequantized wrong"
    # the null block stays all-zero (scale slab zero-init -> dequant 0)
    assert not jnp.any(pages[0].view(jnp.uint8))
    assert not jnp.any(scales[0])


def test_resolve_kv_quant_names():
    assert resolve_kv_quant(None) is None
    assert resolve_kv_quant("int8").name == "int8"
    assert resolve_kv_quant("fp8_e4m3").name == "fp8_e4m3"
    with pytest.raises(ValueError, match="kv_quant"):
        resolve_kv_quant("int4")


def test_engine_rejects_paged_false(nano_model):
    """The block pool is the one KV path: the keyword the benchmark's
    files still pass accepts True and nothing else."""
    cfg, params = nano_model
    DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN, paged=True)
    with pytest.raises(ValueError, match="PR 29"):
        DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                     paged=False)


@pytest.mark.parametrize("quant,itemsize", [(None, 4), ("int8", 1),
                                            ("fp8_e4m3", 1)])
def test_pool_bytes_per_token_are_exact(nano_model, quant, itemsize):
    """What a cached token costs, from shapes (the count the old CPU
    bench printed as 132 against 512 bytes): K and V for every layer
    and KV head at the pool's storage width, plus — quantized — a
    block's two f32 scale rows spread over its T tokens. A byte budget
    buys blocks in that ratio."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       kv_block_tokens=8, kv_quant=quant,
                       kv_pool_bytes=1 << 16)
    L, KV, D = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    per_token = 2 * L * KV * D * itemsize \
        + (2 * L * KV * 4 / 8 if quant else 0)
    assert eng.kv_bytes_per_token == per_token
    assert eng.kv_bytes_per_block == 8 * per_token
    assert eng.kv_pool.blocks_total == (1 << 16) // (8 * per_token)
    if (L, KV, D) == (2, 2, 16):
        assert per_token == (132.0 if quant else 512.0)


# ---------------------------------------------------------------------------
# Fused kernel: interpret-mode parity vs the pure-lax reference
# ---------------------------------------------------------------------------

# (B, MB, T, KV, D, gqa_mult) — covers single/multi block walks, GQA
# replication, and a pool bigger than any one table.
_KERNEL_SHAPES = [
    (1, 1, 4, 1, 8, 1),
    (2, 4, 4, 2, 16, 1),
    (2, 4, 4, 2, 16, 2),     # GQA: H = 2*KV
    (3, 2, 8, 1, 32, 4),     # deep GQA, wider blocks
    (1, 8, 2, 2, 8, 1),      # long walk, tiny blocks
]


@pytest.mark.parametrize("shape", _KERNEL_SHAPES,
                         ids=["b1", "b2", "gqa2", "gqa4", "walk8"])
@pytest.mark.parametrize("quant", [None, "int8", "fp8_e4m3"],
                         ids=["dense", "int8", "fp8"])
def test_kernel_matches_reference(shape, quant):
    """The Pallas block-walking kernel in interpret mode reproduces
    the reference gather path to fp32 tolerance on every shape —
    ragged per-row valid lengths (q_slots mid-block) included."""
    B, MB, TT, KV, D, gm = shape
    H = KV * gm
    NB = MB * B + 3
    rng = np.random.RandomState(B * 100 + MB * 10 + KV)
    kf = jnp.asarray(rng.randn(NB, TT, KV, D), jnp.float32)
    vf = jnp.asarray(rng.randn(NB, TT, KV, D), jnp.float32)
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
    # distinct live blocks per row; block 0 stays the null block
    bt = jnp.asarray(
        1 + np.arange(B * MB).reshape(B, MB), jnp.int32)
    # ragged: each row's frontier lands at a different mid-block slot
    q_slots = jnp.asarray(
        [[min(MB * TT - 1, 1 + 3 * b)] for b in range(B)], jnp.int32)
    sk = sv = None
    if quant is not None:
        qspec = resolve_kv_quant(quant)
        sk = block_scale(jnp.max(jnp.abs(kf), axis=(1, 3)), qspec)
        sv = block_scale(jnp.max(jnp.abs(vf), axis=(1, 3)), qspec)
        kf = quantize(kf, sk[:, None, :, None], qspec)
        vf = quantize(vf, sv[:, None, :, None], qspec)
    kw = dict(layer=LAYER, kv_valid_len=MB * TT, k_scale=_pool(sk),
              v_scale=_pool(sv))
    kf, vf = _pool(kf), _pool(vf)
    ref = paged_attention(q, kf, vf, bt, q_slots, impl="reference",
                          **kw)
    got = paged_attention(q, kf, vf, bt, q_slots, impl="flash", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_masks_garbage_blocks():
    """Slots past the frontier and whole unallocated table entries
    (pointing at block 0 or at another row's blocks) contribute
    exactly nothing, same as the reference's -1e30 fill."""
    rng = np.random.RandomState(9)
    NB, TT, KV, D = 6, 4, 2, 16
    kf = jnp.asarray(rng.randn(NB, TT, KV, D), jnp.float32)
    vf = jnp.asarray(rng.randn(NB, TT, KV, D), jnp.float32)
    q = jnp.asarray(rng.randn(1, 1, 2, D), jnp.float32)
    short = jnp.asarray([[1, 0, 0, 0]], jnp.int32)   # 1 live block
    long = jnp.asarray([[1, 5, 4, 3]], jnp.int32)    # garbage tail
    q_slots = jnp.asarray([[2]], jnp.int32)          # frontier slot 2
    outs = [paged_attention(q, _pool(kf), _pool(vf), bt, q_slots,
                            layer=LAYER, kv_valid_len=16, impl=impl)
            for bt in (short, long) for impl in ("reference", "flash")]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Dispatch seam (the small-fix satellite)
# ---------------------------------------------------------------------------

def test_paged_attention_impl_dispatch_seam():
    """`impl=` is an explicit seam: "reference" and "flash" agree
    off-TPU (flash -> interpret mode), "auto" resolves to the
    reference off-TPU, and bad arguments fail loudly."""
    rng = np.random.RandomState(4)
    NB, TT, KV, D = 5, 4, 2, 16
    kf = _pool(jnp.asarray(rng.randn(NB, TT, KV, D), jnp.float32))
    vf = _pool(jnp.asarray(rng.randn(NB, TT, KV, D), jnp.float32))
    q = jnp.asarray(rng.randn(2, 1, 4, D), jnp.float32)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    q_slots = jnp.asarray([[5], [7]], jnp.int32)
    kw = dict(layer=LAYER, kv_valid_len=8)
    ref = paged_attention(q, kf, vf, bt, q_slots, impl="reference",
                          **kw)
    fla = paged_attention(q, kf, vf, bt, q_slots, impl="flash", **kw)
    np.testing.assert_allclose(np.asarray(fla), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    if jax.default_backend() != "tpu":
        auto = paged_attention(q, kf, vf, bt, q_slots, impl="auto",
                               **kw)
        assert jnp.array_equal(auto, ref)   # auto == reference off-TPU

    with pytest.raises(ValueError, match="impl"):
        paged_attention(q, kf, vf, bt, q_slots, impl="fused", **kw)
    with pytest.raises(ValueError, match="together"):
        paged_attention(q, kf, vf, bt, q_slots,
                        k_scale=jnp.ones((3, NB, KV)), **kw)
    with pytest.raises(ValueError, match="heads"):
        paged_attention(jnp.zeros((2, 1, 3, D)), kf, vf, bt, q_slots,
                        **kw)
    # the pool keeps a token's heads merged: [L, NB, T, KV, D] is refused
    with pytest.raises(ValueError, match="pool"):
        paged_attention(q, kf.reshape(3, NB, TT, KV, D),
                        vf.reshape(3, NB, TT, KV, D), bt, q_slots, **kw)


# ---------------------------------------------------------------------------
# The walk follows each row's length (live_pages, ragged rows, poison)
# ---------------------------------------------------------------------------

from ray_tpu.ops import paged_attention_kernel as pak  # noqa: E402

_RT, _RMB = 4, 6            # ragged sweep: pages of 4, tables of 6
# first query slot of each row: length 1; one slot short of a page
# boundary; the page's last slot; the next page's first; the table's
# last S slots (set per S below); a retired row (table all block 0)
_RAGGED_STARTS = [0, 2 * _RT - 2, 2 * _RT - 1, 2 * _RT, None, 0]


_RL = 3                     # layers of the ragged sweep's pool


def _quantized(kf, vf, quant):
    """[L, NB, T, KV, D] f32 draws -> the engine's flat pools and their
    scales (None for "bf16" and None, which only set the dtype)."""
    sk = sv = None
    if quant not in (None, "bf16"):
        qspec = resolve_kv_quant(quant)
        sk = block_scale(jnp.max(jnp.abs(kf), axis=(2, 4)), qspec)
        sv = block_scale(jnp.max(jnp.abs(vf), axis=(2, 4)), qspec)
        kf = quantize(kf, sk[:, :, None, :, None], qspec)
        vf = quantize(vf, sv[:, :, None, :, None], qspec)
    flat = kf.shape[:3] + (-1,)
    return kf.reshape(flat), vf.reshape(flat), sk, sv


def _ragged_case(S, quant, rng, poison=False):
    """q, pools, table, slots, scales for the ragged rows above, every
    table entry of a live row a distinct real block, so that an entry
    past the live prefix is readable garbage (or NaN with `poison`).
    The pools are the engine's: ``[_RL, NB, T, KV*D]``, each layer its
    own draw; ``quant`` is None (f32), "bf16", "int8" or "fp8_e4m3"."""
    B, KV, D, gm = len(_RAGGED_STARTS), 2, 16, 2
    span = _RT * _RMB
    starts = [span - S if s is None else s for s in _RAGGED_STARTS]
    q_slots = np.asarray(starts)[:, None] + np.arange(S)[None]
    NB = 1 + B * _RMB
    dt = jnp.bfloat16 if quant == "bf16" else jnp.float32
    kf = jnp.asarray(rng.randn(_RL, NB, _RT, KV, D), dt)
    vf = jnp.asarray(rng.randn(_RL, NB, _RT, KV, D), dt)
    bt = 1 + np.arange(B * _RMB).reshape(B, _RMB)
    bt[-1] = 0                                       # the retired row
    q = jnp.asarray(rng.randn(B, S, KV * gm, D), dt)
    kf, vf, sk, sv = _quantized(kf, vf, quant)
    if poison:
        live = q_slots.max(axis=1) // _RT + 1
        dead = np.concatenate([bt[b, live[b]:] for b in range(B - 1)])
        kf = kf.at[:, dead].set(jnp.nan)
        vf = vf.at[:, dead].set(jnp.nan)
    return (q, kf, vf, jnp.asarray(bt, jnp.int32),
            jnp.asarray(q_slots, jnp.int32), sk, sv)


def test_live_pages_is_the_prefix_a_query_may_see():
    q_slots = jnp.asarray([[0, 0], [5, 6], [7, 8], [23, 40], [2, 1]])
    # by slot: 1, 2, 3, 11 -> capped at the table's 6, 1; by length 24: 6
    assert pak.live_pages(q_slots, 24, 4, 6).tolist() == [1, 2, 3, 6, 1]
    # kv_valid_len 10 = 2.5 pages: no row walks more than 3
    assert pak.live_pages(q_slots, 10, 4, 6).tolist() == [1, 2, 3, 3, 1]
    assert pak.live_pages(q_slots, 0, 4, 6).tolist() == [0] * 5


@pytest.mark.parametrize("keys_per_step", [8, 512],
                         ids=["steps_of_2_pages", "one_step"])
@pytest.mark.parametrize("slots", [1, 4], ids=["s1", "s4_straddling"])
@pytest.mark.parametrize("quant", [None, "bf16", "int8", "fp8_e4m3"],
                         ids=["dense", "bf16", "int8", "fp8"])
def test_kernel_ragged_rows_match_reference_and_full_walk(
        monkeypatch, quant, slots, keys_per_step):
    """Rows of every kind in ONE call, against the reference; and the
    walk that stops at each row's live prefix gives the bits of a walk
    over all MB entries (a fully masked step changes nothing). Each of
    the pool's three layers holds its own data and is attended by its
    index: the kernel's answer for layer ``li`` is the reference's, and
    the reference's is what it gives on that layer alone."""
    monkeypatch.setattr(pak, "_KEYS_PER_STEP", keys_per_step)
    rng = np.random.RandomState(17 + slots)
    q, kf, vf, bt, q_slots, sk, sv = _ragged_case(slots, quant, rng)
    tol = 2e-2 if quant == "bf16" else 2e-5
    seen = []
    for li, valid in ((0, _RT * _RMB), (1, 10), (2, _RT * _RMB)):
        # valid 10: below some rows' slots
        kw = dict(kv_valid_len=valid, k_scale=sk, v_scale=sv)
        ref = paged_attention(q, kf, vf, bt, q_slots, impl="reference",
                              layer=li, **kw)
        alone = paged_attention(
            q, kf[li:li + 1], vf[li:li + 1], bt, q_slots, layer=0,
            impl="reference", kv_valid_len=valid,
            k_scale=None if sk is None else sk[li:li + 1],
            v_scale=None if sv is None else sv[li:li + 1])
        assert jnp.array_equal(ref, alone)
        got = paged_attention(q, kf, vf, bt, q_slots, impl="flash",
                              layer=jnp.int32(li), **kw)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol)
        pps, tq, stacked = pak.walk_shape(slots, q.shape[2], 2, q.shape[3],
                                          _RT, _RMB, kf.dtype.itemsize)
        assert tq == slots                       # one tile a row
        full = pak._walk(q, bt, q_slots,
                         n_live=jnp.full((q.shape[0],), _RMB, jnp.int32),
                         k_pool=kf, v_pool=vf, k_scale=sk, v_scale=sv,
                         layer=np.int32(li), kv_valid_len=np.int32(valid),
                         sm_scale=q.shape[3] ** -0.5, interpret=True,
                         pps=pps, stacked=stacked)
        assert jnp.array_equal(got, full)
        seen.append(np.asarray(got, np.float32))
    assert not np.allclose(seen[0], seen[2], atol=1e-3)   # other data


@pytest.mark.parametrize("keys_per_step", [8, 512],
                         ids=["steps_of_2_pages", "one_step"])
@pytest.mark.parametrize("slots", [1, 4], ids=["s1", "s4_straddling"])
@pytest.mark.parametrize("quant", [None, "fp8_e4m3"],
                         ids=["dense", "fp8"])
def test_kernel_never_reads_past_the_live_prefix(monkeypatch, quant, slots,
                                                 keys_per_step):
    """NaN in every page past each row's live prefix: the output is the
    clean pool's, bit for bit — such a page is not fetched, and what the
    buffer holds in its place is zeroed before the matmul sees it."""
    monkeypatch.setattr(pak, "_KEYS_PER_STEP", keys_per_step)
    outs = []
    for poison in (False, True):
        rng = np.random.RandomState(23 + slots)
        q, kf, vf, bt, q_slots, sk, sv = _ragged_case(slots, quant, rng,
                                                      poison=poison)
        outs.append(paged_attention(
            q, kf, vf, bt, q_slots, impl="flash", layer=LAYER,
            kv_valid_len=_RT * _RMB, k_scale=sk, v_scale=sv))
    assert bool(jnp.isfinite(outs[1]).all())
    assert jnp.array_equal(outs[0], outs[1])


# -- the rows of a call are one pipeline: a row's last step fetches the
# -- first pages of the row after it, and no row's bits know its neighbours

_PMB, _PPS = 8, 2           # pipeline sweep: tables of 8, steps of 2 pages
# a row by the pages it walks -> its last query's slot: none (a filler row
# at slot -1), one page, exactly a step, a step and a page, the whole table
_LAST_SLOT = {0: None, 1: _RT - 1, _PPS: _PPS * _RT - 2,
              _PPS + 1: (_PPS + 1) * _RT - 3, _PMB: _PMB * _RT - 1}
_ROW_ORDERS = {
    "empty_first": [0, 1, _PPS, _PPS + 1, _PMB, 1, _PMB, _PPS],
    "empty_last": [_PMB, 1, _PPS, _PPS + 1, 1, _PPS + 1, _PMB, 0],
    "two_empty_in_a_row": [1, 0, 0, _PMB, _PPS, 0, _PPS + 1, 1],
}


def _pipeline_case(last_slots, S, quant, rng, mb=_PMB, t=_RT, KV=2, gm=2,
                   D=16):
    """q, pools (two layers, pages of ``t``), table, slots, scales: row
    b's S queries end at ``last_slots[b]`` (None: every slot -1), every
    table entry a real block of its own; ``KV`` heads of ``D`` with
    ``gm`` query heads each."""
    B = len(last_slots)
    q_slots = np.stack([
        np.full(S, -1) if last is None else
        np.maximum(last - S + 1 + np.arange(S), -1) for last in last_slots])
    NB = 1 + B * mb
    dt = jnp.bfloat16 if quant == "bf16" else jnp.float32
    kf, vf, sk, sv = _quantized(
        jnp.asarray(rng.randn(2, NB, t, KV, D), dt),
        jnp.asarray(rng.randn(2, NB, t, KV, D), dt), quant)
    bt = 1 + np.arange(B * mb).reshape(B, mb)
    q = jnp.asarray(rng.randn(B, S, KV * gm, D), dt)
    return (q, kf, vf, jnp.asarray(bt, jnp.int32),
            jnp.asarray(q_slots, jnp.int32), sk, sv)


def _each_row_alone(q, kf, vf, bt, q_slots, **kw):
    """The kernel's answer for every row in a call of its own (with the
    seven filler rows that make the call's eight)."""
    return jnp.concatenate([
        paged_attention(q[b:b + 1], kf, vf, bt[b:b + 1], q_slots[b:b + 1],
                        impl="flash", **kw) for b in range(q.shape[0])])


@pytest.mark.parametrize("n_rows", [8, 16], ids=["one_eight", "two_eights"])
@pytest.mark.parametrize("order", list(_ROW_ORDERS))
@pytest.mark.parametrize("slots", [1, 4], ids=["s1", "s4"])
@pytest.mark.parametrize("quant", ["bf16", "int8", "fp8_e4m3"],
                         ids=["bf16", "int8", "fp8"])
def test_kernel_rows_of_a_call_are_one_pipeline_and_each_its_own(
        monkeypatch, quant, slots, order, n_rows):
    """Rows that walk 0, 1, exactly pps, pps + 1 and all MB pages, with
    an empty row first, last and two in a row: the batch gives, BIT FOR
    BIT, what each row gives in a call of its own (who fetched a row's
    first pages, and into which buffer slot, leaves no trace), it is the
    reference's within the parity tolerance, and the live walk is the
    walk of all MB entries."""
    monkeypatch.setattr(pak, "_KEYS_PER_STEP", _PPS * _RT)
    kinds = _ROW_ORDERS[order]
    if n_rows == 16:
        kinds = kinds + kinds[::-1]
    rng = np.random.RandomState(41 + slots + n_rows)
    q, kf, vf, bt, q_slots, sk, sv = _pipeline_case(
        [_LAST_SLOT[k] for k in kinds], slots, quant, rng)
    assert pak.live_pages(q_slots, _PMB * _RT, _RT, _PMB).tolist() == kinds
    kw = dict(layer=1, kv_valid_len=_PMB * _RT, k_scale=sk, v_scale=sv)
    got = paged_attention(q, kf, vf, bt, q_slots, impl="flash", **kw)
    assert jnp.array_equal(got, _each_row_alone(q, kf, vf, bt, q_slots,
                                                **kw))
    ref = paged_attention(q, kf, vf, bt, q_slots, impl="reference", **kw)
    tol = 2e-2 if quant == "bf16" else 2e-5
    asked = np.asarray(q_slots) >= 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[asked],
        np.asarray(ref, np.float32)[asked], atol=tol, rtol=tol)
    assert not np.asarray(got, np.float32)[~asked].any()
    pps, tq, stacked = pak.walk_shape(slots, 4, 2, 16, _RT, _PMB,
                                      kf.dtype.itemsize)
    assert (pps, tq) == (_PPS, slots)
    full = pak._walk(q, bt, q_slots,
                     n_live=jnp.full((n_rows,), _PMB, jnp.int32),
                     k_pool=kf, v_pool=vf, k_scale=sk, v_scale=sv,
                     layer=np.int32(1), kv_valid_len=np.int32(_PMB * _RT),
                     sm_scale=16 ** -0.5, interpret=True, pps=pps,
                     stacked=stacked)
    assert jnp.array_equal(got, full)


@pytest.mark.parametrize("order", ["empty_first", "empty_last",
                                   "two_empty_in_a_row"])
@pytest.mark.parametrize("slots", [1, 4], ids=["s1", "s4"])
def test_kernel_window_rows_chain_past_a_row_that_starts_where_it_ends(
        monkeypatch, slots, order):
    """Sliding-window rows in one call: a valid length of 4 pages and a
    window of 10 slots make the row at slot 31 start at page 4 == its
    live pages (it walks NOTHING and reads 0, with a table row of real
    blocks), beside rows that start at page 0, 2 and 3. Batch against
    each row alone bit for bit, and against the reference where a row
    sees anything."""
    monkeypatch.setattr(pak, "_KEYS_PER_STEP", _PPS * _RT)
    W, valid = 10, 4 * _RT
    nothing = _PMB * _RT - 1
    lasts = {"empty_first": [nothing, 12, 2, 10, 24, 7, 18, 12],
             "empty_last": [12, 2, 10, 24, 7, 18, 12, nothing],
             "two_empty_in_a_row": [2, nothing, nothing, 12, 24, 10,
                                    nothing, 7]}[order]
    rng = np.random.RandomState(53 + slots)
    q, kf, vf, bt, q_slots, _, _ = _pipeline_case(lasts, slots, None, rng)
    n_live = pak.live_pages(q_slots, valid, _RT, _PMB)
    first = pak.first_page(q_slots, W, _RT, n_live)
    walked = (n_live - first).tolist()
    assert [w for w, last in zip(walked, lasts) if last == nothing] \
        == [0] * lasts.count(nothing)
    assert {1, _PPS, _PPS + 1, 2 * _PPS} <= set(walked)
    assert 0 < int(first[lasts.index(nothing)]) \
        == int(n_live[lasts.index(nothing)])
    kw = dict(layer=1, kv_valid_len=valid, window=W)
    got = paged_attention(q, kf, vf, bt, q_slots, impl="flash", **kw)
    assert jnp.array_equal(got, _each_row_alone(q, kf, vf, bt, q_slots,
                                                **kw))
    ref = paged_attention(q, kf, vf, bt, q_slots, impl="reference", **kw)
    # a row with nothing to see reads 0 here; the reference's softmax
    # over a row of fills is an average of whatever the table points at
    sees = np.asarray(lasts) != nothing
    asked = (np.asarray(q_slots) >= 0) & sees[:, None]
    np.testing.assert_allclose(np.asarray(got)[asked],
                               np.asarray(ref)[asked], atol=2e-5, rtol=2e-5)
    assert not np.asarray(got)[~sees].any()


@pytest.mark.parametrize("quant", ["bf16", "int8", "fp8_e4m3"],
                         ids=["bf16", "int8", "fp8"])
def test_kernel_chunk_tiles_chain_across_rows_and_filler(monkeypatch, quant):
    """Prefill chunks of 128 queries as 4 tiles of 32 each, three chunk
    rows in one call of 16 grid rows (two eights): a chunk at start 0
    (its first tile walks one step), one several pages in whose last two
    tiles are bucket filler (they walk nothing, and the next row's first
    tile starts its own copies), one all real. The batch is each chunk
    alone, bit for bit, and the reference's."""
    monkeypatch.setattr(pak, "_KEYS_PER_STEP", 4 * _CT)
    monkeypatch.setattr(pak, "_TILE_ACC_BYTES", 32 * 2 * 2 * 16 * 4)
    S, starts, n_real = 128, [0, 3 * _CT + 5, 2], [128, 60, 128]
    mb = -(-(max(starts) + S) // _CT) + 1
    rng = np.random.RandomState(67)
    q, kf, vf, bt, _, sk, sv = _pipeline_case([None] * 3, S, quant, rng,
                                              mb=mb, t=_CT)
    pos = np.arange(S)[None, :]
    q_slots = jnp.asarray(np.where(
        pos < np.asarray(n_real)[:, None],
        np.asarray(starts)[:, None] + pos, -1), jnp.int32)
    pps, tq, _ = pak.walk_shape(S, 4, 2, 16, _CT, mb, kf.dtype.itemsize)
    assert (pps, tq) == (4, 32)
    tiles = pak.live_pages(q_slots.reshape(-1, tq), mb * _CT, _CT, mb)
    assert tiles.tolist()[:4] == [4, 8, 12, 16]        # start 0: one step
    assert tiles.tolist()[4:8] == [8, 12, 0, 0]        # filler tiles
    kw = dict(layer=1, kv_valid_len=mb * _CT, k_scale=sk, v_scale=sv)
    got = paged_attention(q, kf, vf, bt, q_slots, impl="flash", **kw)
    assert jnp.array_equal(got, _each_row_alone(q, kf, vf, bt, q_slots,
                                                **kw))
    ref = paged_attention(q, kf, vf, bt, q_slots, impl="reference", **kw)
    tol = 2e-2 if quant == "bf16" else 2e-5
    asked = np.asarray(q_slots) >= 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[asked],
        np.asarray(ref, np.float32)[asked], atol=tol, rtol=tol)
    assert not np.asarray(got, np.float32)[~asked].any()


@pytest.mark.parametrize("lengths", [
    [(3, 5), (6, 2)],            # inside a page, and across a boundary
    [(9, 7), (4, 7), (1, 3)],    # three requests on two slots
], ids=["two", "three_on_two_slots"])
def test_paged_walk_counters_match_the_hand_count(nano_model, lengths):
    """`paged_walk_pages_total / paged_walk_entries_total` is the share
    of table entries the kernel has to walk: per decode token of a
    request at prompt length L, token i queries slot L + i and walks
    (L + i) // T + 1 pages, of B * MB entries per dispatched token."""
    cfg, params = nano_model
    B = 2
    eng = DecodeEngine(params, cfg, batch_slots=B, max_len=MAX_LEN,
                       kv_block_tokens=T, pipeline_depth=1)
    rng = np.random.RandomState(5)
    for L, n in lengths:
        eng.submit(rng.randint(1, cfg.vocab_size, size=L).tolist(), n)
    while eng.pending():
        eng.step(horizon=1)
    s = eng.stats()
    pages = sum((L + i) // T + 1 for L, n in lengths for i in range(n))
    assert s["paged_walk_pages_total"] == pages
    assert s["paged_walk_entries_total"] == \
        s["decode_dispatches"] * B * (MAX_LEN // T)
    assert 0 < pages / s["paged_walk_entries_total"] < 1
    dense = DecodeEngine(params, cfg, batch_slots=B, max_len=MAX_LEN)
    assert dense.stats()["paged_walk_entries_total"] == 0.0


def test_paged_walk_row_counters_of_a_decode_call(nano_model):
    """`paged_walk_rows_total` counts the rows the kernel walks,
    `paged_walk_rows_chained_total` those whose first pages the row
    before them in the call fetched: every row of a decode call but its
    first (a dead row has a query slot too)."""
    cfg, params = nano_model
    B = 2
    eng = DecodeEngine(params, cfg, batch_slots=B, max_len=MAX_LEN,
                       kv_block_tokens=T, pipeline_depth=1)
    rng = np.random.RandomState(5)
    for L, n in [(5, 5), (6, 2)]:       # one bucket: one prefill call
        eng.submit(rng.randint(1, cfg.vocab_size, size=L).tolist(), n)
    eng.step(horizon=1)                 # prefills both, decodes a token
    before = eng.stats()
    assert (before["prefill_dispatches"],
            before["decode_dispatches"]) == (1, 1)
    assert (before["paged_walk_rows_total"],
            before["paged_walk_rows_chained_total"]) == (2 + B, 1 + 1)
    while eng.pending():
        eng.step(horizon=1)
    s = eng.stats()
    calls = s["decode_dispatches"] - before["decode_dispatches"]
    rows = s["paged_walk_rows_total"] - before["paged_walk_rows_total"]
    chained = s["paged_walk_rows_chained_total"] \
        - before["paged_walk_rows_chained_total"]
    # one slot decodes alone at the end: its dead neighbour walks too
    assert calls >= 4 and rows == calls * B and chained == rows - calls


def test_paged_walk_row_counters_of_prefill_tiles(nano_model, monkeypatch):
    """In prefill the grid is the query tiles row by row (the tile's
    budget is set to 8 tokens here): a tile that walks is counted, and
    counted chained if the tile before it walks too; a tile of bucket
    filler walks nothing and breaks the chain."""
    cfg, params = nano_model
    B = 2
    monkeypatch.setattr(pak, "_TILE_ACC_BYTES",
                        8 * cfg.n_heads * cfg.head_dim * 4)
    eng = DecodeEngine(params, cfg, batch_slots=B, max_len=MAX_LEN,
                       kv_block_tokens=T, prefill_chunk=16)
    # 27 tokens: a chunk of 16 (two tiles of 8), then 11 in a bucket of 16
    # (both tiles hold a real token): two calls of two walking tiles
    rng = np.random.RandomState(3)
    eng.submit(rng.randint(1, cfg.vocab_size, size=27).tolist(), 1)
    eng.run()
    s = eng.stats()
    assert (s["prefill_dispatches"], s["decode_dispatches"]) == (2, 1)
    assert (s["paged_walk_rows_total"],
            s["paged_walk_rows_chained_total"]) == (4 + B, 2 + 1)
    # three rows x four tiles: all real; real up to token 9 (two filler
    # tiles, so the next row's first tile starts its own copies); real up
    # to 17 (one filler tile)
    eng._count_prefill_walk(np.array([0, 0, 8]), np.array([31, 9, 17]), 32)
    t = eng.stats()
    assert t["paged_walk_rows_total"] \
        - s["paged_walk_rows_total"] == 4 + 2 + 3
    assert t["paged_walk_rows_chained_total"] \
        - s["paged_walk_rows_chained_total"] == 3 + 2 + 2
    assert t["paged_walk_rows_chained_total"] <= t["paged_walk_rows_total"]


def test_paged_walk_stacked_counter_of_a_decode_dispatch(nano_model):
    """`paged_walk_rows_stacked_total` counts the rows whose call took
    the row's KV heads as one operand of a step: a decode dispatch of H
    tokens over B slots counts H x B, every row the dispatch walks (the
    rule is `walk_shape`'s, from the call's static shapes: at Mistral's
    a decode row is stacked, at any horizon)."""
    cfg, params = nano_model
    B = 2
    assert pak.walk_shape(1, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, T,
                          MAX_LEN // T, 4)[2]
    assert pak.walk_shape(1, 32, 8, 128, 32, 128, 2) == (16, 1, True)
    eng = DecodeEngine(params, cfg, batch_slots=B, max_len=MAX_LEN,
                       kv_block_tokens=T, pipeline_depth=1)
    rng = np.random.RandomState(5)
    for L, n in [(5, 9), (6, 9)]:
        eng.submit(rng.randint(1, cfg.vocab_size, size=L).tolist(), n)
    eng.step(horizon=1)                 # prefills both, decodes a token
    before = eng.stats()
    for H in (1, 2, 4):
        eng.step(horizon=H)
    s = eng.stats()
    assert s["decode_dispatches"] - before["decode_dispatches"] == 3
    assert s["paged_walk_rows_stacked_total"] \
        - before["paged_walk_rows_stacked_total"] == (1 + 2 + 4) * B
    assert s["paged_walk_rows_stacked_total"] \
        - before["paged_walk_rows_stacked_total"] \
        == s["paged_walk_rows_total"] - before["paged_walk_rows_total"]


def test_paged_walk_stacked_counter_of_128_row_prefill_tiles(nano_model,
                                                             monkeypatch):
    """A 4 x 512 prefill at a tile of 128 query tokens counts 0: a
    tile's heads fill the MXU one at a time and loop, at the nano
    engine's widths (the tile's budget set to 128 tokens) as at
    Mistral's (128 tokens x 32 heads, by `walk_shape` itself)."""
    cfg, params = nano_model
    assert pak.walk_shape(512, 32, 8, 128, 32, 128, 2) == (16, 128, False)
    monkeypatch.setattr(pak, "_TILE_ACC_BYTES",
                        128 * cfg.n_heads * cfg.head_dim * 4)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       kv_block_tokens=T)
    assert eng._walk_shape(512)[1:] == (128, False)
    eng._count_prefill_walk(np.zeros(4, np.int64), np.full(4, 511), 512)
    s = eng.stats()
    assert s["paged_walk_rows_total"] == 4 * 4      # four tiles a row
    assert s["paged_walk_rows_stacked_total"] == 0


def test_own_kv_is_attended_in_place_of_the_pools_slots():
    """`own_kv` lays the queries' own keys and values over the gathered
    rows at `q_slots`: the result is what a pool holding them there
    gives, whatever the pool really holds at those slots; a filler query
    (slot -1) lays nothing; the kernel has no such operand."""
    rng = np.random.RandomState(23)
    q, kf, vf, bt, q_slots, _, _ = _ragged_case(4, "bf16", rng)
    B, S = q_slots.shape
    KV = kf.shape[3] // q.shape[3]
    own = tuple(jnp.asarray(rng.randn(B, S, KV, q.shape[3]), q.dtype)
                for _ in range(2))
    kw = dict(layer=1, kv_valid_len=_RT * _RMB, impl="reference")
    got = paged_attention(q, kf, vf, bt, q_slots, own_kv=own, **kw)
    blk = bt[jnp.arange(B)[:, None], q_slots // _RT]
    want = paged_attention(
        q, *(pool.at[1, blk, q_slots % _RT].set(x.reshape(B, S, -1))
             for pool, x in ((kf, own[0]), (vf, own[1]))),
        bt, q_slots, **kw)
    assert jnp.array_equal(got, want)
    assert not jnp.array_equal(
        got, paged_attention(q, kf, vf, bt, q_slots, **kw))
    filler = q_slots.at[:, -1].set(-1)
    assert jnp.array_equal(
        paged_attention(q, kf, vf, bt, filler, own_kv=own, **kw)[:, :-1],
        got[:, :-1])
    with pytest.raises(ValueError, match="own_kv"):
        paged_attention(q, kf, vf, bt, q_slots, own_kv=own,
                        **{**kw, "impl": "flash"})


# ---------------------------------------------------------------------------
# Query tiles: a prefill chunk through the kernel (PR 30)
# ---------------------------------------------------------------------------

# sha256[:16] of the kernel's float32 output bytes on `_ragged_case(S,
# quant, RandomState(17 + S))`, layer 1, every slot valid. The bf16 pair
# is what the kernel gave BEFORE it gained query tiles (grid `(B,)`, one
# step holding all S*G query rows of a row), and held through PR 51. The
# six with float32 queries were RETAKEN in PR 51: a step's scores are now
# one product over all the row's heads' lanes (a head's query beside
# zeros), and the CPU's float32 matmul associates a contraction of 32
# otherwise than one of 16: at most 2.4e-7 from the old kernel's output
# on values up to 2.5 (dense 1.2e-7 / 2.4e-7 for 1 / 4 slots, int8 1.8e-7
# / 1.8e-7, fp8 2.4e-7 / 2.4e-7), one or two units in the last place.
# bf16 products are exact in float32 and their sums came out the same.
_ONE_TILE_BITS = {
    (None, 1): "e5d1ae45eb6db9b5", (None, 4): "9321599ced3f83c6",
    ("bf16", 1): "8e36b600eac233f7", ("bf16", 4): "b4ba18a027e66db3",
    ("int8", 1): "373d94ce23dd14ba", ("int8", 4): "8ad4e2bc2bb44d96",
    ("fp8_e4m3", 1): "629f61050d7b951e",
    ("fp8_e4m3", 4): "37d04a17d03623dd",
}


@pytest.mark.parametrize("slots", [1, 4], ids=["s1", "s4"])
@pytest.mark.parametrize("quant", [None, "bf16", "int8", "fp8_e4m3"],
                         ids=["dense", "bf16", "int8", "fp8"])
def test_kernel_one_tile_bits_are_what_they_were(monkeypatch, quant, slots):
    """A decode token and a speculative window are ONE tile a row, all
    rows in one call as before tiles existed, so the output is, bit for
    bit, what the untiled kernel gave (what the stacked body gave when
    it replaced the heads' own, where float32 queries round another way:
    `_ONE_TILE_BITS`). And tiling is only a
    grouping of query rows: the same call cut into tiles of one query
    (each walking no further than its own slot) gives the same values
    (a matmul of fewer rows may round its sums in another order)."""
    import hashlib

    rng = np.random.RandomState(17 + slots)
    q, kf, vf, bt, q_slots, sk, sv = _ragged_case(slots, quant, rng)
    kw = dict(impl="flash", layer=jnp.int32(1), kv_valid_len=_RT * _RMB,
              k_scale=sk, v_scale=sv)
    got = paged_attention(q, kf, vf, bt, q_slots, **kw)
    assert pak.walk_shape(slots, 4, 2, 16, _RT, _RMB, 4)[1] == slots
    assert hashlib.sha256(np.asarray(got, np.float32).tobytes()) \
        .hexdigest()[:16] == _ONE_TILE_BITS[quant, slots]
    real = pak.walk_shape
    monkeypatch.setattr(pak, "walk_shape",
                        lambda *a: (real(*a)[0], 1, real(*a)[2]))
    np.testing.assert_allclose(
        np.asarray(paged_attention(q, kf, vf, bt, q_slots, **kw),
                   np.float32),
        np.asarray(got, np.float32), atol=2e-6, rtol=2e-6)


_CT = 8                     # chunk sweep: pages of 8 tokens


def _chunk_case(S, quant, group, rng):
    """Prefill-shaped rows for the tiled kernel: chunks of ``S`` queries
    at start 0, mid-block (3), several blocks in (5 pages + 5), a row
    whose chunk is all bucket filler (every slot -1: it is asked
    nothing) and a group's padding row (the third, verbatim). Pool of
    two layers, ``KV = 2`` heads of 16, ``group`` query heads each."""
    KV, D = 2, 16
    starts = [0, 3, 5 * _CT + 5, 0, 5 * _CT + 5]
    B = len(starts)
    MB = -(-(max(starts) + S) // _CT) + 1
    q_slots = np.asarray(starts)[:, None] + np.arange(S)[None]
    q_slots[3] = -1
    NB = 1 + B * MB
    dt = jnp.bfloat16 if quant == "bf16" else jnp.float32
    kf = jnp.asarray(rng.randn(2, NB, _CT, KV, D), dt)
    vf = jnp.asarray(rng.randn(2, NB, _CT, KV, D), dt)
    bt = 1 + np.arange(B * MB).reshape(B, MB)
    bt[4] = bt[2]
    q = rng.randn(B, S, KV * group, D)
    q[4] = q[2]
    kf, vf, sk, sv = _quantized(kf, vf, quant)
    return (jnp.asarray(q, dt), kf, vf, jnp.asarray(bt, jnp.int32),
            jnp.asarray(q_slots, jnp.int32), sk, sv, MB)


@pytest.mark.parametrize("group", [1, 4], ids=["g1", "g4"])
@pytest.mark.parametrize("quant", ["bf16", "int8", "fp8_e4m3"],
                         ids=["bf16", "int8", "fp8"])
@pytest.mark.parametrize("slots", [1, 4, 128, 512])
def test_kernel_tiled_chunks_match_reference(monkeypatch, slots, quant,
                                             group):
    """A chunk goes tile by tile: with the tile's budget set to 32
    query tokens a 128-token chunk is 4 tiles and a 512-token chunk 16
    (1 and 4 stay one tile, in one call over the rows), steps of 4
    pages, chunks that start inside a page so that tiles straddle pages
    and pages straddle tiles.
    Against the pure-lax reference; a tile walks no further than its own
    last slot (`live_pages` of the tile); the all-filler row walks
    nothing and reads 0; the padding row reads what the row it repeats
    reads."""
    monkeypatch.setattr(pak, "_KEYS_PER_STEP", 4 * _CT)
    monkeypatch.setattr(pak, "_TILE_ACC_BYTES", 32 * 2 * group * 16 * 4)
    rng = np.random.RandomState(31 + slots + group)
    q, kf, vf, bt, q_slots, sk, sv, MB = _chunk_case(slots, quant, group,
                                                     rng)
    pps, tq, _ = pak.walk_shape(slots, 2 * group, 2, 16, _CT, MB,
                                kf.dtype.itemsize)
    assert (pps, tq) == (4, min(slots, 32))
    kw = dict(layer=1, kv_valid_len=MB * _CT, k_scale=sk, v_scale=sv)
    ref = paged_attention(q, kf, vf, bt, q_slots, impl="reference", **kw)
    got = paged_attention(q, kf, vf, bt, q_slots, impl="flash", **kw)
    tol = 2e-2 if quant == "bf16" else 2e-5
    live = [0, 1, 2, 4]
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(ref, np.float32)[live],
        atol=tol, rtol=tol)
    assert not np.asarray(got[3], np.float32).any()
    assert jnp.array_equal(got[4], got[2])
    # what each tile is asked to walk: the pages up to its last slot
    tiles = -(-slots // tq)
    n_live = pak.live_pages(q_slots.reshape(-1, tq), MB * _CT, _CT, MB) \
        .reshape(-1, tiles)
    assert n_live[3].tolist() == [0] * tiles
    assert n_live[1].tolist() == [
        (3 + min((j + 1) * tq, slots) - 1) // _CT + 1 for j in range(tiles)]

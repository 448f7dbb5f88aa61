"""Kernel correctness: flash attention (interpret mode) and ring attention
vs the pure-JAX reference, on the 8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _rand_qkv(b=2, h=4, hkv=2, s=256, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    from ray_tpu.ops import flash_attention, mha_reference

    q, k, v = _rand_qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_grad_matches_reference():
    from ray_tpu.ops import flash_attention, mha_reference

    q, k, v = _rand_qkv(s=128, d=32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                               interpret=True).sum()

    def loss_ref(q, k, v):
        return mha_reference(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal, impl):
    from ray_tpu.ops import mha_reference
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel import create_mesh

    mesh = create_mesh({"sp": 8})
    q, k, v = _rand_qkv(b=2, h=4, hkv=4, s=256, d=32)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal, impl=impl)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_ring_attention_gqa_matches_reference():
    from ray_tpu.ops import mha_reference
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel import create_mesh

    mesh = create_mesh({"sp": 8})
    q, k, v = _rand_qkv(b=1, h=8, hkv=2, s=128, d=16)
    out = ring_attention_sharded(q, k, v, mesh, causal=True,
                                 impl="pallas")
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_ring_attention_grads(impl):
    from ray_tpu.ops import mha_reference
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel import create_mesh

    mesh = create_mesh({"sp": 8})
    q, k, v = _rand_qkv(b=1, h=2, hkv=2, s=128, d=16)

    g1 = jax.grad(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh, causal=True, impl=impl).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: mha_reference(
        q, k, v, causal=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_gqa_reference():
    from ray_tpu.ops import mha_reference

    q, k, v = _rand_qkv(h=8, hkv=2)
    out = mha_reference(q, k, v)
    assert out.shape == q.shape


def test_mesh_and_sharding_rules():
    from ray_tpu.parallel import (MeshSpec, create_mesh, spec_for,
                                  named_sharding)
    from jax.sharding import PartitionSpec as P

    sizes = MeshSpec(dp=-1, tp=2).resolve(8)
    assert sizes == {"dcn": 1, "dp": 4, "pp": 1, "fsdp": 1, "ep": 1,
                     "sp": 1, "tp": 2}
    mesh = create_mesh(sizes)
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2

    assert spec_for("batch", "length", "embed") == \
        P(("dcn", "dp", "fsdp"), "sp", None)  # embed->fsdp used by batch
    assert spec_for("embed", "mlp") == P("fsdp", "tp")
    s = named_sharding(mesh, "batch", None, "embed")
    assert s.mesh is not None


def test_flash_decode_shapes_and_padding():
    """Sq != Sk (decode) and non-divisible lengths match the reference."""
    from ray_tpu.ops import flash_attention, mha_reference

    # decode: 1 query over a 96-token prefix, block bigger than seq
    q, k, v = _rand_qkv(s=96, d=32)
    q1 = q[:, :, -1:, :]
    out = flash_attention(q1, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = mha_reference(q1, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    # non-divisible: 100 tokens with 64-blocks (padding path)
    q, k, v = _rand_qkv(s=100, d=32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    # non-causal with padding (masked kv columns)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


_SCHEDULE_CASES = [
    # sq, sk, block_q, block_k, causal, (live, masked, rectangle) or None
    pytest.param(4096, 4096, 512, 512, True, (36, 8, 64), id="train-cell"),
    pytest.param(4096, 4096, 512, 512, False, (64, 0, 64), id="full"),
    pytest.param(100, 100, 64, 64, True, (3, 2, 4), id="padded"),
    pytest.param(100, 100, 64, 64, False, (4, 2, 4), id="padded-full"),
    pytest.param(1, 96, 64, 64, True, (2, 1, 2), id="decode-prefix"),
    pytest.param(16, 8, 8, 8, True, (1, 1, 2), id="rows-before-every-key"),
    pytest.param(24, 40, 8, 16, True, None, id="prefix-uneven-tiles"),
]


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"])
@pytest.mark.parametrize("sq,sk,bq,bk,causal,counts", _SCHEDULE_CASES)
def test_flash_pair_schedule(kernel, sq, sk, bq, bk, causal, counts):
    """The kernels' grid: every pair `_block_contributes` accepts, once,
    and no other (but ONE dead pair for an outer block with no live inner
    block); `first` / `last` bracket an outer block's run; a pair goes
    unmasked only where `_attn_mask` is all true."""
    import importlib

    # `ray_tpu.ops.flash_attention` the attribute is the function
    F = importlib.import_module("ray_tpu.ops.flash_attention")

    bq, bk = min(bq, sq), min(bk, sk)       # as `_flash_fwd` clips them
    nq, nk = -(-sq // bq), -(-sk // bk)
    off = sk - sq
    sched = F.pair_schedule(kernel, sq, sk, bq, bk, causal)
    q_outer = kernel != "flash_bwd_dkv"
    n_outer, n_inner = (nq, nk) if q_outer else (nk, nq)

    def qk(o, n):
        return (o, n) if q_outer else (n, o)

    want = {(o, n) for o in range(n_outer) for n in range(n_inner)
            if F._block_contributes(*qk(o, n), bq, bk, off, causal)}
    pairs = list(zip(sched.outer.tolist(), sched.inner.tolist()))
    live = [p for p, dead in zip(pairs, sched.dead) if not dead]
    assert len(live) == len(set(live)) and set(live) == want
    # a dead pair stands for an outer block nothing contributes to
    assert ({o for (o, _), dead in zip(pairs, sched.dead) if dead}
            == set(range(n_outer)) - {o for o, _ in want})
    assert not (sched.dead & sched.masked).any()
    # order: outer blocks ascending, each ONE run, inner ascending in it
    assert pairs == sorted(pairs)
    assert sorted({o for o, _ in pairs}) == list(range(n_outer))
    for x, (o, _) in enumerate(pairs):
        assert sched.first[x] == (x == 0 or pairs[x - 1][0] != o)
        assert sched.last[x] == (x == len(pairs) - 1
                                 or pairs[x + 1][0] != o)
    for (o, n), masked, dead in zip(pairs, sched.masked, sched.dead):
        if not dead:
            whole = bool(np.asarray(F._attn_mask(
                *qk(o, n), bq, bk, off, sk, causal)).all())
            assert masked == (not whole), (o, n)
    assert sched.kv_padded == (sk % bk != 0)
    # the grid is the pairs, or the rectangle itself where nothing is
    # skipped (an index map then reads no table)
    assert sched.grid == ((n_outer, n_inner) if len(want) == nq * nk
                          else (len(pairs),))
    if counts is not None:
        assert sched.counts() == dict(zip(("live", "masked", "rectangle"),
                                          counts))
    # the operand the kernels read: a row a field, a column a pair
    op = sched.pairs
    assert op.dtype == np.int32 and op.shape == (5, len(pairs))
    assert set(op[4].tolist()) <= {0, 1, 2}
    assert ((op[4] == 0) == sched.dead).all()
    assert ((op[4] == 2) == sched.masked).all()


def test_flash_pair_counters():
    """A call built counts its pairs once, by kernel: the triangle it
    steps through, the masked pairs in it, and the rectangle."""
    from ray_tpu.ops import flash_attention
    from ray_tpu.util import metrics

    def read():
        return {(r["name"], r["tags"]["kernel"]): r["value"]
                for r in metrics.snapshots()
                if r["name"].startswith("flash_pairs_")}

    q, k, v = _rand_qkv(b=1, h=4, hkv=2, s=256, d=32)
    before = read()
    jax.grad(lambda q: flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64,
        interpret=True).sum())(q)
    now = read()
    for kern in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        got = tuple((now[f"flash_pairs_{what}_total", kern]
                     - before.get((f"flash_pairs_{what}_total", kern), 0))
                    / 4 for what in ("live", "masked", "rectangle"))
        assert got == (10, 4, 16), kern


def test_flash_rejects_bad_gqa():
    from ray_tpu.ops import flash_attention

    q, k, v = _rand_qkv(h=6, hkv=4, s=64, d=16)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, interpret=True)


def test_llama_init_fan_in():
    """wo must be scaled by (heads*head_dim)^-0.5, not heads^-0.5."""
    from ray_tpu.models import LlamaConfig, llama_init

    cfg = LlamaConfig.nano(dim=64, n_heads=4)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    wo = params["layers"]["wo"]  # [L, heads, hd, dim]
    std = float(jnp.std(wo))
    expected = (cfg.n_heads * cfg.head_dim) ** -0.5
    assert abs(std - expected) / expected < 0.15, (std, expected)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grad_gqa_and_padding(causal):
    """Pallas backward: GQA group reduction + non-divisible lengths."""
    from ray_tpu.ops import flash_attention, mha_reference

    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, hkv, s, d = 2, 4, 2, 96, 32  # s=96 not divisible by block 64
    q = jax.random.normal(kq, (b, h, s, d))
    k = jax.random.normal(kk, (b, hkv, s, d))
    v = jax.random.normal(kv, (b, hkv, s, d))

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=64,
                              block_k=64, interpret=True)
        return (out * out).sum()  # nontrivial cotangent

    def loss_ref(q, k, v):
        out = mha_reference(q, k, v, causal=causal)
        return (out * out).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4)


def test_flash_grad_decode_prefix():
    """Backward through the decode/kv-prefix path (Sq != Sk): distinct
    q_offset arithmetic in the bwd kernels."""
    from ray_tpu.ops import flash_attention, mha_reference

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    b, h, d = 1, 2, 32
    q = jax.random.normal(ks[0], (b, h, 8, d))
    k = jax.random.normal(ks[1], (b, h, 96, d))
    v = jax.random.normal(ks[2], (b, h, 96, d))

    def lf(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=64,
                                block_k=64, interpret=True) ** 2).sum()

    def lr(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4)


def test_flash_grad_fully_masked_rows():
    """causal with Sq > Sk: rows before the kv prefix are fully masked —
    their softmax is empty and must contribute zero gradient."""
    from ray_tpu.ops import flash_attention, mha_reference

    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    b, h, d = 1, 2, 16
    q = jax.random.normal(ks[0], (b, h, 16, d))
    k = jax.random.normal(ks[1], (b, h, 8, d))
    v = jax.random.normal(ks[2], (b, h, 8, d))

    def lf(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=8,
                                block_k=8, interpret=True) ** 2).sum()

    def lr(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4)
    # Fully-masked q rows (positions before the kv prefix) carry NO
    # gradient by definition.
    np.testing.assert_allclose(np.asarray(g1[0][:, :, :7]), 0.0,
                               atol=1e-6)

"""Model-layer tests on the 8-device CPU mesh: forward shapes, sharded
train step convergence, graft entry points."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def test_llama_forward_shapes():
    from ray_tpu.models import LlamaConfig, llama_init, llama_forward

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama_forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_llama_causality():
    """Changing a future token must not change past logits."""
    from ray_tpu.models import LlamaConfig, llama_init, llama_forward

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 10].set(7)
    l1 = llama_forward(params, t1, cfg)
    l2 = llama_forward(params, t2, cfg)
    np.testing.assert_allclose(l1[0, :10], l2[0, :10], atol=1e-5)
    assert not np.allclose(l1[0, 10:], l2[0, 10:])


def test_remat_policies_same_loss_and_grads():
    """Every remat policy is a pure memory/FLOPs trade: loss AND grads
    must be bit-comparable to the full-remat baseline (same graph, same
    dtypes — only what is saved vs recomputed differs)."""
    import dataclasses

    import pytest

    from ray_tpu.models import LlamaConfig, llama_init, llama_loss

    base = LlamaConfig.nano(remat=True)
    params = llama_init(jax.random.PRNGKey(0), base)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                                base.vocab_size)
    batch = {"tokens": tokens}

    def loss_and_grads(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p: llama_loss(p, batch, cfg)))(params)

    ref_loss, ref_grads = loss_and_grads(base)
    for policy in ("save_dots", "save:ffn_gate+ffn_up",
                   "save:qkv+attn_out", "save:ffn_down"):
        cfg = dataclasses.replace(base, remat_policy=policy)
        loss, grads = loss_and_grads(cfg)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-6, err_msg=policy)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=1e-6, err_msg=policy),
            ref_grads, grads)

    with pytest.raises(ValueError):
        dataclasses.replace(base, remat_policy="save:not_a_name")
    with pytest.raises(ValueError):
        dataclasses.replace(base, remat_policy="bogus")

    # MoE carries no checkpoint_name tags — named policies (which would
    # silently run as full remat there) must be rejected, not ignored.
    from ray_tpu.models.moe import MoeConfig

    with pytest.raises(ValueError):
        MoeConfig.nano_moe(remat_policy="save:ffn_gate")


def test_chunked_loss_matches_unchunked():
    """cfg.loss_chunk is a pure memory/traffic optimization: loss AND
    grads must match the full-logits path (same f32 softmax math, just
    lax.map'd per chunk under remat)."""
    import dataclasses

    from ray_tpu.models import LlamaConfig, llama_init, llama_loss

    base = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), base)
    # S = 32 after the tokens->inputs shift; chunk 8 divides it
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                base.vocab_size)
    mask = (jnp.arange(32)[None, :] < jnp.array([[30], [20]])).astype(
        jnp.float32)
    for batch in ({"tokens": tokens},
                  {"inputs": tokens[:, :-1], "targets": tokens[:, 1:],
                   "mask": mask}):
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: llama_loss(p, batch, base)))(params)
        chunked = dataclasses.replace(base, loss_chunk=8)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: llama_loss(p, batch, chunked)))(params)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                    atol=1e-6),
            ref_grads, grads)
    # non-dividing chunk falls back to the unchunked path (still correct)
    odd = dataclasses.replace(base, loss_chunk=7)
    loss = llama_loss(params, {"tokens": tokens}, odd)
    ref = llama_loss(params, {"tokens": tokens}, base)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)


def test_lora_init_is_identity_and_adapter_only_training():
    """B=0 at init => merged model == base exactly; training moves ONLY
    the adapters (base tree bit-identical after steps), loss decreases,
    and the merged tree drives generation unchanged."""
    import optax

    from ray_tpu.models import (LlamaConfig, LoraConfig, llama_forward,
                                llama_init, llama_loss, llama_param_specs,
                                lora_init, lora_merge, lora_num_params,
                                make_lora_train_step)
    from ray_tpu.models.generate import generate
    from ray_tpu.parallel import MeshSpec, create_mesh

    cfg = LlamaConfig.nano()
    lcfg = LoraConfig(rank=4, targets=("wq", "wv", "w_gate"))
    base = llama_init(jax.random.PRNGKey(0), cfg)
    lora = lora_init(jax.random.PRNGKey(1), cfg, lcfg)

    # adapter size sanity: tiny versus the base
    n_lora = lora_num_params(cfg, lcfg)
    assert 0 < n_lora < 0.2 * cfg.num_params()

    tokens = jnp.arange(16, dtype=jnp.int32)[None, :] % cfg.vocab_size
    merged0 = lora_merge(base, lora, cfg, lcfg)
    np.testing.assert_allclose(llama_forward(merged0, tokens, cfg),
                               llama_forward(base, tokens, cfg), atol=1e-6)

    mesh = create_mesh(MeshSpec(dp=2, fsdp=2, tp=2).resolve(8))
    init_fn, step_fn = make_lora_train_step(
        lambda p, b: llama_loss(p, b, cfg), optax.adamw(1e-2), mesh,
        cfg, lcfg, llama_param_specs(cfg))
    base_s, lora_s, opt_state = init_fn(base, lora)
    base_before = jax.tree_util.tree_map(np.asarray, base_s)

    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(2), (4, 17), 0, cfg.vocab_size)}
    losses = []
    for _ in range(5):
        lora_s, opt_state, metrics = step_fn(lora_s, opt_state, base_s,
                                             batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
        base_before, base_s)
    # adapters actually moved
    assert float(jnp.abs(lora_s["layers"]["wq"]["b"]).sum()) > 0

    # merged tree serves generation end-to-end
    merged = lora_merge(base, jax.tree_util.tree_map(np.asarray, lora_s),
                        cfg, lcfg)
    out = generate(merged, jnp.array([[5, 6, 7]], jnp.int32), cfg,
                   max_new_tokens=4)
    assert np.asarray(out).shape == (1, 7)

    import pytest

    with pytest.raises(ValueError):
        LoraConfig(rank=0)
    with pytest.raises(ValueError):
        LoraConfig(targets=("attn_norm",))


def test_sharded_train_step_loss_decreases():
    import optax

    from ray_tpu.models import (LlamaConfig, llama_init, llama_loss,
                                llama_param_specs)
    from ray_tpu.models.training import make_sharded_train_step
    from ray_tpu.parallel import MeshSpec, create_mesh

    cfg = LlamaConfig.nano()
    mesh = create_mesh(MeshSpec(dp=2, fsdp=2, tp=2).resolve(8))
    init_fn, step_fn = make_sharded_train_step(
        lambda p, b: llama_loss(p, b, cfg),
        optax.adamw(1e-2), mesh, llama_param_specs(cfg))
    params, opt_state = init_fn(llama_init(jax.random.PRNGKey(0), cfg))

    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    losses = []
    for _ in range(5):
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses

    # param sharding actually applied
    leaf = params["layers"]["w_gate"]
    assert len(leaf.sharding.device_set) == 8


def test_ring_attention_in_model():
    """attn_impl='ring' under shard_map matches reference forward."""
    import functools

    from ray_tpu.models import LlamaConfig, llama_init, llama_forward
    from ray_tpu.parallel import create_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg_ref = LlamaConfig.nano(n_layers=1, n_kv_heads=4)
    cfg_ring = LlamaConfig.nano(n_layers=1, n_kv_heads=4, attn_impl="ring")
    params = llama_init(jax.random.PRNGKey(0), cfg_ref)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg_ref.vocab_size)

    mesh = create_mesh({"sp": 4}, jax.devices()[:4])

    def fwd(params, tokens, positions):
        return llama_forward(params, tokens, cfg_ring, positions=positions)

    positions = jnp.broadcast_to(jnp.arange(32), (2, 32))
    shard = jax.shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False)
    out_ring = shard(params, tokens, positions)
    out_ref = llama_forward(params, tokens, cfg_ref)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_ref),
                               atol=2e-4, rtol=2e-4)


def test_graft_entry():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.ndim == 3
    ge.dryrun_multichip(8)


def test_mlp():
    import optax

    from ray_tpu.models.mlp import MLPConfig, mlp_init, mlp_loss

    cfg = MLPConfig(in_dim=16, hidden=(32,), n_classes=4)
    params = mlp_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    y = jnp.arange(8) % 4
    opt = optax.adam(1e-2)
    state = opt.init(params)
    loss0 = None
    for _ in range(20):
        loss, grads = jax.value_and_grad(mlp_loss)(params, {"x": x, "y": y},
                                                   cfg)
        updates, state = opt.update(grads, state)
        import optax as _o
        params = _o.apply_updates(params, updates)
        loss0 = loss0 if loss0 is not None else float(loss)
    assert float(loss) < loss0


def test_vit_forward_and_sharded_training():
    """ViT family: patchify-as-reshape forward shapes, GSPMD-sharded
    train step on the 8-device mesh, loss decreases, params sharded."""
    import optax

    from ray_tpu.models import (ViTConfig, vit_init, vit_loss,
                                vit_param_specs)
    from ray_tpu.models.vit import vit_forward
    from ray_tpu.models.training import make_sharded_train_step
    from ray_tpu.parallel import MeshSpec, create_mesh

    cfg = ViTConfig(image_size=8, patch_size=4, dim=32, n_layers=2,
                    n_heads=4, ffn_dim=64, num_classes=10,
                    dtype=jax.numpy.float32)
    params = vit_init(jax.random.PRNGKey(0), cfg)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8, 3))
    logits = vit_forward(params, imgs, cfg)
    assert logits.shape == (4, 10)

    mesh = create_mesh(MeshSpec(dp=2, fsdp=2, tp=2).resolve(8))
    init_fn, step_fn = make_sharded_train_step(
        lambda p, b: vit_loss(p, b, cfg),
        optax.adamw(3e-3), mesh, vit_param_specs(cfg))
    params, opt_state = init_fn(params)
    labels = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
    batch = {"images": jax.random.normal(jax.random.PRNGKey(3),
                                         (8, 8, 8, 3)),
             "labels": labels}
    losses = []
    for _ in range(8):
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    # qkv projection ACTUALLY partitioned: the addressable shard is
    # half-sized on both matrix dims ([layers, d/fsdp, 3d/tp]).
    shard = params["layers"]["wqkv"].addressable_shards[0].data
    assert shard.shape == (2, 32 // 2, 3 * 32 // 2), shard.shape


def test_t5_forward_and_sharded_training():
    """Encoder-decoder family: forward shapes, teacher-forcing loss with
    pad masking, GSPMD-sharded train step on the 8-device mesh, loss
    decreases, params actually partitioned, causal decoder semantics."""
    import optax

    from ray_tpu.models import (T5Config, t5_decode, t5_encode, t5_init,
                                t5_loss, t5_param_specs)
    from ray_tpu.models.t5 import t5_forward
    from ray_tpu.models.training import make_sharded_train_step
    from ray_tpu.parallel import MeshSpec, create_mesh

    cfg = T5Config(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                   ffn_dim=64, dtype=jnp.float32)
    assert cfg.num_params() > 0
    params = t5_init(jax.random.PRNGKey(0), cfg)
    src = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 1, 64)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (2, 7), 1, 64)
    memory = t5_encode(params, src, cfg)
    assert memory.shape == (2, 10, 32)
    logits = t5_decode(params, memory, tgt, cfg)
    assert logits.shape == (2, 7, 64)

    # Decoder is causal: changing a LATE target token must not change
    # logits at earlier positions (cross-attention sees all of src).
    tgt2 = tgt.at[:, -1].set((tgt[:, -1] + 1) % 64)
    logits2 = t5_decode(params, memory, tgt2, cfg)
    np.testing.assert_allclose(np.asarray(logits[:, :-1]),
                               np.asarray(logits2[:, :-1]),
                               rtol=1e-5, atol=1e-5)
    # ...and the encoder is NOT causal: a late src change reaches
    # every decoder position through cross-attention.
    src2 = src.at[:, -1].set((src[:, -1] + 1) % 64)
    logits3 = t5_forward(params, src2, tgt, cfg)
    assert not np.allclose(np.asarray(logits), np.asarray(logits3))

    # Pad labels drop out of the loss.
    batch = {"src": src,
             "tgt": jnp.concatenate(
                 [tgt, jnp.zeros((2, 2), tgt.dtype)], axis=1)}
    loss_padded = t5_loss(params, batch, cfg)
    assert jnp.isfinite(loss_padded)

    # Sharded training: copy-task (tgt == src prefix) on the 8-dev mesh.
    mesh = create_mesh(MeshSpec(dp=2, fsdp=2, tp=2).resolve(8))
    init_fn, step_fn = make_sharded_train_step(
        lambda p, b: t5_loss(p, b, cfg),
        optax.adamw(3e-3), mesh, t5_param_specs(cfg))
    params, opt_state = init_fn(params)
    seq = jax.random.randint(jax.random.PRNGKey(3), (8, 8), 1, 64)
    train_batch = {"src": seq,
                   "tgt": jnp.concatenate(
                       [jnp.ones((8, 1), seq.dtype), seq], axis=1)}
    losses = []
    for _ in range(10):
        params, opt_state, metrics = step_fn(params, opt_state,
                                             train_batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    # Cross-attn q projection ACTUALLY partitioned:
    # [layers, d/fsdp, heads/tp, k].
    shard = params["decoder"]["cross_wq"].addressable_shards[0].data
    assert shard.shape == (2, 32 // 2, 4 // 2, 8), shard.shape


def test_llama_kv_cache_generation():
    """Decode path (models/generate.py): cached prefill+decode logits
    must equal the full uncached forward on the same sequence; greedy
    generate is deterministic; eos fill keeps shapes static."""
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.generate import (forward_cached, generate,
                                         init_cache)
    from ray_tpu.models.llama import llama_forward

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    B, P, T = 2, 6, 5
    seq = jax.random.randint(jax.random.PRNGKey(1), (B, P + T), 0,
                             cfg.vocab_size)

    # Reference: full uncached forward over the whole sequence.
    ref_logits = llama_forward(params, seq, cfg)

    # Cached: prefill the first P tokens, then teacher-force one token
    # at a time through the cache.
    cache = init_cache(cfg, B, P + T)
    logits, cache = forward_cached(params, seq[:, :P], cache, 0, cfg)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(ref_logits[:, :P]),
                               rtol=2e-4, atol=2e-4)
    for t in range(T):
        step_logits, cache = forward_cached(
            params, seq[:, P + t:P + t + 1], cache, P + t, cfg)
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]),
            np.asarray(ref_logits[:, P + t]),
            rtol=2e-4, atol=2e-4)

    # Greedy generation: right shape, deterministic, and equal to
    # manually arg-maxing the reference logits one step at a time.
    prompt = seq[:, :P]
    out1 = generate(params, prompt, cfg, max_new_tokens=4)
    out2 = generate(params, prompt, cfg, max_new_tokens=4)
    assert out1.shape == (B, P + 4)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(out1[:, :P]),
                                  np.asarray(prompt))
    manual = prompt
    for _ in range(4):
        step = jnp.argmax(llama_forward(params, manual, cfg)[:, -1],
                          axis=-1)
        manual = jnp.concatenate([manual, step[:, None].astype(
            manual.dtype)], axis=1)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(manual))

    # Sampling path runs (finite tokens in range).
    sampled = generate(params, prompt, cfg, max_new_tokens=3,
                       greedy=False, temperature=0.8,
                       rng=jax.random.PRNGKey(7))
    assert sampled.shape == (B, P + 3)
    assert int(np.asarray(sampled).min()) >= 0
    assert int(np.asarray(sampled).max()) < cfg.vocab_size

    # eos fill: once a row emits eos, it keeps emitting eos.
    eos = int(np.asarray(out1)[0, P])  # force row 0's first new token
    out3 = np.asarray(generate(params, prompt, cfg, max_new_tokens=4,
                               eos_id=eos))
    hit = np.asarray(out3[0, P:]) == eos
    assert hit[0] and hit.all()


def test_llama_ragged_batch_generation():
    """Ragged serving: left-padded batched decode must produce EXACTLY
    the tokens each row would get generated alone (pad slots masked
    out of attention, RoPE positions pad-adjusted)."""
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.generate import generate, pad_prompts

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    p0 = [5, 6, 7]
    p1 = [9, 8, 7, 6, 5, 4]
    padded, live = pad_prompts([p0, p1])
    assert padded.shape == (2, 6) and live[0].sum() == 3

    out = np.asarray(generate(params, jnp.asarray(padded), cfg,
                              max_new_tokens=4,
                              prompt_live=jnp.asarray(live)))
    s0 = np.asarray(generate(params, jnp.asarray([p0], jnp.int32),
                             cfg, max_new_tokens=4))
    s1 = np.asarray(generate(params, jnp.asarray([p1], jnp.int32),
                             cfg, max_new_tokens=4))
    np.testing.assert_array_equal(out[0, -4:], s0[0, -4:])
    np.testing.assert_array_equal(out[1, -4:], s1[0, -4:])

    # Serving-shape bucketing: P rounds to a power of two, filler rows
    # bring B to the cap; real rows are unaffected.
    b_padded, b_live = pad_prompts([p0, p1], bucket_len=True,
                                   pad_batch_to=4)
    assert b_padded.shape == (4, 8) and b_live[2].sum() == 1
    out_b = np.asarray(generate(params, jnp.asarray(b_padded), cfg,
                                max_new_tokens=4,
                                prompt_live=jnp.asarray(b_live)))
    np.testing.assert_array_equal(out_b[0, -4:], s0[0, -4:])
    np.testing.assert_array_equal(out_b[1, -4:], s1[0, -4:])

    # Guard rails: empty prompts and empty batches are rejected.
    import pytest as _pytest
    with _pytest.raises(ValueError, match="BOS"):
        pad_prompts([[1, 2], []])
    with _pytest.raises(ValueError, match="at least one"):
        pad_prompts([])


def test_sampling_filters_topk_topp():
    """filter_logits semantics + generate/generate_stream sampling.

    Unit level: top-k keeps exactly the k largest, top-p keeps the
    smallest prefix of the sorted distribution whose mass reaches p
    (argmax always survives), no-op knobs change nothing. Integration:
    top_k=1 sampling is argmax regardless of temperature, and the
    streamed sampler with the same rng is token-identical to the
    scanned batch sampler (shared key schedule)."""
    import pytest as _pytest

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.generate import (filter_logits, generate,
                                         generate_stream)

    logits = jnp.array([[1.0, 3.0, 2.0, 0.5]], jnp.float32)

    kept = np.isfinite(np.asarray(filter_logits(logits, top_k=2))) \
        & (np.asarray(filter_logits(logits, top_k=2)) > -1e30)
    np.testing.assert_array_equal(kept[0], [False, True, True, False])

    # softmax([1,3,2,.5]) ~ [.086, .631, .232, .052]; sorted cum mass =
    # [.631, .863, .948, 1]. p=0.6 keeps only the argmax (smallest
    # prefix with mass >= .6); p=0.9 needs three tokens (.863 < .9).
    f6 = np.asarray(filter_logits(logits, top_p=0.6))[0]
    assert (f6 > -1e30).tolist() == [False, True, False, False]
    f9 = np.asarray(filter_logits(logits, top_p=0.9))[0]
    assert (f9 > -1e30).tolist() == [True, True, True, False]

    # tied logits must NOT inflate the nucleus: four equal logits
    # (mass .25 each) at p=0.3 keep exactly the 2-token sorted prefix
    # (preceding masses 0 and .25 < .3) — the old value-threshold
    # compare kept all four ties
    tied = jnp.zeros((1, 4), jnp.float32)
    ft = np.asarray(filter_logits(tied, top_p=0.3))[0]
    assert (ft > -1e30).sum() == 2
    # and at p=0.2 only the first sorted token survives
    ft1 = np.asarray(filter_logits(tied, top_p=0.2))[0]
    assert (ft1 > -1e30).sum() == 1
    # partial tie: [3, 3, 1] with p=0.6 keeps both tied threes (their
    # preceding masses 0 and .468 are < .6) and excludes the third
    ft2 = np.asarray(filter_logits(
        jnp.array([[3.0, 3.0, 1.0]], jnp.float32), top_p=0.6))[0]
    assert (ft2 > -1e30).tolist()[2] is False
    assert (ft2 > -1e30).sum() == 2

    # no-op knobs and composition (top-k first, then nucleus)
    np.testing.assert_array_equal(
        np.asarray(filter_logits(logits, top_k=4, top_p=1.0)),
        np.asarray(logits))
    fb = np.asarray(filter_logits(logits, top_k=2, top_p=0.6))[0]
    assert (fb > -1e30).tolist() == [False, True, False, False]

    with _pytest.raises(ValueError, match="top_k"):
        filter_logits(logits, top_k=0)
    with _pytest.raises(ValueError, match="top_p"):
        filter_logits(logits, top_p=0.0)

    # sampling knobs alongside greedy=True (the default) are an error,
    # not silently dropped
    cfg0 = LlamaConfig.nano()
    params0 = llama_init(jax.random.PRNGKey(0), cfg0)
    with _pytest.raises(ValueError, match="greedy=False"):
        generate(params0, jnp.array([[1, 2]], jnp.int32), cfg0,
                 max_new_tokens=2, top_p=0.9)
    with _pytest.raises(ValueError, match="greedy=False"):
        # eager: the error fires at the CALL, before any iteration
        generate_stream(params0, jnp.array([[1, 2]], jnp.int32),
                        cfg0, max_new_tokens=2, top_k=4)

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    prompt = jnp.array([[5, 6, 7], [9, 8, 7]], jnp.int32)

    # top_k=1 == greedy even at high temperature
    g = np.asarray(generate(params, prompt, cfg, max_new_tokens=4))
    k1 = np.asarray(generate(params, prompt, cfg, max_new_tokens=4,
                             greedy=False, temperature=5.0, top_k=1,
                             rng=jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(g, k1)

    # streamed sampling == scanned sampling under the same rng
    rng = jax.random.PRNGKey(11)
    batch = np.asarray(generate(params, prompt, cfg, max_new_tokens=5,
                                greedy=False, temperature=0.9,
                                top_k=16, top_p=0.95, rng=rng))
    streamed = np.stack(list(generate_stream(
        params, prompt, cfg, max_new_tokens=5, greedy=False,
        temperature=0.9, top_k=16, top_p=0.95, rng=rng)), axis=1)
    np.testing.assert_array_equal(batch[:, 3:], streamed)

    # sampled tokens stay inside the top-k set of each step's logits
    from ray_tpu.models.llama import llama_forward
    seq = np.asarray(generate(params, prompt, cfg, max_new_tokens=4,
                              greedy=False, temperature=1.3, top_k=3,
                              rng=jax.random.PRNGKey(5)))
    for t in range(4):
        step_logits = np.asarray(llama_forward(
            params, jnp.asarray(seq[:, :3 + t]), cfg)[:, -1])
        topk = np.argsort(step_logits, axis=-1)[:, -3:]
        for b in range(seq.shape[0]):
            assert seq[b, 3 + t] in topk[b]


def test_t5_generation_matches_uncached_decode():
    """Encoder-decoder decode loop (t5_generate): greedy cached
    generation must equal a manual argmax rollout through the full
    uncached t5_forward; eos fill and source pad masking behave."""
    from ray_tpu.models import T5Config, t5_init
    from ray_tpu.models.t5 import t5_forward, t5_generate

    cfg = T5Config.nano()
    params = t5_init(jax.random.PRNGKey(0), cfg)
    B, S, T = 2, 7, 5
    src = jax.random.randint(jax.random.PRNGKey(1), (B, S), 2, 256)

    out = np.asarray(t5_generate(params, src, cfg, bos_id=1,
                                 max_new_tokens=T))
    assert out.shape == (B, T)

    # Manual uncached rollout: tgt grows one argmax token at a time.
    tgt = jnp.ones((B, 1), jnp.int32)
    for _ in range(T):
        logits = t5_forward(params, src, tgt, cfg)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tgt = jnp.concatenate([tgt, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, np.asarray(tgt[:, 1:]))

    # eos fill: after a row hits eos it keeps emitting eos.
    eos = int(out[0, 0])
    out_eos = np.asarray(t5_generate(params, src, cfg, bos_id=1,
                                     max_new_tokens=T, eos_id=eos))
    assert (out_eos[0] == eos).all()

    # Source pad masking changes nothing when the "pad" region is
    # marked live, but masking real tokens changes the output.
    live = jnp.ones((B, S), bool)
    out_live = np.asarray(t5_generate(params, src, cfg, bos_id=1,
                                      max_new_tokens=T, src_live=live))
    np.testing.assert_array_equal(out, out_live)
    masked = live.at[:, : S // 2].set(False)
    out_masked = np.asarray(t5_generate(params, src, cfg, bos_id=1,
                                        max_new_tokens=T,
                                        src_live=masked))
    assert not np.array_equal(out, out_masked)


def test_speculative_decode_exact_vs_greedy():
    """Speculative output must be token-identical to target-only greedy
    decode for every window size; a draft IDENTICAL to the target must
    reach acceptance rate 1.0 (regression: a fully accepted window once
    left the last draft token's K/V unwritten, corrupting later
    proposals); eos trims early like generate_stream."""
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.generate import generate
    from ray_tpu.models.speculative import speculative_generate

    target_cfg = LlamaConfig.nano()
    draft_cfg = LlamaConfig.nano(n_layers=1, dim=32, n_heads=2,
                                 n_kv_heads=1, ffn_dim=64)
    target = llama_init(jax.random.PRNGKey(0), target_cfg)
    draft = llama_init(jax.random.PRNGKey(7), draft_cfg)

    prompt = jnp.array([[3, 1, 4, 1, 5]], jnp.int32)
    ref = np.asarray(generate(target, prompt, target_cfg,
                              max_new_tokens=24, greedy=True))

    for window in (1, 3, 4, 8):
        out, stats = speculative_generate(
            target, target_cfg, draft, draft_cfg, prompt,
            max_new_tokens=24, window=window)
        np.testing.assert_array_equal(np.asarray(out), ref,
                                      err_msg=f"window={window}")
        assert stats.rounds > 0
        assert 0 <= stats.accepted <= stats.proposed

    # identical draft => every proposal accepted, far fewer rounds
    out, stats = speculative_generate(
        target, target_cfg, target, target_cfg, prompt,
        max_new_tokens=24, window=4)
    np.testing.assert_array_equal(np.asarray(out), ref)
    assert stats.acceptance_rate == 1.0, stats
    assert stats.rounds <= 5  # 24 tokens / (window+1) rounded up

    # eos: pick the 6th generated token as eos — speculative must stop
    # at its first occurrence, matching the reference prefix
    eos = int(ref[0, prompt.shape[1] + 5])
    out, _ = speculative_generate(
        target, target_cfg, draft, draft_cfg, prompt,
        max_new_tokens=24, window=4, eos_id=eos)
    out = np.asarray(out)[0]
    gen_part = list(out[prompt.shape[1]:])
    assert eos in gen_part
    first = gen_part.index(eos)
    assert first == len(gen_part) - 1  # nothing after eos
    np.testing.assert_array_equal(
        out[:prompt.shape[1] + first + 1],
        ref[0, :prompt.shape[1] + first + 1])

    # batched prompts (the historical B=1 restriction is lifted): each
    # row matches its own solo greedy run
    batch = jnp.array([[3, 1, 4, 1, 5], [2, 7, 1, 8, 2]], jnp.int32)
    out, stats = speculative_generate(
        target, target_cfg, draft, draft_cfg, batch,
        max_new_tokens=12, window=4)
    out = np.asarray(out)
    for b in range(2):
        ref_b = np.asarray(generate(target, batch[b:b + 1], target_cfg,
                                    max_new_tokens=12, greedy=True))
        np.testing.assert_array_equal(out[b:b + 1], ref_b,
                                      err_msg=f"row={b}")
    assert stats.rounds > 0

    import pytest

    with pytest.raises(ValueError):
        speculative_generate(target, target_cfg, draft, draft_cfg,
                             prompt, window=0)


def test_llama_streaming_matches_batch_and_ragged():
    """generate_stream yields exactly generate()'s tokens — dense and
    ragged (left-padded) — with the donated-cache stepwise path."""
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.models.generate import (generate, generate_stream,
                                         pad_prompts)

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray([[3, 4, 5], [6, 7, 8]], jnp.int32)
    batch = np.asarray(generate(params, prompt, cfg, max_new_tokens=5))
    streamed = np.stack(list(generate_stream(
        params, prompt, cfg, max_new_tokens=5)), axis=1)
    np.testing.assert_array_equal(streamed, batch[:, -5:])

    padded, live = pad_prompts([[5, 6, 7], [9, 8, 7, 6, 5, 4]])
    batch_r = np.asarray(generate(params, jnp.asarray(padded), cfg,
                                  max_new_tokens=4,
                                  prompt_live=jnp.asarray(live)))
    streamed_r = np.stack(list(generate_stream(
        params, jnp.asarray(padded), cfg, max_new_tokens=4,
        prompt_live=jnp.asarray(live))), axis=1)
    np.testing.assert_array_equal(streamed_r, batch_r[:, -4:])


# -- what a rematerialised layer keeps: the flash kernel's output and row
# statistics (PR 32) -----------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else [v]):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _walk(jaxpr):
    """Every equation of a jaxpr, nested ones (scan, shard_map, remat,
    custom_vjp, pjit) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _kernels(jaxpr):
    """Names of the Pallas calls in a jaxpr, nested ones included."""
    return [str(e.params["name"]) for e in _walk(jaxpr)
            if e.primitive.name == "pallas_call"]


def _kernels_by_scan_body(fn, *args):
    """The kernels inside each `scan` of `fn`'s jaxpr, in program order:
    a layer stack under `value_and_grad` gives the forward pass's body,
    then the backward pass's. `fn` may be a jaxpr already traced."""
    traced = fn if hasattr(fn, "jaxpr") else jax.make_jaxpr(fn)(*args)
    return [sorted(_kernels(e.params["jaxpr"].jaxpr))
            for e in _walk(traced.jaxpr) if e.primitive.name == "scan"]


def _flash_names_in(jaxpr):
    from ray_tpu.ops.flash_attention import FLASH_RESIDUAL_NAMES

    return [e.params["name"] for e in _walk(jaxpr)
            if e.primitive.name == "name"
            and e.params["name"] in FLASH_RESIDUAL_NAMES]


def _plain_checkpoint(monkeypatch):
    """The layer under `jax.checkpoint` with no policy: what every
    policy ran before the kernel's residuals were kept."""
    from ray_tpu.models import llama, moe

    for mod in (llama, moe):
        monkeypatch.setattr(mod, "_layer_checkpoint",
                            lambda fn, policy: jax.checkpoint(fn))


def _assert_bitwise(a, b, what):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=what), a, b)


def _flash_llama(policy="full"):
    from ray_tpu.models import LlamaConfig, llama_init

    cfg = LlamaConfig.nano(remat=True, remat_policy=policy,
                           attn_impl="flash", max_seq_len=32)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 33),
                                          0, cfg.vocab_size)}
    return cfg, params, batch


@pytest.mark.parametrize("policy", ["full", "save:qkv", "save_dots"])
def test_remat_layer_never_reruns_the_flash_forward(policy, monkeypatch):
    """Under every policy the layer's checkpoint keeps the kernel's
    output and row statistics: the backward scan body holds the two
    backward kernels and NO forward kernel (the plain checkpoint re-ran
    it), and loss and every gradient leaf are bitwise what the plain
    checkpoint gives: the same kernels on the same operands, one of
    them once instead of twice."""
    from ray_tpu.models import llama_loss

    cfg, params, batch = _flash_llama(policy)

    def fn():       # a new function each time: JAX caches traces by it
        return jax.value_and_grad(lambda p: llama_loss(p, batch, cfg))

    assert _kernels_by_scan_body(fn(), params) == [
        ["flash_fwd"], ["flash_bwd_dkv", "flash_bwd_dq"]]
    got = jax.jit(fn())(params)
    with monkeypatch.context() as mp:
        _plain_checkpoint(mp)
        assert _kernels_by_scan_body(fn(), params) == [
            ["flash_fwd"], ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]]
        want = jax.jit(fn())(params)
    _assert_bitwise(got, want, policy)


def test_remat_layer_keeps_flash_residuals_inside_shard_map(monkeypatch):
    """The train step's form: the kernel sits in a `shard_map` over an
    `{"fsdp": 4}` mesh, which hands the checkpoint's policy to its body."""
    from ray_tpu.models import llama_loss
    from ray_tpu.ops.attention import spmd_mesh_scope
    from ray_tpu.parallel import create_mesh

    cfg, params, batch = _flash_llama()
    mesh = create_mesh({"fsdp": 4}, jax.devices()[:4])

    def fn():
        def step(p):
            with spmd_mesh_scope(mesh):
                return jax.value_and_grad(
                    lambda q: llama_loss(q, batch, cfg))(p)
        return step

    bodies = jax.make_jaxpr(fn())(params)
    assert any(e.primitive.name == "shard_map" for e in _walk(bodies.jaxpr))
    assert _kernels_by_scan_body(bodies) == [
        ["flash_fwd"], ["flash_bwd_dkv", "flash_bwd_dq"]]
    got = jax.jit(fn())(params)
    with monkeypatch.context() as mp:
        _plain_checkpoint(mp)
        assert len(_kernels_by_scan_body(fn(), params)[1]) == 3
        want = jax.jit(fn())(params)
    _assert_bitwise(got, want, "fsdp4")


def test_moe_remat_layer_never_reruns_the_flash_forward(monkeypatch):
    """`moe_forward` takes the same helper: its layer holds the same
    attention call."""
    from ray_tpu.models.moe import MoeConfig, moe_init, moe_loss

    cfg = MoeConfig.nano_moe(remat=True, attn_impl="flash", max_seq_len=32,
                             dtype=jnp.float32)
    params = moe_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 33),
                                          0, cfg.vocab_size)}

    def fn():
        return jax.value_and_grad(lambda p: moe_loss(p, batch, cfg))

    assert _kernels_by_scan_body(fn(), params) == [
        ["flash_fwd"], ["flash_bwd_dkv", "flash_bwd_dq"]]
    got = jax.jit(fn())(params)
    with monkeypatch.context() as mp:
        _plain_checkpoint(mp)
        assert len(_kernels_by_scan_body(fn(), params)[1]) == 3
        want = jax.jit(fn())(params)
    _assert_bitwise(got, want, "moe")


@pytest.mark.parametrize("program", ["flash_forward", "reference_train",
                                     "engine_decode", "engine_prefill"])
def test_flash_residual_names_exist_only_where_the_kernel_is_differentiated(
        program):
    """The two names are made in the kernel's VJP forward rule and
    nowhere else: a forward-only kernel call, a train step on the
    reference attention and the serving engine's programs trace no
    `name` of theirs, so nothing of this reaches a serving cell."""
    from ray_tpu.models import (DecodeEngine, LlamaConfig, engine, llama_init,
                                llama_loss)
    from ray_tpu.ops.flash_attention import flash_attention

    if program == "flash_forward":
        q = jnp.ones((1, 2, 16, 8), jnp.float32)
        traced = jax.make_jaxpr(functools.partial(
            flash_attention, interpret=True))(q, q, q)
    elif program == "reference_train":
        cfg = LlamaConfig.nano(remat=True, attn_impl="reference")
        params = llama_init(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": jnp.zeros((2, 9), jnp.int32)}
        traced = jax.make_jaxpr(jax.grad(
            lambda p: llama_loss(p, batch, cfg)))(params)
    else:
        cfg = LlamaConfig.nano()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                           kv_block_tokens=4)
        z = jnp.zeros((eng.B,), jnp.int32)
        live = jnp.ones((eng.B,), bool)
        if program == "engine_decode":
            traced = engine._decode_multi_paged.trace(
                params, eng._pool_k, eng._pool_v, jnp.asarray(eng._bt),
                eng._last_logits, z, live, z, z, jnp.asarray(eng._row_keys),
                live, 1.0, cfg, 2, True, None, None, None)
        else:
            traced = engine._prefill_rows_paged.trace(
                params, jnp.zeros((2, 8), jnp.int32), eng._pool_k,
                eng._pool_v, eng._last_logits, jnp.asarray(eng._bt),
                jnp.arange(2), z, z, cfg)
    assert _flash_names_in(traced.jaxpr) == []


def test_flash_residual_names_are_made_where_the_kernel_is_differentiated():
    """The check above can see a name: the differentiated kernel has
    both, the statistic without the kernel's trailing axis."""
    from ray_tpu.models import llama_loss
    from ray_tpu.ops.flash_attention import FLASH_RESIDUAL_NAMES

    cfg, params, batch = _flash_llama()
    traced = jax.make_jaxpr(jax.grad(
        lambda p: llama_loss(p, batch, cfg)))(params)
    assert sorted(set(_flash_names_in(traced.jaxpr))) \
        == sorted(FLASH_RESIDUAL_NAMES)
    lse = [e.outvars[0].aval.shape for e in _walk(traced.jaxpr)
           if e.primitive.name == "name"
           and e.params["name"] == "flash_lse"]
    assert lse and all(shape == (4, cfg.n_heads, 32) for shape in lse)

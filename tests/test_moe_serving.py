"""Serving a sparse model (`MoeConfig`: experts + OLMoE's q/k norm) through
the cached paths: `generate._layer_body` reads the family from the config
in one place, `moe.moe_ffn_dropless` computes every assignment whatever
the routing, and the engine's expert-layer counters count live rows only.

Everything here is float32 at nano widths on the CPU. The engine-against-
solo identities for this family are cases of the identity tests in
tests/test_engine.py, test_engine_horizon.py and test_engine_paged.py.
"""

import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.reference import olmoe_sparse  # noqa: E402
from ray_tpu.models import (LlamaConfig, MoeConfig, llama_init,  # noqa: E402
                            moe_init)
from ray_tpu.models import engine as engine_mod  # noqa: E402
from ray_tpu.models import moe  # noqa: E402
from ray_tpu.models.engine import DecodeEngine  # noqa: E402
from ray_tpu.models.generate import (forward_cached, generate,  # noqa: E402
                                     init_cache)
from ray_tpu.models.lora import LoraConfig  # noqa: E402


def _cfg(n_experts=8, top_k=2, **kw):
    kw = {"qk_norm": True, "norm_topk_prob": False, "dtype": jnp.float32,
          "remat": False, "max_seq_len": 512, **kw}
    return MoeConfig.nano_moe(n_experts=n_experts, top_k=top_k, **kw)


def _model(cfg):
    """The reference's view of a config: Hugging Face key names."""
    return {"num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.top_k,
            "norm_topk_prob": cfg.norm_topk_prob}


def _cached_logits(params, cfg, toks, n_prefill):
    """Prefill toks[:, :n_prefill], then decode the rest one token at a
    time through the cache: logits [B, S, V]."""
    B, S = toks.shape
    step = jax.jit(lambda t, c, i: forward_cached(params, t, c, i, cfg))
    out, cache = step(toks[:, :n_prefill], init_cache(cfg, B, S), 0)
    outs = [out]
    for i in range(n_prefill, S):
        lg, cache = step(toks[:, i:i + 1], cache, i)
        outs.append(lg)
    return jnp.concatenate(outs, axis=1)


def _solo(params, cfg, prompt, n):
    out = np.asarray(generate(params, jnp.asarray([prompt], jnp.int32),
                              cfg, max_new_tokens=n))
    return out[0, len(prompt):].tolist()


# -- (a) the reference against the cached path --------------------------------

@pytest.mark.parametrize("n_experts,top_k", [(8, 2), (64, 8)])
@pytest.mark.parametrize("seq", [24, 300], ids=["dense_regime",
                                               "sorted_regime"])
def test_reference_matches_prefill_then_decode(n_experts, top_k, seq):
    """`olmoe_sparse` (no cache, no sorting, every expert on every
    position) against solo generate's programs: prefill, then decode
    through the cache. Both are float32, so routing is IDENTICAL (no
    8th/9th flip) and the tolerance is arithmetic only: 1e-4 on logits
    of a few units. seq 300 prefills 592 tokens at once, past
    DENSE_EXPERTS_MAX_TOKENS, so the sorted ragged regime is the one
    compared there; decode always runs the all-experts regime."""
    cfg = _cfg(n_experts, top_k)
    params = moe_init(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, seq), 0,
                              cfg.vocab_size)
    assert (2 * (seq - 4) > moe.DENSE_EXPERTS_MAX_TOKENS) == (seq == 300)
    want, gaps = olmoe_sparse.logits_and_gaps(params, toks, _model(cfg))
    got = _cached_logits(params, cfg, toks, seq - 4)
    assert gaps.shape == (cfg.n_layers, 2, seq) and float(gaps.min()) >= 0
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_mixtral_style_renormalised_weights_match_reference():
    cfg = _cfg(norm_topk_prob=True)
    params = moe_init(jax.random.PRNGKey(3), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, 20), 0, 256)
    want = olmoe_sparse.logits(params, toks, _model(cfg))
    np.testing.assert_allclose(_cached_logits(params, cfg, toks, 12), want,
                               atol=1e-4, rtol=0)
    other = olmoe_sparse.logits(
        params, toks, dict(_model(cfg), norm_topk_prob=False))
    assert float(jnp.abs(other - want).max()) > 1e-2


# -- (c) dropless under adversarial routing ------------------------------------

def _loop_ffn(x, layer, top_k, renorm=False):
    """Ten lines of numpy: every token, its top-k experts, one at a time."""
    x = np.asarray(x, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in layer.items()}
    out = np.zeros_like(x)
    for t, u in enumerate(x):
        logit = u @ w["w_router"]
        p = np.exp(logit - logit.max())
        p /= p.sum()
        top = sorted(range(len(p)), key=lambda e: (-p[e], e))[:top_k]
        scale = sum(p[e] for e in top) if renorm else 1.0
        for e in top:
            a = u @ w["we_gate"][e]
            out[t] += p[e] / scale * (
                (a / (1 + np.exp(-a)) * (u @ w["we_up"][e]))
                @ w["we_down"][e])
    return out


@pytest.mark.parametrize("tokens", [16, 600], ids=["dense_regime",
                                                  "sorted_regime"])
@pytest.mark.parametrize("routing", ["one_pair", "ties", "random"])
def test_dropless_for_any_routing(tokens, routing):
    """No capacity: with every token on the same two experts (`one_pair`:
    positive activations and a router whose columns 5 and 2 sum them),
    with all probabilities equal (`ties`: a zero router, the lowest
    indices win), and at random, the layer equals the loop that visits
    each token's experts one by one, and it computed at least as many
    token-expert rows as there were assignments."""
    cfg = _cfg()
    layer = jax.tree_util.tree_map(
        lambda a: a[0], moe_init(jax.random.PRNGKey(5), cfg)["layers"])
    x = jax.random.normal(jax.random.PRNGKey(6), (1, tokens, cfg.dim))
    if routing == "one_pair":
        x = jnp.abs(x)
        layer["w_router"] = jnp.zeros_like(layer["w_router"]) \
            .at[:, 5].set(1.0).at[:, 2].set(0.5)
    elif routing == "ties":
        layer["w_router"] = jnp.zeros_like(layer["w_router"])
    live = jnp.ones((1, tokens), bool)
    out, stats = jax.jit(
        lambda x, layer: moe.moe_ffn_dropless(x, layer, cfg, live))(x, layer)
    np.testing.assert_allclose(out[0], _loop_ffn(x[0], layer, cfg.top_k),
                               atol=2e-4, rtol=0)
    assign, rows, hit = (int(v) for v in stats)
    assert assign == tokens * cfg.top_k and rows >= assign
    assert hit == (2 if routing != "random" else hit) and 1 <= hit <= 8


def test_engine_serves_a_collapsed_router_and_counts_it():
    """The whole path under a collapsed router (zero: every token on
    experts 0 and 1, by ties): engine == solo generate == reference, and
    no snapshot of the counters shows fewer rows than assignments."""
    cfg = _cfg()
    params = moe_init(jax.random.PRNGKey(7), cfg)
    params["layers"]["w_router"] = jnp.zeros_like(
        params["layers"]["w_router"])
    prompts = [[5, 6, 7, 8, 9], list(range(20, 33)), [3, 1]]
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32)
    ids = [eng.submit(p, 5) for p in prompts]
    while eng.pending():
        eng.step()
        s = eng.stats()
        assert s["moe_rows_computed_total"] >= s["moe_assignments_total"]
    for rid, p in zip(ids, prompts):
        got = eng.pop_result(rid)
        assert got == _solo(params, cfg, p, 5)
        seq = jnp.asarray(p + got, jnp.int32)
        assert float(olmoe_sparse.below_best(
            params, seq, _model(cfg))[len(p) - 1:].max()) <= 1e-4
    s = eng.stats()
    # decode: 2 experts hit in each of the expert-layer runs that had a
    # live row; never more than 2
    assert 0 < s["moe_decode_experts_hit_total"] \
        <= 2 * s["moe_decode_layer_steps_total"]


# -- (d) dead slots and padded positions ---------------------------------------

def test_dead_rows_change_no_live_row_and_no_counter():
    """Rows are independent: the live rows' outputs with garbage rows
    beside them equal the live rows computed alone, in both regimes, and
    the counters count the live rows only."""
    cfg = _cfg()
    layer = jax.tree_util.tree_map(
        lambda a: a[0], moe_init(jax.random.PRNGKey(8), cfg)["layers"])
    for rows, chunk in ((8, 1), (4, 160)):
        x = jax.random.normal(jax.random.PRNGKey(9), (rows, chunk, cfg.dim))
        live = jnp.arange(rows)[:, None] % 2 == 0
        live = jnp.broadcast_to(live, (rows, chunk))
        junk = jnp.where(live[..., None], x, 1e4 * x[::-1])
        f = jax.jit(lambda x, lv: moe.moe_ffn_dropless(x, layer, cfg, lv))
        want, _ = f(x, jnp.ones_like(live))
        got, stats = f(junk, live)
        np.testing.assert_allclose(got[::2], want[::2], atol=1e-5, rtol=0)
        assert int(stats[0]) == (rows // 2) * chunk * cfg.top_k


# -- (d2) few tokens: only the experts a live row chose are read ----------------

def _routed_layer(routing, cfg, rows):
    """A layer, rows `x` and the (expert, expert) each row chooses: the
    router is solved for (`pinv`: 32 rows of 64 values have full rank) so
    that ``x @ w_router`` IS a table of logits with the two chosen experts
    on top."""
    e = cfg.n_experts
    layer = jax.tree_util.tree_map(
        lambda a: a[0], moe_init(jax.random.PRNGKey(21), cfg)["layers"])
    x = jax.random.normal(jax.random.PRNGKey(22), (rows, 1, cfg.dim))
    g = np.arange(rows)
    if isinstance(routing, np.ndarray):     # the choices themselves
        choice = routing
    elif routing == "even":
        rng = np.random.RandomState(3)
        choice = np.stack([rng.permutation(e)[:2] for _ in g])
    elif routing == "collapsed":
        choice = np.tile([7, 3], (rows, 1))
    elif routing == "every_expert":
        choice = np.stack([2 * g % e, (2 * g + 1) % e], axis=1)
    else:   # a table by hand: rows 0..7 keep to experts 0..8, the others
        #     to 9..15, so with 8 live rows seven experts have only dead
        #     rows on them
        choice = np.where((g < 8)[:, None],
                          np.stack([g % 6, 6 + g % 3], axis=1),
                          np.stack([12 + g % 4, 9 + g % 3], axis=1))
    logits = np.random.RandomState(4).uniform(-1, 1, (rows, e))
    logits[g, choice[:, 0]] = 4.0
    logits[g, choice[:, 1]] = 3.0
    layer["w_router"] = jnp.asarray(
        np.linalg.pinv(np.asarray(x[:, 0], np.float64)) @ logits,
        jnp.float32)
    return layer, x, choice


@pytest.mark.parametrize("live", ["none", "one", "8_of_32", "all", "no_mask"])
@pytest.mark.parametrize("routing", ["even", "collapsed", "every_expert",
                                     "table"])
def test_few_tokens_read_only_the_experts_a_live_row_chose(routing, live,
                                                           monkeypatch):
    """32 rows through the hit-only form (the kernel in interpret mode)
    and through the all-experts einsum on the same inputs: live rows'
    outputs agree, a dead row's expert output is zero, the form visits
    exactly the experts some LIVE row chose, in order (one chosen by dead
    rows alone is not among them), and `rows` is what `_held_hit` reports:
    experts hit x all the rows."""
    from ray_tpu.ops import hit_experts

    rows = 32
    cfg = _cfg(n_experts=16)
    layer, x, choice = _routed_layer(routing, cfg, rows)
    mask = {"none": np.zeros(rows, bool), "one": np.arange(rows) == 5,
            "8_of_32": np.arange(rows) < 8, "all": np.ones(rows, bool),
            "no_mask": np.ones(rows, bool)}[live]
    lv = None if live == "no_mask" else jnp.asarray(mask)[:, None]
    seen = {}

    def spy(x, cw, ids, n_hit, *stacks, **kw):
        seen.update(ids=np.asarray(ids), n=int(n_hit), cw=np.asarray(cw))
        got = hit_experts.hit_experts_ffn(x, cw, ids, n_hit, *stacks, **kw)
        np.testing.assert_allclose(
            got, hit_experts.hit_experts_ffn_reference(
                x, cw, ids, n_hit, *stacks), atol=1e-5, rtol=0)
        return got

    monkeypatch.setattr(moe, "hit_experts_ffn", spy)
    assert moe.hit_experts_only(cfg, rows)
    got, stats = moe.moe_ffn_dropless(x, layer, cfg, lv)
    monkeypatch.setattr(moe, "HIT_EXPERTS_MAX_TOKENS", 0)
    assert not moe.hit_experts_only(cfg, rows)
    want, want_stats = moe.moe_ffn_dropless(x, layer, cfg, lv)

    chosen = sorted(set(choice[mask].reshape(-1).tolist()))
    assert seen["ids"][:seen["n"]].tolist() == chosen
    assert not seen["cw"][seen["n"]:].any()
    np.testing.assert_allclose(got[mask], want[mask], atol=1e-5, rtol=0)
    assert not np.asarray(got)[~mask].any()
    if lv is None:
        assert stats is None and want_stats is None
    else:
        assert stats.tolist() == [int(mask.sum()) * cfg.top_k,
                                  len(chosen) * rows, len(chosen)]
        assert want_stats.tolist() == [int(mask.sum()) * cfg.top_k,
                                       cfg.n_experts * rows, len(chosen)]


@pytest.mark.parametrize("live", ["none", "one", "8_of_32", "all", "no_mask"])
@pytest.mark.parametrize("routing", ["even", "collapsed", "every_held",
                                     "none_held"])
def test_few_tokens_over_a_held_range_go_through_the_hit_kernel(
        routing, live, monkeypatch):
    """32 rows of a layer that HOLDS experts 4..11 of 16, the third layer
    of stacks that hold three (``first`` = 16): the kernel (interpret
    mode) is handed exactly the held experts some LIVE row chose, as their
    rows in the stacks, and equals its reference and the `cond` form
    (`_held_hit`) on the live rows; a dead row's expert output is zero and
    its input changes no live row's; the counters are the `cond` form's
    over live rows (that form also reads an expert only a dead row
    chose)."""
    from ray_tpu.ops import hit_experts

    rows, lo, eh, li = 32, 4, 8, 2
    cfg = _cfg(n_experts=16, held_experts=(lo, lo + eh))
    g = np.arange(rows)
    away = np.asarray([0, 1, 2, 3, 12, 13, 14, 15])
    layer, x, choice = _routed_layer(
        {"even": "even",
         "collapsed": np.tile([7, 3], (rows, 1)),           # 3 is not held
         "every_held": np.stack([lo + g % eh, away[g % 8]], 1),
         "none_held": np.tile([0, 13], (rows, 1))}[routing], cfg, rows)
    for i, n in enumerate(("we_gate", "we_up", "we_down")):
        layer[n] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(30 + i), (3 * eh,) + layer[n].shape[1:])
    mask = {"none": np.zeros(rows, bool), "one": g == 5, "8_of_32": g < 8,
            "all": np.ones(rows, bool), "no_mask": np.ones(rows, bool)}[live]
    lv = None if live == "no_mask" else jnp.asarray(mask)[:, None]
    seen = {}

    def spy(x, cw, ids, n_hit, *stacks, **kw):
        seen.update(ids=np.asarray(ids), n=int(n_hit), cw=np.asarray(cw))
        got = hit_experts.hit_experts_ffn(x, cw, ids, n_hit, *stacks, **kw)
        np.testing.assert_allclose(
            got, hit_experts.hit_experts_ffn_reference(
                x, cw, ids, n_hit, *stacks), atol=1e-5, rtol=0)
        return got

    def run(x):
        return moe.moe_ffn_dropless(x, layer, cfg, lv,
                                    expert_stack_layer=li)

    monkeypatch.setattr(moe, "hit_experts_ffn", spy)
    assert moe.held_hit_kernel(cfg, rows) \
        and not moe.hit_experts_only(cfg, rows)
    got, stats = run(x)
    junk, _ = run(jnp.where(mask[:, None, None], x, 1e3 * x[::-1]))
    monkeypatch.setattr(moe, "HIT_EXPERTS_MAX_TOKENS", 0)
    assert not moe.held_hit_kernel(cfg, rows)
    want, want_stats = run(x)

    def held(c):
        return sorted({e for e in c.reshape(-1).tolist()
                       if lo <= e < lo + eh})

    chosen = held(choice[mask])
    assert seen["ids"][:seen["n"]].tolist() == \
        [li * eh + e - lo for e in chosen]
    assert not seen["cw"][seen["n"]:].any()
    np.testing.assert_allclose(got[mask], want[mask], atol=1e-5, rtol=0)
    np.testing.assert_array_equal(junk[mask], got[mask])
    assert not np.asarray(got)[~mask].any()
    if lv is None:
        assert stats is None and want_stats is None
    else:
        landed = int(np.isin(choice[mask], chosen).sum())
        assert stats.tolist() == [int(mask.sum()) * cfg.top_k,
                                  len(chosen) * rows, len(chosen), landed]
        assert want_stats.tolist() == [
            int(mask.sum()) * cfg.top_k, len(held(choice)) * rows,
            len(chosen), landed]


def test_a_prefill_groups_padding_row_is_read_though_counted_once():
    """Three short prompts admitted together prefill as a group of four:
    the fourth row repeats the third, is left out of the counters, and
    writes its K/V onto its twin's. At these few tokens the expert layer
    zeroes the rows nobody reads, so the repeat must count as read (a
    zeroed twin wrote other K/V over the third prompt's: caught as wrong
    tokens). Same tokens as solo generate."""
    cfg = _cfg()
    params = moe_init(jax.random.PRNGKey(12), cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, size=n).tolist() for n in (5, 7, 6)]
    assert moe.hit_experts_only(cfg, 4 * 8)
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=64,
                       kv_block_tokens=8, decode_horizon=2)
    ids = [eng.submit(p, 6) for p in prompts]
    out = eng.run()
    assert eng.stats()["prefill_dispatches"] == 1
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, 6)
    assert eng.stats()["moe_assignments_total"] == \
        (sum(map(len, prompts)) + 3 * 5) * cfg.top_k * cfg.n_layers


@pytest.mark.parametrize("slots,bucket", [(1, False), (4, True)])
def test_counters_count_live_tokens_only(slots, bucket):
    """One request of 5 prompt tokens and 4 new ones, alone in an engine
    of 1 slot without length buckets, or of 4 slots (3 dead) with the
    prompt padded to its bucket of 8: the same tokens and the same live
    assignments, (5 + 3) tokens x top_k x layers (the 4th token is
    sampled and never fed); only the rows computed differ."""
    cfg = _cfg()
    params = moe_init(jax.random.PRNGKey(10), cfg)
    prompt = [9, 8, 7, 6, 5]
    eng = DecodeEngine(params, cfg, batch_slots=slots, max_len=32,
                       bucket_lens=bucket, decode_horizon=2)
    rid = eng.submit(prompt, 4)
    out = eng.run()
    assert out[rid] == _solo(params, cfg, prompt, 4)
    s = eng.stats()
    assert s["moe_assignments_total"] == 8 * cfg.top_k * cfg.n_layers
    assert s["moe_rows_computed_total"] >= s["moe_assignments_total"]
    assert s["moe_decode_layer_steps_total"] % cfg.n_layers == 0


def test_sorted_regime_through_the_engine():
    """Four prompts of 150 tokens admitted together prefill as one
    [4, 256] chunk: 1024 tokens, past DENSE_EXPERTS_MAX_TOKENS, so the
    engine's paged prefill program runs the sorted ragged regime. Same
    tokens as solo generate, and the prefill computed exactly
    tokens x top_k rows a layer (no all-expert rows)."""
    cfg = _cfg()
    params = moe_init(jax.random.PRNGKey(11), cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, size=150).tolist() for _ in range(4)]
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=256, kv_block_tokens=32, decode_horizon=1)
    ids = [eng.submit(p, 1) for p in prompts]
    out = eng.run()
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, 1)
    s = eng.stats()
    assert s["prefill_dispatches"] == 1
    # a decode step multiplies its 4 rows by the experts that were hit
    decode_rows = s["moe_decode_experts_hit_total"] * 4
    assert s["moe_rows_computed_total"] - decode_rows \
        == 4 * 256 * cfg.top_k * cfg.n_layers
    assert s["moe_assignments_total"] == 4 * 150 * cfg.top_k * cfg.n_layers


# -- (e) a dense config traces what it traced ----------------------------------

def _lowered_dense_programs():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32, kv_block_tokens=4)
    B = eng.B
    z = jnp.zeros((B,), jnp.int32)
    decode = engine_mod._decode_multi_paged.lower(
        params, eng._pool_k, eng._pool_v, jnp.asarray(eng._bt),
        eng._last_logits, z, jnp.ones((B,), bool), z, z,
        jnp.asarray(eng._row_keys), jnp.ones((B,), bool), 1.0, cfg, 2,
        True, None, None, None)
    prefill = engine_mod._prefill_rows_paged.lower(
        params, jnp.zeros((2, 8), jnp.int32), eng._pool_k, eng._pool_v,
        eng._last_logits, jnp.asarray(eng._bt), jnp.arange(2), z[:2],
        z[:2], cfg)
    return {"decode": decode.as_text(debug_info=True),
            "prefill": prefill.as_text(debug_info=True)}


def test_dense_config_lowers_without_any_sparse_part():
    """The seam costs a dense model nothing: its lowered decode and
    prefill programs (names of scopes included) hold no `moe_` scope, no
    q/k norm and no router, and the token block keeps its [H, B] shape
    (no counter rows). That the text equals the parent commit's was
    checked once by hand (CHANGES.md, PR 26)."""
    programs = _lowered_dense_programs()
    for name, text in programs.items():
        assert "mlp" in text and "attn_qkv" in text, name
        for word in ("moe_router", "moe_dispatch", "moe_experts",
                     "q_norm", "k_norm", "ragged"):
            assert word not in text, (name, word)
    assert "tensor<2x2xi32>" in programs["decode"]


def test_sparse_config_lowers_with_its_scopes():
    cfg = _cfg()
    params = moe_init(jax.random.PRNGKey(0), cfg)
    text = jax.jit(lambda p, t, c: forward_cached(p, t, c, 0, cfg)).lower(
        params, jnp.zeros((1, 8), jnp.int32),
        init_cache(cfg, 1, 8)).as_text(debug_info=True)
    for scope in ("moe_router", "moe_dispatch", "moe_experts"):
        assert scope in text
    assert "/mlp/" not in text


# -- (f) what an MoeConfig refuses ---------------------------------------------

def test_refused_combinations_name_their_option():
    cfg = _cfg()
    params = moe_init(jax.random.PRNGKey(0), cfg)
    dense = LlamaConfig.nano()
    with pytest.raises(ValueError, match="lora="):
        DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                     lora=LoraConfig(rank=2, targets=("wq", "w_up")))
    with pytest.raises(ValueError, match="tp="):
        DecodeEngine(params, cfg, batch_slots=2, max_len=32, tp=2)
    with pytest.raises(ValueError, match="draft_cfg="):
        DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                     draft_params=llama_init(jax.random.PRNGKey(1), dense),
                     draft_cfg=dense)
    with pytest.raises(ValueError, match="draft_cfg="):
        DecodeEngine(llama_init(jax.random.PRNGKey(1), dense), dense,
                     batch_slots=2, max_len=32, draft_params=params,
                     draft_cfg=cfg)


def test_attention_lora_and_same_family_draft_are_served():
    """What does not read the dense feed-forward's names still works:
    LoRA on attention targets, and a draft of the target's own family
    (speculative rounds emit the target's own greedy chain)."""
    cfg = _cfg()
    params = moe_init(jax.random.PRNGKey(0), cfg)
    prompt = [4, 5, 6, 7]
    want = _solo(params, cfg, prompt, 6)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       lora=LoraConfig(rank=2, targets=("wq", "wo")))
    rid = eng.submit(prompt, 6)
    assert eng.run()[rid] == want
    d_cfg = _cfg(n_layers=1)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       draft_params=moe_init(jax.random.PRNGKey(2), d_cfg),
                       draft_cfg=d_cfg, spec_window=2)
    rid = eng.submit(prompt, 6)
    assert eng.run()[rid] == want


# -- the preset ----------------------------------------------------------------

def test_olmoe_preset_counts_its_parameters():
    cfg = MoeConfig.olmoe_1b_7b()
    assert (cfg.n_experts, cfg.top_k, cfg.ffn_dim, cfg.head_dim) \
        == (64, 8, 1024, 128)
    assert cfg.qk_norm and not cfg.norm_topk_prob
    assert cfg.num_params() == 6_919_161_856        # 6.92 B
    assert cfg.active_params() == 1_282_017_280     # 1.28 B
    shapes = jax.eval_shape(lambda k: moe_init(k, MoeConfig.olmoe_1b_7b(
        n_layers=1)), jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == MoeConfig.olmoe_1b_7b(n_layers=1).num_params()

"""Serving an `MlaConfig` with `layer_types` (latent attention of TWO
geometries in one stack: selected full layers beside window layers with
their own head count, ranks, rotary base and scale, a head-wise gate and
rescaled latents: the dots3-note shape) through the one engine: prefill
then decode through the latent and index planes AND the window plane gives
the LOGITS of the plain float32 reference's full forward
(benchmark/reference/dots3_note.py).

Everything here is float32 at nano widths on the CPU: 5 layers F F S S S
(1 dense, 4 expert), hidden 64; full layers of 4 heads, latent 32 + 8
rotary, an indexer that keeps 16 slots; window layers of 2 heads, latent
40 + 8 rotary, a window of 13 slots; 16 routed experts in one group, 4 a
token, experts [0, 4) held here; blocks of 8 tokens, chunks of 16.
"""

import dataclasses
import math
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.reference import dots3_note as ref  # noqa: E402
from ray_tpu.models import MlaConfig, mla_init  # noqa: E402
from ray_tpu.models import mla  # noqa: E402
from ray_tpu.models.engine import DecodeEngine  # noqa: E402
from ray_tpu.models.lora import LoraConfig  # noqa: E402

SWA = dict(sliding_window=13, swa_n_heads=2, swa_q_lora_rank=24,
           swa_kv_lora_rank=40, swa_qk_nope_head_dim=24,
           swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=5e4)
CFG = MlaConfig.nano_mla(
    n_layers=5, layer_types=("full", "full", "window", "window", "window"),
    attn_gate=True, lora_rescale=True, n_group=1, topk_group=1,
    rope_scaling=None, rope_theta=8e7, routed_scaling_factor=1.0,
    norm_eps=1e-5, held_experts=(0, 4), **SWA)
T, CHUNK, TOL = 8, 16, 5e-5
_KINDS = {"full": "full_attention", "window": "sliding_attention"}


def model_of(cfg):
    """The reference's view of a config: the published key names."""
    return {
        "hidden_size": cfg.dim, "num_attention_heads": cfg.n_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "swa_num_attention_heads": cfg.swa_n_heads,
        "swa_q_lora_rank": cfg.swa_q_lora_rank,
        "swa_kv_lora_rank": cfg.swa_kv_lora_rank,
        "swa_qk_nope_head_dim": cfg.swa_qk_nope_head_dim,
        "swa_qk_rope_head_dim": cfg.swa_qk_rope_head_dim,
        "swa_v_head_dim": cfg.swa_v_head_dim,
        "swa_rope_theta": cfg.swa_rope_theta,
        "sliding_window_size": cfg.sliding_window,
        "apply_mla_qkv_lora_rescale": cfg.lora_rescale,
        "n_routed_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk, "rms_norm_eps": cfg.norm_eps,
        "first_k_dense_replace": cfg.n_dense_layers,
        "layer_types": [_KINDS[k] for k in cfg.layer_types]}


MODEL = model_of(CFG)


def bias_up(p, by=33.0):
    """The selection bias scaled up to std 0.1 (the initialiser seeds it
    small so that the experts' loads stay even)."""
    moe = {kind: dict(stack, router_bias=stack["router_bias"] * by)
           for kind, stack in p["moe"].items()}
    return dict(p, moe=moe)


@pytest.fixture(scope="module")
def params():
    return bias_up(jax.jit(mla_init, static_argnums=1)(
        jax.random.PRNGKey(0), CFG))


def engine(params, cfg=CFG, **kw):
    kw = {"batch_slots": 2, "max_len": 128, "kv_block_tokens": T,
          "prefill_chunk": CHUNK, "preempt": "recompute",
          "pipeline_depth": 1, **kw}
    return DecodeEngine(params, cfg, **kw)


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, size=n).tolist()


def served_logits(eng, prompt, n_new):
    """One request through submit/step at horizon 1: its tokens, and the
    engine's device-resident next-token logits after each token it fed."""
    rid = eng.submit(prompt, max_new_tokens=n_new)
    seen = []
    while rid not in eng.finished:
        eng.step(horizon=1)
        rows = [b for b, r in enumerate(eng.row_req)
                if r is not None and r.req_id == rid]
        if rows and rows[0] not in eng._row_prefill:
            seen.append(np.asarray(eng._last_logits[rows[0]]))
    return eng.pop_result(rid), seen


def reference_logits(params, seq, model=MODEL, held=CFG.held_experts):
    return np.asarray(ref.logits(params, jnp.asarray(seq, jnp.int32)[None],
                                 model, held))[0]


def worst_error(params, prompt, toks, seen, **kw):
    P = len(prompt)
    want = reference_logits(params, prompt + toks, **kw)
    assert len(seen) == len(toks) - 1
    return max(float(np.abs(got - want[P + j]).max())
               for j, got in enumerate(seen))


# -- the engine against the reference, logits -------------------------------

@pytest.mark.parametrize("n_prompt,n_new", [
    (5, 6),        # under the window 13 and index_topk 16 throughout
    (9, 12),       # crosses the window, then index_topk, mid-decode
    (24, 10),      # crosses both mid-chunk (the second chunk)
    (57, 30),      # four chunks, far past both: window blocks are freed
], ids=["under_both", "crosses_in_decode", "crosses_in_chunk", "long"])
def test_prefill_then_decode_gives_the_reference_logits(params, n_prompt,
                                                        n_new):
    eng = engine(params)
    prompt = prompt_of(n_prompt, seed=n_prompt)
    toks, seen = served_logits(eng, prompt, n_new)
    assert worst_error(params, prompt, toks, seen) <= TOL
    st = eng.stats()
    n = n_prompt + n_new
    # counted over the FULL layers alone (2 of 5), the window's apart
    assert st["indexer_tokens_scored_total"] % CFG.n_select_layers == 0
    assert st["indexer_tokens_scored_total"] \
        <= CFG.n_select_layers * n * (n + 1) / 2
    assert (st["indexer_tokens_selected_total"]
            < st["indexer_tokens_scored_total"]) == (n > CFG.index_topk + 1)
    # counted at dispatch (the last token's dispatch may ask one more)
    rows = int(st["swa_window_rows_total"])
    assert rows in (n_new - 1, n_new)
    assert st["swa_window_slots_total"] == sum(
        min(n_prompt + j + 1, CFG.sliding_window) for j in range(rows))
    assert (st["window_blocks_freed_total"] > 0) == (n >= T + 13)
    assert st["window_pool_peak_blocks"] <= -(-13 // T) + 1 + CHUNK // T


def test_horizon_and_batch_companions_change_no_token(params):
    work = [(prompt_of(30, seed=1), 20), (prompt_of(11, seed=2), 33),
            (prompt_of(47, seed=3), 9)]
    alone = [served_logits(engine(params), p, n)[0] for p, n in work]
    eng = engine(params, batch_slots=4, decode_horizon=4)
    rids = [eng.submit(p, max_new_tokens=n) for p, n in work]
    out = eng.run()
    assert [out[r] for r in rids] == alone


def test_window_layer_reads_what_the_same_layer_keeping_every_token_reads(
        params):
    """A window layer through the window table's few pages against the
    SAME layer handed every page of a table that released nothing, its
    mask alone doing the pruning: identical inside the window, for a chunk
    that starts mid-block behind released blocks and for a decode token."""
    g = CFG.geometry(mla.SWA)
    p = jax.tree_util.tree_map(lambda x: x[0], params["moe"]["window"])
    nb, mb = 17, 16
    pool = jax.random.normal(jax.random.PRNGKey(3), (3, nb, T, g.lanes))
    bt = jnp.arange(1, 1 + mb, dtype=jnp.int32)[None]
    for start, S in ((44, 12), (61, 1)):
        h = jax.random.normal(jax.random.PRNGKey(start), (1, S, CFG.dim))
        slots = start + jnp.arange(S)[None]
        # the engine's table: blocks wholly behind the first query's window
        # released (the null block)
        keep = jnp.arange(mb) >= (start - CFG.sliding_window + 1) // T
        got, pool_a = mla._attention_window(
            h, p, 1, pool, jnp.where(keep, bt, 0), slots, slots, CFG)
        every = dataclasses.replace(CFG, max_seq_len=999)   # a trace apart
        orig = mla._window_pages

        def all_pages(bt_w, slots, q_slots, T_, window):
            s = jnp.arange(mb * T_)[None, None, :]
            t = q_slots[:, :, None]
            return bt_w, jnp.where((s <= t) & (t - s < window), 0.0,
                                   -1e30).astype(jnp.float32), q_slots

        mla._window_pages = all_pages
        try:
            want, pool_b = mla._attention_window(
                h, p, 1, pool, bt, slots, slots, every)
        finally:
            mla._window_pages = orig
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=0)
        np.testing.assert_array_equal(np.asarray(pool_a[:, 1:]),
                                      np.asarray(pool_b[:, 1:]))


def test_window_pages_mask_the_first_live_page():
    """A row shorter than the window and a window that starts mid-block."""
    bt_w = jnp.array([[0, 0, 3, 4, 5, 0, 0, 0], [7, 0, 0, 0, 0, 0, 0, 0]],
                     jnp.int32)
    slots = jnp.array([[35], [4]])
    pages, bias, local = mla._window_pages(bt_w, slots, slots, 8, 13)
    assert pages.tolist() == [[3, 4, 5], [7, 0, 0]]
    assert local.tolist() == [[35 - 16], [4]]
    live = np.asarray(bias[:, 0] == 0)
    assert np.flatnonzero(live[0]).tolist() == list(range(23 - 16, 36 - 16))
    assert np.flatnonzero(live[1]).tolist() == [0, 1, 2, 3, 4]


# -- planes by kind -----------------------------------------------------------

def test_planes_hold_the_layers_of_their_kind():
    """F F S S S writes 2 + 2 + 3 plane layers, each at its place among its
    kind; the plan is one dense layer and one period of unlike kinds."""
    latent, index, wlatent = CFG.cache_planes()
    assert (latent.layers, index.layers, wlatent.layers) == (2, 2, 3)
    assert (latent.table, index.table, wlatent.table) == \
        ("full", "full", "window")
    assert (latent.lanes, wlatent.lanes) == (128, 128)
    dense, moe = CFG.layer_plan()
    assert (dense.kinds, dense.periods) == ((mla.MLA,), 1)
    assert moe.kinds == (mla.MLA, mla.SWA, mla.SWA, mla.SWA)
    assert (moe.periods, moe.first_layer) == (1, 1)
    p = mla_init(jax.random.PRNGKey(1), CFG)
    cache = mla.init_cache(CFG, 1, 32)
    toks = jnp.asarray([prompt_of(9, seed=4)], jnp.int32)
    _, after = mla.forward_cached(p, toks, cache, 0, CFG)
    for name, layers in (("c", 2), ("i", 2), ("w", 3)):
        written = np.asarray(jnp.abs(after[name]).sum((1, 2, 3)) > 0)
        assert written.tolist() == [True] * layers


def test_a_longer_stack_is_periods_and_what_is_left():
    """The published 46 layers: F, eleven periods F S S S, a last F."""
    kinds = ("full",) + ("full", "window", "window", "window") * 11 \
        + ("full",)
    cfg = dataclasses.replace(CFG, n_layers=46, layer_types=kinds)
    dense, moe, tail = cfg.layer_plan()
    assert (moe.periods, len(moe.kinds), moe.first_layer) == (11, 4, 1)
    assert (tail.name, tail.kinds, tail.first_layer) == \
        ("moe_tail", (mla.MLA,), 45)
    assert cfg.n_select_layers == 13 and cfg.n_window_layers == 33


def test_two_periods_and_a_tail_give_the_reference_logits():
    """F | F S F S | F: plane and expert-stack places across periods."""
    kinds = ("full", "full", "window", "full", "window", "full")
    cfg = dataclasses.replace(CFG, n_layers=6, layer_types=kinds)
    assert [s.periods for s in cfg.layer_plan()] == [1, 2, 1]
    p = mla_init(jax.random.PRNGKey(2), cfg)
    seq = prompt_of(40, seed=8)
    got, _ = mla.forward_cached(p, jnp.asarray([seq], jnp.int32),
                                mla.init_cache(cfg, 1, 64), 0, cfg)
    # the reference reads a kind's layers in stack order: the tail's after
    # the periods'
    flat = dict(p, moe={k: jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b]), p["moe"][k], p["moe_tail"][k])
        if k in p["moe_tail"] else p["moe"][k] for k in p["moe"]})
    want = reference_logits(flat, seq, model_of(cfg))[-1]
    np.testing.assert_allclose(np.asarray(got[0, 0]), want, atol=TOL,
                               rtol=0)


# -- the held shares of a layer -----------------------------------------------

def test_the_held_shares_of_a_layer_add_up_to_the_whole_layer(params):
    """4 shares of 4 experts, the shared expert counted once, against the
    uncut reference layer."""
    w = jax.tree_util.tree_map(lambda x: x[0], params["moe"]["window"])
    whole = mla_init(jax.random.PRNGKey(5),
                     dataclasses.replace(CFG, held_experts=None))
    w = dict(w, **{k: whole["moe"]["window"][k][0]
                   for k in ("we_gate", "we_up", "we_down")})
    u = jax.random.normal(jax.random.PRNGKey(6), (23, CFG.dim))
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(u, w, MODEL)
        got = sum(ref.expert_layer(
            u, dict(w, **{k: w[k][lo:lo + 4]
                          for k in ("we_gate", "we_up", "we_down")}),
            MODEL, (lo, lo + 4), shared=lo == 0) for lo in range(0, 16, 4))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6,
                               rtol=0)


# -- each `assumed` function against its equation -------------------------------

def test_assumed_1_rescale_is_sqrt_hidden_over_rank():
    assert mla.lora_rescale(5120, 1024) == pytest.approx(math.sqrt(5))
    assert mla.lora_rescale(5120, 512) == pytest.approx(math.sqrt(10))
    assert ref.lora_rescale({"apply_mla_qkv_lora_rescale": True,
                             "hidden_size": 5120}, 512) \
        == mla.lora_rescale(5120, 512)
    assert ref.lora_rescale({"apply_mla_qkv_lora_rescale": False,
                             "hidden_size": 5120}, 512) == 1.0


def test_assumed_2_gate_is_one_sigmoid_scalar_a_head():
    a = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 4))
    want = 1 / (1 + np.exp(-np.einsum("bsd,dh->bsh", a, w)))
    np.testing.assert_allclose(mla.head_gate(a, w, jnp.float32), want,
                               atol=1e-6)
    np.testing.assert_allclose(ref.head_gate(a[0], w), want[0], atol=1e-6)


def test_assumed_3_window_counts_the_querys_own_slot():
    mask = np.asarray(ref.window_mask(20, 2, 40, 13))
    assert np.flatnonzero(mask[0]).tolist() == list(range(8, 21))
    bt_w = jnp.arange(1, 6, dtype=jnp.int32)[None]
    q = jnp.array([[20, 21]])
    pages, bias, _ = mla._window_pages(bt_w, q, q, 8, 13)
    first = int(pages[0, 0] - 1) * 8
    assert (np.flatnonzero(np.asarray(bias[0, 0]) == 0) + first).tolist() \
        == list(range(8, 21))


def test_assumed_4_both_kinds_rotate_interleaved_pairs_by_their_own_base():
    for kind, theta in ((mla.MLA, 8e7), (mla.SWA, 5e4)):
        g = CFG.geometry(kind)
        want = theta ** (-np.arange(0, g.rope, 2) / g.rope)
        np.testing.assert_allclose(g.inv_freq, want, rtol=1e-6)
    assert CFG.geometry(mla.MLA).sm_scale == (16 + 8) ** -0.5
    assert CFG.geometry(mla.SWA).sm_scale == (24 + 8) ** -0.5
    x = jnp.arange(8.0)[None]
    ang = jnp.full((1, 4), 0.3)
    got = mla._rope_pairs(x, jnp.cos(ang), jnp.sin(ang), True)
    c, s = math.cos(0.3), math.sin(0.3)
    want = [x0 * c - x1 * s for x0, x1 in ((0, 1), (2, 3), (4, 5), (6, 7))] \
        + [x0 * s + x1 * c for x0, x1 in ((0, 1), (2, 3), (4, 5), (6, 7))]
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-6)


def test_assumed_5_one_group_is_the_top_k_of_all_experts():
    from ray_tpu.models import moe
    logits = jax.random.normal(jax.random.PRNGKey(4), (7, 16))
    bias = jax.random.normal(jax.random.PRNGKey(5), (16,)) * 0.1
    w, idx = moe.route_sigmoid_grouped(logits, bias, CFG)
    u = jnp.eye(16)[:7] * 0 + logits      # route() multiplies by w_router
    rw, ridx = ref.route(u, {"w_router": jnp.eye(16), "router_bias": bias},
                         MODEL)
    assert np.asarray(idx).tolist() == np.asarray(ridx).tolist()
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), atol=1e-6)


@pytest.mark.parametrize("what", ["no_gate", "no_rescale", "window_12",
                                  "swa_theta_full", "swa_scale_full",
                                  "attend_all"])
def test_each_wrong_program_fails_the_comparison(params, what, monkeypatch):
    """The right program is within `TOL` of the reference; each of these
    is a hundred times further."""
    cfg = dataclasses.replace(CFG, max_seq_len=300 + len(what))
    if what == "no_gate":
        monkeypatch.setattr(mla, "head_gate", lambda a, w, dt, scope=None:
                            jnp.ones((*a.shape[:2], w.shape[-1]), dt))
    elif what == "no_rescale":
        monkeypatch.setattr(mla, "lora_rescale", lambda dim, rank: 1.0)
    elif what == "window_12":
        cfg = dataclasses.replace(cfg, sliding_window=12)
    elif what == "swa_theta_full":
        cfg = dataclasses.replace(cfg, swa_rope_theta=cfg.rope_theta)
    elif what == "swa_scale_full":
        plain = MlaConfig.geometry
        monkeypatch.setattr(
            MlaConfig, "geometry", lambda self, kind: plain(self, kind)
            ._replace(sm_scale=plain(self, mla.MLA).sm_scale))
    elif what == "attend_all":
        cfg = dataclasses.replace(cfg, index_topk=128)
    prompt = prompt_of(57, seed=11)
    toks, seen = served_logits(engine(params, cfg), prompt, 12)
    monkeypatch.undo()
    assert worst_error(params, prompt, toks, seen) > 100 * TOL


# -- refusals, by name -----------------------------------------------------------

@pytest.mark.parametrize("kw,match", [
    ({"prefix_cache": True}, "prefix_cache=True"),
    ({"kv_quant": "int8"}, "kv_quant="),
    ({"preempt": "swap"}, "preempt='swap'"),
    ({"tp": 2}, "tp=/mesh="),
    ({"lora": LoraConfig(rank=2)}, "lora="),
    ({"draft_params": {}, "draft_cfg": CFG}, "draft_params="),
], ids=["prefix_cache", "kv_quant", "swap", "tp", "lora", "draft"])
def test_unsupported_options_are_refused_by_name(params, kw, match):
    with pytest.raises(ValueError, match=match):
        engine(params, **kw)


def test_handoff_is_refused_by_name(params):
    eng = engine(params)
    with pytest.raises(ValueError, match="hand-off"):
        eng.export_request(0)


def test_a_config_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="once a layer"):
        dataclasses.replace(CFG, layer_types=("full", "window"))
    with pytest.raises(ValueError, match="leading dense layers"):
        dataclasses.replace(CFG, layer_types=("window",) + ("full",) * 4)
    with pytest.raises(ValueError, match="window layers need"):
        dataclasses.replace(CFG, sliding_window=0)


# -- the family this one grew from is as it was ----------------------------------

def deepseek_program_sums(mla, MlaConfig, mla_init):
    """sha256 of the lowered text of a DeepSeek-shaped nano config's
    initialiser, prefill chunk and decode token (`tools/lowered_text_sums`
    does this for the cell's programs at their real shapes, for a v5e)."""
    import hashlib
    import re

    cfg = MlaConfig.nano_mla(held_experts=(0, 4))
    key = jax.random.PRNGKey(0)
    p = jax.eval_shape(lambda k: mla_init(k, cfg), key)
    cache = jax.eval_shape(lambda: mla.init_cache(cfg, 2, 64))
    texts = [jax.jit(lambda k: mla_init(k, cfg)).lower(key).as_text()]
    for S in (20, 1):
        toks = jax.ShapeDtypeStruct((2, S), jnp.int32)
        texts.append(jax.jit(
            lambda p, t, c: mla.forward_cached(p, t, c, 20, cfg)
        ).lower(p, toks, cache).as_text())
    return [hashlib.sha256(re.sub(r"loc\([^)]*\)", "", t).encode())
            .hexdigest()[:16] for t in texts]


def test_a_deepseek_config_lowers_to_the_text_it_lowered_to():
    """An `MlaConfig` without `layer_types` (DeepSeek's) is one stack a
    segment, planes of every layer, no gate, and its programs are the
    parent commit's BIT FOR BIT: the lowered text of its initialiser, a
    prefill chunk and a decode token, against sums taken from the parent's
    tree with this function (same JAX: the text does not depend on the
    machine)."""
    cfg = MlaConfig.nano_mla(held_experts=(0, 4))
    p = jax.eval_shape(lambda: mla_init(jax.random.PRNGKey(0), cfg))
    assert set(p["moe"]) >= {"wq_a", "wi_q", "we_gate"}      # one stack
    assert "w_attn_gate" not in p["moe"]
    assert [s.kinds for s in cfg.layer_plan()] == [(mla.MLA,)] * 2
    assert [pl.layers for pl in cfg.cache_planes()] == [3, 3]
    assert deepseek_program_sums(mla, MlaConfig, mla_init) == PARENT_SUMS


PARENT_SUMS = ["3d4d66bbf97a3349", "1e17b56e6b515aa0", "2367f24f033d392b"]

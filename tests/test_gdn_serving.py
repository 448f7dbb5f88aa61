"""Serving a `GdnConfig` (gated delta-rule layers with a matrix state a
row beside gated attention 3 : 1, held experts beside a gated shared one)
through the one engine: prefill then decode through the K/V pool and the
recurrent state gives the LOGITS of the plain float32 reference's full
forward (benchmark/reference/qwen3_next.py, the per-token recurrence),
whatever the chunking, the horizon, a slot's last tenant or a preemption,
and each wrong program a reader could mistake for it does not.

Everything here is float32 at nano widths on the CPU: 8 layers (two
periods of [delta, delta, delta, gated attention]), hidden 64, 4 query
heads on 2 KV heads of 16 (4 of them rotary), 2 key heads and 4 value
heads of 16 x 16 state, 16 routed experts 4 a token, experts [0, 4) held
here; blocks of 8 tokens, chunks of 16.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.harness import controls_gdn  # noqa: E402
from benchmark.reference import qwen3_next as ref  # noqa: E402
from ray_tpu.models import GdnConfig, gdn_init  # noqa: E402
from ray_tpu.models import gdn, moe  # noqa: E402
from ray_tpu.models.block_pool import zero_state_planes  # noqa: E402
from ray_tpu.models.engine import DecodeEngine  # noqa: E402
from ray_tpu.models.generate import generate  # noqa: E402
from ray_tpu.models.hybrid import HybridConfig  # noqa: E402
from ray_tpu.models.lora import LoraConfig  # noqa: E402
from ray_tpu.ops import gated_delta as gd  # noqa: E402



def nano_gdn(**kw) -> GdnConfig:
    """The family at widths a CPU runs in float32: two periods, sixteen
    experts of which four a token."""
    defaults = dict(vocab_size=256, dim=64, n_layers=8, n_heads=4,
                    n_kv_heads=2, head_dim=16, key_heads=2,
                    value_heads=4, key_head_dim=16, value_head_dim=16,
                    n_experts=16, top_k=4, expert_dim=32,
                    shared_expert_dim=32, max_seq_len=256,
                    dtype=jnp.float32, param_dtype=jnp.float32)
    defaults.update(kw)
    return GdnConfig(**defaults)


def delta_tokens(state, q, k, v, g, beta, live):
    """`delta_chunks`' result by the recurrence itself, a token at a time
    (`delta_step` under a scan): what the chunk form is tested against."""
    def step(s, xs):
        o, s = gd.delta_step(s, *xs)
        return s, o

    state, o = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(x, 1, 0)
                           for x in (q, k, v, g, beta, live)))
    return jnp.moveaxis(o, 0, 1), state


CFG = nano_gdn(held_experts=(0, 4))
# float32 on the CPU. The chunk form and the recurrence associate a
# chunk's sums differently (1e-6 on a state of unit size, the op test
# below); a matrix state that two hundred tokens have written, six such
# layers and a head 64 wide carry that to 2e-4 of a logit of unit spread
# at the worst position measured here (3e-5 at most positions). Every
# wrong program below is off by 1.7 or more.
T, CHUNK, TOL = 8, 16, 5e-4


def model_of(cfg):
    """The reference's view of a config: the published key names."""
    return {
        "hidden_size": cfg.dim, "num_hidden_layers": cfg.n_layers,
        "full_attention_interval": cfg.full_attention_interval,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "partial_rotary_factor": cfg.partial_rotary_factor,
        "rope_theta": cfg.rope_theta,
        "linear_num_key_heads": cfg.key_heads,
        "linear_num_value_heads": cfg.value_heads,
        "linear_key_head_dim": cfg.key_head_dim,
        "linear_value_head_dim": cfg.value_head_dim,
        "linear_conv_kernel_dim": cfg.conv_kernel,
        "num_experts": cfg.n_experts, "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob, "rms_norm_eps": cfg.norm_eps}


MODEL = model_of(CFG)


@pytest.fixture(scope="module")
def params():
    return jax.jit(gdn_init, static_argnums=1)(jax.random.PRNGKey(0), CFG)


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
    """Largest |served - reference| logit over the decoded positions."""
    P = len(prompt)
    want = reference_logits(params, prompt + toks, **kw)
    assert len(seen) == len(toks) - 1
    return max(float(np.abs(got - want[P + j]).max())
               for j, got in enumerate(seen)), want


# -- the delta rule's two forms ----------------------------------------------

def _rule_inputs(B, S, H, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gd.l2norm(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -0.1 * jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)) + 1.0)
    s0 = jax.random.normal(ks[5], (B, H, dk, dv))
    return s0, q, k, v, g, beta


@pytest.mark.parametrize("S,live", [
    (1, (1, 1)), (5, (5, 3)), (63, (63, 1)), (64, (64, 64)), (65, (65, 64)),
    (128, (128, 100)), (150, (150, 97)), (256, (200, 0))])
def test_chunk_form_is_the_recurrence(S, live):
    """`delta_chunks` against `delta_step` a token at a time: lengths that
    are and are not multiples of the chunk of 64, a state carried in, and
    rows whose live prefix ends inside a chunk (or is empty)."""
    s0, q, k, v, g, beta = _rule_inputs(2, S, 3, 16, 8, seed=S)
    lv = jnp.arange(S)[None, :] < jnp.asarray(live)[:, None]
    o1, s1 = gd.delta_chunks(s0, q, k, v, g, beta, lv, jnp.float32)
    o2, s2 = delta_tokens(s0, q, k, v, g, beta, lv)
    m = np.asarray(lv)[:, :, None, None]
    np.testing.assert_allclose(np.where(m, o1, 0), np.where(m, o2, 0),
                               atol=2e-6, rtol=0)
    np.testing.assert_allclose(s1, s2, atol=2e-6, rtol=0)
    for b, n in enumerate(live):
        if n == 0:     # a row with nothing live keeps its state, bit for bit
            np.testing.assert_array_equal(s1[b], s0[b])


def test_chunk_form_survives_a_repeated_key():
    """Seventy tokens with ONE key (what a greedy row that loops feeds a
    recompute): the triangular system's off-diagonal entries are all near
    beta, where the series I - A + A^2 - ... cancels catastrophically; the
    inverse by halves stays exact."""
    s0, q, k, v, g, beta = _rule_inputs(1, 128, 2, 16, 8, seed=4)
    k = k.at[:, 20:90].set(k[:, 20:21])
    lv = jnp.ones((1, 128), bool)
    o1, s1 = gd.delta_chunks(s0, q, k, v, g, beta, lv, jnp.float32)
    o2, s2 = delta_tokens(s0, q, k, v, g, beta, lv)
    np.testing.assert_allclose(o1, o2, atol=5e-6, rtol=0)
    np.testing.assert_allclose(s1, s2, atol=5e-6, rtol=0)


def test_one_token_update_is_the_published_recurrence():
    s0, q, k, v, g, beta = _rule_inputs(2, 1, 3, 16, 8, seed=9)
    o, s1 = gd.delta_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                          beta[:, 0], jnp.asarray([True, False]))
    S = np.asarray(s0, np.float64) * np.exp(np.asarray(g[:, 0]))[..., None,
                                                                 None]
    d = np.asarray(beta[:, 0])[..., None] * (
        np.asarray(v[:, 0]) - np.einsum("bhkv,bhk->bhv", S, k[:, 0]))
    S = S + np.asarray(k[:, 0])[..., :, None] * d[..., None, :]
    np.testing.assert_allclose(o, np.einsum("bhkv,bhk->bhv", S, q[:, 0]),
                               atol=1e-6)
    np.testing.assert_allclose(s1[0], S[0], atol=1e-6)
    np.testing.assert_array_equal(s1[1], s0[1])      # a dead row stays


# -- the one-token update in place on the plane (the kernel, interpreted) -------

def _plane_inputs(L, B, H, dk, dv, seed):
    s0, q, k, v, g, beta = _rule_inputs(B, L, H, dk, dv, seed=seed)
    plane = jax.random.normal(jax.random.PRNGKey(seed + 50),
                              (L, B, H, dk, dv), jnp.float32)
    # [B, L, ...] -> a layer's operands first
    return plane, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))


_LIVE = {"all": (True,) * 5, "some": (False, True, True, False, True),
         "last": (False,) * 4 + (True,), "none": (False,) * 5}


@pytest.mark.parametrize("hb", [8, 16])
@pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
@pytest.mark.parametrize("live", list(_LIVE))
def test_the_step_kernel_is_delta_step_on_the_planes_layer(live, layer, hb):
    """`delta_step_plane` (interpreted) against `delta_step` on the
    layer's slice: the live rows' state and output within 1e-6 of the
    state's largest value (the two sums over dk are taken in another
    order; everything else is the same float32 multiplies and adds), every
    dead row's state and every other layer of the plane bit for bit, with
    all rows live, some, the last alone and none, on a first and a last
    layer, at two head blocks (one and two grid steps a row)."""
    plane, xs = _plane_inputs(3, 5, 16, 16, 128, seed=3 * layer + hb)
    lv = jnp.asarray(_LIVE[live])
    step = tuple(x[layer] for x in xs)
    o, new = gd.delta_step_plane(plane, jnp.int32(layer), *step, lv, hb=hb)
    o_want, s_want = gd.delta_step(plane[layer], *step, lv)
    tol = 1e-6 * float(jnp.abs(plane).max())
    m = np.asarray(lv)
    np.testing.assert_allclose(new[layer], s_want, atol=tol, rtol=0)
    np.testing.assert_allclose(np.asarray(o)[m], np.asarray(o_want)[m],
                               atol=tol, rtol=0)
    np.testing.assert_array_equal(np.asarray(o)[~m], 0.0)
    np.testing.assert_array_equal(np.asarray(new[layer])[~m],
                                  np.asarray(plane[layer])[~m])
    others = [i for i in range(3) if i != layer]
    np.testing.assert_array_equal(np.asarray(new)[others],
                                  np.asarray(plane)[others])


@pytest.mark.parametrize("hb", [8, None], ids=["hb8", "all_heads"])
def test_two_tokens_through_the_step_kernel_are_two_delta_steps(hb):
    """The fused horizon: a second token reads what the first wrote where
    it lies, a row that died between them stays as the first left it, and
    the walk over the live rows may be handed in (`live_rows`)."""
    plane, xs = _plane_inputs(2, 4, 8, 16, 128, seed=11)
    lives = [jnp.asarray([True, True, False, True]),
             jnp.asarray([True, False, False, True])]
    got, want = plane, plane[1]
    for t, lv in enumerate(lives):
        step = tuple(jnp.roll(x[1], t, axis=0) for x in xs)
        o, got = gd.delta_step_plane(got, jnp.int32(1), *step, lv, hb=hb,
                                     walk=gd.live_rows(lv))
        o_want, want = gd.delta_step(want, *step, lv)
        np.testing.assert_allclose(np.asarray(o)[np.asarray(lv)],
                                   np.asarray(o_want)[np.asarray(lv)],
                                   atol=5e-6, rtol=0)
    np.testing.assert_allclose(got[1], want, atol=5e-6, rtol=0)
    np.testing.assert_array_equal(got[1, 2], plane[1, 2])
    np.testing.assert_array_equal(got[0], plane[0])


@pytest.mark.parametrize("heads,dk,dv,hb", [
    (32, 128, 128, 32), (16, 128, 128, 16), (48, 128, 128, 24),
    (4, 16, 128, 4), (12, 16, 128, 12), (4, 16, 16, None),
    (4, 12, 128, None)])
def test_the_step_kernels_head_block_follows_from_the_shapes(heads, dk, dv,
                                                             hb):
    """The published 32 heads of 128 x 128 go a whole row a step (8 MiB of
    the default scoped 16 for the state's blocks in and out, twice each);
    a block is whole sublane tiles of the per-head rows or all the heads;
    a head that is not whole float32 tiles has no kernel."""
    assert gd.delta_step_heads(heads, dk, dv) == hb
    if hb is not None:
        assert heads % hb == 0 and (hb % 8 == 0 or hb == heads)


def test_live_rows_walks_the_live_rows_first_in_order():
    lv = jnp.asarray([False, True, True, False, True, False])
    rows, n = gd.live_rows(lv)
    assert int(n) == 3 and rows.tolist() == [1, 2, 4, 0, 0, 0]
    rows, n = gd.live_rows(jnp.zeros(4, bool))
    assert int(n) == 0 and rows.tolist() == [0, 0, 0, 0]


# -- the engine against the reference, logits -------------------------------

@pytest.mark.parametrize("n_prompt,n_new", [
    (5, 9),        # one short chunk
    (16, 20),      # exactly a chunk
    (37, 10),      # three chunks, the last of 5 tokens
    (70, 30),      # five chunks, nine blocks
], ids=["short", "one_chunk", "ragged", "long"])
def test_prefill_then_decode_gives_the_reference_logits(params, n_prompt,
                                                        n_new):
    eng = engine(params)
    prompt = prompt_of(n_prompt, seed=n_prompt)
    toks, seen = served_logits(eng, prompt, n_new)
    err, want = worst_error(params, prompt, toks, seen)
    assert err <= TOL
    P = len(prompt)
    assert want[P - 1].max() - want[P - 1][toks[0]] <= TOL
    st = eng.stats()
    assert st["ssm_state_resets_total"] == 1
    # a dispatch a token; the one that samples the last token feeds it too
    assert st["ssm_row_steps_total"] == n_new
    assert st["kv_walk_tokens_full_total"] == CFG.n_attn_layers * sum(
        range(P + 1, P + n_new + 1))


@pytest.mark.parametrize("chunk,n_prompt", [(None, 150), (64, 150),
                                            (128, 200), (None, 64)])
def test_chunk_boundaries_change_no_logit(params, chunk, n_prompt):
    """Prompts longer than the rule's chunk of 64 and no multiple of it,
    prefilled whole or in engine chunks that are (64, 128) multiples of
    it: the reference's logits either way."""
    eng = engine(params, max_len=256, prefill_chunk=chunk)
    prompt = prompt_of(n_prompt, seed=chunk or 1)
    toks, seen = served_logits(eng, prompt, 6)
    err, _ = worst_error(params, prompt, toks, seen)
    assert err <= TOL


# one wrong program a variant: the table the cell's controls run on the chip
WRONG = [v for v in controls_gdn.VARIANTS if v != "right"]


@pytest.mark.parametrize("what", WRONG)
def test_each_wrong_program_fails_the_comparison(params, what, monkeypatch):
    """The tolerance is tight enough to tell: the right program is within
    `TOL` of the reference (the tests above), each of these is a hundred
    times further. One slot, and a request before the one that is scored,
    so that the slot's state is a finished row's."""
    for mod, name, fn in controls_gdn._patches(what):
        monkeypatch.setattr(mod, name, fn)
    # a config of its own: no program traced before (or after) is reused
    cfg = dataclasses.replace(CFG, max_seq_len=300 + WRONG.index(what))
    eng = engine(params, cfg, batch_slots=1)
    served_logits(eng, prompt_of(20, seed=1), 4)
    prompt = prompt_of(57, seed=11)
    toks, seen = served_logits(eng, prompt, 12)
    monkeypatch.undo()
    err, _ = worst_error(params, prompt, toks, seen)
    assert err > 100 * TOL


def test_a_slot_taken_over_starts_from_zero_state(params):
    """Three requests through ONE slot: each is the reference's, so a
    finished row's state (left in the slot) reaches nobody."""
    eng = engine(params, batch_slots=1)
    for seed, n, m in ((1, 30, 8), (2, 9, 12), (3, 45, 5)):
        prompt = prompt_of(n, seed=seed)
        toks, seen = served_logits(eng, prompt, m)
        err, _ = worst_error(params, prompt, toks, seen)
        assert err <= TOL
    assert eng.stats()["ssm_state_resets_total"] == 3
    assert float(jnp.abs(eng._hyb["delta"]).max()) > 0    # state stays put


def test_solo_generation_is_refused_by_name(params):
    """The family has ONE path, the engine's: `generate` says so instead
    of running the dense layer over this stack's parameters."""
    with pytest.raises(ValueError, match="DecodeEngine"):
        generate(params, jnp.ones((1, 4), jnp.int32), CFG,
                 max_new_tokens=2)


# -- batching, horizons, preemption -------------------------------------------

def test_batch_companions_change_nothing(params):
    work = [(prompt_of(30, seed=1), 20), (prompt_of(11, seed=2), 35),
            (prompt_of(47, seed=3), 9)]
    alone = []
    for p, m in work:
        e = engine(params)
        rid = e.submit(p, max_new_tokens=m)
        alone.append(e.run()[rid])
    eng = engine(params, batch_slots=2, max_prefills_per_step=2)
    ids = [eng.submit(p, max_new_tokens=m) for p, m in work]
    out = eng.run()
    assert [out[r] for r in ids] == alone


@pytest.mark.parametrize("horizon,depth", [(8, 1), (8, 2), (2, 2)])
def test_the_fused_horizon_and_the_ring_agree_with_horizon_1(params,
                                                             horizon,
                                                             depth):
    work = [(prompt_of(12, seed=7), 40), (prompt_of(35, seed=8), 23)]
    base = engine(params)
    ids = [base.submit(p, max_new_tokens=m) for p, m in work]
    while base.pending():
        base.step(horizon=1)
    want = [base.pop_result(r) for r in ids]
    eng = engine(params, decode_horizon=horizon, pipeline_depth=depth)
    ids = [eng.submit(p, max_new_tokens=m) for p, m in work]
    out = eng.run()
    assert [out[r] for r in ids] == want


def test_a_queue_behind_full_slots_runs_ahead_like_depth_1(params):
    work = [(prompt_of(12, seed=7), 21), (prompt_of(35, seed=8), 30),
            (prompt_of(20, seed=9), 14), (prompt_of(9, seed=10), 18)]
    calls, stats = {}, {}
    for depth in (1, 2):
        eng = engine(params, decode_horizon=4, pipeline_depth=depth)
        for p, m in work:
            eng.submit(p, max_new_tokens=m)
        calls[depth] = []
        while eng.pending():
            calls[depth].append(eng.step())
        stats[depth] = eng.stats()
    assert eng.kv_pool.blocks_in_use == 0
    assert calls[2] == calls[1]
    assert stats[2]["decode_dispatches_chained_queued"] >= 4
    assert stats[2]["preemptions"] == 0


def test_preempt_recompute_rebuilds_the_state(params):
    """A pool too small for both rows: one is preempted while it decodes,
    its blocks dropped and its slot's state left behind, and both are
    rebuilt by prefill of prompt + tokens (the chunk form over what the
    one-token update wrote)."""
    work = [(prompt_of(20, seed=5), 60), (prompt_of(24, seed=6), 60)]
    roomy = engine(params)
    want = [roomy.submit(p, max_new_tokens=m) for p, m in work]
    want_out = roomy.run()
    block = sum(pl.block_bytes(T) for pl in CFG.cache_planes())
    tight = engine(params, kv_pool_bytes=14 * block)
    got = [tight.submit(p, max_new_tokens=m) for p, m in work]
    got_out = tight.run()
    st = tight.stats()
    assert st["preemptions"] >= 1
    assert st["ssm_state_resets_total"] == 2 + st["preemptions"]
    assert [got_out[r] for r in got] == [want_out[r] for r in want]
    assert tight.kv_pool.blocks_in_use == 0


# -- what a token stores, what a row keeps ------------------------------------

def test_the_pool_and_the_state_are_the_configs_planes(params):
    """K/V of the two attention layers alone behind the table, and a slot
    of each state plane a row: sized from `cache_planes` / `state_planes`,
    with no window pool or table."""
    eng = engine(params, batch_slots=3)
    k, v = CFG.cache_planes()
    assert (k.layers, k.lanes) == (2, CFG.n_kv_heads * CFG.head_dim)
    assert eng._pool_k.shape[0] == eng._pool_v.shape[0] == 2
    assert eng.kv_bytes_per_token == 2 * 2 * 2 * 16 * 4
    assert set(eng._hyb) == {"delta", "conv"}
    assert eng._hyb["delta"].shape == (6, 3, 4, 16, 16)
    assert eng._hyb["delta"].dtype == jnp.float32
    assert eng._hyb["conv"].shape == (6, 3, 3, 2 * 32 + 64)
    assert eng.kv_pool_w is None and not eng._prefill_stops_early
    assert eng.stats()["window_pool_blocks_total"] == 0


def test_published_state_is_two_mebibytes_a_row_a_layer():
    pub = GdnConfig()
    delta, conv = pub.state_planes()
    assert delta.row_bytes() == pub.n_delta_layers * 2 * 2 ** 20
    assert conv.row_bytes() == pub.n_delta_layers * 3 * 8192 * 2
    assert pub.n_delta_layers == 36 and pub.n_attn_layers == 12
    assert [k.mixer for k in pub.layer_kinds()[:5]] == [
        "delta", "delta", "delta", "gated_attn", "delta"]
    assert sum(pl.block_bytes(1) for pl in pub.cache_planes()) \
        == 12 * 2 * 256 * 2 * 2


def test_a_hybrid_configs_state_is_its_declaration_too():
    """One path: the engine sizes a `HybridConfig`'s recurrent state from
    `state_planes` as well, and its window pools from the "window"
    `cache_planes`, all under the planes' own names."""
    from ray_tpu.models import hybrid_init

    cfg = HybridConfig.nano_hybrid()
    eng = engine(hybrid_init(jax.random.PRNGKey(0), cfg), cfg, batch_slots=3)
    window = [pl for pl in cfg.cache_planes() if pl.table == "window"]
    want = zero_state_planes(cfg.state_planes(), 3)
    assert set(want) == {"ssm", "conv"}
    assert [pl.name for pl in window] == ["wk", "wv"]
    assert set(eng._hyb) == {"ssm", "conv", "wk", "wv"}
    for name, x in want.items():
        assert (x.shape, x.dtype) == (eng._hyb[name].shape,
                                      eng._hyb[name].dtype)
    for pl in window:
        assert eng._hyb[pl.name].shape == (
            pl.layers, eng.kv_pool_w.n_blocks, T, pl.lanes)
        assert eng._hyb[pl.name].dtype == pl.dtype


# -- the seam: what the engine and solo `generate` ask a family ---------------

def _families():
    from ray_tpu.models import LlamaConfig, MoeConfig
    from ray_tpu.models.mla import MlaConfig
    return {"llama": LlamaConfig.nano, "moe": MoeConfig.nano_moe,
            "hybrid": HybridConfig.nano_hybrid, "mla": MlaConfig.nano_mla,
            "gdn": nano_gdn}


@pytest.mark.parametrize("family", ["llama", "moe", "hybrid", "mla", "gdn"])
def test_every_family_answers_the_engines_questions(family):
    """`block_pool.ServedConfig`, under one name for all five: what the
    engine and solo `generate` read in place of the config's class."""
    from ray_tpu.models.block_pool import CachePlane, StatePlane

    cfg = _families()[family]()
    refusals = cfg.refusals()
    assert set(refusals) <= {"prefix_cache", "preempt_swap", "draft",
                             "kv_quant", "lora", "tp", "handoff"}
    for option, why in refusals.items():
        assert isinstance(why, str) and type(cfg).__name__ in why, option
        assert ("{}" in why) == (option == "handoff"), option
    assert (family == "llama") == (refusals == {})
    planes = cfg.cache_planes()
    assert all(isinstance(pl, CachePlane) for pl in planes)
    assert [pl.table for pl in planes].count("full") == 2
    assert {pl.table for pl in planes} <= {"full", "window"}
    if any(pl.table == "window" for pl in planes):
        assert cfg.sliding_window > 0 and cfg.n_window_layers > 0
    state = cfg.state_planes()
    assert isinstance(state, tuple)
    assert all(isinstance(pl, StatePlane) for pl in state)
    assert bool(state) == (family in ("hybrid", "gdn"))
    own = cfg.stack()
    assert (own is None) == (family in ("llama", "moe"))
    if own is not None:
        assert callable(own.layers_paged) and callable(own.lm_head)
        assert callable(own.init_cache)
    assert 0 < cfg.prefill_layers() <= cfg.n_layers
    assert (cfg.prefill_layers() < cfg.n_layers) == (family == "hybrid")


@pytest.mark.parametrize("module", ["engine", "generate"])
def test_the_engine_and_generate_name_no_family_class(module):
    """The seam stays closed: neither file imports `hybrid`, `mla` or
    `gdn`, or names one of their config classes, outside comments and
    docstrings."""
    import ast
    import ray_tpu.models as models

    path = os.path.join(os.path.dirname(models.__file__), module + ".py")
    classes = {"HybridConfig", "MlaConfig", "GdnConfig"}
    modules = {"hybrid", "mla", "gdn"}
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.ImportFrom):
            assert node.module.split(".")[-1] not in modules, node.lineno
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                assert a.name.split(".")[-1] not in classes | modules, \
                    node.lineno
        if isinstance(node, ast.Name):
            assert node.id not in classes, node.lineno
        if isinstance(node, ast.Attribute):
            assert node.attr not in classes, node.lineno
    assert "_hybrid" not in open(path).read()


# -- held experts, the gated shared one, the router ---------------------------

def _full_layer(key):
    """One expert layer's parameters with ALL experts, float32."""
    p = gdn_init(key, dataclasses.replace(CFG, held_experts=None))
    return jax.tree_util.tree_map(lambda x: x[0, 0], p["period"]["moe"])


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The 4 shares' partial results of one expert layer (held ranges
    [0,4) .. [12,16), the gated shared expert counted ONCE) add up to the
    uncut reference's layer output; the program's share is the
    reference's share."""
    layer = _full_layer(jax.random.PRNGKey(3))
    u = jax.random.normal(jax.random.PRNGKey(4), (37, CFG.dim), jnp.float32)
    stacks = ("we_gate", "we_up", "we_down")
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(u, layer, MODEL)
        none = dict(layer, **{k: layer[k][:0] for k in stacks})
        shared = ref.expert_layer(u, none, MODEL, held=(0, 0))
    total = jnp.zeros_like(whole)
    for lo in range(0, CFG.n_experts, 4):
        held = (lo, lo + 4)
        mine = dict(layer, **{k: layer[k][lo:lo + 4] for k in stacks})
        with jax.default_matmul_precision("highest"):
            want = ref.expert_layer(u, mine, MODEL, held=held, shared=False)
        cfg = dataclasses.replace(CFG, held_experts=held,
                                  shared_expert_dim=0)
        got, _ = moe.moe_ffn_dropless(u[None], mine, cfg)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   atol=2e-5, rtol=0)
        total = total + got[0]
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), atol=5e-5, rtol=0)
    # and the program's shared expert, under its gate, is the reference's
    cfg = dataclasses.replace(CFG, held_experts=(0, 4))
    mine = dict(layer, **{k: layer[k][:4] for k in stacks})
    got, _ = moe.moe_ffn_dropless(u[None], mine, cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(u, mine, MODEL, held=(0, 4))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5, rtol=0)


def test_counters_count_routed_and_landed(params):
    eng = engine(params)
    prompt = prompt_of(20, seed=2)
    rid = eng.submit(prompt, max_new_tokens=10)
    assert len(eng.run()[rid]) == 10
    st = eng.stats()
    tokens = 20 + 9                      # the last token is never fed
    assert st["moe_assignments_total"] == tokens * CFG.n_layers * CFG.top_k
    assert 0 < st["moe_assignments_landed_total"] \
        < st["moe_assignments_total"]
    # a dispatch a token, the one that samples the last token included
    assert st["moe_decode_layer_steps_total"] == 10 * CFG.n_layers
    assert 0 < st["moe_decode_experts_hit_total"] \
        <= 4 * st["moe_decode_layer_steps_total"]


# -- what the engine refuses ---------------------------------------------------

def _draft(params):
    return dict(draft_params=params, draft_cfg=CFG)


@pytest.mark.parametrize("how,kw,names", [
    ("prefix_cache", dict(prefix_cache=True), "ROADMAP M4"),
    ("kv_quant", dict(kv_quant="int8"), "quantized"),
    ("swap", dict(preempt="swap"), "swap ledger"),
    ("default_preempt", dict(preempt=None), "swap ledger"),
    ("tp", dict(tp=1), "sharding rule"),
    ("mesh", dict(mesh="any"), "sharding rule"),
    ("lora", dict(lora=LoraConfig(rank=2)), "adapter targets"),
    ("speculative", _draft, "ROADMAP M7"),
])
def test_what_a_gdn_config_refuses_at_construction(params, how, kw, names):
    kw = kw(params) if callable(kw) else dict(kw)
    base = dict(batch_slots=2, max_len=64, kv_block_tokens=T,
                preempt="recompute")
    base.update(kw)
    if base["preempt"] is None:
        del base["preempt"]                 # the engine's default is swap
    with pytest.raises(ValueError, match="GdnConfig cannot be served") as e:
        DecodeEngine(params, CFG, **base)
    assert names in str(e.value)


@pytest.mark.parametrize("call", ["export_request", "import_request"])
def test_a_gdn_engine_refuses_a_hand_off(params, call):
    eng = engine(params)
    with pytest.raises(ValueError, match="recurrent state"):
        getattr(eng, call)(0 if call == "export_request" else {})


@pytest.mark.parametrize("bad", [
    dict(n_layers=6), dict(full_attention_interval=1),
    dict(n_kv_heads=3), dict(key_heads=3),
    dict(partial_rotary_factor=0.0), dict(held_experts=(4, 4)),
    dict(held_experts=(0, 17))])
def test_a_config_that_is_no_such_stack_is_refused(bad):
    with pytest.raises(ValueError, match="GdnConfig"):
        nano_gdn(**bad)


# -- the grouped kernel: which programs take it, and who counts them -------------

def _kernel_names(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield str(eqn.params.get("name") or eqn.params.get(
                "name_and_src_info"))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _kernel_names(inner)


@pytest.mark.parametrize("tokens,hit,grouped", [
    (2, True, False), (64, True, False), (128, True, False),
    (256, False, False), (264, False, True)],
    ids=["decode_2", "decode_64", "chunk_128", "chunk_256", "chunk_264"])
def test_only_a_chunk_past_the_all_experts_form_traces_the_grouped_kernel(
        params, tokens, hit, grouped):
    """A fused decode program multiplies at most `batch_slots` rows a
    layer (64 in the cell): its held experts go through the kernel of
    `ops.hit_experts` (widths that fit: `moe.held_hit_kernel`), as any
    program of at most `HIT_EXPERTS_MAX_TOKENS` tokens, and no call of
    `ops.held_grouped_ffn`'s kernel is traced into it; up to
    `DENSE_HELD_MAX_TOKENS` tokens the form is `_held_hit`'s `cond` an
    expert, without a kernel; a prefill program over that many holds the
    grouped kernel, one a layer."""
    from ray_tpu.models import moe
    from ray_tpu.ops import scope_names as sn

    layer = jax.tree_util.tree_map(lambda x: x[0, 0],
                                   params["period"]["moe"])
    x = jnp.zeros((1, tokens, CFG.dim), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: moe.moe_ffn_dropless(
        x, layer, CFG, live=jnp.ones((1, tokens), bool)))(x)
    names = list(_kernel_names(jaxpr.jaxpr))
    assert any(sn.HELD_GROUPED_KERNEL in n for n in names) == grouped
    assert any(sn.HIT_EXPERTS_KERNEL in n for n in names) == hit
    assert len(names) == hit + grouped
    assert moe.held_grouped_prefill(CFG, tokens) == grouped
    assert moe.held_hit_kernel(CFG, tokens) == hit


@pytest.mark.parametrize("family", ["held", "no_held_range", "dense"])
def test_decode_blocks_through_the_hit_kernel_are_counted(family):
    """Every fused decode block of a config that holds a share of its
    experts (at widths the kernel's step fits) is counted by
    `moe_hit_kernel_decode_dispatches_total`; an `MoeConfig` with every
    expert here takes the kernel by `hit_experts_only` and counts nothing,
    as a dense `LlamaConfig`."""
    from ray_tpu.models import (LlamaConfig, MoeConfig, llama_init, moe,
                                moe_init)

    cfg, init = {
        "held": (nano_gdn(held_experts=(0, 4), n_layers=4), gdn_init),
        "no_held_range": (MoeConfig.nano_moe(), moe_init),
        "dense": (LlamaConfig.nano(), llama_init)}[family]
    p = jax.jit(init, static_argnums=1)(jax.random.PRNGKey(1), cfg)
    eng = DecodeEngine(p, cfg, batch_slots=2, max_len=64,
                       kv_block_tokens=T, decode_horizon=2,
                       preempt="recompute")
    assert moe.held_hit_kernel(cfg, eng.B) == (family == "held")
    ids = [eng.submit(prompt_of(n, seed=n), max_new_tokens=7)
           for n in (5, 9, 6)]
    out = eng.run()
    assert all(len(out[i]) == 7 for i in ids)
    st = eng.stats()
    assert st["decode_dispatches"] > 3
    assert st["moe_hit_kernel_decode_dispatches_total"] \
        == (st["decode_dispatches"] if family == "held" else 0)


@pytest.mark.parametrize("family", ["held", "no_held_range"])
def test_grouped_prefill_dispatches_are_counted_where_the_kernel_runs(family):
    """A prompt of three chunks, 264 + 264 + 24 tokens: the two programs
    over `DENSE_HELD_MAX_TOKENS` tokens of a config that holds a share go
    through the grouped kernel and are counted; the last chunk is not,
    and an `MoeConfig` with every expert here counts nothing."""
    from ray_tpu.models import MoeConfig, moe, moe_init

    assert moe.DENSE_HELD_MAX_TOKENS < 264
    if family == "held":
        cfg = nano_gdn(held_experts=(0, 4), n_layers=4, max_seq_len=640)
        p = jax.jit(gdn_init, static_argnums=1)(jax.random.PRNGKey(1), cfg)
    else:
        cfg = MoeConfig.nano_moe(max_seq_len=640)
        p = jax.jit(moe_init, static_argnums=1)(jax.random.PRNGKey(1), cfg)
    eng = DecodeEngine(p, cfg, batch_slots=2, max_len=640,
                       kv_block_tokens=T, prefill_chunk=264,
                       preempt="recompute", pipeline_depth=1)
    rid = eng.submit(prompt_of(552, seed=3), max_new_tokens=2)
    assert len(eng.run()[rid]) == 2
    st = eng.stats()
    assert st["prefill_dispatches"] == 3
    assert st["moe_grouped_prefill_dispatches_total"] \
        == (2 if family == "held" else 0)
    if family == "held":
        # what the kernel multiplied: 128-row visits, not whole windows
        assert st["moe_rows_computed_total"] \
            >= st["moe_assignments_landed_total"] > 0


# -- the one-token kernel: which program takes it, and who counts it ---------------

# the nano widths with a value head of a whole lane tile: what the kernel
# takes (16 x 128 float32 a head)
KCFG = nano_gdn(held_experts=(0, 4), value_head_dim=128)


@pytest.fixture(scope="module")
def kparams():
    return jax.jit(gdn_init, static_argnums=1)(jax.random.PRNGKey(0), KCFG)


@pytest.fixture
def through_the_step_kernel(monkeypatch):
    """The decode branch as the chip takes it, the kernel interpreted: the
    predicate answers for the shapes alone."""
    monkeypatch.setattr(
        gdn, "state_step_kernel", lambda cfg: gd.delta_step_heads(
            cfg.value_heads, cfg.key_head_dim, cfg.value_head_dim)
        is not None)


def test_off_the_chip_a_decode_token_takes_the_plain_form():
    """`state_step_kernel` is said from the platform and the shapes: the
    CPU runs `delta_step` whatever the shapes."""
    assert gd.delta_step_heads(KCFG.value_heads, KCFG.key_head_dim,
                               KCFG.value_head_dim) == 4
    assert not gdn.state_step_kernel(KCFG)
    assert not gdn.state_step_kernel(CFG)


@pytest.mark.parametrize("forced", [False, True], ids=["plain", "kernel"])
def test_the_decode_program_holds_the_step_kernel_where_the_predicate_says(
        kparams, forced, monkeypatch):
    """One call of the kernel in the decode program's delta layer body
    (the scan traces one) where the predicate holds and none where it
    does not; a prefill group's programs (a one-token chunk too) never
    take it."""
    from ray_tpu.ops import scope_names as sn
    if forced:
        monkeypatch.setattr(gdn, "state_step_kernel", lambda cfg: True)
    B = 2
    state = zero_state_planes(KCFG.state_planes(), B)
    k, v = KCFG.cache_planes()
    pool = jnp.zeros((k.layers, 9, T, k.lanes), jnp.float32)

    def program(S, rows):
        return jax.make_jaxpr(lambda st: gdn.layers_paged(
            kparams, jnp.ones((B, S), jnp.int32), pool, pool,
            jnp.zeros((B, 4), jnp.int32), jnp.full((B,), 3, jnp.int32),
            KCFG, state=st, live=jnp.ones((B, S), bool), rows=rows))(state)

    names = list(_kernel_names(program(1, None).jaxpr))
    assert sum(sn.DELTA_STEP_KERNEL in n for n in names) == int(forced)
    chunk = list(_kernel_names(program(1, jnp.arange(B)).jaxpr)) \
        + list(_kernel_names(program(4, jnp.arange(B)).jaxpr))
    assert not any(sn.DELTA_STEP_KERNEL in n for n in chunk)


@pytest.mark.parametrize("n_prompt,n_new", [(5, 9), (37, 10)],
                         ids=["short", "ragged"])
def test_decode_through_the_step_kernel_gives_the_reference_logits(
        kparams, through_the_step_kernel, n_prompt, n_new):
    """`test_prefill_then_decode_gives_the_reference_logits` with every
    decode token's state updated by the kernel, in place on the plane."""
    model = model_of(KCFG)
    eng = engine(kparams, KCFG)
    prompt = prompt_of(n_prompt, seed=n_prompt)
    toks, seen = served_logits(eng, prompt, n_new)
    err, want = worst_error(kparams, prompt, toks, seen, model=model)
    assert err <= TOL
    st = eng.stats()
    assert st["ssm_row_steps_total"] == n_new
    assert st["state_kernel_decode_dispatches_total"] \
        == st["decode_dispatches"] > 0


def test_the_step_kernel_and_the_plain_form_serve_the_same_tokens(
        kparams, monkeypatch):
    """Two rows of unlike lengths through a fused horizon of 8 with a ring
    two deep, rows finishing inside a block (dead for the rest of it): the
    kernel's engine returns the plain form's tokens."""
    work = [(prompt_of(12, seed=7), 19), (prompt_of(35, seed=8), 10),
            (prompt_of(9, seed=9), 6)]

    def served():
        eng = engine(kparams, KCFG, decode_horizon=8, pipeline_depth=2)
        ids = [eng.submit(p, max_new_tokens=m) for p, m in work]
        out = eng.run()
        return [out[r] for r in ids], eng.stats()

    want, st = served()
    assert st["state_kernel_decode_dispatches_total"] == 0
    monkeypatch.setattr(gdn, "state_step_kernel", lambda cfg: True)
    got, st = served()
    assert got == want
    assert st["state_kernel_decode_dispatches_total"] \
        == st["decode_dispatches"] > 0


@pytest.mark.parametrize("family", ["gdn", "hybrid", "dense"])
def test_decode_blocks_through_a_stacks_own_state_kernel_are_counted(
        family, monkeypatch):
    """`state_kernel_decode_dispatches_total` counts the fused decode
    blocks of a stack that says it takes its own kernel
    (`state_step_kernel`): a `GdnConfig` where the predicate holds; a
    `HybridConfig` (recurrent state, no such kernel) and a dense
    `LlamaConfig` count nothing."""
    from ray_tpu.models import LlamaConfig, hybrid_init, llama_init

    cfg, init = {
        "gdn": (nano_gdn(held_experts=(0, 4), n_layers=4,
                         value_head_dim=128), gdn_init),
        "hybrid": (HybridConfig.nano_hybrid(), hybrid_init),
        "dense": (LlamaConfig.nano(), llama_init)}[family]
    monkeypatch.setattr(gdn, "state_step_kernel", lambda cfg: True)
    p = jax.jit(init, static_argnums=1)(jax.random.PRNGKey(1), cfg)
    eng = DecodeEngine(p, cfg, batch_slots=2, max_len=64,
                       kv_block_tokens=T, decode_horizon=2,
                       preempt="recompute")
    ids = [eng.submit(prompt_of(n, seed=n), max_new_tokens=5)
           for n in (5, 9)]
    out = eng.run()
    assert all(len(out[i]) == 5 for i in ids)
    st = eng.stats()
    assert st["decode_dispatches"] > 1
    assert st["state_kernel_decode_dispatches_total"] \
        == (st["decode_dispatches"] if family == "gdn" else 0)

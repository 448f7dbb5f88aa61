"""Serving an `MlaConfig` (latent attention whose keys a learned indexer
selects, leading dense layers, held experts beside a shared one) through
the one engine: prefill then decode through the latent and index planes
gives the LOGITS of the plain float32 reference's full forward
(benchmark/reference/deepseek_v32_sparse.py), whatever the chunking, the
horizon or a preemption, and each wrong program a reader could mistake for
it does not.

Everything here is float32 at nano widths on the CPU: 3 layers (1 dense,
2 expert), hidden 64, 4 heads, latent 32 + 8 rotary, an indexer of 4 heads
of 16 that keeps 16 slots of contexts of 40-100 (so selection prunes), 16
routed experts in 4 groups of which 2 are kept, 4 a token, experts [0, 4)
held here; blocks of 8 tokens, chunks of 16.
"""

import dataclasses
import os
import sys
from unittest import mock

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.reference import deepseek_v32_sparse as ref  # noqa: E402
from ray_tpu.models import MlaConfig, mla_init  # noqa: E402
from ray_tpu.models import mla, moe  # noqa: E402
from ray_tpu.models.engine import DecodeEngine  # noqa: E402
from ray_tpu.models.generate import generate  # noqa: E402
from ray_tpu.models.lora import LoraConfig  # noqa: E402

CFG = MlaConfig.nano_mla(held_experts=(0, 4))
T, CHUNK, TOL = 8, 16, 5e-5


def model_of(cfg):
    """The reference's view of a config: the published key names."""
    f, orig, fast, slow, ms, msa = cfg.rope_scaling
    return {
        "hidden_size": cfg.dim, "num_attention_heads": cfg.n_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "n_routed_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.top_k, "n_group": cfg.n_group,
        "topk_group": cfg.topk_group, "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {"factor": f,
                         "original_max_position_embeddings": orig,
                         "beta_fast": fast, "beta_slow": slow,
                         "mscale": ms, "mscale_all_dim": msa}}


MODEL = model_of(CFG)


@pytest.fixture(scope="module")
def params():
    """Seeded weights, the selection bias scaled up to std 0.1 (the
    initialiser seeds it small so that the experts' loads stay even): at
    16 experts and a hundred tokens a program that drops it must show."""
    p = jax.jit(mla_init, static_argnums=1)(jax.random.PRNGKey(0), CFG)
    return dict(p, moe=dict(p["moe"],
                            router_bias=p["moe"]["router_bias"] * 33.0))


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


# -- the engine against the reference, logits -------------------------------

@pytest.mark.parametrize("n_prompt,n_new", [
    (5, 9),        # under index_topk throughout: attends everything
    (10, 20),      # crosses index_topk 16 mid-decode
    (24, 10),      # crosses it mid-chunk (the second chunk)
    (57, 30),      # four chunks, far past it: most slots pruned
], ids=["under_topk", "crosses_in_decode", "crosses_in_chunk", "long"])
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
    if n_prompt + n_new > CFG.index_topk:
        assert st["indexer_tokens_selected_total"] \
            < st["indexer_tokens_scored_total"]
    else:
        assert st["indexer_tokens_selected_total"] \
            == st["indexer_tokens_scored_total"]


def test_chunking_changes_no_logit(params):
    prompt = prompt_of(43, seed=3)
    t1, l1 = served_logits(engine(params), prompt, 8)
    t2, l2 = served_logits(engine(params, prefill_chunk=None), prompt, 8)
    assert t1 == t2
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(a, b, atol=TOL, rtol=0)


def _wrong(params, what):
    """(params, config, reference model) of a program that departs from
    the published mathematics in ONE way; the reference stays right."""
    cfg, p = CFG, params
    if what == "no_selection_bias":
        p = dict(params, moe=dict(params["moe"], router_bias=jnp.zeros_like(
            params["moe"]["router_bias"])))
    elif what == "no_group_limit":
        cfg = dataclasses.replace(CFG, n_group=1, topk_group=1)
    elif what == "no_scaling_factor":
        cfg = dataclasses.replace(CFG, routed_scaling_factor=1.0)
    elif what == "no_yarn_m2":
        f, orig, fast, slow, ms, _ = CFG.rope_scaling
        cfg = dataclasses.replace(
            CFG, rope_scaling=(f, orig, fast, slow, ms, 0.0))
    elif what == "attend_all":
        cfg = dataclasses.replace(CFG, index_topk=128)
    elif what == "absent_shared_expert":
        zero = {k: jnp.zeros_like(v) for k, v in params["moe"].items()
                if k.startswith("ws_")}
        p = dict(params, moe=dict(params["moe"], **zero))
    elif what in ("no_indexer_relu", "approx_topk_wrong_member"):
        # patched by the test while the program traces: a config of its
        # own, so that no program traced before (or after) is reused
        cfg = dataclasses.replace(CFG, max_seq_len=250 + len(what) % 5)
    return p, cfg


@pytest.mark.parametrize("what", [
    "no_selection_bias", "no_group_limit", "no_scaling_factor",
    "no_yarn_m2", "attend_all", "absent_shared_expert", "no_indexer_relu",
    "approx_topk_wrong_member"])
def test_each_wrong_program_fails_the_comparison(params, what, monkeypatch):
    """The tolerance is tight enough to tell: the right program is within
    `TOL` of the reference (the test above), each of these is a hundred
    times further."""
    p, cfg = _wrong(params, what)
    if what == "no_indexer_relu":
        monkeypatch.setattr(jax.nn, "relu", lambda x: x)
    if what == "approx_topk_wrong_member":
        exact = jax.lax.top_k

        def off_by_one(x, k):
            # an approximate top-k's failure: the k-th member is the
            # (k+1)-th largest
            if x.ndim != 3 or k >= x.shape[-1]:
                return exact(x, k)
            v, i = exact(x, k + 1)
            return (jnp.concatenate([v[..., :k - 1], v[..., k:]], -1),
                    jnp.concatenate([i[..., :k - 1], i[..., k:]], -1))

        monkeypatch.setattr(mla.jax.lax, "top_k", off_by_one)
    prompt = prompt_of(57, seed=11)
    toks, seen = served_logits(engine(p, cfg), prompt, 12)
    monkeypatch.undo()
    err, _ = worst_error(params, prompt, toks, seen)
    assert err > 100 * TOL


def test_absorbed_form_is_the_expanded_form(params):
    """The program scores ``q_nope W_kb`` against the latent and maps the
    latent's weighted sum through ``W_vb``; the reference expands keys and
    values of every head. Same numbers, with everything attended (the
    selection aside): one chunk, solo, context under `index_topk`."""
    cfg = dataclasses.replace(CFG, index_topk=64)
    seq = np.asarray(prompt_of(48, seed=5), np.int32)
    cache = mla.init_cache(cfg, 1, 64)
    got, _ = mla.forward_cached(params, jnp.asarray(seq)[None], cache, 0,
                                cfg)
    want = reference_logits(params, seq.tolist(), model_of(cfg))[-1]
    np.testing.assert_allclose(np.asarray(got[0, 0]), want, atol=TOL,
                               rtol=0)


def test_solo_generate_agrees_with_the_engine(params):
    prompt = prompt_of(21, seed=9)
    eng = engine(params)
    rid = eng.submit(prompt, max_new_tokens=14)
    out = eng.run()[rid]
    solo = generate(params, jnp.asarray([prompt], jnp.int32), CFG,
                    max_new_tokens=14)
    assert np.asarray(solo)[0].tolist()[-14:] == out


def test_left_padded_solo_prompts_are_refused(params):
    with pytest.raises(ValueError, match="left-padded"):
        generate(params, jnp.ones((2, 4), jnp.int32), CFG,
                 max_new_tokens=2, prompt_live=jnp.ones((2, 4), bool))


# -- the chunk's kernel ------------------------------------------------------------

@pytest.mark.parametrize("tiles", [(16, 64, 4), (32, 256, 8)],
                         ids=["many_tiles", "one_key_tile"])
def test_chunk_kernel_is_the_masked_dense_attention(tiles):
    """`ops.sparse_latent_attention` in interpret mode against its plain
    form: rows at different starts, a row whose tail is bucket filler, a
    random selection under the causal mask; tiles past a query tile's
    last slot are skipped and change nothing."""
    from ray_tpu.ops import sparse_latent_attention as sla

    B, H, S, W, rc, span = 2, 8, 32, 128, 96, 256
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (B, H, S, W), jnp.float32)
    lat = jax.random.normal(k[1], (B, span, W), jnp.float32)
    q_slots = jnp.array([40, 100])[:, None] + jnp.arange(S)[None]
    q_slots = q_slots.at[1, 20:].set(-1)
    seen = jnp.arange(span)[None, None, :] <= q_slots[:, :, None]
    pick = jax.random.uniform(k[2], (B, S, span)) < 0.3
    bias = jnp.where(seen & pick, 0.0, -1e30).astype(jnp.float32)
    want = sla.sparse_latent_attention_reference(q, lat, bias, rc=rc,
                                                 sm_scale=0.2)
    tq, tk, hb = tiles
    got = sla.sparse_latent_attention(q, lat, bias, q_slots, rc=rc,
                                      sm_scale=0.2, interpret=True, tq=tq,
                                      tk=tk, hb=hb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-6, rtol=0)
    assert not np.asarray(got)[1, :, 20:].any()      # filler attends nothing


# Rows of `sparse_latent_decode`'s cases: (the query's slot, -1 a row that
# asks nothing; the table's entries). Pages of 32 slots, a pool of 16.
_TOKEN_CASES = {
    # two rows of unlike length: pages past a row's slot are not walked
    "two_rows": dict(rows=[(100, [3, 1, 7, 0]), (40, [5, 2, 0, 0])]),
    # a row that asks nothing between two that do: zero trips, zeros out,
    # and the row before it starts the first copies of the row after
    "asks_nothing_between": dict(
        rows=[(70, [3, 1, 7, 0]), (-1, [0, 0, 0, 0]), (127, [5, 2, 9, 4])]),
    "asks_nothing_first_and_last": dict(
        rows=[(-1, [0, 0, 0, 0]), (33, [6, 8, 0, 0]), (-1, [0, 0, 0, 0])]),
    # a row that fills its whole table (6 entries, 2 pages a step)
    "fills_its_table": dict(
        rows=[(191, [3, 1, 7, 10, 12, 14]), (5, [5, 0, 0, 0, 0, 0])],
        pages_a_step=2),
    # a table whose last step is a half step (6 entries by 4): the row
    # that fills it folds 4 pages and then the 2 that are left
    "table_ends_in_a_half_step": dict(
        rows=[(191, [3, 1, 7, 10, 12, 14]), (140, [5, 2, 4, 6, 8, 0])],
        pages_a_step=4),
    # live counts that are no multiple of the pages a step: 5 and 1 of 3,
    # and 4 of 3 (a last step of one page)
    "odd_live_count": dict(
        rows=[(130, [3, 1, 7, 10, 12, 0]), (31, [5, 0, 0, 0, 0, 0]),
              (100, [2, 4, 6, 8, 0, 0])], pages_a_step=3),
    # every step one page, the slots alternating all through the call
    "a_page_a_step": dict(
        rows=[(95, [3, 1, 7, 0]), (64, [5, 2, 9, 0]), (0, [4, 0, 0, 0])],
        pages_a_step=1),
    "one_row": dict(rows=[(77, [11, 13, 15, 0])], pages_a_step=2),
    # the window layers' geometry: fewer heads, lanes wider than the
    # latent + rope, a table of the 4 pages that cover a 65-slot window
    # (its first page masked in part), slots counted from the first page
    "window": dict(
        rows=[(64 + 20, [3, 1, 7, 0]), (64 + 31, [5, 2, 9, 0]),
              (96 + 7, [4, 6, 8, 10]), (-1, [0, 0, 0, 0]),
              (40, [12, 14, 0, 0])],
        window=65, heads=4, lanes=256, rc=160, pages_a_step=2),
}


@pytest.mark.parametrize("case", list(_TOKEN_CASES))
def test_token_kernel_reads_the_pages_where_they_lie(case, monkeypatch):
    """`sparse_latent_decode` in interpret mode against the plain form on
    the gathered view: rows through a block table, each layer of the
    pool, a row walking the pages up to its slot's and no others (NaN
    in every block no row walks changes nothing)."""
    from ray_tpu.ops import sparse_latent_attention as sla

    c = _TOKEN_CASES[case]
    L, NB, T_ = 2, 16, 32
    H, W, rc = c.get("heads", 8), c.get("lanes", 128), c.get("rc", 96)
    slots = np.asarray([r[0] for r in c["rows"]])
    bt = jnp.asarray([r[1] for r in c["rows"]], jnp.int32)
    B, MB = bt.shape
    if "pages_a_step" in c:
        monkeypatch.setattr(sla, "_STEP_BYTES",
                            c["pages_a_step"] * T_ * W * 4)
        assert sla.pages_per_step(T_, W, 4, MB) == c["pages_a_step"]
    k = jax.random.split(jax.random.PRNGKey(2), 4)
    pool = jax.random.normal(k[0], (L, NB, T_, W), jnp.float32)
    q = jax.random.normal(k[1], (B, H, W), jnp.float32)
    at = np.arange(MB * T_)[None, :]
    seen = at <= slots[:, None]
    if "window" in c:
        pick = slots[:, None] - at < c["window"]
    else:
        pick = np.asarray(jax.random.uniform(k[2], (B, MB * T_)) < 0.4)
    bias = jnp.where(seen & pick, 0.0, -1e30).astype(jnp.float32)
    n_live = sla.pages_walked(slots, T_, MB)
    assert (n_live == np.where(slots < 0, 0, slots // T_ + 1)).all()
    walked = np.zeros(NB, bool)
    for row, n in zip(np.asarray(bt), n_live):
        walked[row[:n]] = True
    dirty = jnp.where(walked[None, :, None, None], pool, jnp.nan)
    for li in range(L):
        lat = pool[li, bt].reshape(B, MB * T_, W)
        want = sla.sparse_latent_attention_reference(
            q[:, :, None], lat, bias[:, None], rc=rc, sm_scale=0.2)[:, :, 0]
        got = sla.sparse_latent_decode(q, dirty, bt, bias, jnp.asarray(slots),
                                       jnp.int32(li), rc=rc, sm_scale=0.2,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-6, rtol=0)
        assert not np.asarray(got)[slots < 0].any()


def test_sparse_decode_counters_are_a_hand_count(params):
    """A decode dispatch of rows at slots 5, 16 and 40 (blocks of 8, a
    table of 16) walks 1, 3 and 6 pages a layer that selects, of 3 x 16
    entries; a row that asks nothing (-1) adds to neither; and the
    kernel's wrapper takes its trip counts from the same function."""
    from ray_tpu.ops import sparse_latent_attention as sla

    eng = engine(params)
    layers = CFG.n_select_layers
    T, MB = eng.kv_block_tokens, eng._mb
    assert (T, MB) == (8, 16)
    slots = np.asarray([5, 16, -1, 40])
    eng._count_sparse_decode(slots)
    st = eng.stats()
    assert st["sparse_decode_pages_walked_total"] == (1 + 3 + 6) * layers \
        == int((slots[slots >= 0] // T + 1).sum()) * layers
    assert st["sparse_decode_pages_table_total"] == 3 * MB * layers
    eng._count_sparse_decode(np.asarray([-1, -1]))
    assert eng.stats()["sparse_decode_pages_walked_total"] \
        == st["sparse_decode_pages_walked_total"]
    assert eng.stats()["sparse_decode_pages_table_total"] \
        == st["sparse_decode_pages_table_total"]
    # the wrapper's trip counts: the scalar-prefetched operand of the call
    with mock.patch.object(sla, "pages_walked",
                           wraps=sla.pages_walked) as asked:
        jax.make_jaxpr(lambda: sla.sparse_latent_decode(
            jnp.zeros((4, 8, 128)), jnp.zeros((1, 9, T, 128)),
            jnp.zeros((4, MB), jnp.int32), jnp.zeros((4, MB * T)),
            jnp.asarray(slots), 0, rc=96, sm_scale=1.0, interpret=True))()
    assert asked.call_count == 1 and asked.call_args.args[1:] == (T, MB)


def test_kth_largest_is_exact_without_a_sort():
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 200), jnp.float32)
    x = x.at[:, :, 150:].set(-jnp.inf).at[0, 0, :10].set(0.25)
    x = x.at[1, 1].set(-jnp.inf).at[2, 2, :195].set(-jnp.inf)
    for k in (1, 7, 64, 150, 200):
        want = jax.lax.top_k(x, k)[0][..., -1]
        np.testing.assert_array_equal(np.asarray(mla.kth_largest(x, k)),
                                      np.asarray(want))


def test_selection_mask_is_the_top_ks_own_members():
    """`select_mask` marks exactly the slots `lax.top_k` returns, ties at
    the k-th value included (the lowest slots win, as many as it took)."""
    cfg = dataclasses.replace(CFG, index_topk=4)
    scores = jnp.asarray([[[5., 1., 3., 3., 3., 3., 0., -jnp.inf],
                           [2., 2., -jnp.inf, -jnp.inf, -jnp.inf,
                            -jnp.inf, -jnp.inf, -jnp.inf]]])
    orig = mla.indexer_scores
    mla.indexer_scores = lambda *a, **k: scores
    try:
        bias = mla.select_mask(None, None, None, None, None, 0, cfg)
    finally:
        mla.indexer_scores = orig
    assert (np.asarray(bias) == 0).tolist() == [[
        [True, False, True, True, True, False, False, False],
        [True, True, False, False, False, False, False, False]]]
    assert (np.asarray(bias)[np.asarray(bias) != 0] < -1e29).all()


# -- the selection's kernel against the lax form -------------------------------

# 4 heads of 32, pages of 32 slots, a table of 8 (256 slots), the top 64
_SEL_T, _SEL_MB, _SEL_K, _SEL_H, _SEL_D, _SEL_L = 32, 8, 64, 4, 32, 2
_SEL_SPAN = _SEL_T * _SEL_MB
_SEL_CFG = MlaConfig.nano_mla(index_n_heads=_SEL_H, index_head_dim=_SEL_D,
                              index_topk=_SEL_K)
# a decode batch: each row's (last) slot
_SEL_TOKEN = {"asks_nothing": -1, "slot_0": 0, "k_minus_1": _SEL_K - 1,
              "k": _SEL_K, "k_plus_1": _SEL_K + 1,
              "page_end": _SEL_K + _SEL_T - 1, "page_start": _SEL_K + _SEL_T,
              "table_end": _SEL_SPAN - 1}
# chunks of 32 queries (two blocks of 16): (slot of the first, real ones)
_SEL_CHUNK = {"below_k": (0, 32), "straddles_k": (_SEL_K - 24, 32),
              "straddles_k_in_a_block": (_SEL_K - 8, 32),
              "filler_tail": (100, 21), "one_block_of_filler": (70, 16),
              "all_filler": (0, 0), "table_end": (_SEL_SPAN - 32, 32)}


def _sel_inputs(q_slots, seed, whole=False):
    """(qi, wt, q_slots, pool, bt): seeded float32 queries, weights and
    an index plane of two layers whose rows' pages are scattered; with
    ``whole`` every value is a small integer, so every sum is exact in
    any order and keys repeat (ties at the k-th value)."""
    rng = np.random.default_rng(seed)
    B, S = q_slots.shape
    nb = 1 + B * _SEL_MB

    def draw(*shape):
        if whole:
            return rng.integers(-2, 3, size=shape).astype(np.float32)
        return rng.standard_normal(shape).astype(np.float32)

    pool = draw(_SEL_L, nb, _SEL_T, _SEL_D)
    bt = 1 + rng.permutation(B * _SEL_MB).reshape(B, _SEL_MB)
    # (the weights as `_attention` scales them: scores of order 1)
    scale = np.float32(1.0 if whole else (_SEL_H * _SEL_D) ** -0.5)
    return (jnp.asarray(draw(B, S, _SEL_H, _SEL_D)),
            jnp.asarray(draw(B, S, _SEL_H) * scale),
            jnp.asarray(q_slots, jnp.int32),
            jnp.asarray(pool), jnp.asarray(bt, jnp.int32))


def _sel_both(q_slots, seed=0, whole=False):
    """The lax form's scores and mask and the kernel's (interpret mode),
    layer 1 of the plane."""
    from ray_tpu.ops import indexer_select as isel

    args = _sel_inputs(q_slots, seed, whole)
    return {
        "q_slots": np.asarray(q_slots),
        "scores": np.asarray(mla.indexer_scores(*args, 1, _SEL_CFG)),
        "mask": np.asarray(mla.select_mask(*args, 1, _SEL_CFG)) == 0,
        # (topk 0: every block walks to its last slot, whatever it is)
        "kernel_scores": np.asarray(isel.indexer_select(
            *args, 1, topk=0, interpret=True, scores_only=True)),
        "kernel_mask": np.asarray(isel.indexer_select(
            *args, 1, topk=_SEL_K, interpret=True)) == 0}


@pytest.fixture(scope="module")
def selected_tokens():
    return _sel_both(np.asarray(list(_SEL_TOKEN.values()))[:, None])


@pytest.fixture(scope="module")
def selected_chunks():
    at = np.arange(32)[None, :]
    first, real = (np.asarray(x)[:, None]
                   for x in zip(*_SEL_CHUNK.values()))
    return _sel_both(np.where(at < real, first + at, -1), seed=1)


def _check_selection(both, row):
    """Row ``row`` of both forms: the kernel's scores are the lax form's
    on the lanes a query sees and -inf elsewhere, it chose exactly
    ``min(t + 1, k)`` slots, and the SAME slots wherever the k-th value
    stands clear of the next one."""
    t = both["q_slots"][row]                              # [S]
    want, got = both["scores"][row], both["kernel_scores"][row]
    seen = np.arange(_SEL_SPAN)[None, :] <= t[:, None]
    assert np.isneginf(got[~seen]).all() and np.isneginf(want[~seen]).all()
    np.testing.assert_allclose(got[seen], want[seen], atol=1e-6, rtol=1e-6)
    chosen = both["kernel_mask"][row]
    assert not chosen[~seen].any()
    assert (chosen.sum(-1) == np.minimum(t + 1, _SEL_K)).all()
    ranked = -np.sort(-want, axis=-1)
    with np.errstate(invalid="ignore"):     # (-inf less -inf)
        clear = np.isneginf(ranked[:, _SEL_K]) \
            | (ranked[:, _SEL_K - 1] - ranked[:, _SEL_K] > 1e-6)
    assert clear.sum() >= len(t) - 1
    np.testing.assert_array_equal(chosen[clear], both["mask"][row][clear])


@pytest.mark.parametrize("case", list(_SEL_TOKEN))
def test_selection_kernel_is_the_lax_form_for_a_decode_token(
        selected_tokens, case):
    """One decode call whose rows end at every edge of the walk: a row
    that asks nothing, slots 0, k - 1 (the last with no choice to make:
    no page walked), k and k + 1 (the first that drop a slot), the last
    slot of a page, the first of the next, the table's last."""
    _check_selection(selected_tokens, list(_SEL_TOKEN).index(case))


@pytest.mark.parametrize("case", list(_SEL_CHUNK))
def test_selection_kernel_is_the_lax_form_for_a_prefill_chunk(
        selected_chunks, case):
    """One prefill call of 32 queries a row (two blocks of 16): chunks
    wholly below k, straddling it between blocks and inside one, with
    bucket filler (slot -1) at the tail, as a whole block and as the whole
    row, and at the table's end."""
    _check_selection(selected_chunks, list(_SEL_CHUNK).index(case))


@pytest.mark.parametrize("queries", [1, 16], ids=["token", "chunk"])
def test_selection_kernel_breaks_ties_as_the_lax_form_does(queries):
    """Small whole numbers everywhere: every sum is exact in any order, so
    both forms hold the same scores bit for bit, many of them equal. Of
    the slots AT the k-th value the lowest are kept, as many as there is
    room for: the kernel's running count across pages."""
    first = np.asarray([_SEL_K + 3, 150, _SEL_SPAN - queries])[:, None]
    both = _sel_both(first + np.arange(queries)[None, :], seed=2,
                     whole=True)
    ranked = -np.sort(-both["scores"], axis=-1)
    assert (ranked[..., _SEL_K - 1] == ranked[..., _SEL_K]).any()
    np.testing.assert_array_equal(both["kernel_scores"], both["scores"])
    np.testing.assert_array_equal(both["kernel_mask"], both["mask"])


def test_selection_kernel_reads_nothing_past_a_rows_last_page():
    """NaN in every block no row can see (the null block, the blocks
    behind table entries past a row's last slot): the same mask."""
    from ray_tpu.ops import indexer_select as isel

    q_slots = np.asarray([[_SEL_K + 5], [3 * _SEL_T - 1], [-1], [7]])
    qi, wt, qs, pool, bt = _sel_inputs(q_slots, seed=3)
    live = np.zeros(pool.shape[1], bool)
    for row, t in zip(np.asarray(bt), q_slots[:, 0]):
        live[row[:t // _SEL_T + 1 if t >= 0 else 0]] = True
    dirty = jnp.where(live[None, :, None, None], pool, jnp.nan)
    clean, got = (np.asarray(isel.indexer_select(
        qi, wt, qs, p, bt, 0, topk=_SEL_K, interpret=True))
        for p in (pool, dirty))
    np.testing.assert_array_equal(got, clean)
    assert ((got == 0).sum(-1)[:, 0] == [_SEL_K, _SEL_K, 0, 8]).all()


def test_selection_counters_are_a_hand_count_for_three_rows(params):
    """A decode dispatch of three rows at slots 5, 16 and 40 (blocks of 8,
    a table of 16, the top 16) walks 0, 3 and 6 pages a layer of its 3 x
    16 entries, and the first query alone has nothing to choose; a chunk
    of 16 queries at slots 8..23 walks 3 of 16 and half of it has."""
    eng = engine(params)
    layers = CFG.n_select_layers
    eng._count_selection(np.asarray([[5], [16], [40]]), decode=True)
    st = eng.stats()
    assert st["indexer_pages_walked_total"] == (0 + 3 + 6) * layers
    assert st["indexer_pages_table_total"] == 3 * 16 * layers
    assert st["indexer_queries_total"] == 3 * layers
    assert st["indexer_queries_unselected_total"] == 1 * layers
    assert st["indexer_decode_tokens_scored_total"] == (6 + 17 + 41) * layers
    eng._count_selection(8 + np.arange(16)[None, :])
    now = eng.stats()
    assert now["indexer_pages_walked_total"] == (9 + 3) * layers
    assert now["indexer_pages_table_total"] == 4 * 16 * layers
    assert now["indexer_queries_total"] == (3 + 16) * layers
    assert now["indexer_queries_unselected_total"] == (1 + 8) * layers


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
    """Four requests on two slots: while a request waits behind two rows
    whose budgets outlast the block in flight the ring stays a block
    ahead (no flush: nobody can be admitted), and every call returns what
    the synchronous engine's call returns."""
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
    assert stats[2]["pipeline_flushes"] == stats[1]["pipeline_flushes"] == 0
    assert stats[2]["preemptions"] == 0
    assert stats[1]["decode_dispatches_chained"] == 0


def test_preempt_recompute_mid_decode_gives_the_same_tokens(params):
    """A pool too small for both rows: one is preempted while it decodes,
    its blocks of both planes dropped, and rebuilt by prefill of prompt +
    tokens."""
    work = [(prompt_of(20, seed=5), 60), (prompt_of(24, seed=6), 60)]
    roomy = engine(params)
    want = [roomy.submit(p, max_new_tokens=m) for p, m in work]
    want_out = roomy.run()
    block = sum(pl.block_bytes(T) for pl in CFG.cache_planes())
    tight = engine(params, kv_pool_bytes=14 * block)
    got = [tight.submit(p, max_new_tokens=m) for p, m in work]
    got_out = tight.run()
    assert tight.stats()["preemptions"] >= 1
    assert [got_out[r] for r in got] == [want_out[r] for r in want]
    assert tight.kv_pool.blocks_in_use == 0


# -- what a token stores ---------------------------------------------------------

def test_the_pools_are_the_configs_planes(params):
    """A latent plane and an index plane for every layer behind one table,
    no value plane; the engine prices a block from them."""
    eng = engine(params, kv_pool_bytes=1 << 20)
    latent, index = CFG.cache_planes()
    assert (latent.name, latent.layers, latent.lanes) == ("latent", 3, 128)
    assert (index.name, index.layers, index.lanes) == ("index", 3, 16)
    nb = eng.kv_pool.n_blocks
    assert eng._pool_k.shape == (3, nb, T, 128)
    assert eng._pool_v.shape == (3, nb, T, 16)
    assert eng.kv_bytes_per_token == 3 * (128 + 16) * 4
    assert nb == 1 + (1 << 20) // (T * 3 * (128 + 16) * 4)
    assert eng._kv_geometry == (("latent", 3, 128), ("index", 3, 16))


def test_published_latent_row_is_priced_with_its_padding():
    cfg = MlaConfig.deepseek_v32_exp(n_layers=5, n_dense_layers=1)
    latent, index = cfg.cache_planes()
    assert latent.lanes == 640 and index.lanes == 128   # 576 -> a lane tile
    assert latent.block_bytes(64) + index.block_bytes(64) \
        == 64 * 5 * (640 + 128) * 2


@pytest.mark.parametrize("family", ["llama", "moe", "hybrid"])
def test_the_other_families_planes_are_what_their_pools_were(family):
    from ray_tpu.models import HybridConfig, LlamaConfig, MoeConfig
    from ray_tpu.models.prefix_cache import block_bytes

    cfg = {"llama": LlamaConfig.nano, "moe": MoeConfig.nano_moe,
           "hybrid": HybridConfig.nano_hybrid}[family]()
    planes = [pl for pl in cfg.cache_planes() if pl.table == "full"]
    layers = 1 if family == "hybrid" else cfg.n_layers
    assert [(pl.layers, pl.lanes) for pl in planes] \
        == [(layers, cfg.n_kv_heads * cfg.head_dim)] * 2
    assert sum(pl.block_bytes(16) for pl in planes) == block_bytes(
        layers, 16, cfg.n_kv_heads, cfg.head_dim, planes[0].dtype.itemsize)


def test_layer_plan_is_dense_then_expert_layers():
    plan = MlaConfig.deepseek_v32_exp().layer_plan()
    assert [(s.name, s.periods, s.first_layer) for s in plan] \
        == [("dense", 3, 0), ("moe", 58, 3)]
    assert all(s.kinds == (mla.MLA,) for s in plan)
    assert mla.MLA.writes == mla.MLA.reads == "latent"


# -- held experts, the shared one, the router -------------------------------------

def _full_layer(key, cfg_all):
    """One expert layer's parameters with ALL experts, float32."""
    p = mla_init(key, dataclasses.replace(cfg_all, held_experts=None))
    return jax.tree_util.tree_map(lambda x: x[0], p["moe"])


def test_the_shares_add_up_to_the_uncut_layer():
    """The share test: the 4 shares' partial results of one expert layer
    (held ranges [0,4) .. [12,16), the shared expert counted ONCE) add up
    to the uncut reference's layer output; the program's share is the
    reference's share."""
    cfg_all = dataclasses.replace(CFG, held_experts=None)
    layer = _full_layer(jax.random.PRNGKey(3), cfg_all)
    u = jax.random.normal(jax.random.PRNGKey(4), (37, CFG.dim), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(u, layer, MODEL)
        shared = ref.expert_layer(u, layer, MODEL, held=(0, 0))
    total = jnp.zeros_like(whole)
    for lo in range(0, CFG.n_experts, 4):
        held = (lo, lo + 4)
        mine = dict(layer, **{k: layer[k][lo:lo + 4]
                              for k in ("we_gate", "we_up", "we_down")})
        with jax.default_matmul_precision("highest"):
            want = ref.expert_layer(u, mine, MODEL, held=held, shared=False)
        cfg = dataclasses.replace(CFG, held_experts=held,
                                  n_shared_experts=0)
        got, _ = moe.moe_ffn_dropless(u[None], mine, cfg)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   atol=2e-5, rtol=0)
        total = total + got[0]
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), atol=5e-5, rtol=0)


# what differs from CFG, held, layers in the stack, this layer, the least
# slack: the nano DeepSeek share (4 of 16, the layer's own stacks) and
# Qwen3-Next's ratio (128 of 512, 10 a token, the second of three layers'
# stacks, the others' NaN: no form may read them; 10 windows of 75 rows,
# not 94 of 8)
_SHARES = {
    "dsv32": ({}, (4, 8), 1, 0, 0.0),
    "qwen3next": ({"n_experts": 512, "top_k": 10, "n_group": 1,
                   "topk_group": 1}, (128, 256), 3, 1, 0.1)}


@pytest.mark.parametrize("share", list(_SHARES))
@pytest.mark.parametrize("tokens,slack", [(40, 2.0), (300, 2.0),
                                          (300, 0.01)],
                         ids=["all_experts", "sorted", "sorted_many_windows"])
def test_held_layer_forms_agree_for_any_routing(tokens, slack, share,
                                                monkeypatch):
    """Few tokens multiply every held expert, many sort the assignments
    that landed and work through them a window at a time; a window far
    too small for what landed takes many turns and drops nothing."""
    other, (lo, hi), n_stack, li, least = _SHARES[share]
    slack = max(slack, least)
    monkeypatch.setattr(moe, "HELD_ROWS_SLACK", slack)
    monkeypatch.setattr(moe, "_HELD_ROWS_ALIGN", 8)
    cfg_all = dataclasses.replace(CFG, held_experts=None, **other)
    top_k = cfg_all.top_k
    model = model_of(cfg_all)
    layer = _full_layer(jax.random.PRNGKey(5), cfg_all)
    stacks = ("we_gate", "we_up", "we_down")
    mine = dict(layer, **{k: layer[k][lo:hi] for k in stacks})
    u = jax.random.normal(jax.random.PRNGKey(6), (tokens, CFG.dim),
                          jnp.float32)
    cfg = dataclasses.replace(cfg_all, held_experts=(lo, hi))
    live = jnp.ones((1, tokens), bool)
    stacked = dict(mine, **{k: jnp.concatenate(
        [mine[k] if i == li else jnp.full_like(mine[k], jnp.nan)
         for i in range(n_stack)]) for k in stacks})
    got, stats = moe.moe_ffn_dropless(
        u[None], stacked, cfg, live=live,
        expert_stack_layer=li if n_stack > 1 else None)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(u, mine, model, held=(lo, hi))
        _, idx = ref.route(u, layer, model)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=5e-5, rtol=0)
    landed = int(((idx >= lo) & (idx < hi)).sum())
    assert int(stats[0]) == tokens * top_k and int(stats[3]) == landed
    if tokens > moe.DENSE_HELD_MAX_TOKENS:
        assert int(stats[1]) >= landed           # rows the matmuls covered
        # the grouped kernel's 128-row visits: in one window at most a
        # tile more a group than what landed fills
        assert moe.held_grouped_prefill(cfg, tokens)
        assert int(stats[1]) % 128 == 0
        if slack == 2.0:
            assert int(stats[1]) <= 128 * (-(-landed // 128) + hi - lo)


def test_counters_count_routed_and_landed(params):
    eng = engine(params)
    rid = eng.submit(prompt_of(33, seed=4), max_new_tokens=17)
    eng.run()[rid]
    st = eng.stats()
    # every live position of every expert layer routes top_k assignments
    # (the last emitted token is never fed)
    assert st["moe_assignments_total"] \
        == (33 + 16) * CFG.top_k * CFG.n_moe_layers
    assert 0 < st["moe_assignments_landed_total"] \
        < st["moe_assignments_total"]
    # a host estimate at dispatch, like the paged-walk counters: a fused
    # dispatch is counted whole, also the iterations a row froze in
    scored = st["indexer_tokens_scored_total"] / CFG.n_layers
    assert sum(range(1, 33 + 16 + 1)) <= scored \
        <= sum(range(1, 33 + 16 + 8 + 1))


def test_an_moe_config_counts_all_its_assignments_as_landed():
    from ray_tpu.models import MoeConfig, moe_init

    cfg = MoeConfig.nano_moe()
    p = jax.jit(moe_init, static_argnums=1)(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(p, cfg, batch_slots=2, max_len=64,
                       kv_block_tokens=8)
    eng.submit(prompt_of(9), max_new_tokens=5)
    eng.run()
    st = eng.stats()
    assert st["moe_assignments_landed_total"] \
        == st["moe_assignments_total"] > 0
    assert st["indexer_tokens_scored_total"] == 0


# -- refusals -------------------------------------------------------------------------

def _draft(params):
    return dict(draft_params=params, draft_cfg=CFG)


@pytest.mark.parametrize("how,kw,names", [
    ("prefix_cache", dict(prefix_cache=True), "ROADMAP M3"),
    ("kv_quant", dict(kv_quant="int8"), "quantized write"),
    ("swap", dict(preempt="swap"), "swap ledger"),
    ("default_preempt", dict(preempt=None), "swap ledger"),
    ("tp", dict(tp=1), "sharding rule"),
    ("mesh", dict(mesh="any"), "sharding rule"),
    ("lora", dict(lora=LoraConfig(rank=2)), "adapter targets"),
    ("speculative", _draft, "ROADMAP M7"),
])
def test_what_an_mla_config_refuses_at_construction(params, how, kw, names):
    kw = kw(params) if callable(kw) else dict(kw)
    base = dict(batch_slots=2, max_len=64, kv_block_tokens=T,
                preempt="recompute")
    base.update(kw)
    if base["preempt"] is None:
        del base["preempt"]                 # the engine's default is swap
    with pytest.raises(ValueError, match="MlaConfig cannot be served") as e:
        DecodeEngine(params, CFG, **base)
    assert names in str(e.value)


@pytest.mark.parametrize("call", ["export_request", "import_request"])
def test_an_mla_engine_refuses_a_hand_off(params, call):
    eng = engine(params)
    with pytest.raises(ValueError, match="latent and an"):
        getattr(eng, call)(0 if call == "export_request" else {})

"""Serving a `HybridConfig` (state-space, window, full and shared-cache
layers in one stack) through the one engine: prefill then decode through
two block pools and the recurrent state gives the LOGITS of the plain
float32 reference's full forward (benchmark/reference/phi4flash_hybrid.py),
whatever the chunking, the slot's history, the horizon or a preemption.

Everything here is float32 at nano widths on the CPU: 8 layers (2 x
[state-space, window], [state-space, full], 1 x [memory unit, cross]),
hidden 64, 4 / 2 heads of 16, window 16, blocks of 8 tokens, chunks of 16.
"""

import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.reference import phi4flash_hybrid as ref  # noqa: E402
from ray_tpu.models import HybridConfig, hybrid_init  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402
from ray_tpu.models.engine import DecodeEngine  # noqa: E402
from ray_tpu.models.generate import generate, generate_stream  # noqa: E402
from ray_tpu.models.lora import LoraConfig  # noqa: E402
from ray_tpu.ops.attention import paged_attention  # noqa: E402

CFG = HybridConfig.nano_hybrid()
T, CHUNK, TOL = 8, 16, 2e-5


def model_of(cfg):
    """The reference's view of a config: the published key names."""
    return {"hidden_size": cfg.dim, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "num_hidden_layers": cfg.n_layers,
            "mb_per_layer": cfg.mb_per_layer,
            "sliding_window": cfg.sliding_window,
            "layer_norm_eps": cfg.norm_eps, "tie_word_embeddings": True,
            "mlp_bias": False, "lm_head_bias": False,
            "assumed": {"head_dim": cfg.head_dim,
                        "mamba_d_state": cfg.d_state,
                        "mamba_d_conv": cfg.d_conv,
                        "mamba_expand": cfg.expand,
                        "mamba_dt_rank": cfg.rank}}


MODEL = model_of(CFG)


@pytest.fixture(scope="module")
def params():
    return jax.jit(hybrid_init, static_argnums=1)(jax.random.PRNGKey(0),
                                                  CFG)


def engine(params, **kw):
    kw = {"batch_slots": 2, "max_len": 128, "kv_block_tokens": T,
          "prefill_chunk": CHUNK, "preempt": "recompute",
          "pipeline_depth": 1, **kw}
    return DecodeEngine(params, CFG, **kw)


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, size=n).tolist()


def served_logits(eng, prompt, n_new):
    """One request through submit/step at horizon 1: its tokens, and the
    engine's device-resident next-token logits after each token it fed
    (the logits at positions P .. P + n_new - 2 of prompt + tokens)."""
    rid = eng.submit(prompt, max_new_tokens=n_new)
    seen = []
    while rid not in eng.finished:
        eng.step(horizon=1)
        rows = [b for b, r in enumerate(eng.row_req)
                if r is not None and r.req_id == rid]
        if rows and rows[0] not in eng._row_prefill:
            seen.append(np.asarray(eng._last_logits[rows[0]]))
    return eng.pop_result(rid), seen


def reference_logits(params, seq):
    return np.asarray(ref.logits(params, jnp.asarray(seq, jnp.int32)[None],
                                 MODEL))[0]


def assert_serves_the_reference(params, prompt, toks, seen):
    P = len(prompt)
    want = reference_logits(params, prompt + toks)
    # the prefill's logits are consumed on the device: the first token is
    # their argmax
    assert want[P - 1].max() - want[P - 1][toks[0]] <= TOL
    assert len(seen) == len(toks) - 1
    for j, got in enumerate(seen):
        np.testing.assert_allclose(got, want[P + j], atol=TOL, rtol=0)


# -- the engine against the reference, logits -------------------------------

@pytest.mark.parametrize("n_prompt,n_new", [
    (5, 10),       # shorter than a chunk
    (40, 12),      # longer than two chunks: state handed over twice
    (9, 60),       # 69 tokens > window 16 + 3 blocks: blocks are freed
    (37, 40),      # both
], ids=["short", "three_chunks", "past_the_window", "chunks_and_window"])
def test_prefill_then_decode_gives_the_reference_logits(params, n_prompt,
                                                        n_new):
    eng = engine(params)
    prompt = prompt_of(n_prompt, seed=n_prompt)
    toks, seen = served_logits(eng, prompt, n_new)
    assert_serves_the_reference(params, prompt, toks, seen)
    if n_prompt + n_new > CFG.sliding_window + 3 * T:
        assert eng.stats()["window_blocks_freed_total"] > 0


def test_chunks_hand_the_state_over_exactly(params):
    """A prompt in chunks of 16 (bucket filler in the last) and the same
    prompt in one piece leave the same state behind: every later logit
    agrees to rounding, and with the reference."""
    prompt = prompt_of(43, seed=3)
    t1, l1 = served_logits(engine(params), prompt, 8)
    t2, l2 = served_logits(engine(params, prefill_chunk=None), prompt, 8)
    assert t1 == t2
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(a, b, atol=TOL, rtol=0)
    assert_serves_the_reference(params, prompt, t1, l1)


def test_a_reused_slot_gives_a_fresh_engines_logits(params):
    """One slot, two requests one after the other: the second starts
    from zero state and from its own blocks, bit for bit what a fresh
    engine gives it."""
    eng = engine(params, batch_slots=1)
    served_logits(eng, prompt_of(30, seed=1), 25)
    second = prompt_of(21, seed=2)
    toks, seen = served_logits(eng, second, 20)
    fresh_toks, fresh = served_logits(engine(params, batch_slots=1),
                                      second, 20)
    assert toks == fresh_toks
    for a, b in zip(seen, fresh):
        np.testing.assert_array_equal(a, b)
    assert eng.stats()["ssm_state_resets_total"] == 2


def test_batch_companions_and_frozen_rows_change_nothing(params):
    """Rows of unlike lengths share the fused programs: a row that is
    frozen, finished, mid-prefill or bucket filler advances nobody's
    state. Every request's tokens are its solo tokens, and solo
    `generate` (batch and stream) agrees."""
    work = [(prompt_of(n, seed=10 + n), m)
            for n, m in ((5, 30), (40, 9), (17, 45), (33, 3), (9, 20))]
    eng = engine(params, batch_slots=3, decode_horizon=4,
                 max_prefills_per_step=2)
    ids = [eng.submit(p, max_new_tokens=m) for p, m in work]
    out = eng.run()
    for rid, (p, m) in zip(ids, work):
        solo = np.asarray(generate(params, jnp.asarray([p], jnp.int32), CFG,
                                   max_new_tokens=m))[0, len(p):].tolist()
        assert out[rid] == solo
        margin = np.asarray(ref.margins(params, jnp.asarray(p + out[rid]),
                                        len(p), MODEL))
        assert margin.max() <= TOL
    p, m = work[2]
    stream = [int(t[0]) for t in generate_stream(
        params, jnp.asarray([p], jnp.int32), CFG, max_new_tokens=m)]
    assert stream == out[ids[2]]


def test_preempt_recompute_mid_decode_gives_the_same_tokens(params):
    """A full-layer pool too small for both rows: one is preempted while
    it decodes, its blocks and state dropped, and rebuilt by prefill of
    prompt + tokens."""
    work = [(prompt_of(20, seed=5), 60), (prompt_of(24, seed=6), 60)]
    roomy = engine(params)
    want = [roomy.submit(p, max_new_tokens=m) for p, m in work]
    want_out = roomy.run()
    block = 2 * T * CFG.n_kv_heads * CFG.head_dim * 4        # one layer
    tight = engine(params, kv_pool_bytes=14 * block)
    got = [tight.submit(p, max_new_tokens=m) for p, m in work]
    got_out = tight.run()
    assert tight.stats()["preemptions"] >= 1
    assert [got_out[r] for r in got] == [want_out[r] for r in want]
    assert tight.kv_pool.blocks_in_use == 0
    assert tight.kv_pool_w.blocks_in_use == 0


@pytest.mark.parametrize("horizon,depth", [(8, 1), (8, 2), (2, 2)])
def test_the_fused_horizon_and_the_ring_agree_with_horizon_1(params,
                                                             horizon,
                                                             depth):
    work = [(prompt_of(12, seed=7), 50), (prompt_of(35, seed=8), 33)]
    base = engine(params)
    ids = [base.submit(p, max_new_tokens=m) for p, m in work]
    while base.pending():
        base.step(horizon=1)
    want = [base.pop_result(r) for r in ids]
    eng = engine(params, decode_horizon=horizon, pipeline_depth=depth)
    ids = [eng.submit(p, max_new_tokens=m) for p, m in work]
    out = eng.run()
    assert [out[r] for r in ids] == want
    if depth > 1:
        assert eng.stats()["pipeline_depth_effective"] > 1.0


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
    assert eng.kv_pool_w.blocks_in_use == 0
    assert calls[2] == calls[1]
    assert stats[2]["decode_dispatches_chained_queued"] >= 4
    assert stats[2]["pipeline_flushes"] == stats[1]["pipeline_flushes"] == 0
    assert stats[2]["preemptions"] == 0
    assert stats[1]["decode_dispatches_chained"] == 0


def test_a_bucket_floor_changes_programs_not_tokens(params):
    """`min_prefill_bucket=8`: a chunk of 1 or 3 tokens (a prompt's
    remainder past a full chunk) runs in the bucket of 8, filler and all,
    and every token is the floorless engine's."""
    work = [(prompt_of(n, seed=20 + n), 12) for n in (3, 17, 35, 6)]
    outs = []
    for floor in (1, 8):
        eng = engine(params, min_prefill_bucket=floor)
        ids = [eng.submit(p, max_new_tokens=m) for p, m in work]
        out = eng.run()
        outs.append([out[r] for r in ids])
        assert [eng._bucket(n) for n in (1, 3, 8, 9, 16)] == (
            [1, 4, 8, 16, 16] if floor == 1 else [8, 8, 8, 16, 16])
    assert outs[0] == outs[1]
    with pytest.raises(ValueError, match="power of two"):
        engine(params, min_prefill_bucket=12)


def test_the_window_pool_stays_bounded_as_a_row_grows(params):
    """A row of 120 tokens never holds more window blocks than the
    window, misaligned, and the dispatches in flight need; the full pool
    grows with the row."""
    eng = engine(params, batch_slots=1, decode_horizon=4, pipeline_depth=2)
    eng.submit(prompt_of(10, seed=9), max_new_tokens=110)
    held, full = [], []
    while eng.pending():
        eng.step()
        held.append(eng.kv_pool_w.blocks_in_use)
        full.append(eng.kv_pool.blocks_in_use)
    bound = CFG.sliding_window // T + 3
    assert max(held) <= bound
    assert max(held[len(held) // 2:]) <= bound
    assert max(full) == 120 // T
    st = eng.stats()
    assert st["window_pool_peak_blocks"] == max(held)
    assert st["window_blocks_freed_total"] >= 120 // T - bound
    assert st["window_pool_blocks_in_use"] == 0


def test_counters_count_what_the_layer_plan_says(params):
    """40 prompt tokens in chunks of 16, 16 and 8: 8 layers a token a
    dense stack would run; the 2 cross-decoder layers skip every position
    but the last of the last chunk. Decode asks the full cache once a
    reader (the full layer and one cross-attention layer here)."""
    eng = engine(params, batch_slots=1)
    eng.submit(prompt_of(40, seed=4), max_new_tokens=3)
    while eng.pending():
        eng.step(horizon=1)
    st = eng.stats()
    assert st["prefill_layer_tokens_total"] == 40 * 8
    assert st["prefill_layer_tokens_skipped_total"] == 39 * 2
    assert st["ssm_state_resets_total"] == 1
    # the host counts at dispatch, pessimistically: three dispatches of
    # one token, fed at slots 40, 41 and (the row's last token, which
    # freezes it on the device) 42
    slots = np.arange(40, 40 + int(st["decode_dispatches"]))
    assert st["ssm_row_steps_total"] == len(slots)
    assert st["kv_walk_tokens_full_total"] == 2 * (slots + 1).sum()
    assert st["kv_walk_tokens_window_total"] == 2 * 16 * len(slots)


def test_layer_plan_is_the_published_pattern():
    cfg = HybridConfig.phi4_mini_flash()
    kinds = cfg.layer_kinds()
    assert len(kinds) == 32
    assert [k.mixer for k in kinds[:18]] == ["ssm", "attn"] * 9
    assert [k.writes for k in kinds[1:16:2]] == ["window"] * 8
    assert kinds[17] == hybrid.FULL_ATTN
    assert [k.mixer for k in kinds[18:]] == ["gmu", "cross"] * 7
    assert sum(k.writes is not None for k in kinds) == 9
    assert sum(k.state == "ssm" for k in kinds) == cfg.n_ssm_layers == 9
    assert cfg.prefill_layers() == 18 and cfg.rank == 160
    assert cfg.full_cache_readers == 8
    # 3.85 B: 8 x (Mamba + window) + Mamba + full + 7 x (GMU + cross)
    assert round(cfg.num_params() / 1e9, 2) == 3.85
    shapes = jax.eval_shape(lambda: hybrid_init(jax.random.PRNGKey(0),
                                                cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == cfg.num_params()


def test_state_space_init_keeps_state_alive(params):
    """`A_log = log(1..N)`, `dt` in [1e-3, 1e-1], `D = 1`: the slowest
    state of a channel decays by exp(-dt) a token, so it is still a
    third of itself after 1 / dt >= 10 and up to 1,000 tokens."""
    m = params["mid"]["mamba"]
    dt = np.asarray(jax.nn.softplus(m["b_dt"]))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    np.testing.assert_allclose(np.exp(np.asarray(m["a_log"]))[:, 0],
                               np.arange(1, CFG.d_state + 1), rtol=1e-6)
    assert (np.asarray(m["d"]) == 1).all()


# -- the kernel: a window start, in interpret mode --------------------------

@pytest.mark.parametrize("n_slots", [1, 24], ids=["decode", "chunk"])
def test_kernel_walks_from_the_first_live_page(n_slots):
    """The Pallas kernel with a window against the pure-lax lowering, on
    rows whose table has NOTHING behind the window (entries point at the
    null block, as the engine leaves them): pair layout (keys of 16 in
    lanes of 32), a frontier inside a page, a row shorter than the window,
    bucket filler."""
    rng = np.random.default_rng(0)
    B, H, KV, D, Tk, MB, NB, W, L = 4, 4, 1, 32, 8, 12, 40, 20, 2
    kp = jnp.asarray(rng.normal(size=(L, NB, Tk, KV * D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(L, NB, Tk, KV * D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, n_slots, H, D)), jnp.float32)
    first = np.array([61, 5, 30, 47])[:, None] if n_slots == 1 \
        else np.array([40, 0, 16, 64])[:, None]
    slots = first + np.arange(n_slots)[None, :]
    if n_slots > 1:
        slots[3, 10:] = -1                         # bucket filler
    bt = np.zeros((B, MB), np.int32)
    ids = iter(rng.permutation(np.arange(1, NB)).tolist())
    for b in range(B):
        live = slots[b][slots[b] >= 0]
        lo = max(0, live.min() - W + 1) // Tk
        for j in range(lo, live.max() // Tk + 1):
            bt[b, j] = next(ids)
    kw = dict(layer=1, kv_valid_len=MB * Tk, sm_scale=0.25, window=W)
    want = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(slots),
                           impl="reference", **kw)
    got = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(slots),
                          impl="flash", **kw)
    keep = np.asarray(slots >= 0)
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(want)[keep],
                               atol=2e-5, rtol=2e-5)
    # and the window bites: without it the result is another
    free = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(slots),
                           impl="reference", **dict(kw, window=None))
    assert np.abs(np.asarray(free) - np.asarray(want))[0].max() > 1e-3


# -- what this family refuses ------------------------------------------------

def _draft(params):
    return dict(draft_params=params, draft_cfg=CFG)


@pytest.mark.parametrize("how,kw,names", [
    ("prefix_cache", dict(prefix_cache=True), "snapshot"),
    ("swap", dict(preempt="swap"), "recurrent state"),
    ("default_preempt", dict(preempt=None), "recurrent state"),
    ("speculative", _draft, "roll-back"),
    ("kv_quant", dict(kv_quant="int8"), "quantized write"),
    ("lora", dict(lora=LoraConfig(rank=2)), "adapter targets"),
    ("tp", dict(tp=1), "sharding rule"),
    ("mesh", dict(mesh="any"), "sharding rule"),
])
def test_what_a_hybrid_config_refuses_at_construction(params, how, kw,
                                                      names):
    kw = kw(params) if callable(kw) else dict(kw)
    base = dict(batch_slots=2, max_len=64, kv_block_tokens=T,
                preempt="recompute")
    base.update(kw)
    if base["preempt"] is None:
        del base["preempt"]                 # the engine's default is swap
    with pytest.raises(ValueError, match="HybridConfig cannot be served"
                       ) as e:
        DecodeEngine(params, CFG, **base)
    assert names in str(e.value)


@pytest.mark.parametrize("call", ["export_request", "import_request"])
def test_a_hybrid_engine_refuses_a_hand_off(params, call):
    eng = engine(params)
    with pytest.raises(ValueError, match="recurrent state"):
        getattr(eng, call)(0 if call == "export_request" else {})


def test_left_padded_solo_prompts_are_refused(params):
    with pytest.raises(ValueError, match="left-padded"):
        generate(params, jnp.ones((2, 4), jnp.int32), CFG,
                 max_new_tokens=2, prompt_live=jnp.ones((2, 4), bool))

"""Engine-integrated speculative decoding (draft-propose / target-verify
inside the fused decode dispatch).

Contract under test: a DecodeEngine built with `draft_params=` emits
tokens IDENTICAL to solo `generate(greedy=True)` under every feature
combination — the draft plane only changes how many verify passes the
target model needs, never which tokens win. Greedy token-match
acceptance (Leviathan et al.) guarantees this regardless of draft
quality: a cold, stale, or adversarial draft shrinks acceptance to
zero but cannot change output. Sampled rows fall back to one
target-sampled token per round via the per-row decode-mode lane and
stay bit-identical to their solo sampled stream.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, llama_init
from ray_tpu.models.engine import DecodeEngine
from ray_tpu.models.generate import generate
from ray_tpu.models.prefix_cache import block_bytes


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    draft = llama_init(jax.random.PRNGKey(1), cfg)
    return cfg, params, draft


def _solo(params, cfg, prompt, n, **kw):
    out = np.asarray(generate(params, jnp.asarray([prompt], jnp.int32),
                              cfg, max_new_tokens=n, **kw))
    return out[0, len(prompt):].tolist()


PROMPTS = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2], [3, 1, 4, 1, 5, 9]]
BUDGETS = [4, 6, 3, 5]
T = 4   # kv_block_tokens of the small-block cases


def _pool_bytes(cfg, n_blocks):
    return n_blocks * block_bytes(cfg.n_layers, T, cfg.n_kv_heads,
                                  cfg.head_dim,
                                  jnp.dtype(cfg.dtype).itemsize)


def _features(cfg):
    pb = lambda n: _pool_bytes(cfg, n)
    return {
        "blocks32": {},                 # the default: one block a row
        "pipeline": dict(pipeline_depth=2),
        "chunked": dict(prefill_chunk=2),
        "chunked+prefix": dict(prefill_chunk=2, prefix_cache=True,
                               kv_block_tokens=T),
        "blocks4": dict(kv_block_tokens=T, kv_pool_bytes=pb(40)),
        "blocks4+prefix": dict(kv_block_tokens=T,
                               kv_pool_bytes=pb(40), prefix_cache=True),
        "blocks4+pipeline": dict(kv_block_tokens=T,
                                 kv_pool_bytes=pb(40), pipeline_depth=2),
    }


# ---------------------------------------------------------------------------
# Token identity across the feature matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("feature", ["blocks32", "pipeline", "chunked",
                                     "chunked+prefix", "blocks4",
                                     "blocks4+prefix",
                                     "blocks4+pipeline"])
def test_spec_identity_feature_matrix(nano_model, feature):
    """Independent nano draft (near-zero acceptance — the adversarial
    case for cache alignment): output must still match solo greedy
    exactly under every engine feature the spec plane composes with."""
    cfg, params, draft = nano_model
    kw = _features(cfg)[feature]
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       draft_params=draft, draft_cfg=cfg, spec_window=4,
                       **kw)
    ids = [eng.submit(p, n) for p, n in zip(PROMPTS, BUDGETS)]
    out = eng.run()
    for rid, p, n in zip(ids, PROMPTS, BUDGETS):
        assert out[rid] == _solo(params, cfg, p, n), (feature, rid)
    s = eng.stats()
    assert s["spec_enabled"] == 1.0
    assert s["spec_dispatches"] >= 1
    assert s["spec_proposed"] >= s["spec_accepted"] >= 0


def test_spec_perfect_draft_full_acceptance(nano_model):
    """Draft == target: every proposal verifies. With budgets that are
    multiples of window+1 no round truncates, so acceptance is exactly
    1.0 and each dispatch advances window+1 tokens per row."""
    cfg, params, _ = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       draft_params=params, draft_cfg=cfg, spec_window=4)
    ids = [eng.submit(p, 20) for p in PROMPTS[:2]]
    out = eng.run()
    for rid, p in zip(ids, PROMPTS[:2]):
        assert out[rid] == _solo(params, cfg, p, 20)
    s = eng.stats()
    assert s["spec_acceptance_rate"] == pytest.approx(1.0)
    assert s["spec_draft_tokens_wasted"] == 0
    assert s["spec_window_effective"] == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# Mixed greedy/sampled lanes
# ---------------------------------------------------------------------------

def test_spec_mixed_greedy_sampled(nano_model):
    """Sampled-mode engine with per-request greedy overrides: greedy
    rows ride speculation, sampled rows advance one target-sampled
    token per round on the same rng schedule as solo."""
    cfg, params, draft = nano_model
    keys = [jax.random.PRNGKey(100 + i) for i in range(4)]
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=32,
                       greedy=False, temperature=0.9, top_k=8,
                       draft_params=draft, draft_cfg=cfg, spec_window=4)
    ids = [eng.submit(p, n, rng=keys[i], greedy=(i % 2 == 0))
           for i, (p, n) in enumerate(zip(PROMPTS, BUDGETS))]
    out = eng.run()
    for i, (rid, p, n) in enumerate(zip(ids, PROMPTS, BUDGETS)):
        if i % 2 == 0:
            want = _solo(params, cfg, p, n, greedy=True)
        else:
            want = _solo(params, cfg, p, n, rng=keys[i], greedy=False,
                         temperature=0.9, top_k=8)
        assert out[rid] == want, ("mixed", i)


def test_spec_mid_window_eos(nano_model):
    """eos verified mid-window truncates the row exactly where solo
    stops; the freed slot is reused by the other request."""
    cfg, params, draft = nano_model
    solo0 = _solo(params, cfg, [5, 6, 7], 8)
    eos = solo0[2]
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       eos_id=eos, draft_params=draft, draft_cfg=cfg,
                       spec_window=4)
    r0 = eng.submit([5, 6, 7], 8)
    r1 = eng.submit([9, 8, 7, 6], 6)
    out = eng.run()
    assert out[r0] == solo0[:solo0.index(eos) + 1]
    s1 = _solo(params, cfg, [9, 8, 7, 6], 6)
    if eos in s1:
        s1 = s1[:s1.index(eos) + 1]
    assert out[r1] == s1


# ---------------------------------------------------------------------------
# Preemption and tensor parallelism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preempt", ["swap", "recompute"])
def test_spec_preempt(nano_model, preempt):
    """Tight paged pool forces a preemption mid-decode; the victim's
    draft plane is dropped with its blocks and re-seeded from
    prompt+emitted on swap-in — a cold draft is safe, so identity
    holds and preemptions actually happened."""
    cfg, params, _ = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=3, max_len=32,
                       kv_block_tokens=T,
                       kv_pool_bytes=_pool_bytes(cfg, 10),
                       preempt=preempt, draft_params=params,
                       draft_cfg=cfg, spec_window=4)
    prompts = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2, 3, 4]]
    ids = [eng.submit(p, 10) for p in prompts]
    out = eng.run()
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, 10), (preempt, rid)
    assert eng.stats()["preemptions"] >= 1


def test_spec_tensor_parallel(nano_model):
    """Both planes shard over the same 2-way ICI mesh."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    cfg, params, draft = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32, tp=2,
                       draft_params=draft, draft_cfg=cfg, spec_window=4)
    ids = [eng.submit(p, n) for p, n in zip(PROMPTS[:3], BUDGETS[:3])]
    out = eng.run()
    for rid, p, n in zip(ids, PROMPTS[:3], BUDGETS[:3]):
        assert out[rid] == _solo(params, cfg, p, n)


# ---------------------------------------------------------------------------
# Guards and stats surface
# ---------------------------------------------------------------------------

def test_spec_submit_margin_rejected(nano_model):
    """Spec engines need spec_window slack above prompt+budget (the
    draft writes up to window ahead); an over-tight request is rejected
    at submit, not mid-decode."""
    cfg, params, draft = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       draft_params=draft, draft_cfg=cfg, spec_window=4)
    with pytest.raises(ValueError):
        eng.submit([1, 2, 3], 29)   # 3 + 29 + 4 > 32
    rid = eng.submit([1, 2, 3], 25)  # 3 + 25 + 4 == 32: fits
    out = eng.run()
    assert out[rid] == _solo(params, cfg, [1, 2, 3], 25)


def test_spec_off_stats_all_zero(nano_model):
    """Spec-off engines still publish every spec_* key, all zero, so
    fleet rollups sum blindly across mixed replica configs."""
    cfg, params, _ = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32)
    rid = eng.submit([5, 6, 7], 4)
    eng.run()
    s = eng.stats()
    for k in ("spec_enabled", "spec_window", "spec_dispatches",
              "spec_rounds", "spec_proposed", "spec_accepted",
              "spec_acceptance_rate", "spec_window_effective",
              "spec_draft_tokens_wasted", "spec_prefill_dispatches"):
        assert s[k] == 0.0, k


# ---------------------------------------------------------------------------
# Satellites: adaptive hints, trace spans, report summary
# ---------------------------------------------------------------------------

def test_spec_window_hint_default_policy():
    """Fresh rows get the full window; measured rows scale linearly
    down to 1 (one proposal still rides free on the verify pass)."""
    from ray_tpu.models.scheduler import SchedulerPolicy

    pol = SchedulerPolicy()
    assert pol.spec_window_hint(rates=[None, 1.0, 0.0, 0.5],
                                spec_window=4) == [4, 4, 1, 3]


def test_spec_trace_spans_and_report(nano_model):
    """A traced spec run emits the engine-lane spans and
    trace_report's speculation summary folds them — separate from the
    per-request phase attribution, which must stay contiguous."""
    from ray_tpu.models.engine_trace import EngineTracer
    from tools.trace_report import request_breakdowns, spec_summary

    cfg, params, draft = nano_model
    tr = EngineTracer(engine_id="spec-tr")
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       draft_params=draft, draft_cfg=cfg, spec_window=4,
                       trace=tr)
    eng.submit([5, 6, 7], 5)
    eng.run()
    events = tr.chrome_events()
    names = {e["name"] for e in events}
    assert {"spec_draft", "spec_verify", "spec_draft_prefill"} <= names
    s = spec_summary(events)
    assert s["spec_dispatches"] >= 1 and s["spec_rounds"] >= 1
    assert s["spec_proposed"] >= s["spec_accepted"]
    # Spec spans ride engine lanes, so per-request rows still exist
    # and never absorb spec durations.
    rows = request_breakdowns(events)
    assert rows and all(r["e2e_s"] >= 0 for r in rows)


def test_spec_summary_pure_aggregation():
    from tools.trace_report import spec_summary

    events = [
        {"name": "spec_draft", "dur": 1000.0},
        {"name": "spec_verify", "dur": 500.0,
         "args": {"rounds": 2, "proposed": 8, "accepted": 6}},
        {"name": "spec_draft_prefill", "dur": 200.0},
        {"name": "decode_block", "dur": 99.0},
    ]
    s = spec_summary(events)
    assert s["spec_dispatches"] == 1 and s["spec_drains"] == 1
    assert s["spec_prefills"] == 1 and s["spec_rounds"] == 2
    assert s["spec_acceptance_rate"] == 0.75
    assert spec_summary([{"name": "decode_block", "dur": 1.0}]) is None

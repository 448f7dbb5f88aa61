"""Fused multi-step decode (ray_tpu/models/engine.py::_decode_multi).

Contract under test, extending test_engine.py's gold contract to the
fused path: for EVERY horizon H — pinned or adaptive — and every
sampling mode, each request's engine output is token-identical to its
solo `generate` run; rows finishing mid-horizon freeze on device; and
the serving loop pays at most TWO device->host transfers per step
(token block + at most one metrics-free pull — the CI gate that keeps
an accidental per-token sync from creeping back in).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, llama_init
from ray_tpu.models import engine as engine_mod
from ray_tpu.models.engine import DecodeEngine
from ray_tpu.models.generate import generate
from ray_tpu.models.scheduler import FIFOPolicy


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _solo(params, cfg, prompt, n, **kw):
    out = np.asarray(generate(params, jnp.asarray([prompt], jnp.int32),
                              cfg, max_new_tokens=n, **kw))
    return out[0, len(prompt):].tolist()


PROMPTS = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2], [3, 1, 4, 1, 5, 9]]
BUDGETS = [4, 6, 3, 5]

SAMPLING_MODES = {
    "greedy": {},
    "top_k": {"greedy": False, "temperature": 0.9, "top_k": 8},
    "top_p": {"greedy": False, "temperature": 1.1, "top_p": 0.9},
}


# ---------------------------------------------------------------------------
# Token identity across horizons x sampling modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", list(SAMPLING_MODES))
@pytest.mark.parametrize("horizon", [1, 2, 8])
def test_identity_across_horizons_and_sampling(nano_model, horizon,
                                               mode):
    _identity_across_horizons(nano_model, horizon, mode)


@pytest.mark.parametrize("horizon", [8])
def test_identity_across_horizons_olmoe(nano_olmoe, horizon):
    """The sparse family inside the fused horizon's scan, greedy."""
    _identity_across_horizons(nano_olmoe, horizon, "greedy")


def _identity_across_horizons(nano_model, horizon, mode):
    """More requests than slots, ragged budgets: every request matches
    its solo run at EVERY pinned horizon, greedy and sampled alike.
    Sampled requests pin their own rng stream; solo uses the same key —
    the shared step_rng_key schedule makes the paths bit-identical."""
    cfg, params = nano_model
    kw = SAMPLING_MODES[mode]
    keys = [jax.random.PRNGKey(100 + i) for i in range(len(PROMPTS))]

    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32, **kw)
    ids = [eng.submit(p, n, rng=k)
           for p, n, k in zip(PROMPTS, BUDGETS, keys)]
    while eng.pending():
        eng.step(horizon=horizon)

    for rid, p, n, k in zip(ids, PROMPTS, BUDGETS, keys):
        want = _solo(params, cfg, p, n, rng=k, **kw)
        assert eng.pop_result(rid) == want, f"req {rid} H={horizon}"


@pytest.mark.parametrize("mode", ["greedy", "top_k"])
def test_identity_adaptive_horizon(nano_model, mode):
    """run() (adaptive horizon: 1 while the queue can take a free slot,
    decode_horizon once saturated) changes only the dispatch cadence,
    never any token."""
    cfg, params = nano_model
    kw = SAMPLING_MODES[mode]
    keys = [jax.random.PRNGKey(200 + i) for i in range(len(PROMPTS))]

    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       decode_horizon=8, **kw)
    ids = [eng.submit(p, n, rng=k)
           for p, n, k in zip(PROMPTS, BUDGETS, keys)]
    out = eng.run()
    for rid, p, n, k in zip(ids, PROMPTS, BUDGETS, keys):
        assert out[rid] == _solo(params, cfg, p, n, rng=k, **kw)


def test_mid_horizon_eos_freezes_row_and_reuses_slot(nano_model):
    """A row hitting eos INSIDE a fused horizon freezes on device (no
    trailing emits), is retired by the host replay, and its slot serves
    the next queued request — which still decodes exactly."""
    cfg, params = nano_model
    p0, p1 = [5, 6, 7], [9, 8, 7, 6]
    solo0 = _solo(params, cfg, p0, 8)
    eos = solo0[2]                       # p0 finishes mid-horizon

    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       eos_id=eos, decode_horizon=8)
    r0 = eng.submit(p0, 8)
    r1 = eng.submit(p1, 6)
    ev0 = eng.step(horizon=8)            # whole horizon in one dispatch
    assert ev0[r0] == solo0[:solo0.index(eos) + 1]   # truncated at eos
    assert r0 in eng.finished
    assert eng.row_req[0] is None        # slot freed mid-horizon
    out = eng.run()
    solo1 = _solo(params, cfg, p1, 6)
    want = solo1[:solo1.index(eos) + 1] if eos in solo1 else solo1
    assert out[r1] == want


def test_horizon_caps_at_remaining_budget(nano_model):
    """Adaptive H never exceeds the largest remaining row budget (no
    trailing fused iterations run with every row frozen), rounded down
    to a power of two (bounded fused-program compile count)."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       decode_horizon=8)
    rid = eng.submit([5, 6, 7], 3)
    ev = eng.step()                      # queue empty after admit -> H
    assert len(ev[rid]) == 2             # pow2 floor of budget 3, not 8
    assert eng.metrics.stats()["decode_horizon_max"] == 2
    ev = eng.step()                      # remaining budget 1 -> H=1
    assert len(ev[rid]) == 1
    assert rid in eng.finished


# ---------------------------------------------------------------------------
# Transfer budget: the CI gate
# ---------------------------------------------------------------------------

def test_fused_step_transfer_gate(nano_model, monkeypatch):
    """<= 2 device->host transfers per step, REGARDLESS of horizon:
    wraps the engine's single transfer choke point (_device_get) and
    counts. One [H, B] token block per step is the design; a second
    pull is tolerated (headroom for debug probes), a per-token sync is
    a regression and fails here."""
    cfg, params = nano_model
    pulls = []
    real = engine_mod._device_get
    monkeypatch.setattr(engine_mod, "_device_get",
                        lambda x: pulls.append(1) or real(x))

    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       decode_horizon=8)
    for p, n in zip(PROMPTS, BUDGETS):
        eng.submit(p, n)
    steps = 0
    while eng.pending():
        before = len(pulls)
        eng.step()
        steps += 1
        assert len(pulls) - before <= 2, \
            f"step {steps} pulled {len(pulls) - before} times"
    assert steps >= 2                    # slots < requests: real churn


def test_host_syncs_per_token_amortized(nano_model):
    """At horizon >= 4 with saturated slots the engine amortizes its
    one transfer over the whole token block: host_syncs_per_token < 1
    (strictly — the whole point of fusing), and the horizon histogram
    + sync counters land in the Prometheus registry."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       decode_horizon=4,
                       engine_id="horizon-gate-engine")
    for p in PROMPTS[:2]:
        eng.submit(p, 16)
    eng.run()
    s = eng.stats()
    assert s["tokens_generated"] == 32
    assert s["host_syncs_per_token"] < 1.0
    assert s["host_syncs_per_token"] <= 0.3   # 4-token blocks: <= 1/4 + slack
    assert s["decode_dispatches"] == s["host_syncs"]
    assert s["dispatches_per_token"] < 1.0

    from ray_tpu._private import metrics as _impl

    rows = {r["name"]: r for r in _impl.snapshots()
            if r["tags"].get("engine") == "horizon-gate-engine"}
    assert rows["llm_engine_host_syncs_total"]["value"] == s["host_syncs"]
    assert rows["llm_engine_decode_dispatches_total"]["value"] == \
        s["decode_dispatches"]
    hor = rows["llm_engine_decode_horizon"]
    assert hor["kind"] == "histogram"
    assert hor["count"] == s["decode_dispatches"]
    assert hor["sum"] == s["tokens_generated"] / 2   # 2 rows per dispatch


# ---------------------------------------------------------------------------
# Adaptive horizon policy
# ---------------------------------------------------------------------------

def test_horizon_hint_units():
    """Default SchedulerPolicy.horizon_hint: 1 while a queued request
    could take a free slot next step (protect TTFT), max_horizon when
    slots are saturated or nothing is queued (amortize dispatch)."""
    pol = FIFOPolicy()
    assert pol.horizon_hint(free_slots=2, max_horizon=8) == 8  # empty q
    pol.push(type("R", (), {"req_id": 0})())
    assert pol.horizon_hint(free_slots=2, max_horizon=8) == 1  # can admit
    assert pol.horizon_hint(free_slots=0, max_horizon=8) == 8  # saturated
    pol.pop()
    assert pol.horizon_hint(free_slots=0, max_horizon=8) == 8


def test_adaptive_horizon_protects_ttft_then_ramps(nano_model):
    """While the queue holds admissible requests the engine steps with
    H=1 (newcomers wait at most one token for a slot); once everyone is
    admitted it ramps to decode_horizon. Observed via the horizon
    histogram aggregate."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       max_prefills_per_step=1, decode_horizon=8)
    # 3 requests, 2 slots, 1 prefill/step: step 1 admits A (B,C queued,
    # 1 slot free -> H=1), step 2 admits B (C queued, slots full -> H
    # ramps), ...
    for p in PROMPTS[:3]:
        eng.submit(p, 8)
    eng.step()
    first_h = eng.metrics.stats()["decode_horizon_max"]
    assert first_h == 1                  # queue non-empty, slot free
    eng.run()
    assert eng.metrics.stats()["decode_horizon_max"] > 1   # ramped


def test_step_horizon_validation(nano_model):
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32)
    with pytest.raises(ValueError, match="horizon"):
        eng.step(horizon=0)
    with pytest.raises(ValueError, match="decode_horizon"):
        DecodeEngine(params, cfg, decode_horizon=0)


# ---------------------------------------------------------------------------
# Batched prefill
# ---------------------------------------------------------------------------

def test_batched_prefill_identity_and_dispatch_count(nano_model):
    """A 4-deep same-step admission burst prefills in FEWER dispatches
    than admissions (same-bucket admissions share one program) and no
    token changes vs one-at-a-time admission."""
    cfg, params = nano_model
    prompts = [[5, 6, 7], [9, 8, 7], [1, 2], [3, 4]]   # buckets: 4,4,2,2

    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=32)
    ids = [eng.submit(p, 4) for p in prompts]
    out = eng.run()
    assert eng.prefill_dispatches < len(prompts)   # batched (2 groups)

    eng1 = DecodeEngine(params, cfg, batch_slots=4, max_len=32,
                        max_prefills_per_step=1)
    ids1 = [eng1.submit(p, 4) for p in prompts]
    out1 = eng1.run()
    assert eng1.prefill_dispatches == len(prompts)  # one per step

    for rid, rid1, p in zip(ids, ids1, prompts):
        want = _solo(params, cfg, p, 4)
        assert out[rid] == want
        assert out1[rid1] == want


def test_prefill_group_pow2_padding_is_exact(nano_model):
    """A 3-wide same-bucket group pads to 4 by repeating the last
    admission (duplicate scatters write identical values) — tokens
    match solo exactly."""
    cfg, params = nano_model
    prompts = [[5, 6, 7], [9, 8, 7], [1, 2, 3]]    # one bucket, n=3
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=32)
    ids = [eng.submit(p, 4) for p in prompts]
    out = eng.run()
    assert eng.prefill_dispatches == 1
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, 4)

"""Serving state API (util/state/serving.py), metrics history
(util/metrics_history.py), and the status CLI (tools/ray_tpu_status).

The load-bearing contract: `list_requests()` classifies every
in-flight request EXACTLY as the engine's own bookkeeping does, under
every engine feature combination — so an operator reading the state
API and an engine reading its own tables can never disagree. The
invariants pinned per step:

- count(queued) + count(swapped) == stats queue_depth (a preempted
  request is re-queued AND in the swap ledger; `swapped` wins),
- count(prefilling) == chunked-prefill frontier rows,
- count(prefilling) + count(decoding) == live slots.

Snapshots must also be read-only: taking one mid-run cannot change a
single emitted token.
"""

import gc

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, llama_init  # noqa: E402
from ray_tpu.models.engine import DecodeEngine  # noqa: E402
from ray_tpu.models.fleet import LLMFleet  # noqa: E402
from ray_tpu.models.prefix_cache import block_bytes  # noqa: E402
from ray_tpu.util.metrics_history import (  # noqa: E402
    MetricsHistory, sample_now, trend_of_points)
from ray_tpu.util.state import serving  # noqa: E402

T = 4
MAX_LEN = 32


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _pool_bytes(cfg, n_blocks):
    return n_blocks * block_bytes(cfg.n_layers, T, cfg.n_kv_heads,
                                  cfg.head_dim,
                                  jnp.dtype(cfg.dtype).itemsize)


def _prompts(n, cfg, seed=7, lo=3, hi=9):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size,
                        size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


def _phase_counts(rows):
    counts = {}
    for r in rows:
        counts[r["status"]] = counts.get(r["status"], 0) + 1
    return counts


def _assert_agrees_with_engine(eng):
    """The identity invariants between the state API's classification
    and the engine's own tables, at the current instant."""
    rows = serving.engine_requests(eng)
    c = _phase_counts(rows)
    s = eng.stats()
    assert c.get("queued", 0) + c.get("swapped", 0) == \
        s["queue_depth"], (c, s["queue_depth"])
    assert c.get("prefilling", 0) == len(eng._row_prefill)
    assert c.get("prefilling", 0) + c.get("decoding", 0) == \
        s["live_slots"]
    assert c.get("swapped", 0) == len(eng._swapped)
    # No request appears twice, and every row names this engine.
    ids = [r["req_id"] for r in rows]
    assert len(ids) == len(set(ids))
    assert all(r["engine_id"] == eng.engine_id for r in rows)
    return rows


# ---------------------------------------------------------------------------
# list_requests vs engine internals, across the feature matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("features", [
    {},
    {"prefix_cache": True, "kv_block_tokens": T},
    {"prefill_chunk": 3, "prefix_cache": True, "kv_block_tokens": T},
    {"prefix_cache": True, "kv_block_tokens": T, "pipeline_depth": 3},
    {"kv_block_tokens": T, "tight_pool": True},
    {"kv_block_tokens": T, "tight_pool": True, "prefill_chunk": 3,
     "pipeline_depth": 2},
], ids=["plain", "prefix", "chunked", "pipeline", "tight_pool",
        "tight_chunked_pipeline"])
def test_list_requests_identity_matrix(nano_model, features):
    """At EVERY engine step of a run that churns 6 requests through 2
    slots, the state API's phase counts equal the engine's own
    bookkeeping — and reading the snapshots never perturbs the token
    stream (output matches an unobserved run)."""
    cfg, params = nano_model
    kw = dict(features)
    if kw.pop("tight_pool", False):
        kw["kv_pool_bytes"] = _pool_bytes(cfg, 16)
    prompts = _prompts(6, cfg)
    budgets = [4, 6, 3, 5, 2, 4]

    def run(observe):
        eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                           **kw)
        ids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        if observe:
            _assert_agrees_with_engine(eng)
        while eng.pending():
            eng.step()
            if observe:
                _assert_agrees_with_engine(eng)
        return [eng.pop_result(r) for r in ids]

    assert run(observe=True) == run(observe=False)


def test_swapped_requests_surface_in_state(nano_model):
    """Preempt-and-swap (pool sized for 2 of 4 requests): while the
    swap ledger is non-empty the spilled requests show as `swapped`
    (with their block counts), not double-counted as `queued` — and
    once the run drains, no in-flight state remains."""
    cfg, params = nano_model
    prompts = [[7, 8, 9, 10, 11], [3, 1, 4, 1, 5],
               [2, 7, 1, 8, 2], [9, 9, 8, 8, 7]]
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=MAX_LEN,
                       kv_block_tokens=T,
                       kv_pool_bytes=_pool_bytes(cfg, 10),
                       prefix_cache=False)
    for p in prompts:
        eng.submit(p, 12)
    saw_swapped = False
    while eng.pending():
        eng.step()
        rows = _assert_agrees_with_engine(eng)
        swapped = [r for r in rows if r["status"] == "swapped"]
        if swapped:
            saw_swapped = True
            for r in swapped:
                assert r["swap_blocks"] > 0
                assert r["resume"] is True
            # The same ids also sit in the scheduler queue; the state
            # API must not report them twice.
            queued_ids = {r["req_id"] for r in rows
                          if r["status"] == "queued"}
            assert queued_ids.isdisjoint(r["req_id"] for r in swapped)
    assert saw_swapped, "pool of 10 blocks never forced a preemption"
    assert eng.stats()["preemptions"] >= 1
    assert serving.engine_requests(eng) == []


def test_list_requests_filters_and_errors(nano_model):
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       engine_id="filt")
    for p in _prompts(4, cfg):
        eng.submit(p, 3)
    eng.step()
    def _stable(rows):
        # age_s is wall-clock-fresh per call; drop it for comparison.
        return [{k: v for k, v in r.items() if k != "age_s"}
                for r in rows]

    everything = serving.list_requests(engine_id="filt")
    for status in ("queued", "prefilling", "decoding", "swapped",
                   "handoff"):
        got = serving.list_requests(status=status, engine_id="filt")
        want = [r for r in everything if r["status"] == status]
        assert _stable(got) == _stable(want)
    assert serving.list_requests(engine_id="no-such-engine") == []
    assert _stable(serving.list_requests(limit=2)) == \
        _stable(serving.list_requests()[:2])
    with pytest.raises(ValueError, match="unknown status"):
        serving.list_requests(status="finished")
    eng.run()


def test_draining_filter_spans_phases(nano_model):
    """status="draining" is a filter, not a phase: it returns the
    draining engine's requests in whatever phase they are in, and
    nothing from healthy engines."""
    cfg, params = nano_model
    a = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                     engine_id="drain-a")
    b = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                     engine_id="drain-b")
    for eng in (a, b):
        for p in _prompts(3, cfg, seed=11):
            eng.submit(p, 4)
        eng.step()
    a.begin_drain()
    rows = serving.list_requests(status="draining")
    assert rows and all(r["engine_id"] == "drain-a" for r in rows)
    assert {r["req_id"] for r in rows} == \
        {r["req_id"] for r in serving.list_requests(engine_id="drain-a")}
    assert all(r["engine_draining"] for r in rows)
    a.run(), b.run()


# ---------------------------------------------------------------------------
# Engine rows, KV pools, fleet summary
# ---------------------------------------------------------------------------

def test_engine_state_row_and_kv_pools(nano_model):
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       kv_block_tokens=T,
                       kv_pool_bytes=_pool_bytes(cfg, 16),
                       engine_id="rowcheck")
    for p in _prompts(3, cfg):
        eng.submit(p, 20)   # > decode_horizon: rows outlive the step
    eng.step()
    while eng.kv_pool.blocks_in_use == 0 and eng.pending():
        eng.step()          # async pipeline: blocks land a step later
    row, = [r for r in serving.list_engines()
            if r["engine_id"] == "rowcheck"]
    s = eng.stats()
    assert row["batch_slots"] == 2 and row["max_len"] == MAX_LEN
    assert row["queue_depth"] == s["queue_depth"]
    assert row["live_slots"] == s["live_slots"]
    assert row["slot_occupancy"] == pytest.approx(s["slot_occupancy"])
    assert row["kv_used_fraction"] == pytest.approx(
        eng.kv_used_fraction())
    assert row["draining"] is False
    assert row["fleet"] is None and row["replica"] is None
    assert row["uptime_s"] >= 0.0 and row["steps_total"] >= 1

    pool, = [p for p in serving.list_kv_pools()
             if p["engine_id"] == "rowcheck"]
    assert pool["block_tokens"] == T and pool["blocks_total"] == 16
    assert pool["blocks_in_use"] == eng.kv_pool.blocks_in_use
    assert 0.0 < pool["occupancy"] <= 1.0
    eng.run()
    pool, = [p for p in serving.list_kv_pools()
             if p["engine_id"] == "rowcheck"]
    assert pool["blocks_in_use"] == 0


def test_summarize_fleet_attribution_and_counts(nano_model):
    """A 2-replica fleet plus one loose engine: the summary attributes
    members to their fleet block (replica names included in
    list_engines rows), counts the loose engine as unattached, and the
    per-phase totals equal a direct list_requests() census."""
    cfg, params = nano_model

    def factory(name):
        return DecodeEngine(params, cfg, engine_id=name, batch_slots=2,
                            max_len=MAX_LEN)

    fleet = LLMFleet(factory, initial_replicas=2, router="round_robin",
                     fleet_id="sumfleet")
    loose = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                         engine_id="loose")
    for p, n in zip(_prompts(5, cfg), [3, 4, 3, 4, 3]):
        fleet.submit(p, n)
    loose.submit([5, 6, 7], 3)
    fleet.step()
    loose.step()

    summary = serving.summarize_fleet()
    block, = [b for b in summary["fleets"]
              if b["fleet_id"] == "sumfleet"]
    assert block["replicas"] == 2
    assert block["replicas_running"] == 2
    assert block["router"] == "RoundRobinRouter"
    member_rows = [r for r in serving.list_engines()
                   if r["fleet"] == "sumfleet"]
    assert len(member_rows) == 2
    assert {r["replica"] for r in member_rows} == \
        {rep.name for rep in fleet.replicas}
    assert block["queue_depth"] == \
        sum(r["queue_depth"] for r in member_rows)
    assert summary["engines_unattached"] >= 1
    assert summary["requests"] == {
        s: len(serving.list_requests(status=s))
        for s in ("queued", "prefilling", "decoding", "swapped",
                  "handoff", "recovering")}
    assert summary["requests_inflight"] == \
        len(serving.list_requests())
    fleet.run(), loose.run()


def test_registry_is_weak(nano_model):
    cfg, params = nano_model
    gc.collect()          # flush cyclic garbage from earlier tests
    before = len(serving.engines())
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       engine_id="ephemeral")
    assert eng in serving.engines()
    del eng
    gc.collect()
    assert len(serving.engines()) == before
    assert all(e.engine_id != "ephemeral" for e in serving.engines())


def test_uptime_and_steps_in_stats(nano_model, fake_clock):
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       clock=fake_clock)
    s = eng.stats()
    assert s["uptime_s"] == 0.0 and s["steps_total"] == 0.0
    eng.submit([5, 6, 7], 3)
    fake_clock.advance(2.5)
    eng.step()
    s = eng.stats()
    assert s["uptime_s"] == pytest.approx(2.5)
    assert s["steps_total"] == 1.0
    eng.run()
    assert eng.stats()["steps_total"] == float(eng.steps_total) > 1.0


def test_engine_metric_series_carry_engine_label(nano_model):
    """SATELLITE LOCK: every exported llm_engine_* series is tagged
    with its engine id — per-replica dashboards depend on it."""
    from ray_tpu.util import metrics as um

    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       engine_id="tagged-eng")
    eng.submit([5, 6, 7], 4)
    eng.run()
    rows = [r for r in um.snapshots()
            if r["name"].startswith("llm_engine_")]
    assert rows, "engine produced no llm_engine_* series"
    for r in rows:
        assert r["tags"].get("engine"), \
            f"{r['name']} missing engine label: {r['tags']}"
    assert any(r["tags"]["engine"] == "tagged-eng" for r in rows)
    text = um.prometheus_text(rows)
    assert 'engine="tagged-eng"' in text


# ---------------------------------------------------------------------------
# Metrics history ring
# ---------------------------------------------------------------------------

def test_history_bounded_under_long_churn(fake_clock):
    """5000 samples through a 32-entry ring: the entry count never
    reaches capacity, every raw sample is still represented (the `n`
    weights sum to samples_taken), and entry times stay sorted."""
    h = MetricsHistory(capacity=32, cadence_s=0.0, clock=fake_clock,
                       keys=("queue_depth",))
    for i in range(5000):
        fake_clock.advance(1.0)
        h.sample({"queue_depth": float(i)})
        assert len(h) < 32
    assert h.samples_taken == 5000
    assert h.compactions > 0
    snap = h.snapshot()
    assert sum(s["n"] for s in snap["samples"]) == 5000
    ts = [s["t"] for s in snap["samples"]]
    assert ts == sorted(ts)


def test_history_downsampling_boundary(fake_clock):
    """Resolution tiers: after compaction the OLD half is coarse
    (n > 1) while the newest samples stay at full cadence (n == 1),
    and a folded entry's value is the n-weighted mean of its raws."""
    h = MetricsHistory(capacity=8, cadence_s=0.0, clock=fake_clock,
                       keys=("v",))
    for i in range(8):          # fills to capacity -> one compaction
        fake_clock.advance(1.0)
        h.sample({"v": float(i)})
    assert h.compactions == 1
    snap = h.snapshot()["samples"]
    assert [s["n"] for s in snap] == [2, 2, 1, 1, 1, 1]
    # First folded entry averages raws 0.0 and 1.0 at t=1,2.
    assert snap[0]["v"] == pytest.approx(0.5)
    assert snap[0]["t"] == pytest.approx(1.5)
    assert [s["v"] for s in snap[2:]] == [4.0, 5.0, 6.0, 7.0]


def test_history_cadence_guard(fake_clock):
    h = MetricsHistory(capacity=8, cadence_s=1.0, clock=fake_clock,
                       keys=("v",))
    assert h.sample({"v": 1.0}) is True
    fake_clock.advance(0.5)
    assert h.sample({"v": 2.0}) is False       # inside cadence
    assert h.sample({"v": 3.0}, force=True) is True
    fake_clock.advance(1.0)
    assert h.sample({"v": 4.0}) is True
    assert h.samples_skipped == 1
    assert h.samples_taken == 3


def test_trend_directions():
    assert trend_of_points([1.0] * 16, window=4) == 0
    assert trend_of_points(list(range(16)), window=4) == 1
    assert trend_of_points(list(range(16, 0, -1)), window=4) == -1
    assert trend_of_points([1.0, 2.0], window=4) == 0   # too short
    # Sub-threshold wiggle reads as flat.
    assert trend_of_points([100.0] * 8 + [101.0] * 8, window=8) == 0


def test_history_capacity_validation():
    with pytest.raises(ValueError):
        MetricsHistory(capacity=4)
    with pytest.raises(ValueError):
        MetricsHistory(cadence_s=-1.0)


def test_collect_serving_sample_aggregates(nano_model):
    cfg, params = nano_model
    a = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                     engine_id="agg-a")
    b = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                     engine_id="agg-b")
    for p in _prompts(3, cfg):
        a.submit(p, 3)
    b.submit([5, 6], 3)
    a.step(), b.step()
    from ray_tpu.util.metrics_history import collect_serving_sample
    vals = collect_serving_sample()
    sa, sb = a.stats(), b.stats()
    assert vals["queue_depth"] == sa["queue_depth"] + sb["queue_depth"]
    assert vals["slot_occupancy"] == pytest.approx(
        (sa["slot_occupancy"] + sb["slot_occupancy"]) / 2)
    assert vals["requests_inflight"] == (
        sa["queue_depth"] + sa["live_slots"]
        + sb["queue_depth"] + sb["live_slots"])
    assert sample_now(force=True) is True
    a.run(), b.run()


# ---------------------------------------------------------------------------
# Status CLI against a live 2-replica CPU fleet
# ---------------------------------------------------------------------------

def test_status_cli_renders_live_fleet(nano_model):
    """The acceptance render: a 2-replica CPU dry-run fleet with work
    genuinely in flight produces a COMPLETE report — every section,
    both replicas with bars, phase-labelled request lines — straight
    from `collect()` with no HTTP in the loop."""
    from tools.ray_tpu_status import collect, format_status

    cfg, params = nano_model

    def factory(name):
        return DecodeEngine(params, cfg, engine_id=name, batch_slots=2,
                            max_len=MAX_LEN, prefix_cache=True,
                            kv_block_tokens=T)

    fleet = LLMFleet(factory, initial_replicas=2, router="round_robin",
                     fleet_id="clifleet")
    for p, n in zip(_prompts(6, cfg), [6, 8, 6, 8, 6, 8]):
        fleet.submit(p, n)
    fleet.step()                       # work is genuinely in flight
    assert serving.list_requests()     # precondition for a real render

    data = collect()
    report = format_status(data, top=3)
    for section in ("======== Fleet ========",
                    "======== Replicas ========",
                    "======== SLO (recent window) ========",
                    "======== Longest-running requests (top 3) "
                    "========"):
        assert section in report
    assert "fleet clifleet: 2 replicas (2 running)" in report
    assert "router=RoundRobinRouter" in report
    for rep in fleet.replicas:
        assert rep.name in report
    assert "occ [" in report and "]" in report        # bars rendered
    assert "ttft_s_p50" in report and "tpot_s_p95" in report
    # At least one in-flight request line with a phase label.
    assert any(p in report for p in ("prefilling", "decoding",
                                     "queued", "swapped"))
    assert "no in-flight requests" not in report
    fleet.run()


def test_status_cli_json_mode(nano_model, capsys):
    import json

    from tools.ray_tpu_status import main

    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=MAX_LEN,
                       engine_id="jsoncli")
    eng.submit([5, 6, 7], 3)
    eng.step()
    main(["--json"])
    data = json.loads(capsys.readouterr().out)
    assert {"engines", "requests", "kv_pools", "summary",
            "history"} <= set(data)
    assert any(e["engine_id"] == "jsoncli" for e in data["engines"])
    eng.run()


def test_status_cli_empty_world():
    """No engines, no fleets, no history: the report still renders
    (the empty-fleet placeholders), it does not crash."""
    from tools.ray_tpu_status import format_status

    report = format_status({
        "engines": [], "requests": [], "kv_pools": [],
        "summary": {"fleets": [], "engines_total": 0,
                    "engines_unattached": 0,
                    "requests": {}, "requests_inflight": 0},
        "history": {"samples": [], "compactions": 0}})
    assert "no fleets registered" in report
    assert "no engines registered" in report
    assert "no in-flight requests" in report

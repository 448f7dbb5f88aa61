"""LLM serving fleet (ray_tpu/models/fleet.py + serve/llm.py).

Gold contract, inherited from the engine suite and re-proven at fleet
scope: a request's tokens are identical to its solo `generate` run —
greedy and sampled — no matter which replica the router picks, whether
replicas appear (scale-up) or leave (drain) mid-stream, and whether
other traffic is being shed around it. Routing and scaling change
WHERE and WHEN a request runs, never what it computes.

Autoscaler hysteresis runs on the injected fake clock (no real
sleeps); the long churn soak is @slow.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, llama_init
from ray_tpu.models.engine import DecodeEngine
from ray_tpu.models.fleet import (EngineStatsAutoscaler,
                                  FleetAutoscalingConfig, LLMFleet,
                                  PowerOfTwoAffinityRouter,
                                  RoundRobinRouter)
from ray_tpu.models.generate import generate
from ray_tpu.models.scheduler import EngineDraining


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _solo(params, cfg, prompt, n, **kw):
    out = np.asarray(generate(params, jnp.asarray([prompt], jnp.int32),
                              cfg, max_new_tokens=n, **kw))
    return out[0, len(prompt):].tolist()


def _factory(params, cfg, **kw):
    def make(name):
        kw.setdefault("batch_slots", 2)
        kw.setdefault("max_len", 32)
        return DecodeEngine(params, cfg, engine_id=name, **kw)
    return make


PROMPTS = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2], [3, 1, 4, 1, 5, 9],
           [11, 13], [2, 7, 1, 8]]
BUDGETS = [4, 6, 3, 5, 2, 4]

SAMPLING_MODES = {
    "greedy": {},
    "top_k": {"greedy": False, "temperature": 0.9, "top_k": 8},
}


# ---------------------------------------------------------------------------
# Token identity: routing x scale-up x drain x shedding, greedy+sampled
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", list(SAMPLING_MODES))
@pytest.mark.parametrize("scenario", ["route", "scale_up", "drain",
                                      "shed"])
@pytest.mark.parametrize("router", ["round_robin", "pow2_affinity"])
def test_fleet_identity_matrix(nano_model, router, scenario, mode):
    """Every request served by the fleet matches its solo generate run
    under both routers, while the scenario column perturbs the pool:
    a replica added mid-stream, a replica drained mid-stream, or
    dead-on-arrival traffic being shed between live requests. Sampled
    requests pin their rng stream, so replica choice cannot change
    their tokens either."""
    cfg, params = nano_model
    kw = SAMPLING_MODES[mode]
    fleet = LLMFleet(
        _factory(params, cfg, prefix_cache=True, kv_block_tokens=4, **kw),
        initial_replicas=2, router=router,
        fleet_id=f"id-{router}-{scenario}-{mode}")
    keys = [jax.random.PRNGKey(40 + i) for i in range(len(PROMPTS))]

    first = [fleet.submit(p, n, rng=k) for p, n, k
             in zip(PROMPTS[:3], BUDGETS[:3], keys[:3])]
    for _ in range(2):
        fleet.step()
    shed_fids = []
    if scenario == "scale_up":
        fleet.add_replica()
    elif scenario == "drain":
        fleet.drain_replica(fleet.replicas[0].name)
    elif scenario == "shed":
        shed_fids = [fleet.submit([4, 4, 4], 4, deadline_s=0.0)
                     for _ in range(2)]
    rest = [fleet.submit(p, n, rng=k) for p, n, k
            in zip(PROMPTS[3:], BUDGETS[3:], keys[3:])]
    out = fleet.run()

    for fid, p, n, k in zip(first + rest, PROMPTS, BUDGETS, keys):
        assert out[fid] == _solo(params, cfg, p, n, rng=k, **kw), \
            f"fleet req {fid} diverged from solo ({scenario})"
    for fid in shed_fids:
        assert out[fid] == []
    if scenario == "drain":
        assert len(fleet.replicas) == 1     # flushed, then removed
        assert fleet.stats()["tokens_lost_to_drain"] == 0.0


# ---------------------------------------------------------------------------
# Drain: flush-before-removal loses nothing
# ---------------------------------------------------------------------------

def test_fleet_drain_zero_loss_midflight(nano_model):
    """Draining a replica that holds queued AND in-flight work: every
    one of its requests still returns its full, exact token sequence;
    the replica leaves the pool only after flushing; its engine
    refuses new submits the moment the drain begins."""
    cfg, params = nano_model
    fleet = LLMFleet(_factory(params, cfg), initial_replicas=2,
                     router="round_robin", fleet_id="drainloss")
    fids = [fleet.submit(p, n)
            for p, n in zip(PROMPTS, BUDGETS)]
    fleet.step()                      # work is now genuinely in flight
    victim = fleet.replicas[0]
    assert victim.engine.pending()
    fleet.drain_replica(victim.name)
    with pytest.raises(EngineDraining):
        victim.engine.submit([1, 2], 2)

    out = fleet.run()
    assert len(fleet.replicas) == 1
    assert fleet.replicas[0] is not victim
    for fid, p, n in zip(fids, PROMPTS, BUDGETS):
        got = out[fid]
        assert len(got) == n, f"req {fid}: {len(got)}/{n} tokens"
        assert got == _solo(params, cfg, p, n)
    s = fleet.stats()
    assert s["tokens_lost_to_drain"] == 0.0
    assert s["replicas_removed"] == 1.0


# ---------------------------------------------------------------------------
# Deadline shedding (engine-level satellite)
# ---------------------------------------------------------------------------

def test_deadline_reject_before_prefill(nano_model):
    """A dead-on-arrival request (deadline_s <= 0) is shed at submit:
    finished immediately with zero tokens, never queued, never
    prefilled — the prefill counters stay untouched."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32)
    rid = eng.submit([5, 6, 7], 4, deadline_s=0.0)
    assert rid in eng.finished and rid in eng.shed_ids
    assert len(eng.scheduler) == 0
    assert eng.prefill_dispatches == 0
    assert eng.prefill_real_tokens == 0
    assert eng.stats()["requests_shed"] == 1.0
    assert eng.pop_result(rid) == []
    # A live request afterwards is unaffected.
    ok = eng.submit([5, 6, 7], 4, deadline_s=60.0)
    out = eng.run()
    assert out[ok] == _solo(params, cfg, [5, 6, 7], 4)


def test_deadline_mid_queue_expiry(nano_model, fake_clock):
    """A request whose deadline passes WHILE QUEUED is shed at its
    admission pop — before its prefill runs — while requests already
    admitted always run to completion. Time is the fake clock's, so
    expiry is exact, not racy."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       clock=fake_clock)
    a = eng.submit([5, 6, 7], 6)                  # takes the only slot
    b = eng.submit([9, 8, 7], 4, deadline_s=5.0)  # queued behind a
    eng.step()
    prefilled_before = eng.prefill_real_tokens
    fake_clock.advance(10.0)                      # b is now past due
    out = eng.run()
    assert b in eng.shed_ids or out[b] == []
    assert out[a] == _solo(params, cfg, [5, 6, 7], 6)
    assert out[b] == []
    # b's 3 prompt tokens were never prefilled.
    assert eng.prefill_real_tokens == prefilled_before
    assert eng.requests_shed == 1


def test_deadline_not_expired_runs_normally(nano_model, fake_clock):
    """A generous deadline changes nothing: same tokens as solo."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       clock=fake_clock)
    rid = eng.submit([3, 1, 4], 5, deadline_s=100.0)
    fake_clock.advance(50.0)
    out = eng.run()
    assert out[rid] == _solo(params, cfg, [3, 1, 4], 5)
    assert eng.requests_shed == 0


# ---------------------------------------------------------------------------
# Router behavior
# ---------------------------------------------------------------------------

def test_router_prefix_affinity_routes_warm(nano_model):
    """After one replica serves a long shared prefix, the affinity
    router sends same-prefix followers to THAT replica (its trie
    matches; the others' don't), and the group's prefix is prefilled
    on one replica only — round-robin recomputes it everywhere."""
    cfg, params = nano_model
    prefix = list(range(1, 17))       # 16 tokens = 4 committed blocks

    def run(router):
        fleet = LLMFleet(
            _factory(params, cfg, prefix_cache=True, kv_block_tokens=4),
            initial_replicas=2, router=router,
            fleet_id=f"affinity-{getattr(router, 'name', router)}")
        for i in range(6):
            fleet.submit(prefix + [30 + i], 2)
            fleet.step()
        fleet.run()
        return fleet

    aff = run(PowerOfTwoAffinityRouter(seed=3))
    rr = run(RoundRobinRouter())
    aff_prefill = sum(r.engine.prefill_real_tokens
                      for r in aff.replicas)
    rr_prefill = sum(r.engine.prefill_real_tokens
                     for r in rr.replicas)
    assert aff.router.affinity_wins > 0
    # Affinity computes the shared blocks once fleet-wide; round-robin
    # pays them once PER replica.
    assert aff_prefill < rr_prefill
    # And the follower traffic really concentrated on the warm replica.
    routed = sorted(r.routed for r in aff.replicas)
    assert routed[-1] >= 5


def test_router_pow2_prefers_less_loaded(nano_model):
    """With no prefix signal, pow-2 sends traffic away from a loaded
    replica: pile work on one replica, then check new submissions
    mostly land on the idle one."""
    cfg, params = nano_model
    fleet = LLMFleet(_factory(params, cfg, batch_slots=2, max_len=64),
                     initial_replicas=2,
                     router=PowerOfTwoAffinityRouter(seed=0,
                                                     affinity=False),
                     fleet_id="pow2-load")
    # Load replica 0 directly (behind the router's back).
    busy = fleet.replicas[0]
    for _ in range(6):
        busy.engine.submit(list(range(1, 9)), 8)
    placed = []
    for i in range(8):
        fid = fleet.submit([7, 7, 7 + i], 2)
        placed.append(fleet._placement.get(fid))
    idle_hits = sum(1 for pl in placed
                    if pl is not None and pl[0] is not busy)
    assert idle_hits >= 6, f"only {idle_hits}/8 routed to idle replica"
    fleet.run()
    busy.engine.run()


# ---------------------------------------------------------------------------
# Autoscaler: hysteresis on the fake clock
# ---------------------------------------------------------------------------

def _stats(ttft=0.0, occ=0.0, queue=0.0):
    return [{"ttft_s_p95": ttft, "slot_occupancy": occ,
             "queue_depth": queue}]


def test_autoscaler_upscale_needs_sustained_breach(fake_clock):
    """A TTFT breach must HOLD for upscale_hold_s: a flap that clears
    resets the timer, a sustained breach fires exactly one +1, and the
    timer re-arms after firing."""
    cfg = FleetAutoscalingConfig(min_replicas=1, max_replicas=3,
                                 ttft_p95_slo_s=1.0,
                                 upscale_hold_s=5.0,
                                 downscale_hold_s=60.0)
    sc = EngineStatsAutoscaler(cfg, clock=fake_clock)
    assert sc.tick(_stats(ttft=2.0, queue=1.0), 1) == 0  # breach starts
    fake_clock.advance(3.0)
    assert sc.tick(_stats(ttft=2.0, queue=1.0), 1) == 0  # held 3s < 5s
    fake_clock.advance(1.0)
    assert sc.tick(_stats(ttft=0.2, queue=1.0), 1) == 0  # flap clears -> reset
    fake_clock.advance(1.0)
    assert sc.tick(_stats(ttft=2.0, queue=1.0), 1) == 0  # new breach epoch
    fake_clock.advance(4.9)
    assert sc.tick(_stats(ttft=2.0, queue=1.0), 1) == 0  # 4.9s < 5s
    fake_clock.advance(0.2)
    assert sc.tick(_stats(ttft=2.0, queue=1.0), 1) == +1  # sustained
    assert sc.tick(_stats(ttft=2.0, queue=1.0), 2) == 0   # re-armed
    assert sc.scale_ups == 1


def test_autoscaler_downscale_hysteresis_and_bounds(fake_clock):
    """Idle must hold for downscale_hold_s before -1; the scaler never
    goes below min_replicas nor above max_replicas."""
    cfg = FleetAutoscalingConfig(min_replicas=1, max_replicas=2,
                                 ttft_p95_slo_s=1.0,
                                 occupancy_low=0.3,
                                 upscale_hold_s=1.0,
                                 downscale_hold_s=10.0)
    sc = EngineStatsAutoscaler(cfg, clock=fake_clock)
    # At max: sustained breach produces no further +1.
    sc.tick(_stats(ttft=5.0, queue=2.0), 2)
    fake_clock.advance(2.0)
    assert sc.tick(_stats(ttft=5.0, queue=2.0), 2) == 0
    # Idle, but not for long enough yet.
    assert sc.tick(_stats(occ=0.0), 2) == 0
    fake_clock.advance(9.0)
    assert sc.tick(_stats(occ=0.0), 2) == 0
    fake_clock.advance(1.5)
    assert sc.tick(_stats(occ=0.0), 2) == -1
    # At min: idle forever, never another -1.
    fake_clock.advance(100.0)
    assert sc.tick(_stats(occ=0.0), 1) == 0
    assert sc.scale_downs == 1


def test_autoscaler_stale_ttft_window_does_not_upscale_idle(fake_clock):
    """The TTFT p95 window is computed over PAST requests, so it stays
    at its last value after traffic stops; an idle fleet quoting a
    stale breach must not scale up."""
    cfg = FleetAutoscalingConfig(min_replicas=1, max_replicas=4,
                                 ttft_p95_slo_s=1.0,
                                 upscale_hold_s=1.0)
    sc = EngineStatsAutoscaler(cfg, clock=fake_clock)
    for _ in range(5):
        fake_clock.advance(5.0)
        # queue empty + zero occupancy: the breach-looking TTFT is stale
        assert sc.tick(_stats(ttft=9.0, occ=0.0, queue=0.0), 1) == 0
    assert sc.scale_ups == 0


def test_fleet_scales_up_and_back_down(nano_model, fake_clock):
    """End-to-end on the fake clock: sustained pressure on one replica
    adds a second; sustained idleness drains back to min — and the
    drained replica leaves only after flushing (token identity holds
    throughout)."""
    cfg, params = nano_model
    auto = FleetAutoscalingConfig(min_replicas=1, max_replicas=2,
                                  ttft_p95_slo_s=0.5,
                                  occupancy_low=0.2,
                                  upscale_hold_s=2.0,
                                  downscale_hold_s=5.0)
    fleet = LLMFleet(
        _factory(params, cfg, clock=fake_clock),
        initial_replicas=1, autoscaling=auto, fleet_id="e2e-scale",
        clock=fake_clock)
    keys, fids, want = [], [], []
    i = 0
    for _ in range(8):                 # sustained feed: queue never dry
        for p, n in zip(PROMPTS[:2], BUDGETS[:2]):
            k = jax.random.PRNGKey(900 + i); i += 1
            fids.append(fleet.submit(p, n, rng=k))
            want.append((p, n, k))
        fake_clock.advance(1.0)
        fleet.step()
    assert len(fleet.replicas) == 2, "no scale-up under breach"
    out = fleet.run()
    for fid, (p, n, k) in zip(fids, want):
        assert out[fid] == _solo(params, cfg, p, n, rng=k)
    for _ in range(8):                 # idle: hysteresis, then drain
        fake_clock.advance(2.0)
        fleet.step()
    assert len(fleet.replicas) == 1, "no scale-down after idle hold"
    s = fleet.stats()
    assert s["scale_ups"] >= 1 and s["scale_downs"] >= 1
    assert s["tokens_lost_to_drain"] == 0.0


# ---------------------------------------------------------------------------
# record_autoscaling_metric -> scale decision (the wired seam)
# ---------------------------------------------------------------------------

def test_recorded_custom_metric_drives_scale_decision(fake_clock,
                                                      monkeypatch):
    """serve.metrics.record_autoscaling_metric was a producer with no
    consumer; now the fleet autoscaler reads it back through
    recorded_autoscaling_metric as its custom_metric_source. Proof: a
    scalar recorded inside a (faked) replica crosses the target and —
    after the hold — produces a +1, then recording a low value lets
    the fleet back down."""
    import ray_tpu.serve._private.replica as replica_mod
    from ray_tpu.serve import metrics as serve_metrics

    class _FakeReplica:
        _deployment = "llm"
        _replica_id = "llm#1"
        _app_name = "app"
        _custom_autoscaling_metric = None

        def get_autoscaling_metric(self):
            return self._custom_autoscaling_metric

    monkeypatch.setattr(replica_mod, "_current_replica", _FakeReplica())

    cfg = FleetAutoscalingConfig(
        min_replicas=1, max_replicas=2,
        target_custom_metric=10.0,
        custom_metric_source=serve_metrics.recorded_autoscaling_metric,
        upscale_hold_s=2.0, downscale_hold_s=4.0)
    sc = EngineStatsAutoscaler(cfg, clock=fake_clock)

    serve_metrics.record_autoscaling_metric(25.0)   # way over target
    assert sc.tick(_stats(), 1) == 0                # hold starts
    fake_clock.advance(3.0)
    assert sc.tick(_stats(), 1) == +1               # recorded scalar
    assert sc.last_signals["custom"] == 25.0        # drove the decision

    serve_metrics.record_autoscaling_metric(1.0)    # back under target
    assert sc.tick(_stats(), 2) == 0
    fake_clock.advance(5.0)
    assert sc.tick(_stats(), 2) == -1
    assert sc.scale_ups == 1 and sc.scale_downs == 1


def test_llm_server_shim_wires_custom_metric_source():
    """LLMFleetServer plugs recorded_autoscaling_metric in as the
    default custom_metric_source whenever target_custom_metric is set
    without an explicit source."""
    from ray_tpu.serve import metrics as serve_metrics
    from ray_tpu.serve.llm import LLMFleetServer

    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    srv = LLMFleetServer(
        _factory(params, cfg), fleet_id="shim-wire",
        initial_replicas=1,
        autoscaling={"min_replicas": 1, "max_replicas": 2,
                     "target_custom_metric": 5.0})
    assert srv.fleet.autoscaler.config.custom_metric_source \
        is serve_metrics.recorded_autoscaling_metric
    r = srv.generate([5, 6, 7], max_new_tokens=4)
    assert r["tokens"] == [5, 6, 7] + _solo(params, cfg, [5, 6, 7], 4)
    assert not r["shed"]
    r2 = srv.generate([5, 6, 7], max_new_tokens=4, deadline_s=0.0)
    assert r2["shed"] and r2["tokens"] == [5, 6, 7]


# ---------------------------------------------------------------------------
# Percentile snapshots (engine_metrics satellite)
# ---------------------------------------------------------------------------

def test_agg_percentiles_exact():
    from ray_tpu.models.engine_metrics import _Agg

    agg = _Agg()
    assert agg.percentile(95.0) == 0.0          # empty: no NaN, no raise
    for v in range(1, 101):                     # 1..100, shuffled order
        agg.add(float((v * 37) % 101))
    assert agg.percentile(50.0) == 51.0         # nearest-rank over 1..100
    assert agg.percentile(0.0) == 1.0
    assert agg.percentile(100.0) == 100.0
    out = {}
    agg.fields("lat", out)
    for k in ("lat_p50", "lat_p95", "lat_p99", "lat_mean", "lat_max"):
        assert k in out
    assert out["lat_p95"] >= out["lat_p50"]


def test_agg_percentiles_windowed():
    """The ring keeps only the most recent WINDOW observations — an
    old latency spike ages out of the snapshot (SLOs judge recent
    traffic), while count/sum/max remain lifetime aggregates."""
    from ray_tpu.models.engine_metrics import _Agg

    agg = _Agg()
    agg.add(1000.0)                             # ancient spike
    for _ in range(agg.WINDOW):
        agg.add(1.0)
    assert agg.percentile(99.0) == 1.0          # spike aged out
    assert agg.max == 1000.0                    # lifetime max remembers
    assert agg.count == agg.WINDOW + 1


def test_engine_stats_exposes_percentiles(nano_model, fake_clock):
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       clock=fake_clock)
    for p, n in zip(PROMPTS[:3], BUDGETS[:3]):
        eng.submit(p, n)
    while eng.pending():
        fake_clock.advance(0.25)
        eng.step()
    s = eng.stats()
    for field in ("ttft_s", "tpot_s", "queue_wait_s"):
        for q in ("p50", "p95", "p99"):
            assert f"{field}_{q}" in s
    assert s["ttft_s_p95"] >= s["ttft_s_p50"] > 0.0


# ---------------------------------------------------------------------------
# Fleet gauges through util.metrics
# ---------------------------------------------------------------------------

def test_fleet_gauges_reach_metrics_registry(nano_model):
    cfg, params = nano_model
    fleet = LLMFleet(_factory(params, cfg), initial_replicas=2,
                     fleet_id="gauge-test")
    fleet.submit(PROMPTS[0], 3, deadline_s=0.0)   # one shed
    fleet.submit(PROMPTS[1], 3)
    fleet.run()
    snap = fleet.stats()
    for key in ("replicas", "replicas_running", "requests_routed",
                "requests_shed", "pending_prefill_tokens",
                "slot_occupancy_mean", "ttft_s_p95_max",
                "tokens_lost_to_drain"):
        assert key in snap
    assert snap["requests_shed"] == 1.0

    from ray_tpu._private import metrics as _impl
    rows = {r["name"]: r for r in _impl.snapshots()
            if r["name"].startswith("llm_fleet_")
            and r["tags"].get("fleet") == "gauge-test"}
    assert "llm_fleet_replicas" in rows
    assert "llm_fleet_requests_shed" in rows
    assert rows["llm_fleet_requests_shed"]["value"] == 1.0
    # The per-replica engines are tagged too (llm_engine_* series).
    engine_rows = [r for r in _impl.snapshots()
                   if r["tags"].get("engine", "").startswith(
                       "gauge-test-r")]
    assert engine_rows


# ---------------------------------------------------------------------------
# Soak: sustained churn with scaling (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_soak_churn_identity(nano_model, fake_clock):
    """Long mixed-priority shared-prefix churn with autoscaling live:
    every non-shed request still matches solo, across many
    scale/drain cycles."""
    cfg, params = nano_model
    rng = np.random.RandomState(5)
    prefix = list(range(1, 9))
    auto = FleetAutoscalingConfig(min_replicas=1, max_replicas=3,
                                  ttft_p95_slo_s=0.5,
                                  occupancy_low=0.2,
                                  upscale_hold_s=2.0,
                                  downscale_hold_s=4.0)
    fleet = LLMFleet(
        _factory(params, cfg, prefix_cache=True, kv_block_tokens=4,
                 clock=fake_clock),
        initial_replicas=1, autoscaling=auto, fleet_id="soak",
        clock=fake_clock)
    want = {}
    for i in range(60):
        p = (prefix if i % 2 else []) + \
            rng.randint(1, cfg.vocab_size, size=3).tolist()
        n = int(rng.randint(2, 6))
        fid = fleet.submit(p, n, priority=int(i % 3),
                           deadline_s=None if i % 7 else 30.0)
        want[fid] = (p, n)
        fake_clock.advance(0.5)
        fleet.step()
        if i == 30:                      # operator-forced drain cycle
            names = [r.name for r in fleet.replicas]
            if len(names) > 1:
                fleet.drain_replica(names[0])
    out = fleet.run()
    shed = fleet.stats()["requests_shed"]
    for fid, (p, n) in want.items():
        if fid in out and out[fid]:
            assert out[fid] == _solo(params, cfg, p, n)
    assert fleet.stats()["tokens_lost_to_drain"] == 0.0
    assert shed == 0.0                   # 30s deadlines never expired

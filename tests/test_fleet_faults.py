"""Fleet fault tolerance (models/fleet.py + models/fault_injection.py).

Gold contract, extended across FAILURES: a request whose replica dies
mid-stream is reconstructed from host bookkeeping and finishes with
tokens IDENTICAL to the fault-free run — greedy and sampled — with
``tokens_lost_to_failure == 0``. The fleet pins every request's
sampling key at submit (fleet-id derived, never replica-derived) and
the engine's per-token keys depend only on (key, token index), so a
resume on a different replica replays the exact stream.

The health state machine (watchdog / slow / silent probes, circuit
breaker, replacement) is unit-tested on stub engines over the shared
FakeClock — no real time, no JAX. The seeded soak (@slow) runs a
random fault schedule against three engine configs and both sampling
modes. Lost requests surface as typed errors from run()/pop_result()
instead of hanging — the regression this file exists to hold.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, llama_init
from ray_tpu.models.engine import DecodeEngine
from ray_tpu.models.fault_injection import FaultInjector, InjectedFault
from ray_tpu.models.fleet import (RUNNING, SUSPECT, FleetHealthConfig,
                                  LLMFleet, ReplicaUnavailable,
                                  RetriesExhausted)
from ray_tpu.models.generate import generate
from ray_tpu.models.scheduler import EngineOverloaded, SubmitTimeout


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


PROMPTS = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2], [3, 1, 4, 1, 5, 9]]

SAMPLING_MODES = {
    "greedy": {},
    "top_k": {"greedy": False, "temperature": 0.9, "top_k": 8},
}


# ---------------------------------------------------------------------------
# Health state machine on stub engines + FakeClock
# ---------------------------------------------------------------------------

class StubEngine:
    """Duck-typed replica for driving the fleet's health probes with
    no JAX and no real time: `step()` advances the shared FakeClock by
    `step_time` (what the watchdog/slow probes measure) and bumps
    `steps_total` unless wedged (what the silent probe measures)."""

    def __init__(self, name, clock, step_time=0.0):
        self.engine_id = name
        self.clock = clock
        self.step_time = step_time
        self.wedged = False      # True: step runs but makes no progress
        self.fail_steps = 0      # next N step() calls raise
        self.steps_total = 0
        self.halted = False
        self.draining = False
        self.finished = set()
        self.shed_ids = set()
        self.results = {}
        self.scheduler = []      # len() == queue depth for the router
        self.row_req = [None, None]
        self._next_rid = 0

    def pending(self):
        return not self.halted

    def step(self, horizon=None):
        if self.fail_steps > 0:
            self.fail_steps -= 1
            raise InjectedFault(f"{self.engine_id}: scripted step error")
        self.clock.advance(self.step_time)
        if not self.wedged:
            self.steps_total += 1
        return {}

    def submit(self, prompt, max_new_tokens=32, priority=0, rng=None,
               deadline_s=None, greedy=None, resume_tokens=None):
        rid = self._next_rid
        self._next_rid += 1
        return rid

    def pop_result(self, rid):
        raise KeyError(rid)

    def stats(self):
        return {}

    def pending_prefill_tokens(self):
        return 0

    def prefix_match_tokens(self, prompt, peek=True):
        return 0

    def halt(self):
        self.halted = True

    def begin_drain(self):
        self.draining = True


def _stub_fleet(clock, health, n=1, step_time=0.0, **kw):
    built = []

    def factory(name):
        eng = StubEngine(name, clock, step_time)
        built.append(eng)
        return eng

    fleet = LLMFleet(factory, initial_replicas=n, router="round_robin",
                     health=health, clock=clock, **kw)
    return fleet, built


def test_watchdog_condemns_after_timeouts(fake_clock):
    """Two steps over the deadline condemn the replica; a replacement
    joins the pool in the same step."""
    health = FleetHealthConfig(step_deadline_s=1.0,
                               unhealthy_after_timeouts=2)
    fleet, built = _stub_fleet(fake_clock, health, step_time=2.0,
                               fleet_id="hw")
    fleet.step()
    assert fleet.replica_health() == {"hw-r0": SUSPECT}
    fleet.step()
    assert fleet.replicas_failed == 1
    assert built[0].halted
    assert fleet.replica_health() == {"hw-r1": RUNNING}
    s = fleet.stats()
    assert s["replicas_failed"] == 1.0
    assert s["replicas_suspect"] == 0.0


def test_slow_steps_suspect_then_recover(fake_clock):
    """Consecutive slow (but under-deadline) steps reach SUSPECT;
    clean steps promote the replica back to RUNNING — no failover."""
    health = FleetHealthConfig(slow_step_s=0.5, suspect_after_slow=2,
                               recover_after=2)
    fleet, built = _stub_fleet(fake_clock, health, step_time=0.6,
                               fleet_id="hs")
    fleet.step()
    assert fleet.replica_health()["hs-r0"] == RUNNING   # streak of 1
    fleet.step()
    assert fleet.replica_health()["hs-r0"] == SUSPECT
    built[0].step_time = 0.0
    fleet.step()
    assert fleet.replica_health()["hs-r0"] == SUSPECT   # 1 good step
    fleet.step()
    assert fleet.replica_health()["hs-r0"] == RUNNING
    assert fleet.replicas_failed == 0


def test_silent_steps_escalate_suspect_then_unhealthy(fake_clock):
    """A stepping-but-frozen engine (steps_total not advancing while
    work is pending) escalates SUSPECT then condemned — the probe that
    catches a wedged or hijacked step that neither raises nor slows."""
    health = FleetHealthConfig(suspect_after_silent=2,
                               unhealthy_after_silent=4)
    fleet, built = _stub_fleet(fake_clock, health, fleet_id="hq")
    built[0].wedged = True
    fleet.step()
    assert fleet.replica_health()["hq-r0"] == RUNNING
    fleet.step()
    assert fleet.replica_health()["hq-r0"] == SUSPECT
    fleet.step()
    fleet.step()
    assert fleet.replicas_failed == 1
    assert built[0].halted


def test_step_error_fails_fast_by_default(fake_clock):
    """max_step_failures=1 (the default): one step() exception condemns
    and replaces the replica immediately."""
    fleet, built = _stub_fleet(fake_clock, FleetHealthConfig(),
                               fleet_id="he")
    built[0].fail_steps = 1
    fleet.step()
    assert fleet.replicas_failed == 1
    assert built[0].halted
    assert fleet.replica_health() == {"he-r1": RUNNING}


def test_step_error_tolerated_until_threshold(fake_clock):
    """max_step_failures=2: the first exception is probation (SUSPECT),
    the second — even after an intervening recovery — condemns (the
    failure count is cumulative, not a streak)."""
    health = FleetHealthConfig(max_step_failures=2, recover_after=1)
    fleet, built = _stub_fleet(fake_clock, health, fleet_id="ht")
    built[0].fail_steps = 1
    fleet.step()
    assert fleet.replica_health()["ht-r0"] == SUSPECT
    assert fleet.replicas_failed == 0
    fleet.step()                       # clean: recovers
    assert fleet.replica_health()["ht-r0"] == RUNNING
    built[0].fail_steps = 1
    fleet.step()
    assert fleet.replicas_failed == 1


def test_circuit_breaker_opens_on_flapping_and_cools_down(fake_clock):
    """breaker_trips SUSPECT entries inside the window open the
    breaker: the replica — though RUNNING again — stops receiving new
    submits until the cooldown lapses."""
    health = FleetHealthConfig(slow_step_s=0.5, suspect_after_slow=1,
                               recover_after=1, breaker_trips=2,
                               breaker_window_s=100.0,
                               breaker_cooldown_s=5.0)
    fleet, built = _stub_fleet(fake_clock, health, n=2, fleet_id="hb")
    flapper, steady = built
    # Flap r0 twice: slow -> SUSPECT -> recover -> slow -> SUSPECT.
    flapper.step_time = 0.6
    fleet.step()
    flapper.step_time = 0.0
    fleet.step()
    assert fleet.replica_health()["hb-r0"] == RUNNING
    flapper.step_time = 0.6
    fleet.step()
    flapper.step_time = 0.0
    fleet.step()
    assert fleet.replica_health()["hb-r0"] == RUNNING
    assert fleet.stats()["breakers_open"] == 1.0
    for _ in range(3):                 # routed around, not to
        fleet.submit([1, 2, 3], 4)
    assert flapper._next_rid == 0
    assert steady._next_rid == 3
    fake_clock.advance(5.1)            # cooldown lapses: half-open
    assert fleet.stats()["breakers_open"] == 0.0
    fleet.submit([1, 2, 3], 4)
    fleet.submit([1, 2, 3], 4)
    assert flapper._next_rid >= 1


# ---------------------------------------------------------------------------
# Deterministic failover: kill mid-churn, bit-identical streams
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", list(SAMPLING_MODES))
def test_failover_token_identity_kill_mid_churn(nano_model, mode):
    """Kill one of two replicas while its rows are mid-generation:
    every request — including the failed-over ones — returns the exact
    token stream of the fault-free run, nothing is lost, and the dead
    replica is replaced."""
    cfg, params = nano_model
    kw = SAMPLING_MODES[mode]
    prompts = PROMPTS + [[11, 13], [2, 7, 1, 8], [8, 3], [6, 6, 6]]

    def drive(fleet_id, inj):
        fleet = LLMFleet(
            _factory(params, cfg, decode_horizon=4, **kw),
            initial_replicas=2, router="round_robin",
            fleet_id=fleet_id, fault_injector=inj)
        fids = [fleet.submit(p, 12) for p in prompts]
        out = fleet.run()
        return [out[f] for f in fids], fleet

    base, _ = drive(f"ff-base-{mode}", None)
    inj = FaultInjector(
        schedule={f"ff-chaos-{mode}-r0": [(1, "kill")]})
    chaos, fleet = drive(f"ff-chaos-{mode}", inj)

    assert inj.fired == [(f"ff-chaos-{mode}-r0", 1, "kill")]
    s = fleet.stats()
    assert s["replicas_failed"] == 1.0
    assert s["tokens_lost_to_failure"] == 0.0
    assert s["requests_recovered"] >= 1.0
    assert s["replicas_running"] == 2.0   # replacement joined
    assert chaos == base


def test_failover_matches_solo_generate_with_pinned_keys(nano_model):
    """The engine suite's gold contract survives a replica failure:
    sampled requests with caller-pinned rng keys still match their
    solo `generate` runs after being failed over mid-stream."""
    cfg, params = nano_model
    kw = SAMPLING_MODES["top_k"]
    inj = FaultInjector(schedule={"fs-r0": [(1, "kill")]})
    fleet = LLMFleet(_factory(params, cfg, decode_horizon=4, **kw),
                     initial_replicas=2, router="round_robin",
                     fleet_id="fs", fault_injector=inj)
    keys = [jax.random.PRNGKey(40 + i) for i in range(len(PROMPTS))]
    fids = [fleet.submit(p, 8, rng=k) for p, k in zip(PROMPTS, keys)]
    out = fleet.run()
    assert inj.fired
    for fid, p, k in zip(fids, PROMPTS, keys):
        assert out[fid] == _solo(params, cfg, p, 8, rng=k, **kw), \
            f"fleet req {fid} diverged from solo across failover"
    assert fleet.tokens_lost_to_failure == 0


def test_streaming_is_gapless_across_failover(nano_model):
    """Tokens streamed via step() before the kill, plus everything
    streamed after, concatenate to exactly the final result — the
    salvage buffer fills the gap, nothing repeats, nothing is lost."""
    cfg, params = nano_model
    inj = FaultInjector(schedule={"fg-r0": [(2, "kill")]})
    fleet = LLMFleet(_factory(params, cfg, decode_horizon=2),
                     initial_replicas=2, router="round_robin",
                     fleet_id="fg", fault_injector=inj)
    fids = [fleet.submit(p, 10) for p in PROMPTS]
    streamed = {f: [] for f in fids}
    while fleet.pending():
        for fid, toks in fleet.step().items():
            streamed[fid].extend(toks)
    for rep in fleet.replicas:
        fleet._sweep_finished(rep)
    assert inj.fired
    for fid in fids:
        assert streamed[fid] == fleet.pop_result(fid)


# ---------------------------------------------------------------------------
# Typed errors instead of hangs (the regression tests)
# ---------------------------------------------------------------------------

def test_run_raises_retries_exhausted_with_partial_results(nano_model):
    """Replica dies, no retries, no replacement: run() returns promptly
    with a typed error carrying WHICH requests died and every
    successful result — it does not hang polling lost tokens."""
    cfg, params = nano_model
    health = FleetHealthConfig(max_retries=0, replace_failed=False)
    inj = FaultInjector(schedule={"lost-r0": [(1, "kill")]})
    fleet = LLMFleet(_factory(params, cfg, decode_horizon=4),
                     initial_replicas=2, router="round_robin",
                     fleet_id="lost", health=health,
                     fault_injector=inj)
    fids = [fleet.submit(p, 8) for p in PROMPTS]
    with pytest.raises(RetriesExhausted) as ei:
        fleet.run()
    err = ei.value
    # Round-robin placement: fids 0, 2 landed on the killed replica.
    assert set(err.failed) == {0, 2}
    assert all(isinstance(e, RetriesExhausted)
               for e in err.failed.values())
    assert set(err.partial) == {1, 3}
    assert all(len(err.partial[f]) == 8 for f in (1, 3))
    assert not fleet.pending()
    assert fids == [0, 1, 2, 3]


def test_pop_result_raises_for_failed_request(nano_model):
    """Polling callers get the same typed error surface: failed fids
    appear in `finished` (wakes pollers) and `failed_ids`, and
    pop_result raises their stored error; surviving requests pop
    normally."""
    cfg, params = nano_model
    health = FleetHealthConfig(max_retries=0, replace_failed=False)
    inj = FaultInjector(schedule={"poll-r0": [(1, "kill")]})
    fleet = LLMFleet(_factory(params, cfg, decode_horizon=4),
                     initial_replicas=2, router="round_robin",
                     fleet_id="poll", health=health,
                     fault_injector=inj)
    [fleet.submit(p, 8) for p in PROMPTS]
    while fleet.pending():
        fleet.step()
    for rep in fleet.replicas:
        fleet._sweep_finished(rep)
    assert fleet.failed_ids == {0, 2}
    assert {0, 2} <= fleet.finished
    with pytest.raises(RetriesExhausted):
        fleet.pop_result(0)
    assert len(fleet.pop_result(1)) == 8


def test_no_survivors_raises_replica_unavailable(nano_model):
    """Retry budget present but nowhere to spend it: with the only
    replica dead and replacement disabled, the parked retry fails with
    ReplicaUnavailable instead of waiting forever, and later submits
    refuse immediately."""
    cfg, params = nano_model
    health = FleetHealthConfig(replace_failed=False)
    inj = FaultInjector(schedule={"empty-r0": [(1, "kill")]})
    fleet = LLMFleet(_factory(params, cfg, decode_horizon=4),
                     initial_replicas=1, fleet_id="empty",
                     health=health, fault_injector=inj)
    fid = fleet.submit([5, 6, 7], 8)
    with pytest.raises(ReplicaUnavailable) as ei:
        fleet.run()
    assert set(ei.value.failed) == {fid}
    with pytest.raises(ReplicaUnavailable):
        fleet.submit([1, 2], 2)


def test_retry_backoff_is_deterministic_and_capped(fake_clock):
    """Retry n's backoff: immediate first failover, exponential after,
    capped, and jittered deterministically from the request's own key
    — the same request backs off identically every run."""
    health = FleetHealthConfig(backoff_base_s=0.02, backoff_factor=2.0,
                               backoff_max_s=0.1)
    fleet, _ = _stub_fleet(fake_clock, health, fleet_id="hbk")
    fid = fleet.submit([1, 2, 3], 4)
    meta = fleet._requests[fid]
    assert fleet._backoff_delay(meta, 1) == 0.0
    d2 = fleet._backoff_delay(meta, 2)
    d3 = fleet._backoff_delay(meta, 3)
    assert 0.02 <= d2 <= 0.03          # base, +<=50% jitter
    assert 0.04 <= d3 <= 0.06
    assert d2 == fleet._backoff_delay(meta, 2)   # deterministic
    d9 = fleet._backoff_delay(meta, 9)
    assert d9 <= 0.1 * 1.5             # capped before jitter


def test_submit_block_timeout_raises_typed_error(nano_model):
    """on_full="block" with block_timeout_s: a submit that cannot find
    queue room before the deadline raises SubmitTimeout (an
    EngineOverloaded, so existing shed handling catches it) instead of
    spinning forever — here the engine is wedged by a silent fault so
    stepping never frees the queue."""
    cfg, params = nano_model

    class TickClock:
        """Self-advancing: every read moves time, so the block loop's
        deadline lapses without real waiting."""

        def __init__(self):
            self.t = 0.0

        def __call__(self):
            self.t += 0.01
            return self.t

    eng = DecodeEngine(params, cfg, engine_id="wedge", batch_slots=1,
                       max_len=32, max_queue=1, on_full="block",
                       block_timeout_s=0.5, clock=TickClock())
    inj = FaultInjector(schedule={"wedge": [(0, ("silent", 1 << 30))]})
    inj.arm(eng, "wedge")
    eng.submit([5, 6, 7], 4)
    with pytest.raises(SubmitTimeout) as ei:
        eng.submit([1, 2, 3], 4)
    assert isinstance(ei.value, EngineOverloaded)


# ---------------------------------------------------------------------------
# Observability: state API, status CLI, trace report
# ---------------------------------------------------------------------------

def test_state_api_reports_health_and_recovering(nano_model):
    """The serving state API shows the fault plane live: per-replica
    health on engine rows, `status="recovering"` rows for requests
    parked in the retry queue, and the fleet summary's health census
    and recovery counters."""
    from ray_tpu.util.state import serving

    cfg, params = nano_model
    inj = FaultInjector(schedule={"sapi-r0": [(1, "kill")]})
    fleet = LLMFleet(_factory(params, cfg, decode_horizon=4),
                     initial_replicas=2, router="round_robin",
                     fleet_id="sapi", fault_injector=inj)
    fids = [fleet.submit(p, 8) for p in PROMPTS]
    for _ in range(10):
        fleet.step()
        if fleet.replicas_failed:
            break
    assert fleet.replicas_failed == 1

    # Between the failing step and the next one the killed replica's
    # requests sit in the retry queue — visible as "recovering".
    rec = [r for r in serving.list_requests(status="recovering")
           if r.get("fleet") == "sapi"]
    assert {r["req_id"] for r in rec} == {0, 2}
    assert all(r["engine_id"] is None for r in rec)
    assert all(r["attempts"] == 1 for r in rec)

    engs = {e["engine_id"]: e for e in serving.list_engines()}
    for name, state in fleet.replica_health().items():
        assert engs[name]["fleet"] == "sapi"
        assert engs[name]["health"] == state == RUNNING

    fb = next(f for f in serving.summarize_fleet()["fleets"]
              if f["fleet_id"] == "sapi")
    assert fb["replicas_failed"] == 1
    assert fb["requests_recovering"] == 2
    assert fb["health"] == {"RUNNING": 2}

    out = fleet.run()
    assert all(len(out[f]) == 8 for f in fids)
    fb = next(f for f in serving.summarize_fleet()["fleets"]
              if f["fleet_id"] == "sapi")
    assert fb["requests_recovered"] == 2
    assert fb["requests_recovering"] == 0
    assert fb["tokens_lost_to_failure"] == 0


def test_status_cli_shows_faults_line(nano_model):
    """ray_tpu_status renders a faults line for a fleet that has seen
    failures — replica count, recoveries, retries, tokens lost."""
    from tools.ray_tpu_status import collect, format_status

    cfg, params = nano_model
    inj = FaultInjector(schedule={"scli-r0": [(1, "kill")]})
    fleet = LLMFleet(_factory(params, cfg, decode_horizon=4),
                     initial_replicas=2, router="round_robin",
                     fleet_id="scli", fault_injector=inj)
    [fleet.submit(p, 8) for p in PROMPTS]
    fleet.run()
    assert fleet.replicas_failed == 1
    text = format_status(collect())
    assert "fleet scli:" in text
    assert "faults: 1 replica(s) failed, 2 requests recovered " \
        "(2 retries)" in text


def test_trace_report_failover_summary(nano_model, tmp_path):
    """A traced chaos run's dump carries the fault instants, and
    trace_report folds them into the failover summary + report
    footer."""
    from tools.trace_report import (failover_summary, format_report,
                                    request_breakdowns)

    cfg, params = nano_model
    inj = FaultInjector(schedule={"trf-r0": [(1, "kill")]})
    fleet = LLMFleet(_factory(params, cfg, decode_horizon=4),
                     initial_replicas=2, router="round_robin",
                     fleet_id="trf", fault_injector=inj, trace=True)
    [fleet.submit(p, 8) for p in PROMPTS]
    fleet.run()
    events = fleet.dump_trace(str(tmp_path / "chaos.trace.json"))

    faults = failover_summary(events)
    assert faults is not None
    assert faults["replicas_failed"] == 1
    assert faults["failed_replicas"] == ["trf-r0"]
    assert faults["failovers"] == 2
    text = format_report(request_breakdowns(events), faults=faults)
    assert "-- faults: 1 replica(s) failed (trf-r0), 2 failovers" \
        in text
    # A fault-free trace has no summary (and no footer line).
    clean = LLMFleet(_factory(params, cfg), initial_replicas=1,
                     fleet_id="trc", trace=True)
    clean.submit([5, 6, 7], 4)
    clean.run()
    assert failover_summary(clean.dump_trace()) is None


# ---------------------------------------------------------------------------
# Seeded soak: random fault schedule x engine configs x sampling modes
# ---------------------------------------------------------------------------

ENGINE_CONFIGS = {
    "prefix": {"prefix_cache": True, "kv_block_tokens": 4},
    "blocks4": {"kv_block_tokens": 4},
    "pipeline": {"pipeline_depth": 2},
}


@pytest.mark.slow
@pytest.mark.parametrize("mode", list(SAMPLING_MODES))
@pytest.mark.parametrize("config", list(ENGINE_CONFIGS))
def test_fault_soak_token_identity(nano_model, config, mode):
    """300 steps of seeded-random kills/raises/silences against live
    traffic, for each engine memory config and sampling mode: every
    request finishes bit-identical to the fault-free arm, zero tokens
    lost, and the pool ends at full strength."""
    cfg, params = nano_model
    kw = SAMPLING_MODES[mode]
    arrivals = [(PROMPTS[i % len(PROMPTS)] + [i % 7 + 1], 3 + i % 6)
                for i in range(30)]

    def drive(fleet_id, inj):
        fleet = LLMFleet(
            _factory(params, cfg, decode_horizon=4,
                     **ENGINE_CONFIGS[config], **kw),
            initial_replicas=2, router="round_robin",
            fleet_id=fleet_id, fault_injector=inj,
            health=FleetHealthConfig(max_retries=10))
        fids = []
        for step in range(300):
            if step % 5 == 0 and len(fids) < len(arrivals):
                p, n = arrivals[len(fids)]
                fids.append(fleet.submit(p, n))
            fleet.step()
        out = fleet.run()
        return [out[f] for f in fids], fleet

    base, _ = drive(f"soak-{config}-{mode}-base", None)
    inj = FaultInjector(seed=1234, p_kill=0.04, p_raise=0.04,
                        p_silent=0.01, stall_s=0.0)
    chaos, fleet = drive(f"soak-{config}-{mode}-chaos", inj)

    assert inj.fired, "seeded fault process never fired — dead soak"
    s = fleet.stats()
    assert s["replicas_failed"] >= 1.0
    assert s["tokens_lost_to_failure"] == 0.0
    assert s["requests_failed"] == 0.0
    assert s["replicas_running"] == 2.0
    assert chaos == base

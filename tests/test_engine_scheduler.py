"""Scheduler policies + request-lifecycle telemetry for DecodeEngine
(ray_tpu/models/{scheduler,engine_metrics}.py).

Contract under test: scheduling only reorders ADMISSIONS — priority
classes, bounded-queue backpressure, and the per-step prefill budget
never change any admitted request's tokens (identity vs solo generate
is extended over policies in test_engine.py; here the policies' own
semantics are pinned down) — and every request's queue-wait/TTFT/TPOT
lands in the util.metrics Prometheus plane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, llama_init
from ray_tpu.models.engine import DecodeEngine, _Request
from ray_tpu.models.engine_metrics import EngineMetrics
from ray_tpu.models.generate import generate
from ray_tpu.models.scheduler import (EngineOverloaded, FIFOPolicy,
                                      PriorityPolicy, make_policy)


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _solo(params, cfg, prompt, n):
    out = np.asarray(generate(params, jnp.asarray([prompt], jnp.int32),
                              cfg, max_new_tokens=n))
    return out[0, len(prompt):].tolist()


def _req(rid, priority=0, seq=None):
    return _Request(rid, [1], 4, priority=priority,
                    seq=rid if seq is None else seq)


# ---------------------------------------------------------------------------
# Policy units (no model)
# ---------------------------------------------------------------------------

def test_fifo_policy_orders_by_submission():
    pol = FIFOPolicy()
    for i in range(4):
        pol.push(_req(i))
    assert len(pol) == 4
    assert sorted(pol.snapshot()) == [0, 1, 2, 3]
    assert [pol.pop().req_id for _ in range(4)] == [0, 1, 2, 3]
    assert len(pol) == 0


def test_priority_policy_orders_by_class_then_fifo():
    pol = PriorityPolicy()
    pol.push(_req(0, priority=5))
    pol.push(_req(1, priority=0))
    pol.push(_req(2, priority=5))     # same class as 0: FIFO within it
    pol.push(_req(3, priority=-1))    # negative = even more urgent
    order = [pol.pop().req_id for _ in range(4)]
    assert order == [3, 1, 0, 2]


def test_make_policy_resolution():
    assert isinstance(make_policy("fifo"), FIFOPolicy)
    assert isinstance(make_policy("priority"), PriorityPolicy)
    pol = FIFOPolicy()
    assert make_policy(pol) is pol
    with pytest.raises(ValueError, match="unknown scheduler policy"):
        make_policy("lifo")
    with pytest.raises(ValueError, match="on_full"):
        DecodeEngine({}, LlamaConfig.nano(), on_full="drop")


# ---------------------------------------------------------------------------
# Engine + policy semantics
# ---------------------------------------------------------------------------

def test_priority_overtakes_queued_fifo_traffic(nano_model):
    """One slot, occupied: a later-submitted priority-0 request must be
    admitted before the earlier priority-10 one — and both still decode
    exactly (scheduling reorders admission, not computation)."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       scheduler="priority")
    running = eng.submit([5, 6, 7], 3)
    eng.step()                                   # occupies the slot
    batch = eng.submit([9, 8, 7, 6], 3, priority=10)
    urgent = eng.submit([1, 2], 3, priority=0)
    admitted = []
    while eng.pending():
        eng.step(horizon=1)      # pinned: per-step occupant observation
        occupant = eng.row_req[0]
        if (occupant is not None and occupant.req_id != running
                and occupant.req_id not in admitted):
            admitted.append(occupant.req_id)
    assert admitted == [urgent, batch]
    assert eng.pop_result(urgent) == _solo(params, cfg, [1, 2], 3)
    assert eng.pop_result(batch) == _solo(params, cfg, [9, 8, 7, 6], 3)


def test_backpressure_reject(nano_model):
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       max_queue=2, on_full="reject")
    eng.submit([1, 2], 2)
    eng.submit([3, 4], 2)
    with pytest.raises(EngineOverloaded, match="queue full"):
        eng.submit([5, 6], 2)
    assert eng.stats()["requests_rejected"] == 1
    # draining the queue makes room again
    eng.run()
    rid = eng.submit([5, 6], 2)
    out = eng.run()
    assert out[rid] == _solo(params, cfg, [5, 6], 2)


def test_backpressure_block_drains_and_preserves_output(nano_model):
    """on_full="block": submit() drives the engine until a queue slot
    frees instead of raising; every request still matches solo."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       max_queue=1, on_full="block")
    prompts = [[5, 6, 7], [9, 8, 7, 6], [1, 2], [3, 1, 4]]
    ids = [eng.submit(p, 3) for p in prompts]    # blocks internally
    out = eng.run()
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, 3), f"req {rid}"
    assert eng.stats()["requests_rejected"] == 0


def test_prefill_budget_guards_decode_rows(nano_model):
    """With 3 free slots, a 4-deep queue, and max_prefills_per_step=1,
    each step admits at most ONE newcomer — in-flight rows never wait
    for more than one prefill per step."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=32,
                       max_prefills_per_step=1)
    first = eng.submit([5, 6, 7], 8)
    eng.step(horizon=1)        # first occupies a slot (pinned horizon:
    for p in ([9, 8], [1, 2], [3, 4], [7, 7]):   # the test observes
        eng.submit(p, 8)       # per-step admissions; adaptive H would
    live = [sum(r is not None for r in eng.row_req)]   # finish rows)
    for _ in range(3):
        eng.step(horizon=1)
        live.append(sum(r is not None for r in eng.row_req))
    assert live == [1, 2, 3, 4]                  # one admission per step
    # unbudgeted engine admits the whole burst in one step
    eng2 = DecodeEngine(params, cfg, batch_slots=4, max_len=32)
    eng2.submit([5, 6, 7], 8)
    eng2.step(horizon=1)
    for p in ([9, 8], [1, 2], [3, 4], [7, 7]):
        eng2.submit(p, 8)
    eng2.step(horizon=1)
    assert sum(r is not None for r in eng2.row_req) == 4
    out = eng.run()
    assert out[first] == _solo(params, cfg, [5, 6, 7], 8)


def test_prefill_budget_counts_rows_mid_prompt(nano_model):
    """A row mid-prompt prefills a chunk every step, so it counts against
    max_prefills_per_step: with prompts four chunks long, a budget of 2
    and six free slots, no step's prefill holds more than two rows (the
    admissions alone would stack all six), and tokens are solo's."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=6, max_len=64,
                       prefill_chunk=8, max_prefills_per_step=2)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (30, 27, 32, 9, 25, 31)]
    ids = [eng.submit(p, 4) for p in prompts]
    mid, out = [], {}
    while len(out) < len(ids):
        eng.step()
        mid.append(len(eng._row_prefill))
        for rid in list(eng.finished):
            out[rid] = eng.pop_result(rid)
    assert max(mid) == 2
    for rid, p in zip(ids, prompts):
        assert out[rid] == _solo(params, cfg, p, 4), f"req {rid}"
    # one chunk a prompt: the budget is the admissions', as it was
    eng = DecodeEngine(params, cfg, batch_slots=6, max_len=64,
                       prefill_chunk=8, max_prefills_per_step=2)
    for _ in range(6):
        eng.submit([3, 4, 5], 8)
    live = []
    for _ in range(3):
        eng.step(horizon=1)
        live.append(sum(r is not None for r in eng.row_req))
    assert live == [2, 4, 6]


def test_knob_validation(nano_model):
    cfg, params = nano_model
    with pytest.raises(ValueError, match="max_queue"):
        DecodeEngine(params, cfg, max_queue=0)
    with pytest.raises(ValueError, match="max_prefills_per_step"):
        DecodeEngine(params, cfg, max_prefills_per_step=0)


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------

def test_engine_metrics_lifecycle_with_fake_clock():
    """Deterministic lifecycle math: queue wait = submit→admit, TTFT =
    submit→first token, TPOT = inter-token gap, finish clears state."""
    t = [100.0]
    m = EngineMetrics(engine_id="fake-clock-engine", batch_slots=4,
                      clock=lambda: t[0])
    m.on_submit(7)
    t[0] = 100.5
    m.on_admit(7)
    t[0] = 100.75
    m.on_token(7)           # first token: TTFT vs submit
    t[0] = 100.80
    m.on_token(7)           # second: TPOT vs previous token
    m.on_finish(7)
    m.on_step(live_slots=2, queue_depth=3, tokens_emitted=2)
    s = m.stats()
    assert s["queue_wait_s_mean"] == pytest.approx(0.5)
    assert s["ttft_s_mean"] == pytest.approx(0.75)
    assert s["tpot_s_mean"] == pytest.approx(0.05)
    assert s["requests_finished"] == 1
    assert s["tokens_generated"] == 2
    assert s["slot_occupancy"] == pytest.approx(0.5)
    assert s["batch_efficiency"] == pytest.approx(0.5)
    assert s["queue_depth"] == 3


def test_engine_workload_telemetry_reaches_metrics_plane(nano_model):
    """A real CPU engine workload: TTFT/TPOT/queue-wait/occupancy land
    both in stats() and in the process-local util/metrics registry (the
    same table the GCS pusher ships to the dashboard's Prometheus
    /metrics endpoint), tagged with this engine's id."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       engine_id="telemetry-test-engine")
    prompts = [[5, 6, 7], [9, 8, 7, 6], [1, 2]]
    ids = [eng.submit(p, 4) for p in prompts]
    out = eng.run()
    assert sorted(out) == sorted(ids)

    s = eng.stats()
    assert s["requests_submitted"] == 3
    assert s["requests_admitted"] == 3
    assert s["requests_finished"] == 3
    assert s["tokens_generated"] == 12
    assert s["queue_wait_s_count"] == 3
    assert s["ttft_s_count"] == 3
    assert s["ttft_s_mean"] > 0
    # 12 tokens, 3 first-tokens -> 9 inter-token gaps
    assert s["tpot_s_count"] == 9
    assert s["queue_depth"] == 0 and s["live_slots"] == 0

    from ray_tpu._private import metrics as _impl

    rows = [r for r in _impl.snapshots()
            if r["tags"].get("engine") == "telemetry-test-engine"]
    by_name = {r["name"]: r for r in rows}
    assert by_name["llm_engine_requests_submitted_total"]["value"] == 3
    assert by_name["llm_engine_requests_finished_total"]["value"] == 3
    assert by_name["llm_engine_tokens_generated_total"]["value"] == 12
    for hist in ("llm_engine_queue_wait_s", "llm_engine_ttft_s",
                 "llm_engine_tpot_s"):
        row = by_name[hist]
        assert row["kind"] == "histogram" and row["count"] >= 3, hist
        assert row["sum"] >= 0
    assert by_name["llm_engine_ttft_s"]["count"] == 3
    assert by_name["llm_engine_tpot_s"]["count"] == 9
    # gauges reflect the drained engine
    assert by_name["llm_engine_queue_depth"]["value"] == 0
    assert by_name["llm_engine_slot_occupancy"]["kind"] == "gauge"


def test_report_engine_stats_outside_replica(nano_model):
    """serve.metrics.report_engine_stats republishes the snapshot as
    serve_llm_engine_* gauges even without a replica context (inside a
    replica the deployment/replica/application tags ride along — see
    test_llm_serving.py)."""
    cfg, params = nano_model
    from ray_tpu.serve import metrics as serve_metrics

    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       engine_id="serve-stats-engine")
    eng.submit([5, 6, 7], 3)
    eng.run()
    serve_metrics.report_engine_stats(eng.stats())

    from ray_tpu._private import metrics as _impl

    rows = {r["name"]: r for r in _impl.snapshots()}
    assert rows["serve_llm_engine_requests_finished"]["value"] == 1
    assert rows["serve_llm_engine_tokens_generated"]["value"] == 3
    assert "serve_llm_engine_ttft_s_mean" in rows
    assert "serve_llm_engine_slot_occupancy" in rows

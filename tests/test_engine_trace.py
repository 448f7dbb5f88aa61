"""Request-lifecycle tracing (ray_tpu/models/engine_trace.py).

Three layers under test:

- the tracer itself: bounded ring + drop counter, open/close pairing,
  the `span_since_mark` contiguity frontier, chrome event shape, env
  gate and the `trace=` knob resolution;
- the engine wiring: a traced run reconstructs every request's
  lifecycle (submit -> queue_wait -> admit -> prefill -> decode ->
  finish, plus preempt/swap and shed paths) with span durations that
  SUM to the request's end-to-end latency — the contiguity property
  `tools/trace_report.py` leans on — and, the gold contract, tokens
  stay identical to solo generate with tracing enabled across the
  engine feature matrix;
- the fleet stitch: replica traces + route spans merge into one
  chrome-loadable file, pid per replica, with the router's scoring
  decision recorded on each route span.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, llama_init
from ray_tpu.models.engine import DecodeEngine
from ray_tpu.models.engine_trace import (EngineTracer, NULL_TRACER,
                                         NullEngineTracer,
                                         maybe_tracer_from_env,
                                         resolve_tracer)
from ray_tpu.models.fleet import LLMFleet
from ray_tpu.models.generate import generate
from ray_tpu.models.prefix_cache import block_bytes


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _solo(params, cfg, prompt, n, mode=None, rng=None):
    out = np.asarray(generate(params, jnp.asarray([prompt], jnp.int32),
                              cfg, max_new_tokens=n, rng=rng,
                              **(mode or {})))
    return out[0, len(prompt):].tolist()


def _spans_by_req(events):
    """chrome events -> {req_id_str: [event, ...]} (request lanes
    only), each list in timestamp order."""
    per = {}
    for ev in events:
        tid = str(ev["tid"])
        if tid.startswith("req-"):
            per.setdefault(tid[4:], []).append(ev)
    for evs in per.values():
        evs.sort(key=lambda e: e["ts"])
    return per


# ---------------------------------------------------------------------------
# Tracer unit behavior
# ---------------------------------------------------------------------------

class TestTracer:
    def test_ring_bounded_with_drop_counter(self, fake_clock):
        tr = EngineTracer(capacity=8, clock=fake_clock)
        for i in range(30):
            tr.instant(f"e{i}")
            fake_clock.advance(1.0)
        assert len(tr) == 8
        assert tr.events_dropped == 22
        # Oldest-first: the ring kept the most recent window.
        assert [e[0] for e in tr.events()] == \
            [f"e{i}" for i in range(22, 30)]

    def test_open_close_span_and_frontier(self, fake_clock):
        tr = EngineTracer(clock=fake_clock)
        tr.open("queue_wait", 1)
        fake_clock.advance(2.0)
        t1 = tr.close("queue_wait", 1, {"shed": False})
        assert t1 == 2.0
        (name, rid, lane, t0, dur, args), = tr.events()
        assert (name, rid, t0, dur) == ("queue_wait", 1, 0.0, 2.0)
        assert args == {"shed": False}
        # close() set the contiguity frontier: the next span starts
        # where queue_wait ended.
        fake_clock.advance(3.0)
        tr.span_since_mark("prefill_chunk", 1)
        assert tr.events()[-1][3:5] == (2.0, 3.0)

    def test_close_without_open_still_advances_frontier(self,
                                                        fake_clock):
        tr = EngineTracer(clock=fake_clock)
        fake_clock.advance(1.0)
        tr.close("queue_wait", 7)
        assert len(tr) == 0          # nothing to emit...
        fake_clock.advance(4.0)
        tr.span_since_mark("decode_block", 7)
        assert tr.events()[-1][3:5] == (1.0, 4.0)   # ...frontier set

    def test_finish_purges_request_state(self, fake_clock):
        tr = EngineTracer(clock=fake_clock)
        tr.open("queue_wait", 1)
        tr.mark(1)
        tr.finish(1, {"tokens": 3})
        assert tr._open == {} and tr._req_mark == {}
        assert tr.events()[-1][0] == "finish"

    def test_chrome_events_shape(self, fake_clock):
        tr = EngineTracer(clock=fake_clock, engine_id="e9")
        tr.instant("submit", req_id=4, args={"prompt_tokens": 3})
        fake_clock.advance(0.5)
        tr.add("dispatch", 0.1, 0.2, lane="dispatch", args={"rows": 2})
        tr.open("queue_wait", 5)     # never closed -> synthesized
        fake_clock.advance(1.0)
        evs = tr.chrome_events()
        assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)
        by_name = {e["name"]: e for e in evs}
        sub = by_name["submit"]
        assert sub["ph"] == "X" and sub["pid"] == "e9"
        assert sub["tid"] == "req-4" and sub["cat"] == "request"
        assert sub["args"] == {"prompt_tokens": 3}
        disp = by_name["dispatch"]
        assert disp["tid"] == "engine:dispatch"
        assert disp["cat"] == "engine"
        assert disp["ts"] == pytest.approx(0.1e6)
        assert disp["dur"] == pytest.approx(0.2e6)
        qw = by_name["queue_wait"]
        assert qw["args"] == {"open": True}
        assert qw["dur"] == pytest.approx(1.0e6)

    def test_dump_writes_loadable_json(self, fake_clock, tmp_path):
        tr = EngineTracer(clock=fake_clock)
        tr.instant("submit", req_id=0)
        path = tmp_path / "t.trace.json"
        returned = tr.dump(str(path), pid="p0")
        loaded = json.loads(path.read_text())
        assert loaded == returned
        assert loaded[0]["pid"] == "p0"

    def test_null_tracer_is_inert(self):
        tr = NULL_TRACER
        assert tr.enabled is False
        tr.instant("x")
        tr.open("y", 1)
        tr.close("y", 1)
        tr.span_since_mark("z", 1)
        tr.finish(1)
        assert len(tr) == 0 and tr.events() == []
        assert tr.chrome_events() == [] and tr.dump() == []

    def test_resolve_tracer_knob(self, monkeypatch):
        monkeypatch.delenv("RAY_TPU_TRACE", raising=False)
        assert resolve_tracer(None, engine_id="e") is NULL_TRACER
        assert resolve_tracer(False, engine_id="e") is NULL_TRACER
        built = resolve_tracer(True, engine_id="e")
        assert isinstance(built, EngineTracer)
        assert built.engine_id == "e"
        mine = EngineTracer(engine_id="mine")
        assert resolve_tracer(mine, engine_id="e") is mine

    def test_env_gate(self, monkeypatch, tmp_path):
        monkeypatch.delenv("RAY_TPU_TRACE", raising=False)
        assert maybe_tracer_from_env("tag") is None
        prefix = str(tmp_path / "run")
        monkeypatch.setenv("RAY_TPU_TRACE", prefix)
        tr = maybe_tracer_from_env("tag")
        assert isinstance(tr, EngineTracer)
        assert tr.dump_path.startswith(prefix + ".tag.")
        assert tr.dump_path.endswith(".trace.json")
        # trace=None defers to the gate.
        via_knob = resolve_tracer(None, engine_id="e")
        assert isinstance(via_knob, EngineTracer)
        via_knob.instant("submit", req_id=0)
        via_knob.dump()              # falls back to the env dump path
        assert json.loads(open(via_knob.dump_path).read())


# ---------------------------------------------------------------------------
# Engine wiring: lifecycle reconstruction + contiguity
# ---------------------------------------------------------------------------

def test_engine_trace_reconstructs_lifecycle(nano_model):
    """A traced run yields, per request: the full span sequence AND
    span durations that sum (exactly, by the frontier construction) to
    the request's submit->finish wall time."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       prefix_cache=True, trace=True)
    prompts = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2], [3, 1, 4, 1]]
    ids = [eng.submit(p, 5) for p in prompts]
    eng.run()

    per = _spans_by_req(eng.dump_trace())
    assert sorted(per) == sorted(str(i) for i in ids)
    for rid, evs in per.items():
        names = [e["name"] for e in evs]
        assert names[0] == "submit" and names[-1] == "finish"
        for must in ("queue_wait", "admit", "prefix_match",
                     "prefill_chunk", "decode_block"):
            assert must in names, f"req {rid} missing {must}"
        finish = evs[-1]
        assert finish["args"]["tokens"] > 0
        e2e = finish["ts"] - evs[0]["ts"]
        spanned = sum(e["dur"] for e in evs)
        # Contiguous spans: durations account for the entire latency
        # (tolerance: the clock reads between adjacent spans).
        assert spanned == pytest.approx(e2e, abs=2e3), \
            f"req {rid}: {spanned} vs e2e {e2e}"

    # Engine lanes carry the batch-level story.
    lanes = {e["tid"] for e in eng.dump_trace()
             if str(e["tid"]).startswith("engine:")}
    assert "engine:dispatch" in lanes and "engine:drain" in lanes


@pytest.mark.parametrize("mode", [
    {"greedy": True},
    {"greedy": False, "temperature": 0.9, "top_k": 5},
], ids=["greedy", "top_k"])
@pytest.mark.parametrize("features", [
    {"prefix_cache": True},
    {"prefix_cache": True, "pipeline_depth": 2},
    {"prefill_chunk": 3, "prefix_cache": True},
    {"kv_block_tokens": 4, "prefix_cache": True},
], ids=["prefix", "pipeline", "chunked", "blocks4"])
def test_traced_engine_token_identity(nano_model, mode, features):
    """The gold contract survives tracing: outputs with the tracer ON
    are identical to solo generate across the feature matrix (the
    tracer only ever reads engine state)."""
    cfg, params = nano_model
    rng = np.random.RandomState(5)
    shared = list(range(3, 11))
    prompts = [shared + rng.randint(1, cfg.vocab_size,
                                    size=4).tolist() for _ in range(2)]
    prompts += [rng.randint(1, cfg.vocab_size,
                            size=rng.randint(3, 8)).tolist()
                for _ in range(2)]
    budgets = [6, 4, 7, 5]
    keys = (None if mode["greedy"] else
            [jax.random.PRNGKey(3000 + i) for i in range(len(prompts))])
    rng_kw = {} if mode["greedy"] else {"rng": jax.random.PRNGKey(7)}

    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       trace=True, **mode, **rng_kw, **features)
    ids = [eng.submit(p, n, rng=None if keys is None else keys[i])
           for i, (p, n) in enumerate(zip(prompts, budgets))]
    out = eng.run()
    for i, (rid, p, n) in enumerate(zip(ids, prompts, budgets)):
        want = _solo(params, cfg, p, n, mode,
                     rng=None if keys is None else keys[i])
        assert out[rid] == want, f"req {rid} diverged under tracing"
    assert len(eng.trace) > 0


def test_trace_preempt_swap_spans(nano_model):
    """Preempt-and-swap shows up in the timeline: the victim's trace
    carries a preempt_swap_out span, a second queue_wait, and a swap_in
    span — and its spans still sum to its e2e latency."""
    cfg, params = nano_model
    T = 4
    pool = 10 * block_bytes(cfg.n_layers, T, cfg.n_kv_heads,
                            cfg.head_dim,
                            jnp.dtype(cfg.dtype).itemsize)
    eng = DecodeEngine(params, cfg, batch_slots=4, max_len=32,
                       kv_block_tokens=T,
                       kv_pool_bytes=pool, prefix_cache=False,
                       trace=True)
    prompts = [[7, 8, 9, 10, 11], [3, 1, 4, 1, 5],
               [2, 7, 1, 8, 2], [9, 9, 8, 8, 7]]
    for p in prompts:
        eng.submit(p, 12)
    eng.run()
    assert eng.stats()["preemptions"] >= 1

    per = _spans_by_req(eng.dump_trace())
    swapped = [evs for evs in per.values()
               if any(e["name"] == "preempt_swap_out" for e in evs)]
    assert swapped, "no preempt_swap_out span traced"
    for evs in swapped:
        names = [e["name"] for e in evs]
        out_i = names.index("preempt_swap_out")
        # The victim's requeue wait folds into its swap_in span (the
        # frontier advanced at swap-out end), keeping spans contiguous.
        assert "swap_in" in names[out_i:]
        swap_ev = evs[out_i]
        assert swap_ev["args"]["mode"] == "swap"
        assert swap_ev["args"]["bytes"] > 0
        e2e = evs[-1]["ts"] - evs[0]["ts"]
        assert sum(e["dur"] for e in evs) == pytest.approx(e2e,
                                                           abs=2e3)


def test_trace_shed_path(nano_model, fake_clock):
    """A dead-on-arrival request's trace ends in a `shed` marker with
    its queue_wait closed (args shed=True), not a `finish`."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       trace=EngineTracer(clock=fake_clock),
                       clock=fake_clock)
    ok = eng.submit([5, 6, 7], 4)
    dead = eng.submit([1, 2, 3], 4, deadline_s=0.0)
    out = eng.run()                  # run() pops shed_ids with results
    assert out[dead] == [] and out[ok] != []

    per = _spans_by_req(eng.dump_trace())
    names_dead = [e["name"] for e in per[str(dead)]]
    assert names_dead[-1] == "shed" and "finish" not in names_dead
    qw = next(e for e in per[str(dead)] if e["name"] == "queue_wait")
    assert qw["args"] == {"shed": True}
    assert [e["name"] for e in per[str(ok)]][-1] == "finish"


def test_trace_off_by_default_and_when_false(nano_model, monkeypatch):
    monkeypatch.delenv("RAY_TPU_TRACE", raising=False)
    cfg, params = nano_model
    for knob in ({}, {"trace": False}):
        eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                           **knob)
        assert isinstance(eng.trace, NullEngineTracer)
        eng.submit([5, 6, 7], 3)
        eng.run()
        assert eng.dump_trace() == []


# ---------------------------------------------------------------------------
# Fleet stitch
# ---------------------------------------------------------------------------

def test_fleet_trace_stitches_replicas_and_routes(nano_model,
                                                  tmp_path):
    cfg, params = nano_model

    def factory(name):
        return DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                            prefix_cache=True, engine_id=name,
                            trace=True)

    fleet = LLMFleet(factory, initial_replicas=2, trace=True,
                     fleet_id="tf")
    rng = np.random.RandomState(2)
    fids = [fleet.submit(rng.randint(1, cfg.vocab_size,
                                     size=6).tolist(), 4)
            for _ in range(6)]
    fleet.run()

    path = tmp_path / "fleet.trace.json"
    events = fleet.dump_trace(str(path))
    assert json.loads(path.read_text()) == events
    assert all(ev["ph"] == "X" for ev in events)
    pids = {ev["pid"] for ev in events}
    assert pids == {"tf", "tf-r0", "tf-r1"}

    routes = [ev for ev in events if ev["name"] == "route"]
    assert len(routes) == len(fids)
    for ev in routes:
        args = ev["args"]
        assert args["replica"] in ("tf-r0", "tf-r1")
        # The scoring decision is on the span: every candidate scored.
        assert sorted(args["scores"]) == ["tf-r0", "tf-r1"]
        assert sorted(args["warm_tokens"]) == ["tf-r0", "tf-r1"]
        assert args["router"] == "pow2_affinity"
    # Each replica's engine spans made it into the merged trace.
    for pid in ("tf-r0", "tf-r1"):
        names = {ev["name"] for ev in events if ev["pid"] == pid}
        assert "decode_block" in names and "finish" in names


def test_fleet_trace_survives_replica_retirement(nano_model,
                                                 fake_clock):
    """Scaling a traced replica down must not lose its request
    history: the fleet harvests the engine's events at retirement."""
    cfg, params = nano_model

    def factory(name):
        return DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                            engine_id=name, trace=True)

    fleet = LLMFleet(factory, initial_replicas=2, trace=True,
                     fleet_id="rt", clock=fake_clock)
    for _ in range(4):
        fleet.submit([5, 6, 7], 3)
    fleet.run()
    victim = fleet.replicas[1].name
    served_by_victim = any(
        ev["pid"] == victim for rep in fleet.replicas
        if rep.name == victim
        for ev in rep.engine.trace.chrome_events(pid=victim))
    fleet.drain_replica(victim)
    fleet.run()                      # drains + retires the replica
    assert all(r.name != victim for r in fleet.replicas)
    if served_by_victim:
        assert any(ev["pid"] == victim for ev in fleet.dump_trace())


# ---------------------------------------------------------------------------
# trace_report on a real dump
# ---------------------------------------------------------------------------

def test_trace_report_breakdowns(nano_model, tmp_path):
    import sys
    sys.path.insert(0, "/root/repo")
    from tools.trace_report import (format_report, load_trace,
                                    request_breakdowns)

    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       trace=True, engine_id="rep")
    ids = [eng.submit([5, 6, 7], 4), eng.submit([1, 2], 6),
           eng.submit([9, 8, 7], 5)]
    eng.run()
    path = tmp_path / "e.trace.json"
    eng.dump_trace(str(path))

    rows = request_breakdowns(load_trace(str(path)))
    assert sorted(r["req"] for r in rows) == \
        sorted(str(i) for i in ids)
    for r in rows:
        assert r["e2e_s"] > 0 and r["tokens"] > 0 and not r["shed"]
        fracs = r["queue_frac"] + r["prefill_frac"] + \
            r["decode_frac"] + r["swap_frac"]
        # Contiguity again, through the reporting lens: the phase
        # fractions cover (almost) all of e2e. Submit/finish instants
        # and admit markers contribute no duration.
        assert 0.9 <= fracs <= 1.0 + 1e-6
    # Sorted slowest-first; report renders.
    assert rows == sorted(rows, key=lambda r: -r["e2e_s"])
    text = format_report(rows, top=2)
    assert "top 2 slowest" in text and "requests" in text


def test_trace_report_json_mode(nano_model, tmp_path, capsys):
    """--json emits the SAME breakdown rows plus a totals block the
    text footer is computed from — one aggregation path, two
    renderings."""
    import json as _json
    import sys
    sys.path.insert(0, "/root/repo")
    from tools.trace_report import (load_trace, main,
                                    request_breakdowns, totals)

    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       trace=True, engine_id="repj")
    for p, n in [([5, 6, 7], 4), ([1, 2], 6)]:
        eng.submit(p, n)
    eng.run()
    path = tmp_path / "j.trace.json"
    eng.dump_trace(str(path))

    main([str(path), "--json"])
    payload = _json.loads(capsys.readouterr().out)
    rows = request_breakdowns(load_trace(str(path)))
    assert payload["requests"] == rows
    assert payload["totals"] == totals(rows)
    t = payload["totals"]
    assert t["requests"] == 2 and t["shed"] == 0
    assert t["tokens"] == sum(r["tokens"] for r in rows)
    assert t["e2e_s_sum"] == pytest.approx(
        sum(r["e2e_s"] for r in rows))
    for p in ("queue", "prefill", "decode", "swap"):
        assert f"{p}_s_sum" in t

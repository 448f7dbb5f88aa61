"""Disaggregated prefill/decode fleet (models/fleet.py + the engine's
export_request/import_request handoff seam).

Gold contract, extended across the CLASS SPLIT: a fleet running
`disaggregated=True` — admission and chunked prefill on one replica
class, fused decode on another, finished KV handed off through the
host-staged swap machinery — emits token streams BIT-IDENTICAL to a
colocated fleet and to solo `generate`, greedy and sampled, across the
paged / quantized / prefix-cache / pipeline / multi-LoRA feature
matrix. The handoff changes WHERE a request decodes, never what it
computes: the carried last-prompt-token logits + the (key, token
index) sampling discipline make the first decode token independent of
which engine samples it.

Also held here: per-class autoscaling (TTFT p95 gates the prefill
class, TPOT p95 gates the decode class — on stub engines over the
shared FakeClock), host-side parking when no decode replica can
import, mid-handoff chaos (`FaultInjector` kills the decode target;
``tokens_lost_to_failure == 0`` and the block-pool ledgers return to
baseline), the state API's `handoff` status + `replica_class` plumbing
through the status CLI, and a sanitizer gate over the export/import
path (zero retraces, zero unexpected transfers).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (LlamaConfig, LoraConfig, llama_init,
                            lora_init, lora_merge)
from ray_tpu.models.engine import DecodeEngine
from ray_tpu.models.fault_injection import FaultInjector
from ray_tpu.models.fleet import (FleetAutoscalingConfig,
                                  FleetHealthConfig, LLMFleet)
from ray_tpu.models.generate import generate
from ray_tpu.models.scheduler import EngineOverloaded


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

ENGINE_COMBOS = {
    "blocks4": {"kv_block_tokens": 4},
    "blocks4_quant": {"kv_block_tokens": 4,
                      "kv_quant": "int8"},
    "blocks32": {},
    "blocks4_prefix": {"kv_block_tokens": 4,
                       "prefix_cache": True},
    "pipeline": {"pipeline_depth": 3},
}


def _pools_empty(fleet):
    """Every paged replica's block-pool ledger back to baseline (no
    leaked refcounts across export/import)."""
    for rep in fleet.replicas:
        pool = getattr(rep.engine, "kv_pool", None)
        if pool is None:
            continue
        snap = pool.snapshot()
        # Prefix-cache blocks legitimately stay resident (evictable);
        # everything else must be released.
        if not getattr(rep.engine, "_prefix", None):
            assert snap["blocks_in_use"] == 0, (rep.name, snap)


# ---------------------------------------------------------------------------
# Token identity: disaggregated == colocated == solo, feature matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", sorted(SAMPLING_MODES))
@pytest.mark.parametrize("combo", sorted(ENGINE_COMBOS))
def test_disagg_token_identity_matrix(nano_model, combo, mode):
    """The split is invisible in the tokens: a 1-prefill/2-decode
    fleet matches a 2-replica colocated fleet request-for-request
    (same rng_seed -> same pinned per-fid keys), and greedy matches
    solo `generate` outright."""
    cfg, params = nano_model
    eng_kw = dict(ENGINE_COMBOS[combo])
    eng_kw.update(SAMPLING_MODES[mode])
    co = LLMFleet(_factory(params, cfg, **eng_kw),
                  initial_replicas=2, rng_seed=7, fleet_id="co")
    dis = LLMFleet(_factory(params, cfg, **eng_kw), rng_seed=7,
                   disaggregated=True, fleet_id="dis",
                   prefill_replicas=1, decode_replicas=2)
    fco = [co.submit(p, n) for p, n in zip(PROMPTS, BUDGETS)]
    fdi = [dis.submit(p, n) for p, n in zip(PROMPTS, BUDGETS)]
    rco, rdi = co.run(), dis.run()
    for i, (a, b) in enumerate(zip(fco, fdi)):
        assert rco[a] == rdi[b], f"req {i} diverged across the split"
        if mode == "greedy" and "kv_quant" not in eng_kw:
            # Quantized KV is tolerance-gated elsewhere; everything
            # else must match solo bit-for-bit.
            assert rdi[b] == _solo(params, cfg, PROMPTS[i],
                                   BUDGETS[i]), f"req {i} vs solo"
    st = dis.stats()
    assert st["disaggregated"] == 1.0
    assert st["handoffs"] == float(len(PROMPTS))
    assert st["handoffs_out"] == st["handoffs_in"] == len(PROMPTS)
    assert st["handoff_parked"] == 0.0
    assert dis.tokens_lost_to_failure == 0
    assert st["handoff_out_bytes"] > 0          # KV actually moved
    assert st["handoff_in_bytes"] == st["handoff_out_bytes"]
    _pools_empty(dis)


LCFG = LoraConfig(rank=4, alpha=8.0)


def _rand_lora(cfg, seed, scale=0.05):
    lp = lora_init(jax.random.PRNGKey(seed), cfg, LCFG)
    leaves, treedef = jax.tree_util.tree_flatten(lp)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    leaves = [l + scale * jax.random.normal(k, l.shape, l.dtype)
              for l, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def test_disagg_lora_handoff_repins_adapter(nano_model):
    """Adapter-gated requests survive the handoff: the prefill-class
    export releases the adapter pin, the decode-class import re-pins
    (prefetching if not resident) and the tokens match the
    merged-weight solo run. adapter_miss_rate reads as a live [0, 1]
    gauge."""
    cfg, params = nano_model
    loras = {f"ad{i}": _rand_lora(cfg, 10 + i) for i in range(2)}
    merged = {a: lora_merge(params, lp, cfg, LCFG)
              for a, lp in loras.items()}

    dis = LLMFleet(_factory(params, cfg, greedy=True, lora=LCFG,
                            max_live_adapters=2),
                   rng_seed=3, disaggregated=True, fleet_id="dis-lora",
                   prefill_replicas=1, decode_replicas=1)
    for a, lp in loras.items():
        dis.register_adapter(a, lp)
    prompts = [[5, 6, 7], [9, 8, 7], [1, 2, 3], [4, 5, 6]]
    aids = ["ad0", "ad1", "ad0", None]
    fids = [dis.submit(p, 4, adapter_id=a)
            for p, a in zip(prompts, aids)]
    out = dis.run()
    for fid, p, a in zip(fids, prompts, aids):
        ref = _solo(params if a is None else merged[a], cfg, p, 4,
                    greedy=True)
        assert out[fid] == ref, f"adapter {a} diverged across handoff"
    st = dis.stats()
    assert st["handoffs"] == float(len(prompts))
    assert 0.0 <= st["adapter_miss_rate"] <= 1.0
    assert st["adapter_miss_rate"] == pytest.approx(
        dis.adapter_miss_rate())
    assert dis.tokens_lost_to_failure == 0


# ---------------------------------------------------------------------------
# Host-side parking: no importable decode replica -> parked, not lost
# ---------------------------------------------------------------------------

def test_handoff_parks_when_decode_wont_import(nano_model):
    """An import refused with EngineOverloaded parks the export on the
    HOST (visible in stats + the state API as status="handoff" with
    engine_id None) and re-places next step — tokens still identical
    to solo."""
    from ray_tpu.util.state import serving

    cfg, params = nano_model
    dis = LLMFleet(_factory(params, cfg,
                            kv_block_tokens=4),
                   rng_seed=5, disaggregated=True, fleet_id="dis-park",
                   prefill_replicas=1, decode_replicas=1)
    dec = next(r for r in dis.replicas if r.replica_class == "decode")
    real_import = dec.engine.import_request
    refusals = {"n": 0}

    def flaky_import(h):
        if refusals["n"] < 1:
            refusals["n"] += 1
            raise EngineOverloaded("scripted refusal")
        return real_import(h)

    dec.engine.import_request = flaky_import
    fids = [dis.submit(p, n) for p, n in zip(PROMPTS[:3], BUDGETS[:3])]
    parked_seen = False
    for _ in range(60):
        dis.step()
        if dis._handoff_parked:
            parked_seen = True
            assert dis.stats()["handoff_parked"] >= 1.0
            rows = serving.list_requests(status="handoff")
            fleet_rows = [r for r in rows if r["engine_id"] is None]
            assert fleet_rows and fleet_rows[0]["fleet"] == "dis-park"
            break
        if not dis.pending():
            break
    assert parked_seen, "the scripted refusal never parked an export"
    out = dis.run()
    for fid, p, n in zip(fids, PROMPTS[:3], BUDGETS[:3]):
        assert out[fid] == _solo(params, cfg, p, n)
    assert refusals["n"] == 1
    assert dis.stats()["handoff_parked"] == 0.0
    _pools_empty(dis)


# ---------------------------------------------------------------------------
# Mid-handoff chaos: decode-class target dies between spill and finish
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", sorted(SAMPLING_MODES))
@pytest.mark.parametrize("kill_step", [0, 2])
def test_mid_handoff_decode_death_is_gapless(nano_model, kill_step,
                                             mode):
    """FaultInjector kills the decode-class replica right after import
    (kill_step=0) or mid-decode (kill_step=2). The request re-routes
    through ordinary failover — resubmitted on the prefill class, its
    recompute replay re-exports, the class-preserving replacement
    imports — and the stream is token-identical to the fault-free run
    with ``tokens_lost_to_failure == 0`` and every block-pool ledger
    back at baseline."""
    cfg, params = nano_model
    eng_kw = dict(SAMPLING_MODES[mode], kv_block_tokens=4)
    prompts, budgets = PROMPTS[:4], BUDGETS[:4]

    ref_fleet = LLMFleet(_factory(params, cfg, **eng_kw), rng_seed=11,
                         disaggregated=True, fleet_id="chaos-ref",
                         prefill_replicas=1, decode_replicas=1)
    rfids = [ref_fleet.submit(p, n)
             for p, n in zip(prompts, budgets)]
    ref_out = ref_fleet.run()

    inj = FaultInjector(
        schedule={"chaos-0-r1": [(kill_step, "kill")]})
    fleet = LLMFleet(_factory(params, cfg, **eng_kw), rng_seed=11,
                     disaggregated=True, fleet_id="chaos-0",
                     prefill_replicas=1, decode_replicas=1,
                     fault_injector=inj,
                     health=FleetHealthConfig(max_retries=3))
    fids = [fleet.submit(p, n) for p, n in zip(prompts, budgets)]
    out = fleet.run()

    assert inj.fired, "the scripted kill never landed"
    assert fleet.replicas_failed == 1
    assert fleet.tokens_lost_to_failure == 0
    for rf, f in zip(rfids, fids):
        assert out[f] == ref_out[rf], \
            "stream diverged across the mid-handoff kill"
    st = fleet.stats()
    assert st["replicas_decode"] == 1.0     # replacement kept the class
    assert st["replicas_prefill"] == 1.0
    assert st["handoff_parked"] == 0.0
    _pools_empty(fleet)


# ---------------------------------------------------------------------------
# Per-class autoscaling on stub engines + FakeClock
# ---------------------------------------------------------------------------

class _ScalerStub:
    """Duck-typed replica engine reporting scripted stats: enough
    surface for the fleet loop, the router, and the class scalers —
    no JAX, no real time."""

    def __init__(self, name, clock, stats, step_time=1.0):
        self.engine_id = name
        self.clock = clock
        self._stats = dict(stats)
        self.step_time = step_time
        self.steps_total = 0
        self.draining = False
        self.finished = set()
        self.shed_ids = set()
        self.results = {}
        self.scheduler = []
        self.row_req = [None, None]

    def pending(self):
        return True

    def step(self, horizon=None):
        self.clock.advance(self.step_time)
        self.steps_total += 1
        return {}

    def stats(self):
        return dict(self._stats)

    def handoff_ready(self):
        return []

    def pending_prefill_tokens(self):
        return 0

    def prefix_match_tokens(self, prompt, peek=True):
        return 0

    def kv_used_fraction(self):
        return self._stats.get("slot_occupancy", 0.0)

    def halt(self):
        pass

    def begin_drain(self):
        self.draining = True


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _stub_disagg_fleet(clock, stats_by_class, **fleet_kw):
    def factory(name):
        # The fleet stamps replica_class AFTER construction; default
        # stats here get replaced once the class is known (below).
        return _ScalerStub(name, clock, {}, step_time=1.0)

    fleet = LLMFleet(factory, disaggregated=True, clock=clock,
                     fleet_id="stub-disagg", **fleet_kw)
    for rep in fleet.replicas:
        rep.engine._stats = dict(stats_by_class[rep.replica_class])
    return fleet


def test_decode_class_scales_on_tpot_p95(nano_model):
    """TPOT p95 over its SLO on busy decode replicas adds DECODE
    capacity after the hold — the prefill class does not move."""
    clock = _FakeClock()
    fleet = _stub_disagg_fleet(
        clock,
        {"prefill": {"slot_occupancy": 0.0, "queue_depth": 0.0},
         "decode": {"tpot_s_p95": 5.0, "slot_occupancy": 0.5,
                    "queue_depth": 1.0}},
        decode_autoscaling=FleetAutoscalingConfig(
            min_replicas=1, max_replicas=3, tpot_p95_slo_s=1.0,
            upscale_hold_s=2.0))
    for _ in range(6):
        fleet.step()
    st = fleet.stats()
    assert st["replicas_decode"] >= 2.0, st
    assert st["replicas_prefill"] == 1.0
    assert fleet._decode_scaler.scale_ups >= 1
    assert fleet._decode_scaler.last_signals["tpot_p95"] == 5.0
    # New decode replicas carry the class (and would be routed
    # handoffs, never fresh admissions).
    for rep in fleet.replicas:
        if rep.engine._stats == {}:
            assert rep.replica_class == "decode"


def test_prefill_class_scales_on_fleet_ttft_p95(nano_model):
    """The fleet-measured submit->first-token tail (prefill engines
    never emit, so no engine window sees it) breaches the prefill
    class SLO and adds PREFILL capacity — decode does not move."""
    clock = _FakeClock()
    fleet = _stub_disagg_fleet(
        clock,
        {"prefill": {"slot_occupancy": 0.2, "queue_depth": 1.0},
         "decode": {"slot_occupancy": 0.0, "queue_depth": 0.0}},
        prefill_autoscaling=FleetAutoscalingConfig(
            min_replicas=1, max_replicas=3, ttft_p95_slo_s=0.5,
            upscale_hold_s=2.0))
    for _ in range(5):
        fleet._ttft_agg.add(2.0)        # measured across the handoff
    for _ in range(6):
        fleet.step()
    st = fleet.stats()
    assert st["replicas_prefill"] >= 2.0, st
    assert st["replicas_decode"] == 1.0
    assert fleet._prefill_scaler.scale_ups >= 1
    assert st["ttft_s_p95_fleet"] == 2.0


def test_disagg_constructor_validation(nano_model):
    cfg, params = nano_model
    fac = _factory(params, cfg)
    with pytest.raises(ValueError, match="disaggregated=True"):
        LLMFleet(fac, prefill_replicas=1)
    with pytest.raises(ValueError, match="per class"):
        LLMFleet(fac, disaggregated=True, initial_replicas=2)
    with pytest.raises(ValueError, match="per class"):
        LLMFleet(fac, disaggregated=True,
                 autoscaling=FleetAutoscalingConfig())
    with pytest.raises(ValueError, match="replica_class"):
        LLMFleet(fac, disaggregated=True).add_replica(
            replica_class="warmup")
    with pytest.raises(ValueError, match="outside autoscaling"):
        LLMFleet(fac, disaggregated=True, decode_replicas=5,
                 decode_autoscaling=FleetAutoscalingConfig(
                     min_replicas=1, max_replicas=2))


def test_colocated_fleet_keeps_zero_disagg_overhead(nano_model):
    """disaggregated=False is the pre-change fleet: no replica class,
    no prefill_only engines, all-zero handoff plane in stats."""
    cfg, params = nano_model
    co = LLMFleet(_factory(params, cfg), initial_replicas=2,
                  rng_seed=2, fleet_id="co-zero")
    fids = [co.submit(p, n) for p, n in zip(PROMPTS[:3], BUDGETS[:3])]
    out = co.run()
    for fid, p, n in zip(fids, PROMPTS[:3], BUDGETS[:3]):
        assert out[fid] == _solo(params, cfg, p, n)
    st = co.stats()
    assert st["disaggregated"] == 0.0
    assert st["handoffs"] == st["handoffs_out"] == \
        st["handoffs_in"] == 0.0
    assert st["replicas_prefill"] == st["replicas_decode"] == 0.0
    for rep in co.replicas:
        assert rep.replica_class is None
        assert not getattr(rep.engine, "prefill_only", False)
        assert rep.engine.handoffs_out == rep.engine.handoffs_in == 0


# ---------------------------------------------------------------------------
# State API + status CLI: handoff status, replica_class column
# ---------------------------------------------------------------------------

def test_state_api_handoff_status_and_replica_class(nano_model):
    from ray_tpu.util.state import serving
    from tools.ray_tpu_status import collect, format_status

    cfg, params = nano_model
    dis = LLMFleet(_factory(params, cfg,
                            kv_block_tokens=4),
                   rng_seed=9, disaggregated=True, fleet_id="dis-api",
                   prefill_replicas=1, decode_replicas=1)
    pre = next(r for r in dis.replicas
               if r.replica_class == "prefill")
    fids = [dis.submit(p, 4) for p in PROMPTS[:2]]

    # Drive the prefill ENGINE directly (not fleet.step, which would
    # immediately export): parked prefill-complete rows must classify
    # as "handoff" on the prefill-class engine.
    for _ in range(20):
        pre.engine.step()
        if pre.engine.handoff_ready():
            break
    assert pre.engine.handoff_ready()
    rows = serving.list_requests(status="handoff")
    eng_rows = [r for r in rows if r["engine_id"] == pre.name]
    assert eng_rows, "parked prefill-complete rows must read handoff"
    # replica_class surfaces on every engine row.
    classes = {e["engine_id"]: e["replica_class"]
               for e in serving.list_engines()}
    assert classes[pre.name] == "prefill"
    assert "decode" in classes.values()
    # The status CLI renders the class column and the disagg census.
    text = format_status(collect())
    assert "class=prefill" in text
    assert "class=decode" in text
    assert "disagg[1P/1D" in text
    assert "handoff" in text

    out = dis.run()
    for fid, p in zip(fids, PROMPTS[:2]):
        assert out[fid] == _solo(params, cfg, p, 4)
    # No double count: once drained, nothing reads handoff anywhere.
    assert serving.list_requests(status="handoff") == []
    assert serving.summarize_fleet()["fleets"][0]["handoffs"] == 2


def test_scheduler_queued_state_carries_handoff_flag(nano_model):
    """An imported request waiting for decode admission is flagged
    ``handoff: True`` in queued_state (flat, no reaching into the
    request object); ordinary queued requests read False."""
    cfg, params = nano_model
    pre = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       kv_block_tokens=4, engine_id="pre")
    pre.prefill_only = True
    dec = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       kv_block_tokens=4, engine_id="dec")
    rids = [pre.submit(p, 4) for p in PROMPTS[:3]]
    for _ in range(30):
        pre.step()
        if len(pre.handoff_ready()) == len(rids):
            break
    for rid in list(pre.handoff_ready()):
        dec.import_request(pre.export_request(rid))
    flags = {e["req_id"]: e["handoff"]
             for e in dec.scheduler.queued_state()}
    assert flags and all(flags.values())
    fresh = dec.submit([4, 4], 2)
    flags = {e["req_id"]: e["handoff"]
             for e in dec.scheduler.queued_state()}
    assert flags[fresh] is False
    dec.run()


# ---------------------------------------------------------------------------
# Sanitizer: the handoff path is retrace-free and transfer-clean
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _disarm_leftover_sanitizer():
    yield
    from ray_tpu._private import sanitize
    san = sanitize.active()
    if san is not None:
        san.disarm()


def test_sanitizer_clean_on_handoff_path(nano_model):
    """Armed pass over export->import->decode: zero retraces, zero
    device->host pulls outside the choke points. The export rides the
    same pow2-padded `_swap_out_gather` entry as preemption, so a warm
    swap cache must fully cover it."""
    from ray_tpu._private.sanitize import SanitizerError

    cfg, params = nano_model

    def handoff_workload(pre, dec):
        rids = [pre.submit(p, 4) for p in PROMPTS[:2]]
        for _ in range(30):
            pre.step()
            if len(pre.handoff_ready()) == len(rids):
                break
        moved = [dec.import_request(pre.export_request(rid))
                 for rid in list(pre.handoff_ready())]
        out = dec.run()
        return [out[r] for r in moved]

    pre = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       kv_block_tokens=4, engine_id="sp")
    pre.prefill_only = True
    dec = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       kv_block_tokens=4, engine_id="sd")
    handoff_workload(pre, dec)          # cold compiles
    handoff_workload(pre, dec)          # warm-hit paths
    san = pre.arm_sanitizer()
    try:
        emitted = handoff_workload(pre, dec)
    except SanitizerError as exc:
        pytest.fail(f"unexpected transfer on the handoff path: {exc}")
    finally:
        pre.disarm_sanitizer()
    assert san.total_retraces() == 0, san.retraces()
    assert san.unexpected_transfers == [], san.unexpected_transfers
    for p, toks in zip(PROMPTS[:2], emitted):
        assert toks == _solo(params, cfg, p, 4)

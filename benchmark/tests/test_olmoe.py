"""What PR 26 added for the sparse configuration: `costs_moe` against the
published widths by hand, the reference against a loop over tokens and
experts, the four `moe_*` readers on hand-made records and a hand-made
trace, the cell's files, and the POWER of the comparison that decides
`correct`: it passes the right program in bf16 and fails four wrong ones.
"""

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.harness import costs_moe, spec, xplane
from benchmark.tests import make_moe_trace

CELL = "olmoe-chat-short"
READERS = ("moe_ffn_device_ms", "moe_ffn_prefill_share_pct",
           "moe_experts_roofline_pct", "moe_pad_waste_pct")
BYTES_PER_S = 819e9          # harness/peaks.json, "TPU v5 lite"


def reader(name):
    return spec.load_module("layer_metrics", name)


def published():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "olmoe-1b-7b-serve.json")) as f:
        return json.load(f)


# -- costs_moe ---------------------------------------------------------------

def test_costs_against_the_published_widths_by_hand():
    m = dict(published(), num_hidden_layers=16)
    # one expert: 3 x 2048 x 1024 = 6,291,456; 64 of them a layer
    assert costs_moe.expert_params(m) == 6_291_456
    assert costs_moe.layer_expert_params(m) == 402_653_184
    assert costs_moe.expert_bytes(m) == 12_582_912
    # a layer: attention 4 x 2048 x 2048 = 16,777,216; router 2048 x 64;
    # norms 2 x 2048 + 2 x 2048; experts. x 16, + 2 x 50,304 x 2048 + 2048
    layer = 16_777_216 + 131_072 + 8_192 + 402_653_184
    assert costs_moe.total_params(m) == 16 * layer + 2 * 103_022_592 + 2048
    assert round(costs_moe.total_params(m) / 1e9, 2) == 6.92
    active = 16 * (16_777_216 + 131_072 + 8 * 6_291_456) + 103_022_592
    assert costs_moe.active_matmul_params(m) == active
    assert costs_moe.experts_flops(m, 1) == 16 * 8 * 2 * 6_291_456
    # all 64 experts of 10 layers read once: 8.05 GB, 9.8 ms at 819 GB/s
    assert costs_moe.decode_experts_least_s(
        dict(m, num_hidden_layers=10), 64, 1, BYTES_PER_S) \
        == pytest.approx(9.83e-3, rel=1e-3)


def test_program_preset_agrees_with_costs():
    from ray_tpu.models import MoeConfig

    m = dict(published(), num_hidden_layers=16)
    assert MoeConfig.olmoe_1b_7b().num_params() == costs_moe.total_params(m)


# -- the reference -----------------------------------------------------------

def _tiny(top_k=2, experts=6, renorm=False):
    import jax

    from ray_tpu.models import MoeConfig, moe_init

    cfg = MoeConfig.nano_moe(n_experts=experts, top_k=top_k, qk_norm=True,
                             norm_topk_prob=renorm, n_layers=1,
                             dtype="float32", remat=False)
    model = {"num_attention_heads": 4, "num_key_value_heads": 2,
             "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
             "num_experts_per_tok": top_k, "norm_topk_prob": renorm}
    return moe_init(jax.random.PRNGKey(3), cfg), model


@pytest.mark.parametrize("renorm", [False, True])
def test_reference_against_a_loop_over_tokens_and_experts(renorm):
    """One layer of `olmoe_sparse`, its attention taken out by zeroing
    `wo`, against ten lines of numpy: every token, its top-k experts one
    at a time, weights as they are or renormalised; and the gap it
    reports is the k-th less the (k+1)-th probability."""
    import jax.numpy as jnp

    from benchmark.reference import olmoe_sparse

    params, model = _tiny(renorm=renorm)
    lay = {k: np.asarray(v[0], np.float64)
           for k, v in params["layers"].items()}
    lay["wo"] = np.zeros_like(lay["wo"])
    h = np.random.default_rng(0).normal(size=(1, 5, 64))
    got, gap = olmoe_sparse._layer(
        jnp.asarray(h, jnp.float32),
        {k: jnp.asarray(v, jnp.float32) for k, v in lay.items()}, model)
    want = h.copy()
    for t, x in enumerate(h[0]):
        u = x / np.sqrt(np.mean(x * x) + 1e-5) * lay["mlp_norm"]
        z = u @ lay["w_router"]
        p = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
        top = np.argsort(-p, kind="stable")[:2]
        for e in top:
            a = u @ lay["we_gate"][e]
            y = (a / (1 + np.exp(-a)) * (u @ lay["we_up"][e])) \
                @ lay["we_down"][e]
            want[0, t] += p[e] / (p[top].sum() if renorm else 1.0) * y
        ranked = np.sort(p)[::-1]
        assert float(gap[0, t]) == pytest.approx(ranked[1] - ranked[2],
                                                 abs=1e-6)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_reference_breaks_ties_by_index_and_refuses_what_it_lacks():
    import jax.numpy as jnp

    from benchmark.reference import olmoe_sparse

    p = jnp.asarray([[0.2, 0.1, 0.2, 0.2, 0.1, 0.2]])
    assert olmoe_sparse._chosen(p, 3).tolist() == [
        [True, False, True, True, False, False]]
    for bad in ({"clip_qkv": 8.0}, {"attention_bias": True},
                {"rope_scaling": {"factor": 2}}, {"sliding_window": 4096}):
        with pytest.raises(ValueError):
            olmoe_sparse.hidden({}, jnp.zeros((1, 4), jnp.int32), bad)


def test_reference_stands_alone():
    with open(os.path.join(spec.BENCH_DIR, "reference",
                           "olmoe_sparse.py")) as f:
        text = f.read()
    assert "ray_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text
    assert "DEPARTURE" in text.split('"""', 2)[1]


# -- the readers -------------------------------------------------------------

def _run(tmp_path, e1=100.0, e2=50.0, scoped=True, snaps=None):
    """`records` and `reduced` around a trace make_moe_trace writes."""
    where = tmp_path / "plugins" / "profile" / "x"
    where.mkdir(parents=True, exist_ok=True)
    (where / "vm.xplane.pb").write_bytes(
        make_moe_trace.space(e1, e2, scoped))
    trace = xplane.load(str(where / "vm.xplane.pb"))
    counters = {"moe_assignments_total": 0.0, "moe_rows_computed_total": 0.0,
                "moe_decode_experts_hit_total": 0.0,
                "moe_decode_layer_steps_total": 0.0}
    base = {
        "t0": {"decode_horizon_mean": 8.0, "decode_horizon_count": 10},
        "t1": {"decode_horizon_mean": 8.0, "decode_horizon_count": 11},
        "w0": dict(counters, moe_assignments_total=1000.0,
                   moe_rows_computed_total=9000.0),
        # the window: 60,000 live assignments of 80,000 rows computed;
        # 200 expert-layer runs that hit 12,000 experts: 60 of 64 a run
        "w1": dict(counters, moe_assignments_total=61000.0,
                   moe_rows_computed_total=89000.0,
                   moe_decode_experts_hit_total=12000.0,
                   moe_decode_layer_steps_total=200.0)}
    records = {"session": types.SimpleNamespace(dir=str(tmp_path)),
               "snaps": base if snaps is None else snaps,
               "model": dict(published(), num_hidden_layers=10),
               "device": {"kind": "TPU v5 lite"}}
    reduced = {"trace": trace, "idlest_chip": 0,
               "window": xplane.span_window(trace.host, "bench.window"),
               "busy_s_by_chip": {0: 700e-6}}
    return records, reduced


def test_readers_on_the_hand_made_trace(tmp_path):
    records, reduced = _run(tmp_path)
    # decode: router 20 + dispatch 10 + experts 100 + 50 = 180 us over the
    # 8 tokens of horizon between the snapshots
    assert reader("moe_ffn_device_ms").read(records, reduced) == \
        pytest.approx(0.180 / 8)
    # prefill: dispatch 50 + experts 200 of 400 us
    assert reader("moe_ffn_prefill_share_pct").read(records, reduced) == \
        pytest.approx(62.5)
    # 60 experts a run x 10 layers x 8 tokens x 12,582,912 B at 819 GB/s
    # = 73.75 ms, over the 150 us under moe_experts (a trace this short
    # for that many bytes is the point of the next test)
    least = 60 * 10 * 8 * 12_582_912 / BYTES_PER_S
    assert reader("moe_experts_roofline_pct").read(records, reduced) == \
        pytest.approx(100.0 * least / 150e-6)
    assert reader("moe_pad_waste_pct").read(records, reduced) == \
        pytest.approx(25.0)


def test_roofline_is_100_when_every_expert_is_hit_at_the_byte_time(
        tmp_path):
    """All 64 experts hit in every run and the device time under
    `moe_experts` equal to the time their bytes take: 100 %, not more. A
    program that read all 64 while 32 were hit reads 50 %."""
    least_us = 64 * 10 * 8 * 12_582_912 / BYTES_PER_S * 1e6
    records, reduced = _run(tmp_path, e1=least_us * 2 / 3,
                            e2=least_us / 3)
    w1 = records["snaps"]["w1"]
    w1["moe_decode_experts_hit_total"] = 64.0 * 200
    got = reader("moe_experts_roofline_pct").read(records, reduced)
    assert got == pytest.approx(100.0, rel=1e-6) and got <= 100.0 + 1e-4
    w1["moe_decode_experts_hit_total"] = 32.0 * 200
    assert reader("moe_experts_roofline_pct").read(records, reduced) == \
        pytest.approx(50.0, rel=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_none_when_scopes_or_counters_are_absent(tmp_path, name):
    """No trace (--trace 0), a trace of a program without the expert
    layer's scopes, an engine without the counters (a dense model, the
    parent commit), no snapshots at all: the metric is left out."""
    records, reduced = _run(tmp_path)
    counter = name == "moe_pad_waste_pct"
    if not counter:
        assert reader(name).read(records, None) is None
        bare, bare_reduced = _run(tmp_path / "bare", scoped=False)
        assert reader(name).read(bare, bare_reduced) is None
    old = {k: {"decode_horizon_mean": 8.0, "decode_horizon_count": 10 + i,
               "steps_total": 40.0 + i}
           for i, k in enumerate(("t0", "t1", "w0", "w1"))}
    if name in ("moe_pad_waste_pct", "moe_experts_roofline_pct"):
        assert reader(name).read(dict(records, snaps=old), reduced) is None
    if name != "moe_ffn_prefill_share_pct":      # reads the trace alone
        assert reader(name).read(dict(records, snaps={}), reduced) is None


def test_entries_agree_with_the_readers_and_the_cell_lists_them():
    bench = spec.load_benchmark()
    cell = {m.name for m in spec.load_cell(CELL).per_layer}
    for name in READERS:
        entry = [m for m in bench["per_layer"] if m["name"] == name][-1]
        mod = reader(name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"])
        assert entry["workloads"] == [CELL] and name in cell
    assert {"decode_step_device_ms", "decode_kv_move_device_ms",
            "paged_attn_roofline_pct", "queue_wait_p95_ms",
            "ttft_prefill_mean_ms"} <= cell


# -- the cell's files --------------------------------------------------------

def test_cell_resolves_with_every_published_width():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["driver"] == "serve_model"
    assert {m.name for m in cell.end_to_end} == {
        "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "OLMoE-1B-7B-0125-Instruct")
    entry = [c for c in spec.load_benchmark()["configs"]
             if c["name"] == "olmoe-1b-7b-serve"][0]
    assert entry["source"] == row["source_url"] == cell.config["source"]
    differs = {k for k, v in row["config"].items()
               if cell.config.get(k, "absent") != v}
    assert differs == set(entry["reduced"]) == {"num_hidden_layers"}
    t = cell.traffic["traffic"]
    assert (t["prompt"], t["output"]) == (
        {"median": 256, "sigma": 0.8, "min": 32, "max": 1536},
        {"median": 128, "sigma": 0.6, "min": 16, "max": 384})
    assert (t["ramp_s"], t["tail_s"], t["schedule_seed"],
            cell.traffic["finish_cap_s"]) == (10.0, 30.0, 23, 60)
    e = cell.config["engine"]
    assert (e["max_len"], e["batch_slots"], e["kv_block_tokens"],
            e["prefill_chunk"], e["max_prefills_per_step"],
            e["warm_groups"]) == (2048, 32, 32, 512, 4, [1, 2, 4])


def test_rehearsal_twin_runs_end_to_end():
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", str(2**31 + 7), "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    last = r.stdout.strip().splitlines()[-1]
    assert '"rehearsal": "ok"' in last and '"correct": true' in last
    assert "moe_pad_waste_pct" in last
    assert '"compiles_in_window": 0' in r.stdout
    assert '"rows_cover_assignments": true' in r.stdout


def test_parent_tree_fails_the_cell_at_once(tmp_path):
    """A checkout without the driver: a SpecError before JAX starts."""
    for rel in ("BENCHMARK.json", "benchmark/run.py", "benchmark/__init__.py",
                "benchmark/harness", "benchmark/configs",
                "benchmark/workloads", "benchmark/traffic",
                "benchmark/layer_metrics"):
        src, dst = os.path.join(spec.ROOT, rel), tmp_path / rel
        if not os.path.exists(src):
            continue
        dst.parent.mkdir(parents=True, exist_ok=True)
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(src, dst)
    os.remove(tmp_path / "benchmark/harness/drivers/serve_model.py")
    (tmp_path / "ray_tpu").mkdir()
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert r.returncode != 0 and "serve_model" in r.stderr


# -- the power of `correct` --------------------------------------------------

def _generated_margins(route, qk_norm=True, dtype="bfloat16",
                       fp8_activations=False):
    """Greedy tokens of solo `generate` at nano widths (64 experts, 8 a
    token, hidden 64, 4 layers) in `dtype`, with the routing function
    swapped for `route`, scored by the float32 reference: (margins, gaps),
    one array a prompt."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import olmoe_sparse
    from ray_tpu.models import MoeConfig, moe, moe_init
    from ray_tpu.models.generate import generate

    kw = dict(n_experts=64, top_k=8, norm_topk_prob=False, remat=False,
              max_seq_len=128, n_layers=4, n_kv_heads=4, ffn_dim=32,
              vocab_size=2048)
    params = moe_init(jax.random.PRNGKey(0), MoeConfig.nano_moe(
        qk_norm=True, dtype=jnp.float32, **kw))
    model = {"num_attention_heads": 4, "num_key_value_heads": 4,
             "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
             "num_experts_per_tok": 8, "norm_topk_prob": False}
    prompts = np.random.default_rng(1).integers(
        1, 2048, size=(16, 16)).astype(np.int32)
    from ray_tpu.models import generate as generate_mod

    kept, kept_norm = moe._route_topk, generate_mod._rmsnorm

    def fp8_norm(x, scale, eps):    # every matmul's input is a norm's
        out = kept_norm(x, scale, eps)      # output: round those to fp8
        return out.astype(jnp.float8_e4m3fn).astype(out.dtype)

    try:
        moe._route_topk = route(kept)
        if fp8_activations:
            generate_mod._rmsnorm = fp8_norm
        jax.clear_caches()
        out = np.asarray(generate(
            params, jnp.asarray(prompts),
            MoeConfig.nano_moe(qk_norm=qk_norm, dtype=jnp.dtype(dtype),
                               **kw), max_new_tokens=64))
    finally:
        moe._route_topk, generate_mod._rmsnorm = kept, kept_norm
        jax.clear_caches()
    score = jax.jit(lambda seq: olmoe_sparse.below_best_and_gaps(
        params, seq, model))
    pairs = [score(jnp.asarray(row)) for row in out]
    return ([np.asarray(m)[15:] for m, _ in pairs],
            [np.asarray(g)[15:] for _, g in pairs])


def _seven_of_eight(kept):
    def route(gates, k, renorm=True):
        w, idx = kept(gates, k, renorm)
        return w.at[:, -1].set(0.0), idx
    return route


def _one_in_eight_dropped(kept):
    def route(gates, k, renorm=True):
        import jax.numpy as jnp

        w, idx = kept(gates, k, renorm)
        lost = (jnp.arange(w.shape[0])[:, None]
                + jnp.arange(k)[None, :]) % 8 == 0
        return jnp.where(lost, 0.0, w), idx
    return route


WRONG = {
    "seven_experts": dict(route=_seven_of_eight),
    "renormalised_weights": dict(
        route=lambda kept: lambda g, k, renorm=True: kept(g, k, True)),
    "no_qk_norm": dict(route=lambda kept: kept, qk_norm=False),
    "one_assignment_in_eight_dropped": dict(route=_one_in_eight_dropped),
    "fp8_activations": dict(route=lambda kept: kept, fp8_activations=True),
}


def _verdict(margins, gaps):
    from benchmark.harness.drivers.serve_model import margin_verdict

    return margin_verdict(margins, gaps, published()["correct"])


def test_correct_passes_the_right_program_in_bf16():
    """bf16 activations against the float32 reference: the router flips
    some 8th experts (the gaps say where it can), margins are no longer
    zero, and the configuration's limits hold."""
    v = _verdict(*_generated_margins(lambda kept: kept))
    assert v["pass"], v
    assert v["margin_max"] > 0 and v["positions"] == 16 * 64
    assert v["clear_positions"] >= published()["correct"]["min_clear"]
    exact = _verdict(*_generated_margins(lambda kept: kept,
                                         dtype="float32"))
    assert exact["pass"] and exact["margin_max"] == 0.0


@pytest.mark.parametrize("how", sorted(WRONG))
def test_correct_fails_a_wrong_program(how):
    """Each of these computes something else than the model, some of them
    only slightly (seven experts drops the SMALLEST weight of eight; fp8
    is the nearest precision below the configuration's bf16): the same
    limits refuse it."""
    v = _verdict(*_generated_margins(**WRONG[how]))
    assert not v["pass"], v

"""What PR 33 added for the `deepseek_v32` configuration: `costs_mla`
against hand counts at the published widths, the eight `dsv32` readers on
hand-made records and a hand-made trace, the cell's files against the
catalog, its rehearsal twin end to end, and the parent's clean failure.
"""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark.harness import costs_mla, spec, xplane
from benchmark.tests import make_mla_trace

CELL = "dsv32-longdoc"
CONFIG = "deepseek-v3.2-exp-serve"
READERS = ("indexer_device_ms", "sparse_attn_device_ms",
           "indexer_roofline_pct", "sparse_attn_roofline_pct",
           "select_keep_pct", "moe_held_hit_pct",
           "sparse_prefill_share_pct", "held_experts_roofline_pct")
COUNTER_READERS = ("select_keep_pct", "moe_held_hit_pct")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return spec.load_module("layer_metrics", name)


def published():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# -- costs_mla ---------------------------------------------------------------

def test_costs_against_hand_counts_at_the_published_widths():
    m = published()
    p = costs_mla.share_parameters(m)
    # ISSUE 33's arithmetic: 11.01 + 37.75 + 4.13 + 16.78 + 117.44 M
    assert p["attention"] == 7168 * 1536 + 1536 * 128 * 192 \
        + 7168 * 576 + 512 * 128 * 256 + 16384 * 7168 == 187_105_280
    assert p["indexer"] == 1536 * 8192 + 7168 * 128 + 7168 * 64 \
        == 13_959_168
    assert p["expert"] == 3 * 7168 * 2048 == 44_040_192
    assert p["expert_layer"] == 187_105_280 + 13_959_168 \
        + 17 * 44_040_192 + 7168 * 256
    assert p["dense_layer"] == 187_105_280 + 13_959_168 + 3 * 7168 * 18432
    assert p["vocabulary"] == 2 * 16160 * 7168
    assert round(p["total"] / 1e6) == 4635
    # a token-layer the indexer scores: 128 values of 2 B, 64 heads
    assert costs_mla.indexer_score_cost(m, 1000) == (
        1000 * 2 * 64 * 128, 1000 * 256)
    # a token-layer attention selects: 576 values of 2 B; 128 heads over
    # 576 (scores) + 512 (weighted sum)
    assert costs_mla.sparse_attention_cost(m, 10) == (
        10 * 2 * 128 * (576 + 512), 10 * 1152)
    assert costs_mla.held_experts_cost(m, 3, 5) == (
        2.0 * 44_040_192 * 5, 44_040_192 * 2.0 * 3)
    # the indexer is memory-bound, a hit expert's read too
    assert costs_mla.least_s((1e9, 1e9), PEAK) == 1e9 / 819e9
    assert costs_mla.least_s((1e12, 1e6), PEAK) == 1e12 / 197e12


def test_program_config_agrees_with_costs():
    pytest.importorskip("jax")
    from benchmark.harness.drivers import serve_mla

    m = published()
    cfg, _, _ = serve_mla.program_config(m, 18432)
    assert cfg.num_params() == costs_mla.share_parameters(m)["total"] \
        + cfg.n_layers * (2 * 7168 + 1536 + 512 + 2 * 128) + 7168 \
        + cfg.n_moe_layers * 256          # norms and biases: not matrices
    assert cfg.held_experts == (0, 16) and cfg.n_held == 16
    latent, index = cfg.cache_planes()
    assert (latent.lanes, index.lanes) == (640, 128)
    assert abs(cfg.sm_scale - 192 ** -0.5 * 1.3689 ** 2) < 1e-4


# -- the readers -------------------------------------------------------------

def _run(tmp_path, snaps=None, **kw):
    """`records` and `reduced` around a trace make_mla_trace writes."""
    where = tmp_path / "plugins" / "profile" / "x"
    where.mkdir(parents=True, exist_ok=True)
    (where / "vm.xplane.pb").write_bytes(make_mla_trace.space(**kw))
    trace = xplane.load(str(where / "vm.xplane.pb"))
    zero = {k: 0.0 for k in (
        "indexer_tokens_scored_total", "indexer_tokens_selected_total",
        "indexer_decode_tokens_scored_total",
        "indexer_decode_tokens_selected_total", "moe_assignments_total",
        "moe_assignments_landed_total", "moe_decode_experts_hit_total")}
    base = {
        # the traced stretch: one dispatch of horizon 8; 24 rows x 8,000
        # live tokens x 5 layers x 8 tokens scored, 2,048 of each kept;
        # 8 of the 16 held experts hit in each of 4 layers x 8 tokens
        "t0": dict(zero, decode_horizon_mean=8.0, decode_horizon_count=10),
        "t1": dict(zero, decode_horizon_mean=8.0, decode_horizon_count=11,
                   indexer_decode_tokens_scored_total=24 * 8000 * 5 * 8.0,
                   indexer_decode_tokens_selected_total=24 * 2048 * 5 * 8.0,
                   moe_decode_experts_hit_total=8 * 4 * 8.0),
        "w0": dict(zero, indexer_tokens_scored_total=1e6,
                   indexer_tokens_selected_total=1e6,
                   moe_assignments_total=1000.0,
                   moe_assignments_landed_total=100.0),
        "w1": dict(zero, indexer_tokens_scored_total=9e6,
                   indexer_tokens_selected_total=3e6,
                   moe_assignments_total=161000.0,
                   moe_assignments_landed_total=10100.0)}
    records = {"session": types.SimpleNamespace(dir=str(tmp_path)),
               "snaps": base if snaps is None else snaps,
               "model": published(), "device": {"kind": "TPU v5 lite"}}
    reduced = {"trace": trace, "idlest_chip": 0,
               "window": xplane.span_window(trace.host, "bench.window"),
               "busy_s_by_chip": {0: 900e-6}}
    return records, reduced


def test_readers_on_the_hand_made_trace(tmp_path):
    records, reduced = _run(tmp_path)
    # decode: score 80 + top-k 20 us over the 8 tokens of the stretch
    assert reader("indexer_device_ms").read(records, reduced) == \
        pytest.approx(0.100 / 8)
    assert reader("sparse_attn_device_ms").read(records, reduced) == \
        pytest.approx(0.075 / 8)
    # 7.68 M token-layers scored x 256 B at 819 GB/s over 80 us
    least = 24 * 8000 * 5 * 8 * 256 / 819e9
    assert reader("indexer_roofline_pct").read(records, reduced) == \
        pytest.approx(100.0 * least / 80e-6)
    # 1.97 M selected: 278,528 operations each (1.41 ns at 197 T/s,
    # 1.407 ns of bytes): compute-bound by a hair, over 75 us
    n = 24 * 2048 * 5 * 8
    least = max(n * 2 * 128 * 1088 / 197e12, n * 1152 / 819e9)
    assert reader("sparse_attn_roofline_pct").read(records, reduced) == \
        pytest.approx(100.0 * least / 75e-6)
    assert reader("select_keep_pct").read(records, reduced) == \
        pytest.approx(25.0)
    assert reader("moe_held_hit_pct").read(records, reduced) == \
        pytest.approx(6.25)
    # prefill: 120 + 60 + 30 + 40 of 500 us
    assert reader("sparse_prefill_share_pct").read(records, reduced) == \
        pytest.approx(50.0)
    least = 8 * 4 * 8 * 44_040_192 * 2 / 819e9
    assert reader("held_experts_roofline_pct").read(records, reduced) == \
        pytest.approx(100.0 * least / 200e-6)


def test_rooflines_read_100_at_the_least_time_and_not_more(tmp_path):
    score_us = 24 * 8000 * 5 * 8 * 256 / 819e9 * 1e6
    n = 24 * 2048 * 5 * 8
    attend_us = max(n * 2 * 128 * 1088 / 197e12, n * 1152 / 819e9) * 1e6
    experts_us = 8 * 4 * 8 * 44_040_192 * 2 / 819e9 * 1e6
    records, reduced = _run(tmp_path, score=score_us, gather=attend_us / 4,
                            attend=attend_us * 3 / 4, experts=experts_us)
    for name in ("indexer_roofline_pct", "sparse_attn_roofline_pct",
                 "held_experts_roofline_pct"):
        got = reader(name).read(records, reduced)
        assert got == pytest.approx(100.0, rel=1e-5) and got <= 100.01
    # a program that multiplied all 16 held experts for the 8 that were
    # hit takes twice the time: half the share
    records, reduced = _run(tmp_path / "all", experts=2 * experts_us)
    assert reader("held_experts_roofline_pct").read(records, reduced) == \
        pytest.approx(50.0, rel=1e-5)


@pytest.mark.parametrize("name", READERS)
def test_none_when_scopes_or_counters_are_absent(tmp_path, name):
    """No trace (--trace 0), a trace of a program without these scopes,
    an engine without the counters (another family, the parent commit),
    no snapshots at all: the metric is left out, and nothing raises."""
    records, reduced = _run(tmp_path)
    if name not in COUNTER_READERS:
        assert reader(name).read(records, None) is None
        bare, bare_reduced = _run(tmp_path / "bare", scoped=False)
        assert reader(name).read(bare, bare_reduced) is None
    old = {k: {"decode_horizon_mean": 8.0, "decode_horizon_count": 10 + i,
               "moe_assignments_total": 100.0 * i,
               "moe_assignments_landed_total": 100.0 * i}
           for i, k in enumerate(("t0", "t1", "w0", "w1"))}
    if name != "sparse_prefill_share_pct":       # reads the trace alone
        if name not in ("indexer_device_ms", "sparse_attn_device_ms"):
            assert reader(name).read(dict(records, snaps=old),
                                     reduced) is None
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
    assert {"step_wall_p50_ms", "step_host_ms", "decode_step_device_ms",
            "decode_kv_move_device_ms", "kv_pool_peak_pct",
            "preemptions"} <= cell
    # `moe_experts_roofline_pct` reads OLMoE's keys; `moe_ffn_device_ms`
    # would read this cell too, but test_olmoe.py (an accepted file) holds
    # the four `moe_*` entries' lists to its one cell
    assert not {"moe_experts_roofline_pct", "moe_ffn_device_ms"} & cell


# -- the cell's files --------------------------------------------------------

def test_cell_resolves_with_every_published_width():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["driver"] == "serve_mla"
    assert {m.name for m in cell.end_to_end} == {
        "tpot_p95_ms", "out_tokens_per_s", "setup_s"}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V3.2-Exp")
    entry = [c for c in spec.load_benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["source"] == row["source_url"] == cell.config["source"]
    differs = {k for k, v in row["config"].items()
               if cell.config.get(k, "absent") != v}
    # n_routed_experts stays 256 in the file (the router's width); what is
    # cut is how many of them this chip HOLDS
    assert differs | {"n_routed_experts"} == set(entry["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert set(cell.config["reduced"]) == set(entry["reduced"])
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"]) == (
        7168, 128, 1536, 512, 128, 64, 128, 18432, 2048)
    assert (c["n_routed_experts"], c["num_experts_per_tok"], c["n_group"],
            c["topk_group"], c["index_n_heads"], c["index_head_dim"],
            c["index_topk"], c["held_experts"]) == (
        256, 8, 8, 4, 64, 128, 2048, [0, 16])
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["vocab_size"], c["num_nextn_predict_layers"]) == (
        5, 1, 16160, 0)
    assert "16 chips share each layer" in c["deployment"]
    t = cell.traffic["traffic"]
    assert (t["clients"], t["prompt"], t["output"]) == (
        32, {"median": 4096, "sigma": 0.6, "min": 2048, "max": 16384},
        {"median": 512, "sigma": 0.5, "min": 128, "max": 1536})
    assert (t["block"], t["blocks"], t["ramp_s"], t["stagger_first"],
            t["schedule_seed"]) == (64, 4, 10.0, 24, 23)
    e = c["engine"]
    assert (e["max_len"], e["batch_slots"], e["prefill_chunk"],
            e["kv_pool_bytes"], e["greedy"], e["preempt"]) == (
        18432, 24, 512, 3 << 30, True, "recompute")
    assert e["max_len"] >= t["prompt"]["max"] + t["output"]["max"]


def test_traffic_ids_come_from_the_vocabulary_slice():
    cell = spec.load_cell(CELL)
    gen = cell.generator.generate(cell.traffic["traffic"], 2**31 + 5, 45.0,
                                  cell.config["vocab_size"])
    lens = [len(r["prompt"]) for r in gen["requests"]]
    assert len(lens) == 256 and min(lens) >= 2048 and max(lens) <= 16384
    assert max(int(r["prompt"].max()) for r in gen["requests"][:32]) < 16160
    # every request lives above index_topk from its first decode token
    assert all(n >= cell.config["index_topk"] for n in lens)


def test_rehearsal_twin_runs_end_to_end():
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", str(2**31 + 7), "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    last = r.stdout.strip().splitlines()[-1]
    assert '"rehearsal": "ok"' in last and '"correct": true' in last
    assert "select_keep_pct" in last and "moe_held_hit_pct" in last
    assert '"compiles_in_window": 0' in r.stdout
    assert '"select_overlap": 1.0' in r.stdout


@pytest.mark.parametrize("variant", ["fp8", "attend_all"])
def test_controls_come_out_not_correct(variant):
    """`harness/controls_mla.py`: the cell's twin with a wrong program
    behind the engine is refused by `margin_verdict` under the twin's own
    limits (the module exits 0 where the verdict is the expected one)."""
    r = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.controls_mla",
         "--variant", variant, "--seed", str(2**31 + 9), "--rehearse"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["refused"] and last["logit_check"]["sampled"] == 3


def _finished(lengths):
    return [types.SimpleNamespace(prompt=[0] * n, max_new=m)
            for n, m in lengths]


def test_sample_takes_the_longest_request_the_reference_fits():
    """Two seeded requests as `serve_hybrid.pick_sample` draws them (both
    past `long_tokens`, none past `reference_max_tokens`) and the LONGEST
    finished one that fits `far_max_tokens`."""
    serve_mla = spec.load_cell(CELL).driver
    ccfg = published()["correct"]
    assert (ccfg["sample"], ccfg["long_share"], ccfg["long_tokens"],
            ccfg["reference_max_tokens"], ccfg["far_max_tokens"]) == (
        3, 2, 4096, 6144, 10240)
    ok = _finished([(2100, 200), (3000, 400), (4500, 500), (5000, 900),
                    (5600, 300), (7000, 512), (9000, 900), (9500, 1000),
                    (14000, 600)])
    pick = serve_mla.pick_sample(ok, ccfg, seed=2**31 + 5)
    total = [len(r.prompt) + r.max_new for r in pick]
    assert len(pick) == 3 and total[2] == 9900
    assert all(4096 < n <= 6144 for n in total[:2])
    assert pick == serve_mla.pick_sample(ok, ccfg, seed=2**31 + 5)
    # nothing that long finished: the longest there is, never one twice
    pick = serve_mla.pick_sample(ok[:4], ccfg, seed=1)
    assert len(pick) == 3 and len({id(r) for r in pick}) == 3


def test_verdict_judges_the_mean_and_the_99th_percentile():
    import numpy as np
    serve_mla = spec.load_cell(CELL).driver
    ccfg = {"margin_mean_tol": 0.2, "margin_p99_cap": 1.0}
    quiet = [np.full(500, 0.05), np.full(500, 0.1)]
    assert serve_mla.margin_verdict(quiet, ccfg)["pass"]
    # ONE position far out is the right program's long tail: reported
    tail = [np.concatenate([quiet[0], [9.0]]), quiet[1]]
    v = serve_mla.margin_verdict(tail, ccfg)
    assert v["pass"] and v["margin_max"] == 9.0 and v["positions"] == 1001
    # two in a hundred far out, or a raised level everywhere, is not
    wide = [np.where(np.arange(500) % 25 == 0, 3.0, 0.05), quiet[1]]
    assert not serve_mla.margin_verdict(wide, ccfg)["pass"]
    assert not serve_mla.margin_verdict([np.full(800, 0.3)], ccfg)["pass"]
    assert not serve_mla.margin_verdict([], ccfg)["pass"]


def test_parent_tree_fails_the_cell_at_once(tmp_path):
    """The parent's program under this PR's benchmark files: `ray_tpu`
    has no `MlaConfig`, and the driver says so and exits before any
    weight is made."""
    for rel in ("BENCHMARK.json", "benchmark"):
        src, dst = os.path.join(spec.ROOT, rel), tmp_path / rel
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(
            src, dst, **({"ignore": shutil.ignore_patterns("out", "tests")}
                         if os.path.isdir(src) else {}))
    pkg = tmp_path / "ray_tpu"
    (pkg / "models").mkdir(parents=True)
    (pkg / "util").mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "models" / "__init__.py").write_text("LlamaConfig = object\n")
    (pkg / "util" / "__init__.py").write_text("")
    (pkg / "util" / "compile_cache.py").write_text(
        "def enable_compile_cache():\n    return None\n")
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--rehearse"], cwd=tmp_path, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert r.returncode != 0
    assert "has no MlaConfig" in r.stderr + r.stdout

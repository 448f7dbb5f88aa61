"""What PR 52 added for the `dots3_note` configuration: `costs_mla_swa`
against hand counts at the published widths, the three `swa_*` readers on
hand-made records and a hand-made trace, the cell's files against the
catalog, its sample, its rehearsal twin end to end with two controls, and
the parent's clean failure.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import costs_mla, costs_mla_swa, spec, xplane
from benchmark.tests import make_mla_swa_trace

CELL = "dots3-mixed-ctx"
CONFIG = "dots3-note-prev-serve"
READERS = ("swa_attn_device_ms", "swa_attn_roofline_pct",
           "swa_prefill_share_pct")
# accepted readers that read this program right as they are
APPENDED = ("setup_programs", "setup_trace_lower_s",
            "setup_cache_miss_programs", "setup_compile_s",
            "setup_cache_fetch_s", "setup_other_s", "step_wall_p50_ms",
            "step_host_ms", "decode_step_device_ms",
            "decode_kv_move_device_ms", "kv_pool_peak_pct", "preemptions",
            "decode_run_ahead_pct", "indexer_device_ms",
            "indexer_roofline_pct", "sparse_attn_device_ms",
            "sparse_attn_roofline_pct", "select_keep_pct",
            "moe_held_hit_pct", "held_experts_roofline_pct",
            "sparse_prefill_share_pct", "window_pool_peak_pct")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return spec.load_module("layer_metrics", name)


def published():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# -- costs_mla_swa -----------------------------------------------------------

def test_costs_against_hand_counts_at_the_published_widths():
    m = published()
    p = costs_mla_swa.share_parameters(m)
    # ISSUE 52's table: 5.24 + 25.17 + 2.95 + 16.78 + 83.89 + 0.66 + 9.37 M
    assert p["full_attention"] == 5120 * 1024 + 1024 * 128 * 192 \
        + 5120 * 576 + 512 * 128 * 256 + 128 * 128 * 5120 + 5120 * 128 \
        + (1024 * 64 * 128 + 5120 * 128 + 5120 * 64) == 144_048_128
    # 5.24 + 16.78 + 5.57 + 20.97 + 41.94 + 0.33 M
    assert p["window_attention"] == 5120 * 1024 + 1024 * 64 * 256 \
        + 5120 * 1088 + 1024 * 64 * 320 + 64 * 128 * 5120 + 5120 * 64 \
        == 90_832_896
    assert p["expert"] == 3 * 5120 * 1536 == 23_592_960
    assert p["dense_ffn"] == 3 * 5120 * 13824 == 212_336_640
    assert p["expert_ffn"] == 5120 * 256 + 33 * 23_592_960 == 779_878_400
    assert p["vocabulary"] == 2 * 19008 * 5120 == 194_641_920
    # 356.4 + 923.9 + 3 x 870.7 + 194.6 = 4,087 M = 7.61 GiB in bf16
    assert p["total"] == 2 * 144_048_128 + 3 * 90_832_896 + 212_336_640 \
        + 4 * 779_878_400 + 194_641_920 == 4_087_087_104
    assert round(p["total"] * 2 / 2**30, 2) == 7.61
    # a slot a window layer reads: 1,088 values in 1,152 lanes of 2 B; 64
    # heads over 1,088 (scores) + 1,024 (weighted sum); three such layers
    assert costs_mla_swa.window_row_bytes(m) == 2304
    assert costs_mla_swa.window_layers(m) == 3
    assert costs_mla_swa.swa_attention_cost(m, 10) == (
        30 * 2 * 64 * (1088 + 1024), 30 * 2304)
    # 117 operations a byte, under the chip's ridge of 240: memory-bound
    ops, nbytes = costs_mla_swa.swa_attention_cost(m, 1e6)
    assert costs_mla_swa.least_s((ops, nbytes), PEAK) == nbytes / 819e9
    # the full layers' costs ARE costs_mla's, from the same keys
    assert costs_mla.sparse_attention_cost(m, 10) == (
        10 * 2 * 128 * (576 + 512), 10 * 1152)
    assert costs_mla.held_experts_cost(m, 3, 0) == (0.0, 23_592_960 * 2.0 * 3)


def test_program_config_agrees_with_costs():
    pytest.importorskip("jax")
    driver = spec.load_cell(CELL).driver

    m = published()
    cfg, _, ref = driver.program_config(m, 33792)
    norms = 2 * (2 * 5120 + 1024 + 512 + 2 * 128) \
        + 3 * (2 * 5120 + 1024 + 1024) + 5120 + 4 * 256
    assert cfg.num_params() == \
        costs_mla_swa.share_parameters(m)["total"] + norms
    assert cfg.held_experts == (0, 32) and cfg.n_held == 32
    latent, index, wlatent = cfg.cache_planes()
    assert (latent.layers, latent.lanes, index.layers, index.lanes) == \
        (2, 640, 2, 128)
    assert (wlatent.table, wlatent.layers, wlatent.lanes) == \
        ("window", 3, 1152)
    assert (cfg.n_select_layers, cfg.n_window_layers) == (2, 3)
    assert cfg.sliding_window == 513 and cfg.n_group == 1
    assert ref.__name__.endswith("dots3_note")
    # 3,072 B a token in the full table, 6,912 B a slot in the window table
    assert latent.block_bytes(1) + index.block_bytes(1) == 3072
    assert wlatent.block_bytes(1) == 6912


# -- the readers -------------------------------------------------------------

def _run(tmp_path, snaps=None, **kw):
    """`records` and `reduced` around a trace make_mla_swa_trace writes."""
    where = tmp_path / "plugins" / "profile" / "x"
    where.mkdir(parents=True, exist_ok=True)
    (where / "vm.xplane.pb").write_bytes(make_mla_swa_trace.space(**kw))
    trace = xplane.load(str(where / "vm.xplane.pb"))
    base = {
        # the traced stretch: one dispatch of horizon 8 over 64 rows, 40 of
        # them past the window and 24 at 300 slots
        "t0": {"decode_horizon_mean": 8.0, "decode_horizon_count": 10,
               "swa_window_slots_total": 1e6, "swa_window_rows_total": 1e3},
        "t1": {"decode_horizon_mean": 8.0, "decode_horizon_count": 11,
               "swa_window_slots_total": 1e6 + 8 * (40 * 513 + 24 * 300),
               "swa_window_rows_total": 1e3 + 8 * 64}}
    records = {"session": types.SimpleNamespace(dir=str(tmp_path)),
               "snaps": base if snaps is None else snaps,
               "model": published(), "device": {"kind": "TPU v5 lite"}}
    reduced = {"trace": trace, "idlest_chip": 0,
               "window": xplane.span_window(trace.host, "bench.window"),
               "busy_s_by_chip": {0: 900e-6}}
    return records, reduced


def test_readers_on_the_hand_made_trace(tmp_path):
    records, reduced = _run(tmp_path)
    # decode: the pages' gather 10 + the kernel 50 us over the 8 tokens
    assert reader("swa_attn_device_ms").read(records, reduced) == \
        pytest.approx(0.060 / 8)
    # 221,760 slots x 3 layers x 2,304 B at 819 GB/s over 60 us
    least = 8 * (40 * 513 + 24 * 300) * 3 * 2304 / 819e9
    assert reader("swa_attn_roofline_pct").read(records, reduced) == \
        pytest.approx(100.0 * least / 60e-6)
    # prefill: 60 + 10 + 125 + 5 of 700 us
    assert reader("swa_prefill_share_pct").read(records, reduced) == \
        pytest.approx(100.0 * 200 / 700)
    # the full layers' readers see what they saw: the window's ops are
    # under scopes of their own
    assert reader("sparse_attn_device_ms").read(records, reduced) == \
        pytest.approx(0.075 / 8)
    assert reader("sparse_prefill_share_pct").read(records, reduced) == \
        pytest.approx(100.0 * 250 / 700)


def test_roofline_reads_100_at_the_least_time_and_not_more(tmp_path):
    least_us = 8 * (40 * 513 + 24 * 300) * 3 * 2304 / 819e9 * 1e6
    records, reduced = _run(tmp_path, window=least_us)
    got = reader("swa_attn_roofline_pct").read(records, reduced)
    assert got == pytest.approx(100.0, rel=1e-5) and got <= 100.01
    # a kernel that reads 3 whole pages of 256 slots a row for a window of
    # 513 reads two thirds at most
    records, reduced = _run(tmp_path / "pages", window=least_us * 768 / 513)
    assert reader("swa_attn_roofline_pct").read(records, reduced) == \
        pytest.approx(100.0 * 513 / 768, rel=1e-5)


@pytest.mark.parametrize("name", READERS)
def test_none_when_scopes_or_counters_are_absent(tmp_path, name):
    """No trace (--trace 0), a trace of a program without these scopes
    (the parent commit's, DeepSeek's), an engine without the counter, no
    snapshots at all: the metric is left out, and nothing raises."""
    from benchmark.tests import make_mla_trace

    records, reduced = _run(tmp_path)
    assert reader(name).read(records, None) is None
    bare, bare_reduced = _run(tmp_path / "bare", scoped=False)
    assert reader(name).read(bare, bare_reduced) is None
    # DeepSeek's program: selection's scopes, no window layer's
    where = tmp_path / "ds" / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(make_mla_trace.space())
    ds = dict(records, session=types.SimpleNamespace(
        dir=str(tmp_path / "ds")))
    ds_reduced = dict(reduced, trace=xplane.load(
        str(where / "vm.xplane.pb")))
    assert reader(name).read(ds, ds_reduced) is None
    if name != "swa_prefill_share_pct":          # reads the trace alone
        old = {k: {"decode_horizon_mean": 8.0,
                   "decode_horizon_count": 10 + i}
               for i, k in enumerate(("t0", "t1"))}
        if name == "swa_attn_roofline_pct":
            assert reader(name).read(dict(records, snaps=old),
                                     reduced) is None
        assert reader(name).read(dict(records, snaps={}), reduced) is None


def test_entries_agree_with_the_readers_and_the_cell_lists_them():
    bench = spec.load_benchmark()
    cell = {m.name for m in spec.load_cell(CELL).per_layer}
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(READERS)
    for name in READERS:
        entry = [m for m in bench["per_layer"] if m["name"] == name][-1]
        mod = reader(name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"])
        assert entry["workloads"] == [CELL] and name in cell
    for name in APPENDED:
        entry = [m for m in bench["per_layer"] if m["name"] == name][-1]
        assert entry["workloads"][-1] == CELL and name in cell
    assert cell == set(READERS) | set(APPENDED)
    # OLMoE's and Qwen3-Next's expert readers read their own keys, the
    # paged kernel's this family does not call
    assert not {"moe_experts_roofline_pct", "moe_ffn_device_ms",
                "qwen3next_experts_roofline_pct",
                "paged_attn_roofline_pct"} & cell
    cells = bench["workloads"]
    assert cells[-1]["name"] == CELL and len(cells) == 8
    assert sum(c["chips"] == 4 for c in cells) == 1


# -- the cell's files --------------------------------------------------------

def test_cell_resolves_with_every_published_width():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["driver"] == "serve_mla_swa"
    assert {m.name for m in cell.end_to_end} == {
        "tpot_p95_ms", "out_tokens_per_s", "setup_s"}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    entry = [c for c in spec.load_benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["source"] == row["source_url"] == cell.config["source"]
    differs = {k for k, v in row["config"].items()
               if cell.config.get(k, "absent") != v}
    # n_routed_experts stays 256 in the file (the router's width); what is
    # cut is how many of them this chip HOLDS
    assert differs | {"n_routed_experts"} == set(entry["reduced"]) == {
        "num_hidden_layers", "layer_types", "n_routed_experts",
        "vocab_size"}
    assert set(cell.config["reduced"]) == set(entry["reduced"])
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["rope_theta"]) == (
        5120, 128, 1024, 512, 128, 64, 128, 13824, 1536, 80000000)
    assert (c["swa_num_attention_heads"], c["swa_q_lora_rank"],
            c["swa_kv_lora_rank"], c["swa_qk_nope_head_dim"],
            c["swa_qk_rope_head_dim"], c["swa_v_head_dim"],
            c["swa_rope_theta"], c["sliding_window_size"]) == (
        64, 1024, 1024, 192, 64, 128, 50000, 513)
    assert (c["n_routed_experts"], c["num_experts_per_tok"],
            c["index_n_heads"], c["index_head_dim"], c["index_topk"],
            c["held_experts"]) == (256, 8, 64, 128, 2048, [0, 32])
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["vocab_size"]) == (5, 1, 19008)
    assert c["layer_types"] == row["config"]["layer_types"][:5] == [
        "full_attention", "full_attention", "sliding_attention",
        "sliding_attention", "sliding_attention"]
    assert "8 chips share each layer" in c["deployment"]
    assert [k[0] for k in sorted(c["assumed"])[:5]] == list("12345")
    t = cell.traffic["traffic"]
    assert (t["clients"], t["output"]) == (
        96, {"median": 384, "sigma": 0.6, "min": 64, "max": 1024})
    # ISSUE 52's prompts, or its ONE recorded fallback of the median
    assert t["prompt"] in (
        {"median": m, "sigma": 0.9, "min": 256, "max": 32768}
        for m in (3072, 2048))
    assert (t["ramp_s"], t["stagger_first"]) == (10.0, 64)
    assert isinstance(t["schedule_seed"], int)
    e = c["engine"]
    assert (e["max_len"], e["batch_slots"], e["prefill_chunk"],
            e["kv_block_tokens"], e["greedy"], e["preempt"]) == (
        33792, 64, 512, 256, True, "recompute")
    assert e["decode_horizon"] in (8, 2)
    assert e["max_len"] >= t["prompt"]["max"] + t["output"]["max"]


def test_traffic_mixes_rows_under_and_past_the_selection():
    cell = spec.load_cell(CELL)
    gen = cell.generator.generate(cell.traffic["traffic"], 2**31 + 5, 45.0,
                                  cell.config["vocab_size"])
    lens = [len(r["prompt"]) for r in gen["requests"]]
    assert min(lens) >= 256 and max(lens) <= 32768
    assert max(int(r["prompt"].max()) for r in gen["requests"][:32]) < 19008
    under = sum(n < 2048 for n in lens) / len(lens)
    far = sum(n > 16384 for n in lens) / len(lens)
    # a third to a half of the contexts under index_topk (the window alone
    # prunes), a few percent past 16 k; every prompt at least half a window
    assert 0.25 < under < 0.55 and 0.005 < far < 0.08


def _finished(lengths):
    return [types.SimpleNamespace(prompt=[0] * n, max_new=m)
            for n, m in lengths]


def test_sample_takes_a_short_request_two_long_and_the_longest():
    driver = spec.load_cell(CELL).driver
    ccfg = published()["correct"]
    assert (ccfg["sample"], ccfg["short_tokens"], ccfg["long_share"],
            ccfg["long_tokens"]) == (4, 2048, 2, 4096)
    ok = _finished([(300, 100), (900, 400), (1800, 300), (3000, 400),
                    (4500, 500), (5000, 900), (5600, 300), (7000, 512),
                    (9000, 900), (ccfg["far_max_tokens"] - 1000, 1000),
                    (30000, 600)])
    pick = driver.pick_sample(ok, ccfg, seed=2**31 + 5)
    total = [len(r.prompt) + r.max_new for r in pick]
    assert len(pick) == 4 and total[0] < 2048
    assert all(4096 < n <= ccfg["reference_max_tokens"] for n in total[1:3])
    assert total[3] == ccfg["far_max_tokens"]
    assert pick == driver.pick_sample(ok, ccfg, seed=2**31 + 5)
    # nothing short finished: four others, never one twice
    pick = driver.pick_sample(ok[3:], ccfg, seed=1)
    assert len(pick) == 4 and len({id(r) for r in pick}) == 4


def test_verdict_has_three_parts_and_leaves_out_what_it_cannot_judge():
    import numpy as np
    driver = spec.load_cell(CELL).driver
    ccfg = {"margin_mean_tol": 1.0, "margin_p99_cap": 3.0,
            "short_mean_tol": 0.1, "given_mean_tol": 0.2,
            "select_overlap_min": 0.95}
    long = [np.full(300, 0.6), np.full(200, 0.8)]
    short, given = np.full(100, 0.04), np.full(300, 0.06)
    good = driver.verdict(long + [short], short, given, 0.99, ccfg)
    assert good["pass"] and good["short"]["pass"] and good["given"]["pass"]
    assert good["select_overlap"] == 0.99 and good["sampled"] == 3
    # each part alone refuses: the window layers' fault shows in the short
    # request, the full layers' attention under the program's own
    # selection, a wrong selection in the overlap
    assert not driver.verdict(long + [short], short * 4, given, 0.99,
                              ccfg)["pass"]
    assert not driver.verdict(long + [short], short, given * 4, 0.99,
                              ccfg)["pass"]
    assert not driver.verdict(long + [short], short, given, 0.90,
                              ccfg)["pass"]
    assert not driver.verdict([m * 2 for m in long], short, given, 0.99,
                              ccfg)["pass"]
    # no short request finished, no long one fits: judged on the rest
    bare = driver.verdict(long, None, None, float("nan"), ccfg)
    assert bare["pass"] and "short" not in bare and "given" not in bare
    limits = published()["correct"]
    assert set(ccfg) <= set(limits)
    assert limits["short_mean_tol"] < limits["given_mean_tol"] \
        < limits["margin_mean_tol"]


# -- end to end on the CPU ---------------------------------------------------

def test_rehearsal_twin_runs_end_to_end():
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", str(2**31 + 7), "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    last = r.stdout.strip().splitlines()[-1]
    assert '"rehearsal": "ok"' in last and '"correct": true' in last
    assert "select_keep_pct" in last and "window_pool_peak_pct" in last
    assert '"compiles_in_window": 0' in r.stdout
    assert '"rows_cover_assignments": true' in r.stdout
    assert '"select_overlap": 1.0' in r.stdout


@pytest.mark.parametrize("variant", ["no_gate", "swa_scale_192"])
def test_controls_come_out_not_correct(variant):
    """`harness/controls_mla_swa.py`: the cell's twin with a wrong program
    behind the engine is refused by `margin_verdict` under the twin's own
    limits (the module exits 0 where the verdict is the expected one)."""
    r = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.controls_mla_swa",
         "--variant", variant, "--seed", str(2**31 + 9), "--rehearse"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["refused"] and last["logit_check"]["sampled"] == 4


def test_the_parent_fails_cleanly_on_this_configuration(monkeypatch):
    """A checkout whose `MlaConfig` has no window layers (the parent
    commit) exits with a sentence, before anything is built."""
    pytest.importorskip("jax")
    import dataclasses

    from ray_tpu.models import mla

    driver = spec.load_cell(CELL).driver

    @dataclasses.dataclass(frozen=True)
    class Old:
        dim: int = 1

    monkeypatch.setattr(mla, "MlaConfig", Old)
    monkeypatch.setattr(sys.modules["ray_tpu.models"], "MlaConfig", Old)
    with pytest.raises(SystemExit, match="no window layers"):
        driver.program_config(published(), 128)

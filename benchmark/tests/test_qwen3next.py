"""What PR 44 added for the `qwen3_next` configuration: `costs_gdn` against
hand counts at the published widths, the eight `qwen3next` readers on
hand-made records and a hand-made trace, the cell's files against the
catalog, its rehearsal twin end to end, its controls, and the parent's
clean failure.
"""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark.harness import costs_gdn, spec, xplane
from benchmark.tests import make_gdn_trace

CELL = "qwen3next-longctx"
CONFIG = "qwen3-next-80b-a3b-serve"
READERS = ("gdn_device_ms", "gdn_state_roofline_pct",
           "gdn_chunk_roofline_pct", "gdn_prefill_share_pct",
           "gated_attn_roofline_pct", "qwen3next_experts_roofline_pct",
           "moe_prefill_share_pct", "moe_rows_per_landed")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return spec.load_module("layer_metrics", name)


def published():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# -- costs_gdn ---------------------------------------------------------------

def test_costs_against_hand_counts_at_the_published_widths():
    m = published()
    p = costs_gdn.share_parameters(m)
    # ISSUE 44's arithmetic: 25.17 + 0.13 + 0.03 + 8.39 M a delta mixer
    assert p["delta_mixer"] == 2048 * 12288 + 2048 * 64 + 4 * 8192 \
        + 4096 * 2048 == 33_718_272
    assert p["attention_mixer"] == 2048 * 8192 + 2 * 2048 * 512 \
        + 4096 * 2048 == 27_262_976
    assert p["expert"] == 3 * 2048 * 512 == 3_145_728
    assert p["expert_layer"] == 2048 * 512 + 3 * 2048 * 512 + 2048 \
        + 128 * 3_145_728 == 406_849_536
    assert p["vocabulary"] == 2 * 37984 * 2048 == 155_582_464
    assert p["total"] == 6 * (33_718_272 + 406_849_536) \
        + 2 * (27_262_976 + 406_849_536) + 155_582_464
    assert round(p["total"] / 1e6) == 3667       # 6.83 GiB in bf16
    assert round(p["total"] * 2 / 2**30, 2) == 6.83
    # what a token stores: 2 layers x K and V of 2 heads of 256, bf16
    assert costs_gdn.kv_token_bytes(m) == 4096
    # what a row keeps: 6 layers x (2 MiB of state + 48 KiB of conv)
    assert costs_gdn.state_bytes(m) == 2 * 2**20
    assert costs_gdn.conv_state_bytes(m) == 3 * 8192 * 2
    assert costs_gdn.recurrent_bytes_per_row(m) == 6 * (2**21 + 49152)
    assert 64 * costs_gdn.recurrent_bytes_per_row(m) / 2**30 \
        == pytest.approx(0.768, abs=1e-3)
    # a decode step of 64 live rows moves 1.5 GiB of state
    assert costs_gdn.step_least_s(m, 64, 819e9) == 64 * 6 * 2 * 2**21 / 819e9
    ops, nbytes = costs_gdn.chunk_cost(m, 1000, 512)
    per_head = 2 * (0.5 * (2 * 64 * 128 + 64 * 128)
                    + 0.5 * (64 * 128 + 64 * 128) + 3 * 128 * 128)
    assert ops == 1000 * 6 * 32 * per_head
    assert nbytes == 1000 * 6 * ((2 * 2048 + 2 * 4096) * 2
                                 + 2 * 2**21 / 512)
    assert costs_gdn.attention_least_s(m, 1e6, 819e9) == 1e6 * 2048 / 819e9
    assert costs_gdn.held_experts_cost(m, 3, 5) == (
        2.0 * 3_145_728 * 5, 3_145_728 * 2.0 * 3)
    assert costs_gdn.least_s((1e9, 1e9), PEAK) == 1e9 / 819e9
    assert costs_gdn.least_s((1e12, 1e6), PEAK) == 1e12 / 197e12


def test_program_config_agrees_with_costs():
    pytest.importorskip("jax")
    from benchmark.harness.drivers import serve_gdn

    m = published()
    cfg, _, _ = serve_gdn.program_config(m, 33792)
    norms = 8 * 2048 + 6 * (2048 + 2 * 32 + 128) + 2 * (2048 + 2 * 256) \
        + 2048                              # not matrices
    assert cfg.num_params() == costs_gdn.share_parameters(m)["total"] + norms
    assert cfg.held_experts == (0, 128) and cfg.n_held == 128
    assert (cfg.n_delta_layers, cfg.n_attn_layers) == (6, 2)
    assert sum(pl.block_bytes(1) for pl in cfg.cache_planes()) \
        == costs_gdn.kv_token_bytes(m)
    assert sum(pl.row_bytes() for pl in cfg.state_planes()) \
        == costs_gdn.recurrent_bytes_per_row(m)
    assert cfg.rotary_dim == 64 and cfg.conv_dim == 8192


# -- the readers -------------------------------------------------------------

def _run(tmp_path, snaps=None, **kw):
    """`records` and `reduced` around a trace make_gdn_trace writes."""
    where = tmp_path / "plugins" / "profile" / "x"
    where.mkdir(parents=True, exist_ok=True)
    (where / "vm.xplane.pb").write_bytes(make_gdn_trace.space(**kw))
    trace = xplane.load(str(where / "vm.xplane.pb"))
    zero = {k: 0.0 for k in (
        "ssm_row_steps_total", "kv_walk_tokens_full_total",
        "moe_decode_experts_hit_total", "prefill_real_tokens")}
    base = {
        # the traced stretch: one dispatch of horizon 2 over 64 live rows
        # of 10,000 tokens, 90 of the 128 held experts hit in each of 8
        # layers x 2 tokens; one prefill group of 4 x 512 tokens, 2,000
        # of them real
        "t0": dict(zero, decode_horizon_mean=2.0, decode_horizon_count=10),
        "t1": dict(zero, decode_horizon_mean=2.0, decode_horizon_count=11,
                   ssm_row_steps_total=2 * 64.0,
                   kv_walk_tokens_full_total=2 * 64 * 10000 * 2.0,
                   moe_decode_experts_hit_total=90 * 8 * 2.0,
                   prefill_real_tokens=2000.0)}
    # the window: 20,480 assignments of which 5,120 landed on a held
    # expert, 23,040 rows multiplied
    base["w0"] = {"moe_rows_computed_total": 1000.0,
                  "moe_assignments_total": 500.0,
                  "moe_assignments_landed_total": 100.0}
    base["w1"] = {"moe_rows_computed_total": 24040.0,
                  "moe_assignments_total": 20980.0,
                  "moe_assignments_landed_total": 5220.0}
    records = {"session": types.SimpleNamespace(dir=str(tmp_path)),
               "snaps": base if snaps is None else snaps,
               "model": published(), "device": {"kind": "TPU v5 lite"}}
    reduced = {"trace": trace, "idlest_chip": 0,
               "window": xplane.span_window(trace.host, "bench.window"),
               "busy_s_by_chip": {0: 900e-6}}
    return records, reduced


def test_readers_on_the_hand_made_trace(tmp_path):
    records, reduced = _run(tmp_path)
    # decode: proj 30 + conv 10 + step 60 us over the 2 tokens
    assert reader("gdn_device_ms").read(records, reduced) == \
        pytest.approx(0.100 / 2)
    least = 128 * 6 * 2 * 2**21 / 819e9
    assert reader("gdn_state_roofline_pct").read(records, reduced) == \
        pytest.approx(100.0 * least / 60e-6)
    ops, nbytes = costs_gdn.chunk_cost(published(), 2000, 512)
    least = max(ops / 197e12, nbytes / 819e9)
    assert reader("gdn_chunk_roofline_pct").read(records, reduced) == \
        pytest.approx(100.0 * least / 180e-6)
    # prefill: 100 + 20 + 180 of 600 us (the ragged dot is the experts')
    assert reader("gdn_prefill_share_pct").read(records, reduced) == \
        pytest.approx(50.0)
    least = 2 * 64 * 10000 * 2 * 2048 / 819e9
    assert reader("gated_attn_roofline_pct").read(records, reduced) == \
        pytest.approx(100.0 * least / 50e-6)
    least = 90 * 8 * 2 * 3_145_728 * 2 / 819e9
    assert reader("qwen3next_experts_roofline_pct").read(
        records, reduced) == pytest.approx(100.0 * least / 200e-6)
    # prefill: the ragged dot's 300 of 600 us is the expert layer's (XLA
    # drops its scope; the readers count it by its name)
    assert reader("moe_prefill_share_pct").read(records, reduced) == \
        pytest.approx(50.0)
    assert reader("moe_rows_per_landed").read(records, reduced) == \
        pytest.approx(23040 / 5120)


def test_rooflines_read_100_at_the_least_time_and_not_more(tmp_path):
    step_us = 128 * 6 * 2 * 2**21 / 819e9 * 1e6
    kern_us = 2 * 64 * 10000 * 2 * 2048 / 819e9 * 1e6
    experts_us = 90 * 8 * 2 * 3_145_728 * 2 / 819e9 * 1e6
    ops, nbytes = costs_gdn.chunk_cost(published(), 2000, 512)
    chunk_us = max(ops / 197e12, nbytes / 819e9) * 1e6
    records, reduced = _run(tmp_path, step=step_us, kern=kern_us,
                            experts=experts_us, chunk=chunk_us)
    for name in ("gdn_state_roofline_pct", "gdn_chunk_roofline_pct",
                 "gated_attn_roofline_pct",
                 "qwen3next_experts_roofline_pct"):
        got = reader(name).read(records, reduced)
        assert got == pytest.approx(100.0, rel=1e-5) and got <= 100.01
    # a program that moved the state of all 64 slots for 32 live rows
    # takes twice the time a live row: half the share
    records, reduced = _run(tmp_path / "all", step=2 * step_us)
    assert reader("gdn_state_roofline_pct").read(records, reduced) == \
        pytest.approx(50.0, rel=1e-5)


@pytest.mark.parametrize("name", READERS)
def test_none_when_scopes_or_counters_are_absent(tmp_path, name):
    """No trace (--trace 0), a trace of a program without these scopes,
    an engine without the counters (another family, the parent commit),
    no snapshots at all: the metric is left out, and nothing raises."""
    records, reduced = _run(tmp_path)
    counters_alone = name == "moe_rows_per_landed"
    trace_alone = name in ("gdn_prefill_share_pct", "moe_prefill_share_pct")
    if not counters_alone:
        assert reader(name).read(records, None) is None
        bare, bare_reduced = _run(tmp_path / "bare", scoped=False)
        if name != "gated_attn_roofline_pct":   # a kernel's name is its
            assert reader(name).read(bare, bare_reduced) is None  # event's
    old = {k: {"decode_horizon_mean": 2.0, "decode_horizon_count": 10 + i}
           for i, k in enumerate(("t0", "t1", "w0", "w1"))}
    if not trace_alone and name != "gdn_device_ms":
        assert reader(name).read(dict(records, snaps=old), reduced) is None
    if not trace_alone:
        assert reader(name).read(dict(records, snaps={}), reduced) is None
    # another family's model keys give the attention reader no geometry
    if name == "gated_attn_roofline_pct":
        other = dict(records, model={"hidden_size": 4096})
        assert reader(name).read(other, reduced) is None


def test_entries_agree_with_the_readers_and_the_cell_lists_them():
    bench = spec.load_benchmark()
    cell = {m.name for m in spec.load_cell(CELL).per_layer}
    for name in READERS:
        entry = [m for m in bench["per_layer"] if m["name"] == name][-1]
        mod = reader(name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"])
        assert CELL in entry["workloads"] and name in cell
        # every cell a reader's entry lists resolves, and reports it
        for other in entry["workloads"]:
            assert name in {m.name for m in spec.load_cell(other).per_layer}
    assert {"step_wall_p50_ms", "step_host_ms", "decode_step_device_ms",
            "decode_kv_move_device_ms",
            "kv_pool_peak_pct", "preemptions", "setup_programs",
            "setup_trace_lower_s", "setup_cache_miss_programs",
            "setup_compile_s", "setup_cache_fetch_s",
            "setup_other_s"} <= cell
    # `decode_run_ahead_pct` would read here too (7.4 on the chip), but its
    # accepted test holds its list to three cells: a `benchmark` PR's edit
    assert "decode_run_ahead_pct" not in cell
    # seven cells, one of them on four chips
    cells = bench["workloads"]
    assert len(cells) == 7 and sum(c["chips"] == 4 for c in cells) == 1
    assert cells[-1]["name"] == CELL and len(bench["configs"]) == 6


# -- the cell's files --------------------------------------------------------

def test_cell_resolves_with_every_published_width():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["driver"] == "serve_gdn"
    assert {m.name for m in cell.end_to_end} == {
        "tpot_p95_ms", "out_tokens_per_s", "setup_s"}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    entry = [c for c in spec.load_benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["source"] == row["source_url"] == cell.config["source"]
    differs = {k for k, v in row["config"].items()
               if cell.config.get(k, "absent") != v}
    # num_experts stays 512 in the file (the router's width); what is cut
    # is how many of them this chip HOLDS
    assert differs | {"num_experts"} == set(entry["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert set(cell.config["reduced"]) == set(entry["reduced"])
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["linear_num_key_heads"], c["linear_num_value_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"],
            c["linear_conv_kernel_dim"], c["moe_intermediate_size"],
            c["shared_expert_intermediate_size"]) == (
        2048, 16, 2, 256, 16, 32, 128, 128, 4, 512, 512)
    assert (c["num_experts"], c["num_experts_per_tok"], c["held_experts"],
            c["full_attention_interval"], c["partial_rotary_factor"],
            c["rope_theta"]) == (512, 10, [0, 128], 4, 0.25, 10000000)
    assert (c["num_hidden_layers"], c["vocab_size"]) == (8, 37984)
    assert "4 sharing each layer" in c["deployment"]
    assert "multi_token_prediction" in c["assumed"]
    t = cell.traffic["traffic"]
    assert (t["clients"], t["prompt"], t["output"]) == (
        96, {"median": 4096, "sigma": 0.7, "min": 2048, "max": 32768},
        {"median": 384, "sigma": 0.6, "min": 64, "max": 1024})
    assert (t["ramp_s"], t["stagger_first"], t["schedule_seed"]) == (
        10.0, 64, 23)
    e = c["engine"]
    assert (e["max_len"], e["batch_slots"], e["prefill_chunk"],
            e["kv_pool_bytes"], e["greedy"], e["preempt"]) == (
        33792, 64, 512, 4 << 30, True, "recompute")
    assert e["max_len"] >= t["prompt"]["max"] + t["output"]["max"]
    assert e["max_len"] % e["kv_block_tokens"] == 0


def test_traffic_ids_come_from_the_vocabulary_slice():
    cell = spec.load_cell(CELL)
    gen = cell.generator.generate(cell.traffic["traffic"], 2**31 + 5, 45.0,
                                  cell.config["vocab_size"])
    lens = [len(r["prompt"]) for r in gen["requests"]]
    assert min(lens) >= 2048 and max(lens) <= 32768
    # ISSUE 44 set the median at 8,192 and allowed 6,144, then 4,096
    # (the clips stay) while a window completed under 80 requests
    assert 5000 < sum(lens) / len(lens) < 5600
    assert max(int(r["prompt"].max()) for r in gen["requests"][:32]) < 37984
    # the same multiset of lengths whatever the seed
    other = cell.generator.generate(cell.traffic["traffic"], 7, 45.0,
                                    cell.config["vocab_size"])
    assert sorted(lens) == sorted(len(r["prompt"])
                                  for r in other["requests"])


def test_rehearsal_twin_runs_end_to_end():
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", str(2**31 + 7), "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    last = r.stdout.strip().splitlines()[-1]
    assert '"rehearsal": "ok"' in last and '"correct": true' in last
    assert "step_host_ms" in last and "kv_pool_peak_pct" in last
    assert '"compiles_in_window": 0' in r.stdout
    assert '"ssm_state_resets_total"' in r.stdout
    # the admission probes went through the engine, before the schedule
    # and into the slots the window left, and both sets were judged
    assert r.stdout.count('"of": "probes"') == 4
    assert r.stdout.count('"of": "probes_after"') == 4
    assert '"probes": {"sampled": 4' in r.stdout
    assert '"probes_after": {"sampled": 4' in r.stdout
    assert "moe_rows_per_landed" in last     # a counter: no trace needed


@pytest.mark.parametrize("variant", [
    "fp8", "not_zeroed", "not_handed", "no_decay", "beta_one", "no_l2norm",
    "no_attn_gate", "full_rotary", "no_shared_gate"])
def test_controls_come_out_not_correct(variant):
    """`harness/controls_gdn.py`: the cell's twin with a wrong program
    behind the engine is refused by the margin verdict under the twin's
    own limits (the module exits 0 where the verdict is the expected
    one)."""
    r = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.controls_gdn",
         "--variant", variant, "--seed", str(2**31 + 9), "--rehearse"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["refused"] and last["logit_check"]["sampled"] == 4


def _finished(lengths):
    return [types.SimpleNamespace(prompt=[0] * n, max_new=m)
            for n, m in lengths]


def test_sample_has_two_past_8192_and_the_longest_that_fits():
    serve_gdn = spec.load_cell(CELL).driver
    ccfg = published()["correct"]
    assert (ccfg["sample"], ccfg["long_share"], ccfg["long_tokens"]) == (
        4, 2, 8192)
    ok = _finished([(2100, 200), (3000, 400), (4500, 500), (8500, 300),
                    (9000, 900), (9500, 1000), (11000, 512), (15000, 600),
                    (30000, 600)])
    pick = serve_gdn.pick_sample(ok, ccfg, seed=2**31 + 5)
    total = [len(r.prompt) + r.max_new for r in pick]
    assert len(pick) == 4 and len({id(r) for r in pick}) == 4
    assert total[3] == 15600                 # the longest that fits
    assert sum(n > 8192 for n in total[:3]) >= 2
    assert all(n <= ccfg["reference_max_tokens"] for n in total[:3])
    assert pick == serve_gdn.pick_sample(ok, ccfg, seed=2**31 + 5)


def test_parent_tree_fails_the_cell_at_once(tmp_path):
    """The parent's program under this PR's benchmark files: `ray_tpu`
    has no `GdnConfig`, and the driver says so and exits before any
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
    assert "no GdnConfig" in r.stderr + r.stdout

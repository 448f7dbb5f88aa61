"""What PR 31 added for the hybrid configuration: `costs_hybrid` against the
published widths by hand, the reference against a loop over tokens, the
five new readers on hand-made records and a hand-made trace, the cell's
files, and the POWER of the comparison that decides `correct`: it passes
the right program in bf16 and fails seven wrong ones.
"""

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.harness import costs_hybrid, spec, xplane
from benchmark.tests import make_hybrid_trace

CELL = "phi4flash-reason"
CONFIG = "phi-4-mini-flash-serve"
READERS = ("ssm_device_ms", "ssm_scan_roofline_pct",
           "hybrid_attn_roofline_pct", "window_pool_peak_pct",
           "prefill_skip_pct")
BYTES_PER_S = 819e9          # harness/peaks.json, "TPU v5 lite"
# the catalog's `config` for Phi-4-mini-flash-reasoning (model-configs
# guide, architectures.jsonl), kept here for a box without the guide
CATALOG = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20,
           "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "vocab_size": 200064}


def reader(name):
    return spec.load_module("layer_metrics", name)


def published():
    with open(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# -- costs_hybrid ------------------------------------------------------------

def test_costs_against_the_published_widths_by_hand():
    m = published()
    assert costs_hybrid.layer_counts(m) == {
        "ssm": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    assert costs_hybrid.ffn_params(m) == 3 * 2560 * 10240 == 78_643_200
    # in 26.2 M, x_proj 0.98, dt_proj 0.82 (+ bias), out 13.1, conv/A/D 0.1
    assert costs_hybrid.ssm_params(m) == (
        26_214_400 + 983_040 + 819_200 + 5_120 + 13_107_200
        + 5_120 * 5 + 5_120 * 16 + 5_120)
    assert round(costs_hybrid.ssm_params(m) / 1e6, 1) == 41.2
    # Wqkv 2560 x 5120 + bias, out_proj 2560 x 2560 + bias, 6 x 64 lambdas
    assert costs_hybrid.attention_params(m) == (
        13_107_200 + 5_120 + 6_553_600 + 2_560 + 384)
    assert costs_hybrid.cross_params(m) == 2 * 6_553_600 + 2 * 2_560 + 384
    assert costs_hybrid.gmu_params(m) == 26_214_400
    assert round(costs_hybrid.total_params(m) / 1e9, 2) == 3.85
    assert round(costs_hybrid.weight_bytes(m) / 2**30, 2) == 7.18
    # a token in a caching layer: 2 x 20 heads x 64 x 2 B; 9 layers cache,
    # 8 read the full one
    assert costs_hybrid.kv_token_layer_bytes(m) == 5_120
    assert costs_hybrid.caching_layers(m) == 9
    assert costs_hybrid.full_cache_readers(m) == 8
    assert costs_hybrid.ssm_state_bytes(m) == 327_680
    assert costs_hybrid.conv_state_bytes(m) == 30_720
    assert costs_hybrid.recurrent_bytes_per_row(m) == 9 * 358_400
    assert costs_hybrid.prefill_skip_share(m) == 14 / 32
    # 64 rows, one token: 9 layers x 2 x 327,680 B x 64 = 377 MB, 0.46 ms
    assert costs_hybrid.scan_least_s(m, 64, BYTES_PER_S) == pytest.approx(
        0.4609e-3, rel=1e-3)


def test_program_config_agrees_with_costs():
    from benchmark.harness.drivers import serve_hybrid

    cfg, _, _ = serve_hybrid.program_config(published(), 5120)
    assert cfg.num_params() == costs_hybrid.total_params(published())
    assert (cfg.d_inner, cfg.rank, cfg.head_dim, cfg.n_ssm_layers,
            cfg.n_window_layers) == (5120, 160, 64, 9, 8)


# -- the reference -----------------------------------------------------------

def _nano(vocab=256, dim=64, dtype="float32"):
    import jax.numpy as jnp

    from ray_tpu.models import HybridConfig

    dt = jnp.dtype(dtype)
    cfg = HybridConfig.nano_hybrid(vocab_size=vocab, dim=dim, dtype=dt,
                                   ffn_dim=2 * dim)
    model = dict(CATALOG, hidden_size=dim, intermediate_size=2 * dim,
                 num_hidden_layers=8, num_attention_heads=4,
                 num_key_value_heads=2, vocab_size=vocab, sliding_window=16,
                 assumed={"head_dim": dim // 4, "mamba_d_state": 4,
                          "mamba_d_conv": 4, "mamba_expand": 2,
                          "mamba_dt_rank": cfg.rank})
    return cfg, model


def test_reference_against_a_loop_over_tokens():
    """The reference's logits at every position equal those of a loop that
    feeds one token at a time with explicit state and an explicit list of
    keys and values: Mamba's recurrence and conv, the window's edge, the
    pairing of heads, the memory before the gate, in numpy float64."""
    import jax

    from benchmark.reference import phi4flash_hybrid as ref
    from ray_tpu.models import hybrid_init

    cfg, model = _nano()
    params = hybrid_init(jax.random.PRNGKey(3), cfg)
    P = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    seq = np.random.default_rng(0).integers(1, 256, size=41)
    got = np.asarray(ref.logits(params, seq[None], model))[0]

    d, H, KV, hd, di, N, R, dc, W = 64, 4, 2, 16, 128, 4, 4, 4, 16

    def ln(x, p):
        xc = x - x.mean()
        return xc / np.sqrt((xc * xc).mean() + 1e-5) * p["w"] + p["b"]

    def silu(x):
        return x / (1 + np.exp(-x))

    def ffn(h, pn, p):
        u = ln(h, pn)
        return h + (silu(u @ p["w_gate"]) * (u @ p["w_up"])) @ p["w_down"]

    def mamba(a, p, st):
        xz = a @ p["w_in"]
        x, z = xz[:di], xz[di:]
        st["conv"] = st["conv"][1:] + [x]
        x = silu(sum(st["conv"][k] * p["conv_w"][k] for k in range(dc))
                 + p["conv_b"])
        dbc = x @ p["w_x"]
        dt = np.log1p(np.exp(dbc[:R] @ p["w_dt"] + p["b_dt"]))
        A = -np.exp(p["a_log"])                              # [N, di]
        st["s"] = np.exp(dt[None] * A) * st["s"] \
            + (dt * x)[None] * dbc[R:R + N][:, None]
        y = dbc[R + N:] @ st["s"] + p["d"] * x
        return (y * silu(z)) @ p["w_out"], y

    def attend(q, ks, vs, p, layer, window):
        t = len(ks) - 1
        lo = 0 if window is None else max(0, t - window + 1)
        K, V = np.stack(ks[lo:]), np.stack(vs[lo:])     # [n, KV, hd]
        l0 = 0.8 - 0.6 * np.exp(-0.3 * layer)
        lam = np.exp(p["lq1"] @ p["lk1"]) - np.exp(p["lq2"] @ p["lk2"]) + l0
        out = []
        for j in range(H // 2):
            kp = j // (H // KV)
            sm = []
            for qi, ki in ((2 * j, 2 * kp), (2 * j + 1, 2 * kp + 1)):
                s = K[:, ki] @ q[qi] / np.sqrt(hd)
                e = np.exp(s - s.max())
                sm.append(e / e.sum())
            vv = np.concatenate([V[:, 2 * kp], V[:, 2 * kp + 1]], axis=-1)
            o = (sm[0] - lam * sm[1]) @ vv
            o = o / np.sqrt((o * o).mean() + 1e-5) * p["subln"]
            out.append(o * (1 - l0))
        return np.concatenate(out)

    def period(tree, i):
        return jax.tree.map(lambda a: a[i], tree)

    periods = [period(P["self"], i) for i in range(2)] + [P["mid"]]
    states = [{"conv": [np.zeros(di)] * dc, "s": np.zeros((N, di))}
              for _ in periods]
    caches = [([], []) for _ in periods]
    want = []
    for tok in seq:
        h = P["tok_embed"][tok]
        for i, (p, st, (ks, vs)) in enumerate(zip(periods, states, caches)):
            out, mem = mamba(ln(h, p["m_norm"]), p["mamba"], st)
            h = ffn(h + out, p["m_mlp_norm"], p["m_mlp"])
            qkv = ln(h, p["a_norm"]) @ p["attn"]["wqkv"] + p["attn"]["bqkv"]
            ks.append(qkv[H * hd:(H + KV) * hd].reshape(KV, hd))
            vs.append(qkv[(H + KV) * hd:].reshape(KV, hd))
            o = attend(qkv[:H * hd].reshape(H, hd), ks, vs, p["attn"],
                       2 * i + 1, W if i < 2 else None)
            h = ffn(h + o @ p["attn"]["wo"] + p["attn"]["bo"],
                    p["a_mlp_norm"], p["a_mlp"])
        p = period(P["cross"], 0)
        a = ln(h, p["g_norm"])
        h = h + (mem * silu(a @ p["gmu"]["w_in"])) @ p["gmu"]["w_out"]
        h = ffn(h, p["g_mlp_norm"], p["g_mlp"])
        q = (ln(h, p["c_norm"]) @ p["attn"]["wq"]
             + p["attn"]["bq"]).reshape(H, hd)
        o = attend(q, *caches[2], p["attn"], 7, None)
        h = ffn(h + o @ p["attn"]["wo"] + p["attn"]["bo"],
                p["c_mlp_norm"], p["c_mlp"])
        want.append(ln(h, P["final_norm"]) @ P["tok_embed"].T)
    np.testing.assert_allclose(got, np.stack(want), atol=5e-6, rtol=0)
    # and `below_best`, head in blocks, agrees with the whole head
    from benchmark.reference import phi4flash_hybrid as mod
    kept, mod.HEAD_BLOCK = mod.HEAD_BLOCK, 100     # 256 rows: 3 blocks
    try:
        below = np.asarray(ref.below_best(params, seq, model))
    finally:
        mod.HEAD_BLOCK = kept
    full = got[:-1].max(-1) - got[np.arange(40), seq[1:]]
    np.testing.assert_allclose(below, full, atol=5e-6, rtol=0)
    assert (below >= 0).all()


def test_reference_stands_alone_and_refuses_what_it_lacks():
    src = open(os.path.join(spec.BENCH_DIR, "reference",
                            "phi4flash_hybrid.py")).read()
    assert "import ray_tpu" not in src and "from ray_tpu" not in src
    from benchmark.reference import phi4flash_hybrid as ref

    _, model = _nano()
    for bad in ({"mb_per_layer": 1}, {"mlp_bias": True},
                {"tie_word_embeddings": False}, {"num_hidden_layers": 6}):
        with pytest.raises(ValueError):
            ref._check(dict(model, **bad))


# -- the readers -------------------------------------------------------------

def _run(tmp_path, scan=25.0, kern=50.0, scoped=True, snaps=None):
    """`records` and `reduced` around a trace make_hybrid_trace writes."""
    where = tmp_path / "plugins" / "profile" / "x"
    where.mkdir(parents=True, exist_ok=True)
    (where / "vm.xplane.pb").write_bytes(
        make_hybrid_trace.space(scan, kern, scoped))
    trace = xplane.load(str(where / "vm.xplane.pb"))
    zero = {k: 0.0 for k in (
        "kv_walk_tokens_window_total", "kv_walk_tokens_full_total",
        "ssm_row_steps_total", "prefill_layer_tokens_total",
        "prefill_layer_tokens_skipped_total", "window_pool_peak_blocks")}
    horizon = {"decode_horizon_mean": 8.0}
    base = {
        # the traced stretch: 8 tokens of horizon for 60 live rows; the
        # kernel is asked 8 x 60 x 8 x 512 window token-layers and
        # 8 readers x 8 x 60 x 1,000 full ones
        "t0": dict(zero, **horizon, decode_horizon_count=10,
                   ssm_row_steps_total=1000.0,
                   kv_walk_tokens_window_total=5e6,
                   kv_walk_tokens_full_total=7e6),
        "t1": dict(zero, **horizon, decode_horizon_count=11,
                   ssm_row_steps_total=1000.0 + 8 * 60,
                   kv_walk_tokens_window_total=5e6 + 8 * 60 * 8 * 512,
                   kv_walk_tokens_full_total=7e6 + 8 * 8 * 60 * 1000),
        "w0": dict(zero, prefill_layer_tokens_total=32_000.0,
                   prefill_layer_tokens_skipped_total=13_000.0),
        "w1": dict(zero, prefill_layer_tokens_total=32_000.0 + 320_000,
                   prefill_layer_tokens_skipped_total=13_000.0 + 136_000,
                   window_pool_peak_blocks=1100.0,
                   window_pool_blocks_total=1352.0)}
    records = {"session": types.SimpleNamespace(dir=str(tmp_path)),
               "snaps": base if snaps is None else snaps,
               "model": published(), "device": {"kind": "TPU v5 lite"}}
    reduced = {"trace": trace, "idlest_chip": 0,
               "window": xplane.span_window(trace.host, "bench.window"),
               "busy_s_by_chip": {0: 400e-6}}
    return records, reduced


def test_readers_on_the_hand_made_trace(tmp_path):
    records, reduced = _run(tmp_path)
    # decode: ssm_proj 40 + ssm_scan 25 + gmu 10 = 75 us over 8 tokens
    # (prefill's ssm_scan, 50 us, is not in it)
    assert reader("ssm_device_ms").read(records, reduced) == \
        pytest.approx(0.075 / 8)
    # 480 row-steps x 9 layers x 2 x 327,680 B at 819 GB/s = 3.457 ms
    least = 480 * 9 * 2 * 327_680 / BYTES_PER_S
    assert reader("ssm_scan_roofline_pct").read(records, reduced) == \
        pytest.approx(100.0 * least / 25e-6)
    # (1,966,080 + 3,840,000) token-layers x 5,120 B, over the decode
    # program's two kernel calls of 50 us (prefill's call is not in it)
    least = (8 * 60 * 8 * 512 + 8 * 8 * 60 * 1000) * 5_120 / BYTES_PER_S
    assert reader("hybrid_attn_roofline_pct").read(records, reduced) == \
        pytest.approx(100.0 * least / 100e-6)
    assert reader("window_pool_peak_pct").read(records, reduced) == \
        pytest.approx(100.0 * 1100 / 1352)
    assert reader("prefill_skip_pct").read(records, reduced) == \
        pytest.approx(42.5)


def test_rooflines_are_100_at_the_byte_time(tmp_path):
    """Device time equal to the time the counted bytes take: 100 %, not
    more; twice the time, 50 %."""
    scan_us = 480 * 9 * 2 * 327_680 / BYTES_PER_S * 1e6
    kern_us = (8 * 60 * 8 * 512 + 8 * 8 * 60 * 1000) * 5_120 \
        / BYTES_PER_S * 1e6 / 2
    records, reduced = _run(tmp_path, scan=scan_us, kern=kern_us)
    for name in ("ssm_scan_roofline_pct", "hybrid_attn_roofline_pct"):
        got = reader(name).read(records, reduced)
        assert got == pytest.approx(100.0, rel=1e-5) and got <= 100.001
    records, reduced = _run(tmp_path / "slow", scan=2 * scan_us,
                            kern=2 * kern_us)
    for name in ("ssm_scan_roofline_pct", "hybrid_attn_roofline_pct"):
        assert reader(name).read(records, reduced) == \
            pytest.approx(50.0, rel=1e-5)


@pytest.mark.parametrize("name", READERS)
def test_none_when_scopes_or_counters_are_absent(tmp_path, name):
    """No trace (--trace 0), a trace of a program without the hybrid
    scopes, an engine without the counters (another family, the parent
    commit), no snapshots at all: the metric is left out, nothing
    raises."""
    records, reduced = _run(tmp_path)
    traced = name in ("ssm_device_ms", "ssm_scan_roofline_pct",
                      "hybrid_attn_roofline_pct")
    if traced:
        assert reader(name).read(records, None) is None
    if name in ("ssm_device_ms", "ssm_scan_roofline_pct"):
        bare, bare_reduced = _run(tmp_path / "bare", scoped=False)
        assert reader(name).read(bare, bare_reduced) is None
    old = {k: {"decode_horizon_mean": 8.0, "decode_horizon_count": 10 + i,
               "steps_total": 40.0 + i}
           for i, k in enumerate(("t0", "t1", "w0", "w1"))}
    if name != "ssm_device_ms":         # reads the trace and the horizon
        assert reader(name).read(dict(records, snaps=old), reduced) is None
    assert reader(name).read(dict(records, snaps={}, stats_end={}),
                             reduced) is None


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
            "step_wall_p50_ms", "step_host_ms", "kv_pool_peak_pct",
            "preemptions"} <= cell
    # these multiply live tokens by every layer, or count one table
    assert not {"paged_attn_roofline_pct", "paged_walk_live_pct"} & cell


# -- the cell's files --------------------------------------------------------

def test_cell_resolves_with_every_published_width():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["driver"] == "serve_hybrid"
    assert {m.name for m in cell.end_to_end} == {
        "out_tokens_per_s", "tpot_p95_ms", "setup_s"}
    want, source = CATALOG, cell.config["source"]
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") \
                as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Phi-4-mini-flash-reasoning")
        want, source = row["config"], row["source_url"]
    except (OSError, StopIteration):
        pass
    entry = [c for c in spec.load_benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["source"] == source == cell.config["source"]
    differs = {k for k, v in want.items()
               if cell.config.get(k, "absent") != v}
    assert differs == set(entry["reduced"]) == set()
    assert cell.config["reduced"] == {}
    a = cell.config["assumed"]
    assert (a["head_dim"], a["mamba_d_state"], a["mamba_d_conv"],
            a["mamba_expand"], a["mamba_dt_rank"]) == (64, 16, 4, 2, 160)
    t = cell.traffic["traffic"]
    assert (t["clients"], t["prompt"], t["output"]) == (
        96, {"median": 256, "sigma": 0.7, "min": 32, "max": 1024},
        {"median": 1536, "sigma": 0.6, "min": 256, "max": 4096})
    assert (t["block"], t["blocks"], t["stagger_first"], t["ramp_s"],
            t["schedule_seed"]) == (256, 8, 96, 10.0, 23)
    assert cell.traffic["generator"] == "closed_lognormal"
    assert cell.traffic["trace"] == {"trace_s": 3.0, "trace_lead_s": 2.0}
    e = cell.config["engine"]
    assert (e["max_len"], e["batch_slots"], e["kv_block_tokens"],
            e["prefill_chunk"], e["max_prefills_per_step"], e["preempt"],
            e["warm_groups"], e["greedy"]) == (
        5120, 64, 32, 512, 4, "recompute", [1, 2, 4], True)
    c = cell.config["correct"]
    assert (c["sample"], c["reference_max_tokens"]) == (4, 3072)
    assert 0 < c["margin_mean_tol"] < c["margin_cap"]


def test_sample_prefers_long_requests():
    from benchmark.harness.drivers import serve_hybrid

    def req(n_prompt, n_new):
        return types.SimpleNamespace(prompt=np.zeros(n_prompt, np.int32),
                                     max_new=n_new)

    ok = [req(100 + i, 300) for i in range(20)] \
        + [req(200, 1000), req(300, 1500), req(500, 2400), req(900, 4000)]
    ccfg = published()["correct"]
    pick = serve_hybrid.pick_sample(ok, ccfg, seed=2**31 + 5)
    total = [len(r.prompt) + r.max_new for r in pick]
    assert len(pick) == 4 and sum(n > 1024 for n in total) == 2
    assert max(total) <= 3072
    again = serve_hybrid.pick_sample(ok, ccfg, seed=2**31 + 5)
    assert [id(r) for r in again] == [id(r) for r in pick]


def test_rehearsal_twin_runs_end_to_end():
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", str(2**31 + 7), "--trace", "1", "--rehearse"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    last = r.stdout.strip().splitlines()[-1]
    assert '"rehearsal": "ok"' in last and '"correct": true' in last
    assert "prefill_skip_pct" in last and "window_pool_peak_pct" in last
    assert '"compiles_in_window": 0' in r.stdout
    hyb = next(json.loads(ln)["hybrid"] for ln in r.stdout.splitlines()
               if ln.startswith('{"hybrid"'))
    assert hyb["window_blocks_freed_total"] > 0
    assert hyb["window_pool_peak_blocks"] <= hyb["window_pool_blocks_total"]


def test_parent_tree_fails_the_cell_at_once(tmp_path):
    """The parent's checkout, with and without this PR's benchmark files
    laid over it: no entry for the cell there (a SpecError), and with the
    files a `ray_tpu` that has no `HybridConfig` (an exit, before any
    weight is made). Both at once, neither a hang."""
    for rel in ("BENCHMARK.json", "benchmark/run.py", "benchmark/__init__.py",
                "benchmark/harness", "benchmark/configs",
                "benchmark/workloads", "benchmark/traffic",
                "benchmark/layer_metrics", "benchmark/reference"):
        src, dst = os.path.join(spec.ROOT, rel), tmp_path / rel
        if not os.path.exists(src):
            continue
        dst.parent.mkdir(parents=True, exist_ok=True)
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(src, dst)
    (tmp_path / "ray_tpu" / "models").mkdir(parents=True)
    (tmp_path / "ray_tpu" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "models" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "util").mkdir()
    (tmp_path / "ray_tpu" / "util" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "util" / "compile_cache.py").write_text(
        "def enable_compile_cache():\n    return None\n")
    run = [sys.executable, "benchmark/run.py", "--workload", CELL,
           "--rehearse"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(run, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0 and "no HybridConfig" in r.stderr
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != CELL]
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if CELL in m.get("workloads", []):
                m["workloads"].remove(CELL)
        bench[group] = [m for m in bench[group]
                        if m.get("workloads") != []]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    r = subprocess.run(run, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=60)
    assert r.returncode != 0 and "SpecError" in r.stderr


# -- the power of `correct` --------------------------------------------------

def _wrong(how, block=8):
    """(module attribute -> replacement) that turns `ray_tpu.models.hybrid`
    into a program that computes something else than the model (`block`:
    the engine's block size, what a window can be short by)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import hybrid

    kept_attn, kept_combine = hybrid.paged_attention, hybrid.diff_combine
    kept_norm = hybrid._layernorm

    def window_as(change):
        def attn(*a, window=None, **kw):
            return kept_attn(*a, window=change(window), **kw)
        return {"paged_attention": attn}

    def plain(o, p, layer, cfg):
        B, S, H, W = o.shape
        o = o.reshape(B, S, H // 2, 2, W).at[:, :, :, 1].set(0)
        return kept_combine(o.reshape(B, S, H, W), p, layer, cfg)

    def fp8_norm(x, p, eps):        # a norm's output feeds every layer's
        out = kept_norm(x, p, eps)  # first matmul: round those to fp8
        return out.astype(jnp.float8_e4m3fn).astype(out.dtype)

    return {
        "window_attends_everything": window_as(lambda w: None),
        "window_off_by_one_block": window_as(
            lambda w: None if w is None else w - block),
        "plain_attention": {"diff_combine": plain},
        "memory_after_the_gate": {
            "_handed_on": lambda y, z: y * jax.nn.silu(z)},
        "state_not_zeroed_at_admission": {
            "_starts_fresh": lambda starts: starts < 0},
        "state_not_handed_between_chunks": {
            "_starts_fresh": lambda starts: starts >= 0},
        "fp8_activations": {"_layernorm": fp8_norm},
    }[how]


def generated_margins(how=None, dtype="bfloat16", vocab=2048, dim=64,
                      n_prompt=40, n_new=72, requests=12):
    """Greedy tokens of the ENGINE at nano widths (8 layers, window 16,
    blocks of 8, chunks of 16, two slots, so that rows are reused, cross
    chunks and pass the window) in `dtype`, with `ray_tpu.models.hybrid`
    patched into the wrong program `how`, scored by the float32 reference:
    one array of margins a request."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import phi4flash_hybrid as ref
    from ray_tpu.models import hybrid, hybrid_init
    from ray_tpu.models.engine import DecodeEngine

    cfg32, model = _nano(vocab, dim)
    cfg, _ = _nano(vocab, dim, dtype)
    params = hybrid_init(jax.random.PRNGKey(0), cfg32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, vocab, size=n_prompt + 3 * i).tolist()
               for i in range(requests)]
    patch = _wrong(how) if how else {}
    kept = {name: getattr(hybrid, name) for name in patch}
    try:
        for name, fn in patch.items():
            setattr(hybrid, name, fn)
        jax.clear_caches()
        eng = DecodeEngine(params, cfg, batch_slots=2, max_len=256,
                           kv_block_tokens=8, prefill_chunk=16,
                           preempt="recompute")
        ids = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
        out = eng.run()
    finally:
        for name, fn in kept.items():
            setattr(hybrid, name, fn)
        jax.clear_caches()
    score = jax.jit(lambda seq, n: ref.margins(params, seq, n, model),
                    static_argnums=1)
    return [np.asarray(score(jnp.asarray(p + out[rid], jnp.int32), len(p)))
            for p, rid in zip(prompts, ids)]


# Two scenes, each what exposes some of the wrong programs: many short
# answers on two slots (stale state is freshest right after an admission,
# and a chunk boundary falls in every prompt), and few long rows (the
# window slides, the memory units see many tokens).
SCENES = {
    "reused_slots": dict(dim=64, n_prompt=24, n_new=24, requests=24),
    "long_rows": dict(dim=128, n_prompt=40, n_new=72, requests=12),
}


def _verdict(margins, dim):
    """The cell's own comparison and limits. The limits were read at the
    published width, where the logits spread sqrt(2560) x the embedding's
    0.02; at `dim` they spread sqrt(dim) x that, and so do the margins: the
    two limits are scaled by the ratio and nothing else is touched."""
    from benchmark.harness.drivers.serve_hybrid import margin_verdict

    ccfg = dict(published()["correct"])
    scale = (dim / CATALOG["hidden_size"]) ** 0.5
    for key in ("margin_mean_tol", "margin_cap"):
        ccfg[key] *= scale
    return margin_verdict(margins, ccfg)


@pytest.mark.parametrize("scene", sorted(SCENES))
def test_correct_passes_the_right_program_in_bf16(scene):
    kw = SCENES[scene]
    v = _verdict(generated_margins(**kw), kw["dim"])
    assert v["pass"], v
    assert v["margin_max"] > 0
    assert v["positions"] == kw["requests"] * kw["n_new"]
    exact = _verdict(generated_margins(dtype="float32", **kw), kw["dim"])
    assert exact["pass"] and exact["margin_max"] <= 1e-5


WRONG = {
    "window_attends_everything": "long_rows",
    "window_off_by_one_block": "long_rows",
    "plain_attention": "long_rows",
    "memory_after_the_gate": "long_rows",
    "state_not_zeroed_at_admission": "reused_slots",
    "state_not_handed_between_chunks": "reused_slots",
    "fp8_activations": "reused_slots",
}


@pytest.mark.parametrize("how", sorted(WRONG))
def test_correct_fails_a_wrong_program(how):
    """Each of these computes something else than the model, some of them
    only slightly (a window one block short drops the oldest 8 of 16 keys;
    fp8 is the nearest precision below the configuration's bf16; a state
    not zeroed decays away within a few dozen tokens): the same limits
    refuse it. On the chip at the published widths every one of them
    fails BOTH limits by 3 x or more (the configuration's
    `correct.derivation`); here the narrowest, the memory after the gate
    and the state not zeroed, fail by 1.6-1.7 x."""
    kw = SCENES[WRONG[how]]
    v = _verdict(generated_margins(how, **kw), kw["dim"])
    assert not v["pass"], v

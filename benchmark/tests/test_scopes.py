"""harness/scopes.py and the six per-layer readers of PR 24 on
data/scoped.xplane.pb (make_scoped_trace.py lays every event out; the
expectations below are worked out by hand from it, microseconds), on
hand-made records, on inputs that lack what they read, and on an excerpt
recorded on the chip."""

import os
import shutil
import types

import pytest

from benchmark.harness import common, scopes, spec, xplane
from benchmark.tests import make_scoped_trace

US = 1000  # ns
DATA = os.path.dirname(make_scoped_trace.PATH)
EXCERPT = os.path.join(DATA, "chat_scopes_excerpt.xplane.pb")


def reader(name):
    return spec.load_module("layer_metrics", name)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """`records` and `reduced` as run.py hands them to a reader, around
    the synthetic trace."""
    root = tmp_path_factory.mktemp("trace")
    where = root / "plugins" / "profile" / "2026_09_27"
    where.mkdir(parents=True)
    shutil.copy(make_scoped_trace.PATH, where / "vm.xplane.pb")
    trace = xplane.load(str(where / "vm.xplane.pb"))
    window = xplane.span_window(trace.host, "bench.window")
    spans = common.Spans()
    spans.by_name["engine.step"] = [(9.0, 0.9), (10.0, 0.5), (10.5, 0.7),
                                    (20.1, 0.6), (99.0, 5.0)]
    records = {
        "session": types.SimpleNamespace(dir=str(root)),
        "spans": spans, "window": (10.0, 20.0),
        "snaps": {
            "t0": {"decode_horizon_mean": 8.0, "decode_horizon_count": 10},
            "t1": {"decode_horizon_mean": 8.0, "decode_horizon_count": 11},
            "w0": {"device_wait_s": 3.0, "steps_total": 40,
                   "queue_wait_s_mean": 0.5,
                   "queue_wait_s_count": 10, "prefill_s_mean": 0.1,
                   "prefill_s_count": 8, "first_block_s_mean": 0.6,
                   "first_block_s_count": 8},
            "w1": {"device_wait_s": 4.5, "steps_total": 43,
                   "queue_wait_s_mean": 0.4,
                   "queue_wait_s_count": 20, "prefill_s_mean": 0.2,
                   "prefill_s_count": 12, "first_block_s_mean": 0.6,
                   "first_block_s_count": 12}}}
    reduced = {"trace": trace, "window": window, "idlest_chip": 0,
               "busy_s_by_chip": {0: 400e-6}}
    return records, reduced


def test_committed_file_is_what_the_maker_writes():
    with open(make_scoped_trace.PATH, "rb") as f:
        assert f.read() == make_scoped_trace.SPACE


def test_op_names_from_str_and_ref_values():
    names = scopes.op_names(make_scoped_trace.PATH)
    assert sorted(names) == [0]
    want = {n: op for n, _, _, op in make_scoped_trace.OPS if op}
    assert names[0] == want                   # the copy has no entry
    assert len(want) == len(make_scoped_trace.OPS) - 1


@pytest.mark.parametrize("op_name, parts, scope", [
    ("jit(step_fn)/transpose(jvp(mlp))/bsd,df->bsf/dot_general:",
     ["step_fn", "mlp", "bsd,df->bsf", "dot_general:"], "mlp"),
    ("jit(f)/while/body/closed_call/paged_attention/kv_gather/reshape",
     ["f", "while", "body", "closed_call", "paged_attention", "kv_gather",
      "reshape"], "kv_gather"),                       # the innermost wins
    ("jit(f)/while/body/dynamic_slice", ["f", "while", "body",
                                         "dynamic_slice"], None),
    ("a/mlp/mul;a/norm/add", ["a", "mlp", "mul"], "mlp"),   # the first
    (None, [], None), ("", [], None),
])
def test_components_and_scope_of(op_name, parts, scope):
    assert scopes.components(op_name) == parts
    assert scopes.scope_of(op_name) == scope


def test_kernels_remat_and_labels():
    per = scopes.op_names(make_scoped_trace.PATH)[0]
    by = {n.split(" ", 1)[0]: (n, per.get(n))
          for n, _, _, _ in make_scoped_trace.OPS}
    assert scopes.kernel_of(*by["%paged_attention.10"]) == "paged_attention"
    assert scopes.kernel_of(*by["%flash_fwd.3"]) == "flash_fwd"
    assert scopes.kernel_of(*by["%fusion.7"]) is None
    assert scopes.kernel_of(by["%flash_fwd.3"][0], None) == ""  # no name
    assert [k for k, v in by.items() if scopes.is_remat(v[1])] == \
        ["%flash_fwd.3", "%fusion.21"]
    assert {k: scopes.label(*v) for k, v in by.items()} == {
        "%while.1": "(no scope, computes) while",
        "%paged_attention.10": "kernel paged_attention",
        "%reshape.294": "kv_gather",
        "%dynamic-slice_bitcast_fusion.4":
            "(no scope, moves bytes) dynamic-slice_bitcast",
        "%copy.74": "(no scope, moves bytes) copy",
        "%fusion.153": "kv_write", "%fusion.7": "mlp", "%fusion.20": "mlp",
        "%flash_fwd.3": "kernel flash_fwd (recomputed)", "%fusion.21": "mlp",
        "%fusion.22": "mlp"}


def test_leaves_within_a_module(run):
    _, reduced = run
    lines = reduced["trace"].devices[0]
    inside = scopes.leaves_within(lines["XLA Ops"], lines["XLA Modules"],
                                  r"decode_multi_paged", reduced["window"])
    assert [n.split(" ", 1)[0] for n, _, _ in inside] == [
        "%paged_attention.10", "%reshape.294",
        "%dynamic-slice_bitcast_fusion.4", "%copy.74", "%fusion.153",
        "%fusion.7"]                          # the while is no leaf
    assert sum(d for _, _, d in inside) == 200 * US
    cut = (reduced["window"][0] + 100 * US, reduced["window"][0] + 155 * US)
    assert len(scopes.leaves_within(lines["XLA Ops"], lines["XLA Modules"],
                                    r"decode_multi_paged", cut)) == 3


def test_report_for_reading_by_hand():
    chip = scopes.report(make_scoped_trace.PATH)["chips"][0]
    assert chip["under_a_scope_or_kernel_pct"] == pytest.approx(87.5)
    assert chip["no_scope_moves_bytes_pct"] == pytest.approx(12.5)
    assert chip["no_scope_computes_pct"] == pytest.approx(0.0)
    assert chip["rematted_pct_of_leaf"] == pytest.approx(20.0)
    assert chip["kernels_s"] == {"kernel paged_attention": 80e-6,
                                 "kernel flash_fwd (recomputed)": 40e-6}
    assert chip["programs"]["jit_step_fn"]["by_scope_s"] == {
        "mlp": pytest.approx(160e-6),
        "kernel flash_fwd (recomputed)": 40e-6}
    # the chip idles [200,300): the engine's leaf spans name it
    assert dict(chip["idle_by_engine_span_s"]) == {
        "eng.device_wait": pytest.approx(90e-6),
        "eng.emit": pytest.approx(10e-6)}


def test_decode_kv_move_device_ms(run):
    records, reduced = run
    # kv_gather 20 + no scope 20 + copy 30 + kv_write 10 = 80 us over the
    # 8 tokens of horizon dispatched between the snapshots
    assert reader("decode_kv_move_device_ms").read(records, reduced) == \
        pytest.approx(0.080 / 8)


def test_remat_recompute_device_pct(run):
    records, reduced = run
    # flash_fwd 40 + fusion.21 40 of 400 us busy
    assert reader("remat_recompute_device_pct").read(records, reduced) == \
        pytest.approx(20.0)


def test_step_host_ms(run):
    records, _ = run
    # the engine counted three steps between the snapshots, the third
    # after the window's end: (0.5 + 0.7 + 0.6 - (4.5 - 3.0)) / 3 s
    assert reader("step_host_ms").read(records, None) == pytest.approx(100.0)


@pytest.mark.parametrize("name, want", [
    # (0.4 x 20 - 0.5 x 10) / 10, (0.2 x 12 - 0.1 x 8) / 4, 0.6 s
    ("ttft_queue_mean_ms", 300.0), ("ttft_prefill_mean_ms", 400.0),
    ("ttft_first_block_mean_ms", 600.0)])
def test_ttft_parts(run, name, want):
    assert reader(name).read(run[0], None) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "step_host_ms", "ttft_queue_mean_ms", "ttft_prefill_mean_ms",
    "ttft_first_block_mean_ms", "decode_kv_move_device_ms",
    "remat_recompute_device_pct"])
def test_none_when_the_input_is_missing(run, name):
    """A program from before PR 24 has no such counter and no scope, and a
    run with --trace 0 has no trace: the metric is left out, not raised."""
    records, reduced = run
    old = dict(records, snaps={
        k: {"decode_horizon_mean": 8.0, "decode_horizon_count": i,
            "queue_wait_s_mean": 0.5, "queue_wait_s_count": 10 + i,
            "steps_total": 40 + i}
        for i, k in enumerate(("t0", "t1", "w0", "w1"))})
    if name == "ttft_queue_mean_ms":        # that counter is an old one
        assert reader(name).read(old, reduced) is not None
        old = dict(old, snaps={})
    if name in ("decode_kv_move_device_ms", "remat_recompute_device_pct"):
        assert reader(name).read(records, None) is None      # no trace
        bare = dict(reduced, trace=xplane.load(
            os.path.join(DATA, "synthetic.xplane.pb")))
        bare["window"] = xplane.span_window(bare["trace"].host,
                                            "bench.window")
        bare["busy_s_by_chip"] = {0: 360e-6, 1: 250e-6}
        old = dict(records, session=types.SimpleNamespace(
            dir=str(_as_trace_dir(os.path.join(DATA,
                                               "synthetic.xplane.pb")))))
        assert reader(name).read(old, bare) is None          # no scopes
    else:
        assert reader(name).read(old, reduced) is None


def _as_trace_dir(path, _made={}):
    import tempfile

    if path not in _made:
        root = tempfile.mkdtemp()
        where = os.path.join(root, "plugins", "profile", "x")
        os.makedirs(where)
        shutil.copy(path, os.path.join(where, "vm.xplane.pb"))
        _made[path] = root
    return _made[path]


def test_spec_accepts_every_cell_with_the_new_metrics():
    cells = {c: {m.name for m in spec.load_cell(c).per_layer}
             for c in ("mistral7b-chat", "mistral7b-rollout",
                       "internlm2-train-fsdp4")}
    assert {"step_host_ms", "decode_kv_move_device_ms"} <= \
        cells["mistral7b-chat"] & cells["mistral7b-rollout"]
    assert {"ttft_queue_mean_ms", "ttft_prefill_mean_ms",
            "ttft_first_block_mean_ms"} <= cells["mistral7b-chat"]
    assert "ttft_queue_mean_ms" not in cells["mistral7b-rollout"]
    assert "remat_recompute_device_pct" in cells["internlm2-train-fsdp4"]
    bench = spec.load_benchmark()
    for m in bench["per_layer"][-6:]:
        mod = reader(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])


# -- the excerpt recorded on the chip ----------------------------------------

def test_recorded_excerpt_holds_scopes_kernels_and_engine_spans():
    """One decode step and one prefill chunk of mistral7b-chat on a v5e
    (PR 24, cut by cut_excerpt.py from a run with an empty compilation
    cache): where the scope is in a real trace."""
    trace = xplane.load(EXCERPT)
    per = scopes.op_names(EXCERPT)[0]
    lines = trace.devices[0]
    window = xplane.span_window(trace.host, "bench.window")
    decode = scopes.leaves_within(lines["XLA Ops"], lines["XLA Modules"],
                                  r"decode_multi_paged", window)
    by = scopes.time_by(decode, per, scopes.label)
    assert by["kernel paged_attention"] > 0.3 * sum(by.values())
    assert {"kv_gather", "kv_write", "attn_qkv", "attn_out", "mlp", "norm",
            "lm_head", "sample", "embed"} <= set(by)
    # every op of the decode program is the program's by name or only
    # moves bytes: nothing that computes is left without a scope
    unnamed = sum(v for k, v in by.items()
                  if k.startswith("(no scope, computes)"))
    assert unnamed < 0.02 * sum(by.values())
    # the pool's copies have no op_name at all on the chip
    assert any(n.startswith("%copy.") and n not in per for n, _, _ in decode)
    kinds = {scopes.scope_of(per.get(n)) for n, _, _ in decode}
    assert None in kinds and "kv_gather" in kinds
    # recorded with an empty compilation cache, so the prefill program
    # (no kernel, hence the cache key of its scope-less twin) has them too
    prefill = scopes.time_by(
        scopes.leaves_within(lines["XLA Ops"], lines["XLA Modules"],
                             r"prefill_rows_paged", window), per,
        lambda n, op: scopes.scope_of(op))
    assert {"cached_attention", "kv_gather", "kv_write", "mlp"} <= set(prefill)
    moved = prefill[None] + prefill["kv_gather"] + prefill["kv_write"]
    assert 0.2 < moved / sum(prefill.values()) < 0.4
    host = {n for n, _, _ in trace.host}
    assert {"eng.device_wait", "eng.emit", "eng.host_drain"} <= host

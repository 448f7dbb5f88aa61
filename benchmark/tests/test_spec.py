"""The loader: every committed cell loads, and a cell that names
something missing, or a name or unit outside the allowed letters, is
refused."""

import copy
import json
import os
import shutil

import pytest

from benchmark.harness import spec


@pytest.fixture()
def bench():
    return spec.load_benchmark()


def _root(tmp_path, bench):
    """A root holding this BENCHMARK.json and the configuration files."""
    for c in spec.load_benchmark()["configs"]:
        dst = tmp_path / c["file"]
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(spec.ROOT, c["file"]), dst)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_every_committed_cell_loads(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert callable(cell.generator.generate)
        assert callable(cell.driver.run_cell)
        for m in cell.per_layer:       # reader's header agrees with the entry
            mod = spec.load_module("layer_metrics", m.name)
            assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == \
                (m.layer, m.unit, m.source, m.moves)


def test_contract_shape_of_benchmark_json(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536


def _break(bench, how):
    b = copy.deepcopy(bench)
    how(b)
    return b


CASES = {
    "missing configuration": lambda b: b["workloads"][0].update(
        config="no-such-config"),
    "missing configuration file": lambda b: b["configs"][0].update(
        file="benchmark/configs/none.json"),
    "missing traffic file": lambda b: b["workloads"][0].update(
        traffic="no-such-mix"),
    "missing per-layer reader": lambda b: b["per_layer"].append(
        {"name": "no_reader_pct", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "kernels",
         "moves": "ttft_p95_ms", "workloads": ["mistral7b-chat"]}),
    "moves an unknown metric": lambda b: b["per_layer"][0].update(
        moves="latency_of_nothing"),
    "moves a metric the cell lacks": lambda b: b["per_layer"][0].update(
        moves="train_tokens_per_s"),
    "name with a space": lambda b: b["end_to_end"][0].update(
        name="ttft p95"),
    "name with a slash": lambda b: b["per_layer"][0].update(
        name="queue/wait"),
    "unit with a space": lambda b: b["end_to_end"][0].update(
        unit="tokens per s"),
    "greek unit": lambda b: b["end_to_end"][0].update(unit="μs"),
    "bad source": lambda b: b["per_layer"][0].update(source="guess"),
    "program source end to end": lambda b: b["end_to_end"][0].update(
        source="program_counter"),
    "bad better": lambda b: b["end_to_end"][0].update(better="faster"),
    "no setup_s": lambda b: b.update(end_to_end=[
        m for m in b["end_to_end"] if m["name"] != "setup_s"]),
    "three chips": lambda b: b["workloads"][0].update(chips=3),
    "metric lists unknown cell": lambda b: b["end_to_end"][0].update(
        workloads=["mistral7b-chat", "nowhere"]),
    "duplicate metric": lambda b: b["per_layer"].append(
        dict(b["per_layer"][0])),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_loader_refuses(case, bench, tmp_path):
    root = _root(tmp_path, _break(bench, CASES[case]))
    with pytest.raises(spec.SpecError):
        spec.load_cell("mistral7b-chat", root=root)


def test_unknown_workload_and_generator(bench, tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.load_module("traffic", "no_such_generator")
    with pytest.raises(spec.SpecError):
        spec.load_module("harness/drivers", "../run")


def test_run_refuses_cpu_and_bare_directory(tmp_path):
    """No metric from a CPU: the measuring path exits non-zero and prints
    no result line; so does a directory with only the benchmark's files."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "mistral7b-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"metrics"' not in r.stdout and "needs a TPU" in r.stderr
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "mistral7b-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and '"metrics"' not in r.stdout

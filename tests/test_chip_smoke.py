"""CPU rehearsal of `chip_smoke.py` and of what it leans on in the runtime.

No chip here, so nothing below says the smoke passes: these hold its
control flow (a dead or silent phase fails the run, the last line is the
contract's), run each phase's function at nano size with the device
check patched, and pin the runtime's one-process-per-chip rules and the
compile cache's placement.
"""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

import chip_smoke
from ray_tpu.models import LlamaConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _stub(code: str) -> list:
    return [sys.executable, "-c", code]


def _reporting(name, **fields) -> list:
    report = {"phase": name, "pass": True, "device": TPU, **fields}
    return _stub(f"print('progress line'); print({json.dumps(report)!r})")


# ---- the parent's child-runner ----------------------------------------------

@pytest.mark.parametrize("stub", [
    _stub("import sys; print('dying'); sys.exit(3)"),
    _stub("print('no report, just chatter')"),
    _stub(""),
    _reporting("other_phase"),
    _reporting("b", **{"pass": False}),
    _reporting("b", device={"platform": "cpu", "kind": "cpu", "count": 1}),
    _reporting("b", device=dict(TPU, count=4)),
    _stub("import time; time.sleep(60)"),
], ids=["exit3", "no_report", "silent", "wrong_phase", "not_ok",
        "cpu_device", "other_device", "hang"])
def test_a_failed_phase_fails_the_run(stub, capsys):
    rc = chip_smoke.run_phases([("a", _reporting("a")), ("b", stub),
                                ("c", _reporting("c"))], timeout_s=1.5)
    out = capsys.readouterr()
    assert rc != 0
    assert '"ok"' not in out.out
    assert not any(json.loads(ln).get("phase") == "c"
                   for ln in out.out.splitlines() if ln.startswith("{"))
    assert "FAILED" in out.err and "phase b" in out.err


def test_last_line_is_the_contract(capsys):
    rc = chip_smoke.run_phases(
        [(n, _reporting(n, seconds=1.5)) for n in ("a", "b")])
    last = capsys.readouterr().out.splitlines()[-1]
    assert rc == 0
    verdict = json.loads(last)
    assert verdict == {"ok": True, "device": TPU}
    assert list(verdict) == ["ok", "device"]
    assert list(verdict["device"]) == ["platform", "kind", "count"]


def test_a_hung_phase_leaves_no_process_behind():
    import psutil

    grandchild = ("import subprocess, sys, time; "
                  "subprocess.Popen([sys.executable, '-c', "
                  "'import time; time.sleep(60)'], start_new_session=True); "
                  "time.sleep(60)")
    before = {p.pid for p in psutil.Process().children(recursive=True)}
    assert chip_smoke.run_phases([("a", _stub(grandchild))],
                                 timeout_s=2) != 0
    after = {p.pid for p in psutil.Process().children(recursive=True)}
    assert after <= before


def test_real_command_refuses_the_cpu():
    """`python chip_smoke.py` where JAX finds no accelerator: non-zero,
    and no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_children_refuse_anything_but_a_tpu():
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke._require_tpu()


# ---- each phase's function, nano size, device check patched -----------------

@pytest.fixture
def fake_tpu(monkeypatch):
    from jax._src import monitoring

    before = (monitoring.get_event_listeners(),
              monitoring.get_event_duration_listeners())
    yield from _fake_tpu(monkeypatch)
    # a phase's compile meter listens for the life of its process; here
    # that process is pytest's
    for fn in monitoring.get_event_listeners():
        if fn not in before[0]:
            monitoring.unregister_event_listener(fn)
    for fn in monitoring.get_event_duration_listeners():
        if fn not in before[1]:
            monitoring.unregister_event_duration_listener(fn)


def _fake_tpu(monkeypatch):
    monkeypatch.setattr(
        chip_smoke, "_require_tpu",
        lambda min_count=1: dict(TPU, count=min_count))
    # the CPU backend keeps no memory statistics: count live shards
    monkeypatch.setattr(
        chip_smoke, "_bytes_per_device",
        lambda devices: [sum(s.data.nbytes for a in jax.live_arrays()
                             for s in a.addressable_shards
                             if s.device == d) for d in devices])
    yield


SERVE_NANO = dict(prompt_lens=(70, 5), new_tokens=9, slots=2, chunk=32)


def test_phase_serve_nano(fake_tpu, capsys):
    report = chip_smoke.phase_serve(
        3, LlamaConfig.nano(max_seq_len=256), kv_block_tokens=8,
        kv_pool_bytes=1 << 20, **SERVE_NANO)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    variants = {ln["variant"]: ln for ln in lines if "variant" in ln}
    assert list(variants) == ["solo_generate", "paged", "paged_int8",
                              "fleet_2x_paged"]
    # f32 on the CPU: the repo's identity holds exactly, off the kernel
    assert report["pass"] and report["identity_vs_solo"]
    assert report["paged_impl"] == "reference"
    for name in ("paged", "fleet_2x_paged"):
        assert variants[name]["identical_to_reference"]
        assert variants[name]["greedy_margin_max"] == 0.0
    int8 = variants["paged_int8"]
    assert int8["logit_atol"] > variants["paged"]["logit_atol"]
    assert 0.0 < int8["logit_max_abs_diff"] <= int8["logit_atol"]
    assert int8["greedy_margin_max"] <= int8["greedy_margin_allowed"]
    assert all(n > 0 for n in variants["fleet_2x_paged"]["routed"])
    assert report["device"] == TPU


def test_phase_serve_fails_on_an_impossible_pool(fake_tpu):
    with pytest.raises(ValueError, match="kv_pool_bytes"):
        chip_smoke.phase_serve(
            3, LlamaConfig.nano(max_seq_len=256), kv_block_tokens=8,
            kv_pool_bytes=1, **SERVE_NANO)


def test_phase_train_nano(fake_tpu):
    report = chip_smoke.phase_train(
        3, LlamaConfig.nano(max_seq_len=64), batch=4, seq=32)
    losses = report["loss"]
    assert losses == sorted(losses, reverse=True) and len(losses) == 3
    # no Mosaic kernel lowers on the CPU, so the phase cannot pass here
    assert report["flash_kernel_in_step"] is False
    assert report["pass"] is False


def test_phase_serve_tp4_nano(fake_tpu, capsys):
    report = chip_smoke.phase_serve_tp4(
        3, LlamaConfig.nano(max_seq_len=256, n_kv_heads=4),
        kv_block_tokens=8, kv_pool_bytes=1 << 20, **SERVE_NANO)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    tp = next(ln for ln in lines if ln.get("variant") == "paged_tp4")
    assert tp["spans_all_devices"] and tp["identical_to_reference"]
    assert tp["impl"] == "reference" and tp["greedy_margin_max"] == 0.0
    assert len(tp["device_bytes_share"]) == 4
    assert report["device"]["count"] == 4


def test_phase_train_mesh4_nano(fake_tpu, capsys):
    chip_smoke.phase_train_mesh4(
        3, LlamaConfig.nano(max_seq_len=64), batch=4, seq=32)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    mesh = next(ln for ln in lines
                if ln.get("variant") == "train_fsdp2xtp2")
    assert mesh["param_devices"] == [4]
    assert mesh["loss_max_abs_diff"] <= mesh["loss_atol"]


# ---- D: one process per chip ------------------------------------------------

def _worker_env(tpu: bool) -> dict:
    from ray_tpu._private.raylet import Raylet
    from ray_tpu.core.ids import NodeID, WorkerID

    raylet = types.SimpleNamespace(
        address="a:1", gcs_address="g:1", node_id=NodeID.from_random(),
        store_path="/dev/shm/x", session_dir="/tmp/x",
        _pkg_pythonpath=Raylet._pkg_pythonpath)
    return Raylet._worker_env(raylet, WorkerID.from_random(), tpu)


def test_only_a_tpu_worker_may_see_the_chip():
    assert _worker_env(tpu=False)["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in _worker_env(tpu=True)


def test_tpu_worker_gets_the_compile_cache(monkeypatch):
    from ray_tpu.util.compile_cache import default_compile_cache_dir

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # a node held to the CPU (this test process) has no chip programs
    assert "JAX_COMPILATION_CACHE_DIR" not in _worker_env(tpu=True)
    monkeypatch.delenv("JAX_PLATFORMS")
    assert default_compile_cache_dir() is not None
    assert _worker_env(tpu=True)["JAX_COMPILATION_CACHE_DIR"] == \
        default_compile_cache_dir()
    assert "JAX_COMPILATION_CACHE_DIR" not in _worker_env(tpu=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert "JAX_COMPILATION_CACHE_DIR" not in _worker_env(tpu=True)


def test_chip_count_comes_from_device_nodes(monkeypatch):
    from ray_tpu._private import accelerators

    monkeypatch.delenv("RAY_TPU_NUM_TPUS", raising=False)
    # what a one-chip v5e machine really shows: the host's topology in
    # the environment, one VFIO group under /dev
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setattr(accelerators.glob, "glob", lambda pat: [])
    monkeypatch.setattr(accelerators.os, "listdir",
                        lambda path: ["1", "vfio"])
    assert accelerators.detect_tpu_chips() == 1
    monkeypatch.setattr(accelerators.glob, "glob",
                        lambda pat: ["/dev/accel0", "/dev/accel1"])
    assert accelerators.detect_tpu_chips() == 2
    monkeypatch.setenv("RAY_TPU_NUM_TPUS", "4")
    assert accelerators.detect_tpu_chips() == 4


def test_chip_count_raises_when_the_probe_fails(monkeypatch):
    from ray_tpu._private import accelerators

    def denied(path):
        raise PermissionError(path)

    monkeypatch.delenv("RAY_TPU_NUM_TPUS", raising=False)
    monkeypatch.setattr(accelerators.glob, "glob", lambda pat: [])
    monkeypatch.setattr(accelerators.os, "listdir", denied)
    with pytest.raises(PermissionError):
        accelerators.detect_tpu_chips()
    monkeypatch.setenv("RAY_TPU_NUM_TPUS", "four")
    with pytest.raises(ValueError):
        accelerators.detect_tpu_chips()


def test_no_chip_is_zero_not_an_error(monkeypatch):
    from ray_tpu._private import accelerators

    def missing(path):
        raise FileNotFoundError(path)

    monkeypatch.delenv("RAY_TPU_NUM_TPUS", raising=False)
    monkeypatch.setattr(accelerators.glob, "glob", lambda pat: [])
    monkeypatch.setattr(accelerators.os, "listdir", missing)
    assert accelerators.detect_tpu_chips() == 0


def test_cpu_work_never_lands_on_an_idle_tpu_worker():
    from ray_tpu._private.raylet import Raylet, WorkerHandle
    from ray_tpu.core.ids import WorkerID

    def idle(tpu):
        w = WorkerHandle(WorkerID.from_random(), None)
        w.state, w.tpu = "idle", tpu
        return w

    tpu_worker, cpu_worker = idle(True), idle(False)
    raylet = types.SimpleNamespace(idle_workers=[tpu_worker])
    assert Raylet._take_idle_worker(raylet, tpu=False) is None
    assert raylet.idle_workers == [tpu_worker]
    assert Raylet._take_idle_worker(raylet, tpu=True) is tpu_worker
    raylet.idle_workers = [cpu_worker]
    assert Raylet._take_idle_worker(raylet, tpu=True) is None


# ---- F: the compile cache is placed from outside ----------------------------

def test_cache_dir_from_the_environment_is_left_alone(monkeypatch):
    from ray_tpu.util import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.default_compile_cache_dir() is None
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    from ray_tpu.util import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # held to the CPU, as every test is: no cache at all
    assert compile_cache.default_compile_cache_dir() is None
    assert compile_cache.enable_compile_cache() is None
    monkeypatch.delenv("JAX_PLATFORMS")
    here = compile_cache.default_compile_cache_dir()
    assert here == os.path.join(REPO, ".jax_cache")
    assert compile_cache.default_compile_cache_dir() == here
    # the children import jax and set its config; they start no backend
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
    code = ("from ray_tpu.util.compile_cache import enable_compile_cache;"
            "import jax; print(enable_compile_cache());"
            "print(jax.config.jax_compilation_cache_dir)")
    other = [subprocess.run([sys.executable, "-c", code], cwd=cwd, env={
        **env, "PYTHONPATH": REPO}, capture_output=True, text=True,
        timeout=120).stdout.split() for cwd in (REPO, "/tmp")]
    assert other == [[here, here], [here, here]]


# ---- H: a stale native build is never loaded --------------------------------

def test_native_artifact_name_follows_its_sources(monkeypatch, tmp_path):
    from ray_tpu._native import build

    src = tmp_path / "unit.cpp"
    src.write_text("extern \"C\" int answer() { return 41; }\n")
    monkeypatch.setattr(build, "_DIR", str(tmp_path))
    monkeypatch.setattr(build, "_BUILD_DIR", str(tmp_path / "build"))
    monkeypatch.setitem(build._LIBS, "unit", ["unit.cpp"])
    first = build.ensure_built("unit")
    assert build.ensure_built("unit") == first
    assert build.load_lib("unit").answer() == 41
    # newer source, OLDER mtime than the artifact: a copied tree
    src.write_text("extern \"C\" int answer() { return 42; }\n")
    os.utime(src, (1, 1))
    second = build.ensure_built("unit")
    assert second != first and not os.path.exists(first)
    assert build.load_lib("unit").answer() == 42

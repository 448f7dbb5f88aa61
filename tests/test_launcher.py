"""Cluster launcher (`ray_tpu up/down/exec`) — reference:
python/ray/autoscaler/_private/commands.py + command_runner.py. The
local provider brings a REAL head up on this host through the same
sync-files → setup → detached-start path SSH targets use."""

import json
import os
import subprocess
import sys
import time

import pytest

from ray_tpu.autoscaler.launcher import (ClusterConfig,
                                         LocalCommandRunner,
                                         SSHCommandRunner,
                                         create_or_update_cluster,
                                         exec_on_cluster,
                                         teardown_cluster)


def test_cluster_config_load_and_validate(tmp_path):
    cfg = tmp_path / "c.yaml"
    cfg.write_text(
        "cluster_name: demo\n"
        "provider:\n  type: local\n  head_ip: 127.0.0.1\n"
        "setup_commands:\n  - echo hi\n")
    c = ClusterConfig.load(str(cfg))
    assert c.cluster_name == "demo"
    assert c.setup_commands == ["echo hi"]

    bad = tmp_path / "bad.yaml"
    bad.write_text("cluster_name: x\nnot_a_key: 1\n")
    with pytest.raises(ValueError, match="not_a_key"):
        ClusterConfig.load(str(bad))


def test_local_command_runner(tmp_path):
    r = LocalCommandRunner()
    assert r.run("echo -n out") == "out"
    with pytest.raises(RuntimeError, match="failed"):
        r.run("exit 3")
    src = tmp_path / "src.txt"
    src.write_text("data")
    dst = tmp_path / "sub" / "dst.txt"
    r.sync_files({str(dst): str(src)})
    assert dst.read_text() == "data"


def test_ssh_runner_argv():
    r = SSHCommandRunner("10.0.0.5", {"ssh_user": "tpu",
                                      "ssh_private_key": "~/.ssh/k"})
    base = r._ssh_base()
    assert base[0] == "ssh" and base[-1] == "tpu@10.0.0.5"
    assert "-i" in base


def test_up_exec_down_local(tmp_path):
    """End-to-end on the local provider: up brings a real head onto this
    host (detached `ray_tpu start --head`), exec runs against it, down
    stops it."""
    marker = tmp_path / "setup_ran"
    cfg = tmp_path / "cluster.yaml"
    pyexe = sys.executable
    cfg.write_text(f"""
cluster_name: launcher_test
provider:
  type: local
  head_ip: 127.0.0.1
setup_commands:
  - touch {marker}
head_start_command: >-
  {pyexe} -m ray_tpu.scripts start --head --dashboard-port=0
stop_command: "{pyexe} -m ray_tpu.scripts stop"
""")
    # Clean any leftover head/state from prior runs on this host.
    subprocess.run(["pkill", "-f", "ray_tpu[.]scripts start --head"],
                   capture_output=True)
    for leftover in ("/tmp/ray_tpu/cluster_address",
                     os.path.expanduser(
                         "~/.ray_tpu/cluster-launcher_test.json")):
        if os.path.exists(leftover):
            os.remove(leftover)
    time.sleep(0.5)
    try:
        state = create_or_update_cluster(str(cfg))
        assert marker.exists()  # setup commands ran
        assert ":" in state["head_address"]
        # exec against the live head: status goes through the GCS.
        out = exec_on_cluster(
            str(cfg), f"{pyexe} -m ray_tpu.scripts status")
        assert "node" in out.lower() or "cpu" in out.lower(), out
    finally:
        try:
            teardown_cluster(str(cfg))
        except Exception:
            subprocess.run([pyexe, "-m", "ray_tpu.scripts", "stop"],
                           capture_output=True)
    # Head is gone: the address file was removed by stop.
    assert not os.path.exists("/tmp/ray_tpu/cluster_address")


def test_node_updater_retry_and_replace(tmp_path):
    """Updater state machine (reference: updater.py NodeUpdater): a node
    whose setup fails is REPLACED (fresh runner) and retried; phases and
    attempts are recorded."""
    from ray_tpu.autoscaler.updater import (FAILED, RUNNING, NodeUpdater)

    flip = tmp_path / "flip"
    replaced = []

    def replace():
        replaced.append(1)
        return LocalCommandRunner()

    upd = NodeUpdater(
        ip="127.0.0.1", runner=LocalCommandRunner(),
        file_mounts={},
        # Fails on the first invocation only.
        setup_commands=[f"test -f {flip} || {{ touch {flip}; false; }}"],
        start_command="true", tag="t", max_update_retries=2,
        retry_backoff_s=0.01, replace_node=replace)
    assert upd.update() == RUNNING
    assert upd.attempts == 2
    assert replaced == [1]
    assert "setting-up" in upd.phase_times
    assert upd.summary()["status"] == RUNNING

    # Exhausted retries -> FAILED with the error recorded.
    upd2 = NodeUpdater(
        ip="127.0.0.1", runner=LocalCommandRunner(), file_mounts={},
        setup_commands=["false"], start_command="true", tag="t2",
        max_update_retries=1, retry_backoff_s=0.01)
    assert upd2.update() == FAILED
    assert "setting-up" in upd2.error


def test_docker_runner_command_shapes():
    """DockerCommandRunner (reference: command_runner.py): commands exec
    inside the container; the container is created once on demand."""
    from ray_tpu.autoscaler.updater import DockerCommandRunner

    calls = []

    class FakeBase(LocalCommandRunner):
        def run(self, cmd, timeout=600.0):
            calls.append(cmd)
            if "docker inspect" in cmd:
                return "absent\n"
            return ""

        def sync_files(self, mounts):
            calls.append(("sync", dict(mounts)))

    d = DockerCommandRunner(FakeBase(), {"image": "python:3.12",
                                         "run_options": ["--network=host"]},
                            tag="t")
    d.run("echo hi")
    assert any("docker run -d --name" in c and "--network=host" in c
               for c in calls if isinstance(c, str))
    assert any(c.startswith("docker exec") and "echo hi" in c
               for c in calls if isinstance(c, str))
    n_runs = sum(1 for c in calls
                 if isinstance(c, str) and "docker run -d" in c)
    d.run("echo again")  # container ensured only once
    assert sum(1 for c in calls
               if isinstance(c, str) and "docker run -d" in c) == n_runs
    d.sync_files({"/app": "/src"})
    assert ("sync", {"/app": "/src"}) in calls
    assert any("docker cp" in c for c in calls if isinstance(c, str))


def test_up_converges_after_partial_failure(tmp_path):
    """`up` with a worker whose setup fails once: the updater retries
    with a fresh runner and the cluster converges (worker present,
    attempts recorded) — reference: updater retry + replacement."""
    pyexe = sys.executable
    count = tmp_path / "count"
    # Invocation-counted setup: head's run (1) passes, the worker's
    # first attempt (2) fails, the retry (3) passes.
    setup = (f"n=$(cat {count} 2>/dev/null || echo 0); "
             f"n=$((n+1)); echo $n > {count}; test $n -ne 2")
    cfg = tmp_path / "cluster.yaml"
    cfg.write_text(f"""
cluster_name: launcher_partial
provider:
  type: local
  head_ip: 127.0.0.1
  worker_ips: ["127.0.0.1"]
setup_commands:
  - "{setup}"
head_start_command: >-
  {pyexe} -m ray_tpu.scripts start --head --dashboard-port=0
worker_start_command: "true"
stop_command: "{pyexe} -m ray_tpu.scripts stop"
update_retries: 2
""")
    subprocess.run(["pkill", "-f", "ray_tpu[.]scripts start --head"],
                   capture_output=True)
    for leftover in ("/tmp/ray_tpu/cluster_address",
                     os.path.expanduser(
                         "~/.ray_tpu/cluster-launcher_partial.json")):
        if os.path.exists(leftover):
            os.remove(leftover)
    # Wait for any pkill'd head to actually EXIT (under full-suite load
    # SIGTERM handling can take seconds; a lingering process makes `up`
    # conclude a foreign head is running and raise).
    for _ in range(40):
        probe = subprocess.run(
            ["pgrep", "-f", "ray_tpu[.]scripts start --head"],
            capture_output=True)
        if probe.returncode != 0:
            break
        time.sleep(0.5)
    if os.path.exists("/tmp/ray_tpu/cluster_address"):
        os.remove("/tmp/ray_tpu/cluster_address")
    try:
        state = create_or_update_cluster(str(cfg))
        assert state["workers"] == ["127.0.0.1"]
        upd = state["node_updates"][0]
        assert upd["status"] == "up-to-date"
        assert upd["attempts"] == 2  # failed once, replaced, converged
    finally:
        try:
            teardown_cluster(str(cfg))
        except Exception:
            subprocess.run([pyexe, "-m", "ray_tpu.scripts", "stop"],
                           capture_output=True)

"""Node — process orchestration for cluster bring-up.

Equivalent of the reference's Node + services (python/ray/_private/node.py:37,
services.py:1439,1504): creates the session directory, sizes and creates the
shm object store, and spawns the GCS server (head only) and the raylet as
separate processes, reading their bound ports off stdout.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from typing import Dict, Optional

from ray_tpu.core.config import Config
from ray_tpu.core.ids import NodeID
from ray_tpu.core.shm_client import ShmClient


def default_resources() -> Dict[str, float]:
    from ray_tpu._private.accelerators import detect_tpu_chips

    res: Dict[str, float] = {"CPU": float(os.cpu_count() or 1)}
    chips = detect_tpu_chips()
    if chips:
        res["TPU"] = float(chips)
    return res


def auto_store_bytes(config: Config) -> int:
    if config.object_store_memory:
        return config.object_store_memory
    try:
        free = shutil.disk_usage("/dev/shm").free
    except OSError:
        free = 1 << 30
    return int(min(free * config.object_store_auto_fraction,
                   config.object_store_max_auto_bytes))


def _read_json_line(proc: subprocess.Popen, timeout: float,
                    what: str) -> dict:
    deadline = time.monotonic() + timeout
    line = ""
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"{what} exited with code {proc.returncode} before "
                f"announcing its port")
        line = proc.stdout.readline().decode()
        if line.strip():
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue  # stray log line on stdout
    raise TimeoutError(f"{what} did not announce its port (last: {line!r})")


class ProcessHandle:
    def __init__(self, proc: subprocess.Popen, name: str):
        self.proc = proc
        self.name = name

    def alive(self) -> bool:
        return self.proc.poll() is None

    def terminate(self) -> None:
        if self.alive():
            self.proc.terminate()
            try:
                self.proc.wait(timeout=3)
            except subprocess.TimeoutExpired:
                self.proc.kill()


class Node:
    """Starts (head) or joins a ray_tpu cluster on this machine."""

    def __init__(self, config: Config,
                 resources: Optional[Dict[str, float]] = None,
                 gcs_address: Optional[str] = None,
                 session_dir: Optional[str] = None,
                 labels: Optional[Dict[str, str]] = None,
                 slice_id: str = "",
                 node_name: str = "node"):
        self.config = config
        self.is_head = gcs_address is None
        self.gcs_address = gcs_address
        self.resources = resources or default_resources()
        self.labels = labels or {}
        self.slice_id = slice_id
        self.node_id = NodeID.from_random()
        self.processes: list[ProcessHandle] = []
        if session_dir is None:
            session_dir = os.path.join(
                self.config.temp_dir,
                f"session_{int(time.time() * 1000)}_{os.getpid()}")
        self.session_dir = session_dir
        os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)
        self.store_path = f"/dev/shm/ray_tpu_{self.node_id.hex()[:12]}"
        self.raylet_address: Optional[str] = None

    def start(self) -> None:
        store_bytes = auto_store_bytes(self.config)
        ShmClient.create_store(self.store_path, store_bytes)
        if self.is_head:
            self._start_gcs()
        self._start_raylet()

    def _spawn(self, args: list, name: str) -> subprocess.Popen:
        log = open(os.path.join(self.session_dir, "logs", f"{name}.err"), "ab")
        import ray_tpu

        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(ray_tpu.__file__)))
        env = {**os.environ, "RAY_TPU_CONFIG_JSON": self.config.to_json()}
        env["PYTHONPATH"] = pkg_root + (
            ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        # Ship the driver's sys.path so workers can unpickle functions
        # defined in driver-side modules (reference: JobConfig
        # py_driver_sys_path propagated to default_worker.py).
        env.setdefault("RAY_TPU_DRIVER_SYS_PATH",
                       ":".join(p for p in sys.path if p))
        proc = subprocess.Popen(
            [sys.executable, "-u", "-m"] + args,
            stdout=subprocess.PIPE, stderr=log, start_new_session=True,
            env=env)
        log.close()
        self.processes.append(ProcessHandle(proc, name))
        return proc

    def _start_gcs(self, port: int = 0) -> None:
        persist = os.path.join(self.session_dir, "gcs_tables.sqlite")
        proc = self._spawn(["ray_tpu._private.gcs_server",
                            "--config", self.config.to_json(),
                            "--port", str(port),
                            "--persist-path", persist], "gcs")
        info = _read_json_line(proc, 30, "gcs_server")
        self.gcs_address = f"127.0.0.1:{info['port']}"
        self._gcs_proc = proc

    def restart_gcs(self) -> None:
        """Restart a dead GCS on the SAME port: state comes back from the
        write-through table storage, raylets and workers re-register over
        their reconnect paths (reference: GCS fault tolerance via Redis
        persistence + HandleNotifyGCSRestart)."""
        port = int(self.gcs_address.rsplit(":", 1)[1])
        self.processes = [p for p in self.processes
                          if p.proc is not getattr(self, "_gcs_proc", None)]
        self._start_gcs(port=port)

    def kill_gcs(self) -> None:
        """Kill the GCS process (fault-injection hook for tests)."""
        proc = getattr(self, "_gcs_proc", None)
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=5)

    def _start_raylet(self) -> None:
        proc = self._spawn([
            "ray_tpu._private.raylet",
            "--gcs-address", self.gcs_address,
            "--store-path", self.store_path,
            "--resources", json.dumps(self.resources),
            "--session-dir", self.session_dir,
            "--node-id", self.node_id.hex(),
            "--labels", json.dumps(self.labels),
            "--slice-id", self.slice_id,
            "--config", self.config.to_json(),
        ], f"raylet-{self.node_id.hex()[:8]}")
        info = _read_json_line(proc, 30, "raylet")
        self.raylet_address = f"127.0.0.1:{info['port']}"

    def kill_raylet(self) -> None:
        """Test/chaos hook: kill this node's raylet process."""
        for p in self.processes:
            if p.name.startswith("raylet"):
                p.terminate()

    def shutdown(self) -> None:
        for p in reversed(self.processes):
            p.terminate()
        self.processes.clear()
        try:
            os.unlink(self.store_path)
        except OSError:
            pass

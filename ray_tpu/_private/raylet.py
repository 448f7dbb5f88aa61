"""Raylet — per-node daemon: worker pool, local scheduler, object manager.

Equivalent of the reference's raylet (src/ray/raylet/node_manager.h:119):
- WorkerPool with prestart and dedicated actor workers
  (src/ray/raylet/worker_pool.h:159,:425).
- Local task manager: worker-lease queue + resource accounting + spillback
  to other raylets (src/ray/raylet/scheduling/cluster_task_manager.cc:44,
  local_task_manager.cc); hybrid policy — pack until the critical-resource
  utilization threshold, then spread.
- Placement-group bundle bookkeeping with 2PC prepare/commit
  (src/ray/raylet/placement_group_resource_manager.h).
- Object manager: cross-node chunked pull/push riding the RPC plane
  (src/ray/object_manager/object_manager.cc, pull_manager.cc), spilling to
  local disk with GCS-recorded URLs (src/ray/raylet/local_object_manager.h).

TPU-native: the node registers its slice identity (slice_id/topology) so the
GCS can gang-schedule SLICE placement groups; TPU chips are normal resources
("TPU": chips) with visibility plumbed to workers via env vars.
"""

from __future__ import annotations

import asyncio
import logging
import os
import signal
import struct
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Set, Tuple

from ray_tpu.core import rpc
from ray_tpu.core.config import Config
from ray_tpu.core.ids import NodeID, ObjectID, WorkerID
from ray_tpu.core.shm_client import ShmClient, StoreFullError

logger = logging.getLogger(__name__)

CHUNK = 4 << 20


class WorkerHandle:
    def __init__(self, worker_id: WorkerID, pid: int, proc=None):
        self.worker_id = worker_id
        self.pid = pid
        self.proc = proc
        self.address: str = ""
        self.fast_address: str = ""  # fastlane (native task path) port
        self.conn: Optional[rpc.Connection] = None
        self.registered = asyncio.Event()
        self.state = "starting"  # starting|idle|leased|actor|dead
        self.lease_id: Optional[bytes] = None
        self.actor_id: Optional[bytes] = None
        self.job_id: Optional[bytes] = None
        self.log_path: Optional[str] = None
        self.log_offset: int = 0
        self.log_partial: bytes = b""
        self.tpu = False  # spawned for TPU work (no JAX_PLATFORMS=cpu)
        self.kill_requested = False  # kill arrived before spawn landed
        self.forked = False  # forkserver child (tracked by pid, not proc)

    def alive(self) -> bool:
        if self.proc is not None:
            return self.proc.poll() is None
        if self.forked and self.pid:
            try:
                os.kill(self.pid, 0)
                return True
            except OSError:
                return False
        return True  # spawn still in flight / driver: liveness via conn

    def terminate(self) -> None:
        if self.proc is not None:
            if self.proc.poll() is None:
                self.proc.terminate()
        elif self.forked and self.pid:
            try:
                os.kill(self.pid, signal.SIGTERM)
            except OSError:
                pass


class LeaseRequest:
    def __init__(self, data: dict):
        self.lease_id: bytes = data["lease_id"]
        self.resources: Dict[str, float] = data.get("resources", {})
        self.pg_id: Optional[bytes] = data.get("pg_id")
        self.pg_bundle: int = data.get("pg_bundle", -1)
        self.job_id: Optional[bytes] = data.get("job_id")
        self.grant_fut: asyncio.Future = asyncio.get_event_loop().create_future()
        self.num_spillbacks: int = data.get("num_spillbacks", 0)


class Raylet:
    def __init__(self, node_id: NodeID, gcs_address: str, store_path: str,
                 resources: Dict[str, float], config: Config,
                 session_dir: str, labels: Optional[Dict[str, str]] = None,
                 slice_id: str = ""):
        self.node_id = node_id
        self.gcs_address = gcs_address
        self.store_path = store_path
        self.resources_total = dict(resources)
        self.available = dict(resources)
        self.config = config
        self.session_dir = session_dir
        self.labels = labels or {}
        self.slice_id = slice_id

        self.workers: Dict[WorkerID, WorkerHandle] = {}
        self.idle_workers: List[WorkerHandle] = []
        self.lease_queue: List[LeaseRequest] = []
        self.leases: Dict[bytes, Tuple[WorkerHandle, Dict[str, float],
                                       Optional[Tuple[bytes, int]]]] = {}
        # (pg_id, bundle_index) -> {"reserved": res, "available": res, "committed": bool}
        self.bundles: Dict[Tuple[bytes, int], dict] = {}
        self.cluster_view: List[dict] = []
        self.gcs: Optional[rpc.Connection] = None
        self.store: Optional[ShmClient] = None
        self._server: Optional[rpc.Server] = None
        self._bg: List[asyncio.Task] = []
        self._spilled_local: Dict[bytes, str] = {}
        self._spill_backend = None
        self._pulls_inflight: Dict[bytes, asyncio.Future] = {}
        self._spawn_tasks: Set[asyncio.Task] = set()
        self.address = ""
        self.dead = False
        # Forkserver (zygote) worker factory: one warm template process;
        # CPU workers fork from it in ~10ms instead of a fresh
        # interpreter + import chain (reference: worker_pool.h:359,:425).
        self._forkserver: Optional[subprocess.Popen] = None
        self._fork_lock = threading.Lock()  # serializes the pipe protocol

    # ------------------------------------------------------------- lifecycle
    async def start(self, host: str = "127.0.0.1", port: int = 0) -> int:
        self.store = ShmClient(self.store_path)
        # Background arena pre-population: first-touch tmpfs page faults
        # move off the first puts' critical path.
        self.store.prefault()
        self._server = rpc.Server(self, host, port)
        port = await self._server.start()
        self.address = f"{host}:{port}"
        ghost, gport = self.gcs_address.rsplit(":", 1)
        self.gcs = await rpc.connect(ghost, int(gport),
                                     handler=self._on_gcs_message,
                                     name="raylet->gcs")
        self.gcs.on_close = self._on_gcs_close
        # Native object-transfer server: bulk object bytes move
        # store-to-store over raw TCP (C++ threads), Python only
        # coordinates (reference: ObjectManager's dedicated rpc service).
        try:
            from ray_tpu.core.transfer_client import TransferServer

            self.transfer_server = TransferServer(self.store_path)
            transfer_port = self.transfer_server.port
        except Exception:
            logger.exception("native transfer server failed to start; "
                             "falling back to rpc chunk transfer")
            self.transfer_server = None
            transfer_port = 0
        self._transfer_port = transfer_port
        await self._register_with_gcs(self.gcs)
        self._bg.append(asyncio.get_event_loop().create_task(self._heartbeat_loop()))
        self._bg.append(asyncio.get_event_loop().create_task(self._reap_loop()))
        self._bg.append(asyncio.get_event_loop().create_task(
            self._log_monitor_loop()))
        self._bg.append(asyncio.get_event_loop().create_task(self._spill_loop()))
        self._bg.append(asyncio.get_event_loop().create_task(
            self._reporter_loop()))
        self._bg.append(asyncio.get_event_loop().create_task(self._drain_loop()))
        if self.config.memory_monitor_refresh_ms > 0:
            self._bg.append(asyncio.get_event_loop().create_task(
                self._memory_monitor_loop()))
        logger.info("raylet %s on %s resources=%s",
                    self.node_id.hex()[:8], self.address, self.resources_total)
        self._maybe_refill_pool()  # prestart the standing worker pool
        return port

    async def close(self) -> None:
        self.dead = True
        for t in self._bg:
            t.cancel()
        if self._spawn_tasks:
            # Let in-flight spawns land so their processes get a proc
            # handle (finish_spawn terminates them when self.dead).
            await asyncio.gather(*list(self._spawn_tasks),
                                 return_exceptions=True)
        for w in self.workers.values():
            w.terminate()
        if self._forkserver is not None and self._forkserver.poll() is None:
            self._forkserver.terminate()
        if getattr(self, "transfer_server", None) is not None:
            await asyncio.get_event_loop().run_in_executor(
                None, self.transfer_server.stop)
        if self._server:
            await self._server.close()
        if self.gcs:
            await self.gcs.close()
        # The shm store stays mapped until process exit: executor-thread
        # work (spill IO, log readers) may still be in flight and a call
        # through a freed store handle segfaults (see core_worker
        # disconnect). The raylet process is exiting anyway.

    async def _register_with_gcs(self, conn: rpc.Connection) -> None:
        await conn.call("register_node", {
            "node_id": self.node_id.binary(),
            "address": self.address,
            "hostname": os.uname().nodename,
            "store_path": self.store_path,
            "resources": self.resources_total,
            "labels": self.labels,
            "slice_id": self.slice_id,
            "transfer_port": self._transfer_port,
            # Live actors hosted here: a restarted GCS reconciles its
            # restored actor table against this (an actor that died
            # during GCS downtime must not stay ALIVE forever).
            "live_actors": [w.actor_id for w in self.workers.values()
                            if w.actor_id and w.state != "dead"],
        })
        await conn.call("subscribe", {"channel": "cluster_view"})
        await conn.call("subscribe", {"channel": "jobs"})

    def _on_gcs_close(self, conn: rpc.Connection) -> None:
        if not self.dead:
            asyncio.get_event_loop().create_task(self._reconnect_gcs())

    async def _reconnect_gcs(self) -> None:
        """The GCS died: reconnect and re-register under the same node id
        once it is back (reference: raylets buffer through GCS restarts —
        HandleNotifyGCSRestart, node_manager.h:614). Workers keep running
        throughout; only control-plane calls stall."""
        ghost, gport = self.gcs_address.rsplit(":", 1)
        deadline = time.monotonic() + self.config.gcs_down_exit_s
        while not self.dead:
            conn = None
            try:
                conn = await rpc.connect(ghost, int(gport),
                                         handler=self._on_gcs_message,
                                         name="raylet->gcs")
                await self._register_with_gcs(conn)
            except Exception:
                if conn is not None:
                    await conn.close()
                if time.monotonic() > deadline:
                    logger.error("GCS unreachable for %.0fs; exiting",
                                 self.config.gcs_down_exit_s)
                    os._exit(1)
                await asyncio.sleep(0.5)
                continue
            conn.on_close = self._on_gcs_close
            self.gcs = conn
            logger.info("re-registered with restarted GCS")
            return

    async def _on_gcs_message(self, method: str, data, conn):
        if method == "publish":
            channel = data["channel"]
            if channel == "cluster_view":
                self.cluster_view = data["data"]
            elif channel == "jobs" and data["data"].get("state") == "FINISHED":
                await self._on_job_finished(data["data"]["job_id"])
            return None
        # The GCS issues RPCs (actor leases, bundle 2PC) back over this
        # connection; dispatch them to the same handlers the server exposes.
        fn = getattr(self, "handle_" + method, None)
        if fn is None:
            raise rpc.RpcError(f"unknown method {method}")
        return await fn(data, conn)

    async def _on_job_finished(self, job_id: bytes) -> None:
        for w in list(self.workers.values()):
            if w.job_id == job_id and w.state == "leased":
                await self._kill_worker(w, "job finished")

    def _notify_resources_changed(self) -> None:
        """Event-driven resource sync (reference: RaySyncer,
        ray_syncer.h:88 — resource deltas push immediately instead of
        waiting out the periodic report): wakes the heartbeat loop so
        other raylets' spillback views refresh within milliseconds of a
        grant/release rather than a full period later."""
        ev = getattr(self, "_hb_event", None)
        if ev is not None:
            ev.set()

    async def _heartbeat_loop(self) -> None:
        self._hb_event = asyncio.Event()
        while not self.dead:
            # Clear BEFORE reading self.available: a change landing while
            # the call is in flight re-arms the event and triggers an
            # immediate follow-up heartbeat.
            self._hb_event.clear()
            try:
                r = await self.gcs.call("heartbeat", {
                    "node_id": self.node_id.binary(),
                    "resources_available": self.available,
                    # Queued lease demands feed the autoscaler (reference:
                    # resource-load piggybacked on raylet heartbeats and
                    # aggregated by GcsAutoscalerStateManager).
                    "pending_demands": [
                        req.resources for req in self.lease_queue[:100]],
                }, timeout=5.0)
                if not r.get("ok"):
                    logger.error("GCS declared this node dead; exiting")
                    os._exit(1)
            except Exception:
                if self.dead:
                    return
            await asyncio.sleep(0.01)  # min gap: bounds event-driven rate
            try:
                await asyncio.wait_for(
                    self._hb_event.wait(),
                    min(self.config.health_check_period_ms / 2, 100) / 1000)
            except asyncio.TimeoutError:
                pass

    async def _reporter_loop(self) -> None:
        """Per-node hardware reporter (reference:
        python/ray/dashboard/modules/reporter/ — per-node cpu/mem/device
        stats flowing into the metrics pipeline): cpu%, memory, object
        store usage, and TPU chip allocation as gauges tagged with this
        node, surfaced at the dashboard's /metrics and /api/node_stats."""
        period = 2.0
        prev_cpu: Optional[Tuple[float, float]] = None
        tags = {"node_id": self.node_id.hex(),
                "hostname": os.uname().nodename}
        while not self.dead:
            await asyncio.sleep(period)
            try:
                gauges = []

                def g(name, value, desc):
                    gauges.append({"name": name, "kind": "gauge",
                                   "value": float(value), "tags": tags,
                                   "description": desc})

                # cpu utilisation from /proc/stat deltas
                with open("/proc/stat") as f:
                    parts = f.readline().split()[1:]
                vals = [float(x) for x in parts]
                total, idle = sum(vals), vals[3] + (
                    vals[4] if len(vals) > 4 else 0.0)
                if prev_cpu is not None:
                    dt, di = total - prev_cpu[0], idle - prev_cpu[1]
                    if dt > 0:
                        g("node.cpu_percent", 100.0 * (1 - di / dt),
                          "node CPU utilisation")
                prev_cpu = (total, idle)
                mem = {}
                with open("/proc/meminfo") as f:
                    for line in f:
                        k, v = line.split(":", 1)
                        mem[k] = float(v.split()[0]) * 1024
                g("node.mem_total_bytes", mem.get("MemTotal", 0),
                  "node memory total")
                g("node.mem_available_bytes", mem.get("MemAvailable", 0),
                  "node memory available")
                if self.store is not None:
                    st = self.store.stats()
                    g("node.object_store_used_bytes",
                      st.get("bytes_used", 0), "plasma bytes used")
                    g("node.object_store_capacity_bytes",
                      st.get("capacity", 0), "plasma capacity")
                    g("node.object_store_num_objects",
                      st.get("num_objects", 0), "plasma object count")
                tpu_total = self.resources_total.get("TPU", 0.0)
                if tpu_total:
                    g("node.tpu_total", tpu_total, "TPU chips on node")
                    g("node.tpu_available",
                      self.available.get("TPU", 0.0),
                      "unallocated TPU chips")
                if self.gcs and not self.gcs.closed:
                    await self.gcs.call("report_metrics", {
                        "worker_id": b"raylet:" + self.node_id.binary(),
                        "metrics": gauges})
            except asyncio.CancelledError:
                return
            except Exception:
                logger.debug("hardware reporter tick failed",
                             exc_info=True)

    async def _memory_monitor_loop(self) -> None:
        """Kill the newest leased worker when node memory crosses the
        threshold (reference: MemoryMonitor + retriable-FIFO policy) —
        shed load before the kernel OOM killer shoots the raylet."""
        from ray_tpu._private.memory_monitor import (memory_usage_fraction,
                                                     pick_worker_to_kill)

        period = self.config.memory_monitor_refresh_ms / 1000.0
        while not self.dead:
            await asyncio.sleep(period)
            try:
                frac = memory_usage_fraction()
                if frac <= self.config.memory_usage_threshold:
                    continue
                victim = pick_worker_to_kill(self.workers.values())
                if victim is None:
                    continue
                logger.warning(
                    "memory usage %.1f%% > %.1f%%: killing worker %s "
                    "(its task will retry)", frac * 100,
                    self.config.memory_usage_threshold * 100,
                    victim.worker_id.hex()[:12])
                try:
                    from ray_tpu.util.events import make_event

                    await self.gcs.call("report_events", {"events": [
                        make_event("raylet", "WORKER_OOM_KILLED",
                                   f"worker {victim.worker_id.hex()[:8]} "
                                   f"killed at {frac:.0%} memory usage",
                                   severity="WARNING",
                                   metadata={"node_id":
                                             self.node_id.hex()})]})
                except Exception:
                    pass
                await self._kill_worker(
                    victim, f"node OOM: memory usage {frac:.2%}")
            except Exception:
                logger.exception("memory monitor iteration failed")

    async def _drain_loop(self) -> None:
        """Periodic queue re-evaluation (cluster view changes over time)."""
        while not self.dead:
            await asyncio.sleep(0.2)
            if self.lease_queue:
                self._drain_queue()

    async def _reap_loop(self) -> None:
        """Monitor spawned worker processes; report deaths."""
        while not self.dead:
            await asyncio.sleep(0.2)
            for w in list(self.workers.values()):
                if (w.proc is not None or w.forked) and \
                        w.state != "dead" and not w.alive():
                    await self._on_worker_death(w)

    async def _on_worker_death(self, w: WorkerHandle) -> None:
        if w.state == "dead":
            return  # reap loop and conn-close can both observe the death
        prev_state = w.state
        w.state = "dead"
        self.workers.pop(w.worker_id, None)
        if w in self.idle_workers:
            self.idle_workers.remove(w)
        if w.lease_id and w.lease_id in self.leases:
            _, res, bundle_key = self.leases.pop(w.lease_id)
            self._release_resources(res, bundle_key)
        if prev_state == "actor":
            try:
                await self.gcs.call("report_worker_death", {
                    "actor_id": w.actor_id,
                    "reason": f"worker process {w.pid} exited",
                })
            except Exception:
                pass
        logger.info("worker %s (pid=%s, state=%s) died",
                    w.worker_id.hex()[:8], w.pid, prev_state)
        self._drain_queue()

    async def _kill_worker(self, w: WorkerHandle, reason: str) -> None:
        logger.info("killing worker %s: %s", w.worker_id.hex()[:8], reason)
        # If the async spawn hasn't landed yet, finish_spawn honors this
        # flag and terminates immediately — otherwise the orphan process
        # (and its lease/resources) would leak.
        w.kill_requested = True
        w.terminate()

    # ------------------------------------------------------------- worker pool
    @staticmethod
    def _pkg_pythonpath() -> str:
        """PYTHONPATH that puts this ray_tpu checkout first."""
        import ray_tpu

        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(ray_tpu.__file__)))
        existing = os.environ.get("PYTHONPATH")
        return pkg_root + (":" + existing if existing else "")

    def _worker_env(self, worker_id: WorkerID, tpu: bool) -> dict:
        """Per-worker environment variables (on top of the raylet's)."""
        env = {
            "PYTHONPATH": self._pkg_pythonpath(),
            "RAY_TPU_WORKER_ID": worker_id.hex(),
            "RAY_TPU_RAYLET_ADDRESS": self.address,
            "RAY_TPU_GCS_ADDRESS": self.gcs_address,
            "RAY_TPU_NODE_ID": self.node_id.hex(),
            "RAY_TPU_STORE_PATH": self.store_path,
            "RAY_TPU_SESSION_DIR": self.session_dir,
        }
        # A chip belongs to one process at a time, and with libtpu
        # installed ANY process that initialises JAX takes it unless
        # told otherwise. Only a worker leased to TPU work may: every
        # other worker (a Data preprocessor, an RLlib env runner) is
        # held to the CPU backend. A TPU worker inherits whatever the
        # node was started with.
        if not tpu:
            env["JAX_PLATFORMS"] = "cpu"
        else:
            from ray_tpu.util.compile_cache import default_compile_cache_dir

            cache_dir = default_compile_cache_dir()
            if cache_dir is not None:
                env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        return env

    def _ensure_forkserver(self) -> subprocess.Popen:
        """Start (or restart) the warm template process. Caller holds
        _fork_lock. Runs on an executor thread, never the loop."""
        fs = self._forkserver
        if fs is not None and fs.poll() is None:
            return fs
        env = dict(os.environ)
        env["PYTHONPATH"] = self._pkg_pythonpath()
        log_path = os.path.join(self.session_dir, "logs", "forkserver.log")
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        logf = open(log_path, "ab")
        fs = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.forkserver"],
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=logf, start_new_session=True)
        logf.close()
        self._forkserver = fs
        return fs

    def _fork_worker(self, extra_env: dict, log_path: str) -> int:
        """Ask the template to fork a worker; returns the child pid.
        Caller is on an executor thread (blocking pipe I/O). Reads are
        select-bounded: a wedged template must fail THIS spawn (and get
        replaced) rather than deadlock every future spawn on the lock."""
        import select

        import msgpack

        header = struct.Struct("<I")

        def read_bounded(n: int) -> bytes:
            out = b""
            deadline = time.monotonic() + 20.0
            fd = fs.stdout.fileno()
            while len(out) < n:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not select.select(
                        [fd], [], [], remaining)[0]:
                    fs.kill()  # wedged: replace on next _ensure
                    raise RuntimeError("forkserver timed out; killed")
                chunk = os.read(fd, n - len(out))
                if not chunk:
                    raise RuntimeError("forkserver died mid-request")
                out += chunk
            return out

        with self._fork_lock:
            fs = self._ensure_forkserver()
            req = msgpack.packb({"env": extra_env, "log_path": log_path},
                                use_bin_type=True)
            fs.stdin.write(header.pack(len(req)) + req)
            fs.stdin.flush()
            (length,) = header.unpack(read_bounded(header.size))
            reply = msgpack.unpackb(read_bounded(length), raw=False)
        if "pid" not in reply:
            raise RuntimeError(f"forkserver spawn failed: {reply}")
        return reply["pid"]

    @staticmethod
    def _resolve_conda_python(conda: str) -> str:
        """Resolve a runtime_env['conda'] name/prefix to its interpreter.

        Conda semantics are interpreter-swap semantics (the reference
        wraps the worker command in `conda run`, runtime_env/conda.py):
        the named env's python runs the worker, so its site-packages ARE
        the environment — no sys.path games. This deployment is hermetic,
        so envs must be PRE-BUILT: a name resolves under
        $RAY_TPU_CONDA_ROOT/envs/<name>, a path containing '/' is used as
        the env prefix directly. The env needs msgpack installed (worker
        wire protocol); ray_tpu itself ships via PYTHONPATH."""
        if os.sep in conda:
            prefix = os.path.abspath(os.path.expanduser(conda))
        else:
            root = os.environ.get("RAY_TPU_CONDA_ROOT", "")
            if not root:
                raise RuntimeError(
                    f"runtime_env conda={conda!r} requires "
                    "RAY_TPU_CONDA_ROOT to point at a conda installation "
                    "with pre-built envs (hermetic deployment: envs are "
                    "not solved/created on the fly)")
            prefix = os.path.join(root, "envs", conda)
        py = os.path.join(prefix, "bin", "python")
        if not os.path.isfile(py):
            raise RuntimeError(
                f"conda env {conda!r} has no interpreter at {py}; "
                "build the env ahead of time (it must include msgpack)")
        return py

    def _spawn_worker(self, tpu: bool = False,
                      image_uri: str = "",
                      conda: str = "") -> WorkerHandle:
        worker_id = WorkerID.from_random()
        extra_env = self._worker_env(worker_id, tpu)
        log_path = os.path.join(self.session_dir, "logs",
                                f"worker-{worker_id.hex()[:12]}.log")
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        # Pre-spawn validation FIRST (a raise here must not leave a ghost
        # WorkerHandle in self.workers):
        # Container hook (reference: runtime_env/image_uri.py): when the
        # env pins an image, the worker launches through the operator's
        # hook command — `<hook> <image_uri> <python> -m ...worker_main`
        # (e.g. a docker-run wrapper). Recorded here in the launch path;
        # no hook configured is a hard error surfaced to the creator.
        container_argv: Optional[List[str]] = None
        if image_uri:
            hook = os.environ.get("RAY_TPU_CONTAINER_HOOK", "")
            if not hook:
                raise RuntimeError(
                    f"runtime_env image_uri={image_uri!r} requires a "
                    "container hook (set RAY_TPU_CONTAINER_HOOK to a "
                    "wrapper command, e.g. a docker-run script)")
            import shlex as _shlex

            container_argv = _shlex.split(hook) + [image_uri]
        # Conda env = different interpreter (resolved before any process
        # starts so a bad env fails the lease, not the worker log).
        py_exe = self._resolve_conda_python(conda) if conda \
            else sys.executable
        w = WorkerHandle(worker_id, None, None)
        w.tpu = tpu
        w.log_path = log_path
        self.workers[worker_id] = w
        # Container and conda workers always launch their own
        # interpreter. TPU workers fork like the rest: the template
        # never imports JAX (forkserver.py), so the child initialises
        # it — and takes the chip — itself, after its env is applied.
        use_fork = self.config.forkserver_enabled and not image_uri and \
            not conda

        # All spawn work OFF the io loop: a spawn storm (hundreds of
        # actors created at once) must not stall heartbeats — a blocked
        # loop gets the whole node declared dead by the GCS health
        # checker.
        def popen():
            env = dict(os.environ)
            env.update(extra_env)
            argv = (container_argv or []) + [
                py_exe, "-m", "ray_tpu._private.worker_main"]
            with open(log_path, "ab") as logf:
                return subprocess.Popen(
                    argv,
                    env=env, stdout=logf, stderr=subprocess.STDOUT,
                    start_new_session=True)

        async def finish_spawn():
            loop = asyncio.get_running_loop()
            pid = proc = None
            if use_fork:
                try:
                    pid = await loop.run_in_executor(
                        None, self._fork_worker, extra_env, log_path)
                except Exception:
                    logger.exception(
                        "forkserver spawn failed; falling back to popen")
            if pid is None:
                try:
                    proc = await loop.run_in_executor(None, popen)
                except Exception:
                    logger.exception("worker spawn failed")
                    # Full death path: releases the lease/resources this
                    # worker may already hold (actor leases are taken
                    # before spawn) and reports actor death to the GCS.
                    await self._on_worker_death(w)
                    return
            w.proc = proc
            w.pid = pid if pid is not None else proc.pid
            w.forked = proc is None
            if (self.dead or w.kill_requested) and w.alive():
                w.terminate()  # shut down / killed mid-spawn

        task = asyncio.get_event_loop().create_task(finish_spawn())
        self._spawn_tasks.add(task)
        task.add_done_callback(self._spawn_tasks.discard)
        return w

    async def _log_monitor_loop(self) -> None:
        """Tail every worker's log file and forward new lines to the GCS
        "logs" pubsub channel, where subscribed drivers print them
        (reference: python/ray/_private/log_monitor.py:103 — the driver
        sees every worker's stdout/stderr)."""
        while not self.dead:
            await asyncio.sleep(0.25)
            loop = asyncio.get_event_loop()
            for w in list(self.workers.values()):
                if w.log_path is None:
                    continue

                def read_chunk(path=w.log_path, off=w.log_offset):
                    with open(path, "rb") as f:
                        f.seek(off)
                        return f.read(256 * 1024)

                try:
                    # Off-loop: tailing hundreds of worker logs must not
                    # add blocking file I/O to the raylet's event loop.
                    chunk = await loop.run_in_executor(None, read_chunk)
                except OSError:
                    continue
                if not chunk:
                    continue
                w.log_offset += len(chunk)
                data = w.log_partial + chunk
                lines = data.split(b"\n")
                w.log_partial = lines.pop()  # tail w/o newline
                text_lines = [ln.decode("utf-8", "replace")
                              for ln in lines if ln.strip()]
                if not text_lines or self.gcs is None or self.gcs.closed:
                    continue
                try:
                    await self.gcs.notify("publish_logs", {
                        "lines": text_lines,
                        "pid": w.pid,
                        "worker_id": w.worker_id.binary(),
                        # Lets each driver filter to its own job's
                        # workers (None while the worker is unleased).
                        "job_id": w.job_id,
                        "node": self.address,
                    })
                except Exception:
                    pass

    async def handle_register_worker(self, data, conn) -> dict:
        worker_id = WorkerID(data["worker_id"])
        w = self.workers.get(worker_id)
        if w is None:
            # Driver registration: not a pool worker.
            w = WorkerHandle(worker_id, data.get("pid", 0))
            w.state = "driver"
            self.workers[worker_id] = w
        w.address = data["address"]
        w.fast_address = data.get("fast_address", "")
        w.conn = conn
        conn.on_close = lambda c, w=w: self._on_conn_close(w)
        w.registered.set()
        if w.state == "starting":
            w.state = "idle"
            self.idle_workers.append(w)
            self._drain_queue()
        return {"node_id": self.node_id.binary(), "ok": True}

    def _on_conn_close(self, w: WorkerHandle) -> None:
        if w.state == "driver":
            self.workers.pop(w.worker_id, None)
            return
        # Registered workers die with their raylet connection (the worker
        # side exits on conn loss; the reverse direction is detected
        # here). This is the pid-independent death signal for forked
        # workers — the _reap_loop's os.kill(pid, 0) probe alone has a
        # one-tick PID-reuse window (forkserver children are auto-reaped).
        if not self.dead and w.state != "dead" and w.registered.is_set():
            asyncio.get_event_loop().create_task(self._on_worker_death(w))

    def _pool_capacity(self) -> int:
        soft = self.config.num_workers_soft_limit
        if soft <= 0:
            soft = max(int(self.resources_total.get("CPU", 1)), 1)
        return soft

    # ------------------------------------------------------------- leases
    async def handle_get_cluster_view(self, data, conn) -> list:
        """Debug/testing: this raylet's current gossip view (what its
        spillback decisions are based on)."""
        return self.cluster_view

    async def handle_list_store_objects(self, data, conn) -> list:
        """This node's shm store contents (id, size, pin count) — one
        shard of the cluster-wide `list objects` state query (reference:
        the per-core-worker object tables behind `ray list objects`)."""
        import ctypes

        from ray_tpu.core import shm_client as sc

        lib = sc._load()
        max_n = int(data.get("limit", 4096))
        ids_buf = (ctypes.c_uint8 * (24 * max_n))()
        sizes = (ctypes.c_uint64 * max_n)()
        refs = (ctypes.c_int64 * max_n)()
        n = lib.shm_list(self.store._ptr, ids_buf, sizes, refs, max_n)
        return [{"object_id": bytes(ids_buf[i * 24:(i + 1) * 24]).hex(),
                 "size_bytes": int(sizes[i]),
                 "pins": int(refs[i]),
                 "node_id": self.node_id.hex()}
                for i in range(n)]

    async def handle_request_worker_lease(self, data, conn) -> dict:
        req = LeaseRequest(data)
        if os.environ.get("RAY_TPU_TRACE_LEASES"):
            logger.info(
                "LEASE req=%s res=%s spills=%d avail=%s queue=%d view=%s",
                req.lease_id.hex()[:6], req.resources, req.num_spillbacks,
                self.available, len(self.lease_queue),
                [(n["node_id"].hex()[:6], n["resources_available"])
                 for n in self.cluster_view])
        if not self._feasible_ever(req):
            target = self._find_spillback_target(req, require_available=False)
            if target:
                return {"spillback": target}
            # No capable node *yet*: queue — reference semantics are that
            # infeasible tasks stay pending until resources appear.
        # Hybrid spillback: local under pressure, someone else has room
        # now. "Pressure" counts requests already QUEUED ahead of this
        # one (reference: ClusterTaskManager accounts allocated AND
        # queued demand) — without that, a burst arriving before the
        # first grant deducts resources sees stale availability and
        # serializes locally instead of spreading.
        if not self._can_grant_now(req, include_queued=True) and \
                req.num_spillbacks < 3:
            target = self._find_spillback_target(req, require_available=True)
            if target and target != self.address:
                if os.environ.get("RAY_TPU_TRACE_LEASES"):
                    logger.info("LEASE req=%s SPILL -> %s",
                                req.lease_id.hex()[:6], target)
                return {"spillback": target}
        if os.environ.get("RAY_TPU_TRACE_LEASES"):
            logger.info("LEASE req=%s QUEUE locally",
                        req.lease_id.hex()[:6])
        self.lease_queue.append(req)
        self._drain_queue()
        granted = await req.grant_fut
        return granted

    async def handle_cancel_lease_request(self, data, conn) -> bool:
        lease_id = data["lease_id"]
        for req in list(self.lease_queue):
            if req.lease_id == lease_id:
                self.lease_queue.remove(req)
                if not req.grant_fut.done():
                    req.grant_fut.set_result({"error": "canceled"})
                return True
        return False

    def _bundle_pool(self, req: LeaseRequest) -> Optional[dict]:
        if req.pg_id is None:
            return None
        return self.bundles.get((req.pg_id, max(req.pg_bundle, 0)))

    def _feasible_ever(self, req: LeaseRequest) -> bool:
        if req.pg_id is not None:
            pool = self._bundle_pool(req)
            return pool is not None and pool["committed"] and \
                _fits(req.resources, pool["reserved"])
        return _fits(req.resources, self.resources_total)

    def _can_grant_now(self, req: LeaseRequest,
                       include_queued: bool = False) -> bool:
        pool = self._bundle_pool(req)
        if req.pg_id is not None:
            return pool is not None and pool["committed"] and \
                _fits(req.resources, pool["available"])
        avail = self.available
        if include_queued:
            queued = {}
            for r in self.lease_queue:
                if r is not req and not r.grant_fut.done() and \
                        r.pg_id is None:
                    for k, v in r.resources.items():
                        queued[k] = queued.get(k, 0) + v
            if queued:
                avail = {k: v - queued.get(k, 0)
                         for k, v in avail.items()}
        return _fits(req.resources, avail)

    def _debited_available(self, n: dict) -> dict:
        """Node availability minus this raylet's recent spillback debits.

        Spilling deducts optimistically so back-to-back decisions fan
        out — but a cluster_view broadcast REPLACES the cached view,
        and one captured before the spilled request landed at its
        target resurrects the stale availability (observed: 3 held
        tasks landing on 2 nodes). Debits live in an overlay with a
        short TTL (long enough for the target's own grant to reach the
        next broadcast) so they survive view refreshes."""
        now = time.monotonic()
        self._spill_debits = [(exp, nid, res) for exp, nid, res in
                              getattr(self, "_spill_debits", [])
                              if exp > now]
        avail = dict(n["resources_available"])
        for _exp, nid, res in self._spill_debits:
            if nid == n["node_id"]:
                for k, v in res.items():
                    avail[k] = avail.get(k, 0) - v
        return avail

    def _find_spillback_target(self, req: LeaseRequest,
                               require_available: bool) -> Optional[str]:
        if req.pg_id is not None:
            return None  # PG tasks are pinned to their bundle's node
        best = None
        for n in self.cluster_view:
            if n["node_id"] == self.node_id.binary():
                continue
            avail = self._debited_available(n)
            pool = avail if require_available else n["resources_total"]
            if _fits(req.resources, pool):
                score = sum(avail.values())
                if best is None or score > best[0]:
                    best = (score, n)
        if best is None:
            return None
        if require_available:
            self._spill_debits.append(
                (time.monotonic() + 2.0, best[1]["node_id"],
                 dict(req.resources)))
        return best[1]["address"]

    def _drain_queue(self) -> None:
        made_progress = True
        while made_progress and self.lease_queue:
            made_progress = False
            for req in list(self.lease_queue):
                if req.grant_fut.done():
                    self.lease_queue.remove(req)
                    continue
                if not self._can_grant_now(req):
                    continue
                needs_tpu = req.resources.get("TPU", 0) > 0
                worker = self._take_idle_worker(tpu=needs_tpu)
                if worker is None:
                    n_starting = sum(1 for w in self.workers.values()
                                     if w.state == "starting")
                    n_live = sum(1 for w in self.workers.values()
                                 if w.state in ("starting", "idle", "leased"))
                    if n_live < self._pool_capacity() or n_starting == 0:
                        self._spawn_worker(tpu=needs_tpu)
                    break  # wait for registration
                self.lease_queue.remove(req)
                self._grant(req, worker)
                made_progress = True
        # Re-evaluate spillback for starved requests: resources freed up on
        # another node since this request was queued (reference:
        # ClusterTaskManager::ScheduleAndDispatchTasks runs the cluster-wide
        # policy on every state change).
        for req in list(self.lease_queue):
            if req.grant_fut.done() or self._can_grant_now(req):
                continue
            # Locally-infeasible requests may always spill; feasible-but-busy
            # ones only a few times (to bound ping-pong).
            if self._feasible_ever(req) and req.num_spillbacks >= 3:
                continue
            target = self._find_spillback_target(req, require_available=True)
            if target and target != self.address:
                self.lease_queue.remove(req)
                req.grant_fut.set_result({"spillback": target})

    def _maybe_refill_pool(self) -> None:
        """Keep a standing pool of registered idle workers (reference:
        WorkerPool::PrestartWorkers): actor storms and task bursts then
        consume warm workers instead of paying process bring-up inline.
        Actor-bound workers leave the pool permanently, so the refill is
        what keeps storms fast beyond the first wave."""
        if not self.config.prestart_workers or self.dead:
            return
        min_idle = self._pool_capacity()
        n_idle = sum(1 for w in self.idle_workers if w.state == "idle")
        n_starting = sum(1 for w in self.workers.values()
                         if w.state == "starting")
        for _ in range(max(0, min_idle - n_idle - n_starting)):
            self._spawn_worker()

    def _schedule_pool_refill(self, delay: float = 0.25) -> None:
        """Refill after a consumed pool worker — debounced ONLY while a
        storm is in flight: replacement spawns must not compete with the
        storm's own worker bring-ups for CPU (a 16-actor storm otherwise
        pays 32 process starts up front), but steady sub-`delay` actor
        creation must not starve the refill either (each consumption
        re-arming the timer would drain the pool and force cold inline
        spawns). Heuristic: spawns already in flight = storm = debounce;
        quiet pool = refill immediately."""
        n_starting = sum(1 for w in self.workers.values()
                         if w.state == "starting")
        if n_starting == 0:
            self._maybe_refill_pool()
            return
        handle = getattr(self, "_refill_handle", None)
        if handle is not None:
            handle.cancel()
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            self._maybe_refill_pool()
            return
        self._refill_handle = loop.call_later(
            delay, self._maybe_refill_pool)

    def _take_idle_worker(self, tpu: bool = False
                          ) -> Optional[WorkerHandle]:
        keep: List[WorkerHandle] = []
        found = None
        while self.idle_workers:
            w = self.idle_workers.pop()
            if w.state != "idle" or not w.alive():
                continue  # dead/stale entry
            if w.tpu == tpu:
                found = w
                break
            # Never the other flavor: CPU work that imports JAX on an
            # idle TPU worker would take the chip the next TPU lease
            # needs, and a CPU worker cannot see the chip at all.
            keep.append(w)
        self.idle_workers.extend(keep)
        return found

    def _grant(self, req: LeaseRequest, worker: WorkerHandle) -> None:
        bundle_key = None
        if req.pg_id is not None:
            bundle_key = (req.pg_id, max(req.pg_bundle, 0))
            pool = self.bundles[bundle_key]
            for k, v in req.resources.items():
                pool["available"][k] = pool["available"].get(k, 0) - v
        else:
            for k, v in req.resources.items():
                self.available[k] = self.available.get(k, 0) - v
        worker.state = "leased"
        worker.lease_id = req.lease_id
        worker.job_id = req.job_id
        worker.lease_started = time.monotonic()
        self.leases[req.lease_id] = (worker, dict(req.resources), bundle_key)
        self._notify_resources_changed()
        req.grant_fut.set_result({
            "granted": True,
            "worker_address": worker.address,
            "worker_fast_address": worker.fast_address,
            "worker_id": worker.worker_id.binary(),
        })

    def _release_resources(self, res: Dict[str, float],
                           bundle_key) -> None:
        if bundle_key is not None:
            pool = self.bundles.get(bundle_key)
            if pool:
                for k, v in res.items():
                    pool["available"][k] = pool["available"].get(k, 0) + v
        else:
            for k, v in res.items():
                self.available[k] = self.available.get(k, 0) + v
        self._notify_resources_changed()

    async def handle_return_worker(self, data, conn) -> bool:
        lease_id = data["lease_id"]
        entry = self.leases.pop(lease_id, None)
        if entry is None:
            return False
        worker, res, bundle_key = entry
        self._release_resources(res, bundle_key)
        if data.get("disconnect") or worker.state == "dead":
            if worker.proc or worker.forked:
                await self._kill_worker(worker, "returned with disconnect")
        elif worker.state == "leased":
            worker.state = "idle"
            worker.lease_id = None
            self.idle_workers.append(worker)
        self._drain_queue()
        return True

    # ------------------------------------------------------- actor leases
    async def handle_lease_worker_for_actor(self, data, conn) -> dict:
        """GCS asks this node to host an actor: spawn a dedicated worker and
        push the creation task to it (reference: raylet grants a worker
        lease for the actor-creation task; worker stays bound for life)."""
        from ray_tpu.core.task_spec import TaskSpec

        spec = TaskSpec.from_wire(data["task"])
        if not _fits(spec.resources, self.available) and \
                spec.placement_group_id is None:
            return {"ok": False, "error": "insufficient resources"}
        bundle_key = None
        if spec.placement_group_id is not None:
            bundle_key = (spec.placement_group_id.binary(),
                          max(spec.placement_group_bundle_index, 0))
            pool = self.bundles.get(bundle_key)
            if pool is None or not pool["committed"] or \
                    not _fits(spec.resources, pool["available"]):
                return {"ok": False, "error": "bundle unavailable"}
            for k, v in spec.resources.items():
                pool["available"][k] = pool["available"].get(k, 0) - v
        else:
            for k, v in spec.resources.items():
                self.available[k] = self.available.get(k, 0) - v
        # Idle-worker reuse (reference: WorkerPool hands pooled workers to
        # actor leases): an already-registered pool worker skips process
        # startup entirely — the dominant cost of actor-creation storms.
        needs_tpu = spec.resources.get("TPU", 0) > 0
        self._notify_resources_changed()
        renv = spec.runtime_env or {}
        image_uri = renv.get("image_uri", "")
        conda_env = renv.get("conda", "")
        if isinstance(conda_env, dict):
            # Spec-form conda ({"dependencies": [...]}) needs a solver —
            # not available hermetically. Named pre-built envs only.
            # permanent: the GCS must fail the actor with THIS error, not
            # retry into a generic "no feasible node".
            self._release_resources(dict(spec.resources),
                                    bundle_key)
            return {"ok": False, "permanent": True, "error":
                    "runtime_env conda specs (dependency lists) are not "
                    "supported in this hermetic deployment; pre-build the "
                    "env and pass its NAME (under RAY_TPU_CONDA_ROOT) or "
                    "prefix path"}
        dedicated = bool(image_uri or conda_env)
        w = None if dedicated else self._take_idle_worker(tpu=needs_tpu)
        if w is None:
            try:
                w = self._spawn_worker(tpu=needs_tpu, image_uri=image_uri,
                                       conda=conda_env)
            except RuntimeError as e:  # pre-spawn validation: image_uri
                # without a hook, unresolvable conda env — permanent
                # config errors; retrying other nodes gives the same
                # answer, so the GCS should surface THIS message.
                if spec.placement_group_id is None:
                    self._release_resources(dict(spec.resources), None)
                else:
                    self._release_resources(dict(spec.resources),
                                            bundle_key)
                return {"ok": False, "permanent": True, "error": str(e)}
        else:
            # Replace the consumed pool worker once the storm quiets
            # (debounced — replacements off the storm's critical path).
            self._schedule_pool_refill()
        w.state = "actor"
        w.actor_id = data["actor_id"]
        w.job_id = spec.job_id.binary()
        lease_id = os.urandom(16)
        w.lease_id = lease_id
        self.leases[lease_id] = (w, dict(spec.resources), bundle_key)
        trace = os.environ.get("RAY_TPU_TRACE_STARTUP")
        t0 = time.monotonic()

        def tr(msg):
            if trace:
                logger.info("TRACE lease %s +%.3f %s",
                            w.worker_id.hex()[:6], time.monotonic() - t0,
                            msg)

        tr("spawned, waiting registration")
        try:
            await asyncio.wait_for(w.registered.wait(),
                                   self.config.worker_startup_timeout_s)
            tr("registered, pushing creation")
            await w.conn.call("push_task", {"task": data["task"]},
                              timeout=self.config.worker_startup_timeout_s)
            tr("creation pushed + done")
        except Exception as e:
            await self._kill_worker(w, f"actor creation failed: {e}")
            return {"ok": False, "error": str(e)}
        return {"ok": True, "worker_address": w.address}

    # ------------------------------------------------------- placement bundles
    async def handle_prepare_bundle(self, data, conn) -> dict:
        key = (data["pg_id"], data["bundle_index"])
        res = data["resources"]
        if key in self.bundles:
            return {"ok": True}
        if not _fits(res, self.available):
            return {"ok": False, "error": "insufficient resources"}
        for k, v in res.items():
            self.available[k] = self.available.get(k, 0) - v
        self.bundles[key] = {"reserved": dict(res), "available": dict(res),
                             "committed": False}
        return {"ok": True}

    async def handle_commit_bundle(self, data, conn) -> bool:
        key = (data["pg_id"], data["bundle_index"])
        if key in self.bundles:
            self.bundles[key]["committed"] = True
            self._drain_queue()
        return True

    async def handle_cancel_bundle(self, data, conn) -> bool:
        key = (data["pg_id"], data["bundle_index"])
        pool = self.bundles.pop(key, None)
        if pool:
            for k, v in pool["reserved"].items():
                self.available[k] = self.available.get(k, 0) + v
            self._drain_queue()
        return True

    # ------------------------------------------------------- object manager
    async def handle_pull_object(self, data, conn) -> dict:
        """Ensure the object is in the local store (fetch/restore), or report
        where it actually is ('inline' = ask the owner's memory store)."""
        oid = ObjectID(data["object_id"])
        key = oid.binary()
        if self.store.contains(oid):
            return {"status": "local"}
        fut = self._pulls_inflight.get(key)
        if fut is None:
            fut = asyncio.get_event_loop().create_task(
                self._pull(oid, data.get("owner_address")))
            self._pulls_inflight[key] = fut
        try:
            return await asyncio.wait_for(
                asyncio.shield(fut), data.get("timeout", 30.0))
        except asyncio.TimeoutError:
            return {"status": "timeout"}
        finally:
            if fut.done():
                self._pulls_inflight.pop(key, None)

    async def _pull(self, oid: ObjectID, owner_address: Optional[str]) -> dict:
        deadline = time.monotonic() + 30.0
        key = oid.binary()
        while time.monotonic() < deadline:
            if self.store.contains(oid):
                return {"status": "local"}
            if key in self._spilled_local:
                ok = await self._restore_spilled(oid,
                                                 self._spilled_local[key])
                if ok:
                    return {"status": "local"}
            locs = await self.gcs.call("get_object_locations",
                                       {"object_id": key})
            for node in locs.get("nodes", []):
                if node["node_id"] == self.node_id.binary():
                    continue
                ok = await self._fetch_from_remote(
                    oid, node["address"], node.get("transfer_port", 0))
                if ok:
                    await self.gcs.call("add_object_location", {
                        "object_id": key,
                        "node_id": self.node_id.binary()})
                    return {"status": "local"}
            url = locs.get("spilled_url")
            if url:
                ok = await self._restore_spilled(oid, url)
                if ok:
                    return {"status": "local"}
            await asyncio.sleep(0.05)
        return {"status": "not_found"}

    async def _fetch_from_remote(self, oid: ObjectID, address: str,
                                 transfer_port: int = 0) -> bool:
        # Fast path: native store-to-store streaming (transfer.cpp) — no
        # Python on the data plane. Falls back to rpc chunks if the remote
        # has no transfer server or the native pull fails.
        # The fetch client opens the local store itself — the remote's
        # transfer_port is all that matters.
        if transfer_port:
            host = address.rsplit(":", 1)[0]
            try:
                from ray_tpu.core import transfer_client as tc

                rc = await asyncio.get_event_loop().run_in_executor(
                    None, tc.fetch, self.store_path, host, transfer_port,
                    oid.binary())
                if rc in (tc.FETCH_OK, tc.FETCH_ALREADY_LOCAL):
                    return True
            except Exception as e:
                logger.info("native fetch of %s from %s:%d failed (%s); "
                            "falling back to rpc", oid.hex()[:8], host,
                            transfer_port, e)
        try:
            host, port = address.rsplit(":", 1)
            c = await rpc.connect(host, int(port), timeout=5.0,
                                  name="om-fetch")
        except Exception:
            return False
        try:
            meta = await c.call("om_object_info", {"object_id": oid.binary()},
                                timeout=10.0)
            if not meta.get("found"):
                return False
            size = meta["size"]
            # Write straight into the local store allocation, chunk by chunk.
            import ctypes

            from ray_tpu.core import shm_client as sc

            off = ctypes.c_uint64()
            rcode = sc._load().shm_create(self.store._ptr, oid.binary(), size,
                                          ctypes.byref(off))
            if rcode == sc.ERR_EXISTS:
                return True
            if rcode != sc.OK:
                return False
            try:
                pos = 0
                while pos < size:
                    n = min(CHUNK, size - pos)
                    chunk = await c.call("om_fetch", {
                        "object_id": oid.binary(), "offset": pos,
                        "length": n}, timeout=30.0)
                    if chunk is None:
                        raise IOError("remote object vanished mid-transfer")
                    self.store._mv[off.value + pos: off.value + pos + len(chunk)] = chunk
                    pos += len(chunk)
            except BaseException:
                sc._load().shm_abort(self.store._ptr, oid.binary())
                raise
            sc._load().shm_seal(self.store._ptr, oid.binary())
            sc._load().shm_release(self.store._ptr, oid.binary())
            return True
        except Exception as e:
            logger.info("fetch of %s from %s failed: %s",
                        oid.hex()[:8], address, e)
            return False
        finally:
            await c.close()

    async def handle_om_object_info(self, data, conn) -> dict:
        oid = ObjectID(data["object_id"])
        buf = self.store.get(oid, timeout_ms=0)
        if buf is None:
            return {"found": False}
        size = len(buf.data)
        buf.release()
        return {"found": True, "size": size}

    async def handle_om_fetch(self, data, conn):
        oid = ObjectID(data["object_id"])
        buf = self.store.get(oid, timeout_ms=0)
        if buf is None:
            return None
        try:
            off, length = data["offset"], data["length"]
            return bytes(buf.data[off: off + length])
        finally:
            buf.release()

    async def handle_free_object(self, data, conn) -> bool:
        """Owner-driven deletion (distributed refcount hit zero)."""
        oid = ObjectID(data["object_id"])
        self.store.delete(oid)
        try:
            await self.gcs.call("remove_object_location", {
                "object_id": oid.binary(),
                "node_id": self.node_id.binary()})
        except Exception:
            pass
        return True

    # ------------------------------------------------------- spilling
    def _spill_storage(self):
        """Spill backend per config (reference:
        python/ray/_private/external_storage.py:72 — filesystem, or any
        URI-schemed backend: fsspec / registered plugin)."""
        if self._spill_backend is None:
            from ray_tpu._private.external_storage import storage_for_path

            path = self.config.object_spilling_dir or \
                os.path.join(self.session_dir, "spill")
            self._spill_backend = storage_for_path(path)
        return self._spill_backend

    async def _spill_loop(self) -> None:
        while not self.dead:
            await asyncio.sleep(0.5)
            try:
                stats = self.store.stats()
                if stats["capacity"] == 0 or \
                        stats["bytes_used"] / stats["capacity"] < \
                        self.config.object_spilling_threshold:
                    continue
                await self._spill_once()
            except Exception:
                logger.exception("spill loop error")

    async def _spill_once(self) -> None:
        """Spill one unreferenced sealed object to external storage
        (reference: LocalObjectManager::SpillObjects)."""
        import ctypes

        from ray_tpu.core import shm_client as sc

        lib = sc._load()
        max_n = 256
        ids_buf = (ctypes.c_uint8 * (24 * max_n))()
        sizes = (ctypes.c_uint64 * max_n)()
        refs = (ctypes.c_int64 * max_n)()
        n = lib.shm_list(self.store._ptr, ids_buf, sizes, refs, max_n)
        best = None
        for i in range(n):
            if refs[i] == 0:
                if best is None or sizes[i] > sizes[best]:
                    best = i
        if best is None:
            return
        oid = ObjectID(bytes(ids_buf[best * 24:(best + 1) * 24]))
        buf = self.store.get(oid, timeout_ms=0)
        if buf is None:
            return
        storage = self._spill_storage()
        loop = asyncio.get_event_loop()
        # The pinned shm view streams straight to storage (no heap copy —
        # the node is under memory pressure right now); remote backends
        # block on IO, so write off-loop. Release the pin after.
        try:
            url = await loop.run_in_executor(None, storage.put, oid.hex(),
                                             buf.data)
        finally:
            buf.release()
        self.store.delete(oid)
        self._spilled_local[oid.binary()] = url
        await self.gcs.call("add_spilled_object",
                            {"object_id": oid.binary(), "url": url})
        await self.gcs.call("remove_object_location", {
            "object_id": oid.binary(), "node_id": self.node_id.binary()})
        logger.info("spilled %s (%d bytes) to %s", oid.hex()[:8],
                    sizes[best], url)

    async def _restore_spilled(self, oid: ObjectID, url: str) -> bool:
        from ray_tpu._private.external_storage import storage_for_path

        try:
            # Restore via the url's own backend (the object may have been
            # spilled by a different node with a different local config).
            storage = storage_for_path(url)
            loop = asyncio.get_event_loop()
            data = await loop.run_in_executor(None, storage.get, url)
        except Exception:
            return False
        try:
            self.store.put_bytes(oid, data)
        except StoreFullError:
            return False
        self._spilled_local.pop(oid.binary(), None)
        await self.gcs.call("add_object_location", {
            "object_id": oid.binary(), "node_id": self.node_id.binary()})
        return True

    # ------------------------------------------------------- stats
    async def handle_node_stats(self, data, conn) -> dict:
        return {
            "node_id": self.node_id.binary(),
            "resources_total": self.resources_total,
            "resources_available": self.available,
            "num_workers": len(self.workers),
            "num_idle": len(self.idle_workers),
            "lease_queue": len(self.lease_queue),
            "store": self.store.stats(),
            "bundles": {f"{k[0].hex()[:8]}:{k[1]}": v["committed"]
                        for k, v in self.bundles.items()},
        }

    async def handle_ping(self, data, conn) -> str:
        return "pong"


def _fits(demand: Dict[str, float], available: Dict[str, float]) -> bool:
    return all(available.get(k, 0.0) >= v for k, v in demand.items() if v > 0)


def main():  # pragma: no cover - exercised via subprocess in tests
    import argparse
    import json

    p = argparse.ArgumentParser()
    p.add_argument("--gcs-address", required=True)
    p.add_argument("--store-path", required=True)
    p.add_argument("--resources", required=True)  # JSON dict
    p.add_argument("--session-dir", required=True)
    p.add_argument("--node-id", default="")
    p.add_argument("--labels", default="{}")
    p.add_argument("--slice-id", default="")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--config", default="{}")
    args = p.parse_args()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s raylet %(levelname)s %(message)s")

    async def run():
        cfg = Config.from_dict(json.loads(args.config)) if args.config != "{}" \
            else Config.from_env()
        node_id = NodeID.from_hex(args.node_id) if args.node_id \
            else NodeID.from_random()
        raylet = Raylet(node_id, args.gcs_address, args.store_path,
                        json.loads(args.resources), cfg, args.session_dir,
                        labels=json.loads(args.labels),
                        slice_id=args.slice_id)
        port = await raylet.start(args.host, args.port)
        print(json.dumps({"port": port, "node_id": node_id.hex()}),
              flush=True)
        await asyncio.Event().wait()

    from ray_tpu._private.profiling_hook import maybe_enable_profiler

    maybe_enable_profiler("raylet")
    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()

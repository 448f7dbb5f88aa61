"""Runtime configuration flag table.

Equivalent of the reference's RAY_CONFIG X-macro table
(src/ray/common/ray_config_def.h — 215 knobs populated from env vars and the
``_system_config`` dict passed to init). Here: one dataclass, every field
overridable via ``RAY_TPU_<UPPER_NAME>`` env vars or the ``system_config``
dict argument to ``ray_tpu.init``.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional


@dataclasses.dataclass
class Config:
    # --- object store ---
    object_store_memory: int = 0  # 0 = auto (30% of /dev/shm or RAM cap)
    object_store_auto_fraction: float = 0.3
    object_store_max_auto_bytes: int = 2 << 30
    # Objects smaller than this are inlined into the owner's memory store and
    # task replies instead of the shm store (reference:
    # src/ray/common/ray_config_def.h max_direct_call_object_size = 100KiB).
    max_direct_call_object_size: int = 100 * 1024
    object_transfer_chunk_bytes: int = 4 << 20
    object_spilling_dir: str = ""  # default: <session_dir>/spill
    object_spilling_threshold: float = 0.8
    # --- scheduler ---
    # Hybrid policy: pack onto the first feasible node until its critical
    # resource utilization exceeds this threshold, then spread
    # (reference: scheduler_spread_threshold, hybrid_scheduling_policy.cc).
    scheduler_spread_threshold: float = 0.5
    # Tasks pushed concurrently to one leased worker (reference:
    # max_tasks_in_flight_per_worker, normal_task_submitter.cc — the
    # pipelining that makes tiny-task throughput). Execution on the worker
    # stays serialized (single-thread executor); only queueing overlaps.
    # Set to 1 for strict one-task-per-lease semantics.
    max_tasks_in_flight_per_worker: int = 10
    # Pipelining engages only for scheduling keys whose observed (worker-
    # reported) execution time EMA is at or below this; longer tasks keep
    # strict one-in-flight spread semantics.
    pipeline_task_duration_s: float = 0.1
    # Observed-fast sync methods/functions run inline on the worker's io
    # loop (no executor-thread round trip — 2 GIL handoffs saved per
    # call); anything slower keeps the executor path. <=0 disables.
    inline_task_threshold_s: float = 0.002
    # Streaming generators: max yielded-but-unconsumed items per stream
    # before the producer pauses (reference:
    # _generator_backpressure_num_objects). <=0 disables.
    streaming_backpressure_num_items: int = 8
    # How long a raylet outlives an unreachable GCS before exiting
    # (reference: gcs_rpc_server_reconnect_timeout_s).
    gcs_down_exit_s: float = 60.0
    max_pending_lease_requests: int = 8
    worker_lease_timeout_s: float = 30.0
    # Idle fallback cadence of the GCS cluster-view broadcast; resource
    # CHANGES push immediately (RaySyncer-style event-driven sync).
    # Injectable so distributed tests can pin deterministic freshness.
    resource_broadcast_interval_ms: int = 200
    # --- health / failure detection ---
    health_check_period_ms: int = 1000
    # Generous threshold (10s): worker-spawn storms (hundreds of actors)
    # can lag loops for seconds; the reference's defaults allow ~15s
    # (health_check_timeout_ms + failure threshold).
    health_check_failure_threshold: int = 10
    num_heartbeats_timeout: int = 30
    # --- workers ---
    num_workers_soft_limit: int = 0  # 0 = num_cpus
    worker_startup_timeout_s: float = 60.0
    prestart_workers: bool = True
    worker_register_timeout_s: float = 30.0
    # Zygote worker factory (reference: worker_pool.h PrestartWorkers /
    # StartWorkerProcess): fork CPU workers from a warm pre-imported
    # template (~10ms) instead of a fresh interpreter (~0.25s, >1s under
    # spawn storms). The template never imports JAX, so TPU workers fork
    # from it too.
    forkserver_enabled: bool = True
    # --- task retries / lineage ---
    task_max_retries: int = 3
    actor_max_restarts: int = 0
    lineage_enabled: bool = True
    # --- memory monitor (reference: memory_monitor.h + kill policies) ---
    memory_monitor_refresh_ms: int = 0  # 0 disables
    memory_usage_threshold: float = 0.95
    # --- rpc ---
    rpc_connect_timeout_s: float = 10.0
    rpc_max_message_bytes: int = 512 << 20
    # Native task-path fast lane (_native/fastlane.cpp): framing, reply
    # correlation, and the submit/receive pump run in C++ threads off the
    # asyncio loops; simple tasks execute without touching the loop at
    # all (reference: the C++ lease/push pipeline,
    # normal_task_submitter.cc:24, server_call.h).
    fastlane_enabled: bool = True
    # GIL switch interval applied in every ray_tpu process (0 = leave
    # Python's 5 ms default). Sub-ms keeps the io loop responsive while
    # the executor thread runs user code — the Python substitute for the
    # reference's dedicated C++ io threads. Matters most on few-core hosts.
    gil_switch_interval_s: float = 0.001
    # --- chaos / testing (reference: src/ray/common/asio/asio_chaos.h) ---
    # "handler_name=delay_us,..." — injects latency into named control-plane
    # handlers for deterministic race amplification.
    testing_rpc_delay: str = ""
    # --- logging / observability ---
    log_dir: str = ""
    # Stream worker stdout/stderr to the driver console via the raylet
    # log monitor + GCS pubsub (reference: log_monitor.py).
    log_to_driver: bool = True
    task_events_enabled: bool = True
    task_events_max_buffer: int = 10000
    # Events per report batch: bigger batches = fewer GCS round trips on
    # the submission hot path (reference: task_events_report_interval_ms
    # batching in TaskEventBuffer).
    task_events_batch_size: int = 1000
    metrics_report_interval_ms: int = 2000
    # --- session ---
    temp_dir: str = "/tmp/ray_tpu"

    @classmethod
    def from_env(cls, system_config: Optional[dict] = None) -> "Config":
        cfg = cls()
        for f in dataclasses.fields(cls):
            env_key = "RAY_TPU_" + f.name.upper()
            if env_key in os.environ:
                raw = os.environ[env_key]
                setattr(cfg, f.name, _coerce(raw, f.type))
        if system_config:
            for k, v in system_config.items():
                if not hasattr(cfg, k):
                    raise ValueError(f"Unknown system_config key: {k}")
                setattr(cfg, k, v)
        return cfg

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        return cls(**d)

    def to_json(self) -> str:
        return json.dumps(self.to_dict())


def _coerce(raw: str, typ) -> object:
    t = str(typ)
    if "int" in t:
        return int(raw)
    if "float" in t:
        return float(raw)
    if "bool" in t:
        return raw.lower() in ("1", "true", "yes")
    return raw


_rpc_delays: Optional[dict] = None


def get_rpc_delay_us(handler: str, config: Optional[Config] = None) -> int:
    """Chaos hook: per-handler injected delay, parsed once.

    Reference: src/ray/common/asio/asio_chaos.h:20 (RAY_testing_asio_delay_us).
    """
    global _rpc_delays
    if _rpc_delays is None:
        spec = (config.testing_rpc_delay if config else
                os.environ.get("RAY_TPU_TESTING_RPC_DELAY", ""))
        _rpc_delays = {}
        for part in spec.split(","):
            if "=" in part:
                name, us = part.split("=", 1)
                _rpc_delays[name.strip()] = int(us)
    return _rpc_delays.get(handler, 0)

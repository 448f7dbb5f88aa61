"""Worker process entrypoint.

Equivalent of the reference's default_worker.py (python/ray/_private/
workers/default_worker.py): spawned by the raylet, connects back, serves
push_task RPCs until told to exit. The raylet holds every worker to the
CPU backend (JAX_PLATFORMS=cpu) except the one leased to TPU work, which
sees every chip of the host: per-lease chip visibility
(accelerators.set_visible_chips_env) is not wired in.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os


def main() -> None:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s worker %(levelname)s %(message)s")
    # Driver sys.path (shipped via the raylet) so functions pickled by
    # reference from driver-side modules (e.g. test files) import here.
    import sys

    for p in reversed(
            os.environ.get("RAY_TPU_DRIVER_SYS_PATH", "").split(":")):
        if p and p not in sys.path:
            sys.path.insert(0, p)
    from ray_tpu.core.config import Config
    from ray_tpu.core.ids import NodeID, WorkerID
    from ray_tpu._private.core_worker import WORKER, CoreWorker

    async def amain():
        import time as _time

        trace = os.environ.get("RAY_TPU_TRACE_STARTUP")
        t_start = _time.time()

        def tr(msg):
            if trace:
                print(f"TRACE {os.getpid()} +{_time.time() - t_start:.3f} "
                      f"{msg}", flush=True)

        tr("amain begin")
        cfg_json = os.environ.get("RAY_TPU_CONFIG_JSON")
        config = Config.from_dict(json.loads(cfg_json)) if cfg_json \
            else Config.from_env()
        cw = CoreWorker(
            mode=WORKER,
            gcs_address=os.environ["RAY_TPU_GCS_ADDRESS"],
            config=config,
            loop=asyncio.get_running_loop(),
            raylet_address=os.environ["RAY_TPU_RAYLET_ADDRESS"],
            store_path=os.environ.get("RAY_TPU_STORE_PATH"),
            node_id=NodeID.from_hex(os.environ["RAY_TPU_NODE_ID"]),
            session_dir=os.environ.get("RAY_TPU_SESSION_DIR", "/tmp/ray_tpu"),
            worker_id=WorkerID.from_hex(os.environ["RAY_TPU_WORKER_ID"]),
        )
        # Make this worker the process-global worker so user code running in
        # tasks can call ray_tpu.get/put/remote recursively.
        from ray_tpu._private import worker as worker_mod

        worker_mod._attach_executor_worker(cw)
        tr("connecting")
        await cw.connect()
        tr("connected (registered with raylet)")
        await cw._should_exit.wait()
        await cw.disconnect()

    profile_dir = os.environ.get("RAY_TPU_WORKER_PROFILE")
    if profile_dir:
        import signal
        import sys as _sys

        signal.signal(signal.SIGTERM, lambda *_: _sys.exit(0))
        # Debug aid: cProfile the whole worker (loop thread) and dump
        # stats at exit — the only way to see inside spawned workers in
        # environments without py-spy/perf.
        import cProfile

        prof = cProfile.Profile()
        try:
            prof.runcall(asyncio.run, amain())
        finally:
            os.makedirs(profile_dir, exist_ok=True)
            prof.dump_stats(os.path.join(
                profile_dir, f"worker_{os.getpid()}.prof"))
    else:
        asyncio.run(amain())


if __name__ == "__main__":
    main()

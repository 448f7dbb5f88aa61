"""Forkserver (zygote) worker factory.

TPU-native equivalent of the reference's worker prestart/reuse machinery
(src/ray/raylet/worker_pool.h:359 ``PrestartWorkers``, :425
``StartWorkerProcess``): instead of paying the Python interpreter + import
cold start (~0.25 s solo, >1 s under spawn storms — round-3 root cause)
for every worker, the raylet keeps ONE warm template process with the
worker's import graph already loaded and asks it to ``fork()`` children:
~10 ms per worker, constant under storms.

Protocol (template stdin/stdout, length-prefixed msgpack):
  request : {"env": {str: str}, "log_path": str}
  reply   : {"pid": int}  |  {"error": str}

Design constraints honored here:
- The template stays SINGLE-THREADED and never starts an event loop, so
  fork() is safe (threads don't survive fork; the child starts its own
  asyncio loop inside worker_main).
- The template must NOT import jax: a process that has initialised JAX
  holds the chip and its threads, and neither survives a fork. Every
  child imports JAX itself, after the raylet's per-worker env
  (JAX_PLATFORMS=cpu for all but TPU-leased workers) is applied.
- SIGCHLD is SIG_IGN so exited workers are auto-reaped (no zombies);
  the raylet checks liveness by pid.
"""

from __future__ import annotations

import os
import signal
import struct
import sys

_LEN = struct.Struct("<I")


def _read_exact(fd: int, n: int) -> bytes:
    out = b""
    while len(out) < n:
        chunk = os.read(fd, n - len(out))
        if not chunk:
            raise EOFError
        out += chunk
    return out


def _child_main(req: dict) -> None:
    """Runs in the forked child: become a clean worker process."""
    os.setsid()
    log_fd = os.open(req["log_path"],
                     os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    os.dup2(log_fd, 1)
    os.dup2(log_fd, 2)
    if log_fd > 2:
        os.close(log_fd)
    # Detach from the template's control pipe.
    devnull = os.open(os.devnull, os.O_RDONLY)
    os.dup2(devnull, 0)
    if devnull > 2:
        os.close(devnull)
    os.environ.update(req["env"])
    signal.signal(signal.SIGCHLD, signal.SIG_DFL)
    from ray_tpu._private import worker_main

    worker_main.main()


def main() -> None:
    # Auto-reap forked workers; the raylet tracks liveness by pid.
    signal.signal(signal.SIGCHLD, signal.SIG_IGN)
    # Pre-import the worker's module graph ONCE; every fork inherits it.
    import msgpack

    from ray_tpu._private import worker_main  # noqa: F401  (warms imports)

    # Modules the worker only pulls in lazily AFTER fork (profiled in a
    # 16-actor storm: concurrent.futures.thread via the first
    # ThreadPoolExecutor, queue via it, fastlane inside connect()) —
    # import them here so forks inherit the bytecode. Also dlopen the
    # native libs: .so mappings survive fork, saving two dlopens per
    # worker. No threads are created (fork safety); fl_server_create is
    # NOT called here.
    import concurrent.futures.thread  # noqa: F401
    import queue  # noqa: F401

    # Actor creation imports runtime_env inside the handler; on a
    # 1-core box a 32-actor storm pays 32 serialized cold imports
    # (~20 ms each) without this warm-up.
    from ray_tpu._private import runtime_env  # noqa: F401
    from ray_tpu.core import fastlane, shm_client

    try:
        fastlane._load()
        shm_client._load()
    except Exception:
        pass  # workers fall back to loading on demand

    in_fd = 0
    out_fd = 1
    while True:
        try:
            (length,) = _LEN.unpack(_read_exact(in_fd, _LEN.size))
            req = msgpack.unpackb(_read_exact(in_fd, length), raw=False)
        except EOFError:
            return  # raylet closed the pipe: shut down
        try:
            pid = os.fork()
        except OSError as e:
            reply = msgpack.packb({"error": str(e)}, use_bin_type=True)
            os.write(out_fd, _LEN.pack(len(reply)) + reply)
            continue
        if pid == 0:
            code = 0
            try:
                _child_main(req)
            except BaseException:
                # Surface startup failures in the worker log (stderr is
                # the log file once dup2 ran; the template's log before).
                code = 1
                try:
                    import traceback

                    traceback.print_exc()
                    sys.stderr.flush()
                except Exception:
                    pass
            finally:
                os._exit(code)
        reply = msgpack.packb({"pid": pid}, use_bin_type=True)
        os.write(out_fd, _LEN.pack(len(reply)) + reply)


if __name__ == "__main__":
    main()

"""Where JAX's persistent compilation cache lives.

A cold run of the serving engine or the train step at real widths spends
minutes compiling, so every entry point that runs on the chip
(`chip_smoke.py`'s children, `bench.py`, the benchmark's drivers) calls
`enable_compile_cache()` first thing in its `main`, and the raylet gives
a worker leased to TPU work the same directory. Not at import: tests
import those modules and run on the CPU without a cache.

The cache is placed from outside where it can be: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
sets another directory. Otherwise it is ONE fixed directory inside the
checkout — never a temporary name, a pid or a time, because the path is
part of the cache key and a directory that moves never hits.

A process held to the CPU backend (``JAX_PLATFORMS=cpu``: every test,
every worker not leased to TPU work) gets no cache here: it has no chip
programs to keep, and a test that runs an entry point in-process must
not switch a global cache on for the rest of the suite.

The directory does not travel between installations: entries JAX wrote
without ``JAX_COMPILATION_CACHE_MAX_SIZE`` carry no ``-atime`` file, and a
JAX that has the variable set (the chip machine does) then fails every
write into a directory that holds them (`Error writing persistent
compilation cache entry … -atime`). `.chiprunignore` lists `.jax_cache` to
keep the sandbox's copy off the chip machine.

**The compile ledger.** What the cache did for a process is counted
here too, from JAX's own `jax.monitoring` events: `ledger()` is the
process's one `CompileLedger`, installed on first use (and by
`enable_compile_cache()` before it decides anything about a directory).
It keeps, by the jitted function's name, how often a program was built,
the seconds the host spent tracing and lowering it, and whether the
backend build was FETCHED from the persistent cache or COMPILED; and a
bounded ring of the single events under `engine_trace.py`'s discipline
(overwrite the oldest, count the drop). A `jit` call that is already
compiled fires no event, so none of this runs on a decode or train hot
path (`tests/test_perf_gates.py` holds it to that).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Tuple

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_compile_cache_dir() -> Optional[str]:
    """The in-checkout cache directory, or None when
    ``JAX_COMPILATION_CACHE_DIR`` already places the cache or this
    process is held to the CPU backend."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Point this process's JAX at the persistent cache; returns the
    directory set here (None: nothing was set, see above). Whatever it
    decides about a directory, the compile ledger counts from here on."""
    ledger()
    path = default_compile_cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


# -- the compile ledger ------------------------------------------------------

# JAX's events (jax 0.9.0). The three timed ones bracket one stage each
# of building a program (`dispatch.log_elapsed_time`): a scalar event
# under the same name when the stage starts, a duration event when it
# ends; the trace's `fun_name` is the function's own (`my_prog`), the
# other two's the module's (`jit(my_prog)`).
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
# These two fire INSIDE the backend stage of the program they belong to,
# on the compiling thread, before its duration event, and carry no name.
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
_STAGES = {_TRACE: "trace", _LOWER: "lower", _BACKEND: "compile"}

# A serving set-up keeps about 200 records (64 programs x trace, lower,
# build; the thousands of nested traces are not kept); the tier-1 suite
# builds thousands of programs a process.
LEDGER_CAPACITY = 4096

# One ring record: (program, kind, stamp, seconds, hit). `kind` is
# "trace", "lower", "compile" (a backend build the persistent cache did
# not hold) or "fetch" (one it did; seconds = the retrieval); `stamp` is
# `time.perf_counter()` at the event's END, read in the listener (its
# start is that less `seconds`); `hit` is None for a trace or a lowering.
Event = Tuple[str, str, float, float, Optional[bool]]


def _program(fun_name: str) -> str:
    """`jit(my_prog)` -> `my_prog`: one key for a program's three stages."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class CompileLedger:
    """What this process built, by program and as single events.

    Three rules the events force (module docstring of
    `tests/test_compile_ledger.py` shows each):

    - Traces NEST: tracing `my_prog` traces `matmul`, `_reduce_sum` and
      every inner `jit` first, each inside the outer's duration. Only a
      TOP-LEVEL stage (none other open on its thread when it ends) is
      kept; a nested trace's seconds are its parent's already.
    - The backend stage also ends on a persistent-cache HIT (it then
      holds the retrieval). Hit or miss comes from the cache's own event
      inside the stage, never from the duration's size; with no
      persistent cache (every CPU test) there is no such event and the
      build is a miss, compiled.
    - Another thread may build at the same time (a feeder's
      `device_put` beside the main thread's compile): what is open, and
      whether the cache hit, is kept per thread; the tables under a lock.
    """

    def __init__(self, capacity: int = LEDGER_CAPACITY):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.events_dropped = 0
        self.builds = 0          # backend builds, helper programs too
        self.misses = 0          # of them, not held by the cache
        self.compile_s = 0.0     # backend seconds of the misses
        self._buf: List[Optional[Event]] = [None] * capacity
        self._n = 0              # records ever written
        self._by_program: Dict[str, dict] = {}
        self._lock = threading.Lock()
        self._thread = threading.local()

    # -- listeners (jax.monitoring calls them on the building thread) ------

    def _on_start(self, event: str, value=None, **_) -> None:
        if event in _STAGES:
            t = self._thread
            t.open = getattr(t, "open", 0) + 1

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self._thread.hit = True

    def _on_duration(self, event: str, seconds: float, fun_name: str = "",
                     **_) -> None:
        t = self._thread
        if event == _CACHE_FETCH:
            t.fetch_s = seconds
            return
        kind = _STAGES.get(event)
        if kind is None:
            return
        stamp = time.perf_counter()
        t.open = max(0, getattr(t, "open", 0) - 1)
        hit = None
        if kind == "compile":
            hit = getattr(t, "hit", False)
            if hit:
                kind, seconds = "fetch", getattr(t, "fetch_s", seconds)
            t.hit = False
        elif t.open:
            return               # inside another stage: its parent's time
        self._add(_program(fun_name), kind, stamp, seconds, hit)

    def _add(self, program: str, kind: str, stamp: float, seconds: float,
             hit: Optional[bool]) -> None:
        with self._lock:
            if self._n >= self.capacity:
                self.events_dropped += 1
            self._buf[self._n % self.capacity] = (
                program, kind, stamp, seconds, hit)
            self._n += 1
            row = self._by_program.get(program)
            if row is None:
                row = self._by_program[program] = dict(
                    builds=0, trace_s=0.0, lower_s=0.0, compile_s=0.0,
                    fetch_s=0.0, hits=0, misses=0)
            row[kind + "_s"] += seconds
            if hit is not None:
                row["builds"] += 1
                row["hits" if hit else "misses"] += 1
                self.builds += 1
                if not hit:
                    self.misses += 1
                    self.compile_s += seconds

    # -- readers -----------------------------------------------------------

    def __len__(self) -> int:
        return min(self._n, self.capacity)

    def events(self) -> List[Event]:
        """Ring contents, oldest first."""
        with self._lock:
            if self._n <= self.capacity:
                return list(self._buf[:self._n])
            i = self._n % self.capacity
            return self._buf[i:] + self._buf[:i]

    def counters(self) -> Dict[str, float]:
        """The three keys `engine.stats()` carries. They are the
        PROCESS's, not an engine's: a fleet takes them once."""
        return {"compiles_total": float(self.builds),
                "compile_cache_misses_total": float(self.misses),
                "compile_s_total": float(self.compile_s)}

    def report(self) -> List[dict]:
        """The per-program table since the process started, the program
        that cost the most seconds first."""
        with self._lock:
            rows = [{"program": name, **row}
                    for name, row in self._by_program.items()]
        rows.sort(key=lambda r: -(r["trace_s"] + r["lower_s"]
                                  + r["compile_s"] + r["fetch_s"]))
        return rows


_LEDGER: Optional[CompileLedger] = None
_INSTALL = threading.Lock()


def ledger() -> CompileLedger:
    """This process's compile ledger; the first call hands its listeners
    to `jax.monitoring`, once (they stay for the process's life)."""
    global _LEDGER
    if _LEDGER is None:
        with _INSTALL:
            if _LEDGER is None:
                import jax.monitoring as monitoring

                led = CompileLedger()
                monitoring.register_scalar_listener(led._on_start)
                monitoring.register_event_listener(led._on_event)
                monitoring.register_event_duration_secs_listener(
                    led._on_duration)
                _LEDGER = led
    return _LEDGER

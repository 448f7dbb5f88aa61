"""Request-lifecycle tracing for the serving stack.

`EngineTracer` is the per-request observability twin of
`engine_metrics.EngineMetrics`: where metrics aggregate (counters,
window percentiles), the tracer keeps the individual spans — one
bounded ring buffer of (name, req_id, lane, t0, dur, args) records fed
by `DecodeEngine` at the exact seams where the metrics hooks already
fire, and stitched across replicas by `LLMFleet`. `dump_trace()` emits
chrome://tracing complete events through `util.timeline`'s shared
event shape, so an engine trace, a fleet trace and a `ray timeline`
task dump all concatenate into one loadable file.

Design rules (mirroring engine_metrics):

- Zero-cost-when-off. The default is `NULL_TRACER`, a no-op twin with
  ``enabled = False``; every engine hot-path call site guards with
  ``if tr.enabled:`` so the off path never builds an args dict, never
  reads a clock, never allocates. `tests/test_perf_gates.py` pins
  this with a tracemalloc gate. The one sanctioned unguarded call is
  `lane()` (below).
- Engine lanes are ALSO on the profiler's clock. Every engine-lane
  span is opened through ``tracer.lane(name, lane, **args)``, on the
  null tracer too: it enters a `jax.profiler.TraceAnnotation` named
  ``eng.<name>`` with those arguments, so whenever a `jax.profiler`
  session is running the engine's seams appear on the ``/host:CPU``
  plane beside the device's ops, with no knob to turn. With no session
  an annotation would be inert, so the null tracer hands out one
  shared no-op object instead (a 20 ns check, no allocation). The ring additionally gets the span under its
  bare name when the ring is on. Per-request spans are not lexically
  scoped and stay in the ring only, guarded as ever.
- Bounded-memory-when-on. The ring overwrites its OLDEST record when
  full and counts the overwrite in ``events_dropped`` — a long churn
  run keeps the most recent window, never grows without bound.
- Injectable ``clock=`` (monotonic by default), same discipline as
  `EngineMetrics`: tests drive spans on a FakeClock.

Per-request spans are CONTIGUOUS by construction: each request carries
a frontier timestamp (`_req_mark`) advanced by every span emitted for
it, so queue_wait + prefill_chunk* + swap spans + decode_block* sums
exactly to submit->finish wall time — the property `tools/trace_report.py`
and the lifecycle tests lean on.

Env gate: ``RAY_TPU_TRACE=<prefix>`` (the `_private/profiling_hook.py`
pattern) turns tracing on for every engine constructed with
``trace=None`` and dumps ``<prefix>.<engine_id>.<pid>.trace.json`` at
process exit. ``RAY_TPU_PROFILE`` composes independently: it profiles
the host control plane with cProfile, this traces requests — setting
both gets both artifacts.

Span catalogue (name / tid lane / meaning):

- ``queue_wait`` (req): submit -> admission.
- ``prefill_chunk`` (req): one prompt-prefill program (chunked
  prefill emits one span per chunk).
- ``decode_block`` (req): the request's share of one fused decode
  dispatch+drain (args: tokens emitted).
- ``preempt_swap_out`` / ``swap_in`` (req): paged preemption round
  trip.
- ``finish`` / ``shed`` (req): instant markers closing the lifecycle.
- ``dispatch`` / ``host_drain`` (engine lane): one batched program
  launch / one drained token block. ``host_drain`` is the parent of
  ``device_wait`` (the blocking device->host pull alone) and ``emit``
  (the host replay of the block: bookkeeping, retirements, metrics).
- ``prefill_dispatch`` (engine ``dispatch`` lane): one batched prefill
  program launch, inside ``advance_prefills`` (one chunk for every row
  mid-prompt).
- ``dispatch``, ``spec_draft`` and ``prefill_dispatch`` carry
  ``after=``: "" where the device had work when the launch went out,
  else why it had none until this launch (``retire`` / ``admit`` /
  ``chunk`` / ``other``: `DecodeEngine._starved_after`), the cause
  `stats()` counts the gap under (``device_starved_<cause>_s_total``).
- The engine reads its own clock at the two ends of ``admit``,
  ``advance_prefills``, ``dispatch`` / ``spec_draft``,
  ``pipeline_flush``, ``device_wait`` and ``emit`` and keeps the sums in
  `stats()` (``step_*_s_total``, ``device_wait_s``): the same intervals
  as these lanes, on the engine's clock, with no session running.
- ``admit`` (engine ``admit`` lane): the admission loop of one step
  and the row binding / prefix work of what it admitted.
- ``pipeline_flush`` (engine ``drain`` lane): a forced drain of the
  whole in-flight ring.
- ``spec_draft`` (engine ``dispatch`` lane): one speculative dispatch
  — draft proposals + target verify fused in one program (args:
  window, proposed, rows, run_ahead).
- ``spec_draft_prefill`` (engine ``dispatch`` lane): draft-plane
  prompt seeding at admission / swap-in (args: bucket, rows).
- ``spec_verify`` (engine ``drain`` lane): the host-side acceptance
  accounting for one drained speculative block (args: window, rounds,
  proposed, accepted).

Speculative spans ride the ENGINE lanes, not per-request tids — one
spec dispatch serves the whole batch, so attributing it to a request
would break the per-request contiguity sum that `tools/trace_report.py`
leans on; the report aggregates them in a separate engine-lane
speculation summary instead.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

from jax.profiler import TraceAnnotation

from ray_tpu.util.timeline import chrome_complete_event

ENV_TRACE = "RAY_TPU_TRACE"

# Engine-lane spans carry this prefix in a `jax.profiler` trace, which
# keeps them apart from spans other code puts on the same host plane.
PROFILER_PREFIX = "eng."

# Default ring capacity: ~16k spans covers thousands of requests of
# recent history at a few spans per request, at < 2 MiB of host RAM.
DEFAULT_CAPACITY = 16384


class _InertSpan:
    """What the null tracer's `lane()` hands out while no profiler
    session runs: one shared object, so the off path allocates
    nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args) -> None:
        """Arguments known only at the span's end (ring only)."""


_INERT = _InertSpan()


class _ProfilerSpan(TraceAnnotation):
    """An engine-lane span of the null tracer while a `jax.profiler`
    session runs: in the profiler's trace only."""

    __slots__ = ()

    def note(self, **args) -> None:
        pass


class _RingSpan(TraceAnnotation):
    """An engine-lane span in the profiler's trace AND the ring."""

    __slots__ = ("_tr", "_name", "_lane", "_args", "_t0")

    def __init__(self, tracer: "EngineTracer", name: str, lane: str,
                 args: dict):
        super().__init__(PROFILER_PREFIX + name, **args)
        self._tr, self._name, self._lane, self._args = \
            tracer, name, lane, args
        self._t0 = 0.0

    def __enter__(self):
        self._t0 = self._tr.clock()
        super().__enter__()
        return self

    def __exit__(self, *exc):
        super().__exit__(*exc)
        ring = self._tr         # only an EngineTracer builds a _RingSpan
        ring.add(self._name, self._t0, ring.clock() - self._t0,
                 lane=self._lane, args=self._args or None)
        return False

    def note(self, **args) -> None:
        self._args.update(args)


class EngineTracer:
    """Bounded ring buffer of lifecycle spans.

    Records are tuples ``(name, req_id, lane, t0, dur, args)``;
    ``req_id=None`` marks an engine-level span (dispatch / host-drain
    lanes), ``dur=0.0`` an instant marker. `chrome_events()` maps them
    to the trace-viewer layout: pid = this tracer's id (the replica),
    tid = ``req-<id>`` per request or ``engine:<lane>`` for engine
    lanes."""

    enabled = True

    def __init__(self, *, capacity: int = DEFAULT_CAPACITY,
                 clock: Callable[[], float] = time.monotonic,
                 engine_id: Optional[str] = None,
                 dump_path: Optional[str] = None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.clock = clock
        self.engine_id = engine_id or "engine"
        self.dump_path = dump_path
        self.events_dropped = 0
        self._buf: List[Optional[tuple]] = [None] * capacity
        self._n = 0          # records ever written
        # Open spans awaiting their close (queue_wait mostly) and the
        # per-request contiguity frontier. Both are pruned on
        # finish/shed, so they stay O(live + queued requests).
        self._open: Dict[Tuple[str, Any], float] = {}
        self._req_mark: Dict[Any, float] = {}

    # -- primitives --------------------------------------------------------

    def now(self) -> float:
        return self.clock()

    def add(self, name: str, t0: float, dur: float = 0.0,
            req_id: Any = None, lane: Optional[str] = None,
            args: Optional[dict] = None) -> None:
        """Append one record; overwrite the oldest (and count the
        drop) when the ring is full."""
        if self._n >= self.capacity:
            self.events_dropped += 1
        self._buf[self._n % self.capacity] = (
            name, req_id, lane, t0, dur, args)
        self._n += 1

    def instant(self, name: str, req_id: Any = None,
                args: Optional[dict] = None,
                lane: Optional[str] = None) -> None:
        self.add(name, self.clock(), 0.0, req_id, lane, args)

    def lane(self, name: str, lane: str, **args) -> _RingSpan:
        """``with tracer.lane("dispatch", "dispatch", horizon=8):`` —
        one engine-lane span: a `TraceAnnotation` ``eng.<name>`` on the
        profiler's clock and a ring record ``name`` on this tracer's.
        Call sites do NOT guard it (see the module docstring)."""
        return _RingSpan(self, name, lane, args)

    def open(self, name: str, req_id: Any) -> None:
        """Mark the start of a span closed later by `close` (or
        synthesized as still-open at dump time, the `util/timeline.py`
        discipline for hung work)."""
        self._open[(name, req_id)] = self.clock()

    def close(self, name: str, req_id: Any,
              args: Optional[dict] = None) -> float:
        """Emit the span opened by `open`; returns its end time (which
        also becomes the request's contiguity frontier)."""
        t1 = self.clock()
        t0 = self._open.pop((name, req_id), None)
        if t0 is not None:
            self.add(name, t0, t1 - t0, req_id, None, args)
        self._req_mark[req_id] = t1
        return t1

    def mark(self, req_id: Any) -> None:
        """Reset a request's frontier to now (span-less advance)."""
        self._req_mark[req_id] = self.clock()

    def span_since_mark(self, name: str, req_id: Any,
                        args: Optional[dict] = None) -> None:
        """Emit a span from the request's frontier to now and advance
        the frontier — the primitive that keeps each request's spans
        contiguous (durations sum to end-to-end latency)."""
        t1 = self.clock()
        t0 = self._req_mark.get(req_id, t1)
        self.add(name, t0, t1 - t0, req_id, None, args)
        self._req_mark[req_id] = t1

    def finish(self, req_id: Any, args: Optional[dict] = None,
               name: str = "finish") -> None:
        """Instant `finish` (or `shed`) marker + drop the request's
        frontier/open state (bounded bookkeeping under endless
        churn)."""
        self.add(name, self.clock(), 0.0, req_id, None, args)
        self._req_mark.pop(req_id, None)
        for key in [k for k in self._open if k[1] == req_id]:
            del self._open[key]

    # -- introspection / export --------------------------------------------

    def __len__(self) -> int:
        return min(self._n, self.capacity)

    def events(self) -> List[tuple]:
        """Ring contents, oldest first."""
        if self._n <= self.capacity:
            return [e for e in self._buf[:self._n]]
        i = self._n % self.capacity
        return [e for e in self._buf[i:] + self._buf[:i]]

    def chrome_events(self, pid: Any = None) -> List[dict]:
        """Ring -> chrome://tracing complete events (plus synthesized
        still-open spans for anything `open`ed but never closed), in
        timestamp order."""
        pid = self.engine_id if pid is None else pid
        out = []
        for name, req_id, lane, t0, dur, args in self.events():
            tid = (f"req-{req_id}" if req_id is not None
                   else f"engine:{lane or 'events'}")
            out.append(chrome_complete_event(
                name, "request" if req_id is not None else "engine",
                t0, dur, pid, tid, args))
        now = self.clock()
        for (name, req_id), t0 in self._open.items():
            out.append(chrome_complete_event(
                name, "request", t0, now - t0, pid, f"req-{req_id}",
                {"open": True}))
        out.sort(key=lambda e: e["ts"])
        return out

    def dump(self, path: Optional[str] = None,
             pid: Any = None) -> List[dict]:
        """Write (and return) the chrome-trace JSON. ``path=None``
        falls back to the env-gate dump path; with neither, the events
        are just returned."""
        events = self.chrome_events(pid=pid)
        path = path or self.dump_path
        if path:
            with open(path, "w") as f:
                json.dump(events, f)
        return events


class NullEngineTracer:
    """No-op twin: every engine/fleet hot-path call site guards on
    ``enabled`` so the off path costs one attribute read; the methods
    exist so unguarded callers still work."""

    enabled = False
    engine_id = "disabled"
    events_dropped = 0
    dump_path = None

    def now(self) -> float:
        return 0.0

    def add(self, *a, **k) -> None:
        pass

    def instant(self, *a, **k) -> None:
        pass

    def lane(self, name: str, lane: str, **args):
        if not TraceAnnotation.is_enabled():    # no session: ~20 ns
            return _INERT
        return _ProfilerSpan(PROFILER_PREFIX + name, **args)

    def open(self, *a, **k) -> None:
        pass

    def close(self, *a, **k) -> float:
        return 0.0

    def mark(self, *a, **k) -> None:
        pass

    def span_since_mark(self, *a, **k) -> None:
        pass

    def finish(self, *a, **k) -> None:
        pass

    def __len__(self) -> int:
        return 0

    def events(self) -> List[tuple]:
        return []

    def chrome_events(self, pid: Any = None) -> List[dict]:
        return []

    def dump(self, path: Optional[str] = None, pid: Any = None) -> List[dict]:
        return []


NULL_TRACER = NullEngineTracer()


def maybe_tracer_from_env(tag: str,
                          clock: Callable[[], float] = time.monotonic,
                          ) -> Optional[EngineTracer]:
    """`RAY_TPU_TRACE=<prefix>` -> an EngineTracer that dumps
    ``<prefix>.<tag>.<pid>.trace.json`` at process exit (the
    `profiling_hook.maybe_enable_profiler` pattern); None when the
    env gate is off."""
    prefix = os.environ.get(ENV_TRACE)
    if not prefix:
        return None
    import atexit

    tracer = EngineTracer(
        clock=clock, engine_id=tag,
        dump_path=f"{prefix}.{tag}.{os.getpid()}.trace.json")
    atexit.register(tracer.dump)
    return tracer


def resolve_tracer(spec: Union[None, bool, EngineTracer,
                               NullEngineTracer, "EngineTracer"],
                   *, engine_id: str,
                   clock: Callable[[], float] = time.monotonic):
    """The `trace=` knob: an EngineTracer instance is used as-is,
    ``True`` builds one, ``False`` forces off, and ``None`` (the
    default) defers to the RAY_TPU_TRACE env gate."""
    if spec is None:
        # Explicit None check: an EngineTracer defines __len__, so a
        # fresh (empty) one is FALSY — `env_tracer or NULL_TRACER`
        # would silently discard it.
        env_tracer = maybe_tracer_from_env(engine_id, clock)
        return NULL_TRACER if env_tracer is None else env_tracer
    if spec is False:
        return NULL_TRACER
    if spec is True:
        return EngineTracer(clock=clock, engine_id=engine_id)
    return spec

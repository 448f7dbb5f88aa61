"""What both drivers share: the clock, the device check, the compile
counter, host spans, the profiler session and the records handed to the
per-layer readers."""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Dict, List, Optional

now = time.perf_counter        # the benchmark's one host clock, seconds

SPAN_WINDOW = "bench.window"   # the traced stretch, as a host span


def say(**fields) -> None:
    """One JSON note on stdout (the result object is the last line)."""
    print(json.dumps(fields, default=float), flush=True)


def seconds_since_process_start() -> float:
    """Wall seconds since the kernel started this process (imports and
    interpreter start-up included), from /proc."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def require_device(chips: int, rehearse: bool) -> Dict[str, Any]:
    """The device as JAX reports it, or no run: a measuring run needs a
    TPU with exactly the cell's chips. A rehearsal takes what is there."""
    import jax

    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            raise SystemExit(f"benchmark: needs a TPU, JAX found "
                             f"{devs[0].platform!r}; no metric is taken "
                             "on another platform")
        if len(devs) != chips:
            raise SystemExit(f"benchmark: the cell needs {chips} chip(s), "
                             f"JAX found {len(devs)}")
    elif len(devs) < chips:
        raise SystemExit(f"rehearsal needs {chips} devices, found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one past 2**31."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


class CompileWatch:
    """Counts programs JAX compiled (or fetched from the persistent cache:
    either way a shape the warm-up missed) while `armed`."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.in_window = 0
        self.total = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.total += 1
            self.seconds += duration
            if self.armed:
                self.in_window += 1


class Spans:
    """Host spans of the harness: each is a `TraceAnnotation` (so it is in
    the profiler's trace, on the profiler's clock) and a (start, seconds)
    pair on the benchmark's clock."""

    def __init__(self):
        self.by_name: Dict[str, List[tuple]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        from jax.profiler import TraceAnnotation

        t0 = now()
        with TraceAnnotation(name):
            yield
        self.by_name.setdefault(name, []).append((t0, now() - t0))

    def durations(self, name: str, t0: float, t1: float) -> List[float]:
        return [d for s, d in self.by_name.get(name, []) if t0 <= s < t1]


class ProfilerSession:
    """One traced stretch of the window. The Python tracer is off: it
    multiplies the host's work; host `TraceAnnotation`s and the device
    stay on."""

    def __init__(self, out_dir: str):
        self.dir = out_dir
        self.t_begin: Optional[float] = None
        self.t_end: Optional[float] = None
        self._ann = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(SPAN_WINDOW)
        self._ann.__enter__()
        self.t_begin = now()

    def stop(self) -> None:
        import jax

        self.t_end = now()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @property
    def active(self) -> bool:
        return self.t_begin is not None and self.t_end is None


def reduce_trace(session: ProfilerSession, span_names: List[str]) -> dict:
    """The traced stretch as numbers: per chip busy seconds, the window,
    the contract's `breakdown`, and the trace itself for the readers."""
    from benchmark.harness import xplane

    trace = xplane.load(xplane.find_xplane(session.dir),
                        host_names=span_names + [SPAN_WINDOW])
    window = xplane.span_window(trace.host, SPAN_WINDOW)
    if window is None:
        raise RuntimeError("the trace holds no bench.window span")
    busy = {chip: xplane.busy_ns(lines.get(xplane.OPS_LINE, []), window)
            for chip, lines in trace.devices.items()}
    if not busy or max(busy.values()) == 0:
        raise RuntimeError("no operation ran on a device in the traced "
                           "window")
    worst = min(busy, key=busy.get)          # the idlest chip
    ops = trace.devices[worst].get(xplane.OPS_LINE, [])
    return {
        "trace": trace, "window": window,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "busy_s_by_chip": {c: b / 1e9 for c, b in busy.items()},
        "idlest_chip": worst,
        "breakdown": {
            "device_ops": xplane.top_ops(ops, window),
            "idle_gaps": xplane.idle_gaps(ops, trace.host, window,
                                          span_names)},
    }

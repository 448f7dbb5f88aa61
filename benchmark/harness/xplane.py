"""From a profiler trace (.xplane.pb) to the numbers the benchmark prints.

Read with nothing but JAX (`jax.profiler.ProfileData`). What a TPU trace
of this installation holds (looked at by hand, PERF.md section 3):

- one plane per chip, "/device:TPU:<n>". Its line "XLA Ops" carries one
  event per executed HLO op, NESTED: a `%while` or `%call` event spans
  the events of its body, so sums are taken over leaves (`leaves`). An
  event's name is the op's whole HLO text ("%fusion.3 = bf16[..] fusion(
  ..), kind=kLoop, ..."); a Pallas kernel is a `custom-call` whose text
  holds `custom_call_target="tpu_custom_call"` and nothing of the
  kernel's own name. "XLA Modules" has one event per execution of a
  jitted program, named "jit_<function>(<fingerprint>)", one fingerprint
  per compiled shape. "Async XLA Ops" has the start..done stretch of
  asynchronous copies and collectives.
- "/host:CPU": one line per host thread; `TraceAnnotation` spans of the
  harness appear on the thread that made them ("main/<tid>") by name, on
  the same clock as the device planes.

Busy time is the union of the op intervals of a chip; a module's or a
kernel's time is the sum of its events' durations. All functions take
plain lists of (name, start_ns, duration_ns) so that tests can feed them
hand-made events.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]            # name, start_ns, duration_ns
Interval = Tuple[int, int]              # start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PALLAS_KERNEL = r'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast", re.I)


@dataclasses.dataclass
class Trace:
    devices: Dict[int, Dict[str, List[Event]]]   # chip -> line -> events
    host: List[Event]                            # every host-thread event


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, host_names: Optional[Iterable[str]] = None) -> Trace:
    """Device lines in full; of the host plane only events whose name is
    in `host_names` (all of them when None)."""
    from jax.profiler import ProfileData

    keep = None if host_names is None else set(host_names)
    data = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = devices.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if keep is None or e.name in keep:
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    return Trace(devices=devices, host=host)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(events: Iterable[Event], window: Interval) -> List[Interval]:
    w0, w1 = window
    return [(max(s, w0), min(s + d, w1)) for _, s, d in events
            if s < w1 and s + d > w0]


def total_ns(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of merged `a` not covered by merged `b`."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events of a nested line that span no other event: an op's own
    time, without the loops and calls that merely contain ops."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, e in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][1] < e[1] + e[2] \
                and evs[i + 1][1] + evs[i + 1][2] <= e[1] + e[2]:
            continue                      # the next event lies inside it
        out.append(e)
    return out


def short_name(name: str, limit: int = 96) -> str:
    """"%closed_call.16 custom-call bf16[32,8,4,128] tpu_custom_call" from
    an op's whole HLO text; other names unchanged (cut to `limit`)."""
    if name.startswith("%") and " = " in name:
        lhs, rhs = name.split(" = ", 1)
        m = re.search(r"[\s}]([a-z][a-z0-9\-]*)\(", rhs)
        shape = rhs.split("{", 1)[0].strip()
        name = " ".join([lhs, m.group(1) if m else "", shape] + (
            ["tpu_custom_call"] if re.search(PALLAS_KERNEL, rhs) else []))
    return name[:limit]


def span_window(host: Sequence[Event], name: str) -> Optional[Interval]:
    """[start, end] of the first host span called `name`."""
    for n, s, d in host:
        if n == name:
            return (s, s + d)
    return None


def busy_ns(ops: Sequence[Event], window: Interval) -> int:
    return total_ns(merge(clip(ops, window)))


def sum_matching(events: Sequence[Event], pattern: str, window: Interval
                 ) -> Tuple[int, int]:
    """(summed duration in ns, count) of events whose name matches and
    which start inside the window."""
    rx = re.compile(pattern)
    hits = [d for n, s, d in events
            if window[0] <= s < window[1] and rx.search(n)]
    return sum(hits), len(hits)


def sum_within(events: Sequence[Event], pattern: str,
               modules: Sequence[Event], module_pattern: str,
               window: Interval) -> Tuple[int, int]:
    """`sum_matching` over the events that start inside an execution of a
    module whose name matches `module_pattern`."""
    rx = re.compile(module_pattern)
    spans = merge((s, s + d) for n, s, d in modules if rx.search(n))
    inside = [e for e in events
              if any(a <= e[1] < b for a, b in spans)]
    return sum_matching(inside, pattern, window)


def exposed_collective_ns(ops: Sequence[Event], window: Interval) -> int:
    """Time inside the window in which a collective op runs on this chip
    and no other op does."""
    def is_coll(e):
        return bool(COLLECTIVE.search(e[0].split("(", 1)[0]))

    ops = leaves(ops)
    coll = merge(clip([e for e in ops if is_coll(e)], window))
    comp = merge(clip([e for e in ops if not is_coll(e)], window))
    return total_ns(subtract(coll, comp))


def top_ops(ops: Sequence[Event], window: Interval, k: int = 10
            ) -> List[List]:
    """[short name, seconds] of the k ops with most summed device time of
    their own (leaves: a loop is not charged its body's time)."""
    acc: Dict[str, int] = {}
    for n, s, d in leaves(ops):
        if window[0] <= s < window[1]:
            n = short_name(n)
            acc[n] = acc.get(n, 0) + d
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in best]


def idle_gaps(ops: Sequence[Event], host: Sequence[Event],
              window: Interval, names: Sequence[str], k: int = 10
              ) -> List[List]:
    """Idle time of a chip inside the window, by what the host was doing:
    each idle stretch is split among the harness spans (of `names`, the
    first name winning where two overlap) that cover it; what no span
    covers is "uncovered". Returns [name, seconds], largest first."""
    gaps = subtract([window], merge(clip(ops, window)))
    acc: Dict[str, int] = {}
    left = gaps
    for name in names:
        spans = merge(clip([e for e in host if e[0] == name], window))
        rest = subtract(left, spans)
        acc[name] = total_ns(left) - total_ns(rest)
        left = rest
    acc["uncovered"] = total_ns(left)
    best = sorted(((n, v) for n, v in acc.items() if v > 0),
                  key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in best]


def summary(trace: Trace, k: int = 40) -> dict:
    """What a trace holds, for reading by hand: per chip and line the
    event count and the names with most time; host span names."""
    out: dict = {"devices": {}, "host": {}}
    for chip, lines in sorted(trace.devices.items()):
        out["devices"][chip] = {}
        for line, evs in lines.items():
            acc: Dict[str, List[int]] = {}
            for n, _, d in (leaves(evs) if line == OPS_LINE else evs):
                a = acc.setdefault(short_name(n, 160), [0, 0])
                a[0] += d
                a[1] += 1
            best = sorted(acc.items(), key=lambda kv: -kv[1][0])[:k]
            out["devices"][chip][line] = {
                "events": len(evs),
                "top": [[n, v[0] / 1e9, v[1]] for n, v in best]}
    acc = {}
    for n, _, d in trace.host:
        a = acc.setdefault(n, [0, 0])
        a[0] += d
        a[1] += 1
    out["host"] = {n: [v[0] / 1e9, v[1]] for n, v in sorted(
        acc.items(), key=lambda kv: -kv[1][0])[:k]}
    return out

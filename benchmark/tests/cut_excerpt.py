"""Cuts a small excerpt out of a recorded .xplane.pb for tests/data:

    python3 -m benchmark.tests.cut_excerpt <in.xplane.pb> <out.xplane.pb> \
        <module name pattern> [<executions>]

keeps, of every chip's plane, the "XLA Modules" and "XLA Ops" events from
the start of the first execution of a module whose name matches to the
end of the `executions`-th module after it (default 2), with the event
metadata those events use (name, and of the stats only `tf_op`, the op's
scope path, which harness/scopes.py reads), and of the host plane the
spans of the harness and the engine that overlap that stretch, cut to
it. Times are kept as recorded.
Protobuf wire format by hand, as make_synthetic_trace.py writes it.
"""

import re
import sys

from benchmark.harness import scopes, xplane
from benchmark.tests.make_synthetic_trace import field, varint

KEEP_LINES = (xplane.MODULES_LINE, xplane.OPS_LINE)
HOST_SPANS = re.compile(r"^(eng\..*|bench\.window|engine\.step|submit|"
                        r"idle_no_request|train\.step|feed_batch)$")


def _raw(num, view):
    return varint(num << 3 | 2) + varint(len(view)) + bytes(view)


def _event(view):
    """(metadata id, offset_ps, duration_ps) of an XEvent."""
    mid = off = dur = 0
    for num, wt, val in scopes.wire_fields(view):
        if wt == 0 and num == 1:
            mid = val
        elif wt == 0 and num == 2:
            off = val
        elif wt == 0 and num == 3:
            dur = val
    return mid, off, dur


def _line(view):
    name, ts, events = "", 0, []
    for num, wt, val in scopes.wire_fields(view):
        if num == 2 and wt == 2:
            name = scopes.wire_text(val)
        elif num == 3 and wt == 0:
            ts = val
        elif num == 4 and wt == 2:
            events.append(_event(val))
    return name, ts, events


def _plane(view):
    out = {"id": 0, "name": "", "lines": [], "events": {}, "stats": {}}
    for num, wt, val in scopes.wire_fields(view):
        if num == 1 and wt == 0:
            out["id"] = val
        elif num == 2 and wt == 2:
            out["name"] = scopes.wire_text(val)
        elif num == 3 and wt == 2:
            out["lines"].append(_line(val))
        elif num == 4 and wt == 2:
            key, meta = scopes.map_entry(val)
            out["events"][key] = meta
        elif num == 5 and wt == 2:
            key, meta = scopes.map_entry(val)
            out["stats"][key] = meta
    return out


def _meta_name(meta):
    for num, wt, val in scopes.wire_fields(meta):
        if num == 2 and wt == 2:
            return scopes.wire_text(val)
    return ""


def _slim_meta(key, meta, keep_stats):
    """The event metadata with only its id, name and wanted stats."""
    body = field(1, key)
    for num, wt, val in scopes.wire_fields(meta):
        if num == 2 and wt == 2:
            body += _raw(2, val)
        elif num == 5 and wt == 2:
            sid = next((v for n, w, v in scopes.wire_fields(val)
                        if n == 1 and w == 0), None)
            if sid in keep_stats:
                body += _raw(5, val)
    return body


def _write_plane(p, lines, used):
    stat_name = {k: _meta_name(m) for k, m in p["stats"].items()}
    keep = {k for k, n in stat_name.items() if n == scopes.OP_NAME_STAT}
    body = field(1, p["id"]) + field(2, p["name"])
    for lid, (name, ts, events) in enumerate(lines, 1):
        ln = field(1, lid) + field(2, name) + field(3, ts)
        for mid, off, dur in events:
            ln += field(4, field(1, mid) + field(2, off) + field(3, dur))
        body += field(3, ln)
    refs = set()
    for key in sorted(used):
        meta = _slim_meta(key, p["events"][key], keep)
        for num, wt, val in scopes.wire_fields(memoryview(meta)):
            if num == 5 and wt == 2:
                refs |= {v for n, w, v in scopes.wire_fields(val)
                         if w == 0 and n in (1, 7)}
        body += field(4, field(1, key) + field(2, meta))
    for key in sorted(refs & set(p["stats"])):
        body += field(5, field(1, key) + _raw(2, p["stats"][key]))
    return field(1, body)


def cut(src: str, dst: str, module_pattern: str, executions: int = 2):
    with open(src, "rb") as f:
        space = memoryview(f.read())
    planes = [_plane(v) for n, w, v in scopes.wire_fields(space)
              if n == 1 and w == 2]
    rx = re.compile(module_pattern)
    t0 = t1 = None
    for p in planes:
        if not xplane.DEVICE_PLANE.match(p["name"]):
            continue
        for name, ts, events in p["lines"]:
            if name != xplane.MODULES_LINE:
                continue
            evs = sorted((ts * 1000 + off, dur, mid)
                         for mid, off, dur in events)
            for i, (s, d, mid) in enumerate(evs):
                if rx.search(_meta_name(p["events"][mid])):
                    last = evs[min(i + executions - 1, len(evs) - 1)]
                    t0, t1 = s, last[0] + last[1]
                    break
        break
    if t0 is None:
        raise SystemExit(f"no module matches {module_pattern!r}")
    out = b""
    for p in planes:
        device = bool(xplane.DEVICE_PLANE.match(p["name"]))
        if not device and p["name"] != "/host:CPU":
            continue
        lines, used = [], set()
        for name, ts, events in p["lines"]:
            if device and name not in KEEP_LINES:
                continue
            kept = []
            for mid, off, dur in events:
                s = ts * 1000 + off
                nm = _meta_name(p["events"][mid])
                if not device and not HOST_SPANS.match(nm):
                    continue
                if not device and s < t1 and s + dur > t0:
                    end = min(s + dur, t1)       # cut to the stretch
                    s = max(s, t0)
                    off, dur = s - ts * 1000, end - s
                if t0 <= s and s + dur <= t1:
                    kept.append((mid, off, dur))
                    used.add(mid)
            if kept:
                lines.append((name, ts, kept))
        out += _write_plane(p, lines, used)
    with open(dst, "wb") as f:
        f.write(out)
    return (t1 - t0) / 1e12


if __name__ == "__main__":
    n = int(sys.argv[4]) if len(sys.argv) > 4 else 2
    print("stretch of", cut(sys.argv[1], sys.argv[2], sys.argv[3], n), "s")

"""Writes data/synthetic.xplane.pb: a hand-made XSpace whose every number
the tests work out by hand. Protobuf wire format, written here so that no
profiler library is needed (tensorflow/tsl/profiler/protobuf/xplane.proto:
XSpace.planes=1; XPlane.id=1,name=2,lines=3,event_metadata=4 (map<int64,
XEventMetadata>); XLine.id=1,name=2,timestamp_ns=3,events=4;
XEvent.metadata_id=1,offset_ps=2,duration_ps=3; XEventMetadata.id=1,name=2).

Times below are microseconds from the trace's start.

chip 0, "XLA Ops":  fusion.1 [0,100)  all-gather.2 [80,150)  custom-call.3
                    (paged kernel) [200,260)  fusion.1 [300,400)
                    all-reduce.4 [400,450)
        "XLA Modules": jit__decode_multi_paged(1) [0,150)
                    jit__prefill_rows_paged(2) [200,450)
chip 1, "XLA Ops":  fusion.1 [0,50)  all-gather.2 [50,250)
host:   bench.window [0,500)  engine.step [0,180) [190,470)
        idle_no_request [470,500)
"""

import os
import sys

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def plane(pid: int, name: str, lines: dict, unit_ns: int = 1,
          line_start_ns: int = 0) -> bytes:
    """An XPlane of lines {line name: [(event name, start, end)]}, times
    in `unit_ns` nanoseconds after the line's own `line_start_ns`."""
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    body = field(1, pid) + field(2, name)
    for lid, (lname, evs) in enumerate(lines.items(), 1):
        line = field(1, lid) + field(2, lname) + field(3, line_start_ns)
        for n, start, end in evs:
            line += field(4, field(1, ids[n])
                          + field(2, start * unit_ns * 1000)
                          + field(3, (end - start) * unit_ns * 1000))
        body += field(3, line)
    for n, i in ids.items():
        body += field(4, field(1, i) + field(2, field(1, i) + field(2, n)))
    return field(1, body)


def _us(pid, name, lines):
    return plane(pid, name, lines, unit_ns=1000, line_start_ns=1000)


SPACE = (
    _us(1, "/device:TPU:0", {
        "XLA Ops": [("fusion.1", 0, 100), ("all-gather.2", 80, 150),
                    ("custom-call.3", 200, 260), ("fusion.1", 300, 400),
                    ("all-reduce.4", 400, 450)],
        "XLA Modules": [("jit__decode_multi_paged(1)", 0, 150),
                        ("jit__prefill_rows_paged(2)", 200, 450)]})
    + _us(2, "/device:TPU:1", {
        "XLA Ops": [("fusion.1", 0, 50), ("all-gather.2", 50, 250)]})
    + _us(3, "/host:CPU", {
        "main": [("bench.window", 0, 500), ("engine.step", 0, 180),
                 ("engine.step", 190, 470),
                 ("idle_no_request", 470, 500)]}))

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "synthetic.xplane.pb")

if __name__ == "__main__" and len(sys.argv) == 1:
    with open(PATH, "wb") as f:
        f.write(SPACE)
    print(PATH, len(SPACE), "bytes")


def excerpt(src: str, dst: str, t0_ms: float, t1_ms: float) -> None:
    """Re-encode the events of a recorded trace that start inside
    [t0_ms, t1_ms) after the first device event (device lines "XLA Ops"
    and "XLA Modules", the harness's spans on the host): names and times
    kept to the nanosecond, stats dropped. How data/chat_excerpt.xplane.pb
    was cut from a 3.6 MB trace of mistral7b-chat (PR 23)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(src)
    first = min(e.start_ns for p in data.planes
                if p.name.startswith("/device:TPU:")
                for l in p.lines for e in l.events)
    lo, hi = first + t0_ms * 1e6, first + t1_ms * 1e6
    out = b""
    for pid, p in enumerate(data.planes, 1):
        device = p.name.startswith("/device:TPU:")
        if not device and p.name != "/host:CPU":
            continue
        lines = {}
        for l in p.lines:
            if device and l.name not in ("XLA Ops", "XLA Modules"):
                continue
            evs = [(e.name, int(round(e.start_ns - lo)),
                    int(round(e.start_ns - lo)) + int(round(e.duration_ns)))
                   for e in l.events
                   if lo <= e.start_ns < hi and (device or e.name in HOST)]
            if evs:
                lines[l.name] = evs
        out += plane(pid, p.name, lines)
    with open(dst, "wb") as f:
        f.write(out)
    print(dst, len(out), "bytes")


HOST = ("bench.window", "engine.step", "submit", "idle_no_request")


if __name__ == "__main__" and len(sys.argv) > 1 and sys.argv[1] == "excerpt":
    excerpt(sys.argv[2], sys.argv[3], float(sys.argv[4]), float(sys.argv[5]))

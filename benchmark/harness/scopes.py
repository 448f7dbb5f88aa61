"""From a device op in a trace to the part of the program it belongs to.

The program names its parts itself: every jitted program of
`ray_tpu.models` wraps them in `jax.named_scope` and every Pallas kernel
has a `name=` (the one list is `ray_tpu/ops/scope_names.py`). XLA keeps a
scope as the op's `op_name`, a path like

    jit(_decode_multi_paged)/while/body/closed_call/while/body/closed_call/kv_write/scatter

and the backward pass wraps a component in what made it
(`transpose(jvp(mlp))`), and JAX itself adds `checkpoint/
rematted_computation` around a forward that the backward pass runs again.

**Where a trace holds it** (looked at by hand on this installation, PR
24): an "XLA Ops" event's name is the op's HLO text without metadata, and
its own stats are times only. The `op_name` is a stat called `tf_op` on
the event's METADATA (`XPlane.event_metadata[id].stats`, one entry per
distinct op of a chip's plane, keyed by the same name the event carries),
which `jax.profiler.ProfileData` does not show and `xplane.load` therefore
drops. So this file reads the `.xplane.pb` once more, as protobuf wire
format with nothing but the standard library (the five messages of
tensorflow/tsl/profiler/protobuf/xplane.proto that are needed), and
returns for each chip `{event name: op_name}`. An op the compiler made
itself (a copy of the KV pool, a layout change) has no `tf_op` at all.

**The reader contract.** A reader takes `op_names(path)[chip]`, looks an
event of `trace.devices[chip]["XLA Ops"]` up by its name, and asks
`scope_of(op_name)` for the innermost component that is one of the
program's scopes (`kernel_of` for the kernel names, `is_remat` for JAX's
recomputation mark). Sums are over `xplane.leaves`, as everywhere. `None`
from `scope_of` means the op is under no scope of the program's: either
the program did not write it (the layer scan's slicing of its stacked
operands, copies the compiler inserts) or the program has no scopes. A
reader returns `None` for its metric when no op of the module it reads
carries any scope (a program from before PR 24, or one fetched from a
compilation cache that an older commit filled: JAX's cache key leaves
metadata out) and when `SCOPES` is `None` (the program has no
`ray_tpu/ops/scope_names.py`).

    python3 -m benchmark.harness.scopes <trace dir or .xplane.pb>

prints, for reading by hand, device time by scope for each jitted
program, the kernels by name, the recomputed share, and the chip's idle
time by the engine's own `eng.*` host spans.
"""

from __future__ import annotations

import functools
import json
import os
import re
import sys
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

from benchmark.harness import xplane

try:                                    # the program's own list
    from ray_tpu.ops.scope_names import KERNELS, KV_MOVE, SCOPES
except ImportError:                     # a program from before PR 24
    KERNELS = KV_MOVE = SCOPES = None

REMAT = "rematted_computation"          # JAX's, jax/_src/ad_checkpoint.py
ENGINE_SPAN_PREFIX = "eng."
OP_NAME_STAT = "tf_op"
# HLO opcodes that only move or relabel bytes: what an op under no scope
# of the program's usually is.
MOVES = ("copy", "copy-start", "copy-done", "bitcast", "reshape",
         "transpose", "slice", "dynamic-slice", "dynamic-update-slice",
         "concatenate", "pad", "broadcast", "constant", "tuple",
         "get-tuple-element")


# -- protobuf wire format ----------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def wire_fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview, a varint an int, fixed-width values are skipped
    over as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} in an xplane file")
        yield num, wt, val


def wire_text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def map_entry(view) -> Tuple[int, Optional[memoryview]]:
    key, value = 0, None
    for num, wt, val in wire_fields(view):
        if num == 1 and wt == 0:
            key = val
        elif num == 2 and wt == 2:
            value = val
    return key, value


def _plane_op_names(plane) -> Tuple[Optional[int], Dict[str, str]]:
    """(chip, {event metadata name: its tf_op}) of a device plane, (None,
    {}) of any other. XPlane: name=2,
    event_metadata=4, stat_metadata=5; XEventMetadata: name=2, stats=5;
    XStatMetadata: name=2; XStat: metadata_id=1, str_value=5,
    ref_value=7 (a string kept once, as a stat metadata's name)."""
    name = ""
    events: List[memoryview] = []
    stat_names: Dict[int, str] = {}
    for num, wt, val in wire_fields(plane):
        if num == 2 and wt == 2:
            name = wire_text(val)
        elif num == 4 and wt == 2:
            events.append(map_entry(val)[1])
        elif num == 5 and wt == 2:
            key, meta = map_entry(val)
            for n2, w2, v2 in wire_fields(meta) if meta is not None else ():
                if n2 == 2 and w2 == 2:
                    stat_names[key] = wire_text(v2)
    chip = xplane.DEVICE_PLANE.match(name)
    if not chip:
        return None, {}
    wanted = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
    out: Dict[str, str] = {}
    for meta in events:
        ev_name, op_name = "", None
        for num, wt, val in wire_fields(meta) if meta is not None else ():
            if num == 2 and wt == 2:
                ev_name = wire_text(val)
            elif num == 5 and wt == 2:
                sid, sval = 0, None
                for n2, w2, v2 in wire_fields(val):
                    if n2 == 1 and w2 == 0:
                        sid = v2
                    elif n2 == 5 and w2 == 2:
                        sval = wire_text(v2)
                    elif n2 == 7 and w2 == 0:
                        sval = stat_names.get(v2)
                if sid in wanted and sval:
                    op_name = sval
        if op_name is not None:
            out[ev_name] = op_name.rstrip(":")
    return int(chip.group(1)), out


@functools.lru_cache(maxsize=4)
def op_names(path: str) -> Dict[int, Dict[str, str]]:
    """{chip: {"XLA Ops" event name: op_name}} of one .xplane.pb."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[int, Dict[str, str]] = {}
    for num, wt, plane in wire_fields(space):
        if num == 1 and wt == 2:
            chip, names = _plane_op_names(plane)
            if chip is not None:
                out[chip] = names
    return out


# -- from an op_name to a scope ----------------------------------------------

_IDENT = re.compile(r"[A-Za-z_][A-Za-z0-9_\-]*")


def components(op_name: Optional[str]) -> List[str]:
    """The path's components, each freed of the transformations wrapped
    around it: "transpose(jvp(mlp))" -> "mlp", "jit(step_fn)" ->
    "step_fn"; "bsd,df->bsf" and "dot_general" stay."""
    out = []       # a fused op may carry several paths, "a/b;a/c": the first
    for part in (op_name or "").split(";")[0].split("/"):
        if part.endswith(")"):
            ids = _IDENT.findall(part)
            part = ids[-1] if ids else ""
        if part:
            out.append(part)
    return out


def innermost(op_name: Optional[str], names: Optional[Iterable[str]]
              ) -> Optional[str]:
    if not names:
        return None
    names = set(names)
    for part in reversed(components(op_name)):
        if part in names:
            return part
    return None


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost scope of the program's an op lies under, or None."""
    return innermost(op_name, SCOPES)


def kernel_of(event_name: str, op_name: Optional[str]) -> Optional[str]:
    """The `pallas_call(name=)` of a Pallas kernel's event; "" for a
    kernel with no name of the program's; None for any other op."""
    if not re.search(xplane.PALLAS_KERNEL, event_name):
        return None
    return innermost(op_name, KERNELS) or ""


def is_remat(op_name: Optional[str]) -> bool:
    return REMAT in components(op_name)


def opcode(event_name: str) -> str:
    """"dynamic-update-slice" from an op's whole HLO text."""
    rhs = event_name.split(" = ", 1)[-1]
    m = re.search(r"(?:^|[\s}\])])([a-z][a-z0-9\-]*)\(", rhs)
    return m.group(1) if m else "?"


def label(event_name: str, op_name: Optional[str]) -> str:
    """One name for an op in a table: its kernel, else its scope, else
    what kind of op it is and that nothing of the program's names it."""
    k = kernel_of(event_name, op_name)
    if k is not None:
        again = " (recomputed)" if is_remat(op_name) else ""
        return f"kernel {k or '(unnamed)'}{again}"
    s = scope_of(op_name)
    if s is not None:
        return s
    op = opcode(event_name)
    parts = [op]
    if op == "fusion":      # XLA names a fusion after what it fuses:
        m = re.match(r"%([a-z\-_]+?)_fusion", event_name)  # %a_b_fusion.4
        parts = m.group(1).split("_") if m else parts
    kind = "moves bytes" if all(p in MOVES for p in parts) or (
        op == "custom-call" and not op_name) else "computes"
    return f"(no scope, {kind}) {'_'.join(parts)}"


def leaves_within(ops: Sequence[xplane.Event], modules: Sequence[xplane.Event],
                  module_pattern: str, window: xplane.Interval
                  ) -> List[xplane.Event]:
    """Leaf ops that start inside the window and inside an execution of a
    module whose name matches."""
    rx = re.compile(module_pattern)
    spans = xplane.merge((s, s + d) for n, s, d in modules if rx.search(n))
    out, j = [], 0
    for e in xplane.leaves(ops):         # sorted by start
        if not window[0] <= e[1] < window[1]:
            continue
        while j < len(spans) and spans[j][1] <= e[1]:
            j += 1
        if j < len(spans) and spans[j][0] <= e[1]:
            out.append(e)
    return out


def time_by(events: Iterable[xplane.Event], names: Dict[str, str], key
            ) -> Dict[object, int]:
    """Summed duration in ns by `key(event name, op_name)`."""
    acc: Dict[object, int] = {}
    for n, _, d in events:
        k = key(n, names.get(n))
        acc[k] = acc.get(k, 0) + d
    return acc


# -- reading by hand ---------------------------------------------------------

def report(path: str) -> dict:
    trace = xplane.load(path)
    names = op_names(path)
    window = xplane.span_window(trace.host, "bench.window")
    if window is None:
        starts = [s for ls in trace.devices.values()
                  for evs in ls.values() for _, s, _ in evs]
        ends = [s + d for ls in trace.devices.values()
                for evs in ls.values() for _, s, d in evs]
        window = (min(starts), max(ends)) if starts else (0, 0)
    spans: Dict[str, List[int]] = {}          # eng.* name -> [ns, count]
    for n, _, d in trace.host:
        if n.startswith(ENGINE_SPAN_PREFIX):
            tot = spans.setdefault(n, [0, 0])
            tot[0] += d
            tot[1] += 1
    # Where two spans cover an idle stretch the first wins: shortest total
    # first puts a child (eng.device_wait) before its parent (eng.host_drain).
    order = sorted(spans, key=lambda n: spans[n][0])
    out: dict = {"scopes_of_program": list(SCOPES or ()), "chips": {}}
    for chip, lines in sorted(trace.devices.items()):
        ops = lines.get(xplane.OPS_LINE, [])
        mods = lines.get(xplane.MODULES_LINE, [])
        per = names.get(chip, {})
        lv = [e for e in xplane.leaves(ops) if window[0] <= e[1] < window[1]]
        total = sum(d for _, _, d in lv) or 1
        by = time_by(lv, per, label)
        named = sum(v for k, v in by.items() if not k.startswith("(no scope"))
        moved = sum(v for k, v in by.items()
                    if k.startswith("(no scope, moves"))
        programs = {}
        for mod in sorted({re.sub(r"\(.*", "", n) for n, _, _ in mods}):
            inside = leaves_within(ops, mods, re.escape(mod) + r"\(", window)
            t = sum(d for _, _, d in inside)
            if t:
                programs[mod] = {
                    "leaf_s": t / 1e9,
                    "by_scope_s": {k: v / 1e9 for k, v in sorted(
                        time_by(inside, per, label).items(),
                        key=lambda kv: -kv[1])}}
        remat = sum(d for n, _, d in lv if is_remat(per.get(n)))
        out["chips"][chip] = {
            "leaf_s": total / 1e9,
            "under_a_scope_or_kernel_pct": 100.0 * named / total,
            "no_scope_moves_bytes_pct": 100.0 * moved / total,
            "no_scope_computes_pct": 100.0 * (total - named - moved) / total,
            "kernels_s": {k: v / 1e9 for k, v in by.items()
                          if k.startswith("kernel ")},
            "rematted_pct_of_leaf": 100.0 * remat / total,
            "programs": programs,
            "idle_by_engine_span_s": xplane.idle_gaps(
                ops, trace.host, window,
                order + ["engine.step", "submit", "idle_no_request"], k=20),
            "engine_spans_s": {n: [spans[n][0] / 1e9, spans[n][1]]
                               for n in sorted(spans)}}
    return out


def main(argv) -> int:
    path = argv[1]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    print(json.dumps(report(path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

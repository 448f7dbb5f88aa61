"""What the six `setup_*` readers share: the program's own account of what
it built before the window, split into the parts of `setup_s`. Not a
reader itself (no entry names it).

The program keeps a process-wide compile ledger fed by `jax.monitoring`
(`ray_tpu/util/compile_cache.py`: `ledger()`; `run.py`'s call of
`enable_compile_cache()` installs it before anything is built). Its ring
holds one record a stage of every program built, `(program, kind, stamp,
seconds, hit)`, `stamp` on `time.perf_counter`, the clock of
`records["window"]`. "Before the window" is `stamp < records["window"][0]`:

    setup_programs             backend builds ("compile" + "fetch" records)
    setup_trace_lower_s        seconds of the top-level "trace" and "lower"
                               records: the host's Python
    setup_cache_miss_programs  "compile" records: builds the persistent
                               cache did not hold
    setup_compile_s            their backend seconds
    setup_cache_fetch_s        seconds of the "fetch" records: retrievals
    setup_other_s              `setup_s` less the three sums of seconds

so the four parts in seconds add up to `setup_s` by construction. A
program from before the ledger gives None for all six. The first reader
to ask also writes the per-program table beside `last_trace1.json`
(`compile_ledger.json`): what a builder opens when `setup_s` moved or
`compiles_in_window` is not 0.
"""

import json
import os

try:                    # absent from a program older than the ledger
    from ray_tpu.util.compile_cache import ledger
except ImportError:
    ledger = None

_KEY = "_setup_split"       # where `records` keeps the split once made


def split(records):
    """The six numbers of this run, or None where the program has no
    ledger or its ring has overwritten records (the oldest, so the
    set-up's)."""
    if _KEY in records:
        return records[_KEY]
    out = None
    led = ledger() if ledger is not None else None
    if led is not None and not led.events_dropped:
        events = led.events()
        w0 = records["window"][0]
        before = [e for e in events if e[2] < w0]
        sums = {k: sum(e[3] for e in before if e[1] == k)
                for k in ("trace", "lower", "compile", "fetch")}
        misses = sum(e[1] == "compile" for e in before)
        out = {
            "setup_programs": misses + sum(e[1] == "fetch" for e in before),
            "setup_trace_lower_s": sums["trace"] + sums["lower"],
            "setup_cache_miss_programs": misses,
            "setup_compile_s": sums["compile"],
            "setup_cache_fetch_s": sums["fetch"],
        }
        out["setup_other_s"] = records["e2e"]["setup_s"] - (
            out["setup_trace_lower_s"] + out["setup_compile_s"]
            + out["setup_cache_fetch_s"])
        session = records.get("session")
        if session is not None:
            path = os.path.join(os.path.dirname(session.dir),
                                "compile_ledger.json")
            with open(path, "w") as f:
                json.dump(table(records, events, out), f, indent=1)
    records[_KEY] = out
    return out


def table(records, events, parts):
    """`compile_ledger.json`: the split, a row a program (its builds, the
    four times, hits and misses, the seconds of them stamped before and
    from the window's start), the engine's `compiles_total` at the window's
    two ends, how long the process ran before it built anything, and every
    record stamped from the window's start on with the harness span that
    held it."""
    w0, w1 = records["window"]
    spans = getattr(records.get("spans"), "by_name", {})
    rows, late = {}, []
    for program, kind, stamp, seconds, hit in events:
        row = rows.setdefault(program, dict(
            program=program, builds=0, trace_s=0.0, lower_s=0.0,
            compile_s=0.0, fetch_s=0.0, hits=0, misses=0,
            before_window_s=0.0, from_window_s=0.0))
        row[kind + "_s"] += seconds
        if hit is not None:
            row["builds"] += 1
            row["hits" if hit else "misses"] += 1
        if stamp < w0:
            row["before_window_s"] += seconds
            continue
        row["from_window_s"] += seconds
        late.append({"program": program, "kind": kind,
                     "end_s_from_window": stamp - w0, "seconds": seconds,
                     "hit": hit, "in_window": stamp < w1,
                     "harness_spans": sorted(
                         name for name, ss in spans.items()
                         if any(t <= stamp <= t + d for t, d in ss))})
    snaps = records.get("snaps", {})
    setup_s = records["e2e"]["setup_s"]
    first = min((stamp - seconds for _, _, stamp, seconds, _ in events),
                default=w0)
    return {"setup_s": setup_s, "split": parts, "window_s": w1 - w0,
            # process start (the window's start less `setup_s`, on this
            # clock) to the first stage of the first program: interpreter,
            # imports and reaching the chip, the head of `setup_other_s`
            "first_build_after_s": first - (w0 - setup_s),
            # the engine's own count at the window's two ends (`stats()`):
            # equal in a run that built nothing inside it
            "engine_compiles_total": {
                k: snaps[k]["compiles_total"] for k in ("w0", "w1")
                if "compiles_total" in snaps.get(k, {})},
            "programs": sorted(
                rows.values(),
                key=lambda r: -(r["before_window_s"] + r["from_window_s"])),
            "from_window_start": late}

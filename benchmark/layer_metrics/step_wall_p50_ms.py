"""Median host time of one `engine.step()` call inside the window, from
the harness's own span around the call."""

import statistics

LAYER = "engine step loop, host"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    d = records["spans"].durations("engine.step", *records["window"])
    return statistics.median(d) * 1e3 if d else None

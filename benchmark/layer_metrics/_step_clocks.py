"""What the step-clock readers share: differences of the engine's own
counters (`engine.stats()`, PR 54) between the snapshots the driver takes
at the window's two ends (`w0`, `w1` of `records["snaps"]`). Every reader
returns None on a tree whose engine has no such counter."""


def delta(records, key, upto="w1"):
    """`key` at the snapshot `upto` less `key` at `w0`; None without the
    counter."""
    a, b = records["snaps"].get("w0"), records["snaps"].get(upto)
    if not a or not b or key not in b:
        return None
    return b[key] - a.get(key, 0.0)


def before_trace(records):
    """The closing snapshot for what accrues BETWEEN steps: in a traced
    run `t0`, taken as the profiler starts, else `w1`. The harness stops
    the profiler inside the window and stands in the stop 4-15 s
    (`trace_stop_s`; ROADMAP R0 vii): no step runs, a device whose ring
    is empty idles all of it, and that is the harness's doing, not the
    engine's (my chip run, PR 54: 6.59 of rollout's 7.36 starved
    seconds)."""
    return "t0" if records["snaps"].get("t0") else "w1"


def per_step_ms(records, *keys):
    """The summed seconds of `keys` over the steps the engine counted
    between the snapshots, in ms a step; None without a counter or a
    step."""
    steps = delta(records, "steps_total")
    parts = [delta(records, k) for k in keys]
    if not steps or steps <= 0 or None in parts:
        return None
    return sum(parts) / steps * 1e3


def share_pct(records, part, whole, upto="w1"):
    """100 x `part` / `whole`, both differences; None without a counter.
    Where the counters are there and `whole` did not move, 0: a cell that
    lists the metric has to report it in every traced run, and the share
    of nothing reads as nothing."""
    p, w = delta(records, part, upto), delta(records, whole, upto)
    if p is None or w is None:
        return None
    return 100.0 * p / w if w > 0 else 0.0

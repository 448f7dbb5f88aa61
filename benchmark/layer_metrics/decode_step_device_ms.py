"""Device time of the fused decode program per token of horizon: summed
duration of its executions in the traced stretch ("XLA Modules" events
whose name matches MODULE) over the tokens of horizon dispatched in that
stretch (the engine's `decode_horizon` aggregate, difference of two
snapshots)."""

from benchmark.harness import xplane

LAYER = "jitted programs"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"
MODULE = r"decode_multi_paged"


def read(records, reduced):
    a, b = records["snaps"].get("t0"), records["snaps"].get("t1")
    if reduced is None or not a or not b:
        return None
    mods = reduced["trace"].devices[reduced["idlest_chip"]].get(
        xplane.MODULES_LINE, [])
    ns, n = xplane.sum_matching(mods, MODULE, reduced["window"])
    steps = b["decode_horizon_mean"] * b["decode_horizon_count"] \
        - a["decode_horizon_mean"] * a["decode_horizon_count"]
    if not n or steps <= 0:
        return None
    return ns / 1e6 / steps

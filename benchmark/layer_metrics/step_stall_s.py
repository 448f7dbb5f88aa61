"""Seconds of the window inside STALLED steps, `engine.step()` calls of
`STALL_STEP_S` (0.5 s) or more where a step takes 50-190 ms: the
`step_stalled_s_total` counter of `engine.stats()` between the snapshots
at the window's two ends. 0 in a run the machine did not stall; a far-off
run carries its stall into the ledger's line here, and
`step_stall_device_wait_pct` says where it stood. None where the engine
has no such counter."""

from benchmark.layer_metrics import _step_clocks as sc

LAYER = "engine step loop, host"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    return sc.delta(records, "step_stalled_s_total")

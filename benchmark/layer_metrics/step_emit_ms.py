"""Seconds a step inside `eng.emit`, the host's replay of a drained token
block (`_emit_block`: tokens to their requests, retirements, block
frees, the metrics plane's per-request hooks): `step_emit_s_total` over
`steps_total`, differences of the snapshots at the window's two ends.
One part of `step_self_ms`. None where the engine has no such counter."""

from benchmark.layer_metrics import _step_clocks as sc

LAYER = "engine step loop, host"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    return sc.per_step_ms(records, "step_emit_s_total")

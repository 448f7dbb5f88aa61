"""Seconds a step inside `eng.admit`: the scheduler's gate over the free
rows (pops, deadline and capacity checks) and the binding of what it
admitted (prefix probe, block chains, swap-ins); `step_admit_s_total` over
`steps_total`, differences of the snapshots at the window's two ends. The
scheduler's time BUSY, beside the time requests spend waiting for it
(`ttft_queue_mean_ms`). None where the engine has no such counter."""

from benchmark.layer_metrics import _step_clocks as sc

LAYER = "scheduler and admission"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"


def read(records, reduced):
    return sc.per_step_ms(records, "step_admit_s_total")

"""WHERE the window's stalled steps stood (`step_stall_s`): the share of
their seconds inside `_device_get` (`step_stalled_device_wait_s_total`
over `step_stalled_s_total`, differences of the snapshots at the window's
two ends). Near 100: the device, the runtime or its tunnel held the pull;
near 0: the host stood between the engine's seams (descheduled). A label
and no score: "lower" is the schema's. 0 where no step stalled
(`step_stall_s` reads 0 there and says so: the line of every traced run
has to carry the metric); None where the engine has no such counter."""

from benchmark.layer_metrics import _step_clocks as sc

LAYER = "engine step loop, host"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    return sc.share_pct(records, "step_stalled_device_wait_s_total",
                        "step_stalled_s_total")

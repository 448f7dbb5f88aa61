"""The delta rule's one-token update of a decode step against the HBM
roofline: the least time the chip could take to read and write the matrix
state of every LIVE row once in each delta layer, over the device time
under `gdn_step` inside executions of the fused decode program in the
traced stretch.

Bytes = `ssm_row_steps_total` (live rows x decode tokens, counted at
dispatch; the counter counts recurrent state of any kind) between the
traced stretch's two snapshots x 6 layers x 2 x the row's float32 state,
32 heads x 128 x 128 x 4 B = 2 MiB (costs_gdn.step_least_s). The conv
state is NOT in the bytes: the conv is under `gdn_conv`. From live rows
and not from all slots, so a program that moves the state of empty slots
too reads lower, which is what it then is. Memory-bound: 6 operations a
state element."""

from benchmark.harness import costs, costs_gdn
from benchmark.layer_metrics import _gdn_scopes as gs

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    by = gs.time_by_scope(records, reduced, gs.DECODE_MODULE)
    row_steps = gs.delta(records, "ssm_row_steps_total", "t0", "t1")
    if by is None or not row_steps or not by.get(gs.GDN_STEP):
        return None
    peak = costs.peaks(records["device"]["kind"])
    least_s = costs_gdn.step_least_s(records["model"], row_steps,
                                     peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (by[gs.GDN_STEP] / 1e9)

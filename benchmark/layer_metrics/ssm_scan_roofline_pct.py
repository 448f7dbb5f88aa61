"""The recurrence of a decode step against the HBM roofline: the least
time the chip could take to read and write the scan state of every LIVE
row once in each state-space layer, over the device time under `ssm_scan`
inside executions of the fused decode program in the traced stretch.

Bytes = `ssm_row_steps_total` (live rows x decode tokens, counted at
dispatch) between the traced stretch's two snapshots x 9 layers x 2 x the
row's float32 state, 5,120 x 16 x 4 B (costs_hybrid.scan_least_s). The
conv state (5,120 x 3 x 2 B a layer) is NOT in the bytes: the conv is
under `ssm_proj`, and bytes whose time is elsewhere would raise the share
falsely. From live rows and not from all slots, so a program that moves
the state of empty slots too reads lower, which is what it then is; the
scope also holds x_proj and dt_proj, whose weights are not in the bytes
either. Memory-bound: 6 operations a state element."""

from benchmark.harness import costs, costs_hybrid
from benchmark.layer_metrics import _hybrid_scopes as hs

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    by = hs.decode_time_by_scope(records, reduced)
    row_steps = hs.delta(records, "ssm_row_steps_total", "t0", "t1")
    if by is None or not row_steps:
        return None
    ns = by.get(hs.SSM_SCAN, 0)
    if not ns:
        return None
    peak = costs.peaks(records["device"]["kind"])
    least_s = costs_hybrid.scan_least_s(records["model"], row_steps,
                                        peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9)

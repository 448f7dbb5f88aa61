"""The window layers' attention in a decode step against its roofline: the
least time the chip could take to read, once, the latent rows of the slots
inside each row's window (harness/costs_mla_swa.py: 2,304 B a slot a
window layer as stored, 270,336 operations, memory-bound) over the device
time under `swa_attention` inside executions of the fused decode program
in the traced stretch. Slots = `swa_window_slots_total` between the
stretch's two snapshots (min(row length, window) a dispatched row-token,
counted at dispatch), times the configuration's window layers. The kernel
reads whole pages (3 of 256 slots for a window of 513: 1.5 times the
window) and every row of the batch, live or not, so a full batch reads
two thirds at most. None without the scope or the counter."""

from benchmark.harness import costs, costs_mla_swa
from benchmark.layer_metrics import _mla_swa_scopes as ws

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    spent = ws.window_time(records, reduced, ws.DECODE_MODULE,
                           ws.ATTEND_SCOPES)
    slots = ws.delta(records, "swa_window_slots_total", "t0", "t1")
    if spent is None or not slots or not spent[0]:
        return None
    peak = costs.peaks(records["device"]["kind"])
    least = costs_mla_swa.least_s(
        costs_mla_swa.swa_attention_cost(records["model"], slots), peak)
    return 100.0 * least / (spent[0] / 1e9)

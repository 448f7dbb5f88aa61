"""The held experts' matmuls in a decode step against the HBM roofline:
the least time the chip could take to read, once, the weights of the held
experts that were HIT (harness/costs_mla.py: 3 x 7,168 x 2,048 values of
2 B an expert), over the device time under `moe_experts` inside
executions of the fused decode program in the traced stretch. Experts hit
= `moe_decode_experts_hit_total` between the stretch's two snapshots
(held experts with a live assignment, summed over layers and tokens).
The operations of the assignments that landed (24 rows x 8 / 16 a layer)
are three orders below the byte time and left out of the least. A
program that multiplies every held expert whatever was hit reads the hit
share at most. `moe_experts_roofline_pct` is this for a layer that holds
all its experts and reads OLMoE's keys. None without the scope or the
counter."""

from benchmark.harness import costs, costs_mla
from benchmark.layer_metrics import _mla_scopes as ms

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    by = ms.time_by_scope(records, reduced, ms.DECODE_MODULE)
    hit = ms.delta(records, "moe_decode_experts_hit_total", "t0", "t1")
    if by is None or not hit or not by.get(ms.MOE_EXPERTS):
        return None
    peak = costs.peaks(records["device"]["kind"])
    least = costs_mla.least_s(
        costs_mla.held_experts_cost(records["model"], hit, 0.0), peak)
    return 100.0 * least / (by[ms.MOE_EXPERTS] / 1e9)

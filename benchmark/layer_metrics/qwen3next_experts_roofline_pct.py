"""The 128 held experts' matmuls in a decode step against the HBM
roofline: the least time the chip could take to read, once, the weights of
the held experts that were HIT (harness/costs_gdn.py: 3 x 2,048 x 512
values of 2 B an expert), over the device time under `moe_experts` inside
executions of the fused decode program in the traced stretch. Experts hit
= `moe_decode_experts_hit_total` between the stretch's two snapshots (held
experts with a live assignment, summed over layers and tokens: about 90 of
128 a layer at 64 live rows x 10 of 512). The operations of the
assignments that landed (160 a layer a step) are two orders below the byte
time and left out of the least. `held_experts_roofline_pct` is this for
the latent family, whose reader asks for that family's scopes. None
without the scope or the counter."""

from benchmark.harness import costs, costs_gdn
from benchmark.layer_metrics import _gdn_scopes as gs

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    by = gs.time_by_scope(records, reduced, gs.DECODE_MODULE)
    hit = gs.delta(records, "moe_decode_experts_hit_total", "t0", "t1")
    if by is None or not hit or not by.get(gs.MOE_EXPERTS):
        return None
    peak = costs.peaks(records["device"]["kind"])
    least = costs_gdn.least_s(
        costs_gdn.held_experts_cost(records["model"], hit, 0.0), peak)
    return 100.0 * least / (by[gs.MOE_EXPERTS] / 1e9)

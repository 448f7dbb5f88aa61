"""The expert matmuls of a decode step against the HBM roofline: the least
time the chip could take to read the weights of the experts that were HIT
over the device time under `moe_experts` inside executions of the fused
decode program in the traced stretch.

Bytes = experts hit per expert-layer run x layers x decode tokens of the
traced stretch x one expert's bytes (3 x d x f x 2, costs_moe). Experts
hit per run is `moe_decode_experts_hit_total` over
`moe_decode_layer_steps_total` between the WINDOW's two snapshots: a
ratio, because the engine's counters move when a token block is drained
and so lag the device by up to the ring's depth; a ratio over the whole
window does not shift with that lag, a difference over the 3 s stretch
would. From experts hit and not from all 64, so that a program which
skips experts nobody chose cannot read above 100 %; one that reads all
of them at low occupancy reads low, which is what it then is.
Memory-bound at decode: 32 rows x 8 choices spread over 64 experts of
12.6 MB each."""

from benchmark.harness import costs, costs_moe
from benchmark.layer_metrics import _moe_scopes as ms

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    by = ms.time_by_scope(records, reduced, ms.DECODE_MODULE)
    steps = ms.decode_tokens_traced(records)
    hit = ms.counter_delta(records, "moe_decode_experts_hit_total")
    runs = ms.counter_delta(records, "moe_decode_layer_steps_total")
    if by is None or steps is None or not hit or not runs:
        return None
    ns = by.get(ms.MOE_EXPERTS, 0)
    if not ns:
        return None
    peak = costs.peaks(records["device"]["kind"])
    least_s = costs_moe.decode_experts_least_s(
        records["model"], hit / runs, steps, peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9)

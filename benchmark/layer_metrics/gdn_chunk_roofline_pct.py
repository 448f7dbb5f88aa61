"""The delta rule's chunk form over prompts against the roofline: the
least time the chip could take for the operations and bytes the published
chunkwise algorithm needs for the REAL prompt tokens prefilled in the
traced stretch (costs_gdn.chunk_cost: the longer of the bf16 compute time
and the HBM time), over the device time under `gdn_chunk` inside
executions of the prefill program there.

Tokens = `prefill_real_tokens` between the stretch's two snapshots (bucket
filler and a group's padding rows are computed and not counted: a program
that pads more reads lower). The triangular products count half, the state
moves once a dispatch of `prefill_chunk` tokens. None without the scope,
the counter or a prefill in the stretch."""

from benchmark.harness import costs, costs_gdn
from benchmark.layer_metrics import _gdn_scopes as gs

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    by = gs.time_by_scope(records, reduced, gs.PREFILL_MODULE)
    tokens = gs.delta(records, "prefill_real_tokens", "t0", "t1")
    if by is None or not tokens or not by.get(gs.GDN_CHUNK):
        return None
    model = records["model"]
    peak = costs.peaks(records["device"]["kind"])
    least = costs_gdn.least_s(costs_gdn.chunk_cost(
        model, tokens, int(model["engine"]["prefill_chunk"])), peak)
    return 100.0 * least / (by[gs.GDN_CHUNK] / 1e9)

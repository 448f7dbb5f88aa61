"""Share of the prefill program's device time that the gated delta-rule
layers take: leaf ops under `gdn_proj`, `gdn_conv`, `gdn_chunk` (or
`gdn_step`, a chunk of one token) over all leaf ops, both inside
executions of the prefill program in the traced stretch. Every prompt
token goes through the chunk form in three layers of four. None for a
program without these scopes or a stretch without a prefill."""

from benchmark.layer_metrics import _gdn_scopes as gs

LAYER = "jitted programs"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    by = gs.time_by_scope(records, reduced, gs.PREFILL_MODULE)
    if by is None:
        return None
    return 100.0 * sum(by.get(s, 0) for s in gs.GDN_SCOPES) \
        / sum(by.values())

"""Share of the prefill program's device time that the WINDOW layers'
attention takes: leaf ops under `swa_proj`, `swa_write`, `swa_attention`
or `swa_gate` (their low-rank projections, cache write, the pages that
cover the chunk's windows and the attention over them, the gate; their
norms and expert layers are not told from the full layers') over all leaf
ops, both inside executions of the prefill program in the traced stretch.
Beside `sparse_prefill_share_pct`, the full layers' selection and
attention. None for a program without these scopes or a stretch without a
prefill."""

from benchmark.layer_metrics import _mla_swa_scopes as ws

LAYER = "jitted programs"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    spent = ws.window_time(records, reduced, ws.PREFILL_MODULE,
                           ws.LAYER_SCOPES)
    if spent is None or not spent[1]:
        return None
    return 100.0 * spent[0] / spent[1]

"""Attention over the selected tokens in a decode step against its
roofline: the least time the chip could take to read the latent rows of
the tokens SELECTED once and do the absorbed form's two products over
them (harness/costs_mla.py: 1,152 B and 278,528 operations a token-layer,
at the chip's ridge), over the device time under `latent_gather` +
`sparse_attention` inside executions of the fused decode program in the
traced stretch. Tokens = `indexer_decode_tokens_selected_total` between
the stretch's two snapshots. A program that copies the rows out of the
pool and reads the copy twice reads a third at most. None without the
scopes or the counter."""

from benchmark.harness import costs, costs_mla
from benchmark.layer_metrics import _mla_scopes as ms

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    by = ms.time_by_scope(records, reduced, ms.DECODE_MODULE)
    chosen = ms.delta(records, "indexer_decode_tokens_selected_total",
                      "t0", "t1")
    ns = sum(by.get(s, 0) for s in ms.ATTEND_SCOPES) if by else 0
    if not chosen or not ns:
        return None
    peak = costs.peaks(records["device"]["kind"])
    least = costs_mla.least_s(
        costs_mla.sparse_attention_cost(records["model"], chosen), peak)
    return 100.0 * least / (ns / 1e9)

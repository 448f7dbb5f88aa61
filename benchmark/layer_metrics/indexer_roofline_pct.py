"""The indexer's scoring in a decode step against its roofline: the least
time the chip could take to read the index keys of the tokens it SCORED
and multiply them by the query's heads (harness/costs_mla.py: 256 B and
16,384 operations a token-layer, memory-bound), over the device time
under `indexer_score` inside executions of the fused decode program in
the traced stretch. Tokens = `indexer_decode_tokens_scored_total` between
the stretch's two snapshots (live tokens x layers, counted at dispatch).
A program that reads a row's whole table where a part is live, or moves
the keys before it multiplies them, reads low here. None without the
scope or the counter."""

from benchmark.harness import costs, costs_mla
from benchmark.layer_metrics import _mla_scopes as ms

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    by = ms.time_by_scope(records, reduced, ms.DECODE_MODULE)
    scored = ms.delta(records, "indexer_decode_tokens_scored_total",
                      "t0", "t1")
    if by is None or not scored or not by.get(ms.INDEXER_SCORE):
        return None
    peak = costs.peaks(records["device"]["kind"])
    least = costs_mla.least_s(
        costs_mla.indexer_score_cost(records["model"], scored), peak)
    return 100.0 * least / (by[ms.INDEXER_SCORE] / 1e9)

"""Share of the prefill program's device time that selection and the
attention it feeds take: leaf ops under `indexer_score`, `indexer_topk`,
`latent_gather` or `sparse_attention` over all leaf ops, both inside
executions of the prefill program in the traced stretch. Every query of a
chunk scores everything live below it and takes its own top 2,048. None
for a program without these scopes or a stretch without a prefill."""

from benchmark.layer_metrics import _mla_scopes as ms

LAYER = "jitted programs"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    by = ms.time_by_scope(records, reduced, ms.PREFILL_MODULE)
    if by is None:
        return None
    return 100.0 * sum(by.get(s, 0) for s in
                       ms.INDEXER_SCOPES + ms.ATTEND_SCOPES) \
        / sum(by.values())

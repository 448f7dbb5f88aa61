"""Device time per decoded token that the sparse attention's indexer
takes: leaf ops inside executions of the fused decode program in the
traced stretch whose scope is `indexer_score` (every live token's key
read from the index plane and scored against the query's 64 heads) or
`indexer_topk` (the exact top 2,048 of a row's scores), all layers, over
the tokens of horizon dispatched in the stretch, as `decode_step_device_ms`
counts them: a part of that sum. None for a program without these scopes
(another family, the parent commit)."""

from benchmark.layer_metrics import _mla_scopes as ms

LAYER = "jitted programs"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    return ms.per_decode_token_ms(records, reduced, ms.INDEXER_SCOPES)

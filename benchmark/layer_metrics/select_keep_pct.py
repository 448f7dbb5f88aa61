"""Share of the tokens the indexer scored that attention then read:
`indexer_tokens_selected_total` over `indexer_tokens_scored_total`
(`engine.stats()`, token-layers of decode and prefill, counted at
dispatch) between the snapshots at the window's two ends. 100 % for any
query under position `index_topk`; at 8 k live tokens a decode token
keeps a quarter. What selection saves attention is the rest. None where
the engine has no such counter."""

from benchmark.layer_metrics import _mla_scopes as ms

LAYER = "jitted programs"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    kept = ms.delta(records, "indexer_tokens_selected_total")
    scored = ms.delta(records, "indexer_tokens_scored_total")
    if kept is None or not scored:
        return None
    return 100.0 * kept / scored

"""Share of prefill's token-layers that were not run:
`prefill_layer_tokens_skipped_total` over `prefill_layer_tokens_total`
(`engine.stats()`) between the snapshots at the window's two ends. The
total is what a stack that runs every layer for every prompt token would
run (real tokens x layers); skipped are the layers after the
full-attention layer for every position but a prompt's last (the
published design: the cross-decoder reads one cache and needs no pass
over the prompt). 14 of 32 layers = 43.75 % for a long prompt, a little
less for a short one. None where the engine has no such counter."""

from benchmark.layer_metrics import _hybrid_scopes as hs

LAYER = "jitted programs"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    skipped = hs.delta(records, "prefill_layer_tokens_skipped_total")
    total = hs.delta(records, "prefill_layer_tokens_total")
    if skipped is None or not total:
        return None
    return 100.0 * skipped / total

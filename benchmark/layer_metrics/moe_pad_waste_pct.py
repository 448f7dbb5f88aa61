"""Share of the token-expert rows the expert matmuls computed that were no
live assignment: 1 - `moe_assignments_total` / `moe_rows_computed_total`
(`engine.stats()`, counted on the device over live rows, prefill and
decode together) between the snapshots at the window's two ends. Rows of
padded prompt positions, of dead slots, of a group's padding rows, and the
rows of experts a token did not choose where every row is multiplied by
every expert (decode: 56 of 64) all count as waste: it is the share of the
expert FLOPs that no token asked for. None where the engine has no such
counter (a dense model, or a program from before the expert layer)."""

from benchmark.layer_metrics import _moe_scopes as ms

LAYER = "jitted programs"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"


def read(records, reduced):
    live = ms.counter_delta(records, "moe_assignments_total")
    rows = ms.counter_delta(records, "moe_rows_computed_total")
    if live is None or not rows:
        return None
    return 100.0 * (1.0 - live / rows)

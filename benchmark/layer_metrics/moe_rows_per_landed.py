"""Token-expert rows the expert matmuls multiplied for each assignment
that landed on an expert this chip holds: `moe_rows_computed_total` /
`moe_assignments_landed_total` (`engine.stats()`, counted on the device
over live rows, prefill and decode together) between the snapshots at the
window's two ends. 1 is a grouped matmul whose tiles follow the groups;
above it are the windows' padding rows around 128 small groups in a
prefill chunk and the dead slots a hit expert is multiplied with in
decode. None where the engine has no such counters (no held range, a
dense model, the parent commit) or nothing landed."""

from benchmark.layer_metrics import _moe_scopes as ms

LAYER = "jitted programs"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    rows = ms.counter_delta(records, "moe_rows_computed_total")
    landed = ms.counter_delta(records, "moe_assignments_landed_total")
    if rows is None or not landed:
        return None
    return rows / landed

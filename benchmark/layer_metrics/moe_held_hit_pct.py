"""Share of the routed assignments that landed on an expert this chip
holds: `moe_assignments_landed_total` over `moe_assignments_total`
(`engine.stats()`, counted on the device over live rows, decode and
prefill) between the snapshots at the window's two ends. 16 of 256
experts held and a router that spreads evenly give 6.25 %; what lands
here is what the expert matmuls have to compute. None where the engine
keeps no such counter (a layer that holds all its experts reports none
landed elsewhere and is not listed for this metric)."""

from benchmark.layer_metrics import _mla_scopes as ms

LAYER = "jitted programs"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    snap = records["snaps"].get("w1") or {}
    if "indexer_tokens_scored_total" not in snap:
        return None          # the counter's meaning is this family's
    landed = ms.delta(records, "moe_assignments_landed_total")
    routed = ms.delta(records, "moe_assignments_total")
    if landed is None or not routed:
        return None
    return 100.0 * landed / routed

"""Share of block-table entries the paged decode kernel has to walk:
live pages of the active rows over all `B * MB` entries, summed over the
decode tokens dispatched inside the window. Difference of the engine's
counters `paged_walk_pages_total` and `paged_walk_entries_total`
(`engine.stats()`, counted on the host at every fused decode dispatch)
between the snapshots at the window's two ends. A kernel whose trip
count follows the rows' lengths takes time in proportion to this share;
`paged_attn_roofline_pct` says whether it does. None where the engine
has no such counter."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    a, b = records["snaps"].get("w0"), records["snaps"].get("w1")
    if not a or not b or "paged_walk_entries_total" not in b:
        return None
    entries = b["paged_walk_entries_total"] \
        - a.get("paged_walk_entries_total", 0.0)
    pages = b["paged_walk_pages_total"] - a.get("paged_walk_pages_total", 0.0)
    return 100.0 * pages / entries if entries > 0 else None

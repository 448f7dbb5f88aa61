"""Pages a prefill dispatch asks the paged kernel to walk per entry of
its rows' block tables, x 100: the chunks' query tiles each walk the
pages up to their last real token's slot (every tile of a chunk its own
prefix), against the `n_pad * MB` entries of the dispatched rows'
tables, which is what the dense per-row view of before PR 30 gathered,
attended and wrote back whatever was live. It is work asked for against
the table's size, NOT a fraction of the table: a late chunk of a long
prompt has several tiles that each walk most of the row and reads above
100, so it follows the prompt mix as much as the program. Difference of
the engine's counters `prefill_walk_pages_total` and
`prefill_table_entries_total` (`engine.stats()`, counted on the host at
every prefill dispatch whose chunks go tile by tile: not under a tp mesh,
not for a quantized pool) between the snapshots at the window's two
ends. None where the engine has no such counter."""

LAYER = "jitted programs"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"


def read(records, reduced):
    a, b = records["snaps"].get("w0"), records["snaps"].get("w1")
    if not a or not b or "prefill_table_entries_total" not in b:
        return None
    entries = b["prefill_table_entries_total"] \
        - a.get("prefill_table_entries_total", 0.0)
    pages = b["prefill_walk_pages_total"] \
        - a.get("prefill_walk_pages_total", 0.0)
    return 100.0 * pages / entries if entries > 0 else None

"""Highest share of the WINDOW pool's blocks ever in use
(`window_pool_peak_blocks` over `window_pool_blocks_total`,
`engine.stats()` at the window's end): the pool the sliding-window layers
write, of which a row holds only the blocks that intersect its last
`sliding_window` slots. It stays flat while rows grow; a manager that
stopped freeing behind the window would fill it within seconds. None where
the engine has no such pool (another family, the parent commit)."""

LAYER = "KV manager"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    end = records["snaps"].get("w1") or records.get("stats_end") or {}
    total = end.get("window_pool_blocks_total")
    if not total:
        return None
    return 100.0 * end.get("window_pool_peak_blocks", 0.0) / total

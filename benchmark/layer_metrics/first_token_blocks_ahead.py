"""Mean number of decode blocks in flight IN FRONT of the block that
carries a request's first token, at that block's dispatch, over the
requests whose first token arrived inside the window (ROADMAP S10's
"committed blocks in front of a first token"): the `first_blocks_ahead`
aggregate of `engine.stats()` between the snapshots at the window's two
ends. 0 where every first token rides a block dispatched on an empty
ring."""

from benchmark.layer_metrics.ttft_queue_mean_ms import window_mean_ms

LAYER = "engine step loop, host"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"
KEY = "first_blocks_ahead"


def read(records, reduced):
    mean_x1000 = window_mean_ms(records, KEY)   # the helper scales to ms
    return None if mean_x1000 is None else mean_x1000 / 1e3

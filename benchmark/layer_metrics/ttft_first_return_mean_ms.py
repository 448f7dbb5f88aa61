"""Mean time from the dispatch of the decode block that carries a
request's first token to that token on the host, over the requests whose
first token arrived inside the window: the `first_return_s` aggregate of
`engine.stats()` between the snapshots at the window's two ends. The
second half of `ttft_first_block_mean_ms`: what the device had queued in
front of the block (`first_token_blocks_ahead`, the prompt's last chunk),
the block's own run, and the pull, one drain behind the device."""

from benchmark.layer_metrics.ttft_queue_mean_ms import window_mean_ms

LAYER = "engine step loop, host"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"
KEY = "first_return_s"


def read(records, reduced):
    return window_mean_ms(records, KEY)

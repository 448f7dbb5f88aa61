"""Mean time from a request's row becoming decodable to the DISPATCH of
the decode block that carries its first token, over the requests whose
first token arrived inside the window: the `first_dispatch_s` aggregate of
`engine.stats()` between the snapshots at the window's two ends. The first
half of `ttft_first_block_mean_ms`: the wait for the host to get to that
dispatch (the blocks in flight it drains first, the step in between where
the last chunk went ahead). With `ttft_first_return_mean_ms` it adds up to
`ttft_first_block_mean_ms` for every request."""

from benchmark.layer_metrics.ttft_queue_mean_ms import window_mean_ms

LAYER = "engine step loop, host"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"
KEY = "first_dispatch_s"


def read(records, reduced):
    return window_mean_ms(records, KEY)

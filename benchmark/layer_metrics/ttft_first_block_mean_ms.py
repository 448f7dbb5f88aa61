"""Mean time from a request's row becoming decodable to its first token
reaching the host, over the requests whose first token arrived inside
the window: the `first_block_s` aggregate of `engine.stats()` between the
snapshots at the window's two ends. The third of a first token's three
parts: the first token rides a fused decode block, and the host drains
one block behind the device."""

from benchmark.layer_metrics.ttft_queue_mean_ms import window_mean_ms

LAYER = "engine step loop, host"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"
KEY = "first_block_s"


def read(records, reduced):
    return window_mean_ms(records, KEY)

"""Mean time from admission to the dispatch of a request's last prompt
chunk (its row becomes decodable), over the requests whose first token
arrived inside the window: the `prefill_s` aggregate of `engine.stats()`
between the snapshots at the window's two ends. The second of a first
token's three parts; it holds the prefill programs' device time and the
ring flushes a chunk waits behind."""

from benchmark.layer_metrics.ttft_queue_mean_ms import window_mean_ms

LAYER = "jitted programs"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"
KEY = "prefill_s"


def read(records, reduced):
    return window_mean_ms(records, KEY)

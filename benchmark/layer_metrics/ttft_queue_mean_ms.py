"""Mean time from submit to admission over the requests admitted inside
the window: difference of the `queue_wait_s` aggregate's sum (mean x
count) over the difference of its count, between the snapshots of
`engine.stats()` at the window's two ends. The first of a first token's
three parts (queue, prefill, first block), which add up to `ttft_s` for
every request on the engine's clock. Unlike `queue_wait_p95_ms` it is
the window's alone."""

LAYER = "scheduler and admission"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"
KEY = "queue_wait_s"


def window_mean_ms(records, key):
    """Mean of the `_Agg` called `key` over what it observed between the
    snapshots `w0` and `w1`, in ms; None where the engine has no such
    aggregate or it observed nothing."""
    a, b = records["snaps"].get("w0"), records["snaps"].get("w1")
    if not a or not b or key + "_count" not in b:
        return None
    n = b[key + "_count"] - a.get(key + "_count", 0)
    if n <= 0:
        return None
    total = b[key + "_mean"] * b[key + "_count"] \
        - a.get(key + "_mean", 0.0) * a.get(key + "_count", 0)
    return total / n * 1e3


def read(records, reduced):
    return window_mean_ms(records, KEY)

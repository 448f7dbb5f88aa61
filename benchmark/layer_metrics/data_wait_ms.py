"""Mean host time per step spent waiting for the feeder's queue before a
step could be dispatched, inside the window (the harness's `feed_batch`
span)."""

LAYER = "input feeder"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_tokens_per_s"


def read(records, reduced):
    d = records["spans"].durations("feed_batch", *records["window"])
    return sum(d) / len(d) * 1e3 if d else None

"""Rows the engine preempted (swapped out or recomputed) inside the
window: the difference of `engine.stats()["preemptions"]`."""

LAYER = "KV manager"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    a, b = records["snaps"].get("w0"), records["snaps"].get("w1")
    if not a or not b:
        return None
    return b["preemptions"] - a["preemptions"]

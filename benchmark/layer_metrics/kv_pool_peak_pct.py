"""Highest share of the paged KV pool's blocks in use, sampled by the
harness after every step inside the window (`engine.kv_used_fraction()`)."""

LAYER = "KV manager"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    return 100.0 * records["kv_peak"]

"""Backend builds before the window that the persistent cache did not hold: the
ledger's "compile" records (no `/jax/compilation_cache/cache_hits` inside
the build). A new program text, or the machine's cache evicted it; 0 on a
warm run. None for a program from before the ledger."""

from benchmark.layer_metrics import _setup_ledger as sl

LAYER = "set-up"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(records, reduced):
    parts = sl.split(records)
    return None if parts is None else parts["setup_cache_miss_programs"]

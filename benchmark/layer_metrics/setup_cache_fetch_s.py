"""Seconds spent retrieving programs from the persistent cache before the
window: the ledger's "fetch" records (JAX's
`/jax/compilation_cache/cache_retrieval_time_sec` of the builds that hit).
None for a program from before the ledger."""

from benchmark.layer_metrics import _setup_ledger as sl

LAYER = "set-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(records, reduced):
    parts = sl.split(records)
    return None if parts is None else parts["setup_cache_fetch_s"]

"""Backend seconds of the builds the persistent cache did not hold, before the
window: the ledger's "compile" records (`backend_compile_duration` of a
build without a cache hit). None for a program from before the ledger."""

from benchmark.layer_metrics import _setup_ledger as sl

LAYER = "set-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(records, reduced):
    parts = sl.split(records)
    return None if parts is None else parts["setup_compile_s"]

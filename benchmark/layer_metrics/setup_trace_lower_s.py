"""Seconds the host spent tracing and lowering before the window: the ledger's
top-level "trace" and "lower" records (JAX's `jaxpr_trace_duration` and
`jaxpr_to_mlir_module_duration`; a trace inside another's span is its
parent's time). The part of `setup_s` that a change to a program's source
moves. None for a program from before the ledger."""

from benchmark.layer_metrics import _setup_ledger as sl

LAYER = "set-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(records, reduced):
    parts = sl.split(records)
    return None if parts is None else parts["setup_trace_lower_s"]

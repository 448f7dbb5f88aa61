"""Backend builds before the window (helper programs such as
`convert_element_type` included, as the harness's printed `programs=` counts
them): the ledger's "compile" and "fetch" records, fed by JAX's
`/jax/core/compile/backend_compile_duration`. Fewer programs is the lever on
a warm set-up. None for a program from before the ledger."""

from benchmark.layer_metrics import _setup_ledger as sl

LAYER = "set-up"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(records, reduced):
    parts = sl.split(records)
    return None if parts is None else parts["setup_programs"]

"""`setup_s` less what the ledger accounts for (tracing and lowering,
compiling, fetching): interpreter and imports, the weight draw's run, pool
allocation, the warm-up programs' execution, schedule generation and the
ramp, a constant of the cell. If THIS wanders from run to run, the noise is
the machine's disk or CPU and not its cache. None for a program from before
the ledger."""

from benchmark.layer_metrics import _setup_ledger as sl

LAYER = "set-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(records, reduced):
    parts = sl.split(records)
    return None if parts is None else parts["setup_other_s"]

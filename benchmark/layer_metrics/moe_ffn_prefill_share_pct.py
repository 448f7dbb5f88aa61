"""Share of the prefill program's device time that the expert layer takes:
leaf ops under `moe_router`, `moe_dispatch` or `moe_experts`, and the
kernels XLA makes of `ragged_dot` (`_moe_scopes.RAGGED_DOT`: the compiler
drops their scope), over all leaf ops, both inside executions of the
prefill program in the traced stretch.
None for a program without these scopes or a stretch without a prefill."""

from benchmark.layer_metrics import _moe_scopes as ms

LAYER = "jitted programs"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "ttft_p95_ms"


def read(records, reduced):
    by = ms.time_by_scope(records, reduced, ms.PREFILL_MODULE)
    if by is None:
        return None
    return 100.0 * sum(by.get(s, 0) for s in ms.MOE_SCOPES) \
        / sum(by.values())

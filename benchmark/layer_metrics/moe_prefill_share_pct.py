"""Share of the prefill program's device time that the expert layer takes,
in a cell whose end-to-end metric is tokens a second: leaf ops under
`moe_router`, `moe_dispatch` or `moe_experts`, and the kernels XLA makes of
`ragged_dot` (`_moe_scopes.RAGGED_DOT`: the compiler drops their scope),
over all leaf ops, both inside executions of the prefill program in the
traced stretch. The quantity `moe_ffn_prefill_share_pct` reads where time
to first token is what a user feels; here prefill is three quarters of the
device's time and the expert layer the largest part of it, so what a
grouped matmul or a cheaper dispatch wins shows in completed tokens.
None for a program without these scopes or a stretch without a prefill."""

from benchmark.layer_metrics import _moe_scopes as ms

LAYER = "jitted programs"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    by = ms.time_by_scope(records, reduced, ms.PREFILL_MODULE)
    if by is None:
        return None
    return 100.0 * sum(by.get(s, 0) for s in ms.MOE_SCOPES) \
        / sum(by.values())

"""Device time per decoded token that the expert layer takes: leaf ops
inside executions of the fused decode program in the traced stretch whose
scope is `moe_router`, `moe_dispatch` or `moe_experts`
(ray_tpu/ops/scope_names.py), over the tokens of horizon dispatched in the
stretch, as `decode_step_device_ms` and `decode_kv_move_device_ms` count
them: the three are parts of one sum. None for a program without these
scopes (a dense model, or one from before the expert layer)."""

from benchmark.layer_metrics import _moe_scopes as ms

LAYER = "jitted programs"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    by = ms.time_by_scope(records, reduced, ms.DECODE_MODULE)
    steps = ms.decode_tokens_traced(records)
    if by is None or steps is None:
        return None
    return sum(by.get(s, 0) for s in ms.MOE_SCOPES) / 1e6 / steps

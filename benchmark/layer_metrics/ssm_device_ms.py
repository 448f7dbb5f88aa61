"""Device time per decoded token that the state-space layers and the
memory units take: leaf ops inside executions of the fused decode program
in the traced stretch whose scope is `ssm_proj` (in/out projections, conv,
gate), `ssm_scan` (x_proj, dt, the recurrence) or `gmu`
(ray_tpu/ops/scope_names.py), over the tokens of horizon dispatched in the
stretch, as `decode_step_device_ms` counts them: a part of that sum. None
for a program without these scopes (another family, the parent commit)."""

from benchmark.layer_metrics import _hybrid_scopes as hs

LAYER = "jitted programs"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    by = hs.decode_time_by_scope(records, reduced)
    steps = hs.decode_tokens_traced(records)
    if by is None or steps is None:
        return None
    return sum(by.get(s, 0) for s in hs.SSM_SCOPES) / 1e6 / steps

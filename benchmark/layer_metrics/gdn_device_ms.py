"""Device time per decoded token that the gated delta-rule layers take:
leaf ops inside executions of the fused decode program in the traced
stretch whose scope is `gdn_proj` (in/out projections, gates, head norm),
`gdn_conv` (the causal conv and its state) or `gdn_step` (the one-token
update with the matrix state it reads and writes)
(ray_tpu/ops/scope_names.py), over the tokens of horizon dispatched in the
stretch, as `decode_step_device_ms` counts them: a part of that sum. None
for a program without these scopes (another family, the parent commit)."""

from benchmark.layer_metrics import _gdn_scopes as gs

LAYER = "jitted programs"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    by = gs.time_by_scope(records, reduced, gs.DECODE_MODULE)
    steps = gs.decode_tokens_traced(records)
    if by is None or steps is None:
        return None
    return sum(by.get(s, 0) for s in gs.GDN_SCOPES) / 1e6 / steps

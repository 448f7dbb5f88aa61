"""Device time per decoded token that the WINDOW layers' attention takes:
leaf ops inside executions of the fused decode program in the traced
stretch whose scope is `swa_attention` (the pages that cover a row's last
`sliding_window_size` slots picked out of the window table, the window's
mask, and the latent decode kernel over them at 64 heads x 1,152 lanes),
all window layers, over the tokens of horizon dispatched in the stretch: a
part of `decode_step_device_ms`, beside `sparse_attn_device_ms` (the full
layers'). None for a program without the scope."""

from benchmark.layer_metrics import _mla_swa_scopes as ws

LAYER = "jitted programs"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    spent = ws.window_time(records, reduced, ws.DECODE_MODULE,
                           ws.ATTEND_SCOPES)
    steps = ws.decode_tokens_traced(records)
    if spent is None or not steps:
        return None
    return spent[0] / 1e6 / steps

"""Device time per decoded token that attention over the selected tokens
takes: leaf ops inside executions of the fused decode program in the
traced stretch whose scope is `latent_gather` (the chosen slots' latent
rows out of the pool) or `sparse_attention` (scores, softmax and weighted
sum over them, absorbed form), all layers, over the tokens of horizon
dispatched in the stretch: a part of `decode_step_device_ms`. None for a
program without these scopes."""

from benchmark.layer_metrics import _mla_scopes as ms

LAYER = "jitted programs"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    return ms.per_decode_token_ms(records, reduced, ms.ATTEND_SCOPES)

"""The paged kernel of the gated-attention layers' decode step (2 KV heads
of 256, 8 query heads each) against the HBM roofline: the least time the
chip could take to read the keys and values the kernel is ASKED for, over
the kernel's summed device time inside executions of the fused decode
program in the traced stretch.

Bytes = `kv_walk_tokens_full_total` between the traced stretch's two
snapshots x one token-layer, 2,048 B (2 x 2 KV heads x 256 x 2 B;
costs_gdn). The engine counts a token once for each attention layer that
reads it (2 of the 8 layers cache anything). `paged_attn_roofline_pct`
multiplies live tokens by `num_hidden_layers`, which here would count 8
caches where 2 exist. Memory-bound: one query row a sequence. The output
gate is outside the kernel and not in its time (in the decode program XLA
fuses it into the output projection's fusion, so no `attn_gate` op is
left there to tell by). The counter and the kernel's events decide whether
the reader applies; a model whose keys give no such geometry reads None."""

from benchmark.harness import costs, costs_gdn
from benchmark.layer_metrics import _gdn_scopes as gs

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    ns = gs.decode_kernel_ns(reduced)
    full = gs.delta(records, "kv_walk_tokens_full_total", "t0", "t1")
    if not ns or not full:
        return None
    peak = costs.peaks(records["device"]["kind"])
    try:
        least_s = costs_gdn.attention_least_s(records["model"], full,
                                              peak["hbm_bytes_per_s"])
    except KeyError:             # another family's keys: not this geometry
        return None
    return 100.0 * least_s / (ns / 1e9)

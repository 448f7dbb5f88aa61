"""The paged kernel of a hybrid stack's decode step against the HBM
roofline: the least time the chip could take to read the keys and values
the kernel is ASKED for, over the kernel's summed device time inside
executions of the fused decode program in the traced stretch.

Bytes = (`kv_walk_tokens_window_total` + `kv_walk_tokens_full_total`)
between the traced stretch's two snapshots x one token-layer, 5,120 B
(2 x 20 KV heads x 64 x 2 B; costs_hybrid). The engine counts a token
once for each layer that reads it: a window layer at most the window, the
full-attention layer's cache once a READER (itself and the seven
cross-attention layers, one after another). `paged_attn_roofline_pct`
multiplies live tokens by `num_hidden_layers`, which here would count 32
caches where 9 exist; this is that share for a stack whose layers read
unlike amounts. Memory-bound: one query row a sequence."""

from benchmark.harness import costs, costs_hybrid
from benchmark.layer_metrics import _hybrid_scopes as hs

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    ns = hs.decode_kernel_ns(reduced)
    window = hs.delta(records, "kv_walk_tokens_window_total", "t0", "t1")
    full = hs.delta(records, "kv_walk_tokens_full_total", "t0", "t1")
    if not ns or window is None or full is None or not window + full:
        return None
    peak = costs.peaks(records["device"]["kind"])
    least_s = costs_hybrid.attention_least_s(
        records["model"], window + full, peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9)

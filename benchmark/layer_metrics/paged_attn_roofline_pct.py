"""The paged decode-attention kernel against the HBM roofline: the least
time the chip could take to read the keys and values of every live token
once per decode token (bytes from shapes, costs.paged_attention_bytes, over
the chip's published HBM bandwidth) over the kernel's summed device time
in the traced stretch. The trace does not carry a Pallas kernel's name
(harness/xplane.py), so the kernel is found as what it is today: every
`tpu_custom_call` op that runs inside an execution of the decode program
(MODULE). A second kernel in that program would need a `jax.named_scope`
in the program to be told apart (PERF.md, Open questions). Memory-bound:
one query row per sequence."""

from benchmark.harness import costs, xplane

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"
KERNEL = xplane.PALLAS_KERNEL
MODULE = r"decode_multi_paged"


def read(records, reduced):
    if reduced is None or not records.get("kv_tokens_traced"):
        return None
    lines = reduced["trace"].devices[reduced["idlest_chip"]]
    ns, n = xplane.sum_within(
        lines.get(xplane.OPS_LINE, []), KERNEL,
        lines.get(xplane.MODULES_LINE, []), MODULE, reduced["window"])
    if not n:
        return None
    peak = costs.peaks(records["device"]["kind"])
    least_s = costs.paged_attention_bytes(
        records["model"], records["kv_tokens_traced"]) \
        / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)

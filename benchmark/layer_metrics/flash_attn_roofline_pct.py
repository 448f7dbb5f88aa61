"""The flash-attention kernels against the bf16 peak: the FLOPs the
kernels' executions need in the traced steps (counted in the trace) (costs.flash_flops per chip;
the forward counted `fwd_executions` times, twice under full remat, where
the backward pass runs the forward kernel again) over peak, over the
kernels' summed device time, averaged over chips. The trace does not carry
a Pallas kernel's name (harness/xplane.py): the kernels are every
`tpu_custom_call` op inside an execution of the train step (MODULE), which
today are the flash forward, dq and dk/dv kernels and nothing else;
forward and backward cannot be told apart without a `jax.named_scope` in
the program. Compute-bound at 4,096 tokens."""

from benchmark.harness import costs, xplane

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"
KERNEL = xplane.PALLAS_KERNEL
MODULE = r"step_fn"


def read(records, reduced):
    if reduced is None:
        return None
    times = []
    steps = 0
    for lines in reduced["trace"].devices.values():
        steps = max(steps, xplane.sum_matching(
            lines.get(xplane.MODULES_LINE, []), MODULE,
            reduced["window"])[1])
        ns, n = xplane.sum_within(
            lines.get(xplane.OPS_LINE, []), KERNEL,
            lines.get(xplane.MODULES_LINE, []), MODULE, reduced["window"])
        if n:
            times.append(ns / 1e9)
    if not times:
        return None
    need = costs.flash_flops(records["model"], records["seq_len"],
                             steps * records["batch"])
    per_chip = (need["fwd"] * records["fwd_executions"] + need["bwd"]) \
        / records["chips"]
    peak = costs.peaks(records["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * per_chip / peak / (sum(times) / len(times))

"""Share of a chip's busy time in the traced steps that runs the forward
pass a second time: leaf ops inside executions of the train step (MODULE)
whose op_name carries JAX's mark for the recomputation of a
`jax.checkpoint`ed function (`rematted_computation`, under `checkpoint`;
harness/scopes.py), over the chip's busy time, averaged over chips. The
layers' forward under `remat_policy="full"` and the chunked loss's
projection both carry it. None when no op of the step has an op_name."""

from benchmark.harness import scopes, xplane

LAYER = "train step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"
MODULE = r"step_fn"


def read(records, reduced):
    if reduced is None:
        return None
    names = scopes.op_names(xplane.find_xplane(records["session"].dir))
    shares = []
    for chip, lines in reduced["trace"].devices.items():
        per = names.get(chip, {})
        inside = scopes.leaves_within(
            lines.get(xplane.OPS_LINE, []),
            lines.get(xplane.MODULES_LINE, []), MODULE, reduced["window"])
        busy = reduced["busy_s_by_chip"].get(chip, 0.0)
        if not busy or not any(n in per for n, _, _ in inside):
            continue
        again = sum(d for n, _, d in inside if scopes.is_remat(per.get(n)))
        shares.append(100.0 * again / 1e9 / busy)
    return sum(shares) / len(shares) if shares else None

"""What the `phi4flash` readers share: the hybrid stack's scopes as the
program names them, device time by scope inside executions of the fused
decode program in the traced stretch, and differences of the engine's
counters. Not a reader itself (no entry names it). A program without
these scopes or counters (any other family, the parent commit) gives
None everywhere, and the readers leave their metric out.
"""

from benchmark.harness import scopes, xplane
from benchmark.layer_metrics._moe_scopes import (   # noqa: F401
    decode_tokens_traced)

try:            # the program's own names; absent before the hybrid family
    from ray_tpu.ops.scope_names import GMU, SSM_PROJ, SSM_SCAN
    SSM_SCOPES = (SSM_PROJ, SSM_SCAN, GMU)
except ImportError:
    SSM_SCAN = None
    SSM_SCOPES = ()

DECODE_MODULE = r"decode_multi_paged"


def decode_time_by_scope(records, reduced):
    """{scope or None: ns} of the leaf ops inside executions of the decode
    program in the traced stretch on the idlest chip; None when there is
    no trace, the program has no such scopes, or no op there carries one."""
    if reduced is None or not SSM_SCOPES or scopes.SCOPES is None:
        return None
    chip = reduced["idlest_chip"]
    lines = reduced["trace"].devices[chip]
    names = scopes.op_names(
        xplane.find_xplane(records["session"].dir)).get(chip, {})
    leaves = scopes.leaves_within(
        lines.get(xplane.OPS_LINE, []), lines.get(xplane.MODULES_LINE, []),
        DECODE_MODULE, reduced["window"])
    by = scopes.time_by(leaves, names, lambda n, op: scopes.scope_of(op))
    return by if any(s in by for s in SSM_SCOPES) else None


def decode_kernel_ns(reduced):
    """Summed device time of the Pallas kernels inside executions of the
    decode program in the traced stretch (this family's decode program
    has the paged kernel alone); None without a trace or a kernel."""
    if reduced is None:
        return None
    lines = reduced["trace"].devices[reduced["idlest_chip"]]
    ns, n = xplane.sum_within(
        lines.get(xplane.OPS_LINE, []), xplane.PALLAS_KERNEL,
        lines.get(xplane.MODULES_LINE, []), DECODE_MODULE,
        reduced["window"])
    return ns if n else None


def delta(records, name: str, first: str = "w0", last: str = "w1"):
    """`engine.stats()[name]` at snapshot `last` less `first` (the
    window's ends, or "t0"/"t1": the traced stretch's; the counters of
    this family are counted at dispatch, as the horizon aggregate is, so
    a difference over the stretch lines up with what the device ran there
    to within a step). None when the engine has no such counter."""
    a, b = records["snaps"].get(first), records["snaps"].get(last)
    if not a or not b or name not in b:
        return None
    return b[name] - a.get(name, 0.0)

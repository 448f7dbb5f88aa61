"""What the `qwen3next` readers share: the delta-rule stack's scopes as the
program names them, device time by scope inside one jitted program's
executions in the traced stretch, and differences of the engine's
counters. Not a reader itself (no entry names it). A program without
these scopes or counters (any other family, the parent commit) gives None
everywhere, and the readers leave their metric out.
"""

from benchmark.harness import scopes, xplane
from benchmark.layer_metrics._hybrid_scopes import (   # noqa: F401
    decode_kernel_ns, delta)
from benchmark.layer_metrics._moe_scopes import (   # noqa: F401
    DECODE_MODULE, PREFILL_MODULE, _scope, decode_tokens_traced)

try:            # the program's own names; absent before this family
    from ray_tpu.ops.scope_names import (GDN_CHUNK, GDN_CONV, GDN_PROJ,
                                         GDN_STEP, MOE_EXPERTS)
    GDN_SCOPES = (GDN_PROJ, GDN_CONV, GDN_CHUNK, GDN_STEP)
except ImportError:
    GDN_CHUNK = GDN_STEP = MOE_EXPERTS = None
    GDN_SCOPES = ()


def time_by_scope(records, reduced, module: str):
    """{scope or None: ns} of the leaf ops inside executions of `module`
    in the traced stretch on the idlest chip; None when there is no trace,
    the program has no delta-rule scopes, or no op there carries one."""
    if reduced is None or not GDN_SCOPES or scopes.SCOPES is None:
        return None
    chip = reduced["idlest_chip"]
    lines = reduced["trace"].devices[chip]
    names = scopes.op_names(
        xplane.find_xplane(records["session"].dir)).get(chip, {})
    leaves = scopes.leaves_within(
        lines.get(xplane.OPS_LINE, []), lines.get(xplane.MODULES_LINE, []),
        module, reduced["window"])
    by = scopes.time_by(leaves, names, _scope)
    return by if any(s in by for s in GDN_SCOPES) else None

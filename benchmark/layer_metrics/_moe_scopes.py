"""What the four `moe_*` readers share: the expert layer's scopes as the
program names them, and device time by scope inside one jitted program's
executions in the traced stretch. Not a reader itself (no entry names it).
"""

from benchmark.harness import scopes, xplane

try:            # the program's own names; absent before the expert layer
    from ray_tpu.ops.scope_names import (MOE_DISPATCH, MOE_EXPERTS,
                                         MOE_ROUTER)
    MOE_SCOPES = (MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS)
except ImportError:
    MOE_EXPERTS = None
    MOE_SCOPES = ()

# XLA:TPU lowers `jax.lax.ragged_dot` to kernels of its own and REPLACES
# their op_name ("ragged-dot-none", "ragged-dot-metadata"; seen in PR 26's
# traces and in the program compiled for a described v5e), so the scope
# they were written under is lost. The program's only ragged dots are the
# sorted regime's expert matmuls: they count as `moe_experts`.
RAGGED_DOT = "ragged-dot"

DECODE_MODULE = r"decode_multi_paged"
PREFILL_MODULE = r"prefill_rows_paged"


def _scope(event_name: str, op_name):
    scope = scopes.scope_of(op_name)
    if scope is None and RAGGED_DOT in (op_name or event_name):
        return MOE_EXPERTS
    return scope


def time_by_scope(records, reduced, module: str):
    """{scope or None: ns} of the leaf ops inside executions of `module`
    in the traced stretch on the idlest chip; None when there is no trace,
    the program has no expert-layer scopes, or no op there carries any."""
    if reduced is None or not MOE_SCOPES or scopes.SCOPES is None:
        return None
    chip = reduced["idlest_chip"]
    lines = reduced["trace"].devices[chip]
    names = scopes.op_names(
        xplane.find_xplane(records["session"].dir)).get(chip, {})
    leaves = scopes.leaves_within(
        lines.get(xplane.OPS_LINE, []), lines.get(xplane.MODULES_LINE, []),
        module, reduced["window"])
    if not any(scopes.scope_of(names.get(n)) in MOE_SCOPES
               for n, _, _ in leaves):
        return None
    return scopes.time_by(leaves, names, _scope)


def decode_tokens_traced(records):
    """Tokens of horizon the decode program was dispatched for in the
    traced stretch, as `decode_step_device_ms` counts them; None without
    the two snapshots."""
    a, b = records["snaps"].get("t0"), records["snaps"].get("t1")
    if not a or not b:
        return None
    steps = b["decode_horizon_mean"] * b["decode_horizon_count"] \
        - a["decode_horizon_mean"] * a["decode_horizon_count"]
    return steps if steps > 0 else None


def counter_delta(records, name: str):
    """`engine.stats()[name]` at the window's end less its start; None when
    the engine has no such counter."""
    a, b = records["snaps"].get("w0"), records["snaps"].get("w1")
    if not a or not b or name not in b:
        return None
    return b[name] - a.get(name, 0.0)

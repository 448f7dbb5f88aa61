"""Device time per decoded token that moves cached K/V and computes
nothing: leaf ops inside executions of the fused decode program (MODULE)
in the traced stretch whose scope is `kv_write` or `kv_gather`
(ray_tpu/ops/scope_names.py: KV_MOVE), plus the leaf ops there under no
scope of the program's at all (the layer scan's slicing of the pool and
the copies of it the compiler inserts), over the tokens of horizon
dispatched in the stretch, as `decode_step_device_ms` counts them. None
when no op of the decode program carries a scope (harness/scopes.py says
when that is)."""

from benchmark.harness import scopes, xplane

LAYER = "jitted programs"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"
MODULE = r"decode_multi_paged"


def read(records, reduced):
    a, b = records["snaps"].get("t0"), records["snaps"].get("t1")
    if reduced is None or not a or not b or scopes.SCOPES is None:
        return None
    chip = reduced["idlest_chip"]
    lines = reduced["trace"].devices[chip]
    names = scopes.op_names(
        xplane.find_xplane(records["session"].dir)).get(chip, {})
    by = scopes.time_by(
        scopes.leaves_within(lines.get(xplane.OPS_LINE, []),
                             lines.get(xplane.MODULES_LINE, []), MODULE,
                             reduced["window"]),
        names, lambda n, op: scopes.scope_of(op))
    steps = b["decode_horizon_mean"] * b["decode_horizon_count"] \
        - a["decode_horizon_mean"] * a["decode_horizon_count"]
    if steps <= 0 or not any(k is not None for k in by):
        return None
    moved = by.get(None, 0) + sum(by.get(s, 0) for s in scopes.KV_MOVE)
    return moved / 1e6 / steps

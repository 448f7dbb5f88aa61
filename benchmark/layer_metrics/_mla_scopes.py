"""What the `dsv32` readers share: the selection's scopes as the program
names them, device time by scope inside one jitted program's executions in
the traced stretch, and differences of the engine's counters. Not a reader
itself (no entry names it). A program without these scopes or counters
(any other family, the parent commit) gives None everywhere, and the
readers leave their metric out.
"""

from benchmark.harness import scopes, xplane
from benchmark.layer_metrics._hybrid_scopes import delta   # noqa: F401
from benchmark.layer_metrics._moe_scopes import (   # noqa: F401
    DECODE_MODULE, PREFILL_MODULE, decode_tokens_traced)

try:            # the program's own names; absent before this family
    from ray_tpu.ops.scope_names import (INDEXER_SCORE, INDEXER_TOPK,
                                         LATENT_GATHER, MOE_EXPERTS,
                                         SPARSE_ATTENTION)
    INDEXER_SCOPES = (INDEXER_SCORE, INDEXER_TOPK)
    ATTEND_SCOPES = (LATENT_GATHER, SPARSE_ATTENTION)
except ImportError:
    INDEXER_SCORE = MOE_EXPERTS = None
    INDEXER_SCOPES = ATTEND_SCOPES = ()


def time_by_scope(records, reduced, module: str):
    """{scope or None: ns} of the leaf ops inside executions of `module`
    in the traced stretch on the idlest chip; None when there is no trace,
    the program has no selection scopes, or no op there carries one."""
    if reduced is None or not INDEXER_SCOPES or scopes.SCOPES is None:
        return None
    chip = reduced["idlest_chip"]
    lines = reduced["trace"].devices[chip]
    names = scopes.op_names(
        xplane.find_xplane(records["session"].dir)).get(chip, {})
    leaves = scopes.leaves_within(
        lines.get(xplane.OPS_LINE, []), lines.get(xplane.MODULES_LINE, []),
        module, reduced["window"])
    by = scopes.time_by(leaves, names, lambda n, op: scopes.scope_of(op))
    mine = INDEXER_SCOPES + ATTEND_SCOPES
    return by if any(s in by for s in mine) else None


def per_decode_token_ms(records, reduced, which):
    """Device ms a decoded token under the scopes `which`, inside the
    fused decode program in the traced stretch."""
    by = time_by_scope(records, reduced, DECODE_MODULE)
    steps = decode_tokens_traced(records)
    if by is None or steps is None:
        return None
    return sum(by.get(s, 0) for s in which) / 1e6 / steps

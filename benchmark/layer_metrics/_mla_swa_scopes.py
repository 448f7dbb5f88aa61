"""What the `swa_*` readers share: a latent WINDOW layer's scopes as the
program names them (`dots3_note`: an `MlaConfig` with `layer_types`), read
through `_mla_scopes`' device time by scope. Not a reader itself (no entry
names it). A program without these scopes (any other family, the parent
commit) gives empty tuples, and the readers leave their metric out.
"""

from benchmark.layer_metrics._mla_scopes import (   # noqa: F401
    DECODE_MODULE, PREFILL_MODULE, decode_tokens_traced, delta,
    time_by_scope)

try:            # the program's own names; absent before this family
    from ray_tpu.ops.scope_names import (SWA_ATTENTION, SWA_GATE, SWA_PROJ,
                                         SWA_WRITE)
    ATTEND_SCOPES = (SWA_ATTENTION,)
    LAYER_SCOPES = (SWA_PROJ, SWA_WRITE, SWA_ATTENTION, SWA_GATE)
except ImportError:
    ATTEND_SCOPES = LAYER_SCOPES = ()


def window_time(records, reduced, module: str, which):
    """(ns under the scopes `which`, ns of all leaf ops) inside executions
    of `module` in the traced stretch; None without a trace, without the
    scopes, or where no op carries one of them."""
    if not which:
        return None
    by = time_by_scope(records, reduced, module)
    if by is None or not any(s in by for s in which):
        return None
    return sum(by.get(s, 0) for s in which), sum(by.values())

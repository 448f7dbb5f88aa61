"""Seconds a step spent handing programs to the device: inside
`eng.dispatch` / `eng.spec_draft` (a decode block: its operands, the
block-table snapshot, the launch; `step_dispatch_s_total`) and inside
`eng.advance_prefills` (the chunks' groups, their `eng.prefill_dispatch`
launches, the frontier's bookkeeping; `step_prefill_dispatch_s_total`),
over `steps_total`; differences of the snapshots at the window's two
ends. One part of `step_self_ms`. None where the engine has no such
counter."""

from benchmark.layer_metrics import _step_clocks as sc

LAYER = "engine step loop, host"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    return sc.per_step_ms(records, "step_dispatch_s_total",
                          "step_prefill_dispatch_s_total")

"""The host's own work per `engine.step()` inside the window, on the
engine's own clock: `step_s_total` (entry to exit of every `step()`) less
`device_wait_s` (the seconds inside `_device_get`), over `steps_total`;
differences of the snapshots at the window's two ends. The quantity
`step_host_ms` takes from outside (the harness's spans less ONE counter)
taken from inside: the two should agree to a few tenths of a ms, and this
one splits further (`step_emit_ms`, `step_dispatch_ms`, `step_admit_ms`;
the flush and the remainder are `step_flush_s_total` and
`step_other_s_total` of `engine.stats()`). The same intervals as the
`eng.*` lanes of a profiler trace. None where the engine has no such
counter."""

from benchmark.layer_metrics import _step_clocks as sc

LAYER = "engine step loop, host"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    wall = sc.per_step_ms(records, "step_s_total")
    waited = sc.per_step_ms(records, "device_wait_s")
    return None if wall is None or waited is None else wall - waited

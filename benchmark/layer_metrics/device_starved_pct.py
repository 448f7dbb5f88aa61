"""Share of the window in which the device had nothing to run AS FAR AS
THE HOST KNOWS: `device_starved_s_total` of `engine.stats()` (from a pull
that returned with the ring empty and no program dispatched after the
pulled block, to the return of the next dispatch of any program) over the
engine's own seconds (`uptime_s`) between the snapshot at the window's
start and, in a traced run, the one taken as the profiler starts (`t0`;
`_step_clocks.before_trace` says why the profiler's stop is left out),
else the one at the window's end. The host's estimate over 40 of the
window's 45 s, where the trace's idle share covers three: it misses what
the host cannot see (a ring that ran dry under a slow replay, the
dispatch's own latency) and counts a chunk sent ahead as work in flight.
By cause in `engine.stats()` (`device_starved_retire_s_total`, `_admit_`,
`_chunk_`, `_other_`); `starved_after_retire_pct` is the first. None where
the engine has no such counter."""

from benchmark.layer_metrics import _step_clocks as sc

LAYER = "engine step loop, host"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    return sc.share_pct(records, "device_starved_s_total", "uptime_s",
                        sc.before_trace(records))

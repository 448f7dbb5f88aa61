"""Of the seconds the device had nothing to run (`device_starved_pct`),
the share that followed a RETIREMENT: a row ended in a block drained since
the last decode dispatch, so the ring had stopped short of that block (a
budget known to end in it, a request waiting) or an EOS freed a slot, and
the device stood until the host had replayed the block, gated and
dispatched. What admitting a newcomer BEHIND such a block would win
(ROADMAP S14 b). `device_starved_retire_s_total` over
`device_starved_s_total`, over the same stretch as `device_starved_pct`.
0 where nothing starved; None where the engine has no such counter."""

from benchmark.layer_metrics import _step_clocks as sc

LAYER = "engine step loop, host"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    return sc.share_pct(records, "device_starved_retire_s_total",
                        "device_starved_s_total", sc.before_trace(records))

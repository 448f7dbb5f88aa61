"""Share of the device's busy time in the traced stretch that lay inside
prefill programs ("XLA Modules" events whose name matches MODULE)."""

from benchmark.harness import xplane

LAYER = "jitted programs"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "ttft_p95_ms"
MODULE = r"prefill_rows_paged"


def read(records, reduced):
    if reduced is None:
        return None
    chip = reduced["idlest_chip"]
    mods = reduced["trace"].devices[chip].get(xplane.MODULES_LINE, [])
    ns, n = xplane.sum_matching(mods, MODULE, reduced["window"])
    busy = reduced["busy_s_by_chip"][chip]
    return 100.0 * ns / 1e9 / busy if n and busy else None

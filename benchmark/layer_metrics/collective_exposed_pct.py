"""Share of the traced stretch in which a collective op ran on a chip
while no other op did, on the chip where that share is largest."""

from benchmark.harness import xplane

LAYER = "collectives"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(records, reduced):
    if reduced is None or len(reduced["trace"].devices) < 2:
        return None
    worst = max(xplane.exposed_collective_ns(
        lines.get(xplane.OPS_LINE, []), reduced["window"])
        for lines in reduced["trace"].devices.values())
    return 100.0 * worst / 1e9 / reduced["window_s"]

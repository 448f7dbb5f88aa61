"""Read a trace by hand: `python3 -m benchmark.harness.trace_summary
<trace dir or .xplane.pb>` prints, per chip and line, the event count and
the names with most time, and the host spans."""

import json
import os
import sys

from benchmark.harness import xplane


def main(argv) -> int:
    path = argv[1]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    print(json.dumps(xplane.summary(xplane.load(path)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""The host's own work per `engine.step()` inside the window: the summed
wall time of the harness's spans around the call, less the time the
engine itself counted blocked on the device in them (`device_wait_s` of
`engine.stats()`, the seconds inside `_device_get`; difference of the
snapshots at the window's two ends), over the number of steps. What
`step_wall_p50_ms` cannot say: how far the host holds the chip once a
decode token is fast. None where the engine has no such counter.

The steps are the ones the engine itself counted between the two
snapshots (`steps_total`), taken from the first span that starts inside
the window: in a traced run the driver takes the closing snapshot one
step after the window's end (it reads the clock before it stops the
profiler, which takes two seconds, and tests the stale reading), and
that step's wait must not be charged to the steps before it."""

LAYER = "engine step loop, host"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(records, reduced):
    a, b = records["snaps"].get("w0"), records["snaps"].get("w1")
    if not a or not b or "device_wait_s" not in b:
        return None
    spans = sorted(s for s in records["spans"].by_name.get("engine.step", [])
                   if s[0] >= records["window"][0])
    steps = [d for _, d in spans[:int(b["steps_total"] - a["steps_total"])]]
    if not steps:
        return None
    waited = b["device_wait_s"] - a.get("device_wait_s", 0.0)
    return (sum(steps) - waited) / len(steps) * 1e3

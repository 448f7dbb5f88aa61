"""95th percentile of the time a request waited in the engine's queue
before admission, all requests since the engine started (ramp included),
on the engine's own host clock (`engine.stats()["queue_wait_s_p95"]`)."""

LAYER = "scheduler and admission"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"


def read(records, reduced):
    v = records["stats_end"].get("queue_wait_s_p95")
    return None if v is None else v * 1e3

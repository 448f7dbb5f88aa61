"""Share of prefill positions computed inside the window that were bucket
or group padding: d(padded) / d(real + padded) of the engine's counters
between the window's first and last step."""

LAYER = "scheduler and admission"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"


def read(records, reduced):
    a, b = records["snaps"].get("w0"), records["snaps"].get("w1")
    if not a or not b:
        return None
    pad = b["prefill_padded_tokens"] - a["prefill_padded_tokens"]
    real = b["prefill_real_tokens"] - a["prefill_real_tokens"]
    return 100.0 * pad / (pad + real) if pad + real else None

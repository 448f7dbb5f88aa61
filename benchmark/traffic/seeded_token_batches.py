"""Training batches: uniform token ids, a fresh batch for every step.

Parameters: batch (global), seq_len. `make(step)` returns the step's
[batch, seq_len + 1] int32 array; it depends on (seed, step) only, so a
seed gives the same batches whatever thread asks and in whatever order.
"""

from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int, seconds: float, vocab_size: int
             ) -> dict:
    batch, seq = int(params["batch"]), int(params["seq_len"])

    def make(step: int) -> np.ndarray:
        rng = np.random.default_rng([int(seed), 0xBA7C4, int(step)])
        return rng.integers(0, vocab_size, size=(batch, seq + 1),
                            dtype=np.int32)

    return {"kind": "batches", "batch": batch, "seq_len": seq, "make": make}

"""Closed loop: a fixed number of clients, each sending its next request
when its last one ends.

Parameters: clients; prompt / output {"median", "sigma", "min", "max"};
block (size of one stratified set); blocks (how many sets are made: more
than any run consumes); ramp_s (set-up before the window); stagger_first
(the first so many requests get a uniform share of their output length,
so the slots do not all finish together at the start: a loop that has
run for a while, without the two residences of ramp that would cost).

`schedule_seed` (optional) fixes the order of lengths as part of the mix,
as in open_poisson_lognormal.py; the run's seed then draws token ids only.

The list is block after block, each block the same stratified set of
lengths in an order the seed picks, so any stretch of a run sees the same
mix. Token ids are uniform in [1, vocab) from the seed.
"""

from __future__ import annotations

import numpy as np

from benchmark.traffic._stratified import lognormal_lengths, shuffled


def generate(params: dict, seed: int, seconds: float, vocab_size: int
             ) -> dict:
    tok = np.random.default_rng([int(seed), 0xC105ED])
    rng = np.random.default_rng(
        [int(params.get("schedule_seed", seed)), 0x5C4ED])
    n = int(params["block"])
    p, o = params["prompt"], params["output"]
    requests = []
    for _ in range(int(params["blocks"])):
        plens = shuffled(lognormal_lengths(
            n, p["median"], p["sigma"], p["min"], p["max"]), rng)
        olens = shuffled(lognormal_lengths(
            n, o["median"], o["sigma"], o["min"], o["max"]), rng)
        for pl, ol in zip(plens, olens):
            requests.append({
                "prompt": tok.integers(1, vocab_size, size=pl,
                                       dtype=np.int32),
                "max_new_tokens": int(ol)})
    k = int(params.get("stagger_first", 0))
    shares = shuffled([(i + 0.5) / k for i in range(k)], rng) if k else []
    for r, share in zip(requests, shares):
        r["max_new_tokens"] = max(int(o["min"]) // 4, 2, int(round(
            r["max_new_tokens"] * share)))
    return {"kind": "closed", "clients": int(params["clients"]),
            "requests": requests, "ramp_s": float(params["ramp_s"])}

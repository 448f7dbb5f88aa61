"""Open loop: arrivals at a fixed rate, whatever the system does.

Parameters (the cell's file, under "traffic"):
  rate_rps                 offered requests per second, fixed
  prompt / output          {"median", "sigma", "min", "max"}: lognormal
  ramp_s                   arrivals before the window (set-up, not counted)
  tail_s                   arrivals after it, so counted requests end under
                           the same load they began in (not counted)
  schedule_seed            optional: fixes the ORDER of gaps and lengths as
                           part of the mix; the run's seed then draws the
                           token ids (and the weights) only. Without it the
                           run's seed also picks the order.

Three segments (ramp, window, tail), each with round(rate * length)
requests: exponential gaps that sum to the segment's length and lognormal
lengths, both as fixed stratified sets that the seed permutes
(_stratified.py) or, with `schedule_seed`, that the mix itself fixes:
which long prompt meets which burst moves a 95th percentile of some seventy
requests by 5-15 % (PERF.md), more than any change a check should see. Token
ids are uniform in [1, vocab) from the run's seed; no prefix is shared. Times are seconds relative to the start of the window.
"""

from __future__ import annotations

import numpy as np

from benchmark.traffic._stratified import exponential_gaps, lognormal_lengths, shuffled


def generate(params: dict, seed: int, seconds: float, vocab_size: int
             ) -> dict:
    tok = np.random.default_rng([int(seed), 0x0A11])
    rng = np.random.default_rng(
        [int(params.get("schedule_seed", seed)), 0x5C4ED])
    rate = float(params["rate_rps"])
    requests = []
    for name, start, length in (("ramp", -float(params["ramp_s"]),
                                 float(params["ramp_s"])),
                                ("window", 0.0, float(seconds)),
                                ("tail", float(seconds),
                                 float(params["tail_s"]))):
        n = int(round(rate * length))
        if n == 0:
            continue
        gaps = shuffled(exponential_gaps(n, length), rng)
        due = start + np.cumsum(gaps) - gaps[0] * rng.random()
        p, o = params["prompt"], params["output"]
        plens = shuffled(lognormal_lengths(
            n, p["median"], p["sigma"], p["min"], p["max"]), rng)
        olens = shuffled(lognormal_lengths(
            n, o["median"], o["sigma"], o["min"], o["max"]), rng)
        for t, pl, ol in zip(due, plens, olens):
            requests.append({
                "due_s": float(t),
                "prompt": tok.integers(1, vocab_size, size=pl,
                                       dtype=np.int32),
                "max_new_tokens": int(ol),
                "counted": name == "window"})
    requests.sort(key=lambda r: r["due_s"])
    return {"kind": "open", "requests": requests,
            "ramp_s": float(params["ramp_s"])}

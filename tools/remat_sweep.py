"""One-off: sweep remat policies on the real chip to place bench.py's
flagship/large configs on the HBM/recompute frontier.

Full remat re-runs the whole layer forward in the backward pass (~+33%
executed FLOPs that MFU does not count). Saving the FLOPs-heavy dot
outputs (ffn gate/up/down, qkv) trades HBM for recompute; this sweep
measures each candidate policy's tokens/s + MFU and reports OOMs.

Usage: python tools/remat_sweep.py [flagship|large|both]
"""

import json
import os
import sys

# repo root on sys.path, so the script runs from any directory
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_policy(cfg, policy, batch_size, seq_len, steps, trials=3):
    """One timed config through bench.py's own measurement harness (same
    warmup/median/sanity-check code path the round bench uses)."""
    import dataclasses

    import jax

    from bench import _bench_config, _detect_peak

    r = _bench_config(dataclasses.replace(cfg, remat_policy=policy),
                      batch_size=batch_size, seq_len=seq_len, steps=steps,
                      trials=trials, devices=jax.devices()[:1],
                      peak=_detect_peak())
    return {"policy": policy,
            "tokens_per_sec": r["tokens_per_sec_per_chip"],
            "mfu": r["mfu"], "spread_pct": r["trial_spread_pct"]}


def sweep(name, cfg, batch_size, seq_len, steps, policies):
    import jax

    print(f"== {name} (batch={batch_size}) ==", flush=True)
    results = []
    for policy in policies:
        try:
            r = bench_policy(cfg, policy, batch_size, seq_len, steps)
        except Exception as e:  # noqa: BLE001 — OOM is an expected outcome
            r = {"policy": policy,
                 "error": f"{type(e).__name__}: {str(e)[:120]}"}
        # free compilation caches between configs
        jax.clear_caches()
        print(json.dumps(r), flush=True)
        results.append(r)
    return results


def main():
    import dataclasses

    # the configs under test ARE bench.py's (its remat_policy choice is
    # what this sweep selects; reset to the full-remat baseline here)
    from bench import flagship_config, large_config

    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    if which not in ("flagship", "large", "both"):
        sys.exit(f"usage: remat_sweep.py [flagship|large|both] "
                 f"(got {which!r})")

    flagship = dataclasses.replace(flagship_config(), remat_policy="full")
    large = dataclasses.replace(large_config(), remat_policy="full")

    if which in ("flagship", "both"):
        sweep("flagship 551M", flagship, 8, 2048, 10, [
            "full",
            "save:ffn_down",
            "save:ffn_down+wo_out",
            "save:ffn_down+wo_out+qkv",
            "save:ffn_gate+ffn_up+ffn_down",
            "save:qkv+ffn_gate+ffn_up+ffn_down",
            "save_dots",
        ])
    if which in ("large", "both"):
        sweep("large 1.55B", large, 4, 2048, 6, [
            "full",
            "save:ffn_down",
            "save:ffn_down+wo_out",
            "save:ffn_down+wo_out+qkv",
            "save:ffn_gate+ffn_up+ffn_down",
        ])


if __name__ == "__main__":
    main()

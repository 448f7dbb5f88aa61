"""The controls of `dsv32-longdoc`'s `correct`: the cell itself, its own
traffic, engine options, sample and limits, with a WRONG program behind
the engine and the reference left right. A wrong program has to come out
not correct through `serve_mla.margin_verdict`; the right one correct.

    python3 -m benchmark.harness.controls_mla --variant fp8 --seed <n>
        [--seconds <s>] [--rehearse]

Variants:
  right       the program as it is (benchmark/run.py's run, untraced)
  fp8         every RMSNorm's output rounded to float8 e4m3: activations
              in the nearest precision below the configuration's bf16
  attend_all  the engine attends every live token (`index_topk` =
              `max_len`): the selection is gone

Prints the run's lines, then one line {"variant", "refused", "logit_check"}
and exits 0 where the verdict is the expected one (a wrong variant
refused by the margins, the right one passed), 1 otherwise. The limits'
readings in the configuration's `correct.derivation` come from this file
on the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

VARIANTS = ("right", "fp8", "attend_all")


@contextlib.contextmanager
def wrong_program(serve_mla, variant: str, seen: dict):
    """Put ``variant`` behind the engine of the cell's driver
    (``serve_mla``, the module the cell loaded) for the block, and leave
    the verdict of its `check_logits` in ``seen``."""
    from ray_tpu.models import mla

    plain = {"norm": mla._rmsnorm, "config": serve_mla.program_config,
             "check": serve_mla.check_logits}

    def fp8_norm(x, w, eps):
        import jax.numpy as jnp
        y = plain["norm"](x, w, eps)
        return y.astype(jnp.float8_e4m3fn).astype(y.dtype)

    def attend_all_config(model, max_len):
        cfg, init, ref = plain["config"](model, max_len)
        return dataclasses.replace(cfg, index_topk=max_len), init, ref

    def check(*args, **kw):
        seen["logit_check"] = plain["check"](*args, **kw)
        return seen["logit_check"]

    if variant == "fp8":
        mla._rmsnorm = fp8_norm
    if variant == "attend_all":
        serve_mla.program_config = attend_all_config
    serve_mla.check_logits = check
    try:
        yield
    finally:
        mla._rmsnorm = plain["norm"]
        serve_mla.program_config = plain["config"]
        serve_mla.check_logits = plain["check"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", choices=VARIANTS, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark.harness import spec
    from benchmark.harness.common import say

    cell = spec.load_cell("dsv32-longdoc")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    seconds = args.seconds if args.seconds is not None else cell.run_seconds
    out_dir = os.path.join(ROOT, "benchmark", "out", cell.name)
    os.makedirs(out_dir, exist_ok=True)

    import jax

    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    say(workload=cell.name, variant=args.variant, seed=args.seed,
        seconds=seconds, rehearse=args.rehearse)
    seen: dict = {}
    with wrong_program(cell.driver, args.variant, seen):
        result = cell.driver.run_cell(cell, args.seed, seconds, False,
                                      args.rehearse, out_dir, say)
    say(correct=result["correct"], attempted=result["attempted"],
        failed=result["failed"], e2e=result["e2e"])
    check = seen.get("logit_check", {})
    refused = not check.get("pass", False)
    print(json.dumps({"variant": args.variant, "refused": refused,
                      "logit_check": check}), flush=True)
    return 0 if refused == (args.variant != "right") else 1


if __name__ == "__main__":
    sys.exit(main())

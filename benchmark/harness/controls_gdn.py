"""The controls of `qwen3next-longctx`'s `correct`: the cell itself, its
own traffic, engine options, sample and limits, with a WRONG program
behind the engine and the reference left right. A wrong program has to
come out not correct through `serve_gdn`'s margin verdict (of the sampled
requests or of the admission probes: `not_zeroed` shows in the probes
alone); the right one correct.

    python3 -m benchmark.harness.controls_gdn --variant fp8 --seed <n>
        [--seconds <s>] [--rehearse]

Variants:
  right        the program as it is (benchmark/run.py's run, untraced)
  fp8          every zero-centred RMSNorm's output rounded to float8 e4m3:
               activations in the nearest precision below the
               configuration's bf16
  not_zeroed   a slot's recurrent state is not zeroed at admission: a new
               row starts from what the slot's last tenant left
  not_handed   the state is not handed from chunk to chunk: every chunk
               of a prompt starts from zero
  no_decay     the decay exp(g) dropped (g = 0): the state never forgets
  beta_one     the write strength beta = 1
  no_l2norm    q and k not L2-normalised
  no_attn_gate the attention heads' output gate dropped
  full_rotary  rotary over all 256 dims of a head, not the first 64
  no_shared_gate  the shared expert's sigmoid gate dropped

Prints the run's lines, then one line {"variant", "refused", "logit_check"}
and exits 0 where the verdict is the expected one (a wrong variant
refused by the margins, the right one passed), 1 otherwise. The limits'
readings in the configuration's `correct.derivation` come from this file
on the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

VARIANTS = ("right", "fp8", "not_zeroed", "not_handed", "no_decay",
            "beta_one", "no_l2norm", "no_attn_gate", "full_rotary",
            "no_shared_gate")


def _patches(variant: str):
    """[(module, attribute, replacement)] of one wrong program."""
    import jax.numpy as jnp

    from ray_tpu.models import gdn, moe

    def fp8_norm(x, w, eps, plain=gdn._rmsnorm1p):
        y = plain(x, w, eps)
        return y.astype(jnp.float8_e4m3fn).astype(y.dtype)

    return {
        "right": [],
        "fp8": [(gdn, "_rmsnorm1p", fp8_norm)],
        "not_zeroed": [(gdn, "_starts_fresh", lambda starts: starts < 0)],
        "not_handed": [(gdn, "_starts_fresh", lambda starts: starts >= 0)],
        "no_decay": [(gdn, "_log_decay",
                      lambda a, p: jnp.zeros(a.shape, jnp.float32))],
        "beta_one": [(gdn, "_write_strength",
                      lambda b: jnp.ones(b.shape, jnp.float32))],
        "no_l2norm": [(gdn, "_unit_keys", lambda q, k, scale: (
            q.astype(jnp.float32) * scale, k.astype(jnp.float32)))],
        "no_attn_gate": [(gdn, "_gate_heads", lambda o, gate: o)],
        "full_rotary": [(gdn, "_rotary_dims", lambda cfg: cfg.head_dim)],
        "no_shared_gate": [(moe, "_shared_gate",
                            lambda x, w, dt: jnp.ones((), dt))],
    }[variant]


@contextlib.contextmanager
def wrong_program(serve_gdn, variant: str, seen: dict):
    """Put ``variant`` behind the engine of the cell's driver
    (``serve_gdn``, the module the cell loaded) for the block, and leave
    the verdict of its `check_logits` in ``seen``."""
    plain_check = serve_gdn.check_logits
    patches = [(m, n, getattr(m, n), fn) for m, n, fn in _patches(variant)]

    def check(*args, **kw):
        seen["logit_check"] = plain_check(*args, **kw)
        return seen["logit_check"]

    for m, n, _, fn in patches:
        setattr(m, n, fn)
    serve_gdn.check_logits = check
    try:
        yield
    finally:
        for m, n, was, _ in patches:
            setattr(m, n, was)
        serve_gdn.check_logits = plain_check


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", choices=VARIANTS, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark.harness import spec
    from benchmark.harness.common import say

    cell = spec.load_cell("qwen3next-longctx")
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

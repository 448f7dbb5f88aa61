"""The controls of `dots3-mixed-ctx`'s `correct`: the cell itself, its own
traffic, engine options, sample and limits, with a WRONG program behind
the engine and the reference left right. A wrong program should come out
not correct through `serve_mla.margin_verdict`; the right one correct. A
control the limits cannot tell from the right program is REPORTED as such
(`refused` false, exit code 1), not left out.

    python3 -m benchmark.harness.controls_mla_swa --variant no_gate
        --seed <n> [--seconds <s>] [--rehearse]

Variants:
  right           the program as it is (benchmark/run.py's run, untraced)
  no_gate         the head-wise output gate dropped (every g_h = 1), both
                  kinds of layer
  no_rescale      the low-rank latents not rescaled (s_q = s_kv = 1)
  window_512      the window layers attend 512 slots, not 513 (the window
                  counted without the query's own slot)
  window_8x       the window layers attend 4,104 slots. "Everything" needs
                  64 rows x 135 window blocks = 15 GB of window pool at the
                  cell's engine options and cannot run; 8 windows is what
                  fits beside the weights
  swa_theta_full  the window layers rotated with the full layers' base
                  (8e7 where theirs is 5e4)
  swa_scale_192   the window layers' softmax scale (128 + 64)^-0.5, the
                  full layers', where theirs is (192 + 64)^-0.5
  attend_all      the full layers attend every live token (`index_topk` =
                  `max_len`): the selection is gone
  drop_expert     the first held expert's output dropped (weight 0 where
                  the router chose it)
  fp8             every RMSNorm's output rounded to float8 e4m3:
                  activations in the nearest precision below the
                  configuration's bf16

Prints the run's lines, then one line {"variant", "refused", "logit_check"}
and exits 0 where the verdict is the expected one (a wrong variant refused
by the margins, the right one passed), 1 otherwise. The limits' readings
in the configuration's `correct.derivation` come from this file on the
chip.
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

VARIANTS = ("right", "no_gate", "no_rescale", "window_512", "window_8x",
            "swa_theta_full", "swa_scale_192", "attend_all", "drop_expert",
            "fp8")


def _config_edit(variant: str):
    """What a variant changes of the program's config, or None."""
    return {
        "window_512": lambda c: {"sliding_window": c.sliding_window - 1},
        "window_8x": lambda c: {"sliding_window": 8 * c.sliding_window},
        "swa_theta_full": lambda c: {"swa_rope_theta": c.rope_theta},
        "attend_all": lambda c: {"index_topk": c.max_seq_len},
    }.get(variant)


@contextlib.contextmanager
def wrong_program(driver, variant: str, seen: dict):
    """Put ``variant`` behind the engine of the cell's driver (``driver``,
    the module the cell loaded) for the block, and leave the verdict of
    its `check_logits` in ``seen``."""
    import jax.numpy as jnp

    from ray_tpu.models import mla, moe

    plain = {"norm": mla._rmsnorm, "gate": mla.head_gate,
             "rescale": mla.lora_rescale, "route": moe.route_sigmoid_grouped,
             "geometry": mla.MlaConfig.geometry,
             "config": driver.program_config, "check": driver.check_logits}

    def fp8_norm(x, w, eps):
        y = plain["norm"](x, w, eps)
        return y.astype(jnp.float8_e4m3fn).astype(y.dtype)

    def open_gate(a, w_gate, dt, scope=None):
        return jnp.ones((*a.shape[:2], w_gate.shape[-1]), dt)

    def route_without_first(logits, bias, cfg):
        weights, idx = plain["route"](logits, bias, cfg)
        lo = (cfg.held_experts or (0, 0))[0]
        return jnp.where(idx == lo, 0.0, weights), idx

    def full_scale_geometry(self, kind):
        g = plain["geometry"](self, kind)
        return g._replace(sm_scale=plain["geometry"](self, mla.MLA).sm_scale)

    def edited_config(model, max_len):
        cfg, init, ref = plain["config"](model, max_len)
        return dataclasses.replace(cfg, **_config_edit(variant)(cfg)), \
            init, ref

    def check(*args, **kw):
        seen["logit_check"] = plain["check"](*args, **kw)
        return seen["logit_check"]

    if variant == "fp8":
        mla._rmsnorm = fp8_norm
    if variant == "no_gate":
        mla.head_gate = open_gate
    if variant == "no_rescale":
        mla.lora_rescale = lambda dim, rank: 1.0
    if variant == "drop_expert":
        moe.route_sigmoid_grouped = route_without_first
    if variant == "swa_scale_192":
        mla.MlaConfig.geometry = full_scale_geometry
    if _config_edit(variant):
        driver.program_config = edited_config
    driver.check_logits = check
    try:
        yield
    finally:
        mla._rmsnorm, mla.head_gate = plain["norm"], plain["gate"]
        mla.lora_rescale = plain["rescale"]
        moe.route_sigmoid_grouped = plain["route"]
        mla.MlaConfig.geometry = plain["geometry"]
        driver.program_config = plain["config"]
        driver.check_logits = plain["check"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", choices=VARIANTS, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark.harness import spec
    from benchmark.harness.common import say

    cell = spec.load_cell("dots3-mixed-ctx")
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

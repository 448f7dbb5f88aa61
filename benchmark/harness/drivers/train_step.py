"""Driver `train_step`: `make_sharded_train_step` over a mesh of the
host's chips, fed a fresh batch every step by a host thread.

The window starts after a step whose loss has been fetched and ends in
the fetch of the last step's loss; steps are dispatched back to back, one
ahead of the fetch, so the device never waits for the host between them.
"""

from __future__ import annotations

import math
import queue
import threading
import numpy as np

from benchmark.harness import common, costs
from benchmark.harness.model import llama_config
from benchmark.harness.common import now

SPAN_NAMES = ["feed_batch", "train_step.dispatch", "loss.fetch"]


class Feeder(threading.Thread):
    """Makes batch `step` on the host, places it on the mesh and queues
    it, `depth` ahead of the consumer."""

    def __init__(self, make, sharding, depth: int):
        super().__init__(daemon=True)
        self.make, self.sharding = make, sharding
        self.q: "queue.Queue" = queue.Queue(maxsize=depth)
        self.stop_flag = threading.Event()

    def run(self):
        import jax

        step = 0
        while not self.stop_flag.is_set():
            batch = {"tokens": jax.device_put(self.make(step),
                                              self.sharding)}
            while not self.stop_flag.is_set():
                try:
                    self.q.put((step, batch), timeout=0.1)
                    break
                except queue.Full:
                    pass
            step += 1

    def close(self):
        self.stop_flag.set()
        self.join()


def run_cell(cell, seed: int, seconds: float, trace: bool, rehearse: bool,
             out_dir: str, say) -> dict:
    import jax
    import optax
    from jax.sharding import NamedSharding

    from benchmark.reference import llama_dense
    from ray_tpu.models import llama_init, llama_loss, llama_param_specs
    from ray_tpu.models.training import (batch_sharding_fn,
                                         make_sharded_train_step)
    from ray_tpu.parallel import create_mesh

    device = common.require_device(cell.chips, rehearse)
    watch = common.CompileWatch()
    model = dict(cell.config)
    topts = dict(cell.config["train"])
    tparams = dict(cell.traffic["traffic"])
    ccfg = dict(cell.config["correct"])
    trace_steps = int(cell.traffic.get("trace", {}).get("trace_steps", 3))
    if rehearse:
        model.update(cell.config["rehearsal"]["model"])
        topts.update(cell.config["rehearsal"]["train"])
        tparams.update(cell.traffic["rehearsal"]["traffic"])
        seconds = cell.traffic["rehearsal"]["seconds"]
        trace_steps = 2
    gen = cell.generator.generate(tparams, seed, seconds,
                                  model["vocab_size"])
    B, S = gen["batch"], gen["seq_len"]
    cfg = llama_config(
        model, S, activation_dtype=topts["activation_dtype"],
        param_dtype=topts["param_dtype"], remat=topts["remat"],
        remat_policy=topts["remat_policy"], attn_impl=topts["attn_impl"],
        loss_chunk=topts.get("loss_chunk"))
    mesh = create_mesh(dict(topts["mesh"]), jax.devices()[:cell.chips])
    specs = llama_param_specs(cfg)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    opt = optax.adamw(topts["optimizer"]["lr"],
                      weight_decay=topts["optimizer"]["weight_decay"])
    _, step_fn = make_sharded_train_step(
        lambda p, b: llama_loss(p, b, cfg), opt, mesh, specs)

    t = now()
    params = jax.jit(lambda k: llama_init(k, cfg),
                     out_shardings=shardings)(common.seed_key(seed))
    jax.block_until_ready(params)
    say(phase="weights", seconds=now() - t,
        param_devices=sorted({len(x.sharding.device_set)
                              for x in jax.tree.leaves(params)}))
    batch_sharding = batch_sharding_fn(mesh, ("batch", None))(
        np.zeros((B, S + 1), np.int32))

    # correctness, part one: the reference's float32 loss of batch 0
    # under the initial weights, before the optimizer state takes its
    # share of the memory
    t = now()
    batch0 = jax.device_put(gen["make"](0), batch_sharding)
    n = cell.chips

    def reference_loss(p, b):
        # one sequence a chip at a time: [B, S+1] split over the chips on
        # its first axis becomes B/n slices of [n, S+1]; the mean over
        # equal slices is the mean over the batch
        g = b.reshape(n, B // n, S + 1).swapaxes(0, 1)
        return jax.numpy.mean(jax.lax.map(
            lambda t: llama_dense.loss(p, t, model), g))

    ref_loss = float(jax.jit(reference_loss)(params, batch0))
    say(phase="reference_loss", seconds=now() - t, ref_loss=ref_loss)

    # The optimizer state is made here, sharded like the parameters it
    # mirrors. The program's own `init_fn` leaves its placement to GSPMD,
    # which replicates it (zeros depend on no sharded input): 15 GB a chip
    # for this model (PR 23, first four-chip run; PERF.md).
    by_shape = {(x.shape, x.dtype): x.sharding
                for x in jax.tree.leaves(params)}
    replicated = NamedSharding(mesh, jax.sharding.PartitionSpec())
    opt_shardings = jax.tree_util.tree_map(
        lambda a: by_shape.get((a.shape, a.dtype), replicated),
        jax.eval_shape(opt.init, params))
    opt_state = jax.jit(opt.init, out_shardings=opt_shardings)(params)
    feeder = Feeder(gen["make"], batch_sharding, int(tparams["queue_depth"]))
    feeder.start()
    spans = common.Spans()

    def next_batch():
        with spans.span("feed_batch"):
            return feeder.q.get()[1]

    def dispatch(params, opt_state):
        batch = next_batch()
        with spans.span("train_step.dispatch"):
            return step_fn(params, opt_state, batch)

    def fetch(metrics) -> float:
        with spans.span("loss.fetch"):
            return float(metrics["loss"])

    # warm-up: step 0 (compiles; its loss is the system's side of the
    # correctness check) and one more
    t = now()
    params, opt_state, m = dispatch(params, opt_state)
    loss0 = fetch(m)
    params, opt_state, m = dispatch(params, opt_state)
    fetch(m)
    say(phase="warm_up", seconds=now() - t, programs=watch.total,
        compile_s=watch.seconds, loss0=loss0)

    session = common.ProfilerSession(out_dir + "/trace") if trace else None
    losses = []
    watch.armed = True
    setup_s = common.seconds_since_process_start()
    w0 = now()
    params, opt_state, pending = dispatch(params, opt_state)
    n_dispatched = 1
    trace_at = None
    untraced = None      # (steps, seconds) of the window before the trace
    while True:
        if now() - w0 >= seconds:
            break
        if session is not None and session.t_begin is None \
                and now() - w0 >= seconds / 2:
            losses.append(fetch(pending))     # drain, so the trace holds
            untraced = (len(losses), now() - w0)     # whole steps only
            session.start()
            trace_at = len(losses)
            params, opt_state, pending = dispatch(params, opt_state)
            n_dispatched += 1
            continue
        params, opt_state, m = dispatch(params, opt_state)
        n_dispatched += 1
        losses.append(fetch(pending))
        pending = m
        if session is not None and session.active \
                and len(losses) - trace_at >= trace_steps:
            losses.append(fetch(pending))
            session.stop()
            say(trace_stop_s=now() - session.t_end)
            params, opt_state, pending = dispatch(params, opt_state)
            n_dispatched += 1
    losses.append(fetch(pending))
    w1 = now()
    if session is not None and session.active:
        session.stop()
    watch.armed = False
    feeder.close()
    assert len(losses) == n_dispatched

    tokens = len(losses) * B * S
    e2e = {"setup_s": setup_s, "train_tokens_per_s": tokens / (w1 - w0)}
    finite = all(math.isfinite(x) for x in losses)
    loss_diff = abs(loss0 - ref_loss)
    correct = bool(finite and loss_diff <= ccfg["loss_tol"]
                   and watch.in_window == 0)
    say(steps=len(losses), step_s=(w1 - w0) / len(losses),
        tokens_per_step=B * S, loss_first=losses[0], loss_last=losses[-1],
        loss0=loss0, ref_loss=ref_loss, loss_diff=loss_diff,
        compiles_in_window=watch.in_window, correct=correct)
    records = {
        "model": model, "device": device, "e2e": e2e, "spans": spans,
        "window": (w0, w1), "session": session, "span_names": SPAN_NAMES,
        "steps": len(losses),
        # starting and stopping the profiler stalls the loop for seconds,
        # so a traced run's utilisation is taken before the trace began
        "tokens_per_s_untraced": untraced[0] * B * S / untraced[1]
        if untraced else e2e["train_tokens_per_s"],
        "batch": B, "seq_len": S, "chips": cell.chips,
        "fwd_executions": 2 if topts["remat"] and topts[
            "remat_policy"] == "full" else 1,
        "train_flops_per_token": costs.train_flops_per_token(model, S),
    }
    return {"correct": correct, "attempted": len(losses),
            "failed": 0 if finite else sum(
                not math.isfinite(x) for x in losses),
            "e2e": e2e, "records": records, "device": device,
            "memory_peak_bytes": common.memory_peak_bytes()}

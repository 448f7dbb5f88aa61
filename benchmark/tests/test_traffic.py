"""Each generator: the same output for a seed, the stated medians and
clips, and the same set of sizes whatever the seed."""

import json
import os
import statistics

import numpy as np
import pytest

from benchmark.harness import spec


def _workload(name):
    with open(os.path.join(spec.BENCH_DIR, "workloads", name + ".json")) as f:
        w = json.load(f)
    return w, spec.load_module("traffic", w["generator"])


def _same(a, b):
    return len(a) == len(b) and all(
        x["max_new_tokens"] == y["max_new_tokens"]
        and x.get("due_s") == y.get("due_s")
        and np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_chat_is_open_poisson_lognormal(seed):
    w, gen = _workload("chat")
    p = w["traffic"]
    a = gen.generate(p, seed, 40, 32768)
    assert _same(a["requests"], gen.generate(p, seed, 40, 32768)["requests"])
    b = gen.generate(p, seed + 1, 40, 32768)["requests"]
    assert not _same(a["requests"], b)
    # the mix fixes the order (schedule_seed); the seed draws the tokens
    assert [(r["due_s"], len(r["prompt"]), r["max_new_tokens"])
            for r in a["requests"]] == [
        (r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in b]
    free = dict(p)
    del free["schedule_seed"]         # without it the seed picks the order
    assert [len(r["prompt"]) for r in
            gen.generate(free, seed, 40, 32768)["requests"]] != [
        len(r["prompt"]) for r in
        gen.generate(free, seed + 1, 40, 32768)["requests"]]
    counted = [r for r in a["requests"] if r["counted"]]
    assert len(counted) == round(p["rate_rps"] * 40)
    assert all(0 <= r["due_s"] < 40 for r in counted)
    ramp = [r for r in a["requests"] if r["due_s"] < 0]
    assert len(ramp) == round(p["rate_rps"] * p["ramp_s"])
    assert min(r["due_s"] for r in ramp) >= -p["ramp_s"]
    plens = [len(r["prompt"]) for r in counted]
    olens = [r["max_new_tokens"] for r in counted]
    assert p["prompt"]["min"] <= min(plens) and \
        max(plens) <= p["prompt"]["max"]
    assert p["output"]["min"] <= min(olens) and \
        max(olens) <= p["output"]["max"]
    assert statistics.median(plens) == pytest.approx(
        p["prompt"]["median"], rel=0.03)
    assert statistics.median(olens) == pytest.approx(
        p["output"]["median"], rel=0.03)
    # the heavy tail is there: some prompt hits the clip
    assert max(plens) == p["prompt"]["max"]
    # every seed gets the same multiset of sizes and of gaps
    other = [r for r in gen.generate(free, seed + 5, 40, 32768)["requests"]
             if r["counted"]]
    assert sorted(plens) == sorted(len(r["prompt"]) for r in other)
    assert sorted(olens) == sorted(r["max_new_tokens"] for r in other)
    ids = np.concatenate([r["prompt"] for r in counted])
    assert ids.min() >= 1 and ids.max() < 32768


def test_rollout_is_closed_lognormal():
    w, gen = _workload("rollout")
    p = w["traffic"]
    a = gen.generate(p, 3, 40, 32768)
    assert a["kind"] == "closed" and a["clients"] == 64
    assert _same(a["requests"], gen.generate(p, 3, 40, 32768)["requests"])
    assert len(a["requests"]) == p["block"] * p["blocks"]
    body = a["requests"][p["block"]:2 * p["block"]]      # an unstaggered block
    plens = [len(r["prompt"]) for r in body]
    olens = [r["max_new_tokens"] for r in body]
    assert statistics.median(plens) == pytest.approx(192, rel=0.03)
    assert statistics.median(olens) == pytest.approx(384, rel=0.03)
    assert 32 <= min(plens) and max(plens) <= 1024
    assert 64 <= min(olens) and max(olens) <= 1024
    # every block is the same set
    nxt = a["requests"][2 * p["block"]:3 * p["block"]]
    assert sorted(plens) == sorted(len(r["prompt"]) for r in nxt)
    # the first requests are cut short so the slots do not finish together
    first = a["requests"][:p["stagger_first"]]
    assert sum(r["max_new_tokens"] for r in first) < 0.7 * sum(olens[:64])
    assert min(r["max_new_tokens"] for r in first) >= 2


def test_token_batches_depend_on_seed_and_step_only():
    w, gen = _workload("pretrain-4k")
    a = gen.generate(w["traffic"], 5, 40, 92544)
    b = gen.generate(w["traffic"], 5, 40, 92544)
    assert a["batch"] == w["traffic"]["batch"] and a["seq_len"] == 4096
    x = a["make"](3)
    assert x.shape == (a["batch"], 4097) and x.dtype == np.int32
    assert np.array_equal(x, b["make"](3))
    assert not np.array_equal(x, a["make"](4))
    assert not np.array_equal(
        x, gen.generate(w["traffic"], 6, 40, 92544)["make"](3))
    assert 0 <= x.min() and x.max() < 92544

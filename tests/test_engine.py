"""Continuous-batching decode engine (ray_tpu/models/engine.py).

Gold contract: greedy engine output for every request is
token-identical to that request's solo `generate` run — regardless of
admission order, mid-flight joins, slot reuse, or length bucketing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, llama_init
from ray_tpu.models.engine import DecodeEngine
from ray_tpu.models.generate import generate


@pytest.fixture(scope="module")
def nano_model():
    cfg = LlamaConfig.nano()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _solo(params, cfg, prompt, n):
    out = np.asarray(generate(params, jnp.asarray([prompt], jnp.int32),
                              cfg, max_new_tokens=n))
    return out[0, len(prompt):].tolist()


@pytest.mark.parametrize("knobs", [
    {},                                             # implicit FIFO
    {"scheduler": "fifo"},
    {"scheduler": "priority"},                      # ragged priorities
    {"scheduler": "priority", "max_prefills_per_step": 1},
    {"scheduler": "fifo", "max_queue": 2, "on_full": "block"},
], ids=["default", "fifo", "priority", "priority+prefill_budget",
        "fifo+bounded_block"])
def test_engine_matches_solo_generate(nano_model, knobs):
    _engine_matches_solo_generate(nano_model, knobs)


@pytest.mark.parametrize("knobs", [
    {"scheduler": "priority", "max_prefills_per_step": 1},
], ids=["priority+prefill_budget"])
def test_engine_matches_solo_generate_olmoe(nano_olmoe, knobs):
    """The same contract for the sparse family (experts + q/k norm): the
    engine serves it through the programs the dense family uses."""
    _engine_matches_solo_generate(nano_olmoe, knobs)


def _engine_matches_solo_generate(nano_model, knobs):
    """More requests than slots, ragged lengths, ragged budgets: every
    request's tokens equal its solo run (slots are reused as earlier
    requests finish) — under EVERY scheduler policy and admission
    knob. Scheduling reorders admissions, never what a row computes."""
    cfg, params = nano_model
    prompts = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2], [3, 1, 4, 1, 5, 9],
               [11, 13]]
    budgets = [4, 6, 3, 5, 2]
    priorities = [5, 0, 9, 0, 3]    # only the priority policy reads these

    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32, **knobs)
    ids = [eng.submit(p, n, priority=pr)
           for p, n, pr in zip(prompts, budgets, priorities)]
    out = eng.run()

    assert not eng.pending()
    for rid, p, n in zip(ids, prompts, budgets):
        assert out[rid] == _solo(params, cfg, p, n), f"req {rid}"


def test_engine_midflight_admission_and_streaming(nano_model):
    """Requests joining a RUNNING batch must not perturb in-flight
    rows; step() streams per-request tokens whose concatenation is the
    final result."""
    cfg, params = nano_model
    eng = DecodeEngine(params, cfg, batch_slots=3, max_len=32)
    a = eng.submit([5, 6, 7], 6)
    streamed = {a: []}

    def collect(ev):
        for rid, toks in ev.items():
            streamed.setdefault(rid, []).extend(toks)

    collect(eng.step())
    collect(eng.step())
    b = eng.submit([9, 8, 7, 6], 5)     # joins mid-flight
    collect(eng.step())
    c = eng.submit([2, 4], 4)           # joins later still
    while eng.pending():
        collect(eng.step())

    assert streamed[a] == _solo(params, cfg, [5, 6, 7], 6)
    assert streamed[b] == _solo(params, cfg, [9, 8, 7, 6], 5)
    assert streamed[c] == _solo(params, cfg, [2, 4], 4)
    assert eng.results[a].tokens == streamed[a]


def test_engine_eos_frees_slot_for_reuse(nano_model):
    """A row finishing on eos releases its slot; the next queued
    request occupies it and still decodes exactly."""
    cfg, params = nano_model
    p0, p1 = [5, 6, 7], [9, 8, 7, 6]
    solo0 = _solo(params, cfg, p0, 8)
    eos = solo0[2]                       # force p0 to finish early

    eng = DecodeEngine(params, cfg, batch_slots=1, max_len=32,
                       eos_id=eos)
    r0 = eng.submit(p0, 8)
    r1 = eng.submit(p1, 3)               # waits for the only slot
    out = eng.run()

    # truncated at the FIRST eos (inclusive) — on some boxes the nano
    # model's greedy run repeats the chosen token before index 2
    assert out[r0] == solo0[:solo0.index(eos) + 1]
    assert r0 not in eng.results         # run() pops finished requests
    solo1 = _solo(params, cfg, p1, 3)
    want = solo1[:solo1.index(eos) + 1] if eos in solo1 else solo1
    assert out[r1] == want


def test_engine_bucketing_is_exact(nano_model):
    """Length-bucketed prefill (power-of-two padding) must not change
    any token vs unbucketed admission."""
    cfg, params = nano_model
    prompts = [[5, 6, 7], [9, 8, 7, 6, 5, 4, 3], [1, 2]]

    outs = []
    for bucket in (False, True):
        eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                           bucket_lens=bucket)
        ids = [eng.submit(p, 4) for p in prompts]
        res = eng.run()
        outs.append([res[i] for i in ids])
    assert outs[0] == outs[1]


def test_engine_sampling_and_guards(nano_model):
    cfg, params = nano_model

    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=32,
                       greedy=False, temperature=0.9, top_k=8,
                       top_p=0.95, rng=jax.random.PRNGKey(7))
    rid = eng.submit([5, 6, 7], 5)
    out = eng.run()
    assert len(out[rid]) == 5
    assert all(0 <= t < cfg.vocab_size for t in out[rid])

    with pytest.raises(ValueError, match="greedy=False"):
        DecodeEngine(params, cfg, top_k=4)
    with pytest.raises(ValueError, match="BOS"):
        eng.submit([], 4)
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit([1, 2, 3], 64)
    with pytest.raises(ValueError, match="max_len"):
        DecodeEngine(params, cfg, max_len=cfg.max_seq_len + 1)

    # run() popped the finished request; popping twice is an error and
    # an in-flight request cannot be popped
    with pytest.raises(KeyError):
        eng.pop_result(rid)
    rid2 = eng.submit([5, 6], 3)
    eng.step(horizon=1)                  # pinned: adaptive H would
    with pytest.raises(KeyError):        # finish all 3 tokens at once
        eng.pop_result(rid2)             # still decoding
    eng.run()
    assert rid2 not in eng.results

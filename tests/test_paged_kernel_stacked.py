"""A step of the paged kernel takes a decode row's KV heads as ONE
operand (PR 51): the stacked body at the serving cells' head layouts, in
interpret mode, against the pure-lax reference, a walk of all table
entries and each row alone; and a mutation of its final cut.

These live beside `tests/test_engine_kv_quant.py`'s kernel sweeps (whose
case builders they use) in a file of their own, because that file is
the longest of the tier-1 run and a file is one worker's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention_kernel as pak
from ray_tpu.ops.attention import paged_attention
from test_engine_kv_quant import (_LAST_SLOT, _PMB, _PPS, _RT,
                                  _pipeline_case)

# KV heads x query heads a group (x head size) of the four serving cells'
# decode calls, scaled down: Mistral's, phi4flash's pairs, OLMoE's,
# qwen3next's, and that one at a head of two lane tiles
_HEAD_LAYOUTS = {"8x4": (8, 4, 16), "10x4": (10, 4, 16), "16x1": (16, 1, 16),
                 "2x8": (2, 8, 16), "2x8_two_lane_tiles": (2, 8, 256)}
# rows by the pages they walk: one, none (a filler row), the whole table,
# a step and a page, exactly a step; and the filler rows that make the
# call's eight (`paged_attention_kernel` pads the same way)
_KINDS = (1, 0, _PMB, _PPS + 1, _PPS, 0, 0, 0)
_REAL = 5


def _walk(case, *, n_live, stacked, window=None, jitted=True):
    """`_walk` on layer 1 of `_pipeline_case`'s pools; ``jitted=False``
    traces the kernel anew, past `_walk`'s cache."""
    q, kf, vf, bt, q_slots, sk, sv = case
    walk = pak._walk if jitted else pak._walk.__wrapped__
    return walk(q, bt, q_slots, n_live=n_live, k_pool=kf, v_pool=vf,
                k_scale=sk, v_scale=sv, layer=np.int32(1),
                kv_valid_len=np.int32(_PMB * _RT),
                sm_scale=q.shape[3] ** -0.5, interpret=True, pps=_PPS,
                stacked=stacked, window=window)


def _row_alone(case, b):
    """Row ``b`` in a call of its own: the other seven are filler rows
    (slot -1, a table of the null block), which walk nothing."""
    q, kf, vf, bt, q_slots, sk, sv = case
    only = jnp.arange(q.shape[0]) == b
    return (jnp.where(only[:, None, None, None], q, 0), kf, vf,
            jnp.where(only[:, None], bt, 0),
            jnp.where(only[:, None], q_slots, -1), sk, sv)


@pytest.mark.parametrize("window", [None, 10], ids=["full", "window"])
@pytest.mark.parametrize("slots", [1, 4], ids=["s1", "s4"])
@pytest.mark.parametrize("quant", ["bf16", "int8", "fp8_e4m3"],
                         ids=["bf16", "int8", "fp8"])
@pytest.mark.parametrize("layout", list(_HEAD_LAYOUTS))
def test_kernel_stacked_heads_at_the_cells_layouts(monkeypatch, layout,
                                                   quant, slots, window):
    """The four cells' head layouts, five rows that walk 0 to all MB
    pages in steps of 2 (and three filler rows): `walk_shape` stacks the
    heads while all the row's query rows are within 128 (10 x 4 heads x
    4 slots are 160 and loop), and either form gives the reference's
    values, the bits of a walk over all MB entries, and for the batch,
    BIT FOR BIT, what each row gives in a call of its own."""
    monkeypatch.setattr(pak, "_KEYS_PER_STEP", _PPS * _RT)
    KV, gm, D = _HEAD_LAYOUTS[layout]
    rng = np.random.RandomState(59 + slots + KV)
    case = _pipeline_case([_LAST_SLOT[k] for k in _KINDS], slots, quant,
                          rng, KV=KV, gm=gm, D=D)
    q, kf, vf, bt, q_slots, sk, sv = case
    pps, tq, stacked = pak.walk_shape(slots, KV * gm, KV, D, _RT, _PMB,
                                      kf.dtype.itemsize)
    assert (pps, tq) == (_PPS, slots)
    assert stacked == (KV * gm * slots <= 128)
    n_live = pak.live_pages(q_slots, _PMB * _RT, _RT, _PMB)
    assert n_live.tolist() == list(_KINDS)
    got = _walk(case, n_live=n_live, stacked=stacked, window=window)
    for b in range(_REAL):
        alone = _walk(_row_alone(case, b), n_live=n_live * (
            jnp.arange(len(_KINDS)) == b), stacked=stacked, window=window)
        assert jnp.array_equal(alone[b], got[b])
        assert not np.asarray(alone, np.float32)[b + 1:].any()
    ref = paged_attention(q, kf, vf, bt, q_slots, impl="reference", layer=1,
                          kv_valid_len=_PMB * _RT, k_scale=sk, v_scale=sv,
                          window=window)
    tol = 2e-2 if quant == "bf16" else 2e-5
    asked = np.asarray(q_slots) >= 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[asked],
        np.asarray(ref, np.float32)[asked], atol=tol, rtol=tol)
    assert not np.asarray(got, np.float32)[~asked].any()
    assert jnp.array_equal(got, _walk(
        case, n_live=jnp.full((len(_KINDS),), _PMB, jnp.int32),
        stacked=stacked, window=window))
    # and the public entry point takes the same form to the same bits
    if (quant, window) == ("bf16", None):
        assert jnp.array_equal(got, paged_attention(
            q, kf, vf, bt, q_slots, impl="flash", layer=1,
            kv_valid_len=_PMB * _RT))


def _neighbours_lanes(out, kv, rows, d):
    """The mutation: head ``kv``'s rows, head ``kv + 1``'s lanes."""
    return jax.lax.slice(out, (kv * rows, (kv + 1) * d),
                         ((kv + 1) * rows, (kv + 2) * d))


@pytest.mark.parametrize("quant", ["bf16", "int8"], ids=["bf16", "int8"])
def test_kernel_stacked_heads_cut_their_own_lanes(monkeypatch, quant):
    """The stacked result holds p x V of EVERY head's lanes in every
    row; a head's answer is its own diagonal block. A body that cuts the
    neighbouring head's lanes instead is finite, plausible and wrong:
    the comparison against the reference has to see it (and the stacked
    and looping forms agree where it is right)."""
    monkeypatch.setattr(pak, "_KEYS_PER_STEP", _PPS * _RT)
    KV, gm, D = _HEAD_LAYOUTS["8x4"]
    rng = np.random.RandomState(61)
    case = _pipeline_case([_LAST_SLOT[k] for k in _KINDS], 1, quant, rng,
                          KV=KV, gm=gm, D=D)
    q, kf, vf, bt, q_slots, sk, sv = case
    ref = np.asarray(paged_attention(
        q, kf, vf, bt, q_slots, impl="reference", layer=1,
        kv_valid_len=_PMB * _RT, k_scale=sk, v_scale=sv), np.float32)
    asked = (np.asarray(q_slots) >= 0)[:, 0]
    tol = 2e-2 if quant == "bf16" else 2e-5
    n_live = jnp.full((len(_KINDS),), _PMB, jnp.int32)

    def walk(stacked):
        return np.asarray(_walk(case, n_live=n_live, stacked=stacked,
                                jitted=False), np.float32)

    right = walk(True)
    np.testing.assert_allclose(right[asked], ref[asked], atol=tol, rtol=tol)
    np.testing.assert_allclose(walk(False), right, atol=tol, rtol=tol)
    real = pak._own_lanes
    monkeypatch.setattr(
        pak, "_own_lanes",
        lambda out, kv, rows, d: real(out, kv, rows, d) if kv % 2
        else _neighbours_lanes(out, kv, rows, d))
    wrong = walk(True)
    assert np.isfinite(wrong).all()
    # the odd heads are still right, every even head is its neighbour's
    got = wrong.reshape(wrong.shape[0], KV, gm, D)
    want = ref.reshape(got.shape)
    np.testing.assert_allclose(got[asked][:, 1::2], want[asked][:, 1::2],
                               atol=tol, rtol=tol)
    assert np.abs(got[asked][:, 0::2] - want[asked][:, 0::2]).max() > 0.1

"""`ops.held_grouped_ffn` (the sorted form's expert matmuls over a held
range, one kernel whose grid visits the groups that hold rows) against
plain `lax`, in interpret mode: what Mosaic refuses is in
`tests/test_tpu_compile.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.held_grouped_ffn import (
    ROW_TILE, held_grouped_ffn, held_grouped_ffn_reference,
    held_grouped_tiles, visit_schedule)


def _stacks(d, f, eh, layers, layer, dtype, seed=0):
    """Expert stacks of ``layers`` layers; every layer but ``layer`` is NaN:
    a kernel that reads another layer's group shows it."""
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    n = layers * eh
    mine = (jnp.arange(n) // eh == layer)[:, None, None]
    shapes = ((n, d, f), (n, d, f), (n, f, d))
    return [jnp.where(mine, jax.random.normal(ki, s, jnp.float32)
                      * s[1] ** -0.5, jnp.nan).astype(dtype)
            for ki, s in zip(k, shapes)]


# c rows, d, f, held groups, layers in the stack, this layer, the groups'
# sizes, the first group's first row, tf (None: `held_grouped_tiles`')
CASES = {
    "empty_groups_between": (256, 128, 128, 8, 1, 0,
                             [0, 40, 0, 0, 100, 30, 0, 7], 0, None),
    "group_across_a_tile_boundary": (384, 128, 128, 4, 1, 0,
                                     [100, 60, 150, 20], 0, None),
    "all_on_one_expert": (512, 128, 128, 8, 1, 0,
                          [0, 0, 0, 512, 0, 0, 0, 0], 0, None),
    "nothing_landed": (256, 128, 128, 8, 2, 1, [0] * 8, 0, None),
    "first_in_a_stack_of_layers": (256, 128, 128, 4, 3, 2,
                                   [30, 0, 90, 50], 0, None),
    "rows_not_a_multiple_of_the_tile": (300, 128, 128, 4, 2, 1,
                                        [100, 50, 0, 150], 0, None),
    # a later window of a layer's assignments: the groups before it are
    # empty at row 0, the first group that has rows starts mid-window
    "a_window_that_starts_inside_a_group": (256, 128, 128, 6, 2, 0,
                                            [0, 0, 70, 10, 120, 0], 0, None),
    "groups_that_start_past_row_0": (384, 128, 128, 4, 1, 0,
                                     [20, 130, 0, 40], 150, None),
    "several_f_tiles": (256, 128, 256, 4, 2, 1, [60, 0, 100, 90], 0, 128),
    # 2048 x 512 and 7168 x 2048, a sixteenth
    "qwen3_next_width_ratio": (640, 128, 32, 32, 3, 1,
                               [20] * 32, 0, None),
    "deepseek_width_ratio": (256, 448, 128, 4, 2, 1,
                             [64, 70, 58, 64], 0, None),
    "one_row_groups": (128, 128, 128, 16, 1, 0, [1] * 16, 0, None),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_plain_lax(case, dtype):
    c, d, f, eh, layers, layer, sizes, start0, tf = CASES[case]
    w1, w3, w2 = _stacks(d, f, eh, layers, layer, dtype)
    xs = jax.random.normal(jax.random.PRNGKey(9), (c, d), dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    starts = start0 + jnp.cumsum(sizes) - sizes
    first = jnp.int32(layer * eh)
    sched = visit_schedule(starts, sizes, c)
    got = held_grouped_ffn(xs, sched, first, w1, w3, w2, tf=tf)
    want = held_grouped_ffn_reference(xs, starts, sizes, first, w1, w3, w2)
    assert got.shape == (c, d) and got.dtype == dtype
    row = np.arange(c)
    inside = (row >= start0) & (row < start0 + int(sizes.sum()))
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got[inside]).all()   # no other layer's NaN came in
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got[inside], want[inside], atol=tol, rtol=tol)
    # a visited tile's rows of no group are zero; an unvisited tile's are
    # nobody's
    visited = np.zeros(c, bool)
    for t in np.asarray(sched.tile)[:int(sched.n)]:
        visited[t * ROW_TILE:(t + 1) * ROW_TILE] = True
    assert not got[visited & ~inside].any()


@pytest.mark.parametrize("sizes,start0,c,visits", [
    ([40] * 8, 0, 512, 8 + 2),            # 320 rows: tiles 0..2, 2 straddles
    ([0] * 8, 0, 512, 0),
    ([512] + [0] * 7, 0, 512, 4),
    ([0, 0, 1, 0], 127, 256, 1),
    ([0, 0, 2, 0], 127, 256, 2),
    ([128, 128, 128, 128], 0, 512, 4),    # aligned: a visit a group
], ids=["small_groups", "empty", "one_group", "one_row", "two_rows_two_tiles",
        "aligned"])
def test_the_schedule_visits_only_pairs_that_hold_rows(sizes, start0, c,
                                                       visits):
    sizes = jnp.asarray(sizes, jnp.int32)
    starts = start0 + jnp.cumsum(sizes) - sizes
    s = visit_schedule(starts, sizes, c)
    n = int(s.n)
    assert n == visits and s.group.shape == (c // ROW_TILE + len(sizes),)
    grp, tile = np.asarray(s.group), np.asarray(s.tile)
    lo, hi = np.asarray(s.lo), np.asarray(s.hi)
    seen = set()
    for g, t in zip(grp[:n], tile[:n]):
        # the pair has rows, and is visited once
        assert max(lo[g], t * ROW_TILE) < min(hi[g], (t + 1) * ROW_TILE)
        assert (g, t) not in seen
        seen.add((g, t))
    # by group then tile, and every row of every group is covered
    assert list(zip(grp[:n], tile[:n])) == sorted(seen)
    covered = sum(min(hi[g], (t + 1) * ROW_TILE) - max(lo[g], t * ROW_TILE)
                  for g, t in seen)
    assert covered == int(sizes.sum())
    # the dead entries repeat the last live one: their blocks are there
    if n:
        assert (grp[n:] == grp[n - 1]).all() and (tile[n:] == tile[n - 1]).all()


@pytest.mark.parametrize("d,f,dtype,fits", [
    (2048, 512, jnp.bfloat16, True),      # Qwen3-Next-80B-A3B's experts
    (2048, 1024, jnp.bfloat16, True),     # OLMoE's
    (7168, 2048, jnp.bfloat16, False),    # DeepSeek-V3.2's: whole rows of d
    (64, 32, jnp.float32, True),          # a test's
], ids=["qwen3_next", "olmoe", "deepseek_v32", "tiny"])
def test_tiles_follow_the_widths(d, f, dtype, fits):
    tiles = held_grouped_tiles(d, f, dtype)
    assert (tiles is not None) == fits
    if fits:
        tm, tf = tiles
        assert tm == ROW_TILE and f % tf == 0 and (tf == f or tf % 128 == 0)

"""``ops.grouped_matmul``: forward and both gradients against a loop over
the groups, with empty groups and rows past ``sum(group_sizes)``, on both
implementations (the Pallas kernels in interpret mode)."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops import grouped_matmul_census
from chainermn_tpu.ops.grouped_matmul import IMPLS, grouped_matmul

ROWS, K, N = 256, 128, 256
# name -> group sizes: uneven, empty groups first / between / last, rows
# past the sum, a boundary off every tile edge, and no row at all
SIZES = {
    "uneven": [100, 60, 96],
    "empty_groups": [0, 130, 0, 70, 0],
    "rows_past_the_sum": [40, 0, 50],
    "off_the_tile_edges": [1, 127, 3, 60],
    "one_group_takes_all": [0, 256, 0],
    "nothing_routed": [0, 0, 0],
}


def _operands(groups, dtype=jnp.float32):
    key = jax.random.key(7)
    lhs = jax.random.normal(key, (ROWS, K), dtype)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (groups, K, N), dtype)
    cot = jax.random.normal(jax.random.fold_in(key, 2), (ROWS, N), dtype)
    return lhs, rhs, cot


def _loop(lhs, rhs, cot, sizes):
    """The grouped product, ``dlhs`` and ``drhs`` one group at a time."""
    lhs, rhs, cot = (np.asarray(a, np.float64) for a in (lhs, rhs, cot))
    out, dlhs, drhs = np.zeros_like(cot), np.zeros_like(lhs), np.zeros_like(rhs)
    start = 0
    for group, size in enumerate(sizes):
        rows = slice(start, start + size)
        out[rows] = lhs[rows] @ rhs[group]
        dlhs[rows] = cot[rows] @ rhs[group].T
        drhs[group] = lhs[rows].T @ cot[rows]
        start += size
    return out, dlhs, drhs


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(SIZES))
def test_forward_and_both_gradients_match_a_loop_over_the_groups(case, impl):
    sizes = SIZES[case]
    lhs, rhs, cot = _operands(len(sizes))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    out, vjp = jax.vjp(
        lambda a, b: grouped_matmul(a, b, group_sizes, impl), lhs, rhs)
    dlhs, drhs = vjp(cot)
    want = _loop(lhs, rhs, cot, sizes)
    for got, expected in zip((out, dlhs, drhs), want):
        np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-4,
                                   atol=1e-3)
    # rows that belong to no group: zero out, zero gradient
    past = sum(sizes)
    assert not np.asarray(out)[past:].any()
    assert not np.asarray(dlhs)[past:].any()


def _both_impls(lhs, rhs, cot, sizes):
    group_sizes = jnp.asarray(sizes, jnp.int32)

    def run(impl):
        out, vjp = jax.vjp(
            lambda a, b: grouped_matmul(a, b, group_sizes, impl), lhs, rhs)
        return (out,) + vjp(cot)

    return run("pallas"), run("ragged_dot")


# Mellum's expert shapes: hidden 2304 x expert width 896 (gate, up) and back
# (down).  ``_fit_tile`` gives 2304 tiles of 768 (three steps where it is K,
# three column blocks where it is N) and 896 one tile of its own: shapes no
# other model's experts have run
@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304)])
def test_mellums_expert_shapes_match_xlas_ragged_dot(k, n):
    module = sys.modules["chainermn_tpu.ops.grouped_matmul"]
    assert module._fit_tile(2304, module._TILE_K) == 768
    assert module._fit_tile(2304, module._TILE_N) == 768
    assert module._fit_tile(896, module._TILE_K) == 896
    assert module._fit_tile(896, module._TILE_N) == 896
    sizes = [70, 0, 130, 40]                    # 16 rows past the sum
    key = jax.random.key(11)
    lhs = jax.random.normal(key, (ROWS, k), jnp.float32)
    rhs = 0.05 * jax.random.normal(jax.random.fold_in(key, 1),
                                   (len(sizes), k, n), jnp.float32)
    cot = jax.random.normal(jax.random.fold_in(key, 2), (ROWS, n))
    for got, want in zip(*_both_impls(lhs, rhs, cot, sizes)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-3)


# Kimi-VL-A3B's (DeepSeek-V2-Lite's, Moonlight's) expert shapes: hidden 2048 x
# expert width 1408 = 11 x 128, which no multiple of 128 from 512 to 1024
# divides.  It is one tile, as K and as N, in all three kernels
@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_kimis_expert_shapes_match_xlas_ragged_dot(k, n):
    census = grouped_matmul_census([70, 0, 130, 40], ROWS, k, n)
    for kernel in census["tiles"].values():
        assert sorted((kernel["tile_k"], kernel["tile_n"])) == [1024, 1408]
    key = jax.random.key(13)
    lhs = jax.random.normal(key, (ROWS, k), jnp.float32)
    rhs = 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (4, k, n))
    cot = jax.random.normal(jax.random.fold_in(key, 2), (ROWS, n))
    got, want = _both_impls(lhs, rhs, cot, [70, 0, 130, 40])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-3)


@pytest.mark.parametrize("size,tile", [
    (896, 896), (1024, 1024), (1792, 896), (2048, 1024), (2304, 768),
    (1408, 1408),           # no divisor from 512 up: whole, not 11 x 128
    (2944, 2944), (640, 640), (3072, 1024), (2560, 640)])
def test_a_k_or_n_tile_is_never_a_sliver(size, tile):
    module = sys.modules["chainermn_tpu.ops.grouped_matmul"]
    assert module._fit_tile(size, module._TILE_K) == tile
    assert module._fit_tile(size, module._TILE_N) == tile
    assert tile == size or (size % tile == 0 and tile >= 512)


@pytest.mark.parametrize("cell,rows,groups,sub", [
    ("kimi-vl-a3b: ~770 rows a group", 9216, 8, 128),
    ("trinity-mini: ~510", 12288, 16, 128),
    ("mellum2: ~1,020", 24576, 16, 256),
    ("lfm2-8b-a1b: ~3,070", 36864, 8, 256),
    ("one row tile", 512, 1, 128),
    ("fewer rows than a sub-tile of 256", 128, 1, 128)])
def test_the_sub_tile_follows_the_rows_a_group_is_expected_to_hold(
        cell, rows, groups, sub):
    census = grouped_matmul_census([rows // (2 * groups)] * groups, rows,
                                   2048, 1024)
    assert census["sub_rows"] == sub


# three row tiles of 512; name -> group sizes.  The first group's edge lies
# ``edge`` rows into the second tile: on a sub-tile's first row, its last,
# one either side, and at the tile's own ends
TILE = 512
EDGES = {f"edge_{edge}_rows_into_a_tile": [TILE + edge, 400]
         for edge in (1, 127, 128, 129, 255, 256, 511)}
EDGES.update({
    "a_group_inside_one_sub_tile": [130, 60, 500],
    "an_empty_group_between_two_full_ones": [TILE, 0, TILE],
    "three_groups_in_one_sub_tile": [600, 5, 7, 20, 300],
    "every_tile_full": [TILE, TILE, TILE],
})


@pytest.mark.parametrize("sub", [128, 256, 512])
@pytest.mark.parametrize("case", sorted(EDGES))
def test_a_step_multiplies_the_sub_tiles_its_group_reaches(
        case, sub, monkeypatch):
    """Forward and both gradients where a group's edge cuts a row tile, at
    each sub-tile size the plan may choose; the rows past the groups hold
    NaN going in and coming back, and reach nothing."""
    module = sys.modules["chainermn_tpu.ops.grouped_matmul"]
    monkeypatch.setattr(module, "_sub_rows", lambda rows, groups: sub)
    sizes = EDGES[case]
    rows, k, n, past = 3 * TILE, 128, 256, sum(sizes)
    key = jax.random.key(17)
    lhs = jax.random.normal(key, (rows, k), jnp.float32)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (len(sizes), k, n))
    cot = jax.random.normal(jax.random.fold_in(key, 2), (rows, n))
    want = _loop(lhs, rhs, cot, sizes)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    out, vjp = jax.vjp(
        lambda a, b: grouped_matmul(a, b, group_sizes, "pallas"),
        lhs.at[past:].set(jnp.nan), rhs)
    for got, expected in zip((out,) + vjp(cot.at[past:].set(jnp.nan)), want):
        np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-4,
                                   atol=1e-3)


def _brute_force_census(sizes, rows, tile_rows, sub):
    """(visits, row slots): the (row tile, group) pairs that share a row,
    and the rows of the sub-tiles each pair shares a row with."""
    group_of = np.full(rows, -1)
    held = np.repeat(np.arange(len(sizes)), sizes)[:rows]
    group_of[:len(held)] = held
    visits = slots = 0
    for tile in range(rows // tile_rows):
        in_tile = group_of[tile * tile_rows:(tile + 1) * tile_rows]
        for group in set(in_tile[in_tile >= 0].tolist()):
            visits += 1
            slots += sub * sum(
                (in_tile[first:first + sub] == group).any()
                for first in range(0, tile_rows, sub))
    return visits, slots


@pytest.mark.parametrize("sub", [128, 256, 512])
@pytest.mark.parametrize("case", sorted(EDGES) + ["drawn", "past_the_rows"])
def test_the_census_counts_what_a_brute_force_count_does(
        case, sub, monkeypatch):
    module = sys.modules["chainermn_tpu.ops.grouped_matmul"]
    monkeypatch.setattr(module, "_sub_rows", lambda rows, groups: sub)
    if case == "drawn":     # Kimi-VL's main pass: eight groups of ~770
        rows = 9216
        sizes = np.random.default_rng(3).integers(600, 940, 8).tolist()
    elif case == "past_the_rows":   # a routing that passes the layer's bound:
        rows, sizes = 3 * TILE, [700, 0, 800, 300, 0, 90]   # 36 of the 300
    else:
        rows, sizes = 3 * TILE, EDGES[case]
    census = grouped_matmul_census(np.asarray(sizes, np.float32), rows, 2048,
                                   1408)
    visits, slots = _brute_force_census(sizes, rows, TILE, sub)
    own = min(sum(sizes), rows)
    empty = sizes.count(0) + (case == "past_the_rows")  # the 90 are past too
    assert (census["tile_rows"], census["sub_rows"]) == (TILE, sub)
    assert (census["visits"], census["row_slots"]) == (visits, slots)
    assert census["own_rows"] == own
    assert census["row_fill"] == pytest.approx(own / slots)
    # K = 2048 in two tiles beside N = 1408 whole, and the other way round
    assert census["grid_steps"] == {
        "forward": 2 * visits, "dlhs": 2 * visits,
        "drhs": 2 * (visits + empty)}
    if sub == 512:
        assert census["row_slots"] == TILE * visits


@pytest.mark.parametrize("k,n", [(1408, 256), (256, 1408), (1408, 1408)])
def test_a_dimension_too_long_to_hold_whole_runs_in_ragged_tiles(
        k, n, monkeypatch):
    """1408 where the blocks of a whole dimension pass the VMEM budget (here
    the budget is shrunk, on the chip that takes a dimension of 4,736 or
    more): two tiles of 768, the second ragged.  What pads it counts nothing
    in ``_gmm``'s contraction, and nothing of it is stored."""
    module = sys.modules["chainermn_tpu.ops.grouped_matmul"]
    monkeypatch.setattr(module, "_VMEM_BUDGET", 2 ** 20)
    census = grouped_matmul_census([70, 0, 130, 40], ROWS, k, n)
    assert census["tiles"]["forward"]["tile_k"] == min(k, 768)
    assert census["tiles"]["forward"]["tile_n"] == min(n, 768)
    assert census["tiles"]["drhs"]["tile_k"] == min(k, 768)
    key = jax.random.key(19)
    lhs = jax.random.normal(key, (ROWS, k), jnp.float32)
    rhs = 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (4, k, n))
    cot = jax.random.normal(jax.random.fold_in(key, 2), (ROWS, n))
    got, want = _both_impls(lhs, rhs, cot, [70, 0, 130, 40])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-3)


def test_what_the_rows_past_the_groups_hold_reaches_nothing(
        impl="ragged_dot"):
    """XLA's TPU ``ragged_dot`` leaves the rows past ``sum(group_sizes)``
    unwritten (infinities on the chip: chained through the expert layer's
    remainder they put NaNs into a model's loss, PERF.md, PR 32), so that
    path selects zeros there itself.  With infinities and NaNs in those
    rows, going in and coming back, the result and both gradients are
    finite, zero past the groups, and what the clean operands give.  (The
    Pallas path writes its own zeros and is fed finite rows; its ``drhs``
    masks one operand only.)"""
    sizes = SIZES["rows_past_the_sum"]
    past = sum(sizes)
    lhs, rhs, cot = _operands(len(sizes))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    dirty = lambda a: a.at[past::2].set(jnp.inf).at[past + 1::2].set(jnp.nan)
    product = lambda a, b: grouped_matmul(a, b, group_sizes, impl)
    out, vjp = jax.vjp(product, dirty(lhs), rhs)
    clean_out, clean_vjp = jax.vjp(product, lhs, rhs)
    for got, want in zip((out,) + vjp(dirty(cot)),
                         (clean_out,) + clean_vjp(cot)):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert not np.asarray(out)[past:].any()
    assert not np.asarray(vjp(dirty(cot))[0])[past:].any()


def test_bfloat16_operands_accumulate_in_float32():
    sizes = SIZES["uneven"]
    lhs, rhs, cot = _operands(len(sizes), jnp.bfloat16)
    got = grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32), "pallas")
    assert got.dtype == jnp.bfloat16
    want, _, _ = _loop(lhs, rhs, cot, sizes)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=2e-2,
                               atol=0.2)


def test_the_two_implementations_agree_under_jit():
    sizes = jnp.asarray(SIZES["empty_groups"], jnp.int32)
    lhs, rhs, _ = _operands(5)
    loss = lambda impl: jax.jit(jax.grad(
        lambda a, b: jnp.sum(jnp.tanh(grouped_matmul(a, b, sizes, impl))),
        argnums=(0, 1)))(lhs, rhs)
    for got, want in zip(loss("pallas"), loss("ragged_dot")):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bad", [
    dict(impl="dense"),
    dict(rows=250),                       # not a multiple of 128 (pallas)
    dict(group_sizes=jnp.zeros((4,), jnp.int32)),
    dict(rhs_dtype=jnp.bfloat16),
])
def test_what_it_cannot_compute_is_refused(bad):
    lhs, rhs, _ = _operands(3)
    lhs = lhs[:bad.get("rows", ROWS)]
    rhs = rhs.astype(bad.get("rhs_dtype", rhs.dtype))
    sizes = bad.get("group_sizes", jnp.asarray([10, 20, 30], jnp.int32))
    with pytest.raises(ValueError):
        grouped_matmul(lhs, rhs, sizes, bad.get("impl", "pallas"))

"""``ops.grouped_matmul``: forward and both gradients against a loop over
the groups, with empty groups and rows past ``sum(group_sizes)``, on both
implementations (the Pallas kernels in interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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

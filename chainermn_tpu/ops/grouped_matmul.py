"""Grouped matrix product — the expert layer's matmul.

``grouped_matmul(lhs[rows, K], rhs[G, K, N], group_sizes[G])`` multiplies
the first ``group_sizes[0]`` rows of ``lhs`` by ``rhs[0]``, the next
``group_sizes[1]`` rows by ``rhs[1]``, and so on; rows past
``sum(group_sizes)`` belong to no group and come out as zeros.  That is
what a dropless mixture-of-experts layer needs: the (token, choice) pairs
ordered by expert are the rows, the held experts' weights are the groups,
and however uneven the routing is no row is left out and no padding row is
computed (:func:`chainermn_tpu.parallel.expert.dropless_moe`).

Two implementations, chosen by ``impl`` (a model's ``moe_matmul_impl``, as
``attention_impl`` chooses the attention):

* ``"pallas"`` — on the chip: the repo's own two kernels, in the design of
  JAX's ``jax.experimental.pallas.ops.tpu.megablox`` and on its group
  metadata (``make_group_metadata``, plain ``jax.numpy``, imported).  The
  grid is sized AT RUN TIME from the group sizes: one step per (row tile,
  group) pair that holds rows, so row tiles past ``sum(group_sizes)`` are
  never visited, an empty group costs nothing in the forward and one
  zeroing visit in ``drhs``, and a tile that straddles two groups is
  visited once for each with its stores masked by row.  The kernels are
  the repo's own because megablox's ``pallas_call`` gives its result no
  varying-axes type, which ``make_train_step``'s ``shard_map`` refuses
  (flash attention types its results the same way); being here they also
  take this chip's tile sizes (megablox's default 128 x 128 x 128 is a grid
  step every 4 MFLOP), mask in the operands' own dtype, contract the
  ``drhs`` tile over its rows without a transpose in float32, and zero the
  rows no tile wrote.
* ``"ragged_dot"`` — ``jax.lax.ragged_dot``, XLA's own grouped product with
  JAX's own derivative: the CPU path (tests, rehearsals, toy sizes; Pallas'
  interpreter cannot run inside a ``shard_map`` that checks varying axes).

The VJP of the Pallas path: ``dlhs = grouped_matmul(g, rhs^T)`` (the same
kernel with the group's matrix read transposed) and ``drhs[g] = lhs_g^T @
g_g``, the transposed grouped product.

No scope is opened here: the chip's trace names a Pallas kernel after the
innermost scope around it, which is the flax module that called it
(docs/observability.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

IMPLS = ("pallas", "ragged_dot")

# (rows, K, N) tile sizes of the Pallas kernels.  Row tiles of 512 keep the
# share of tiles that straddle two groups small at a thousand rows a group;
# K and N tiles near 1024 amortize the ~0.35 us a grid step costs.  A tile
# never exceeds the dimension it tiles and always divides it (1792 = 2 x
# 896).
_TILE_ROWS = 512
_TILE_K = 1024
_TILE_N = 1024
_LANE = 128


def _fit_tile(size: int, limit: int) -> int:
    """The largest multiple of 128 that divides ``size`` and is at most
    ``limit``; ``size`` itself where it is at most ``limit``."""
    if size <= limit:
        return size
    for tile in range(limit - limit % _LANE, 0, -_LANE):
        if size % tile == 0:
            return tile
    raise ValueError(f"grouped_matmul(impl='pallas'): no multiple of {_LANE}"
                     f" up to {limit} divides the dimension {size}")


def _fit_row_tile(rows: int) -> int:
    if rows % _LANE:
        raise ValueError(
            f"grouped_matmul(impl='pallas') needs a multiple of {_LANE} rows,"
            f" got {rows}: pad the rows (rows past sum(group_sizes) cost "
            "nothing)")
    tile = _TILE_ROWS
    while rows % tile:
        tile //= 2
    return tile


def _metadata(group_sizes, rows, tile_rows, visit_empty_groups):
    """megablox's schedule: ``(group_offsets [G + 1], group_ids, row_tile_ids
    [row tiles + G - 1])`` and the number of grid steps that do work."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)

    return make_group_metadata(
        group_sizes=group_sizes, m=rows, tm=tile_rows,
        start_group=jnp.int32(0), num_nonzero_groups=group_sizes.shape[0],
        visit_empty_groups=visit_empty_groups)


def _rows_of_group(step, metadata, tile_rows):
    """[tile_rows, 1] mask: which rows of this step's tile are its group's."""
    group_offsets, group_ids, row_tile_ids = metadata
    group = group_ids[step]
    rows = row_tile_ids[step] * tile_rows + jax.lax.broadcasted_iota(
        jnp.int32, (tile_rows, 1), 0)
    return (rows >= group_offsets[group]) & (rows < group_offsets[group + 1])


def _interpret():
    return jax.default_backend() != "tpu"


def _gmm(lhs, rhs, group_sizes, transpose_rhs):
    """[rows, K] x [G, K, N] (or [G, N, K] read transposed) -> [rows, N]."""
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tile_rows = _fit_row_tile(rows)
    tile_k, tile_n = _fit_tile(k, _TILE_K), _fit_tile(n, _TILE_N)
    steps_k = k // tile_k
    metadata, active_steps = _metadata(group_sizes, rows, tile_rows, False)
    contract = (((1,), (1,)), ((), ())) if transpose_rhs else (
        ((1,), (0,)), ((), ()))

    def kernel(group_offsets, group_ids, row_tile_ids, lhs_ref, rhs_ref,
               out_ref, acc_ref):
        step, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _start():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], contract,
            preferred_element_type=jnp.float32)

        @pl.when(k_i == steps_k - 1)
        def _store():
            # a tile shared by two groups is visited by each in turn and
            # stays resident between the visits: keep the other's rows
            mine = _rows_of_group(
                step, (group_offsets, group_ids, row_tile_ids), tile_rows)
            out_ref[...] = jnp.where(
                mine, acc_ref[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    def lhs_index(n_i, step, k_i, offsets, group_ids, row_tile_ids):
        return row_tile_ids[step], k_i

    def rhs_index(n_i, step, k_i, offsets, group_ids, row_tile_ids):
        if transpose_rhs:
            return group_ids[step], n_i, k_i
        return group_ids[step], k_i, n_i

    def out_index(n_i, step, k_i, offsets, group_ids, row_tile_ids):
        return row_tile_ids[step], n_i

    rhs_block = (None, tile_n, tile_k) if transpose_rhs else (
        None, tile_k, tile_n)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype,
                                       vma=jax.typeof(lhs).vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tile_rows, tile_k), lhs_index),
                      pl.BlockSpec(rhs_block, rhs_index)],
            out_specs=pl.BlockSpec((tile_rows, tile_n), out_index),
            grid=(n // tile_n, active_steps, steps_k),
            scratch_shapes=[pltpu.VMEM((tile_rows, tile_n), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(lhs.size + rhs.size + rows * n)
            * lhs.dtype.itemsize),
        interpret=_interpret(),
    )(*metadata, lhs, rhs)
    # no step visits a tile past the last group: those rows were never
    # written
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return jnp.where(row < jnp.sum(group_sizes), out,
                     jnp.zeros((), out.dtype))


def _tgmm(lhs, g, group_sizes):
    """``out[group] = lhs[group's rows]^T @ g[group's rows]``: [rows, K] and
    [rows, N] -> [G, K, N], zeros for an empty group."""
    rows, k = lhs.shape
    n = g.shape[1]
    groups = group_sizes.shape[0]
    tile_rows = _fit_row_tile(rows)
    tile_k, tile_n = _fit_tile(k, _TILE_K), _fit_tile(n, _TILE_N)
    metadata, active_steps = _metadata(group_sizes, rows, tile_rows, True)

    def kernel(group_offsets, group_ids, row_tile_ids, lhs_ref, g_ref,
               out_ref, acc_ref):
        step = pl.program_id(2)
        group = group_ids[step]
        first = (step == 0) | (group_ids[jnp.maximum(step - 1, 0)] != group)
        last_step = pl.num_programs(2) - 1
        last = (step == last_step) | (
            group_ids[jnp.minimum(step + 1, last_step)] != group)

        @pl.when(first)
        def _start():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(group_offsets[group + 1] > group_offsets[group])
        def _accumulate():
            mine = _rows_of_group(
                step, (group_offsets, group_ids, row_tile_ids), tile_rows)
            # the other group's rows of a shared tile count nothing: it is
            # enough to zero them in one operand (both are finite)
            block = jnp.where(mine, g_ref[...], jnp.zeros((), g_ref.dtype))
            acc_ref[...] += jax.lax.dot_general(
                lhs_ref[...], block, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(last)
        def _store():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def lhs_index(n_i, k_i, step, offsets, group_ids, row_tile_ids):
        return row_tile_ids[step], k_i

    def g_index(n_i, k_i, step, offsets, group_ids, row_tile_ids):
        return row_tile_ids[step], n_i

    def out_index(n_i, k_i, step, offsets, group_ids, row_tile_ids):
        return group_ids[step], k_i, n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype,
                                       vma=jax.typeof(lhs).vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tile_rows, tile_k), lhs_index),
                      pl.BlockSpec((tile_rows, tile_n), g_index)],
            out_specs=pl.BlockSpec((None, tile_k, tile_n), out_index),
            grid=(n // tile_n, k // tile_k, active_steps),
            scratch_shapes=[pltpu.VMEM((tile_k, tile_n), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(lhs.size + g.size + groups * k * n)
            * lhs.dtype.itemsize),
        interpret=_interpret(),
    )(*metadata, lhs, g)


@jax.custom_vjp
def _pallas_grouped_matmul(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes, transpose_rhs=False)


def _pallas_fwd(lhs, rhs, group_sizes):
    return _pallas_grouped_matmul(lhs, rhs, group_sizes), (
        lhs, rhs, group_sizes)


def _pallas_bwd(residual, g):
    lhs, rhs, group_sizes = residual
    dlhs = _gmm(g, rhs, group_sizes, transpose_rhs=True)
    return dlhs, _tgmm(lhs, g, group_sizes), None


_pallas_grouped_matmul.defvjp(_pallas_fwd, _pallas_bwd)


def grouped_matmul(lhs, rhs, group_sizes, impl: str = "pallas"):
    """``out[r] = lhs[r] @ rhs[group of row r]``; rows past
    ``sum(group_sizes)`` are zero.

    ``lhs`` [rows, K], ``rhs`` [G, K, N] (one dtype; bfloat16 or float32),
    ``group_sizes`` [G] int32 with ``sum <= rows``.  Returns [rows, N] in
    ``lhs``'s dtype, accumulated in float32.  Differentiable in ``lhs`` and
    ``rhs``; the rows past the groups get and give zero gradient.
    """
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1]:
        raise ValueError(f"grouped_matmul needs lhs [rows, K] and rhs "
                         f"[G, K, N], got {lhs.shape} and {rhs.shape}")
    if lhs.dtype != rhs.dtype:
        raise ValueError(f"grouped_matmul needs one dtype, got {lhs.dtype} "
                         f"and {rhs.dtype}")
    if group_sizes.shape != (rhs.shape[0],):
        raise ValueError(f"group_sizes {group_sizes.shape} must give one "
                         f"size for each of the {rhs.shape[0]} groups")
    group_sizes = group_sizes.astype(jnp.int32)
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    return _pallas_grouped_matmul(lhs, rhs, group_sizes)


__all__ = ["grouped_matmul"]

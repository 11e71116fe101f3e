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
  visited once for each, which multiplies the sub-tiles its rows reach and
  masks by row the one its edge cuts (the tile plan, below the imports;
  :func:`grouped_matmul_census` counts what it does).  The kernels are
  the repo's own because megablox's ``pallas_call`` gives its result no
  varying-axes type, which ``make_train_step``'s ``shard_map`` refuses
  (flash attention types its results the same way); being here they also
  take this chip's tile sizes (megablox's default 128 x 128 x 128 is a grid
  step every 4 MFLOP), mask in the operands' own dtype, contract the
  ``drhs`` tile over its rows without a transpose in float32, and zero the
  rows no tile wrote.
* ``"ragged_dot"`` — ``jax.lax.ragged_dot``, XLA's own grouped product with
  JAX's own derivative: the CPU path (tests, rehearsals, toy sizes; Pallas'
  interpreter cannot run inside a ``shard_map`` that checks varying axes)
  and the expert layer's guarded remainder on the chip.  XLA's TPU kernel
  leaves the rows past the groups UNWRITTEN (infinities and NaNs on the
  chip, PERF.md, PR 32; the CPU's writes zeros), so this path selects zeros
  there itself, on the way in and on the way out: the promise above holds
  forward and backward, whatever those rows held.

The VJP of the Pallas path: ``dlhs = grouped_matmul(g, rhs^T)`` (the same
kernel with the group's matrix read transposed) and ``drhs[g] = lhs_g^T @
g_g``, the transposed grouped product.

No scope is opened here: the chip's trace names a Pallas kernel after the
innermost scope around it, which is the flax module that called it
(docs/observability.md).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

IMPLS = ("pallas", "ragged_dot")

# The tile plan of the Pallas kernels: which (rows, K, N) blocks a grid step
# holds and which part of its row tile it multiplies.  A function of what a
# kernel sees (rows, K, N, the number of groups, the operands' width and the
# prefetched offsets), the same for every model.
#
# Rows: tiles of 512, walked by sub-tiles of 128 or 256 (``_sub_rows``).  A
# step belongs to one (row tile, group) pair and multiplies only the
# sub-tiles that hold a row of its group: a tile that two groups share costs
# each of them the sub-tiles it reaches into, not the whole tile (a group of
# ~500 rows fills half of the 512-row tiles it visits and 0.8 of their
# 128-row sub-tiles).
#
# K and N: tiles near 1024 amortize the ~0.35 us a grid step costs.  A
# dimension up to the limit is one tile; a longer one takes the largest
# multiple of 128 that divides it (1792 = 2 x 896, 2304 = 3 x 768) where
# that is at least ``_MIN_TILE``.  A dimension with no such divisor (1408 =
# 11 x 128: in 128-wide tiles a grid step does a tenth of the work and the
# rows are fetched eleven times) is taken WHOLE where the step's blocks then
# fit ``_VMEM_BUDGET`` (the kernel asks Mosaic for what its blocks need
# where that nears the scoped default), and in equal tiles with a ragged
# last one otherwise: Pallas pads a block that overhangs its array, what is
# stored past the edge is dropped, and the one place where the padding
# would count, the contraction of ``_gmm``, zeroes it in both operands.
_TILE_ROWS = 512
_TILE_K = 1024
_TILE_N = 1024
_MIN_TILE = 512
_LANE = 128
_VMEM_DEFAULT = 16 * 2 ** 20    # Mosaic's scoped limit on a v5e
_VMEM_BUDGET = 40 * 2 ** 20     # of the 128 MiB a v5e core has


def _fit_tile(size: int, limit: int) -> int:
    """One dimension's K or N tile: ``size`` where it is at most ``limit``,
    else the largest multiple of 128 that divides it, is at most ``limit``
    and at least ``_MIN_TILE``; where there is none, ``size`` again (the
    whole dimension: :func:`_fit_tiles` sees whether that fits)."""
    if size <= limit:
        return size
    for tile in range(limit - limit % _LANE, _MIN_TILE - 1, -_LANE):
        if size % tile == 0:
            return tile
    if size % _LANE:
        raise ValueError(f"grouped_matmul(impl='pallas') needs K and N a "
                         f"multiple of {_LANE} where they pass {limit}, got "
                         f"{size}")
    return size


def _ragged_tile(size: int, limit: int) -> int:
    """Equal tiles of at most ``limit``, a multiple of 128, the last ragged."""
    tiles = -(-size // limit)
    return -(-size // (tiles * _LANE)) * _LANE


class _Plan(NamedTuple):
    """One kernel's blocks: row tile and the sub-tile it is walked by, K and
    N tiles, and what to ask Mosaic for (None: its default does)."""
    tile_rows: int
    sub_rows: int
    tile_k: int
    tile_n: int
    vmem_limit_bytes: Optional[int]


def _fit_tiles(vmem_bytes, rows: int, groups: int, k: int, n: int,
               itemsize: int) -> _Plan:
    """The plan of one kernel; ``vmem_bytes(tile_rows, tile_k, tile_n,
    itemsize)`` is what its blocks hold."""
    tile_rows = _fit_row_tile(rows)
    tile_k, tile_n = _fit_tile(k, _TILE_K), _fit_tile(n, _TILE_N)
    if vmem_bytes(tile_rows, tile_k, tile_n, itemsize) > _VMEM_BUDGET:
        # only a dimension taken whole for want of a divisor can be so long
        if tile_k > _TILE_K:
            tile_k = _ragged_tile(k, _TILE_K)
        if tile_n > _TILE_N:
            tile_n = _ragged_tile(n, _TILE_N)
    need = vmem_bytes(tile_rows, tile_k, tile_n, itemsize)
    # Mosaic keeps temporaries of its own beside the blocks: ask with room
    limit = None if 4 * need <= 3 * _VMEM_DEFAULT else need + need // 2
    return _Plan(tile_rows, min(_sub_rows(rows, groups), tile_rows), tile_k,
                 tile_n, limit)


def _sub_rows(rows: int, groups: int) -> int:
    """The sub-tile a step walks its row tile by, from what a group is
    expected to hold: the expert layer's bound is the even share and a half
    (``dropless_rows_bound``), so two thirds of ``rows`` over ``groups``.
    Under two row tiles a group 128, else 256: a group's two edges leave
    about a sub-tile of its rows' worth multiplied for nothing, and the MXU
    runs 128-row operands some 5 % slower than 256-row ones (PERF.md, PR
    43: the kernels alone at groups of ~500, ~770, ~1,020 and ~3,070 rows)."""
    return 128 if 2 * rows < 3 * groups * 2 * _TILE_ROWS else 256


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


def _gmm_vmem_bytes(tile_rows, tile_k, tile_n, itemsize):
    """Double-buffered operand and result blocks, the float32 accumulator
    and the product of a sub-tile (the larger, 256 rows)."""
    blocks = tile_rows * tile_k + tile_k * tile_n + tile_rows * tile_n
    return (2 * blocks * itemsize + 4 * tile_rows * tile_n
            + 4 * min(256, tile_rows) * tile_n)


def _tgmm_vmem_bytes(tile_rows, tile_k, tile_n, itemsize):
    blocks = tile_rows * tile_k + tile_rows * tile_n + tile_k * tile_n
    return 2 * blocks * itemsize + 4 * tile_k * tile_n


def _metadata(group_sizes, rows, tile_rows, visit_empty_groups):
    """megablox's schedule: ``(group_offsets [G + 1], group_ids, row_tile_ids
    [row tiles + G - 1])`` and the number of grid steps that do work."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)

    return make_group_metadata(
        group_sizes=group_sizes, m=rows, tm=tile_rows,
        start_group=jnp.int32(0), num_nonzero_groups=group_sizes.shape[0],
        visit_empty_groups=visit_empty_groups)


def _for_each_sub_tile(step, metadata, tile_rows, sub, visit):
    """Walk this step's row tile by sub-tiles of ``sub`` rows and call
    ``visit(rows, whole, mine)`` on those that hold a row of the step's
    group: ``rows`` the sub-tile's slice of the tile, ``whole`` whether it
    holds no other row, ``mine(block, other=zeros)`` the block [sub, width]
    with ``other`` in the rows that are not the group's.  ``lax`` primitives,
    not ``jnp`` calls: a step traces 36-45 of these kernels."""
    group_offsets, group_ids, row_tile_ids = metadata
    group = group_ids[step]
    first_row = lax.mul(row_tile_ids[step], jnp.int32(tile_rows))
    # the group's rows, counted from the tile's first: [low, high)
    low = lax.sub(group_offsets[group], first_row)
    high = lax.sub(group_offsets[lax.add(group, jnp.int32(1))], first_row)

    def one(i, _):
        first = pl.multiple_of(lax.mul(i, jnp.int32(sub)), sub)

        def mine(block, other=None):
            row = lax.add(lax.broadcasted_iota(jnp.int32, block.shape, 0),
                          first)
            keep = lax.bitwise_and(lax.ge(row, low), lax.lt(row, high))
            return lax.select(
                keep, block,
                lax.full_like(block, 0) if other is None else other)

        visit(pl.ds(first, sub),
              lax.bitwise_and(lax.le(low, first),
                              lax.ge(high, lax.add(first, jnp.int32(sub)))),
              mine)

    # the sub-tiles a group reaches are neighbours: from the one that holds
    # its first row of the tile to the one that holds its last
    reached_from = lax.div(lax.max(low, jnp.int32(0)), jnp.int32(sub))
    reached_to = lax.div(
        lax.add(lax.min(high, jnp.int32(tile_rows)), jnp.int32(sub - 1)),
        jnp.int32(sub))
    # an empty group (``drhs`` visits it once, to zero its result): none
    reached_to = lax.select(lax.gt(high, low), reached_to, reached_from)
    lax.fori_loop(reached_from, reached_to, one, None)


def _interpret():
    return jax.default_backend() != "tpu"


def _gmm(lhs, rhs, group_sizes, transpose_rhs):
    """[rows, K] x [G, K, N] (or [G, N, K] read transposed) -> [rows, N]."""
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tile_rows, sub, tile_k, tile_n, vmem_limit = _fit_tiles(
        _gmm_vmem_bytes, rows, rhs.shape[0], k, n, lhs.dtype.itemsize)
    steps_k = pl.cdiv(k, tile_k)
    metadata, active_steps = _metadata(group_sizes, rows, tile_rows, False)
    contract = (((1,), (1,)), ((), ())) if transpose_rhs else (
        ((1,), (0,)), ((), ()))

    def kernel(group_offsets, group_ids, row_tile_ids, lhs_ref, rhs_ref,
               out_ref, *acc_ref):
        step, k_i = pl.program_id(1), pl.program_id(2)

        def in_k(block, axis):
            """The last K tile overhangs both operands: what pads it counts
            nothing (it may hold anything, so zero it on both sides)."""
            if k % tile_k == 0:
                return block
            column = lax.broadcasted_iota(jnp.int32, block.shape, axis)
            return lax.select(column < k - k_i * tile_k, block,
                              lax.full_like(block, 0))

        def multiply(rows_of, whole, mine):
            def product():
                # loads inside the loop: a sub-tile not reached loads none
                return lax.dot_general(
                    in_k(lhs_ref[rows_of, :], 1),
                    in_k(rhs_ref[...], 1 if transpose_rhs else 0), contract,
                    preferred_element_type=jnp.float32)

            def store(total):
                @pl.when(whole)
                def _plainly():
                    out_ref[rows_of, :] = lax.convert_element_type(
                        total, out_ref.dtype)

                @pl.when(lax.bitwise_not(whole))
                def _masked():
                    # the group's edge cuts this sub-tile: a tile shared by
                    # two groups is visited by each in turn and stays
                    # resident between the visits, keep the other's rows
                    out_ref[rows_of, :] = mine(
                        lax.convert_element_type(total, out_ref.dtype),
                        out_ref[rows_of, :])

            # each K step's product goes where it is wanted at once (the
            # accumulator, or with it into the result): a product held for
            # a branch to pick up is a pass over the tile more
            if steps_k == 1:
                store(product())
                return
            acc, = acc_ref

            @pl.when(k_i == 0)
            def _start():
                acc[rows_of, :] = product()

            if steps_k > 2:
                @pl.when((k_i > 0) & (k_i < steps_k - 1))
                def _accumulate():
                    acc[rows_of, :] = lax.add(acc[rows_of, :], product())

            @pl.when(k_i == steps_k - 1)
            def _finish():
                store(lax.add(acc[rows_of, :], product()))

        _for_each_sub_tile(step, (group_offsets, group_ids, row_tile_ids),
                           tile_rows, sub, multiply)

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
            grid=(pl.cdiv(n, tile_n), active_steps, steps_k),
            # K in one tile: a sub-tile's product is its result
            scratch_shapes=[pltpu.VMEM((tile_rows, tile_n), jnp.float32)]
            if steps_k > 1 else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(lhs.size + rhs.size + rows * n)
            * lhs.dtype.itemsize),
        interpret=_interpret(),
    )(*metadata, lhs, rhs)
    # no step visits a tile past the last group, and no step stores a
    # sub-tile that holds no row of its group: those rows were never written
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return jnp.where(row < jnp.sum(group_sizes), out,
                     jnp.zeros((), out.dtype))


def _tgmm(lhs, g, group_sizes):
    """``out[group] = lhs[group's rows]^T @ g[group's rows]``: [rows, K] and
    [rows, N] -> [G, K, N], zeros for an empty group."""
    rows, k = lhs.shape
    n = g.shape[1]
    groups = group_sizes.shape[0]
    tile_rows, sub, tile_k, tile_n, vmem_limit = _fit_tiles(
        _tgmm_vmem_bytes, rows, groups, k, n, lhs.dtype.itemsize)
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

        def accumulate(rows_of, whole, mine):
            # another group's rows, or rows past the groups, which may hold
            # anything, count nothing: ``mine`` zeroes them on both sides
            # (on every sub-tile: a branch for the uncut ones gains nothing)
            acc_ref[...] = lax.add(acc_ref[...], lax.dot_general(
                mine(lhs_ref[rows_of, :]), mine(g_ref[rows_of, :]),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))

        # an empty group reaches no sub-tile: its one visit zeroes and stores
        _for_each_sub_tile(step, (group_offsets, group_ids, row_tile_ids),
                           tile_rows, sub, accumulate)

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
            grid=(pl.cdiv(n, tile_n), pl.cdiv(k, tile_k), active_steps),
            scratch_shapes=[pltpu.VMEM((tile_k, tile_n), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
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
        in_groups = (jnp.arange(lhs.shape[0]) < group_sizes.sum())[:, None]
        zero = jnp.zeros((), lhs.dtype)
        out = jax.lax.ragged_dot(jnp.where(in_groups, lhs, zero), rhs,
                                 group_sizes)
        return jnp.where(in_groups, out, zero)
    return _pallas_grouped_matmul(lhs, rhs, group_sizes)


def grouped_matmul_census(group_sizes, rows: int, k: int, n: int,
                          itemsize: int = 2) -> dict:
    """What the tile plan does with concrete ``group_sizes``: a counter of
    shapes and sizes alone, from the arithmetic the kernels use, for the
    three kernels of one ``grouped_matmul(lhs[rows, k], rhs[G, k, n])`` and
    its VJP.  ``group_sizes`` is a sequence or array of G counts (a layer's
    ``tokens_per_held_expert`` from a ``with_counters`` run beside its
    ``rows_bound``: what passes ``rows`` is the remainder's and is left
    out), ``itemsize`` the operands' width in bytes.

    ``tile_rows`` and ``sub_rows``; ``tiles``, the ``(tile_k, tile_n)`` of
    ``forward``, ``dlhs`` (the same kernel over ``[rows, n] x [G, n, k]``)
    and ``drhs``, with ``vmem_limit_bytes`` what each asks Mosaic for (None:
    the default); ``visits``, the (row tile, group) pairs that hold rows,
    one grid step each a K and N tile, and ``grid_steps`` the three kernels'
    (``drhs`` also visits every empty group once, to zero its result).
    ``row_slots`` are the rows the visits multiply, whole sub-tiles;
    ``own_rows`` those that are the visiting group's, every row of a group
    once; ``row_fill`` their share (1.0: no row is multiplied for a group
    that is not its own)."""
    # the part of the sizes below ``rows``, as the expert layer's main pass
    # reads a routing that passes its bound
    ends = [0]
    for size in group_sizes:
        ends.append(min(ends[-1] + int(size), rows))
    sizes = [end - start for start, end in zip(ends, ends[1:])]
    # kernel -> (what its blocks hold, the dimension it has as K, as N)
    kernels = {"forward": (_gmm_vmem_bytes, k, n),
               "dlhs": (_gmm_vmem_bytes, n, k),
               "drhs": (_tgmm_vmem_bytes, k, n)}
    plans = {name: _fit_tiles(vmem_bytes, rows, len(sizes), inner, outer,
                              itemsize)
             for name, (vmem_bytes, inner, outer) in kernels.items()}
    tile_rows, sub = plans["forward"][:2]
    visits = row_slots = start = 0
    for size in sizes:
        end = start + size
        if size:
            visits += -(-end // tile_rows) - start // tile_rows
            row_slots += (-(-end // sub) - start // sub) * sub
        start = end

    def steps(name, visits):
        (_, inner, outer), plan = kernels[name], plans[name]
        return visits * -(-inner // plan.tile_k) * -(-outer // plan.tile_n)

    return {
        "tile_rows": tile_rows, "sub_rows": sub,
        "tiles": {name: {"tile_k": plan.tile_k, "tile_n": plan.tile_n,
                         "vmem_limit_bytes": plan.vmem_limit_bytes}
                  for name, plan in plans.items()},
        "visits": visits,
        "grid_steps": {"forward": steps("forward", visits),
                       "dlhs": steps("dlhs", visits),
                       "drhs": steps("drhs", visits + sizes.count(0))},
        "row_slots": row_slots, "own_rows": sum(sizes),
        "row_fill": sum(sizes) / row_slots if row_slots else 1.0}


__all__ = ["grouped_matmul", "grouped_matmul_census"]

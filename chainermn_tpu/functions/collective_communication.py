"""Differentiable collective communication.

Reference being rebuilt (path unverified, SURVEY.md provenance):
〔chainermn/functions/collective_communication.py〕 — ``AllGather``,
``AllToAll``, ``Bcast``, ``Gather``, ``Scatter`` as Chainer Functions whose
backwards are the *transposed collectives* (alltoall <-> alltoall, gather <->
scatter, bcast <-> reduce).

TPU-native version: these are thin wrappers over the communicator's traced
collectives — JAX already knows the transpose of every XLA collective
(``all_gather``'s transpose is ``psum_scatter``, ``all_to_all``'s is itself
with swapped axes, ``psum``'s is broadcast), so the reference's hand-written
backward classes collapse into the wrappers below.  They must be called
inside an SPMD region (``comm.run_spmd`` / shard_map over the comm's mesh),
where each device is one reference rank.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chainermn_tpu.utils import pvary


def allgather(communicator, x):
    """Gather every rank's ``x`` onto all ranks -> stacked [size, ...].
    Backward: each rank gets the summed slice of the cotangent that
    corresponds to its contribution (reduce-scatter — automatic)."""
    return communicator.allgather(x)


def alltoall(communicator, xs):
    """Transposed exchange of per-peer slots (leading axis == size).
    Backward: alltoall again (its own transpose — automatic)."""
    return communicator.alltoall(xs)


def bcast(communicator, x, root: int = 0):
    """Broadcast ``x`` from ``root``.  Backward: the cotangents from all
    ranks are summed onto ``root`` (bcast <-> reduce — automatic)."""
    return communicator.bcast(x, root=root)


def gather(communicator, x, root: int = 0):
    """Gather onto ``root`` (SPMD: materialized everywhere; see the
    communicator's note).  Backward: scatter of the cotangent."""
    return communicator.gather(x, root=root)


def scatter(communicator, x, root: int = 0):
    """Each rank takes its slice of root's stacked [size, ...] value.
    Backward: gather of the cotangents."""
    return communicator.scatter(x, root=root)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 2))
def _allreduce_diff(communicator, x, op):
    return communicator.allreduce(x, op=op)


def _allreduce_fwd(communicator, x, op):
    # residual: a scalar per leaf that carries the input's varying-axes
    # type into the backward rule (types are not values, so a traced zero
    # of that type is the carrier)
    like = jax.tree.map(
        lambda v: pvary(jnp.zeros((), v.dtype), tuple(jax.typeof(v).vma)), x)
    return communicator.allreduce(x, op=op), like


def _allreduce_bwd(communicator, op, like, g):
    # The cotangent of an allreduce output is replicated across ranks, so
    # the transpose is the identity (scaled by 1/size for the mean).  Pinned
    # explicitly because a region traced WITHOUT varying-axes tracking
    # (``shard_map(check_vma=False)``, which Pallas interpret mode forces
    # on the CPU) transposes psum to psum, inflating the gradient by
    # ``size``; with tracking on, this is what JAX does natively, and the
    # cotangent only has to be retyped as varying like the input it
    # answers for.
    if op == "mean":
        g = jax.tree.map(lambda v: v / communicator.size, g)
    return (jax.tree.map(
        lambda v, z: pvary(v, tuple(jax.typeof(z).vma)), g, like),)


_allreduce_diff.defvjp(_allreduce_fwd, _allreduce_bwd)


def allreduce(communicator, x, op: str = "sum"):
    """Allreduce with differentiable semantics (psum's transpose is the
    identity broadcast of the cotangent to every rank)."""
    if op in ("sum", "mean"):
        return _allreduce_diff(communicator, x, op)
    return communicator.allreduce(x, op=op)

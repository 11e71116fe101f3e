"""Flat-buffer pack / unpack of gradient pytrees.

Reference being rebuilt (path unverified, SURVEY.md provenance):
``pack_params`` / ``unpack_params`` / ``DeviceMemory`` in
〔chainermn/communicators/_memory_utility.py〕 — gather every ``param.grad``
into one contiguous GPU buffer by byte offset (with optional dtype cast via a
runtime-compiled CUDA kernel), run one collective over the buffer, scatter
back.

TPU-native version: the "buffer" is a flat jnp array built inside the traced
allreduce; XLA owns the actual memory.  Leaves are grouped by dtype (one flat
buffer per dtype) unless a communication dtype is forced, in which case a
single buffer is used and the cast in/out is fused by XLA.

Who still packs: what shards, stripes or quantizes a flat index space —
plans with a reduce-scatter/all-gather, striped plans, quantizing plans
(``planner.compiler.plan_needs_buffer``), ZeRO-1, FSDP and the compressed
allreduce.  A gradient mean whose every stage is an all-reduce does NOT: the
compiler reduces its leaves where they lie, because on the chip gathering
them into one buffer and slicing it apart again cost more than the
collective between (PERF.md, PR 25).

What ``pack`` and ``unpack`` put into a traced program carries the named
scopes ``chainermn.pack`` / ``chainermn.unpack`` (wire cast and 1/size scale
included), so a device trace can tell the copies around a collective from
the collective (docs/observability.md).  The leaf-wise lowering opens the
same two scopes around its wire cast and its cast-back + scale.
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def cast_like(tree: Any, like: Any = None):
    """``tree`` with every leaf in the dtype of ``like``'s leaf (``like``
    None: ``tree`` as it is).  What ``allreduce_grad(..., like=)`` does to
    gradients kept in a wire dtype before a lowering that has no use for
    them so: a narrower float widens losslessly, and the same values reach
    the wire."""
    if like is None:
        return tree
    return jax.tree.map(
        lambda leaf, l: leaf if leaf.dtype == l.dtype
        else leaf.astype(l.dtype), tree, like)


def pack(tree: Any, comm_dtype: Optional[jnp.dtype] = None):
    """Flatten a pytree into per-dtype flat buffers.

    Returns ``(buffers, meta)`` where ``buffers`` is a list of 1-D arrays and
    ``meta`` recovers the tree via :func:`unpack`.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return [], (treedef, [], [])
    groups: dict = {}
    order = []  # (group_key, index_within_group, shape, orig_dtype)
    with jax.named_scope("chainermn.pack"):
        for leaf in leaves:
            key = "comm" if comm_dtype is not None else str(leaf.dtype)
            groups.setdefault(key, [])
            order.append((key, len(groups[key]), leaf.shape, leaf.dtype))
            flat = leaf.reshape(-1)
            if comm_dtype is not None and leaf.dtype != comm_dtype:
                flat = flat.astype(comm_dtype)
            groups[key].append(flat)
        keys = list(groups.keys())
        buffers = [jnp.concatenate(groups[k]) if len(groups[k]) > 1
                   else groups[k][0] for k in keys]
    return buffers, (treedef, keys, order)


def unpack(buffers: List[jnp.ndarray], meta, scale: Optional[float] = None):
    """Inverse of :func:`pack`; optionally fuses a ``*= scale`` (the
    reference's 1/size multiply, fused with the cast-back kernel).

    The scale is applied AFTER the cast back to each leaf's original
    dtype: with a reduced-precision comm dtype (bf16 wire) a wire-dtype
    multiply would round the 1/size factor into the wire's mantissa
    before the full-precision restore — the cast-back must see the raw
    reduced values and the scaling happen in leaf precision."""
    treedef, keys, order = meta
    if not order:
        return jax.tree.unflatten(treedef, [])
    # Compute split points per group.
    offsets = {k: [0] for k in keys}
    sizes: dict = {k: [] for k in keys}
    for key, _, shape, _ in order:
        n = int(np.prod(shape)) if shape else 1
        sizes[key].append(n)
        offsets[key].append(offsets[key][-1] + n)
    pieces_by_group = {}
    leaves = []
    with jax.named_scope("chainermn.unpack"):
        for k, buf in zip(keys, buffers):
            cuts = offsets[k][1:-1]
            pieces_by_group[k] = jnp.split(buf, cuts) if cuts else [buf]
        for key, idx, shape, dtype in order:
            piece = pieces_by_group[key][idx].reshape(shape)
            if piece.dtype != dtype:
                piece = piece.astype(dtype)
            if scale is not None:
                piece = piece * jnp.asarray(scale, piece.dtype)
            leaves.append(piece)
    return jax.tree.unflatten(treedef, leaves)


class PadStrip(int):
    """The pad amount returned by :func:`pad_to_multiple`, doubling as
    the inverse operation: ``strip(buf)`` slices a flat buffer of the
    padded length back to the original one.  Subclasses ``int`` so the
    historical ``buf, pad = pad_to_multiple(...)`` call sites keep their
    arithmetic/truthiness semantics (``full[:n - pad]``, ``if pad:``)
    unchanged."""

    def __new__(cls, rem: int, orig_len: int):
        self = super().__new__(cls, rem)
        self.orig_len = int(orig_len)
        return self

    def __call__(self, buf: jnp.ndarray) -> jnp.ndarray:
        return buf[: self.orig_len]


def pad_to_multiple(buf: jnp.ndarray, m: int) -> Tuple[jnp.ndarray, PadStrip]:
    """Pad a flat buffer so its length divides ``m`` (needed by the
    reduce-scatter leg of the two-dimensional communicator and by the
    FSDP shard layout).

    Returns ``(padded, strip)``.  ``strip`` makes the inverse contract
    explicit: ``strip(padded) == buf`` (it is also the pad amount as an
    ``int``, for callers that track offsets themselves)."""
    n = int(buf.shape[0])
    rem = (-n) % m
    if rem:
        buf = jnp.concatenate([buf, jnp.zeros((rem,), buf.dtype)])
    return buf, PadStrip(rem, n)

"""Weights from the seed, made by the benchmark and not by the program: one
jitted call on the device, in float32 (the type the configurations keep
their parameters in).  The program's model gives only the SHAPES
(``jax.eval_shape`` of its ``init``); the values follow the rules below by
the leaf's name, so the plain reference and the program start from the same
tree and neither takes weights the other has made."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0):
    """A key for ``--seed`` (any whole number up to 2**32 - 1) and a stream
    (0 weights, 1 data)."""
    return jax.random.fold_in(jax.random.key(int(seed) % (1 << 32)), stream)


def _leaf(path, shape, key, kernel_std):
    name = str(getattr(path[-1], "key", path[-1]))
    if name in ("scale",):
        return jnp.ones(shape.shape, jnp.float32)
    if name in ("bias", "mean"):
        return jnp.zeros(shape.shape, jnp.float32)
    if name == "var":
        return jnp.ones(shape.shape, jnp.float32)
    if name in ("kernel", "embedding"):
        if kernel_std is None:   # He-normal: fan_in is all but the last axis
            std = math.sqrt(2.0 / math.prod(shape.shape[:-1]))
        else:
            std = kernel_std
        return std * jax.random.normal(key, shape.shape, jnp.float32)
    raise ValueError(f"no init rule for parameter leaf {path}")


def make_tree(shapes, key, kernel_std=None):
    """Fill a tree of ShapeDtypeStructs by the rules above; every leaf draws
    from its own fold of ``key`` (by position in the flattened tree)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = [_leaf(path, shape, jax.random.fold_in(key, i), kernel_std)
           for i, (path, shape) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)

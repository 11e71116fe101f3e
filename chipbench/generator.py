"""The one general generator of training traffic.  A traffic mix is a data
file (``traffic/<name>.json``: batch per chip, sequence length, ring size,
layout) and a configuration lists its ``inputs`` (shape, dtype,
distribution); this module turns the two and a seed into a ring of global
batches, made on the device in one jitted program and sharded by rows over
the cell's chips.  Every seed gives the same sizes; only the values differ.

Distributions: ``normal`` (standard normal floats), ``uniform_int`` over
``[0, high)`` and ``skewed_int`` (``floor(high * u**3)``, u uniform: a
long-tailed distribution whose entropy is 0.9 nats under ``log(high)``, so
that a language model's loss falls within tens of steps as it learns the
marginal).  An input may carry a ``class_signal``: a per-class offset of
its last axis (one fixed table a run, drawn from the seed), added to every
row according to that row's label, so that labels can be learnt from the
inputs and a classifier's loss falls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def global_batch(sizes, chips):
    return int(sizes["batch_per_chip"]) * chips


def _dim(token, sizes, batch):
    if token == "B":
        return batch
    if token == "T":
        return int(sizes["seq_len"])
    if isinstance(token, str):
        return int(sizes[token])
    return int(token)


def input_shapes(sizes, chips):
    batch = global_batch(sizes, chips)
    return [(tuple(_dim(t, sizes, batch) for t in spec["shape"]), spec)
            for spec in sizes["inputs"]]


def _draw(key, shape, spec, sizes):
    if spec["dist"] == "normal":
        return jax.random.normal(key, shape, jnp.dtype(spec["dtype"]))
    if spec["dist"] in ("skewed_int", "uniform_int"):
        high = _dim(spec["high"], sizes, None)
        u = jax.random.uniform(key, shape, jnp.float32)
        power = 3 if spec["dist"] == "skewed_int" else 1
        return jnp.minimum((high * u ** power).astype(jnp.int32),
                           high - 1).astype(jnp.dtype(spec["dtype"]))
    raise ValueError(f"unknown distribution {spec['dist']!r}")


def make_ring(sizes, chips, key, mesh, data_axes):
    """``ring`` global batches (tuples of arrays, rows sharded over
    ``data_axes``), resident on the device."""
    shapes = input_shapes(sizes, chips)
    sharding = NamedSharding(mesh, P(data_axes))

    names = [spec["name"] for _, spec in shapes]

    def one(k, table_key):
        drawn = [_draw(jax.random.fold_in(k, i), shape, spec, sizes)
                 for i, (shape, spec) in enumerate(shapes)]
        for i, (shape, spec) in enumerate(shapes):
            signal = spec.get("class_signal")
            if signal:
                labels = drawn[names.index(signal["labels"])]
                table = jax.random.normal(
                    jax.random.fold_in(table_key, i),
                    (_dim(signal["classes"], sizes, None), shape[-1]),
                    drawn[i].dtype)
                offset = signal["strength"] * table[labels]
                drawn[i] = drawn[i] + offset.reshape(
                    (shape[0],) + (1,) * (len(shape) - 2) + (shape[-1],))
        return tuple(drawn)

    make = jax.jit(one, out_shardings=tuple(sharding for _ in shapes))
    tables = jax.random.fold_in(key, 1 << 20)
    return [make(jax.random.fold_in(key, index), tables)
            for index in range(int(sizes["ring"]))]

"""Tensor (Megatron-style intra-layer) parallelism.

**Beyond-reference extension.** The reference has no tensor parallelism
(SURVEY.md §2.4 — its closest ancestor is the channel-split convolution
*example*).  These are the two canonical sharded linear layers, built on
mesh axes like everything else here:

* :class:`ColumnParallelDense` — weight columns sharded over the axis;
  each device computes its slice of the output features.  Output stays
  feature-sharded (``gather_output=False``, feed a RowParallelDense) or
  is all-gathered.
* :class:`RowParallelDense` — weight rows sharded; each device holds a
  feature slice of the input, computes a partial product, and the psum
  over the axis completes the matmul.

The canonical MLP block is ``Column(gather_output=False) -> activation
-> Row`` — one all-reduce per block, the Megatron recipe.  Both layers
are plain flax modules whose parameters are the LOCAL shards: inside
``shard_map`` every device initializes its own slice (vary the rng per
device or accept identical-slice init; tests shard a reference weight).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


class ColumnParallelDense(nn.Module):
    """Output-feature-sharded Dense: full input -> local feature slice.

    ``features`` is the LOCAL feature count (global // axis size).
    """

    features: int
    axis_name: Any = "tp"
    gather_output: bool = False
    use_bias: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("kernel", nn.initializers.lecun_normal(),
                       (x.shape[-1], self.features), jnp.float32)
        y = jnp.dot(x.astype(self.dtype), w.astype(self.dtype))
        if self.use_bias:
            b = self.param("bias", nn.initializers.zeros,
                           (self.features,), jnp.float32)
            y = y + b.astype(self.dtype)
        if self.gather_output:
            # psum of a position-scattered buffer rather than all_gather:
            # value-identical, but typed INVARIANT over the axis (the vma
            # system cannot infer invariance for all_gather outputs), so
            # the result composes with replicated out_specs.
            size = jax.lax.axis_size(self.axis_name)
            idx = lax.axis_index(self.axis_name)
            full = jnp.zeros(y.shape[:-1] + (size * self.features,),
                             y.dtype)
            full = lax.dynamic_update_slice_in_dim(
                full, y, idx * self.features, axis=y.ndim - 1)
            y = lax.psum(full, self.axis_name)
        return y


class RowParallelDense(nn.Module):
    """Input-feature-sharded Dense: local feature slice -> full output.

    The partial products are summed over the axis (ONE psum — the
    Megatron allreduce).  ``features`` is the GLOBAL output size.
    """

    features: int
    axis_name: Any = "tp"
    use_bias: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("kernel", nn.initializers.lecun_normal(),
                       (x.shape[-1], self.features), jnp.float32)
        y = jnp.dot(x.astype(self.dtype), w.astype(self.dtype))
        y = lax.psum(y, self.axis_name)
        if self.use_bias:
            # bias is replicated; added AFTER the reduction (once)
            b = self.param("bias", nn.initializers.zeros,
                           (self.features,), jnp.float32)
            y = y + b.astype(self.dtype)
        return y


class TensorParallelMLP(nn.Module):
    """Column -> activation -> Row: the canonical Megatron MLP block.

    ``hidden`` is the GLOBAL hidden width (must divide by the axis size);
    output width equals the input width.
    """

    hidden: int
    axis_name: Any = "tp"
    activation: Callable = nn.gelu
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        size = jax.lax.axis_size(self.axis_name)
        if self.hidden % size:
            raise ValueError(
                f"hidden ({self.hidden}) must divide by the tp axis "
                f"size ({size})")
        h = ColumnParallelDense(self.hidden // size, self.axis_name,
                                gather_output=False, dtype=self.dtype,
                                name="up")(x)
        h = self.activation(h)
        return RowParallelDense(x.shape[-1], self.axis_name,
                                dtype=self.dtype, name="down")(h)


__all__ = ["ColumnParallelDense", "RowParallelDense", "TensorParallelMLP"]

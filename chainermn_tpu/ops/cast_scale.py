"""Fused cast + scale Pallas kernel.

Reference being rebuilt (SURVEY.md §2.3, path unverified): the runtime-
compiled ``cupy.ElementwiseKernel`` strings inside
〔chainermn/communicators/pure_nccl_communicator.py〕 that (a) cast fp32
gradients into the fp16 communication buffer before ``ncclAllReduce`` and
(b) scale by 1/size fused with the fp16 -> fp32 cast-back afterwards.

TPU-native version: one Pallas VPU kernel ``y = (x * scale).astype(dst)``
over the packed flat gradient buffer.  XLA usually fuses the equivalent
``astype``+``mul`` on its own; this kernel exists as the native-kernel parity
item and as the guaranteed-fused path when profiling shows XLA didn't fuse
(enable with ``XlaCommunicator(use_pallas_cast=True)``).

Runs in interpret mode off-TPU so the CPU test mesh exercises it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from chainermn_tpu.utils import pvary

_LANE = 128
_BLOCK_ROWS = 256  # 256 x 128 f32 = 128 KiB per buffer; in+out fit VMEM easily


def _kernel(x_ref, s_ref, o_ref):
    # Compute in f32 so a half-precision source is scaled at full precision,
    # matching the reference's cast-then-scale kernel semantics.  The scale
    # arrives as a (1, 1) input (not a closure constant) so its varying-axes
    # metadata matches x's under shard_map interpret mode.
    v = x_ref[...].astype(jnp.float32)
    o_ref[...] = (v * s_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("target_dtype", "scale"))
def cast_scale(x: jnp.ndarray, target_dtype: Optional[jnp.dtype], scale: float):
    """Elementwise ``(x * scale).astype(target_dtype)`` as one fused kernel.

    ``x`` may be any shape; it is processed as a flat buffer (this is the
    packed-gradient path).  ``target_dtype=None`` keeps ``x.dtype``.
    """
    dst = jnp.dtype(target_dtype) if target_dtype is not None else x.dtype
    orig_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    # varying-over-mesh-axes set of the input: non-empty inside shard_map
    vma = jax.typeof(flat).vma
    interpret = jax.default_backend() != "tpu"
    if interpret and vma:
        # jax's HLO interpreter for pallas is not vma-aware (its internal
        # dynamic_slice mixes varying/invariant operands and trips
        # check_vma), so inside a shard_map off-TPU we emit the XLA-fused
        # equivalent instead; the kernel itself is exercised by direct
        # interpret-mode tests and runs for real on TPU.
        return (flat.astype(jnp.float32) * jnp.float32(scale)).astype(dst).reshape(orig_shape)

    def _zeros(k):
        # match the input's varying-axes set so concatenate is legal
        return pvary(jnp.zeros((k,), flat.dtype), tuple(vma))

    rows = -(-n // _LANE)
    pad = rows * _LANE - n
    if pad:
        flat = jnp.concatenate([flat, _zeros(pad)])
    grid_rows = -(-rows // _BLOCK_ROWS)
    padded_rows = grid_rows * _BLOCK_ROWS
    if padded_rows != rows:
        flat = jnp.concatenate([flat, _zeros((padded_rows - rows) * _LANE)])
    x2 = flat.reshape(padded_rows, _LANE)
    s_arr = jnp.full((1, 1), scale, jnp.float32)
    # Under shard_map with vma-checking, the out aval must carry the same
    # varying-across-mesh-axes set as the input (a cast is rank-local), and
    # every kernel input must share it.
    s_arr = pvary(s_arr, tuple(vma))
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((padded_rows, _LANE), dst, vma=vma),
        grid=(grid_rows,),
        in_specs=[pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0)),
        interpret=interpret,
    )(x2, s_arr)
    return out.reshape(-1)[:n].reshape(orig_shape)

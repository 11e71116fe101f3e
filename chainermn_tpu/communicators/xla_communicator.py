"""XLA communicator — the ``pure_nccl`` analogue and this framework's flagship.

Reference (path unverified, SURVEY.md provenance): ``PureNcclCommunicator``
〔chainermn/communicators/pure_nccl_communicator.py〕 — the fork's signature
component: every collective over one global NCCL communicator; gradient
allreduce = pack -> ncclAllReduce -> scale 1/size -> unpack, entirely on GPU
streams; ``allreduce_grad_dtype='float16'`` casts fp32 grads to an fp16
buffer (runtime-compiled CUDA cast kernel), allreduces in fp16, casts back —
the mixed-precision contribution behind the 15-minute ImageNet result.

TPU-native version: each gradient leaf is cast to the communication dtype
(``allreduce_grad_dtype``; pass ``bfloat16`` for the TPU-natural half type,
``None`` keeps each leaf's own dtype and casts nothing), all-reduced WHERE IT
LIES with ``lax.psum`` over *all* data axes at once, cast back and then
scaled by 1/size.  There is no packed buffer: on NCCL one launch over one
buffer beat a launch a parameter, but XLA's combiner already merges the
leaves' all-reduces into a few variadic ones (every small vector into one),
and on the chip the buffer cost two extra passes over the gradients — more
than the all-reduce it served (PERF.md, PR 25).  The plan compiler decides
this from the plan's stages (``planner.compiler.plan_needs_buffer``).  The
cast-in / scale+cast-out can optionally run through the Pallas kernel in
``chainermn_tpu/ops/cast_scale.py`` over packed buffers (the native-kernel
parity item, SURVEY.md §2.3; ``use_pallas_cast``) — by default XLA's own
fusion is used.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.communicators import _packing
from chainermn_tpu.communicators.mesh_communicator_base import MeshCommunicator


class XlaCommunicator(MeshCommunicator):
    supports_allreduce_grad_dtype = True
    flavor = "xla"

    def __init__(self, *args, allreduce_grad_dtype=None, use_pallas_cast: bool = False,
                 **kwargs):
        super().__init__(*args, allreduce_grad_dtype=allreduce_grad_dtype, **kwargs)
        self.use_pallas_cast = use_pallas_cast

    def _allreduce_grad_traced(self, grads):
        if self.use_pallas_cast and self.allreduce_grad_dtype is not None:
            # The Pallas cast+scale kernel path stays hand-lowered: it
            # is a kernel-selection knob, not a decomposition (the stage
            # sequence is identical to the plan's single all-reduce).
            return self._pallas_allreduce_grad_traced(grads)
        # Plan path: wire cast, an all-reduce a leaf, cast back and scale
        # — the base delegates to the plan compiler.
        return super()._allreduce_grad_traced(grads)

    def _pallas_allreduce_grad_traced(self, grads):
        comm_dtype = self.allreduce_grad_dtype
        ax = self._axis_arg()
        scale = 1.0 / self.size
        from chainermn_tpu.ops.cast_scale import cast_scale

        # Per-dtype groups keep each leaf's original dtype in meta so the
        # cast-back target is known per buffer.
        buffers, meta = _packing.pack(grads)
        _, group_dtypes, _ = meta
        # the kernel is the wire cast (and the 1/size scale) of this path:
        # it reads under the same names as the cast inside pack / unpack
        with jax.named_scope("chainermn.pack"):
            comm_bufs = [cast_scale(b, comm_dtype, 1.0) for b in buffers]
        comm_bufs = [lax.psum(b, ax) for b in comm_bufs]
        with jax.named_scope("chainermn.unpack"):
            out = [cast_scale(b, jnp.dtype(k), scale)
                   for b, k in zip(comm_bufs, group_dtypes)]
        return _packing.unpack(out, meta, scale=None)

    def _legacy_allreduce_grad_traced(self, grads):
        # pre-planner lowering, kept as the census-parity reference
        if self.use_pallas_cast and self.allreduce_grad_dtype is not None:
            return self._pallas_allreduce_grad_traced(grads)
        comm_dtype = self.allreduce_grad_dtype
        ax = self._axis_arg()
        buffers, meta = _packing.pack(grads, comm_dtype=comm_dtype)
        buffers = [lax.psum(b, ax) for b in buffers]
        return _packing.unpack(buffers, meta, scale=1.0 / self.size)


# The reference name, kept as an alias so stock scripts'
# ``create_communicator('pure_nccl')`` resolves to the TPU data-plane class.
PureXlaCommunicator = XlaCommunicator

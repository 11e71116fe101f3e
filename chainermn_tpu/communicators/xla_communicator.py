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
this from the plan's stages (``planner.compiler.plan_needs_buffer``); the
casts are XLA's own fusions, which run at the HBM bound (PERF.md, PR 25).
"""

from jax import lax

from chainermn_tpu.communicators import _packing
from chainermn_tpu.communicators.mesh_communicator_base import MeshCommunicator


class XlaCommunicator(MeshCommunicator):
    supports_allreduce_grad_dtype = True
    flavor = "xla"

    def _legacy_allreduce_grad_traced(self, grads):
        # pre-planner lowering, kept as the census-parity reference
        comm_dtype = self.allreduce_grad_dtype
        ax = self._axis_arg()
        buffers, meta = _packing.pack(grads, comm_dtype=comm_dtype)
        buffers = [lax.psum(b, ax) for b in buffers]
        return _packing.unpack(buffers, meta, scale=1.0 / self.size)


# The reference name, kept as an alias so stock scripts'
# ``create_communicator('pure_nccl')`` resolves to the TPU data-plane class.
PureXlaCommunicator = XlaCommunicator

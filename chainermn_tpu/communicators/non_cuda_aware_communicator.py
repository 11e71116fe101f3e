"""Host-staged communicator.

Reference (path unverified, SURVEY.md provenance):
``NonCudaAwareCommunicator`` 〔chainermn/communicators/non_cuda_aware_communicator.py〕
— like flat, but stages GPU buffers through pinned host memory before MPI,
for MPI builds that are not CUDA-aware.

TPU-native interpretation: the eager path genuinely stages gradients through
*host* memory and reduces across hosts over the DCN control plane — the
debugging/escape-hatch path when one wants the data plane off the ICI (the
exact role the reference class played).  Inside a traced SPMD region there is
no host to stage through (XLA owns execution), so the traced decomposition
falls back to flat-buffer psum and the class documents that staging is an
eager-mode behavior.
"""

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu.communicators import _packing
from chainermn_tpu.communicators.flat_communicator import FlatCommunicator
from chainermn_tpu.utils.placement import local_device_put


class NonCudaAwareCommunicator(FlatCommunicator):
    # same stage sequence as flat (host staging is eager-only), but its
    # own plan name so sweep rows / plan tables attribute timings right
    flavor = "non_cuda_aware"

    def allreduce_grad(self, grads, *, compressor=None, state=None,
                       like=None):
        from chainermn_tpu.compression import base as _cbase
        from chainermn_tpu.compression import quantize as _cq
        comp = (_cbase.resolve_compressor(compressor)
                if compressor is not None else
                (self.compression if _cq.is_quantizing(self.compression)
                 else None))
        if _cq.is_quantizing(comp) or self.in_spmd_context():
            # No host exists inside an XLA program, and quantizing codecs
            # ride the in-wire-summing collective either way; use the flat
            # decomposition (codec handling included).
            return super().allreduce_grad(
                grads, compressor=compressor, state=state, like=like)
        # Eager: device -> host -> (DCN mean across hosts) -> device, the
        # staged path the reference implements with pinned buffers.
        if comp is not None and comp.wire is not None:
            # Honor an explicit lossless wire codec with the same
            # cast-roundtrip the in-program path observes.
            grads = jax.tree.map(
                lambda g: g.astype(comp.wire).astype(g.dtype), grads)
        host = jax.device_get(_packing.cast_like(grads, like))
        if self.host_size > 1:
            summed = self.allreduce_obj(host, op="sum")
            host = jax.tree.map(lambda a: np.asarray(a) / self.host_size, summed)
        repl = NamedSharding(self._mesh, P())
        # every host holds the reduced value — place process-locally
        return local_device_put(host, repl)

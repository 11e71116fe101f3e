"""Flat communicator — single packed-buffer allreduce.

Reference (path unverified, SURVEY.md provenance): ``FlatCommunicator`` in
〔chainermn/communicators/flat_communicator.py〕 — pack all grads into one
contiguous GPU buffer, one CUDA-aware ``MPI.Allreduce`` over it, unpack.

Here the flavor is the plan "one all-reduce over every data axis, flat
packing", and the plan compiler lowers such a plan over the LEAVES (one
``lax.psum`` a leaf, which XLA's combiner merges): a buffer serves nothing
that only all-reduces (``planner.compiler.plan_needs_buffer``).  The
reference's literal form — concatenate into flat per-dtype buffers, one
``lax.psum`` per buffer, split back — is kept below as the parity reference
the leaf-wise lowering is tested against, equal to the bit.
"""

from jax import lax

from chainermn_tpu.communicators import _packing
from chainermn_tpu.communicators.mesh_communicator_base import MeshCommunicator


class FlatCommunicator(MeshCommunicator):
    flavor = "flat"

    def _legacy_allreduce_grad_traced(self, grads):
        # pre-planner lowering, kept as the census-parity reference
        buffers, meta = _packing.pack(grads)
        ax = self._axis_arg()
        buffers = [lax.psum(b, ax) for b in buffers]
        return _packing.unpack(buffers, meta, scale=1.0 / self.size)

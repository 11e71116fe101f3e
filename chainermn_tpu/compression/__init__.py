"""Gradient compression subsystem (beyond-reference extension).

Pluggable wire codecs for the three gradient exchange seams —
``Communicator.allreduce_grad(compressor=...)``,
``create_multi_node_optimizer(compression=...)``, and
``fsdp_init(bucket_compressors=...)`` — generalizing the anaruse fork's
``allreduce_grad_dtype`` cast (now exactly ``NoCompression(wire_dtype)``)
into int8/fp8 quantization with error feedback.  See ``base.py`` for
the protocol, ``quantize.py`` for the codecs, ``error_feedback.py`` for
the checkpointed EF state, and ``docs/compression.md`` for when to
reach for which wire.
"""

from chainermn_tpu.compression.base import (
    Compressor,
    NoCompression,
    available_compressors,
    register_compressor,
    resolve_compressor,
)
from chainermn_tpu.compression.error_feedback import (
    EF_VERSION,
    CompressionState,
    compression_layout,
    init_state,
    iter_compression_states,
)
from chainermn_tpu.compression.quantize import (
    Fp8Compressor,
    Int8Compressor,
    is_quantizing,
    wire_bits_per_param,
)

__all__ = [
    "CompressionState",
    "Compressor",
    "EF_VERSION",
    "Fp8Compressor",
    "Int8Compressor",
    "NoCompression",
    "available_compressors",
    "compression_layout",
    "init_state",
    "is_quantizing",
    "iter_compression_states",
    "register_compressor",
    "resolve_compressor",
    "wire_bits_per_param",
]

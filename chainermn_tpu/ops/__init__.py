"""Native/fused TPU kernels (Pallas) — the reference's CUDA-kernel role
(SURVEY.md §2.3)."""

from chainermn_tpu.ops.flash_attention import (
    flash_attention,
    flash_tile_census,
)
from chainermn_tpu.ops.fused_norm import (
    FusedBatchNormAct,
    fused_norm,
    fused_norm_reference,
    fused_norm_traffic_bytes,
    resnet_bn_traffic_bytes,
)
from chainermn_tpu.ops.grouped_matmul import (
    grouped_matmul,
    grouped_matmul_census,
)
from chainermn_tpu.ops.qk_norm_rope import qk_norm_rope

__all__ = [
    "flash_attention",
    "flash_tile_census",
    "fused_norm",
    "fused_norm_reference",
    "grouped_matmul",
    "grouped_matmul_census",
    "qk_norm_rope",
    "FusedBatchNormAct",
    "fused_norm_traffic_bytes",
    "resnet_bn_traffic_bytes",
]

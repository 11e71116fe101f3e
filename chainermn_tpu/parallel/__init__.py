"""Mesh topology and parallelism strategies.

``topology`` — the rank/axis bookkeeping every communicator builds on
(the reference's 〔_communication_utility.py〕 role).  ``sequence`` —
sequence/context parallelism (ring + Ulysses attention), a beyond-reference
extension for long-context training (SURVEY.md §5.7 records the reference
has none).
"""

from chainermn_tpu.parallel.topology import (
    DATA_AXES,
    INTER_AXIS,
    INTRA_AXIS,
    Topology,
    init_topology,
    topology_from_mesh,
)
from chainermn_tpu.parallel.sequence import (
    attention,
    ring_attention,
    ulysses_attention,
)
from chainermn_tpu.parallel.pipeline import (
    make_pipeline_fn,
    make_pipeline_train_fn,
    pipeline_1f1b,
    pipeline_apply,
)
from chainermn_tpu.parallel.tensor import (
    ColumnParallelDense,
    RowParallelDense,
    TensorParallelMLP,
)
from chainermn_tpu.parallel.expert import (
    ExpertParallelMLP,
    dropless_moe,
    moe_apply,
    moe_plan_topology,
)
from chainermn_tpu.parallel.buckets import (
    BucketAssignment,
    describe_buckets,
    partition_buckets,
)
from chainermn_tpu.parallel.fsdp import (
    BucketLayout,
    FsdpMeta,
    FsdpState,
    fsdp_full_params,
    fsdp_init,
    make_fsdp_train_step,
)

__all__ = [
    "BucketAssignment",
    "BucketLayout",
    "ColumnParallelDense",
    "ExpertParallelMLP",
    "RowParallelDense",
    "TensorParallelMLP",
    "describe_buckets",
    "dropless_moe",
    "moe_apply",
    "moe_plan_topology",
    "partition_buckets",
    "DATA_AXES",
    "FsdpMeta",
    "FsdpState",
    "INTER_AXIS",
    "INTRA_AXIS",
    "fsdp_full_params",
    "fsdp_init",
    "make_fsdp_train_step",
    "Topology",
    "attention",
    "init_topology",
    "make_pipeline_fn",
    "make_pipeline_train_fn",
    "pipeline_1f1b",
    "pipeline_apply",
    "ring_attention",
    "topology_from_mesh",
    "ulysses_attention",
]

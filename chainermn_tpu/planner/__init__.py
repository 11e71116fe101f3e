"""Hierarchical collective planner (ROADMAP item 1, HiCCL direction).

The decomposition IS the communicator spec: a :class:`Plan` is a
serializable sequence of collective :class:`Stage` records over a
declared :class:`PlanTopology`; :func:`execute_plan` is the ONE compiler
lowering any plan to traced primitives; :func:`flavor_plan` gives the
seven legacy flavors as fixed plans; :class:`PlanTable` +
:func:`autotune_from_rows` select per-message-size plans from
``bench_allreduce --sweep`` data for ``create_communicator("auto")``.

The global scheduler (ROADMAP item 4) lifts the cost model from one
plan to the SET of plans in flight per step: :class:`StepWorkload` +
:func:`workload_modeled_time_s` price concurrent plans under fair link
sharing, :func:`jointly_tune` picks every slot's plan together, and
:class:`JointPlanTable` carries the decision keyed by workload
signature (``planner/schedule.py``).

See docs/collective_planner.md.
"""

from chainermn_tpu.planner.autotune import (
    BUCKET_EDGES,
    FIXED_PLAN_NAMES,
    PLAN_TABLE_SCHEMA,
    PlanTable,
    SWEEP_SCHEMA,
    autotune_from_rows,
    size_bucket,
    validate_sweep_rows,
)
from chainermn_tpu.planner.compiler import (
    LINK_CLASS,
    execute_alltoall,
    execute_plan,
    init_plan_compression_states,
    plan_census_kinds,
    plan_compressed_hops,
    plan_dcn_bytes,
    plan_group_lengths,
    plan_link_bytes,
    plan_modeled_time_s,
    plan_needs_buffer,
    plan_stage_lengths,
    plan_wire_bytes,
    plan_wire_dtypes,
    validate_link_gbps,
)
from chainermn_tpu.planner.schedule import (
    JOINT_TABLE_SCHEMA,
    JointPlanTable,
    StepWorkload,
    WORKLOAD_SCHEMA,
    WORKLOAD_TAG,
    WorkloadSchedule,
    WorkloadSlot,
    clear_plan_slots,
    default_candidates,
    derated_link_gbps,
    get_slot_plan,
    independent_plans,
    jointly_tune,
    plan_workload_signature,
    reconstruct_workload,
    register_plan_slot,
    registered_slots,
    resolve_slot_plan,
    set_slot_plan,
    simulate_workload,
    tag_plan,
    untagged_plan_name,
    workload_modeled_time_s,
)
from chainermn_tpu.planner.online import (
    LinkObservations,
    ONLINE_TUNE_SCHEMA,
    OnlineTuner,
    active_plan_table_meta,
    clear_active_plan_table,
    get_active_plan_table,
    plan_table_hash,
    recommend_prefetch_depth,
    set_active_plan_table,
    synthesize_sweep_rows,
)
from chainermn_tpu.planner.ir import (
    Plan,
    PlanError,
    PlanTopology,
    SCOPES,
    STAGE_OPS,
    Stage,
    StageGroup,
    load_plan,
)
from chainermn_tpu.planner.plans import (
    FLAVOR_NAMES,
    STRIPE_RATIOS,
    alltoall_plans,
    broadcast_plans,
    candidate_plans,
    flavor_plan,
    multicast_plan,
    striped_plan,
)

__all__ = [
    "BUCKET_EDGES",
    "FIXED_PLAN_NAMES",
    "FLAVOR_NAMES",
    "JOINT_TABLE_SCHEMA",
    "JointPlanTable",
    "LINK_CLASS",
    "LinkObservations",
    "ONLINE_TUNE_SCHEMA",
    "OnlineTuner",
    "PLAN_TABLE_SCHEMA",
    "Plan",
    "PlanError",
    "PlanTable",
    "PlanTopology",
    "SCOPES",
    "STAGE_OPS",
    "STRIPE_RATIOS",
    "SWEEP_SCHEMA",
    "Stage",
    "StageGroup",
    "StepWorkload",
    "WORKLOAD_SCHEMA",
    "WORKLOAD_TAG",
    "WorkloadSchedule",
    "WorkloadSlot",
    "active_plan_table_meta",
    "alltoall_plans",
    "autotune_from_rows",
    "broadcast_plans",
    "clear_active_plan_table",
    "clear_plan_slots",
    "candidate_plans",
    "default_candidates",
    "derated_link_gbps",
    "execute_alltoall",
    "execute_plan",
    "flavor_plan",
    "get_active_plan_table",
    "get_slot_plan",
    "independent_plans",
    "init_plan_compression_states",
    "jointly_tune",
    "load_plan",
    "multicast_plan",
    "plan_census_kinds",
    "plan_compressed_hops",
    "plan_dcn_bytes",
    "plan_group_lengths",
    "plan_link_bytes",
    "plan_modeled_time_s",
    "plan_needs_buffer",
    "plan_stage_lengths",
    "plan_wire_bytes",
    "plan_table_hash",
    "plan_wire_dtypes",
    "plan_workload_signature",
    "recommend_prefetch_depth",
    "reconstruct_workload",
    "register_plan_slot",
    "registered_slots",
    "resolve_slot_plan",
    "set_active_plan_table",
    "set_slot_plan",
    "simulate_workload",
    "size_bucket",
    "striped_plan",
    "synthesize_sweep_rows",
    "tag_plan",
    "untagged_plan_name",
    "validate_link_gbps",
    "validate_sweep_rows",
    "workload_modeled_time_s",
]

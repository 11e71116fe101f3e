"""Auto communicator — the tuned flavor.

``create_communicator("auto", plan_table=...)`` routes every
``allreduce_grad`` through the plan the autotuned table selects for this
(topology, gradient dtype, packed byte size) — the planner's answer to
the fixed zoo: instead of the user picking a flavor once, the table
picks the measured-fastest decomposition per message-size bucket
(``chainermn_tpu/planner/autotune.py``; tuned from
``bench_allreduce.py --sweep`` rows).

Message size is static at trace time (gradient shapes are known), so
plan selection happens in Python during tracing — different step
functions/bucket sizes compile to different decompositions with zero
runtime dispatch cost, and retracing on a new tree shape re-selects.

With no table (or a table miss) the flat plan runs — the generic
single-all-reduce decomposition that is legal on every topology.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.communicators.mesh_communicator_base import MeshCommunicator
from chainermn_tpu.planner.autotune import PlanTable
from chainermn_tpu.planner.ir import Plan
from chainermn_tpu.planner.plans import flavor_plan


class AutoCommunicator(MeshCommunicator):
    flavor = "auto"

    def __init__(self, *args,
                 plan_table: Union[None, str, dict, PlanTable] = None,
                 **kwargs):
        super().__init__(*args, **kwargs)
        if plan_table is None:
            self.plan_table = PlanTable()
        elif isinstance(plan_table, PlanTable):
            self.plan_table = plan_table
        elif isinstance(plan_table, dict):
            self.plan_table = PlanTable.from_dict(plan_table)
        else:
            self.plan_table = PlanTable.load(plan_table)

    def swap_plan_table(self, plan_table: Union[dict, PlanTable]) -> None:
        """Hot-swap the plan table (the online tuner's step-boundary
        apply).  Selection is trace-time, so the swap is the assignment
        plus dropping this communicator's cached SPMD programs — the
        next dispatch retraces and ``plan_for`` re-selects against the
        new table.  Callers holding their own ``jax.jit`` step (e.g.
        ``make_train_step``'s) must drop that cache too
        (``step_fn.clear_cache()``); ``MetricsReport`` does both."""
        self.plan_table = plan_table if isinstance(plan_table, PlanTable) \
            else PlanTable.from_dict(plan_table)
        cache = getattr(self, "_jit_cache", None)
        if cache is not None:
            cache.clear()

    def plan(self) -> Plan:
        """The fallback plan (table-independent); per-message selection
        happens in :meth:`plan_for`."""
        return flavor_plan("flat")

    def plan_for(self, nbytes: int, dtype) -> Plan:
        """Tuned plan for a packed payload of ``nbytes`` of ``dtype`` on
        this communicator's topology (fallback: the flat plan)."""
        found = self.plan_table.lookup(self.plan_topology(),
                                       np.dtype(dtype).name, int(nbytes))
        return found if found is not None else self.plan()

    def _allreduce_grad_traced(self, grads, like=None):
        from chainermn_tpu.planner.compiler import execute_plan
        from chainermn_tpu.planner.schedule import register_plan_slot
        leaves = jax.tree.leaves(grads)
        nbytes = sum(int(np.prod(jnp.shape(l)) or 1)
                     * jnp.dtype(l.dtype).itemsize for l in leaves)
        # key the lookup on the dominant gradient dtype (by bytes)
        by_dtype: dict = {}
        for l in leaves:
            name = np.dtype(l.dtype).name
            by_dtype[name] = by_dtype.get(name, 0) + \
                int(np.prod(jnp.shape(l)) or 1) * jnp.dtype(l.dtype).itemsize
        dtype = max(by_dtype, key=lambda k: by_dtype[k]) if by_dtype \
            else "float32"
        # announce the in-flight gradient allreduce to the global
        # scheduler (trace time — shapes are static), so a joint retune
        # can re-price it against whatever else shares the links; its
        # compiled plan stages show up in occupancy timelines under
        # "plan:<scope>" (or "fsdp"/"collective" on pre-planner paths)
        register_plan_slot("allreduce", nbytes=nbytes, dtype=dtype,
                           op="all-reduce",
                           owners=("plan:", "fsdp", "collective"))
        return execute_plan(self.plan_for(nbytes, dtype), self, grads,
                            like=like)

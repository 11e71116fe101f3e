"""Multi-node evaluator.

Reference being rebuilt (path unverified, SURVEY.md provenance):
``create_multi_node_evaluator(evaluator, comm)`` in
〔chainermn/extensions/__init__.py〕 — dynamically subclasses the wrapped
evaluator so ``evaluate()`` runs on the local validation shard and then
**allreduce-averages the observation dict** across ranks; every rank reports
global validation metrics.

Two aggregation levels here, matching the two-level world:

* device level — :func:`make_eval_fn` builds a jitted SPMD eval step whose
  metrics are psum-averaged over the mesh (each device evaluates its shard
  of the batch);
* host level — :func:`create_multi_node_evaluator` wraps an evaluator so the
  per-host result dict is mean-reduced over the DCN control plane (the
  reference's observation-dict allreduce).
"""

from __future__ import annotations

from typing import Callable

import jax

from jax.sharding import PartitionSpec as P


def make_eval_fn(communicator, metrics_fn: Callable,
                 with_model_state: bool = False):
    """Jitted SPMD evaluation step.

    ``metrics_fn(params, local_batch) -> dict of scalars`` runs per device on
    its batch shard; the returned dict is psum-averaged across the mesh.

    ``with_model_state=True`` adds a device-local mutable-state slot
    (flax ``batch_stats`` — stacked [size, ...] like the training step's,
    see ``init_model_state``): ``metrics_fn(params, state, batch)``; each
    device evaluates with ITS running statistics, the reference's
    local-BN posture (sync beforehand with ``AllreducePersistent`` when a
    globally-consistent eval is wanted).
    """
    comm = communicator

    if with_model_state:
        def eval_step(params, state, batch):
            state = jax.tree.map(lambda a: a.squeeze(0), state)
            m = metrics_fn(params, state, batch)
            return comm.allreduce(m, "mean")

        mapped = jax.shard_map(
            eval_step, mesh=comm.mesh,
            in_specs=(P(), P(comm.data_axes), P(comm.data_axes)),
            out_specs=P())
        return jax.jit(mapped)

    def eval_step(params, batch):
        m = metrics_fn(params, batch)
        return comm.allreduce(m, "mean")

    mapped = jax.shard_map(
        eval_step, mesh=comm.mesh,
        in_specs=(P(), P(comm.data_axes)), out_specs=P())
    return jax.jit(mapped)


def create_multi_node_evaluator(actual_evaluator, communicator):
    """Wrap an evaluator so ``evaluate()`` returns globally averaged metrics.

    The wrapped object keeps its class behavior (the reference does this by
    dynamic subclassing; here we subclass at runtime the same way) — only
    ``evaluate`` is overridden to allreduce the result dict across hosts.
    """
    comm = communicator
    base = type(actual_evaluator)

    class _MultiNodeEvaluator(base):
        def evaluate(self, *args, **kwargs):
            local = base.evaluate(self, *args, **kwargs)
            summed = comm.allreduce_obj(local, op="sum")
            return {k: v / comm.host_size for k, v in summed.items()}

    actual_evaluator.__class__ = _MultiNodeEvaluator
    return actual_evaluator

"""SPMD micro-batch pipeline parallelism (GPipe-style schedule).

**Beyond-reference extension.** The reference's model parallelism
(``MultiNodeChainList``, SURVEY.md §2.4) keeps exactly ONE activation in
flight — a pipeline of depth 1, stages idle while their neighbors work.
This module adds the standard micro-batch schedule on top of the same
mesh machinery: split the batch into M micro-batches and keep all S
stages busy after the (S-1)-tick fill bubble — utilization M/(M+S-1).

TPU-native shape: the schedule is a single ``lax.scan`` over
S + M - 1 ticks inside ``shard_map``; every tick, each device runs ITS
stage on the activation it holds and ``ppermute``-s the result one hop to
the next stage — nearest-neighbor traffic that maps directly onto the ICI
torus.  All stages execute the same ``stage_fn`` (homogeneous-stage SPMD
pipelining, the form XLA compiles to one program); heterogeneous chains
stay on ``MultiNodeChainList``.

Differentiable end to end: the backward of the scan re-runs the schedule
reversed (``ppermute`` transposes to the opposite shift), which is exactly
the reference-free derivation of pipeline backprop.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.utils import pvary


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x,
    axis_name,
    *,
    collect: str = "all_gather",
):
    """Run a homogeneous S-stage pipeline over micro-batches, SPMD.

    Per device (inside ``shard_map`` with ``axis_name`` bound):

    - ``stage_params`` — THIS device's stage parameters (device-varying
      pytree; shard a stacked [S, ...] tree over the pipeline axis).
    - ``x`` — the full micro-batch stack [M, mb, ...], same on every
      device (replicated in_spec).
    - ``stage_fn(params, activation) -> activation`` — one stage.

    Returns the last stage's outputs [M, mb, ...] on every device
    (``collect="all_gather"``), or zeros everywhere but the last stage
    (``collect="last"`` — cheaper when only the final stage computes the
    loss).

    Schedule: tick t feeds micro-batch t into stage 0; stage s runs
    micro-batch t - s at tick t; outputs emerge at ticks S-1 .. S+M-2.
    """
    if collect not in ("all_gather", "last"):
        raise ValueError(f"collect must be 'all_gather' or 'last', "
                         f"got {collect!r}")
    size = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    m = x.shape[0]
    ticks = size + m - 1

    x = pvary(x, axis_name)
    zero_act = jnp.zeros_like(x[0])

    def tick(act, t):
        # stage 0 ingests micro-batch t (clamped; invalid ticks produce
        # bubble values that never reach a collected output)
        fed = lax.dynamic_index_in_dim(x, jnp.clip(t, 0, m - 1), 0,
                                       keepdims=False)
        inp = jnp.where(me == 0, fed, act)
        y = stage_fn(stage_params, inp)
        # shift one hop toward the next stage; stage 0 receives zeros
        # (it reads from x), the last stage's output leaves the ring here
        # and is collected from the scan's per-tick outputs instead.
        nxt = lax.ppermute(y, axis_name,
                           perm=[(i, i + 1) for i in range(size - 1)])
        return nxt, y

    _, ys = lax.scan(tick, zero_act, jnp.arange(ticks))
    # ys: [ticks, mb, ...]; on the LAST stage, ticks S-1 .. S+M-2 hold the
    # pipeline outputs for micro-batches 0 .. M-1.
    outs = lax.dynamic_slice_in_dim(ys, size - 1, m, axis=0)
    if collect == "last":
        return jnp.where(me == size - 1, outs, jnp.zeros_like(outs))
    # broadcast the last stage's outputs to every device: zero elsewhere,
    # then sum around the ring.  A masked psum moves ~2x the payload
    # bytes per device INDEPENDENT of pipeline size (ring allreduce), and
    # any true broadcast of the full stack costs >= payload per link too
    # (log-hop doubling: log2(S) x payload) — so psum is within 2x of
    # optimal at every S, and S-invariant.  The real saving when the
    # stack is big is collect="last" (no broadcast at all; compute the
    # loss on the final stage and psum the scalar).
    masked = jnp.where(me == size - 1, outs, jnp.zeros_like(outs))
    return lax.psum(masked, axis_name)


def pipeline_1f1b(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    x,
    targets,
    axis_name,
):
    """1F1B pipeline schedule: returns ``(mean_loss, stage_grads)``.

    Unlike :func:`pipeline_apply` (GPipe: all forwards, then autodiff
    replays the whole schedule backward, saving one residual set per
    tick — O(M + S) activation memory), 1F1B interleaves each stage's
    backward with later microbatches' forwards.  The in-flight window per
    stage is bounded by the schedule (≤ 2S − 1 microbatches), so the
    stored-state high-water-mark is **O(S), independent of M** — the
    property that makes long microbatch streams trainable.

    Mechanics (lockstep SPMD, one `lax.scan` over M + 2S − 1 ticks):

    - tick ``t``, stage ``s`` runs the FORWARD of microbatch ``i = t − s``
      (when 0 ≤ i < M), storing the stage INPUT in a ring buffer of
      2S slots and shipping the output one hop forward;
    - the BACKWARD of microbatch ``j = t − S − (S−1−s)`` recomputes the
      stage forward from the stored input via ``jax.vjp`` (per-stage
      activation checkpointing — the standard 1F1B memory/compute
      trade), seeds it with the cotangent ppermuted from stage ``s+1``
      (or with d(loss)/dy on the last stage, where ``loss_fn(y, target)``
      is folded into the same vjp), accumulates parameter gradients,
      and ships d(input) one hop backward.

    Bubble slots still execute (lockstep SPMD cannot skip per-device
    work — a device-varying `cond` lowers to `select`); their outputs
    are masked out of every accumulator.

    ``stage_fn(params, a) -> a`` must preserve the activation shape
    (homogeneous pipeline, as in :func:`pipeline_apply`); ``loss_fn(y,
    target) -> scalar`` is the per-microbatch loss.  Returns the mean
    loss over microbatches (replicated via a scalar psum) and THIS
    device's parameter gradients of that mean.
    """
    size = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    m = x.shape[0]
    if targets.shape[0] != m:
        raise ValueError(f"targets leading dim {targets.shape[0]} != "
                         f"microbatch count {m}")
    buf = 2 * size  # in-flight bound; +1 scratch slot for bubble writes

    x = pvary(x, axis_name)
    targets = pvary(targets, axis_name)
    zero_act = jnp.zeros_like(x[0])
    is_last = me == size - 1

    def fwd_and_loss(p, a, j):
        y = stage_fn(p, a)
        tj = lax.dynamic_index_in_dim(targets, jnp.clip(j, 0, m - 1), 0,
                                      keepdims=False)
        return y, loss_fn(y, tj).astype(jnp.float32)

    def tick(carry, t):
        fwd_act, bwd_cot, inbuf, gacc, lacc = carry

        # ---- forward slot: microbatch i = t - me ----
        i = t - me
        f_valid = (i >= 0) & (i < m)
        xi = lax.dynamic_index_in_dim(x, jnp.clip(i, 0, m - 1), 0,
                                      keepdims=False)
        inp = jnp.where(me == 0, xi, fwd_act)
        y = stage_fn(stage_params, inp)
        widx = jnp.where(f_valid, jnp.clip(i, 0, m - 1) % buf, buf)
        inbuf = lax.dynamic_update_index_in_dim(inbuf, inp, widx, 0)
        nxt_fwd = lax.ppermute(y, axis_name,
                               perm=[(k, k + 1) for k in range(size - 1)])

        # ---- backward slot: microbatch j (S ticks behind the fwd wave,
        # reflected through the last stage) ----
        j = t - size - (size - 1 - me)
        b_valid = (j >= 0) & (j < m)
        jslot = jnp.where(b_valid, jnp.clip(j, 0, m - 1) % buf, buf)
        saved_in = lax.dynamic_index_in_dim(inbuf, jslot, 0, keepdims=False)
        (_, lj), pull = jax.vjp(
            lambda p, a: fwd_and_loss(p, a, j), stage_params, saved_in)
        # one pullback serves both roles: the last stage seeds d(loss)=1,
        # inner stages seed d(y)=received cotangent
        g_l = jnp.where(is_last & b_valid, 1.0, 0.0).astype(jnp.float32)
        cot = jnp.where(is_last, jnp.zeros_like(bwd_cot), bwd_cot)
        dp, da = pull((cot, g_l))
        gacc = jax.tree.map(
            lambda g, d: g + jnp.where(b_valid, d, jnp.zeros_like(d)),
            gacc, dp)
        lacc = lacc + jnp.where(is_last & b_valid, lj, 0.0)
        nxt_cot = lax.ppermute(da, axis_name,
                               perm=[(k, k - 1) for k in range(1, size)])
        return (nxt_fwd, nxt_cot, inbuf, gacc, lacc), None

    # every carry component becomes device-varying inside the scan body;
    # pvary the initial values so the carry types are fixed points
    inbuf0 = pvary(jnp.zeros((buf + 1,) + x.shape[1:], x.dtype), axis_name)
    gacc0 = jax.tree.map(
        lambda p: pvary(jnp.zeros_like(p), axis_name), stage_params)
    carry0 = (pvary(zero_act, axis_name), pvary(zero_act, axis_name),
              inbuf0, gacc0, pvary(jnp.float32(0.0), axis_name))
    ticks = m + 2 * size - 1
    (_, _, _, gacc, lacc), _ = lax.scan(tick, carry0, jnp.arange(ticks))
    grads = jax.tree.map(lambda g: g / m, gacc)
    # scalar broadcast: loss lives on the last stage, zeros elsewhere
    loss = lax.psum(lacc / m, axis_name)
    return loss, grads


def make_pipeline_train_fn(
    stage_fn: Callable,
    loss_fn: Callable,
    mesh,
    axis_name: str = "pp",
    *,
    n_microbatches: int,
):
    """Jit-ready 1F1B training step:
    ``fn(stacked_params, batch, targets) -> (loss, stacked_grads)``.

    ``stacked_params`` has leading axis S (one slice per stage, sharded
    over ``axis_name``); ``batch``/``targets`` are [B, ...] global arrays
    with B divisible by ``n_microbatches``.  Gradients come back in the
    same stacked layout, ready for a per-stage optimizer.
    """
    from jax.sharding import PartitionSpec as P

    def fn(stacked_params, batch, targets):
        def body(params_stacked, xb, tb):
            local = jax.tree.map(lambda a: jnp.squeeze(a, 0), params_stacked)
            mb = xb.reshape((n_microbatches, -1) + xb.shape[1:])
            tmb = tb.reshape((n_microbatches, -1) + tb.shape[1:])
            loss, grads = pipeline_1f1b(stage_fn, loss_fn, local, mb, tmb,
                                        axis_name)
            return loss, jax.tree.map(lambda g: g[None], grads)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis_name), P(), P()),
            out_specs=(P(), P(axis_name)))(
                stacked_params, batch, targets)

    return jax.jit(fn)


def make_pipeline_fn(
    stage_fn: Callable,
    mesh,
    axis_name: str = "pp",
    *,
    n_microbatches: int,
):
    """Jit-ready wrapper: returns ``fn(stacked_params, batch) -> out``.

    ``stacked_params`` — pytree with leading axis S (one slice per stage),
    sharded over ``axis_name``.  ``batch`` — [B, ...] global batch,
    B divisible by ``n_microbatches``; replicated to all stages.  The
    output is the last stage's result, replicated (all-gather collect, so
    the replicated out_spec holds).
    """
    from jax.sharding import PartitionSpec as P

    def fn(stacked_params, batch):
        def body(params_stacked, xb):
            local = jax.tree.map(lambda a: jnp.squeeze(a, 0), params_stacked)
            mb = xb.reshape((n_microbatches, -1) + xb.shape[1:])
            out = pipeline_apply(stage_fn, local, mb, axis_name)
            return out.reshape((-1,) + out.shape[2:])

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis_name), P()),
            out_specs=P())(stacked_params, batch)

    return jax.jit(fn)


__all__ = ["make_pipeline_fn", "make_pipeline_train_fn", "pipeline_1f1b",
           "pipeline_apply"]

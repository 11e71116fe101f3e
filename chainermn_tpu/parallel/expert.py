"""Expert parallelism — mixture-of-experts with all-to-all token routing.

**Beyond-reference extension** (SURVEY.md §2.4: the reference has no
EP/MoE).  The standard recipe on a mesh axis ``ep`` (P devices, E experts,
E a multiple of P — each device hosts E/P experts):

1. every device routes its local tokens: top-k softmax gate over the E
   experts (k=1 Switch-style, k=2 GShard-style with renormalized combine
   weights);
2. capacity-bucketed dispatch: each device builds one fixed-size buffer
   per expert (capacity C tokens — static shapes for XLA).  Slots are
   assigned choice-major (all first choices before any second choice),
   so under pressure top-1 traffic wins buckets;
3. one ``all_to_all`` ships each expert its buffers; the local experts
   (batched MLPs) process them; the inverse ``all_to_all`` returns
   outputs;
4. outputs are combined back into token order, weighted by the gate
   probabilities.  Tokens whose every choice overflowed pass through
   unchanged (residual).

Training-grade bookkeeping (``return_stats=True`` / ``with_stats=True``):

* ``aux_loss`` — the Switch/GShard load-balancing loss
  ``E * sum_e load_e * mean_prob_e`` (globally pmean-ed), to be added to
  the task loss with a small weight (~1e-2); minimized exactly when
  routing is uniform;
* ``overflow_fraction`` — fraction of (token, choice) dispatch attempts
  dropped by capacity.  A collapsed router shows up here immediately
  instead of silently degrading the layer to identity;
* ``expert_load`` — [E] global fraction of top-1 traffic per expert.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


def moe_plan_topology(axis_name):
    """The :class:`~chainermn_tpu.planner.ir.PlanTopology` of the MoE
    exchange axes: one axis per mesh axis name, sizes read from the
    bound SPMD region (static at trace time).  ``axis_name`` may be one
    name (flat ep axis) or an (inter, intra) tuple — the LAST name is
    the ICI axis, matching the planner convention."""
    from chainermn_tpu.planner.ir import PlanTopology
    names = (tuple(axis_name) if isinstance(axis_name, (tuple, list))
             else (axis_name,))
    return PlanTopology(axes=tuple(
        (str(n), int(jax.lax.axis_size(n))) for n in names))


def moe_apply(expert_fn: Callable, gate_logits, x, axis_name,
              capacity: Optional[int] = None, top_k: int = 1,
              num_experts: Optional[int] = None,
              normalize_gates: Optional[bool] = None,
              return_stats: bool = False,
              plan=None, plan_topology=None, plan_obs=None):
    """Route local tokens [N, D] to mesh-distributed experts; return [N, D].

    ``gate_logits``: [N, E].  E defaults to the gate width and must be a
    multiple of the axis size P; each device hosts E/P experts.

    ``expert_fn`` applies THIS device's expert(s) to their received
    buffers: with one expert per device it gets ``[P*C, D]`` (the
    original contract); with E/P > 1 it gets ``[E/P, P*C, D]`` and must
    apply expert ``i`` to row ``i``.

    ``capacity`` is the per-expert bucket size, default ``2 * N * k / E``
    per device; tokens past it fall through the residual path.
    ``normalize_gates`` renormalizes the combine weights over the k
    selected experts (default: off for k=1 — Switch scales by the raw
    top prob — and on for k>1, the GShard convention).

    With ``return_stats=True`` returns ``(y, stats)`` — see module
    docstring for the stats contract.

    ``plan`` routes the two exchanges through the collective planner
    (:func:`~chainermn_tpu.planner.compiler.execute_alltoall`): an
    all-to-all :class:`~chainermn_tpu.planner.ir.Plan` from the
    ``alltoall_plans`` zoo — flat (bit-exact with the default raw
    ``lax.all_to_all`` path), hierarchical ICI+DCN, or narrow-DCN-wire.
    ``axis_name`` may then be an (inter, intra) tuple of mesh axes;
    ``plan_topology`` overrides the derived topology and ``plan_obs``
    (``observability.spans.get_plan_obs()``) turns on per-hop
    ``plan_stage`` spans.  ``plan=None`` is today's raw path, untouched.
    """
    p = jax.lax.axis_size(axis_name)
    n, d = x.shape
    e = int(num_experts) if num_experts is not None else gate_logits.shape[-1]
    if gate_logits.shape[-1] != e:
        raise ValueError(
            f"gate_logits has {gate_logits.shape[-1]} experts but "
            f"num_experts={e}")
    if e % p:
        raise ValueError(
            f"num_experts ({e}) must be a multiple of the '{axis_name}' "
            f"axis size ({p}) so every device hosts E/P experts; a "
            f"mismatch would silently misroute via clamped indices")
    epd = e // p
    if not 1 <= top_k <= e:
        raise ValueError(f"top_k={top_k} out of range for {e} experts")
    c = capacity if capacity is not None else max(1, 2 * top_k * n // e)
    if normalize_gates is None:
        normalize_gates = top_k > 1

    gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    topv, topi = lax.top_k(gates, top_k)                  # [N, K]
    combine = topv / topv.sum(-1, keepdims=True) if normalize_gates else topv

    # capacity slots, choice-major priority: every token's 1st choice is
    # slotted before any token's 2nd choice
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.int32)     # [N, K, E]
    flat = onehot.transpose(1, 0, 2).reshape(top_k * n, e)
    slot_flat = jnp.cumsum(flat, axis=0) - 1
    slot = (slot_flat * flat).sum(-1).reshape(top_k, n).T  # [N, K]
    keep = slot < c
    slot_safe = jnp.where(keep, slot, 0)

    # scatter tokens into [E, C, D] send buffers (dropped choices add 0)
    send = jnp.zeros((e, c, d), x.dtype)
    send = send.at[topi, slot_safe].add(
        jnp.where(keep[..., None], x[:, None, :], jnp.zeros((), x.dtype)))

    # experts are laid out contiguously per owner device, so grouping the
    # E axis as [P, E/P * C] makes all_to_all ship each device its block
    if plan is None:
        exchange = lambda b: lax.all_to_all(
            b, axis_name, split_axis=0, concat_axis=0, tiled=True)
    else:
        from chainermn_tpu.planner.compiler import execute_alltoall
        from chainermn_tpu.planner.schedule import (register_plan_slot,
                                                    resolve_slot_plan)
        topo = (plan_topology if plan_topology is not None
                else moe_plan_topology(axis_name))
        # global-scheduler seam (trace time): announce the exchange
        # payload as the "moe" plan slot and honor a jointly-tuned
        # override when the online tuner installed one — the dispatch
        # and combine exchanges are one slot (same buffer both ways)
        register_plan_slot(
            "moe", nbytes=e * c * d * jnp.dtype(x.dtype).itemsize,
            dtype=jnp.dtype(x.dtype).name, op="all-to-all",
            owners=("moe",))
        plan = resolve_slot_plan("moe", plan)
        exchange = lambda b: execute_alltoall(plan, topo, b, pobs=plan_obs)
    recv = exchange(send.reshape(p, epd * c, d))
    recv = recv.reshape(p, epd, c, d).transpose(1, 0, 2, 3)  # [E/P, P, C, D]
    if epd == 1:
        out = expert_fn(recv.reshape(p * c, d))
    else:
        out = expert_fn(recv.reshape(epd, p * c, d))
    out = out.reshape(epd, p, c, d).transpose(1, 0, 2, 3)
    back = exchange(out.reshape(p, epd * c, d))
    back = back.reshape(e, c, d)

    # combine: sum kept choices weighted by gate prob; all-dropped tokens
    # pass through (residual)
    routed = back[topi, slot_safe]                        # [N, K, D]
    weight = (keep * combine).astype(x.dtype)[..., None]
    y = (routed * weight).sum(axis=1)
    y = jnp.where(keep.any(-1)[:, None], y, x)
    if not return_stats:
        return y

    probs_mean = lax.pmean(gates.mean(axis=0), axis_name)         # [E]
    load = lax.pmean(
        jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32).mean(0), axis_name)
    stats = {
        "aux_loss": e * (probs_mean * load).sum(),
        "overflow_fraction": 1.0 - lax.pmean(
            keep.astype(jnp.float32).mean(), axis_name),
        "expert_load": load,
    }
    return y, stats


class ExpertParallelMLP(nn.Module):
    """Top-k MoE layer: router + E distinct expert MLPs over the mesh.

    Apply inside ``shard_map`` with tokens sharded [B*T/P, D] on
    ``axis_name`` and the parameters REPLICATED (the usual ``P()`` spec).
    Expert parameters are global ``[E, ...]`` stacks; each device slices
    out its own ``E/P`` experts by ``axis_index`` at apply time, so the
    experts are genuinely distinct weights.  In the backward, each
    device's gradient is zero outside its slice and shard_map's transpose
    psums the slices into the correct per-expert gradients — a plain
    replicated optimizer therefore trains E diverging experts with no
    special handling (device-local sharding of the stacks is a memory
    optimization the caller can add via NamedSharding, not a correctness
    requirement).

    ``with_stats=True`` makes ``__call__`` return ``(y, stats)`` so
    training code can add ``aux_weight * stats["aux_loss"]`` to its loss
    and monitor ``overflow_fraction`` for routing collapse.
    """

    hidden: int
    axis_name: Any = "ep"
    capacity: Optional[int] = None
    dtype: Any = jnp.float32
    top_k: int = 1
    num_experts: Optional[int] = None   # default: one expert per device
    with_stats: bool = False
    #: all-to-all Plan routing the dispatch/combine exchanges through
    #: the collective planner (None = the raw flat path, bit-exact)
    plan: Any = None

    @nn.compact
    def __call__(self, x):
        p = jax.lax.axis_size(self.axis_name)
        e = self.num_experts if self.num_experts is not None else p
        if e % p:
            raise ValueError(f"num_experts ({e}) must be a multiple of the "
                             f"'{self.axis_name}' axis size ({p})")
        epd = e // p
        d = x.shape[-1]
        router = nn.Dense(e, dtype=jnp.float32, param_dtype=jnp.float32,
                          name="router")
        init = nn.initializers.lecun_normal()
        up_k = self.param("up_kernel", init, (e, d, self.hidden),
                          jnp.float32)
        up_b = self.param("up_bias", nn.initializers.zeros_init(),
                          (e, self.hidden), jnp.float32)
        down_k = self.param("down_kernel", init, (e, self.hidden, d),
                            jnp.float32)
        down_b = self.param("down_bias", nn.initializers.zeros_init(),
                            (e, d), jnp.float32)

        # this device's expert slice (global expert ids [me*epd, (me+1)*epd))
        me = lax.axis_index(self.axis_name)
        mine = lambda t: lax.dynamic_slice_in_dim(t, me * epd, epd, axis=0)
        up_kl, up_bl = mine(up_k), mine(up_b)
        down_kl, down_bl = mine(down_k), mine(down_b)

        def expert_fn(tokens):
            if epd == 1:
                h = nn.gelu(jnp.dot(tokens, up_kl[0].astype(self.dtype))
                            + up_bl[0].astype(self.dtype))
                return (jnp.dot(h, down_kl[0].astype(self.dtype))
                        + down_bl[0].astype(self.dtype))
            h = nn.gelu(
                jnp.einsum("ead,edh->eah", tokens, up_kl.astype(self.dtype))
                + up_bl[:, None].astype(self.dtype))
            return (jnp.einsum("eah,ehd->ead", h, down_kl.astype(self.dtype))
                    + down_bl[:, None].astype(self.dtype))

        plan_obs = None
        if self.plan is not None:
            from chainermn_tpu.observability.spans import get_plan_obs
            plan_obs = get_plan_obs()
        shape = x.shape
        flat = x.reshape(-1, d)
        res = moe_apply(expert_fn, router(flat), flat, self.axis_name,
                        capacity=self.capacity, top_k=self.top_k,
                        num_experts=e, return_stats=self.with_stats,
                        plan=self.plan, plan_obs=plan_obs)
        if self.with_stats:
            y, stats = res
            return y.reshape(shape), stats
        return res.reshape(shape)


__all__ = ["ExpertParallelMLP", "moe_apply", "moe_plan_topology"]

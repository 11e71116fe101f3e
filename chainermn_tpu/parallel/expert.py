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

**The dropless path** (:func:`dropless_moe`, beside the capacity path and
sharing nothing with it) is for a layer that is TOLD WHICH EXPERTS IT HOLDS:
it routes over all ``num_experts`` of the model, orders the (token, choice)
pairs by expert, computes its own ``held`` experts' part of the result with
a grouped matrix product (:mod:`chainermn_tpu.ops.grouped_matmul`) over
exactly the rows routed to them — no capacity, no dropped pair, whatever
the imbalance — and leaves out what the absent experts would have added.
Three named parts: :func:`dropless_route`, :func:`dropless_dispatch`,
:func:`dropless_combine`.  Only the sort sees all ``N * K`` pairs: what
carries a feature dimension is bounded by what the held experts get
(:func:`dropless_rows_bound`), with a guarded second pass for a routing
that exceeds it.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

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


# ---------------------------------------------------------------------------
# the dropless path
# ---------------------------------------------------------------------------

class Dispatch(NamedTuple):
    """How the (token, choice) pairs are ordered for the held experts.
    ``order[i]`` is the pair (``token * top_k + choice``) at sorted row i,
    ``inverse`` its inverse permutation; pairs of experts not held sort last.
    ``group_sizes[e]`` rows belong to held expert e; ``is_held`` [N, K].

    The layer's passes each take a WINDOW ``(start, stop)`` of the sorted
    rows: only the rows of a window are ever gathered, computed or summed."""

    order: jax.Array
    inverse: jax.Array
    group_sizes: jax.Array
    is_held: jax.Array


_ROW_TILE = 512     # grouped_matmul's row tile: the bound is whole tiles


def dropless_rows_bound(pairs: int, held_experts: int,
                        num_experts: int) -> int:
    """How many sorted rows the layer's main pass materialises: what an even
    routing sends to ``held_experts`` of ``num_experts``, half as much
    again, in whole row tiles, and never more than the ``pairs`` there are.
    A function of shapes alone: with every expert held it is ``pairs``."""
    even_and_a_half = -(-(pairs * held_experts * 3) // (num_experts * 2))
    return min(pairs, -(-even_and_a_half // _ROW_TILE) * _ROW_TILE)


def dropless_route(router_logits, expert_bias, top_k: int,
                   normalize: bool = True, scaling_factor: float = 1.0):
    """Scores, biased top-k, renormalised weights.

    ``router_logits`` [N, E] over ALL the model's experts.  Scores are
    ``sigmoid`` in float32; the ``top_k`` experts are chosen by ``score +
    expert_bias`` (the bias steers the SELECTION only: it has no gradient
    and does not weigh the result); the weights are the chosen experts' own
    scores, divided by their sum (+1e-6) when ``normalize``, times
    ``scaling_factor``.  Returns ``(chosen [N, K] int32, weights [N, K]
    float32)``."""
    # (a scope's name with a second dot goes on a line of its own:
    # tests/test_named_scopes.py reads every line that opens a scope)
    with jax.named_scope(
            "chainermn.moe.route"):
        scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
        biased = scores if expert_bias is None else (
            scores + expert_bias.astype(jnp.float32))
        _, chosen = lax.top_k(lax.stop_gradient(biased), top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if normalize:
            weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
        return chosen.astype(jnp.int32), weights * scaling_factor


def _window_sizes(group_sizes, window):
    """The part of every group that lies in sorted rows [start, stop)."""
    ends = jnp.cumsum(group_sizes)
    clip = lambda offsets: jnp.clip(offsets, *window)
    return clip(ends) - clip(ends - group_sizes)


def _window_tokens(dispatch: Dispatch, window):
    """The token of every sorted row of the window."""
    return dispatch.order[slice(*window)] // dispatch.is_held.shape[1]


def _window_weights(weights, dispatch: Dispatch, window):
    """[rows] float32: the weight of every window row's pair (``weights``
    [N, K]; None: one), and zero for a row outside the held experts' groups
    (held pairs sort first: those are the rows from the groups' sum on)."""
    start, stop = window
    in_groups = jnp.arange(start, stop) < dispatch.group_sizes.sum()
    if weights is None:
        return in_groups.astype(jnp.float32)
    return jnp.where(in_groups,
                     weights.reshape(-1)[dispatch.order[start:stop]], 0.0)


def _token_sums(rows, weights, dispatch: Dispatch, window):
    """Rows -> tokens: ``out[t] = sum over t's pairs in the window of
    weights[pair] * rows[row of pair]``, in float32.  ``rows`` [stop -
    start, D]; ``weights`` [N, K] or None (ones); returns [N, D].  A
    scatter-add of the window's rows into their tokens (on the chip it beat
    gathering every pair's row out of the window: PERF.md, PR 27).  Rows
    outside the groups count nothing, whatever they hold."""
    weighted = rows.astype(jnp.float32) * _window_weights(
        weights, dispatch, window)[:, None]
    return jnp.zeros((dispatch.is_held.shape[0], rows.shape[1]),
                     jnp.float32).at[_window_tokens(dispatch, window)].add(
                         weighted).astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _token_rows(x, dispatch, window):
    """Tokens -> rows: every window row's token row, ``x[token of row]``.
    The derivative is :func:`_token_sums`, where autodiff would scatter-add
    every pair's row, the pairs not held among them."""
    return x[_window_tokens(dispatch, window)]


def _token_rows_fwd(x, dispatch, window):
    return _token_rows(x, dispatch, window), dispatch


def _token_rows_bwd(window, dispatch, g):
    return _token_sums(g, None, dispatch, window), None


_token_rows.defvjp(_token_rows_fwd, _token_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _weighted_token_sums(expert_rows, weights, dispatch, window):
    """:func:`_token_sums` as the layer differentiates it: the rows'
    gradient is the token's, gathered and weighted in one pass over the
    window; the weights' gradient one dot product a window row, then a
    gather of scalars into (token, choice) order."""
    return _token_sums(expert_rows, weights, dispatch, window)


def _weighted_token_sums_fwd(expert_rows, weights, dispatch, window):
    return (_token_sums(expert_rows, weights, dispatch, window),
            (expert_rows, weights, dispatch))


def _weighted_token_sums_bwd(window, residual, g):
    expert_rows, weights, dispatch = residual
    g_rows = g[_window_tokens(dispatch, window)].astype(jnp.float32)
    d_rows = (g_rows * _window_weights(weights, dispatch, window)[:, None]
              ).astype(expert_rows.dtype)
    d_sorted = (expert_rows.astype(jnp.float32) * g_rows).sum(-1)
    # scalars back to (token, choice) order: a pair's row of the window,
    # if its expert is held and the window holds it
    start, stop = window
    slot = dispatch.inverse - start
    inside = (slot >= 0) & (slot < stop - start) & dispatch.is_held.reshape(
        -1)
    d_weights = jnp.where(
        inside, d_sorted[jnp.clip(slot, 0, stop - start - 1)], 0.0)
    d_weights = d_weights.reshape(weights.shape)
    return d_rows, d_weights.astype(weights.dtype), None


_weighted_token_sums.defvjp(_weighted_token_sums_fwd,
                            _weighted_token_sums_bwd)


def dropless_rows(x, dispatch: Dispatch, start: int, stop: int):
    """Sorted rows ``[start, stop)``: each one's token row of ``x`` [N, D]."""
    with jax.named_scope(
            "chainermn.moe.dispatch"):
        return _token_rows(x, dispatch, (start, stop))


def dropless_dispatch(x, chosen, first_expert: int, held_experts: int,
                      bound: Optional[int] = None):
    """Order the pairs by expert.  ``x`` [N, D], ``chosen`` [N, K] expert
    ids.  Pairs whose expert lies in ``[first_expert, first_expert +
    held_experts)`` come first, grouped by expert in token order; the
    others go last and are never computed.  Returns ``(rows [bound, D],
    Dispatch)``, ``bound`` defaulting to all ``N * K``: the first ``bound``
    sorted rows (:func:`dropless_rows` gives any other window).  The
    grouped product reads ``rows`` by the part of ``dispatch.group_sizes``
    below ``bound`` and skips what lies past their sum."""
    with jax.named_scope(
            "chainermn.moe.dispatch"):
        local = chosen.reshape(-1) - first_expert
        is_held = (local >= 0) & (local < held_experts)
        key = jnp.where(is_held, local, held_experts)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        pairs = order.shape[0]
        inverse = jnp.zeros((pairs,), jnp.int32).at[order].set(
            jnp.arange(pairs, dtype=jnp.int32))
        group_sizes = jnp.zeros((held_experts + 1,), jnp.int32).at[key].add(
            1)[:held_experts]
    dispatch = Dispatch(order, inverse, group_sizes,
                        is_held.reshape(chosen.shape))
    return dropless_rows(x, dispatch, 0,
                         pairs if bound is None else bound), dispatch


def dropless_combine(expert_rows, weights, dispatch: Dispatch,
                     start: int = 0):
    """The held experts' weighted sum for every token: ``expert_rows``
    [rows, D], the sorted rows from ``start`` on (zero past the groups) ->
    [N, D], summed in float32 over a token's choices.  A choice whose
    expert is not held, or whose row lies outside the window, adds
    nothing."""
    with jax.named_scope(
            "chainermn.moe.combine"):
        return _weighted_token_sums(
            expert_rows, weights, dispatch,
            (start, start + expert_rows.shape[0]))


def dropless_counters(dispatch: Dispatch, bound: Optional[int] = None):
    """What the routing did, for ``make_train_step(has_aux=True)``: all
    float32, so that the step's report can average them over devices.
    ``bound`` is the main pass's rows (default: all the pairs)."""
    sizes = dispatch.group_sizes.astype(jnp.float32)
    routed_here = dispatch.is_held.sum().astype(jnp.float32)
    bound = dispatch.is_held.size if bound is None else bound
    return {
        "tokens_per_held_expert": sizes,
        "held_share": sizes.sum() / dispatch.is_held.size,
        "load_max_over_mean": sizes.max() / jnp.maximum(sizes.mean(), 1.0),
        # pairs routed to a held expert that no group's rows cover
        "dropped_pairs": routed_here - sizes.sum(),
        # the main pass's rows, and the rows the guarded remainder computed:
        # 0 on every step it did not run
        "rows_bound": jnp.full((), bound, jnp.float32),
        "rows_past_bound": jnp.maximum(sizes.sum() - bound, 0.0),
    }


def _held_part(expert_fn, rows, weights, dispatch, expert_args, start):
    """One pass over the sorted rows from ``start`` on: the experts, then
    the weighted sum into the tokens."""
    window = (start, start + rows.shape[0])
    expert_rows = expert_fn(rows, _window_sizes(dispatch.group_sizes, window),
                            *expert_args)
    return dropless_combine(expert_rows, weights, dispatch, start)


@functools.partial(jax.jit, static_argnames=("expert_fn", "bound"))
def _remainder(x, weights, dispatch, expert_args, *, expert_fn, bound):
    """The held experts' part of the result over the sorted rows from
    ``bound`` on: the main pass again, on the other rows.  It keeps its
    inputs alone and recomputes in the backward.  Jitted so that layers
    with one ``expert_fn`` and equal shapes share its trace and its
    derivative's."""
    def rest(x, weights, dispatch, expert_args):
        rows = dropless_rows(x, dispatch, bound, dispatch.order.shape[0])
        return _held_part(expert_fn, rows, weights, dispatch, expert_args,
                          bound)

    return jax.checkpoint(rest)(x, weights, dispatch, expert_args)


def dropless_moe(x, router_logits, expert_bias, expert_fn: Callable, *,
                 num_experts: int, top_k: int, first_expert: int = 0,
                 held_experts: Optional[int] = None, axis_name=None,
                 normalize: bool = True, scaling_factor: float = 1.0,
                 expert_args=(), remainder_fn: Optional[Callable] = None):
    """The held experts' part of a top-k mixture of experts, droplessly.

    ``x`` [N, D]; ``router_logits`` [N, num_experts]; ``expert_fn(rows [R,
    D], group_sizes [held], *expert_args) -> [R, D]`` applies held expert e
    to the e-th group of rows (a grouped matrix product) and must return
    zeros past the groups.  Returns ``(y [N, D], counters)``.

    **Rows.**  Of the ``N * K`` sorted rows the main pass materialises the
    first ``R`` (:func:`dropless_rows_bound`: what an even routing gives
    the held experts, half as much again) — the gathers, ``expert_fn`` and
    the sums back are all ``R`` rows.  What an uneven routing puts past
    ``R`` goes through the same pass over rows ``[R, N * K)`` under a
    ``lax.cond`` that a normal step does not take (``rows_past_bound``
    counts its rows); it keeps its inputs alone and recomputes in the
    backward, so that it costs a normal step no memory.  With every expert
    held ``R = N * K`` and no second pass is traced.

    **What the remainder costs every program** is its TRACING,
    differentiated under the ``cond``, though almost no step runs it; on
    the benchmark's cell an ``expert_fn`` of Pallas kernels made that 16 s
    of warm set-up (PERF.md, PR 27).  Two things keep it small.
    ``remainder_fn`` (default ``expert_fn``) is what the remainder calls in
    ``expert_fn``'s place: the same function of ``(rows, group_sizes,
    *expert_args)``, which may take XLA's own grouped product
    (``grouped_matmul(impl="ragged_dot")``).  And the remainder is ONE
    jitted function of ``(x, weights, dispatch, expert_args)``: layers that
    pass the same callable (the same object: a module-level function, not a
    closure made per layer) and equal shapes share one trace of it, its
    derivative included.  A closure over the weights still works, with
    ``expert_args=()``, and shares nothing.

    With ``axis_name=None`` the layer runs on one device and exchanges
    nothing: it computes what ITS experts give and nothing stands in for
    the experts held elsewhere.  The exchange of rows between the devices
    of an ``ep`` axis (a ragged all-to-all by group sizes) is not written
    yet (ROADMAP.md)."""
    if axis_name is not None:
        raise NotImplementedError(
            "dropless_moe exchanges no rows between devices yet: the ragged "
            "all-to-all over an expert-parallel axis is ROADMAP.md's; pass "
            "axis_name=None and the range of experts this device holds")
    held_experts = num_experts if held_experts is None else held_experts
    if router_logits.shape[-1] != num_experts:
        raise ValueError(f"router_logits has {router_logits.shape[-1]} "
                         f"experts but num_experts={num_experts}")
    if not (0 <= first_expert and held_experts >= 1
            and first_expert + held_experts <= num_experts):
        raise ValueError(
            f"held experts [{first_expert}, {first_expert + held_experts}) "
            f"must lie within the model's {num_experts}")
    chosen, weights = dropless_route(router_logits, expert_bias, top_k,
                                     normalize, scaling_factor)
    pairs = chosen.size
    bound = dropless_rows_bound(pairs, held_experts, num_experts)
    rows, dispatch = dropless_dispatch(x, chosen, first_expert, held_experts,
                                       bound)

    # the main pass: unconditional and straight-line (its kernels keep the
    # names the trace's readers know: docs/observability.md)
    y = _held_part(expert_fn, rows, weights, dispatch, expert_args, 0)
    if bound < pairs:
        y = y + lax.cond(
            dispatch.group_sizes.sum() > bound,
            functools.partial(_remainder, expert_fn=remainder_fn or expert_fn,
                              bound=bound),
            lambda x, weights, dispatch, expert_args: jnp.zeros_like(x),
            x, weights, dispatch, expert_args)
    return y, dropless_counters(dispatch, bound)


__all__ = ["Dispatch", "ExpertParallelMLP", "dropless_combine",
           "dropless_counters", "dropless_dispatch", "dropless_moe",
           "dropless_route", "dropless_rows", "dropless_rows_bound",
           "moe_apply", "moe_plan_topology"]

"""Plan compiler — lowers a :class:`~chainermn_tpu.planner.ir.Plan` to
today's traced primitives.

ONE lowering serves every plan; the seven communicator flavors are fixed
plans fed through here (``tests/test_planner.py`` pins HLO-census parity
against the legacy per-class decompositions via the shared
``analysis/hlo.py`` parser).  The conventions the compiler must respect,
inherited from the code it replaces:

* **packing** — a flat plan that NEEDS a buffer (:func:`plan_needs_buffer`:
  a reduce-scatter/all-gather shards a flat index space, stripe ratios
  are offsets into it, a quantizer's error-feedback state maps
  one-to-one onto it) runs over ``_packing.pack`` buffers with the
  1/size mean fused into ``unpack``.  A flat plan whose every stage is
  an all-reduce is elementwise in every leaf, so there is nothing for a
  buffer to do: it is lowered over the LEAVES — each cast to the wire
  dtype (``chainermn.pack``), all-reduced where it lies, cast back and
  then scaled (``chainermn.unpack``) — with no reshape, concatenate or
  split.  XLA's combiner merges the small all-reduces; on a TPU the one
  big buffer only bought two extra passes over memory (PERF.md, PR 25).
  Either way the scale is applied AFTER the cast back (see
  ``_packing.unpack``).  Leaf plans apply the mean per leaf after the
  stage chain, exactly like the naive/hierarchical bodies did.
* **padding** — a reduce-scatter pads its buffer to a multiple of the
  scope size with ``_packing.pad_to_multiple`` and the matching
  all-gather strips it, the two_dimensional/FSDP layout convention.
* **masked-psum all-gather** — the default gather-back is the
  dynamic_update_slice + psum form, NOT ``lax.all_gather``: psum output
  is invariant-typed, a native all_gather's varying-axes type would
  poison replicated out_specs downstream (two_dimensional's module
  docstring has the full story).  ``lowering: "native"`` opts into the
  cheaper true gather when the caller owns the out_spec consequences.
* **degenerate scopes** — a stage whose scope resolves to NO axes is
  skipped (the legacy ``if inter_axes:`` guard); a stage over axes of
  size 1 IS emitted — XLA does not elide singleton-group collectives,
  and the type-clearing psum over a trivial inter axis is load-bearing
  (see single_node).
* **transpose pinning** — the compiler emits raw collectives, same as
  the legacy ``_allreduce_grad_traced`` bodies; differentiating THROUGH
  an executed plan goes via ``chainermn_tpu.functions.allreduce``'s
  custom VJP, unchanged.

:func:`plan_census_kinds` is the static mirror of the lowering: the
expected HLO collective-kind sequence of a compiled plan, read off the
IR.  ``analysis/rules.expected_kinds`` is now a thin wrapper over it —
the census table is derived, not maintained.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chainermn_tpu.planner.ir import Plan, PlanError, PlanTopology, Stage


def _axis_arg(axes: Tuple[str, ...]):
    """Single axis name when there is one, tuple otherwise — the same
    normalization ``MeshCommunicator._axis_arg`` applies."""
    return axes if len(axes) > 1 else axes[0]


def _with_wire(buf, wire_dtype: Optional[str], fn):
    """Run ``fn`` with ``buf`` cast to the stage wire dtype (if any),
    casting the result back to the original dtype — the per-stage cast
    seam per-hop compression (DynamiQ, ROADMAP item 2) extends."""
    if wire_dtype is None:
        return fn(buf)
    orig = buf.dtype
    wire = jnp.dtype(wire_dtype)
    if wire == orig:
        return fn(buf)
    return fn(buf.astype(wire)).astype(orig)


def _all_reduce_wire(st: Stage) -> Optional[str]:
    """Wire dtype of a non-quantizing all-reduce stage: an identity
    compressor IS the wire-dtype cast path, so its ``wire_dtype`` reads
    like the stage's own."""
    if st.compression is not None:
        return st.compressor().wire_dtype
    return st.wire_dtype


def _stage_scope(i: int, st: Stage):
    """The named scope one emitted stage runs under:
    ``chainermn.plan.<i>.<op>`` (``all-reduce`` reads ``all_reduce``).  It
    names the stage's instructions in the device trace, so a stage's
    time is read on the device's own clock and at no cost to the program
    (docs/observability.md)."""
    return jax.named_scope(
        f"chainermn.plan.{i}.{re.sub('[^0-9A-Za-z]', '_', st.op)}")


class _ShardFrame:
    """Book-keeping for one live reduce-scatter (popped by the matching
    all-gather)."""

    def __init__(self, scope: str, axis: str, size: int, padded_len: int,
                 strip):
        self.scope = scope
        self.axis = axis
        self.size = size
        self.padded_len = padded_len
        self.strip = strip


def _quantizer_for(st: Stage):
    """The stage's resolved compressor when it is a stateful quantizer
    (int8/fp8); None for uncompressed and identity-compressed stages."""
    if st.compression is None:
        return None
    from chainermn_tpu.compression import quantize as _cq
    comp = st.compressor()
    return comp if _cq.is_quantizing(comp) else None


def plan_group_lengths(plan: Plan, length: int) -> List[int]:
    """Element count of each concurrent group's slice of a packed flat
    buffer of ``length`` elements.  Boundaries land at
    ``round(length * cumulative_ratio)`` — deterministic Python ints at
    trace time, monotone, and summing exactly to ``length`` (the last
    group absorbs the rounding remainder).  A plain plan is one group
    owning the whole buffer."""
    groups = plan.stage_groups()
    bounds = [0]
    cum = 0.0
    for grp in groups[:-1]:
        cum += grp.ratio
        b = int(round(length * cum))
        bounds.append(min(max(b, bounds[-1]), int(length)))
    bounds.append(int(length))
    return [bounds[i + 1] - bounds[i] for i in range(len(groups))]


def _stage_at(plan: Plan, key) -> Stage:
    """The Stage a hop key addresses: ``(group, stage)`` tuples for
    striped plans, bare stage indices for plain ones."""
    if isinstance(key, tuple):
        g, i = key
        return plan.groups[g].stages[i]
    return plan.stages[key]


def plan_compressed_hops(plan: Plan,
                         topology: Optional[PlanTopology] = None) -> Dict:
    """``{hop_key: Compressor}`` for every stage carrying a stateful
    quantizer — ``hop_key`` is the stage index for a plain plan and a
    ``(group, stage)`` tuple for a striped one (two groups may each own
    a compressed stage 0; their EF states must not collide).  With a
    ``topology``, stages whose scope resolves to no axes are dropped
    (the compiler skips them, so they hold no state)."""
    hops = {}
    striped = plan.groups is not None
    for g, grp in enumerate(plan.stage_groups()):
        for i, st in enumerate(grp.stages):
            if topology is not None and not topology.scope_axes(st.scope):
                continue
            comp = _quantizer_for(st)
            if comp is not None:
                hops[(g, i) if striped else i] = comp
    return hops


def _chain_stage_lengths(stages, topology: PlanTopology,
                         length: int) -> Dict[int, int]:
    lengths: Dict[int, int] = {}
    cur = int(length)
    stack: List[Tuple[int, int]] = []  # (orig_len, padded_len)
    for i, st in enumerate(stages):
        axes = topology.scope_axes(st.scope)
        if not axes:
            continue
        lengths[i] = cur
        if st.op == "reduce-scatter":
            size = topology.scope_size(st.scope)
            padded = cur + (-cur) % size
            stack.append((cur, padded))
            cur = padded // size
        elif st.op == "all-gather":
            orig, _ = stack.pop()
            cur = orig
    return lengths


def plan_stage_lengths(plan: Plan, topology: PlanTopology,
                       length: int) -> Dict:
    """Flat-buffer element count at ENTRY to each emitted stage — the
    static mirror of ``_run_stages_flat``'s pad/shard bookkeeping, used
    to size per-hop EF state (a compressed inter hop after a
    reduce-scatter sees 1/intra of the packed buffer).  Keys follow
    :func:`plan_compressed_hops`: bare indices for plain plans,
    ``(group, stage)`` for striped plans, where each group's chain
    starts from ITS slice length (``plan_group_lengths``)."""
    if plan.groups is None:
        return _chain_stage_lengths(plan.stages, topology, length)
    lengths: Dict = {}
    for g, (grp, ln) in enumerate(
            zip(plan.stage_groups(), plan_group_lengths(plan, length))):
        for i, val in _chain_stage_lengths(
                grp.stages, topology, ln).items():
            lengths[(g, i)] = val
    return lengths


def init_plan_compression_states(plan: Plan, topology: PlanTopology,
                                 length: int) -> Optional[Dict]:
    """Fresh per-hop EF states for ``plan`` over a packed buffer of
    ``length`` float32 elements: ``{hop_key: CompressionState}``, one
    per quantizing stage, each sized to the buffer AT that stage and
    tagged with its hop key (``state.hop`` — the stage index, or the
    ``(group, stage)`` tuple for a striped plan) so the checkpoint
    sidecar pins which hop carried which spec.  ``None`` when the plan
    has no quantizing stages."""
    hops = plan_compressed_hops(plan, topology)
    if not hops:
        return None
    lengths = plan_stage_lengths(plan, topology, length)
    states = {}
    for key, comp in hops.items():
        world = topology.scope_size(_stage_at(plan, key).scope)
        comp.clip_limit(world)  # fail early at unworkable scope sizes
        states[key] = comp.init_state(lengths[key], world, hop=key)
    return states


def _compressed_psum(st: Stage, idx: int, axes, world: int, buf, state):
    """Lower one quantized all-reduce stage: EF-encode to wire codes,
    psum the codes (and piggybacked saturation flags) IN wire
    arithmetic over the scope axes, decode + delayed-scale update.
    Returns ``(summed_f32_buffer, new_state)`` — sum semantics, same as
    the psum it replaces, so the fused 1/world mean at unpack is
    untouched."""
    comp = _quantizer_for(st)
    m = int(buf.shape[0])
    if int(state.ef.shape[0]) != comp._padded(m):
        raise ValueError(
            f"per-hop compression state for stage {idx} is sized for "
            f"ef={int(state.ef.shape[0])} but the buffer at this stage "
            f"has {m} elements (needs {comp._padded(m)}): build the "
            "states with init_plan_compression_states(plan, topology, "
            "packed_length) / comm.init_compression_state(grads)")
    orig_dtype = buf.dtype
    rank = lax.axis_index(_axis_arg(axes))
    with jax.named_scope("chainermn.compress"):
        codes, state = comp.compress(buf.astype(jnp.float32), state,
                                     rank=rank, world_size=world)
    summed = lax.psum(codes, _axis_arg(axes))
    with jax.named_scope("chainermn.decompress"):
        out, state = comp.decompress(summed, state, world_size=world,
                                     axes=_axis_arg(axes))
    return out[:m].astype(orig_dtype), state


def _run_stages_flat(plan: Plan, topology: PlanTopology, buf,
                     states: Optional[Dict] = None,
                     group: Optional[int] = None):
    """Apply one stage chain to one flat buffer.  ``group`` selects a
    concurrent group's chain (striped plans — ``buf`` is that group's
    slice and hop keys become ``(group, stage)`` tuples); ``None`` runs
    a plain plan's ``stages`` with bare stage-index keys.  ``states``
    maps hop key -> per-hop CompressionState for quantizing stages;
    returns ``(buf, new_states)`` (``new_states`` empty when nothing is
    stateful)."""
    from chainermn_tpu.communicators import _packing

    states = dict(states or {})
    new_states: Dict = {}
    shard_stack: List[_ShardFrame] = []
    stages = plan.stages if group is None else plan.groups[group].stages
    for i, st in enumerate(stages):
        key = i if group is None else (group, i)
        axes = topology.scope_axes(st.scope)
        if not axes:
            continue
        with _stage_scope(i, st):
            quant = _quantizer_for(st)
            if quant is not None:
                world = topology.scope_size(st.scope)
                state = states.get(key)
                if state is None:
                    # One-shot path (benchmark sweeps, candidate validation):
                    # a cold EF state built inside the trace, discarded by
                    # the caller.  Training seams thread persistent states.
                    state = quant.init_state(
                        int(buf.shape[0]), world, hop=key)
                buf, new_states[key] = _compressed_psum(
                    st, key, axes, world, buf, state)
            elif st.op == "all-reduce":
                buf = _with_wire(buf, _all_reduce_wire(st),
                                 lambda b: lax.psum(b, _axis_arg(axes)))
            elif st.op == "reduce-scatter":
                if len(axes) != 1:
                    raise PlanError(
                        f"reduce-scatter scope {st.scope!r} resolves to "
                        f"{axes} — psum_scatter shards over exactly one "
                        "axis; "
                        "declare a topology whose scope is a single axis")
                size = topology.scope_size(st.scope)
                buf, strip = _packing.pad_to_multiple(buf, size)
                frame = _ShardFrame(st.scope, axes[0], size,
                                    int(buf.shape[0]), strip)
                buf = _with_wire(
                    buf, st.wire_dtype,
                    lambda b: lax.psum_scatter(b, axes[0], tiled=True))
                shard_stack.append(frame)
            elif st.op == "all-gather":
                frame = shard_stack.pop()  # validate() guarantees matching
                if st.lowering == "native":
                    buf = _with_wire(
                        buf, st.wire_dtype,
                        lambda b: lax.all_gather(b, frame.axis, tiled=True))
                else:
                    me = lax.axis_index(frame.axis)
                    shard_len = frame.padded_len // frame.size

                    def gather(b):
                        placed = lax.dynamic_update_slice_in_dim(
                            jnp.zeros((frame.padded_len,), b.dtype), b,
                            me * shard_len, 0)
                        return lax.psum(placed, frame.axis)

                    buf = _with_wire(buf, st.wire_dtype, gather)
                buf = frame.strip(buf)
            elif st.op == "multicast":
                idx = lax.axis_index(_axis_arg(axes))

                def bcast(b):
                    masked = jnp.where(idx == st.root, b, jnp.zeros_like(b))
                    return lax.psum(masked, _axis_arg(axes))

                buf = _with_wire(buf, st.wire_dtype, bcast)
            elif st.op == "p2p":
                if len(axes) != 1:
                    raise PlanError(
                        f"p2p scope {st.scope!r} resolves to {axes} — "
                        "ppermute rings run over exactly one axis")
                n = topology.scope_size(st.scope)
                perm = [(i, (i + 1) % n) for i in range(n)]
                buf = _with_wire(buf, st.wire_dtype,
                                 lambda b: lax.ppermute(b, axes[0], perm))
            elif st.op == "all-to-all":
                raise PlanError(
                    f"plan {plan.name!r}: all-to-all stages lower through "
                    "execute_alltoall (a block exchange over [P, ...] "
                    "buffers), not the gradient-mean executor")
            else:  # pragma: no cover — ir validation rejects unknown ops
                raise PlanError(f"unknown stage op {st.op!r}")
    return buf, new_states


def _leaf_stage_op(plan: Plan, topology: PlanTopology, st: Stage, leaf):
    """Apply ONE stage to one leaf (leaf-mode ops only: all-reduce/
    multicast/p2p — ir.validate).  Degenerate scopes pass through."""
    axes = topology.scope_axes(st.scope)
    if not axes:
        return leaf
    if st.op == "all-reduce":
        return _with_wire(leaf, _all_reduce_wire(st),
                          lambda v: lax.psum(v, _axis_arg(axes)))
    if st.op == "multicast":
        idx = lax.axis_index(_axis_arg(axes))

        def bcast(v):
            masked = jnp.where(idx == st.root, v, jnp.zeros_like(v))
            return lax.psum(masked, _axis_arg(axes))

        return _with_wire(leaf, st.wire_dtype, bcast)
    if st.op == "p2p":
        n = topology.scope_size(st.scope)
        perm = [(i, (i + 1) % n) for i in range(n)]
        return _with_wire(leaf, st.wire_dtype,
                          lambda v: lax.ppermute(v, axes[0], perm))
    # pragma: no cover — leaf validation rejects sharding ops
    raise PlanError(f"stage op {st.op!r} is not legal under leaf packing")


def _run_stages_leaf(plan: Plan, topology: PlanTopology, leaf):
    """Leaf-mode chain: all-reduce/multicast/p2p only (ir.validate)."""
    for i, st in enumerate(plan.stages):
        with _stage_scope(i, st):
            leaf = _leaf_stage_op(plan, topology, st, leaf)
    return leaf


def _run_stages_leaves(plan: Plan, topology: PlanTopology, leaves: List,
                       group: Optional[int] = None) -> List:
    """Apply one chain of leaf-mode stages (all-reduce/multicast/p2p) to
    a LIST of leaves, each where it lies.  Runs stage-outer / leaf-inner
    — per leaf the chain is identical to :func:`_run_stages_leaf` (leaves
    are independent), and the order of the all-reduces in the program is
    the one the asynchronous exchange was measured on (PERF.md, PR 29).
    ``group`` as in :func:`_run_stages_flat`."""
    stages = plan.stages if group is None else plan.groups[group].stages
    for i, st in enumerate(stages):
        if not topology.scope_axes(st.scope):
            continue
        with _stage_scope(i, st):
            leaves = [_leaf_stage_op(plan, topology, st, l) for l in leaves]
    return leaves


def plan_needs_buffer(plan: Plan, topology: PlanTopology) -> bool:
    """Whether ``plan``'s lowering against ``topology`` builds the packed
    flat buffer — a pure function of the two, read by
    :func:`execute_plan` itself (one value a compiled step).

    A buffer is an index space.  Three things use one: a
    reduce-scatter/all-gather (it shards the space), stripes (ratio
    boundaries are offsets into it) and a quantizing stage (its
    error-feedback state maps one-to-one onto it).  A flat plan with
    none of them — every emitted stage an all-reduce, wire casts and
    identity codecs included — is elementwise in every leaf and is
    lowered over the leaves.  Leaf-packed plans never had a buffer."""
    if plan.packing != "flat":
        return False
    groups = plan.stage_groups()
    if len(groups) > 1 or plan_compressed_hops(plan, topology):
        return True
    return any(st.op != "all-reduce" for st in groups[0].stages
               if topology.scope_axes(st.scope))


def _mean_over_leaves(plan: Plan, topology: PlanTopology, grads,
                      like=None):
    """The lowering of a flat plan that needs no buffer: the same cast,
    reduce, cast back, scale as pack -> stages -> unpack, leaf by leaf.
    The scopes stay where ``_packing`` has them, so ``chainermn.pack``
    goes on naming what the exchange costs before the collective (here
    the wire cast alone) and ``chainermn.unpack`` what it costs after.
    The means come back in ``like``'s dtypes (None: the leaves' own): a
    leaf that arrives in the wire dtype meets no cast before its
    collective, and is cast to its ``like`` before the scale."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    dtypes = [l.dtype for l in
              (leaves if like is None else treedef.flatten_up_to(like))]
    if plan.wire_dtype is not None:
        wire = jnp.dtype(plan.wire_dtype)
        with jax.named_scope("chainermn.pack"):
            leaves = [l if l.dtype == wire else l.astype(wire)
                      for l in leaves]
    leaves = _run_stages_leaves(
        plan, topology, leaves,
        group=None if plan.groups is None else 0)
    scale = 1.0 / topology.size
    with jax.named_scope("chainermn.unpack"):
        # cast back FIRST: the scale multiplies in the precision asked
        # for, the leaf's or its ``like``'s (``_packing.unpack``'s order)
        leaves = [(l if l.dtype == dt else l.astype(dt))
                  * jnp.asarray(scale, dt)
                  for l, dt in zip(leaves, dtypes)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def execute_plan(plan: Plan, comm, grads, *, states: Optional[Dict] = None,
                 like=None):
    """Run ``plan`` as ``comm``'s gradient mean — the one lowering every
    flavor's ``_allreduce_grad_traced`` now delegates to.

    ``comm`` supplies the axis names and world size through
    ``comm.plan_topology()`` (the shared Topology-derived descriptor —
    one source of truth for group sizes).  Must be called inside an SPMD
    region, like the methods it replaces.

    Three lowerings, chosen by what the plan's own stages say: leaf
    packing runs the chain per leaf; flat packing builds the packed
    buffer when :func:`plan_needs_buffer` says something shards, stripes
    or quantizes it, and otherwise reduces the leaves where they lie.

    ``states`` threads per-hop error-feedback state through quantizing
    stages: a ``{stage_index: CompressionState}`` dict from
    :func:`init_plan_compression_states`.  When given, the call returns
    ``(mean_grads, new_states)``; when omitted, quantizing stages run
    from a cold in-trace state (EF discarded — the one-shot
    benchmark/validation path) and the return is just ``mean_grads``,
    keeping every pre-existing call site unchanged.

    ``like`` gives the dtypes the means come back in
    (``allreduce_grad``'s keyword; None: the gradients' own).  The
    leaf-wise lowering of a flat plan reduces a leaf that is in the wire
    dtype already as it lies; the other two widen the tree first.
    """
    from chainermn_tpu.communicators import _packing

    topology = comm.plan_topology()
    n = topology.size
    if plan.packing == "leaf":
        if states is not None:
            raise PlanError(
                f"plan {plan.name!r}: leaf packing carries no per-hop "
                "compression state")
        leaves, treedef = jax.tree_util.tree_flatten(
            _packing.cast_like(grads, like))
        leaves = _run_stages_leaves(plan, topology, leaves)
        return jax.tree_util.tree_unflatten(
            treedef, [l / n for l in leaves])
    if not plan_needs_buffer(plan, topology):
        result = _mean_over_leaves(plan, topology, grads, like)
        return (result, {}) if states is not None else result
    grads = _packing.cast_like(grads, like)
    # Quantizing plans exchange ONE float32 buffer (the quantizer's
    # native dtype; per-stage wires still cast per hop) so EF state maps
    # one-to-one onto the packed buffer.
    has_quant = bool(plan_compressed_hops(plan, topology))
    comm_dtype = (jnp.dtype(plan.wire_dtype)
                  if plan.wire_dtype is not None else None)
    if has_quant and comm_dtype is None:
        comm_dtype = jnp.float32
    buffers, meta = _packing.pack(grads, comm_dtype=comm_dtype)
    new_states: Dict = {}
    out_buffers = []
    for b in buffers:
        if plan.groups is not None:
            # Striped lowering: partition the packed buffer at its
            # static ratio boundaries, run each concurrent group's
            # chain over its slice (the chains are data-independent, so
            # XLA interleaves them — the ICI stripe's hops overlap the
            # DCN stripe's slow hop, no host joins), re-concatenate
            # before unpack.  A single ratio-1.0 group skips the
            # slice/concat entirely, keeping it bit-exact with the
            # equivalent flat plan.
            lens = plan_group_lengths(plan, int(b.shape[0]))
            if len(lens) == 1:
                b, st_out = _run_stages_flat(
                    plan, topology, b, states=states, group=0)
                new_states.update(st_out)
            else:
                parts = []
                off = 0
                for g, ln in enumerate(lens):
                    seg = lax.slice_in_dim(b, off, off + ln)
                    off += ln
                    if ln == 0:
                        # a tiny buffer can round a stripe to nothing;
                        # an empty slice has no collective to run
                        parts.append(seg)
                        continue
                    seg, st_out = _run_stages_flat(
                        plan, topology, seg, states=states, group=g)
                    new_states.update(st_out)
                    parts.append(seg)
                b = jnp.concatenate(parts)
        else:
            b, st_out = _run_stages_flat(plan, topology, b, states=states)
            new_states.update(st_out)
        out_buffers.append(b)
    result = _packing.unpack(out_buffers, meta, scale=1.0 / n)
    if states is not None:
        return result, new_states
    return result


def _run_alltoall_chain(plan: Plan, topology: PlanTopology, stages, buf):
    """Lower one exchange chain over one ``[P, ...]`` block buffer.

    Two canonical decompositions (the zoo ``plans.alltoall_plans``
    emits):

    * **flat** — one stage over scope ``all`` (or ``intra`` on a
      single-axis topology): one tiled ``lax.all_to_all`` over the
      scope's axes, blocks indexed by destination global rank in
      topology (inter-major) order.
    * **hierarchical** — ``intra`` then ``inter``: the ICI hop regroups
      blocks by destination intra coordinate (each intra peer ``j``
      collects the node's traffic for every ``(i, j)`` target), a local
      transpose re-majors them by destination host, and the DCN hop
      ships each host its aggregate — at the stage's (narrow)
      ``wire_dtype``.  The composed exchange lands blocks in source
      global-rank order, IDENTICAL to the flat exchange (pinned
      bit-exact in ``tests/test_moe_plan.py``).
    """
    emitted = [(i, st) for i, st in enumerate(stages)
               if topology.scope_axes(st.scope)]
    scopes = tuple(st.scope for _, st in emitted)
    if int(buf.shape[0]) != topology.size:
        raise PlanError(
            f"plan {plan.name!r}: exchange buffer leading dim "
            f"{int(buf.shape[0])} != topology size {topology.size} — "
            "all-to-all buffers carry one block per destination rank")
    if scopes in (("all",), ("intra",)):
        if scopes == ("intra",) and topology.inter_size != 1:
            raise PlanError(
                f"plan {plan.name!r}: an intra-only exchange on a "
                f"multi-host topology ({topology.key()}) is not a full "
                "all-to-all — use scope 'all' or the hierarchical "
                "intra+inter chain")
        i, st = emitted[0]
        axes = topology.scope_axes(st.scope)
        with _stage_scope(i, st):
            buf = _with_wire(
                buf, st.wire_dtype,
                lambda b: lax.all_to_all(b, _axis_arg(axes), 0, 0,
                                         tiled=True))
        return buf
    if scopes != ("intra", "inter"):
        raise PlanError(
            f"plan {plan.name!r}: unsupported exchange chain over scopes "
            f"{scopes}; supported: one flat stage (scope 'all') or the "
            "hierarchical 'intra' then 'inter' pair")
    (ii, intra_st), (ji, inter_st) = emitted
    intra_axis = topology.scope_axes("intra")[0]
    inter_axes = topology.scope_axes("inter")
    isz, jsz = topology.inter_size, topology.intra_size
    rest = tuple(buf.shape[1:])
    # [P(dest rank, inter-major), ...] -> intra-major so the ICI hop
    # splits by destination intra coordinate
    x = buf.reshape((isz, jsz) + rest)
    x = jnp.moveaxis(x, 1, 0).reshape((jsz * isz,) + rest)
    with _stage_scope(ii, intra_st):
        x = _with_wire(
            x, intra_st.wire_dtype,
            lambda b: lax.all_to_all(b, intra_axis, 0, 0, tiled=True))
    # x[b'*I + i] = block from intra peer b' destined (i, self_j);
    # re-major by destination host for the DCN hop
    x = x.reshape((jsz, isz) + rest)
    x = jnp.moveaxis(x, 1, 0).reshape((isz * jsz,) + rest)
    with _stage_scope(ji, inter_st):
        x = _with_wire(
            x, inter_st.wire_dtype,
            lambda b: lax.all_to_all(b, _axis_arg(inter_axes), 0, 0,
                                     tiled=True))
    # x[a'*J + b'] = block from source (a', b') — source global-rank
    # order, exactly the flat exchange's output layout
    return x


def execute_alltoall(plan: Plan, topology: PlanTopology, buf):
    """Run ``plan`` as a block exchange over ``buf`` — the MoE
    dispatch/combine seam (``parallel/expert.moe_apply(plan=...)``).

    ``buf`` is a ``[P, ...]`` buffer inside an SPMD region whose mesh
    axes match ``topology`` (one block per destination global rank,
    topology axis order = mesh order, inter-major).  Returns the
    exchanged buffer with blocks indexed by SOURCE global rank — exactly
    ``lax.all_to_all(..., split_axis=0, concat_axis=0, tiled=True)``
    semantics over the combined axes, whatever decomposition the plan
    picked.  Every emitted hop runs under its own
    ``chainermn.plan.<i>.<op>`` scope, so the ICI and DCN legs of one
    dispatch are separate in the device trace.

    A striped plan (``plan.groups``) splits the buffer's SECOND dim (the
    within-block payload) at the group ratio boundaries and runs each
    group's chain over its slice — the chains are data-independent, so
    XLA interleaves them, same as the striped allreduce lowering.
    """
    if plan.packing != "flat":
        raise PlanError(
            f"plan {plan.name!r}: all-to-all requires flat packing")
    if plan.groups is None:
        return _run_alltoall_chain(plan, topology, plan.stages, buf)
    if buf.ndim < 2:
        raise PlanError(
            f"plan {plan.name!r}: a striped exchange splits the "
            "within-block payload — the buffer needs a second dim")
    lens = plan_group_lengths(plan, int(buf.shape[1]))
    if len(lens) == 1:
        return _run_alltoall_chain(plan, topology, plan.groups[0].stages, buf)
    parts = []
    off = 0
    for g, ln in enumerate(lens):
        seg = lax.slice_in_dim(buf, off, off + ln, axis=1)
        off += ln
        if ln:
            seg = _run_alltoall_chain(plan, topology,
                                      plan.groups[g].stages, seg)
        parts.append(seg)
    return jnp.concatenate(parts, axis=1)


#: stage op -> HLO collective kind its default lowering compiles to
_CENSUS_KIND = {
    "all-reduce": "all-reduce",
    "reduce-scatter": "reduce-scatter",
    # default all-gather lowering is the masked psum (invariant-typed)
    "all-gather": "all-reduce",
    "multicast": "all-reduce",
    "p2p": "collective-permute",
    "all-to-all": "all-to-all",
}


def _group_stages(plan: Plan, group: Optional[int]):
    """Stage chain(s) a census walk covers: one group's chain, or every
    chain in group order (trace order) when ``group`` is None."""
    if group is not None:
        return plan.stage_groups()[group].stages
    return tuple(st for grp in plan.stage_groups() for st in grp.stages)


def plan_census_kinds(plan: Plan, topology: PlanTopology,
                      group: Optional[int] = None) -> tuple:
    """Expected HLO collective-kind sequence of ``plan`` compiled against
    ``topology`` — the census, derived from the IR.

    Per packed buffer (flat) / per leaf (leaf): the census probes in
    ``analysis/lint.allreduce_hlo`` and ``tests/test_census.py`` trace a
    single-leaf single-dtype tree, so the sequence is the whole program.
    A stage over a scope with NO axes emits nothing (it is skipped by
    the compiler); a stage over axes of size 1 IS counted — XLA keeps
    singleton-group collectives (measured on the CPU mesh; the old
    hand-written table got exactly this wrong at ``inter == 1``).

    For a striped plan, ``group`` selects ONE concurrent group's
    expected sequence; ``group=None`` concatenates the groups in trace
    order.  Because the groups are data-independent, XLA may interleave
    their collectives — compare per group (the census-drift rule checks
    the observed program is a valid interleaving of the per-group
    sequences, order preserved within each group).
    """
    kinds = []
    for st in _group_stages(plan, group):
        if not topology.scope_axes(st.scope):
            continue
        if st.op == "all-gather" and st.lowering == "native":
            kinds.append("all-gather")
        else:
            kinds.append(_CENSUS_KIND[st.op])
    return tuple(kinds)


def plan_scale_exchanges(plan: Plan, topology: PlanTopology) -> int:
    """How many scale exchanges ``plan`` compiles to: each quantizing hop
    the compiler emits adds one float32 all-reduce (a ``pmax`` typing its
    rank-identical per-chunk scale exponents replicated — see
    ``_ScaledQuantizer.decompress``).  It depends on nothing but the
    carried state until the decode, so XLA schedules it freely: in the
    census it is a one-op sequence interleaved with the stage chains,
    not a position in them."""
    return len(plan_compressed_hops(plan, topology))


def plan_wire_dtypes(plan: Plan, topology: PlanTopology,
                     dtype="float32", group: Optional[int] = None) -> tuple:
    """Expected on-wire numpy dtype NAME per emitted stage, aligned with
    :func:`plan_census_kinds` (same ``group`` semantics) — the per-hop
    census the lint rules compare against compiled HLO.  A compressed
    stage's wire is its compressor's (``int8`` / ``float8_e4m3fn`` / an
    identity codec's ``wire_dtype``); otherwise the stage wire dtype,
    the plan wire dtype, then the payload ``dtype``, in that order."""
    payload = np.dtype(dtype).name if plan.wire_dtype is None \
        else np.dtype(plan.wire_dtype).name
    if plan_compressed_hops(plan, topology) and plan.wire_dtype is None:
        payload = "float32"  # quantizing plans pack one f32 buffer
    out = []
    for st in _group_stages(plan, group):
        if not topology.scope_axes(st.scope):
            continue
        if st.compression is not None:
            comp = st.compressor()
            wire = getattr(comp, "wire", None) or \
                getattr(comp, "wire_dtype", None)
            out.append(np.dtype(str(wire)).name if wire else payload)
        elif st.wire_dtype is not None:
            out.append(np.dtype(st.wire_dtype).name)
        else:
            out.append(payload)
    return tuple(out)


def _stage_wire_elem_bytes(plan: Plan, st: Stage, elems: float,
                           item: int) -> float:
    """Bytes ``elems`` payload elements occupy on THIS stage's wire —
    the per-stage dtype priority the compiler itself applies (stage
    wire, then plan wire, then payload), extended with compressed-stage
    pricing: a quantizing hop pays the compressor's wire width on the
    chunk-grid-padded length PLUS one flag slot per chunk (the
    saturation flags ride the same collective)."""
    quant = _quantizer_for(st)
    if quant is not None:
        n = int(np.ceil(elems))
        wire_item = np.dtype(quant.wire).itemsize
        return float(quant._padded(n) + quant.n_chunks(n)) * wire_item
    if st.compression is not None:  # identity codec
        wd = st.compressor().wire_dtype
        wire_item = np.dtype(wd).itemsize if wd else item
        return elems * wire_item
    wire_item = (np.dtype(st.wire_dtype).itemsize
                 if st.wire_dtype else
                 np.dtype(plan.wire_dtype).itemsize
                 if plan.wire_dtype else item)
    return elems * wire_item


def _chain_stage_costs(plan: Plan, stages, topology: PlanTopology,
                       nbytes: float, item: int) -> List[Tuple[str, float]]:
    """Per emitted stage of one chain: ``(scope, bytes_moved)`` under
    the ring cost model (all-reduce 2x, reduce-scatter/all-gather 1x,
    p2p 1/size), each stage priced at its own wire width."""
    out: List[Tuple[str, float]] = []
    frac = 1.0  # fraction of the chain's payload live at this stage
    for st in stages:
        axes = topology.scope_axes(st.scope)
        if not axes:
            continue
        size = topology.scope_size(st.scope)
        elems = (nbytes / item) * frac
        stage_bytes = _stage_wire_elem_bytes(plan, st, elems, item)
        if st.op == "all-reduce":
            moved = 2.0 * stage_bytes * (size - 1) / max(size, 1)
        elif st.op == "reduce-scatter":
            moved = stage_bytes * (size - 1) / max(size, 1)
            frac /= size
        elif st.op == "all-gather":
            gathered = stage_bytes * size
            if st.lowering == "native":
                moved = gathered * (size - 1) / max(size, 1)
            else:  # masked psum pays ring-allreduce cost on full length
                moved = 2.0 * gathered * (size - 1) / max(size, 1)
            frac *= size
        elif st.op == "multicast":
            moved = 2.0 * stage_bytes * (size - 1) / max(size, 1)
        elif st.op == "p2p":
            moved = stage_bytes
        elif st.op == "all-to-all":
            # tiled exchange: each device keeps its own 1/size block and
            # ships the rest — (size-1)/size of the stage payload per
            # device, shape-preserving (frac unchanged)
            moved = stage_bytes * (size - 1) / max(size, 1)
        else:  # pragma: no cover
            moved = stage_bytes
        out.append((st.scope, moved))
    return out


def plan_wire_bytes(plan: Plan, topology: PlanTopology, nbytes: int,
                    dtype="float32") -> dict:
    """Static per-scope wire-cost model of a plan moving ``nbytes`` of
    ``dtype`` payload: bytes each scope's links carry per device, using
    ring costs (all-reduce 2x, reduce-scatter/all-gather 1x, p2p
    1/size).  Each stage is priced at ITS OWN wire width — stage
    ``wire_dtype`` first, then the plan-level dtype, then the payload;
    a quantizing stage at its compressor's wire width including the
    chunk pad and per-chunk saturation-flag overhead.  A striped plan
    sums across its concurrent groups, each group priced on its split
    ratio of the payload.  Used by the autotuner to break timing ties
    and by the docs to explain WHY a plan wins a cell; not a substitute
    for measurement.
    """
    item = np.dtype(dtype).itemsize
    costs: dict = {}
    for grp in plan.stage_groups():
        for scope, moved in _chain_stage_costs(
                plan, grp.stages, topology, nbytes * grp.ratio, item):
            costs[scope] = costs.get(scope, 0.0) + moved
    return costs


#: scope -> physical link class its traffic rides: the intra (last) axis
#: is the ICI domain, inter and flat-over-all traffic crosses the DCN
#: boundary
LINK_CLASS = {"intra": "ici", "inter": "dcn", "all": "dcn"}


def validate_link_gbps(link_gbps: Dict[str, float]) -> Dict[str, float]:
    """Validate a ``{link class: GB/s}`` mapping against the known
    :data:`LINK_CLASS` values and return it normalized to float rates.

    A typo'd key (``icn`` for ``ici``) would otherwise be SILENT: the
    cost model reads links via ``link_gbps.get(link)`` and prices a
    missing class as free, so the misspelled rate never constrains
    anything and every plan looks equally fast on that wire.  Fail
    loudly instead, naming the accepted classes — ``bench_allreduce``
    / ``bench_moe`` ``--link-gbps`` parsing and every modeled-time
    entry point route through this."""
    accepted = sorted(set(LINK_CLASS.values()))
    unknown = sorted(set(str(k) for k in link_gbps) - set(accepted))
    if unknown:
        raise ValueError(
            f"unknown link class(es) {unknown} in link rates; accepted "
            f"names are {accepted} (the LINK_CLASS values)")
    out = {}
    for link, bw in link_gbps.items():
        bw = float(bw)
        if bw < 0:
            raise ValueError(
                f"link class {link!r} has negative bandwidth {bw}")
        out[str(link)] = bw
    return out


def plan_link_bytes(plan: Plan, topology: PlanTopology, nbytes: int,
                    dtype="float32") -> dict:
    """Per-(scope, link-class) wire bytes of ``plan`` moving ``nbytes``
    of ``dtype`` payload, summed over a striped plan's concurrent
    groups: ``{(scope, link): bytes}`` with ``link`` in {"ici", "dcn"}
    per :data:`LINK_CLASS`.  The per-link ledger
    :func:`plan_modeled_time_s` prices against declared per-link GB/s —
    and the by-link marginal that tells you WHICH wire a candidate
    stripe would relieve."""
    costs = plan_wire_bytes(plan, topology, nbytes, dtype=dtype)
    return {(scope, LINK_CLASS[scope]): moved
            for scope, moved in costs.items()}


def plan_modeled_time_s(plan: Plan, topology: PlanTopology, nbytes: int,
                        link_gbps: Dict[str, float],
                        dtype="float32") -> float:
    """Predicted wire time (seconds) of ``plan`` moving ``nbytes`` of
    ``dtype`` payload over links of declared bandwidth ``link_gbps``
    (``{"ici": GB/s, "dcn": GB/s}``; a missing link class is free).

    Two lower bounds, and the prediction is their max:

    * **chain time, max over groups** — each concurrent group's stage
      chain is sequentially dependent, so a group costs the SUM of its
      stages' link times; the groups are data-independent, so the plan
      costs the slowest group, NOT the sum of groups.  This is the
      striping win: the ICI stripe's hops hide behind the DCN stripe's
      slow hop.
    * **link busy time, max over link classes** — concurrency cannot
      exceed a wire: every byte all groups put on one link class still
      serializes on that link, so splitting a plan into identical
      stripes buys nothing.

    A plain single-chain plan degenerates to its chain sum (which
    dominates any one link's share).

    ``link_gbps`` keys are validated against :data:`LINK_CLASS` values
    (:func:`validate_link_gbps`) — an unknown class would silently
    price as free.
    """
    item = np.dtype(dtype).itemsize
    link_gbps = validate_link_gbps(link_gbps)

    def _rate(link: str) -> float:
        bw = link_gbps.get(link)
        return float(bw) * 1e9 if bw else float("inf")

    chain_times = []
    link_busy: Dict[str, float] = {}
    for grp in plan.stage_groups():
        t = 0.0
        for scope, moved in _chain_stage_costs(
                plan, grp.stages, topology, nbytes * grp.ratio, item):
            link = LINK_CLASS[scope]
            dt = moved / _rate(link)
            t += dt
            link_busy[link] = link_busy.get(link, 0.0) + dt
        chain_times.append(t)
    busiest = max(link_busy.values()) if link_busy else 0.0
    return max(max(chain_times, default=0.0), busiest)


def plan_dcn_bytes(plan: Plan, topology: PlanTopology, nbytes: int,
                   dtype="float32") -> float:
    """Bytes a plan moves across the slow (DCN) boundary: the ``inter``
    scope plus the ``all`` scope (a flat ring over every data axis
    crosses the inter boundary, so its traffic is priced at DCN rates —
    which is exactly why hierarchical plans exist).  The
    ``dcn_wire_bytes`` perf budget and ``bench_allreduce --sweep``'s
    per-hop shrink column read this."""
    costs = plan_wire_bytes(plan, topology, nbytes, dtype=dtype)
    return float(costs.get("inter", 0.0) + costs.get("all", 0.0))


__all__ = ["LINK_CLASS", "execute_alltoall", "execute_plan",
           "init_plan_compression_states",
           "plan_census_kinds", "plan_compressed_hops", "plan_dcn_bytes",
           "plan_group_lengths", "plan_link_bytes", "plan_modeled_time_s",
           "plan_needs_buffer", "plan_stage_lengths", "plan_wire_bytes",
           "plan_wire_dtypes", "validate_link_gbps"]

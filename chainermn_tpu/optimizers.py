"""Multi-node optimizers — gradient allreduce woven into the update step.

Reference being rebuilt (path unverified, SURVEY.md provenance):
〔chainermn/optimizers.py〕 — ``create_multi_node_optimizer(opt, comm,
double_buffering=False)`` wraps any Chainer optimizer so ``update()`` runs
local forward/backward, then ``comm.allreduce_grad(model)``, then the inner
update rule; ``_DoubleBufferingOptimizer`` (the fork's flagship) keeps two
gradient buffer sets and a dedicated CUDA stream so the allreduce of step
t-1's gradients overlaps the forward/backward of step t, applying averaged
gradients with one step of staleness.

TPU-native design: the wrapped object is an **optax GradientTransformation**
(the Chainer-optimizer role in the JAX world) and the overlap is expressed as
*dataflow*, not streams.  In :class:`_DoubleBufferingOptimizer`, ``update``
allreduces the gradients stored from the previous step and stashes the fresh
local gradients for the next one.  Inside the jitted train step the psum of
the stale gradients has no data dependency on the current forward/backward,
so the compiler is free to run the collective beside compute.  TPU XLA does
so only when told: on a TPU mesh of several devices the step is compiled with
the communicator's ``exchange_compiler_options`` (PERF.md, PR 29: what that
hides and what it does not).  The 1-step-staleness semantics (first update
applies zero gradients) are what changes convergence, and are kept exactly.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu.communicators import _packing
from chainermn_tpu.utils import pvary
from chainermn_tpu.utils.placement import local_device_put


class _MultiNodeOptimizer:
    """optax-compatible wrapper: allreduce-mean the grads, then inner update.

    Reference: ``_MultiNodeOptimizer`` 〔optimizers.py〕, which delegated all
    attributes to the wrapped optimizer; here the optax interface is two
    functions, so delegation is explicit (`init`/`update` + passthrough).

    ``compression`` (a stateless codec, i.e. :class:`NoCompression`) is
    forwarded to ``allreduce_grad`` per call — ``NoCompression(wire)``
    lowers to the exact cast-allreduce-cast program of the legacy
    ``allreduce_grad_dtype`` knob.  Stateful quantizers live in
    :class:`_CompressedOptimizer` instead (they thread EF state).
    """

    def __init__(self, actual_optimizer: optax.GradientTransformation, comm,
                 compression=None):
        self.actual_optimizer = actual_optimizer
        self.communicator = comm
        self.compression = compression

    def init(self, params):
        return self.actual_optimizer.init(params)

    def update(self, grads, state, params=None, **kwargs):
        grads = self.communicator.allreduce_grad(
            grads, compressor=self.compression)
        with jax.named_scope("chainermn.update"):
            return self.actual_optimizer.update(
                grads, state, params, **kwargs)

    # pytree spec of this optimizer's state inside an SPMD train step:
    # everything is device-invariant (replicated).
    def state_partition_spec(self):
        return P()


class _CompressedState(NamedTuple):
    inner: Any   # wrapped optimizer's state (replicated)
    comp: Any    # CompressionState — EF residual is per-rank (varying)


class _CompressedOptimizer:
    """Quantized gradient exchange: ``allreduce_grad(compressor=...)``
    with the error-feedback state carried inside the optimizer state —
    **beyond-reference extension** (see :mod:`chainermn_tpu.compression`).

    The EF residual is device-varying (each rank remembers ITS
    quantization error), so it rides the optimizer-state slot exactly the
    way the double-buffer's pending gradients do: stacked ``[size, ...]``
    outside the step, squeezed to the local state inside.
    """

    def __init__(self, actual_optimizer: optax.GradientTransformation, comm,
                 compression):
        self.actual_optimizer = actual_optimizer
        self.communicator = comm
        self.compression = compression

    def init(self, params):
        return _CompressedState(
            inner=self.actual_optimizer.init(params),
            comp=self.communicator.init_compression_state(
                params, self.compression))

    def update(self, grads, state, params=None, **kwargs):
        grads, comp = self.communicator.allreduce_grad(
            grads, compressor=self.compression, state=state.comp)
        with jax.named_scope("chainermn.update"):
            updates, inner = self.actual_optimizer.update(
                grads, state.inner, params, **kwargs)
        return updates, _CompressedState(inner=inner, comp=comp)

    def state_partition_spec(self):
        return _CompressedState(inner=P(), comp=_VARYING)


class _DoubleBufferState(NamedTuple):
    inner: Any            # wrapped optimizer's state (replicated)
    pending: Any          # previous step's *local* grads (device-varying),
    #                       matrices in the exchange's wire dtype if it has one
    step: jnp.ndarray     # update counter


class _DoubleBufferingOptimizer:
    """The fork's double-buffered optimizer, as dataflow.

    Semantics (reference 〔optimizers.py〕, SURVEY.md §3.4): update at step t
    applies the allreduced gradients of step t-1 (1-step staleness); step 0
    applies zero gradients (buffers start zero-filled).  The allreduce of the
    pending buffer depends on nothing step t computes; whether anything runs
    beside it is the compiler's: ``comm.exchange_compiler_options()`` (TPU).

    ``pending`` is kept in the dtype the exchange sends.  Where the
    communicator has a wire dtype (``allreduce_grad_dtype``) every leaf of
    two dimensions or more is rounded to it as it is stored, which is the
    rounding the next step's exchange applied to the same values, one step
    earlier: bit for bit the same update, from half the buffer, with no
    cast before the collective (PERF.md, PR 46).  The means come back in
    the fresh gradients' dtype (the parameters'), so the ``1 / size`` scale
    multiplies there (``allreduce_grad(like=)``).  A VECTOR keeps its own
    dtype and is cast at the read as before (a few ten-thousandths of the
    bytes): a bias gradient written as bfloat16 made TPU XLA fuse its
    reduction into the product that makes the activations' gradient, whose
    layout then cost a transposing copy a layer (PERF.md, PR 46).  A
    communicator without a wire dtype keeps every leaf as its gradient is
    and traces the program it always traced.
    """

    def __init__(self, actual_optimizer: optax.GradientTransformation, comm):
        self.actual_optimizer = actual_optimizer
        self.communicator = comm

    def init(self, params):
        wire = getattr(self.communicator, "allreduce_grad_dtype", None)
        zeros = jax.tree.map(
            lambda p: jnp.zeros_like(
                p, dtype=None if jnp.ndim(p) < 2 else wire), params)
        return _DoubleBufferState(
            inner=self.actual_optimizer.init(params),
            pending=zeros,
            step=jnp.zeros((), jnp.int32),
        )

    def update(self, grads, state, params=None, **kwargs):
        with jax.named_scope("chainermn.allreduce_grad"):
            # Step 0 applies zeros: the buffer starts so, and for a leaf
            # held in the wire dtype this select says so in the program.  It
            # changes no value and stands where the wire cast stood, because
            # the asynchronous exchange stands on it: TPU XLA gives an
            # all-reduce its start - steps - done form only where a fusion
            # of the step makes its operand, and one that reads the step's
            # ARGUMENT stays blocking (11 of the dp4 LM's 43 matrices;
            # PERF.md, PR 46).  It costs no pass of its own: the argument is
            # donated, so XLA copies it before an in-place all-reduce
            # anyway; on one device it rides in the update's fusion.
            stale = jax.tree.map(
                lambda held, g: held if held.dtype == g.dtype
                else jax.lax.select(
                    state.step != 0, held, jnp.zeros_like(held)),
                state.pending, grads)
        comm_grads = self.communicator.allreduce_grad(stale, like=grads)
        with jax.named_scope("chainermn.update"):
            updates, inner = self.actual_optimizer.update(
                comm_grads, state.inner, params, **kwargs)
            # the state's write: the fresh gradients in the dtype they are
            # held in (the exchange's wire cast, made where they are made)
            pending = jax.tree.map(
                lambda g, held: g.astype(held.dtype), grads, state.pending)
        new_state = _DoubleBufferState(
            inner=inner, pending=pending, step=state.step + 1)
        return updates, new_state

    def state_partition_spec(self):
        # ``pending`` holds per-device local grads — varying across the data
        # axes; inner state and counter are replicated.
        return _DoubleBufferState(
            inner=P(), pending=_VARYING, step=P())


# Sentinel replaced by the communicator's data axes in make_train_step.
_VARYING = "__varying__"


class _ZeroState(NamedTuple):
    inner: Any  # inner optax state over THIS device's flat shard (varying)


class _Zero1Optimizer:
    """ZeRO stage-1 optimizer-state sharding — **beyond-reference
    extension** (the reference had nothing like it; clearly labeled, like
    the other `parallel/` extensions).

    Each device owns 1/size of the flattened parameter space: gradients
    arrive via ``reduce_scatter`` (mean) as this device's shard, the inner
    optax update runs on the shard only — so optimizer state (e.g. Adam's
    m/v, 2x params) is divided by the world size — and the resulting
    update shards ``all_gather`` back to the full parameter vector, which
    stays replicated (stage 1: state sharded, params/grads not).

    Wire cost per step: the reduce-scatter leg is half a ring allreduce;
    the gather-back is a masked psum (~2x a ring gather's bytes — the
    price of an invariant-typed result, see the inline comment), so the
    total is ~1.5x one ring allreduce on the cheap ICI resource, while
    per-device optimizer memory drops by ~size.  The communicator's
    ``allreduce_grad_dtype`` (when set) applies to the reduce-scatter leg
    exactly as it applies to ``allreduce_grad``: cast in, reduce in the
    wire dtype, cast back before the inner update.  Inner optimizers
    whose ``init`` depends on parameter VALUES (not just shapes/dtypes)
    are unsupported — every standard optax rule
    (sgd/momentum/adam/adamw/...) initializes from shapes.  Layer-wise
    rules whose UPDATE depends on per-leaf structure (LARS/LAMB trust
    ratios) are also out: the flat per-dtype shards erase leaf
    boundaries, so the "layer-wise" norms would be shard-wise — silently
    different semantics (the ImageNet example rejects --zero with
    --optimizer lars for this reason).
    """

    def __init__(self, actual_optimizer: optax.GradientTransformation, comm,
                 compression=None):
        self.actual_optimizer = actual_optimizer
        self.communicator = comm
        self.compression = compression

    def _wire_dtype(self):
        """The reduce-scatter leg's wire dtype: an explicit
        ``NoCompression(wire_dtype)`` wins; else the communicator's legacy
        ``allreduce_grad_dtype`` knob (deprecated spelling of the same)."""
        if self.compression is not None and \
                getattr(self.compression, "wire", None) is not None:
            return self.compression.wire
        return getattr(self.communicator, "allreduce_grad_dtype", None)

    def _shard_zeros(self, params):
        """Zero-filled flat shards shaped like one device's slice —
        computed from leaf shapes alone (no transient full flat copy;
        mirrors _packing.pack's by-dtype grouping)."""
        size = self.communicator.size
        groups: dict = {}
        for leaf in jax.tree.leaves(params):
            key = str(leaf.dtype)
            n = 1
            for d in leaf.shape:
                n *= int(d)
            groups[key] = (groups.get(key, (0, leaf.dtype))[0] + n,
                           leaf.dtype)
        return [jnp.zeros(((n + (-n) % size) // size,), dt)
                for n, dt in groups.values()]

    def init(self, params):
        return _ZeroState(
            inner=self.actual_optimizer.init(self._shard_zeros(params)))

    def update(self, grads, state, params=None, **kwargs):
        comm = self.communicator
        size = comm.size
        idx = comm.axis_index()
        # honor the wire dtype (the pure_nccl fp16/bf16 recipe): cast in,
        # reduce in the wire dtype, cast back — same numerics as
        # allreduce_grad's cast-allreduce-cast path
        wire_dtype = self._wire_dtype()
        # both legs of the exchange carry allreduce_grad's scope, so that
        # "the gradient exchange" reads the same whatever the wrapper
        with jax.named_scope("chainermn.allreduce_grad"):
            g_bufs, meta = _packing.pack(grads)
            p_bufs, _ = _packing.pack(params) if params is not None else (
                [None] * len(g_bufs), None)
            g_shards, p_shards, strips = [], [], []
            for g, p in zip(g_bufs, p_bufs):
                g, strip = _packing.pad_to_multiple(g, size)
                strips.append(strip)
                orig_dtype = g.dtype
                if wire_dtype is not None and g.dtype != wire_dtype:
                    g = g.astype(wire_dtype)
                # reduce_scatter sums; the reference's allreduce_grad means
                gs = comm.reduce_scatter(g) / size
                g_shards.append(gs.astype(orig_dtype))
                if p is not None:
                    p, _ = _packing.pad_to_multiple(p, size)
                    p_shards.append(
                        jax.lax.dynamic_index_in_dim(
                            p.reshape(size, -1), idx, axis=0,
                            keepdims=False))
        with jax.named_scope("chainermn.update"):
            updates_sh, inner = self.actual_optimizer.update(
                g_shards, state.inner,
                p_shards if params is not None else None, **kwargs)
        # Gather-back as a masked psum rather than all_gather: value-
        # identical, but psum output is INVARIANT in JAX's varying-axes
        # type system, so the updated parameters keep their replicated
        # out_spec (same trick as the two_dimensional communicator's
        # gather-back leg; ~2x the bytes of a ring gather on the cheap
        # ICI resource).
        with jax.named_scope("chainermn.allreduce_grad"):
            upd_bufs = []
            for u, strip in zip(updates_sh, strips):
                placed = jax.lax.dynamic_update_slice_in_dim(
                    jnp.zeros((u.shape[0] * size,), u.dtype), u,
                    idx * u.shape[0], 0)
                upd_bufs.append(strip(comm.allreduce(placed, "sum")))
            updates = _packing.unpack(upd_bufs, meta)
        return updates, _ZeroState(inner=inner)

    def state_partition_spec(self):
        # the whole inner state lives on per-device shards
        return _ZeroState(inner=_VARYING)


def create_multi_node_optimizer(
    actual_optimizer: optax.GradientTransformation,
    communicator,
    double_buffering: bool = False,
    zero: bool = False,
    compression=None,
):
    """Reference signature: ``create_multi_node_optimizer(optimizer, comm,
    double_buffering)`` 〔optimizers.py〕.  ``actual_optimizer`` is an optax
    GradientTransformation (the Chainer-optimizer role).

    ``zero=True`` (beyond-reference extension) shards the optimizer state
    ZeRO-1-style over the communicator's devices — see
    :class:`_Zero1Optimizer`.  Mutually exclusive with ``double_buffering``
    (the pending-gradient buffer would defeat the memory saving).

    ``compression`` (beyond-reference extension) selects the gradient
    wire codec — a name (``"int8"``/``"fp8"``), dtype string, config
    dict, or :class:`~chainermn_tpu.compression.Compressor`.
    ``NoCompression(wire_dtype=...)`` reproduces the communicator-level
    ``allreduce_grad_dtype`` program bit for bit; the quantizers carry
    error-feedback state inside the optimizer state (initialize it with
    :func:`init_opt_state`, which places the per-rank EF residual).

    ``compression`` may also be a :class:`~chainermn_tpu.planner.Plan`
    whose stages carry per-hop ``Stage.compression`` specs (e.g.
    ``compressed_two_dimensional(...)``): the gradient exchange executes
    that plan with one EF state per quantized hop riding the optimizer
    state as a stage-indexed dict — the DynamiQ per-hop path."""
    from chainermn_tpu.compression import base as _cbase
    from chainermn_tpu.compression import quantize as _cq
    from chainermn_tpu.planner.compiler import plan_compressed_hops
    from chainermn_tpu.planner.ir import Plan as _Plan
    if isinstance(compression, _Plan):
        if zero or double_buffering:
            raise NotImplementedError(
                "compression=<Plan> (per-hop) composes with neither "
                "zero=True nor double_buffering=True — the per-hop EF "
                "states ride the plain compressed-optimizer state slot")
        if not plan_compressed_hops(compression,
                                    communicator.plan_topology()):
            raise ValueError(
                f"compression plan {compression.name!r} has no quantizing "
                "stages on this topology; pass the plan to "
                "create_communicator(plan_table=...) instead, or add "
                "Stage.compression specs")
        return _CompressedOptimizer(actual_optimizer, communicator,
                                    compression)
    compression = _cbase.resolve_compressor(compression)
    if zero and double_buffering:
        raise ValueError("zero=True and double_buffering=True are mutually "
                         "exclusive (the pending full-size gradient buffer "
                         "would defeat ZeRO's memory saving)")
    if _cq.is_quantizing(compression):
        if zero:
            raise NotImplementedError(
                "compression=<quantizer> with zero=True is not supported "
                "yet: ZeRO-1's reduce-scatter leg would need per-shard EF "
                "state (the bucketed FSDP engine has that — use "
                "fsdp_init(bucket_compressors=...))")
        if double_buffering:
            raise NotImplementedError(
                "compression=<quantizer> with double_buffering=True is not "
                "supported: stale-gradient buffering and error feedback "
                "both delay the update stream; composing them changes "
                "convergence semantics")
        return _CompressedOptimizer(actual_optimizer, communicator,
                                    compression)
    if zero:
        return _Zero1Optimizer(actual_optimizer, communicator,
                               compression=compression)
    if double_buffering:
        if compression is not None and compression.wire is not None:
            raise NotImplementedError(
                "compression=NoCompression(wire_dtype) with "
                "double_buffering=True: set allreduce_grad_dtype on the "
                "communicator instead (the pending-buffer allreduce "
                "honors it)")
        return _DoubleBufferingOptimizer(actual_optimizer, communicator)
    if compression is not None and compression.wire is None:
        compression = None  # bare NoCompression() is the do-nothing default
    return _MultiNodeOptimizer(actual_optimizer, communicator,
                               compression=compression)


def _resolve_spec(spec_tree, axes):
    is_sentinel = lambda s: isinstance(s, str) and s == _VARYING
    return jax.tree.map(
        lambda s: P(axes) if is_sentinel(s) else s,
        spec_tree,
        is_leaf=lambda s: isinstance(s, (P, str)),
    )


def make_train_step(
    communicator,
    loss_fn: Callable,
    optimizer,
    has_aux: bool = False,
    donate: bool = True,
    with_model_state: bool = False,
    accum_steps: int = 1,
):
    """Build the canonical jitted SPMD train step (the hot loop of SURVEY.md
    §3.2): per-device forward/backward on the local batch shard -> explicit
    ``allreduce_grad`` -> inner optimizer update, all in one XLA program.

    ``loss_fn(params, batch)`` sees the *local* batch shard, exactly like a
    reference rank saw its local minibatch.  Returns
    ``step(params, opt_state, batch) -> (params, opt_state, loss[, aux])``
    where ``batch`` leaves are sharded on their leading axis across the
    communicator's data axes.

    ``accum_steps=K`` (K > 1) — gradient accumulation: each device splits
    its local batch shard into K equal microbatches, runs forward/backward
    per microbatch under ``lax.scan``, and averages the K gradients before
    the ONE allreduce + optimizer update.  Because every microbatch loss
    is a mean over an equal slice, the averaged gradient equals the
    full-shard gradient exactly — same numerics as ``accum_steps=1`` (the
    parity test pins it bitwise-close), with peak activation memory
    divided by ~K.  That is the knob's purpose: fitting a reference
    global batch on fewer/smaller chips.  The exactness claim is scoped
    to batch-DECOMPOSABLE losses (a mean of independent per-sample
    terms).  Two caveats: (a) BatchNorm breaks decomposability — each
    microbatch normalizes over its own b/K samples, so the forward
    activations AND gradients differ from the full-shard computation
    (ghost-batch-norm semantics; smaller effective normalization batch),
    and the running statistics likewise update K times per step; (b) on
    TPU the scan body pins conv weight layouts
    (measured ~1.5x emitter regression for conv nets —
    docs/performance.md), so use it when memory demands it, not for
    speed.

    ``with_model_state=True`` adds a non-trainable mutable model state slot
    (flax ``batch_stats``) that stays **device-local** — the reference trains
    BatchNorm on local statistics and only syncs via ``AllreducePersistent``
    (SURVEY.md §7 hard part 5), so the state is carried stacked per-device
    ([size, ...], sharded over the data axes; see :func:`init_model_state`)
    and never reduced inside the step.  Signatures become
    ``loss_fn(params, model_state, batch) -> (loss, new_state)`` (or
    ``(loss, (new_state, aux))`` with ``has_aux``) and
    ``step(params, model_state, opt_state, batch) ->
    (params, model_state, opt_state, loss[, aux])``.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    comm = communicator
    axes = comm.data_axes
    state_spec = _resolve_spec(
        optimizer.state_partition_spec()
        if hasattr(optimizer, "state_partition_spec") else P(), axes)

    def step(params, model_state, opt_state, batch):
        if isinstance(opt_state, _DoubleBufferState):
            # The stacked pending buffer arrives as per-device [1, ...]
            # slices; inside the SPMD body it is this rank's local grads.
            opt_state = opt_state._replace(
                pending=jax.tree.map(lambda a: jnp.squeeze(a, 0),
                                     opt_state.pending))
        if isinstance(opt_state, _ZeroState):
            # stacked per-device shard states arrive as [1, ...] slices
            opt_state = _ZeroState(inner=jax.tree.map(
                lambda a: jnp.squeeze(a, 0), opt_state.inner))
        if isinstance(opt_state, _CompressedState):
            # stacked per-device EF state arrives as [1, ...] slices
            opt_state = opt_state._replace(comp=jax.tree.map(
                lambda a: jnp.squeeze(a, 0), opt_state.comp))
        if with_model_state:
            model_state = jax.tree.map(lambda a: jnp.squeeze(a, 0), model_state)
        # Mark the replicated params device-varying for the local backward:
        # otherwise shard_map's autodiff inserts an automatic psum when
        # differentiating the per-device loss w.r.t. invariant params, and
        # gradients would arrive pre-summed — the explicit allreduce below
        # (the reference's semantics) must be the only cross-device reduction.
        params_local = jax.tree.map(lambda p: pvary(p, axes), params)
        grad_fn = jax.value_and_grad(
            loss_fn, has_aux=has_aux or with_model_state)

        def compute(model_state, batch):
            if with_model_state:
                (loss, packed), grads = grad_fn(
                    params_local, model_state, batch)
                model_state, aux = packed if has_aux else (packed, None)
            elif has_aux:
                (loss, aux), grads = grad_fn(params_local, batch)
            else:
                loss, grads = grad_fn(params_local, batch)
                aux = None
            return loss, aux, model_state, grads

        # The step names its parts for the device trace: a scope is HLO
        # metadata, never a different program (docs/observability.md).
        with jax.named_scope("chainermn.grad"):
            if accum_steps > 1:
                from chainermn_tpu.utils.accum import accumulate_microbatches

                loss, aux, model_state, grads = accumulate_microbatches(
                    compute, model_state, batch, accum_steps, has_aux)
            else:
                loss, aux, model_state, grads = compute(model_state, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        with jax.named_scope("chainermn.update"):
            params = optax.apply_updates(params, updates)
        if isinstance(opt_state, _DoubleBufferState):
            # Anchor the loss/aux reporting reductions AFTER the parameter
            # update: XLA's all-reduce combiner otherwise merges them with
            # the pending-gradient psum into ONE collective, which then
            # cannot start until the loss (i.e. the whole forward) is ready
            # — squandering the overlap the double buffer exists for.  The
            # barrier makes merging a dependency cycle, so the gradient
            # psum keeps zero data dependencies and is schedulable from
            # program start.  (Found in the 8-device-mesh HLO; see
            # docs/performance.md "Double-buffering overlap".)
            anchor = jax.tree.leaves(params)[0]
            loss, anchor = jax.lax.optimization_barrier((loss, anchor))
            if aux is not None:
                aux, anchor = jax.lax.optimization_barrier((aux, anchor))
            opt_state = opt_state._replace(
                pending=jax.tree.map(lambda a: a[None], opt_state.pending))
        if isinstance(opt_state, _ZeroState):
            opt_state = _ZeroState(inner=jax.tree.map(
                lambda a: a[None], opt_state.inner))
        if isinstance(opt_state, _CompressedState):
            opt_state = opt_state._replace(comp=jax.tree.map(
                lambda a: a[None], opt_state.comp))
        if with_model_state:
            model_state = jax.tree.map(lambda a: a[None], model_state)
        with jax.named_scope("chainermn.report"):
            loss = comm.allreduce(loss, "mean")
            if has_aux:
                aux = comm.allreduce(aux, "mean")
        outs = (params, model_state, opt_state, loss, aux)
        keep = (True, with_model_state, True, True, has_aux)
        return tuple(o for o, k in zip(outs, keep) if k)

    out_spec_all = (P(), P(axes), state_spec, P(), P())
    keep = (True, with_model_state, True, True, has_aux)
    out_specs = tuple(s for s, k in zip(out_spec_all, keep) if k)
    in_specs = ((P(), P(axes), state_spec, P(axes)) if with_model_state
                else (P(), state_spec, P(axes)))
    inner = step
    if not with_model_state:
        def inner(params, opt_state, batch):  # noqa: F811
            return step(params, None, opt_state, batch)
    mapped = jax.shard_map(
        inner,
        mesh=comm.mesh,
        in_specs=in_specs,
        out_specs=out_specs,
    )
    donate_argnums = ((0, 1, 2) if with_model_state else (0, 1)) if donate else ()
    # An optimizer that exchanges gradients has the communicator say how the
    # step is compiled (asynchronous all-reduces on a TPU mesh of several
    # devices; None, XLA's defaults, everywhere else).
    options = (comm.exchange_compiler_options()
               if hasattr(optimizer, "communicator") else None)
    return jax.jit(mapped, donate_argnums=donate_argnums,
                   compiler_options=options)


class PerStageOptimizer:
    """Optimizer for model-parallel parameter lists (``MultiNodeChainList``):
    one optax state per stage, each update jitted on that stage's devices.

    A single optax update over the whole list would jit one computation over
    leaves committed to disjoint device groups, which XLA rejects; stage-wise
    application is also what the reference does (each rank updates only its
    own sub-chain's parameters).
    """

    def __init__(self, actual_optimizer: optax.GradientTransformation):
        self.actual_optimizer = actual_optimizer
        self._jit_update = jax.jit(actual_optimizer.update)
        self._jit_apply = jax.jit(optax.apply_updates)

    def init(self, params_list):
        return [self.actual_optimizer.init(p) for p in params_list]

    def update(self, grads_list, states, params_list):
        if not (len(grads_list) == len(states) == len(params_list)):
            raise ValueError(
                f"stage count mismatch: {len(grads_list)} grads, "
                f"{len(states)} states, {len(params_list)} params — "
                "re-init the optimizer after changing the chain list")
        new_params, new_states = [], []
        for g, s, p in zip(grads_list, states, params_list):
            updates, s2 = self._jit_update(g, s, p)
            new_params.append(self._jit_apply(p, updates))
            new_states.append(s2)
        return new_params, new_states


def create_per_stage_optimizer(actual_optimizer: optax.GradientTransformation):
    return PerStageOptimizer(actual_optimizer)


def init_model_state(communicator, model_state):
    """Stack per-device copies of initial mutable model state (``batch_stats``)
    into the device-local layout ``make_train_step(with_model_state=True)``
    expects: leading axis == communicator.size, sharded over the data axes.
    Every device starts from the same (typically zero/one-initialized) stats,
    then they drift apart — local BN, the reference's semantics."""
    comm = communicator
    stacked = jax.tree.map(
        lambda z: jnp.broadcast_to(z, (comm.size,) + z.shape), model_state)
    # identical on every rank — placement stays process-local
    # (utils/placement.py: cross-process device_put is order-hazardous)
    return local_device_put(
        stacked, NamedSharding(comm.mesh, P(comm.data_axes)))


def init_opt_state(communicator, optimizer, params):
    """Initialize optimizer state with the right shardings: replicated inner
    state; for double buffering, a stacked per-device ``pending`` buffer
    (leading axis == communicator.size) sharded over the data axes, in the
    dtype the optimizer's ``init`` gives it (matrices in the communicator's
    wire dtype where it has one, everything else in the parameters')."""
    comm = communicator
    state = optimizer.init(params)
    if isinstance(state, _ZeroState):
        # every device's shard state starts as identical zeros; stack to
        # the device-local layout ([size, ...] sharded over the data axes)
        stacked = jax.tree.map(
            lambda z: jnp.broadcast_to(z, (comm.size,) + z.shape),
            state.inner)
        return _ZeroState(inner=local_device_put(
            stacked, NamedSharding(comm.mesh, P(comm.data_axes))))
    if isinstance(state, _CompressedState):
        # inner replicated; EF state stacked per device (each rank owns
        # its residual; scale/step start — and stay — rank-identical)
        stacked = jax.tree.map(
            lambda z: jnp.broadcast_to(z, (comm.size,) + z.shape),
            state.comp)
        return _CompressedState(
            inner=local_device_put(state.inner,
                                   NamedSharding(comm.mesh, P())),
            comp=local_device_put(
                stacked, NamedSharding(comm.mesh, P(comm.data_axes))))
    if not isinstance(state, _DoubleBufferState):
        return local_device_put(state, NamedSharding(comm.mesh, P()))
    stacked_pending = jax.tree.map(
        lambda z: jnp.zeros((comm.size,) + z.shape, z.dtype), state.pending)
    return _DoubleBufferState(
        inner=local_device_put(state.inner, NamedSharding(comm.mesh, P())),
        pending=local_device_put(
            stacked_pending, NamedSharding(comm.mesh, P(comm.data_axes))),
        step=local_device_put(state.step, NamedSharding(comm.mesh, P())),
    )

"""ZeRO-3 / FSDP — fully-sharded data parallelism, bucketed.

**Beyond-reference extension** (the reference shards nothing: params,
grads, and optimizer state are replicated per GPU — SURVEY.md §2.4; this
module is labeled exactly like the other `parallel/` extensions).  It
completes the ZeRO ladder the rebuild already climbs:
`create_multi_node_optimizer(zero=True)` is stage 1 (optimizer state
sharded, gradients reduce-scattered); here the PARAMETERS are sharded
too — each device persistently stores 1/size of the flattened parameter
space plus the inner optimizer state over that shard, and the full
parameter set exists only transiently inside the train step.

TPU-native design — the stage-3 communication pattern is K explicit
collectives plus their autodiff transposes, where K is the number of
parameter BUCKETS (`parallel/buckets.py` cuts the pytree into ~N
size-balanced contiguous buckets along leaf boundaries, deterministic
across ranks by construction):

* forward: the step ``all_gather``\\ s each bucket's flat shards over the
  data axes and unpacks them into that bucket's leaves (a
  device-varying, transient full copy — exactly the memory the forward
  needs anyway).  With ``num_buckets > 1`` the gathers are ISSUED IN
  BUCKET ORDER under a prefetch window of depth D
  (``prefetch``): bucket i's gather is pinned — via an
  ``optimization_barrier`` whose custom VJP also pins the transpose — to
  start only after bucket i-1-D's gather completed, so at most D+1
  gathers are in flight and XLA's latency-hiding scheduler can overlap
  bucket i+1's ICI traffic with bucket i's MXU compute;
* backward: differentiating *with respect to the shards* makes JAX
  transpose each bucket's all_gather into its own ``reduce_scatter`` of
  that bucket's gradients — the ZeRO-2/3 gradient path falls out of the
  chain rule per bucket instead of one giant transpose-derived
  collective (the reference's NCCL world would need explicit bucketed
  reduce-scatter calls; here the bucketing IS the schedule);
* update: the inner optax rule runs on the local shards only, so its
  state (Adam m/v = 2x params) is divided by the world size, and the
  updated shards feed the next step's gathers.

``num_buckets=1`` (the default) reproduces the monolithic
single-collective schedule bit for bit — no barriers are inserted and
the traced program is unchanged.  Per-step wire cost is unchanged by
bucketing: all_gather(params) + reduce_scatter(grads) ≈ one ring
allreduce of the parameter bytes, on the cheap ICI resource; what
changes is that the pieces can hide behind compute.  The CPU test mesh
cannot *time* that overlap — `benchmarks/bench_fsdp_overlap.py` pins the
schedule structurally (K gathers, K scatters, barrier count) and
`tools/multichip_day1.sh` carries the on-chip measurement leg.

Same caveat as ZeRO-1: the flat per-bucket shards erase leaf boundaries,
so inner rules whose update depends on per-leaf structure (LARS/LAMB
trust ratios) get shard-wise — i.e. wrong — semantics; use element-wise
rules (sgd/momentum/adam/adamw/...).  BatchNorm state stays device-local
and un-sharded (the reference's local-BN semantics, SURVEY.md §7 hard
part 5).
"""

from __future__ import annotations

import types
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu.utils.placement import local_device_put

from chainermn_tpu.communicators import _packing
from chainermn_tpu.parallel import buckets as _buckets


def _reject_multi_node_wrapper(optimizer):
    """FSDP takes a PLAIN optax rule: a multi-node wrapper's allreduce
    inside the step would sum unrelated parameter shards across devices —
    silent corruption, so refuse it loudly."""
    from chainermn_tpu import optimizers as _opt

    if isinstance(optimizer, (_opt._MultiNodeOptimizer,
                              _opt._DoubleBufferingOptimizer,
                              _opt._Zero1Optimizer)):
        raise TypeError(
            "fsdp takes a plain optax GradientTransformation, not a "
            "create_multi_node_optimizer wrapper — the gather/scatter "
            "collectives ARE the multi-node integration here")


# optax's layer-wise rules all funnel through scale_by_trust_ratio (the
# LARS/LAMB trust-ratio transform); its qualname survives inside the
# closure of a chain()'s update function, which is what we walk below.
_LAYERWISE_QUALNAMES = ("scale_by_trust_ratio", "_scale_by_trust_ratio")


def _contains_layerwise_rule(fn, _depth: int = 0, _seen=None) -> bool:
    """Walk a transformation's update function (and the functions captured
    in its closure cells — ``optax.chain`` stores its ``update_fns`` tuple
    there) looking for a trust-ratio rule."""
    if (not isinstance(fn, types.FunctionType) or _depth > 6
            or (_seen is not None and id(fn) in _seen)):
        return False
    _seen = set() if _seen is None else _seen
    _seen.add(id(fn))
    if getattr(fn, "__qualname__", "").startswith(_LAYERWISE_QUALNAMES):
        return True
    for cell in fn.__closure__ or ():
        try:
            v = cell.cell_contents
        except ValueError:
            continue
        for x in (v if isinstance(v, (list, tuple)) else [v]):
            if isinstance(x, types.FunctionType) \
                    and _contains_layerwise_rule(x, _depth + 1, _seen):
                return True
            u = getattr(x, "update", None)
            if isinstance(u, types.FunctionType) \
                    and _contains_layerwise_rule(u, _depth + 1, _seen):
                return True
    return False


def _reject_layerwise_optimizer(optimizer):
    """LARS/LAMB trust ratios are per-LAYER norms; FSDP's flat per-bucket
    shards erase leaf boundaries, so the rule would silently compute
    shard-wise — i.e. wrong — ratios (ADVICE r5).  Detect and refuse;
    ``fsdp_init(..., allow_layerwise=True)`` is the explicit override for
    rules we misidentify or users who accept shard-wise semantics."""
    u = getattr(optimizer, "update", None)
    if isinstance(u, types.FunctionType) and _contains_layerwise_rule(u):
        raise ValueError(
            "optimizer contains a layer-wise trust-ratio rule (optax "
            "lars/lamb): FSDP flattens parameters into per-bucket shards, "
            "so trust ratios would be computed over arbitrary shard "
            "boundaries instead of layers — silently wrong updates. Use "
            "an element-wise rule (sgd/momentum/adam/adamw/...), or pass "
            "allow_layerwise=True to fsdp_init if you explicitly want "
            "shard-wise semantics.")


# ---- schedule pinning -------------------------------------------------------
# lax.optimization_barrier has no autodiff rule on the jax versions this
# rebuild supports; the custom VJP makes the pin differentiable AND
# mirrors it onto the cotangents, so the backward's per-bucket
# reduce-scatters inherit the same windowed ordering in reverse.

@jax.custom_vjp
def _sched_barrier(xs):
    return lax.optimization_barrier(xs)


def _sched_barrier_fwd(xs):
    return lax.optimization_barrier(xs), None


def _sched_barrier_bwd(_, cts):
    return (lax.optimization_barrier(cts),)


_sched_barrier.defvjp(_sched_barrier_fwd, _sched_barrier_bwd)


class BucketLayout(NamedTuple):
    """Static layout of ONE parameter bucket: a contiguous ``[start,
    stop)`` run of the flattened leaf order, packed into per-dtype flat
    buffers exactly like the monolithic layout used to be."""
    start: int              # first leaf index (flatten order, inclusive)
    stop: int               # last leaf index (exclusive)
    pack_meta: Any          # _packing meta over this bucket's leaf list
    orig_lens: tuple        # unpadded flat length per dtype buffer
    shard_lens: tuple       # per-device shard length per dtype buffer
    pads: tuple             # pad appended to each buffer (len = world pad)
    nbytes: int             # unpadded payload bytes of the bucket
    wire_dtype: Optional[str] = None  # per-bucket wire override (or None)
    compressor: Optional[str] = None  # quantizer spec JSON (or None)


class FsdpMeta(NamedTuple):
    """Static (host-side) layout of the bucketed sharded parameter space."""
    treedef: Any                    # full parameter pytree structure
    n_leaves: int
    buckets: tuple                  # tuple[BucketLayout, ...]

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    @property
    def shard_lens(self) -> tuple:
        """Flat per-buffer shard lengths across buckets (compat view —
        ``sum(meta.shard_lens) * size`` bounds the padded parameter
        count exactly as in the monolithic layout)."""
        return tuple(l for b in self.buckets for l in b.shard_lens)

    @property
    def orig_lens(self) -> tuple:
        return tuple(l for b in self.buckets for l in b.orig_lens)


class FsdpState(NamedTuple):
    """Per-device persistent state: ``shards`` is a list (one entry per
    bucket) of lists of stacked [size, shard] leaves, sharded over the
    communicator's data axes (same layout convention as the ZeRO-1 inner
    state and the double-buffer pending grads).  ``comp`` (compressed
    buckets only) carries one stacked
    :class:`~chainermn_tpu.compression.CompressionState` per bucket —
    each rank's error-feedback residual over the bucket's full flat
    buffer plus its OWN delayed scale exponent; ``()`` when no bucket is
    quantized (the layout old checkpoints saved)."""
    shards: Any             # [bucket][buffer] -> [size, shard_len] params
    inner: Any              # inner optax state over the (squeezed) shards
    comp: Any = ()          # [bucket] -> CompressionState | None (or ())


def _normalize_wire(dtype) -> Optional[jnp.dtype]:
    if dtype is None:
        return None
    wire = jnp.dtype(dtype)
    if not jnp.issubdtype(wire, jnp.floating):
        raise ValueError(
            f"wire_dtype must be a floating dtype, got {wire} — an "
            f"integer wire would truncate the gathered parameters")
    return wire


def fsdp_init(communicator, params, optimizer,
              allow_layerwise: bool = False,
              num_buckets: int = 1,
              bucket_bytes: Optional[int] = None,
              bucket_wire_dtypes: Optional[Sequence] = None,
              bucket_compressors=None):
    """Shard ``params`` for stage-3 training.

    Returns ``(state, meta)``: ``state`` is the :class:`FsdpState` whose
    leaves live sharded on the mesh; ``meta`` is the static bucketed
    layout that :func:`make_fsdp_train_step` and :func:`fsdp_full_params`
    need.  ``optimizer`` is a plain optax rule (NOT a multi-node wrapper —
    the collective pattern here IS the multi-node integration) and must
    be element-wise: layer-wise trust-ratio rules (optax lars/lamb) are
    detected and rejected because the flat shards erase layer boundaries;
    ``allow_layerwise=True`` overrides if you accept shard-wise ratios.

    Bucketing knobs (see ``parallel/buckets.py``):

    * ``num_buckets=K`` — cut the parameter pytree into K size-balanced
      contiguous buckets; the train step then runs K all-gathers and K
      reduce-scatters that can overlap with compute.  The default 1 is
      the monolithic schedule (bit-for-bit the pre-bucketing step).
    * ``bucket_bytes`` — derive the count from a per-bucket size target
      instead (``num_buckets`` wins when both are given).
    * ``bucket_wire_dtypes`` — optional per-bucket wire-dtype override
      list (entries None fall back to the step's ``wire_dtype``), e.g.
      keep embedding buckets on a full-precision wire while the
      transformer-block buckets ride bf16.
    * ``bucket_compressors`` — per-bucket gradient wire codec (single
      value broadcast to all buckets, or a K-list; names / dtype strings
      / config dicts / :class:`~chainermn_tpu.compression.Compressor`
      instances, see :func:`~chainermn_tpu.compression.\
resolve_compressor`).  ``NoCompression(wire_dtype=...)`` folds into the
      bucket's ``wire_dtype`` (identical program); a quantizer
      (``"int8"``/``"fp8"``) makes that bucket's gradient reduce-scatter
      run over 1-byte codes with per-rank error feedback, carried in
      ``state.comp`` — note the EF residual is full-bucket-sized per
      rank (the standard EF memory cost).
    """
    _reject_multi_node_wrapper(optimizer)
    if not allow_layerwise:
        _reject_layerwise_optimizer(optimizer)
    from chainermn_tpu.compression import base as _cbase
    from chainermn_tpu.compression import error_feedback as _cef
    from chainermn_tpu.compression import quantize as _cq
    comm = communicator
    size = comm.size
    leaves, treedef = jax.tree.flatten(params)
    assignments = _buckets.partition_buckets(
        leaves, num_buckets=num_buckets if bucket_bytes is None or
        num_buckets != 1 else None, bucket_bytes=bucket_bytes)
    if bucket_wire_dtypes is not None \
            and len(bucket_wire_dtypes) != len(assignments):
        raise ValueError(
            f"bucket_wire_dtypes has {len(bucket_wire_dtypes)} entries "
            f"but the partition produced {len(assignments)} buckets")
    if bucket_compressors is None:
        bucket_compressors = [None] * len(assignments)
    elif not isinstance(bucket_compressors, (list, tuple)):
        bucket_compressors = [bucket_compressors] * len(assignments)
    elif len(bucket_compressors) != len(assignments):
        raise ValueError(
            f"bucket_compressors has {len(bucket_compressors)} entries "
            f"but the partition produced {len(assignments)} buckets")
    bucket_compressors = [_cbase.resolve_compressor(c)
                          for c in bucket_compressors]
    layouts, stacked, comp_states = [], [], []
    for a in assignments:
        bufs, pack_meta = _packing.pack(list(leaves[a.start:a.stop]))
        orig_lens, pads, bucket_stacked = [], [], []
        for b in bufs:
            orig_lens.append(int(b.shape[0]))
            b, strip = _packing.pad_to_multiple(b, size)
            pads.append(int(strip))
            bucket_stacked.append(b.reshape(size, -1))
        wire = None
        if bucket_wire_dtypes is not None \
                and bucket_wire_dtypes[a.index] is not None:
            wire = str(_normalize_wire(bucket_wire_dtypes[a.index]))
        comp = bucket_compressors[a.index]
        comp_spec, cstate = None, None
        if isinstance(comp, _cbase.NoCompression):
            # the identity codec IS the wire-dtype knob: fold it in so
            # the step traces the exact uncompressed program
            if comp.wire is not None:
                if wire is not None and wire != str(comp.wire):
                    raise ValueError(
                        f"bucket {a.index}: bucket_wire_dtypes={wire!r} "
                        f"conflicts with bucket_compressors="
                        f"NoCompression(wire_dtype={comp.wire_dtype!r}) "
                        "— pass only one spelling")
                wire = str(comp.wire)
        elif _cq.is_quantizing(comp):
            # quantizers ride ONE flat float buffer per bucket; mixed
            # dtype groups would need per-group EF state
            if len(bucket_stacked) != 1 or not jnp.issubdtype(
                    bucket_stacked[0].dtype, jnp.floating):
                raise NotImplementedError(
                    f"bucket {a.index}: compressor {comp.name!r} needs a "
                    f"single float packed buffer, got "
                    f"{[str(s.dtype) for s in bucket_stacked]} — keep "
                    "integer/mixed-dtype leaves in an uncompressed "
                    "bucket")
            comp.clip_limit(size)  # raise early at unworkable world sizes
            comp_spec = comp.spec
            n_full = int(bucket_stacked[0].shape[1]) * size
            cstate = _cef.CompressionState(
                ef=jnp.zeros((n_full,), jnp.float32),
                scale=jnp.zeros((1,), jnp.float32),
                step=jnp.zeros((1,), jnp.float32),
                spec=comp.spec, ef_version=_cef.EF_VERSION)
        elif comp is not None:
            raise TypeError(f"bucket {a.index}: cannot use {comp!r} as a "
                            "bucket compressor")
        layouts.append(BucketLayout(
            start=a.start, stop=a.stop, pack_meta=pack_meta,
            orig_lens=tuple(orig_lens),
            shard_lens=tuple(int(s.shape[1]) for s in bucket_stacked),
            pads=tuple(pads), nbytes=a.nbytes, wire_dtype=wire,
            compressor=comp_spec))
        stacked.append(bucket_stacked)
        comp_states.append(cstate)
    meta = FsdpMeta(treedef=treedef, n_leaves=len(leaves),
                    buckets=tuple(layouts))
    # inner state over one device's shard shapes (identical zeros on every
    # device at init, so broadcasting the stack is exact)
    inner = optimizer.init([[jnp.zeros((l,), s.dtype)
                             for l, s in zip(bl.shard_lens, bufs)]
                            for bl, bufs in zip(meta.buckets, stacked)])
    stacked_inner = jax.tree.map(
        lambda z: jnp.broadcast_to(z, (size,) + z.shape), inner)
    sharding = NamedSharding(comm.mesh, P(comm.data_axes))
    if all(c is None for c in comp_states):
        comp_out = ()
    else:
        comp_out = local_device_put(
            jax.tree.map(
                lambda z: jnp.broadcast_to(z, (size,) + z.shape),
                comp_states),
            sharding)
    # every rank computes the full stacks — placement stays
    # process-local (utils/placement.py)
    return FsdpState(
        shards=local_device_put(stacked, sharding),
        inner=local_device_put(stacked_inner, sharding),
        comp=comp_out,
    ), meta


def iter_fsdp_states(tree):
    """Yield every :class:`FsdpState` inside a python container tree
    (the checkpoint-state dicts of the examples: ``{"fsdp": state}``).
    Walks dicts/lists/tuples only — the FsdpState itself is the leaf."""
    if isinstance(tree, FsdpState):
        yield tree
    elif isinstance(tree, dict):
        for v in tree.values():
            yield from iter_fsdp_states(v)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            yield from iter_fsdp_states(v)


def fsdp_layout(tree) -> Optional[dict]:
    """Sharding layout of every FsdpState in ``tree`` (None when there is
    none): the world size baked into the stacked [size, shard] leaves,
    the bucket count, and the per-bucket shard lengths.  The multi-node
    checkpointer persists this next to the arrays so a resume into a
    different world size, bucket config, or an unsharded state fails
    loudly instead of restoring garbage."""
    states = list(iter_fsdp_states(tree))
    if not states:
        return None
    sizes = sorted({int(jnp.shape(b)[0])
                    for st in states for b in jax.tree.leaves(st.shards)})
    n_buckets = sorted({len(st.shards) for st in states})
    layout = {
        "world_size": sizes[0] if len(sizes) == 1 else sizes,
        "num_buckets": n_buckets[0] if len(n_buckets) == 1 else n_buckets,
        "shard_lens": [[[int(jnp.shape(b)[1]) for b in bucket]
                        for bucket in st.shards] for st in states],
        "n_states": len(states),
    }
    # bucket compression rides the same sidecar, but ONLY when present —
    # uncompressed layouts stay byte-identical to pre-compression saves
    from chainermn_tpu.compression import compression_layout as _clayout
    comp = _clayout([getattr(st, "comp", ()) for st in states])
    if comp is not None:
        layout["compression"] = comp
    return layout


def fsdp_full_params(state: FsdpState, meta: FsdpMeta):
    """Materialize the full (replicated) parameter pytree from the
    bucketed shards — for evaluation, checkpointing, or export.  No
    collective and no communicator needed: outside the step the stacked
    [size, shard] leaves ARE the full buffers, just reshaped (XLA
    resolves the cross-device reads when the result is consumed)."""
    leaves = []
    for bl, bufs in zip(meta.buckets, state.shards):
        flat = [b.reshape(-1)[:n] for b, n in zip(bufs, bl.orig_lens)]
        leaves.extend(_packing.unpack(flat, bl.pack_meta))
    return jax.tree.unflatten(meta.treedef, leaves)


def _leg_scope(leg: str, bucket: int):
    """The named scope of one bucket's collective:
    ``chainermn.fsdp.gather.<i>`` around its all-gather,
    ``chainermn.fsdp.scatter.<i>`` around the reduce-scatter in the
    transpose (the naming rule, docs/observability.md)."""
    return jax.named_scope(
        f"chainermn.fsdp.{leg}.{bucket}")


# ---- quantized bucket exchange ---------------------------------------------

def _make_compressed_gather(comp, layout, wire, axis_arg, size,
                            bucket: int):
    """The custom-VJP gather for ONE quantized bucket — the seam where
    compression meets the bucketed schedule.

    Forward: ``all_gather`` of ``concat(shard, own_scale_exponent)`` —
    the 1-slot piggyback redistributes every rank's delayed scale on the
    parameter gather itself, so the backward quantizes against the full,
    rank-identical exponent vector with ZERO extra collectives (power-of-
    two exponents are exactly representable in any float wire dtype).

    Backward — the compressed reduce-scatter: error-feedback add, encode
    to overflow-safe wire codes (clipped to ``max_code/size`` so in-wire
    summation cannot saturate), append one saturation flag per
    destination shard (the clip count rides the scatter, mirroring the
    forward's exponent piggyback), ``psum_scatter`` the CODES in wire
    arithmetic, decode this rank's summed shard by its OWN scale, and
    update the owned exponent from the summed amax and clip count —
    without the count, gradient cancellation across ranks keeps the
    summed amax small while every rank clips, wedging the scale below
    the signal forever.  The new
    :class:`~chainermn_tpu.compression.CompressionState` leaves the
    backward as the *cotangent of the state input*: ``jax.grad`` over a
    ``(shards, comp)`` carry hands it back alongside the gradient
    shards, which is what lets the EF state thread through
    ``jax.value_and_grad`` without restructuring the step.

    The collectives run under :func:`_leg_scope`, the quantizer under
    ``chainermn.compress`` / ``chainermn.decompress``.
    """
    L = int(layout.shard_lens[0])

    @jax.custom_vjp
    def cgather(shard, cstate):
        full, _ = _fwd(shard, cstate)
        return full

    def _fwd(shard, cstate):
        orig = shard.dtype
        ext = jnp.concatenate([shard.astype(jnp.float32),
                               cstate.scale.astype(jnp.float32)])
        if wire is not None:
            ext = ext.astype(wire)
        with _leg_scope("gather", bucket):
            g = lax.all_gather(ext, axis_arg, tiled=True)
        g = g.reshape(size, L + 1)
        full = g[:, :L].reshape(-1).astype(orig)
        e_vec = g[:, L].astype(jnp.float32)
        return full, (e_vec, cstate)

    def _bwd(res, ct):
        e_vec, cstate = res
        rank = lax.axis_index(axis_arg)
        scale_pos = jnp.repeat(jnp.exp2(e_vec), L)
        with jax.named_scope("chainermn.compress"):
            v = ct.astype(jnp.float32) + cstate.ef
            key = comp.make_key(cstate.step[0], rank)
            codes = comp.encode(v, scale_pos, key, size)
            new_ef = v - comp.decode(codes, scale_pos)
            flags = comp.saturation_flags(v, scale_pos, size, L)
            ext = jnp.concatenate([codes.reshape(size, L), flags[:, None]],
                                  axis=1).reshape(-1)
        with _leg_scope("scatter", bucket):
            summed = lax.psum_scatter(ext, axis_arg, tiled=True)
        with jax.named_scope("chainermn.decompress"):
            # my slot of e_vec is my own (current) exponent by
            # construction; the trailing slot is my shard's summed clip
            # count
            gshard = (summed[:L].astype(jnp.float32)
                      * jnp.exp2(cstate.scale[0]))
            amax = jnp.max(jnp.abs(gshard))[None]
            new_e = comp.next_exponent(cstate.scale, amax, size,
                                       summed[L:].astype(jnp.float32))
        new_state = cstate._replace(ef=new_ef, scale=new_e,
                                    step=cstate.step + 1.0)
        return gshard.astype(ct.dtype), new_state

    cgather.defvjp(_fwd, _bwd)
    return cgather


def _make_gather(axis_arg, bucket: int):
    """The all-gather of ONE plain bucket with its transpose, the
    gradients' reduce-scatter, spelled out: what autodiff would derive,
    written as a custom VJP so that each leg runs under a scope of its
    own in the device trace (:func:`_leg_scope`)."""

    @jax.custom_vjp
    def gather(s):
        with _leg_scope("gather", bucket):
            return lax.all_gather(s, axis_arg, tiled=True)

    def _fwd(s):
        return gather(s), None

    def _bwd(_, ct):
        with _leg_scope("scatter", bucket):
            return (lax.psum_scatter(ct, axis_arg, tiled=True),)

    gather.defvjp(_fwd, _bwd)
    return gather


def make_fsdp_train_step(
    communicator,
    loss_fn: Callable,
    optimizer,
    meta: FsdpMeta,
    has_aux: bool = False,
    donate: bool = True,
    with_model_state: bool = False,
    wire_dtype=None,
    accum_steps: int = 1,
    batch_spec=None,
    global_loss: bool = False,
    check_vma: bool = True,
    prefetch: int = 1,
):
    """Build the jitted stage-3 SPMD train step over the bucketed layout.

    ``loss_fn(params, batch)`` (or ``loss_fn(params, model_state, batch)``
    with ``with_model_state=True``) sees the full parameter pytree and the
    local batch shard, exactly like :func:`make_train_step`'s — FSDP is a
    storage/communication strategy, not a modeling change.  Returns
    ``step(state, batch) -> (state, loss[, aux])`` (model-state variants
    insert their slot like ``make_train_step``).  ``batch`` leaves are
    sharded on their leading axis over the data axes; the loss reported is
    the global mean.

    ``prefetch`` (depth D, default 1) governs the bucketed schedule when
    ``meta.num_buckets > 1``: bucket i's all-gather is pinned to issue
    only after bucket i-1-D's gather completed, bounding in-flight
    gathers to D+1 and giving XLA's latency-hiding scheduler a window to
    overlap bucket i+1's ICI with bucket i's compute.  The pin is an
    ``optimization_barrier`` with a custom VJP, so the backward's
    per-bucket reduce-scatters inherit the mirrored window in reverse.
    With one bucket no barrier is inserted and the step is the
    monolithic schedule unchanged.

    ``wire_dtype`` (e.g. ``"bfloat16"``) casts each float shard to the
    wire dtype before the all_gather and back after — and because the
    backward is the transpose of that chain, the gradient reduce-scatter
    runs in the wire dtype too.  This is the fork's fp16-allreduce idea
    (`allreduce_grad_dtype`) applied to stage 3's BOTH collectives:
    half the gather bytes and half the scatter bytes, with the same
    numerics tradeoff (the reduction accumulates in the wire dtype).
    A per-bucket override in ``meta`` (``fsdp_init(...,
    bucket_wire_dtypes=...)``) wins over this step-wide default.  Master
    shards and the inner optimizer state stay full precision.  Non-float
    buffers (int params, if any) are never cast.

    ``accum_steps=K`` — gradient accumulation with the same semantics as
    :func:`chainermn_tpu.optimizers.make_train_step`'s: K equal
    microbatches per device under ``lax.scan``, averaged gradients, one
    update per optimizer step.  The gather/scatter pair runs per
    MICROBATCH (each scan iteration re-gathers the params and
    reduce-scatters its gradients — K× the collective bytes, the
    standard FSDP-accumulation trade), but the gradient accumulator
    lives at SHARD size and the transient full params are freed between
    microbatches — exactly the memory posture stage 3 exists for.
    Exact for batch-decomposable losses; BatchNorm models get
    ghost-batch semantics (see make_train_step's docstring).

    **Composing with sequence/context parallelism** (FSDP over the
    sequence-parallel group — how long-context training ships: each
    device computes its SEQUENCE shard with the full gathered params):

    * ``batch_spec`` — PartitionSpec for the batch leaves (default
      ``P(axes)``: data-parallel leading-axis sharding).  Pass e.g.
      ``P(None, "sp")`` for sequence-sharded tokens.
    * ``global_loss=True`` — declare that ``loss_fn`` already reduces
      to the GLOBAL scalar itself (``lax.psum`` over the mesh axes, like
      a sequence-parallel objective must).  The step then skips both its
      /size gradient normalization (the transpose-summed shard grads ARE
      the global gradient of a psum'd loss) and its final loss/aux
      allreduce.  With the default ``False``, ``loss_fn`` returns the
      LOCAL mean and the step applies reference ``allreduce_grad``
      (mean) semantics.  With ``has_aux``, the aux leaves must be
      globally reduced the same way — a device-local aux violates the
      invariant out_spec and is rejected by the vma check at trace
      time (do NOT disable ``check_vma`` while returning local aux:
      that would silently report one device's value as global).
    * ``check_vma`` — forwarded to ``shard_map`` (Pallas interpret mode
      on the CPU backend trips a dynamic_slice vma check; TPU compiled
      runs keep it True).
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if prefetch < 0:
        raise ValueError(f"prefetch must be >= 0, got {prefetch}")
    _reject_multi_node_wrapper(optimizer)
    comm = communicator
    axes = comm.data_axes
    axis_arg = axes if len(axes) > 1 else axes[0]
    size = comm.size
    default_wire = _normalize_wire(wire_dtype)
    bucket_wires = [
        _normalize_wire(bl.wire_dtype) if bl.wire_dtype is not None
        else default_wire
        for bl in meta.buckets]
    K = len(meta.buckets)
    # Quantized buckets (fsdp_init(bucket_compressors=...)).  When none
    # are, every branch below is statically dead and the step traces the
    # exact pre-compression program — the bit-for-bit contract.
    from chainermn_tpu.compression import base as _cbase
    bucket_comps = [
        _cbase.resolve_compressor(bl.compressor)
        if getattr(bl, "compressor", None) else None
        for bl in meta.buckets]
    any_compressed = any(c is not None for c in bucket_comps)
    if any_compressed and accum_steps > 1:
        raise NotImplementedError(
            "accum_steps > 1 with quantized buckets is not supported: "
            "error feedback would advance once per MICROBATCH, changing "
            "the accumulation semantics — accumulate uncompressed or "
            "drop the bucket's compressor")

    gathers = [
        _make_gather(axis_arg, i) if c is None else _make_compressed_gather(
            c, meta.buckets[i], bucket_wires[i], axis_arg, size, i)
        for i, c in enumerate(bucket_comps)]

    def step(state, model_state, batch):
        shards = jax.tree.map(lambda a: jnp.squeeze(a, 0), state.shards)
        inner = jax.tree.map(lambda a: jnp.squeeze(a, 0), state.inner)
        comp = (jax.tree.map(lambda a: jnp.squeeze(a, 0), state.comp)
                if any_compressed else None)
        if with_model_state:
            model_state = jax.tree.map(
                lambda a: jnp.squeeze(a, 0), model_state)

        def gather_bucket(i, bufs):
            # all_gather over the data axes; its transpose
            # (``_make_gather``) IS the reduce-scatter of this bucket's
            # gradients (sum over devices).  With a wire dtype the cast
            # sits INSIDE the gather chain, so the transpose
            # reduce-scatters in the wire dtype as well.
            bl = meta.buckets[i]
            wire = bucket_wires[i]
            full = []
            for s, n in zip(bufs, bl.orig_lens):
                orig = s.dtype
                if wire is not None \
                        and jnp.issubdtype(orig, jnp.floating) \
                        and orig != wire:
                    s = s.astype(wire)
                full.append(gathers[i](s)[:n].astype(orig))
            return full

        def local_loss(carry, model_state_, batch_):
            # Issue the per-bucket gathers in bucket order under the
            # prefetch window: bucket i may not start gathering until
            # bucket i-1-prefetch finished (at most prefetch+1 gathers in
            # flight).  The barrier's custom VJP mirrors the pin onto the
            # backward, windowing the per-bucket reduce-scatters too.
            shards_, comp_ = carry if any_compressed else (carry, None)
            gathered = []
            leaves = []
            for i, bufs in enumerate(shards_):
                if K > 1 and i > prefetch and gathered[i - prefetch - 1]:
                    anchor = gathered[i - prefetch - 1]
                    pinned = _sched_barrier(tuple(bufs) + tuple(anchor))
                    bufs = list(pinned[:len(bufs)])
                    # the forward consumes the anchor's post-barrier
                    # values, keeping the pin live in the graph
                    gathered[i - prefetch - 1] = list(pinned[len(bufs):])
                if bucket_comps[i] is not None:
                    # quantized bucket: same pinned slot in the gather
                    # order, compressed gradient leg in the transpose
                    full = gathers[i](bufs[0], comp_[i])
                    gathered.append([full[:meta.buckets[i].orig_lens[0]]])
                else:
                    gathered.append(gather_bucket(i, bufs))
            for bl, full in zip(meta.buckets, gathered):
                leaves.extend(_packing.unpack(full, bl.pack_meta))
            params = jax.tree.unflatten(meta.treedef, leaves)
            if with_model_state:
                return loss_fn(params, model_state_, batch_)
            return loss_fn(params, batch_)

        grad_fn = jax.value_and_grad(
            local_loss, has_aux=has_aux or with_model_state)
        carry0 = (shards, comp) if any_compressed else shards

        def compute(model_state_, batch_):
            if with_model_state:
                (loss, packed), gcarry = grad_fn(carry0, model_state_,
                                                 batch_)
                model_state_, aux = packed if has_aux else (packed, None)
            elif has_aux:
                (loss, aux), gcarry = grad_fn(carry0, None, batch_)
            else:
                loss, gcarry = grad_fn(carry0, None, batch_)
                aux = None
            return loss, aux, model_state_, gcarry

        if accum_steps > 1:
            from chainermn_tpu.utils.accum import accumulate_microbatches

            loss, aux, model_state, gcarry = accumulate_microbatches(
                compute, model_state, batch, accum_steps, has_aux)
        else:
            loss, aux, model_state, gcarry = compute(model_state, batch)
        if any_compressed:
            # the comp "gradient" IS the advanced EF state (cotangent
            # smuggling via the custom VJP) — mean-normalization below
            # must not touch it
            gshards, comp = gcarry
        else:
            gshards = gcarry
        if not global_loss:
            # transpose delivered the SUM over devices; reference
            # allreduce_grad semantics are the mean.  (With global_loss
            # the loss was already psum-normalized inside loss_fn, so
            # the summed shard grads ARE the global gradient.)
            gshards = jax.tree.map(
                lambda g: g / jnp.asarray(size, g.dtype), gshards)
        elif not check_vma:
            # Without vma tracking psum transposes to psum instead of
            # the identity broadcast, so the gradient of a psum'd
            # (global_loss) objective comes back inflated by the world
            # size; divide it back out.
            gshards = jax.tree.map(
                lambda g: g / jnp.asarray(size, g.dtype), gshards)
        updates, inner = optimizer.update(gshards, inner, shards)
        shards = optax.apply_updates(shards, updates)

        state = FsdpState(
            shards=jax.tree.map(lambda s: s[None], shards),
            inner=jax.tree.map(lambda a: a[None], inner),
            comp=(jax.tree.map(lambda a: a[None], comp)
                  if any_compressed else state.comp))
        if with_model_state:
            model_state = jax.tree.map(lambda a: a[None], model_state)
        if not global_loss:
            loss = comm.allreduce(loss, "mean")
            if has_aux:
                aux = comm.allreduce(aux, "mean")
        outs = (state, model_state, loss, aux)
        keep = (True, with_model_state, True, has_aux)
        return tuple(o for o, k in zip(outs, keep) if k)

    state_spec = FsdpState(
        shards=[[P(axes)] * len(bl.shard_lens) for bl in meta.buckets],
        inner=P(axes),
        comp=([P(axes)] * K if any_compressed else P(axes)))
    out_spec_all = (state_spec, P(axes), P(), P())
    keep = (True, with_model_state, True, has_aux)
    out_specs = tuple(s for s, k in zip(out_spec_all, keep) if k)
    b_spec = P(axes) if batch_spec is None else batch_spec
    in_specs = ((state_spec, P(axes), b_spec) if with_model_state
                else (state_spec, b_spec))
    inner_fn = step
    if not with_model_state:
        def inner_fn(state, batch):  # noqa: F811
            return step(state, None, batch)
    mapped = jax.shard_map(inner_fn, mesh=comm.mesh,
                           in_specs=in_specs, out_specs=out_specs,
                           check_vma=check_vma)
    donate_argnums = ((0, 1) if with_model_state else (0,)) if donate else ()
    return jax.jit(mapped, donate_argnums=donate_argnums)


__all__ = ["BucketLayout", "FsdpMeta", "FsdpState", "fsdp_init",
           "fsdp_full_params", "fsdp_layout", "iter_fsdp_states",
           "make_fsdp_train_step"]

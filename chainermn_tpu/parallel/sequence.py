"""Sequence/context parallelism: ring attention and Ulysses-style all-to-all.

**Beyond-reference extension.** The reference (2017-era, SURVEY.md §2.4 /
§5.7) has NO sequence parallelism — sequence length was bounded by one
device's memory.  This module is the TPU-era answer to that bound, built on
the same mesh-axis machinery as the communicators: shard the *sequence*
dimension across a mesh axis and express the cross-device data movement as
XLA collectives over ICI.

Two strategies, the two used in practice:

* :func:`ring_attention` — keep Q resident, rotate K/V blocks around the
  ring with ``lax.ppermute`` (one neighbor hop per step, bandwidth-optimal
  on a torus), accumulating softmax online (flash-attention-style running
  max / denominator), so the full [T, T] score matrix never materializes
  on any chip.  Peak memory per chip: O(T_local * T_local) scores +
  O(T_local) stats.

* :func:`ulysses_attention` — two ``lax.all_to_all``s: trade the sequence
  shard for a head shard, run exact local attention over the *full*
  sequence for H/P heads, trade back.  Cheaper compute bookkeeping, needs
  heads divisible by the axis size; all-to-all rides ICI well on TPU.

Both are differentiable (``ppermute``/``all_to_all`` transpose to
themselves reversed) and numerically match single-device attention — the
test suite asserts forward and gradient parity on an 8-way sequence mesh.

Use inside ``shard_map``/``run_spmd`` with arrays sharded [B, T/P, H, D]
on the sequence axis::

    mesh = Mesh(devices, ("sp",))
    out = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=True),
        mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"))(q, k, v)
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax


def attention(q, k, v, *, causal: bool = False, sm_scale: Optional[float] = None,
              q_offset=0, k_offset=0):
    """Plain single-shard softmax attention, fp32-stable.

    ``q``: [B, Tq, H, D]; ``k``/``v``: [B, Tk, H, D] -> [B, Tq, H, D].
    ``q_offset``/``k_offset`` are the global positions of the first row of
    the local blocks (used by the causal mask when shards are slices of a
    longer sequence).
    """
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    scores = jnp.einsum("bthd,bshd->bhts", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def ring_attention(q, k, v, axis_name, *, causal: bool = False,
                   sm_scale: Optional[float] = None,
                   attn_fn: Optional[Callable] = None):
    """Exact attention over a sequence sharded on mesh axis ``axis_name``.

    Per device: ``q``/``k``/``v`` are the local sequence block
    [B, T_local, H, D]; global sequence order is rank order on the axis.
    K/V blocks rotate ring-wise (``ppermute`` to the next rank) while a
    running (max, denominator, accumulator) triple folds each visiting
    block in — the online-softmax recurrence, so results are exactly (up
    to fp associativity) the single-device softmax.  The per-step body is
    rematerialized in the backward pass (``jax.checkpoint``) so the
    [T_local, T_local] probability tiles are never stored per step.

    ``attn_fn``: an inner attention kernel with the
    :func:`chainermn_tpu.ops.flash_attention` extended signature
    (``q_offset``/``kv_offset``/``return_lse``).  When given, each
    visiting K/V block is processed by the fused kernel (the [T_local,
    T_local] score tile never reaches HBM) and the per-block (out, lse)
    pairs are folded with the standard logsumexp merge — differentiable
    because the kernel's lse output is (its cotangent feeds ``a·g_lse``
    back into the score gradients).
    """
    if attn_fn is not None:
        return _ring_attention_kernel(q, k, v, axis_name, causal=causal,
                                      sm_scale=sm_scale, attn_fn=attn_fn)
    size = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    t_local = q.shape[1]
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    qf = q.astype(jnp.float32)

    def fold(carry, step):
        k_blk, v_blk, acc, m, l = carry
        # block currently held arrived from rank (me - step) mod size
        src = (me - step) % size
        scores = jnp.einsum("bthd,bshd->bhts", qf,
                            k_blk.astype(jnp.float32)) * scale
        if causal:
            q_pos = me * t_local + jnp.arange(t_local)
            k_pos = src * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        blockmax = scores.max(-1)                       # [B, H, T]
        new_m = jnp.maximum(m, blockmax)
        finite = jnp.isfinite(new_m)
        safe_m = jnp.where(finite, new_m, 0.0)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(finite[..., None], p, 0.0)        # fully-masked rows
        alpha = jnp.where(finite, jnp.exp(m - safe_m), 1.0)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhts,bshd->bhtd", p, v_blk.astype(jnp.float32))
        k_blk, v_blk = lax.ppermute(
            (k_blk, v_blk), axis_name,
            perm=[(i, (i + 1) % size) for i in range(size)])
        return (k_blk, v_blk, acc, new_m, l), None

    from chainermn_tpu.utils import pvary

    b, _, h, d = q.shape
    # The accumulators are device-varying from step one (they fold in the
    # varying K/V blocks); mark the zero-inits varying up front so the scan
    # carry type is stable.
    acc0 = pvary(jnp.zeros((b, h, t_local, d), jnp.float32), axis_name)
    m0 = pvary(jnp.full((b, h, t_local), -jnp.inf, jnp.float32), axis_name)
    l0 = pvary(jnp.zeros((b, h, t_local), jnp.float32), axis_name)
    (k, v, acc, m, l), _ = lax.scan(
        jax.checkpoint(fold), (k, v, acc0, m0, l0), jnp.arange(size))
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _ring_attention_kernel(q, k, v, axis_name, *, causal, sm_scale, attn_fn):
    """Ring attention with a fused per-block kernel (see ring_attention)."""
    from chainermn_tpu.utils import pvary

    size = jax.lax.axis_size(axis_name)
    # Only the causal mask consumes the global block offsets; computing
    # axis_index in the non-causal trace would leave a dead PartitionId
    # that XLA hoists out of the manual region and then refuses to
    # partition under jit.
    me = lax.axis_index(axis_name) if causal else None
    b, t_local, h, d = q.shape
    sentinel = 1e29  # kernel marks fully-masked rows with lse ~ 1e30

    def fold(carry, step):
        k_blk, v_blk, o_run, lse_run = carry
        if causal:
            src = (me - step) % size
            offsets = dict(q_offset=me * t_local, kv_offset=src * t_local)
        else:
            offsets = {}
        o_blk, lse_blk = attn_fn(
            q, k_blk, v_blk, causal=causal, sm_scale=sm_scale,
            return_lse=True, **offsets)
        # sentinel rows attended nothing in this block -> merge weight 0
        lse_b = jnp.where(lse_blk >= sentinel, -jnp.inf, lse_blk)
        m = jnp.maximum(lse_run, lse_b)
        finite = jnp.isfinite(m)
        safe_m = jnp.where(finite, m, 0.0)
        w_run = jnp.where(finite, jnp.exp(lse_run - safe_m), 0.0)
        w_blk = jnp.where(finite, jnp.exp(lse_b - safe_m), 0.0)
        denom = w_run + w_blk
        safe_denom = jnp.where(denom == 0.0, 1.0, denom)
        # weights arrive [B, H, T]; activations are [B, T, H, D]
        tr = lambda w: w.transpose(0, 2, 1)[..., None]
        o_new = (o_run * tr(w_run)
                 + o_blk.astype(jnp.float32) * tr(w_blk)) / tr(safe_denom)
        lse_new = jnp.where(finite, safe_m + jnp.log(safe_denom), -jnp.inf)
        k_blk, v_blk = lax.ppermute(
            (k_blk, v_blk), axis_name,
            perm=[(i, (i + 1) % size) for i in range(size)])
        return (k_blk, v_blk, o_new, lse_new), None

    o0 = pvary(jnp.zeros((b, t_local, h, d), jnp.float32), axis_name)
    lse0 = pvary(jnp.full((b, h, t_local), -jnp.inf, jnp.float32), axis_name)
    (k, v, o_run, _), _ = lax.scan(
        jax.checkpoint(fold), (k, v, o0, lse0), jnp.arange(size))
    return o_run.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name, *, causal: bool = False,
                      sm_scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None):
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism.

    Per device in/out: [B, T_local, H, D] sharded on ``axis_name``.  Two
    collectives: trade the sequence shard for a head shard (each device
    ends up with the FULL sequence for H/P heads), run exact attention
    locally, trade back.  Requires ``H % axis_size == 0``.

    ``attn_fn(q, k, v, causal=..., sm_scale=...)`` defaults to
    :func:`attention`; pass a fused kernel to swap the inner math.
    """
    size = jax.lax.axis_size(axis_name)
    h = q.shape[2]
    if h % size != 0:
        raise ValueError(
            f"ulysses_attention needs heads ({h}) divisible by the axis "
            f"size ({size}); use ring_attention for odd head counts")
    a2a = functools.partial(lax.all_to_all, axis_name=axis_name, tiled=True)
    # [B, T/P, H, D] -> [B, T, H/P, D]
    qg, kg, vg = (a2a(x, split_axis=2, concat_axis=1) for x in (q, k, v))
    fn = attn_fn if attn_fn is not None else attention
    out = fn(qg, kg, vg, causal=causal, sm_scale=sm_scale)
    # [B, T, H/P, D] -> [B, T/P, H, D]
    return a2a(out, split_axis=1, concat_axis=2)


__all__ = ["attention", "ring_attention", "ulysses_attention"]

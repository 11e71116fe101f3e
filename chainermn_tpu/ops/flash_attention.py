"""Fused attention — Pallas TPU kernels, forward AND backward.

**Beyond-reference native kernel** (the reference's native surface was
CUDA elementwise strings — SURVEY.md §2.3; this is the TPU analogue for
the attention hot op used by the sequence-parallel extension).

Forward: K/V-STREAMING grid (round 3) — grid (batch*head, q-tile,
k-tile): the q tile and the online-softmax accumulators (acc, running
max, denominator) live in VMEM scratch across the k-tile grid steps,
while each K/V TILE is fetched by the Pallas pipeline per step.  VMEM
residency is O(block) rather than O(T), which lifts the previous
full-sequence-resident bound (~T=12k at D=128) to HBM capacity; the
pipelined tile fetches overlap the MXU matmuls.  The softmax is online
(never a full [T, T] score matrix anywhere); the per-row logsumexp is
written out as a residual so the backward never re-derives it.

Backward: two streaming Pallas kernels in the standard flash-gradient
shape — grid (bh, k-tile, q-tile) accumulating (dk, dv) in scratch
while q/dO/lse/delta tiles stream, and grid (bh, q-tile, k-tile)
accumulating dq while K/V tiles stream — each recomputing its score
tile from q/k and the saved logsumexp, so the [T, T] matrix is
materialized in NEITHER direction and VMEM stays O(block) end to end.
A pure-XLA blockwise backward with identical math is kept
(``bwd_impl="blockwise"``) as the cross-check oracle for the
gradient-parity tests.

Masking and dropout:

* ``causal`` — lower-triangular mask; fully-masked K/V tiles are
  skipped (forward) / never visited (backward).
* ``q_segment_ids``/``kv_segment_ids`` ([B, T] int32) — attention is
  allowed only where the ids match, which expresses packed-sequence and
  padding masks (give padding a sentinel id that matches nothing).
  Fully-masked rows produce zero output and zero gradients.
* ``dropout_rate``/``dropout_seed`` — attention-weight dropout applied
  after normalization with inverted scaling (kept weights / keep_p).
  The mask is a counter-based hash of (seed, batch*head, q_pos, k_pos)
  computed identically in forward, backward, and the blockwise oracle —
  nothing random is stored, so the recompute-based backward stays exact.

Scope: per-shard sequence lengths where K/V fit VMEM (T*D*2B each —
thousands of positions at D=64..128), which is exactly the per-device
block regime of :func:`chainermn_tpu.parallel.sequence.ring_attention` /
``ulysses_attention`` (pass ``attn_fn=flash_attention``).  Off-TPU the
kernels run in Pallas interpret mode so the CPU test mesh exercises the
same code path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # pltpu only imports on TPU-capable installs; interpret mode needs it not
    from jax.experimental.pallas import tpu as pltpu
    _VMEM = pltpu.VMEM
except Exception:  # pragma: no cover
    pltpu = None
    _VMEM = None

_BLOCK_Q = 1024  # measured optimum on v5e (benchmarks: 81 TFLOP/s fwd at
_BLOCK_K = 1024  # T=8k vs 24 at 256/256 — per-grid-step overhead amortizes)
_NEG_INF = -1e30
_LSE_SENTINEL = 1e30  # lse for fully-masked rows: exp(s - sentinel) == 0


def _keep_mask(seed_u32, bh_idx, q_pos, k_pos, rate):
    """Deterministic dropout keep-mask from a counter-based hash.

    ``q_pos``/``k_pos`` are GLOBAL positions (broadcastable int32
    arrays), so forward and backward — which tile the [T, T] plane
    differently — reproduce the identical mask.  Murmur3-finalizer
    rounds give well-mixed bits from pure uint32 VPU arithmetic (no
    stateful PRNG, works under both compiled and interpret modes).
    """
    x = (q_pos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ k_pos.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         ^ (bh_idx.astype(jnp.uint32) if hasattr(bh_idx, "astype")
            else jnp.uint32(bh_idx)) * jnp.uint32(0xC2B2AE35)
         ^ seed_u32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    thresh = min(int(rate * 2 ** 32), 2 ** 32 - 1)
    return x >= jnp.uint32(thresh)


def _shape_like(template, shape, dtype):
    """ShapeDtypeStruct carrying ``template``'s varying-axes (vma) metadata
    — needed for shard_map composition."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(template).vma)


def _unpack_rest(rest, has_seg, dropout_rate, has_offsets=False):
    """Split a kernel's trailing refs into (qseg, kseg, seed, offs, outputs)
    — shared by all three kernels so the optional-input threading lives
    once."""
    idx = 0
    qseg_ref = kseg_ref = seed_ref = offs_ref = None
    if has_seg:
        qseg_ref, kseg_ref = rest[0], rest[1]
        idx = 2
    if dropout_rate > 0.0:
        seed_ref = rest[idx]
        idx += 1
    if has_offsets:
        offs_ref = rest[idx]
        idx += 1
    return qseg_ref, kseg_ref, seed_ref, offs_ref, rest[idx:]


def _mask_tile(causal, q_pos, k_pos, seg_q, seg_k):
    """[bq, bk] bool allow-mask (or None when nothing masks)."""
    mask = None
    if causal:
        mask = q_pos >= k_pos
    if seg_q is not None:
        m2 = seg_q[:, None] == seg_k[None, :]
        mask = m2 if mask is None else (mask & m2)
    return mask


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal,
                has_seg, dropout_rate, has_offsets):
    # Streaming grid (bh, q-tile, k-tile): q_ref [1, BQ, D] (fixed per
    # (bh, j)); k_ref/v_ref [1, BK, D] = THIS grid step's tile; optional
    # qseg [1, 1, BQ], kseg [1, 1, BK], seed [1, 1], offs [1, 2]; outputs
    # o [1, BQ, D], lse [1, 1, BQ] (written at the last k step); scratch
    # acc [BQ, D], m [BQ, 1], l [BQ, 1] persist across the k dimension.
    qseg_ref, kseg_ref, seed_ref, offs_ref, rest = _unpack_rest(
        rest, has_seg, dropout_rate, has_offsets)
    o_ref, lse_ref, acc_s, m_s, l_s = rest

    q = q_ref[0]                                         # [BQ, D]
    k = k_ref[0]                                         # [BK, D]
    v = v_ref[0]
    bq, d = q.shape
    bk = k.shape[0]
    kk = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_off = pl.program_id(1) * bq
    k_off = kk * bk
    bh_idx = pl.program_id(0)
    seed = seed_ref[0, 0].astype(jnp.uint32) if seed_ref is not None else None
    # global position offsets (ring-attention blocks of a longer sequence)
    goff_q = offs_ref[0, 0] if has_offsets else 0
    goff_k = offs_ref[0, 1] if has_offsets else 0

    @pl.when(kk == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    # causal full-tile skip: tile contributes only if some q row can see
    # its first k row (the fetch still pipelines; the MXU work is skipped)
    run = ((goff_q + q_off + bq - 1 >= goff_k + k_off)
           if causal else (kk >= 0))

    @pl.when(run)
    def _tile():
        q_pos = goff_q + q_off + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        k_pos = goff_k + k_off + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        # scale after the matmul — same op order as the unfused reference,
        # so results match it to tight tolerance
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        seg_q = qseg_ref[0, 0] if has_seg else None
        seg_k = kseg_ref[0, 0] if has_seg else None
        mask = _mask_tile(causal, q_pos, k_pos, seg_q, seg_k)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        m = m_s[...]
        l = l_s[...]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            # when a whole row of the tile is masked, s - m_new == 0 and
            # exp would give 1 — zero the masked entries explicitly
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_s[...] = l * alpha + p.sum(axis=1, keepdims=True)
        m_s[...] = m_new
        if dropout_rate > 0.0:
            keep = _keep_mask(seed, bh_idx, q_pos, k_pos, dropout_rate)
            p_use = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        else:
            p_use = p
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p_use.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _finish():
        l = l_s[...]
        empty = l == 0.0
        o_ref[0] = (acc_s[...] / jnp.where(empty, 1.0, l)).astype(
            o_ref.dtype)
        lse = jnp.where(empty[:, 0], _LSE_SENTINEL,
                        m_s[...][:, 0] + jnp.log(
                            jnp.where(empty[:, 0], 1.0, l[:, 0])))
        lse_ref[0, 0] = lse.astype(jnp.float32)


def _scratch(shapes_dtypes):
    """VMEM scratch allocations — the accumulators that persist across the
    streaming grid dimension (interpret mode allocates them as arrays).
    Installs without pltpu (pure-CPU jax) fall back to the memory-space-
    agnostic MemoryRef, which the interpreter accepts."""
    if pltpu is not None:
        return [pltpu.VMEM(s, dt) for s, dt in shapes_dtypes]
    return [pl.MemoryRef(jax.core.ShapedArray(s, dt), pl.ANY)
            for s, dt in shapes_dtypes]


def _forward(q, k, v, qseg, kseg, seed, offs, causal, sm_scale, block_q,
             block_k, dropout_rate, interpret):
    b, tq, h, d = q.shape
    tk, hk = k.shape[1], k.shape[2]
    grp = h // hk  # q heads per kv head (1 = MHA; >1 = GQA/MQA)
    scale = sm_scale if sm_scale is not None else d ** -0.5
    bq = min(block_q, tq)
    bk = min(block_k, tk)
    if tq % bq or tk % bk:
        raise ValueError(
            f"flash_attention needs seq lens ({tq}, {tk}) divisible by "
            f"their tiles ({bq}, {bk}); pad the sequence or pass smaller "
            f"block sizes")
    # [B, T, H, D] -> [B*H, T, D]
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], d)
    qf, kf, vf = fold(q), fold(k), fold(v)
    # grid dim 0 iterates q heads (b*h programs); a kv tensor row for
    # program i is its (batch, kv-head) pair
    kv_row = lambda i: (i // h) * hk + (i % h) // grp
    has_seg = qseg is not None
    has_offsets = offs is not None

    kern = functools.partial(_fwd_kernel, sm_scale=scale, causal=causal,
                             has_seg=has_seg, dropout_rate=dropout_rate,
                             has_offsets=has_offsets)
    kw = {} if _VMEM is None else {"memory_space": _VMEM}
    ins = [qf, kf, vf]
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0), **kw),
        pl.BlockSpec((1, bk, d), lambda i, j, kk: (kv_row(i), kk, 0), **kw),
        pl.BlockSpec((1, bk, d), lambda i, j, kk: (kv_row(i), kk, 0), **kw),
    ]
    if has_seg:
        # segment ids are per-batch; heads share them (index map i // h).
        # TPU tiling wants the last two block dims divisible by (8, 128) or
        # equal to the array dims — a singleton row dim satisfies that, so
        # host-side vectors ride as [*, 1, T].
        ins += [qseg.reshape(b, 1, tq), kseg.reshape(b, 1, tk)]
        in_specs += [
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i // h, 0, j), **kw),
            pl.BlockSpec((1, 1, bk), lambda i, j, kk: (i // h, 0, kk), **kw),
        ]
    if dropout_rate > 0.0:
        ins.append(seed.reshape(1, 1))
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0), **kw))
    if has_offsets:
        # offs is [B, 2] (per-sequence global positions); each program
        # reads its batch row — a [1, 2] block, like the seg-id vectors.
        ins.append(offs)
        in_specs.append(
            pl.BlockSpec((1, 2), lambda i, j, kk: (i // h, 0), **kw))
    # Inside shard_map the outputs must carry the inputs' varying-axes
    # metadata (vma) so the kernel composes with sequence parallelism.
    out_shape = [_shape_like(qf, (b * h, tq, d), q.dtype),
                 _shape_like(qf, (b * h, 1, tq), jnp.float32)]
    out, lse = pl.pallas_call(
        kern,
        grid=(b * h, tq // bq, tk // bk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0), **kw),
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, 0, j), **kw)],
        out_shape=out_shape,
        scratch_shapes=_scratch([((bq, d), jnp.float32),
                                 ((bq, 1), jnp.float32),
                                 ((bq, 1), jnp.float32)]),
        interpret=interpret,
    )(*ins)
    return out.reshape(b, h, tq, d).transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref, *rest,
                sm_scale, causal, has_seg, dropout_rate,
                has_offsets, with_lse):
    # Streaming grid (bh, k-tile, q-tile): k_ref/v_ref [1, BK, D] fixed
    # per (bh, kk); q_ref/g_ref [1, BQ, D] = this step's q tile;
    # lse_ref/delta_ref [1, 1, BQ] tiles; optional glse [1, 1, BQ];
    # outputs dk/dv [1, BK, D] written at the last q step; scratch
    # dk/dv accumulators persist across the q dimension.
    qseg_ref, kseg_ref, seed_ref, offs_ref, outs = _unpack_rest(
        rest, has_seg, dropout_rate, has_offsets)
    if with_lse:
        glse_ref, dk_ref, dv_ref, dk_s, dv_s = outs
    else:
        glse_ref = None
        dk_ref, dv_ref, dk_s, dv_s = outs

    k = k_ref[0]                                          # [BK, D]
    v = v_ref[0]
    q = q_ref[0]                                          # [BQ, D]
    g = g_ref[0]
    bk = k.shape[0]
    bq = q.shape[0]
    qq = pl.program_id(2)
    n_q = pl.num_programs(2)
    k_off = pl.program_id(1) * bk
    q_off = qq * bq
    bh_idx = pl.program_id(0)
    seed = seed_ref[0, 0].astype(jnp.uint32) if seed_ref is not None else None
    goff_q = offs_ref[0, 0] if has_offsets else 0
    goff_k = offs_ref[0, 1] if has_offsets else 0

    @pl.when(qq == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    # causal: this q tile contributes only if its last row sees the
    # k tile's first row
    run = ((goff_q + q_off + bq - 1 >= goff_k + k_off)
           if causal else (qq >= 0))

    @pl.when(run)
    def _tile():
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        k_pos = goff_k + k_off + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        q_pos = goff_q + q_off + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        seg_q = qseg_ref[0, 0] if has_seg else None
        seg_k = kseg_ref[0, 0] if has_seg else None
        mask = _mask_tile(causal, q_pos, k_pos, seg_q, seg_k)
        a = jnp.exp(s - lse[:, None])                     # normalized probs
        if mask is not None:
            a = jnp.where(mask, a, 0.0)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed, bh_idx, q_pos, k_pos, dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            a_drop = jnp.where(keep, a * inv, 0.0)
            da = jnp.where(keep, dp * inv, 0.0)
        else:
            a_drop = a
            da = dp
        dv_s[...] = dv_s[...] + jax.lax.dot_general(
            a_drop.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = a * (da - delta[:, None]) * sm_scale
        if with_lse:
            # cotangent flowing into the logsumexp output: d lse_i / d s_ij
            # = a_ij (same a as above), in scaled-score space
            glse = glse_ref[0, 0]
            ds = ds + a * glse[:, None] * sm_scale
        dk_s[...] = dk_s[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qq == n_q - 1)
    def _finish():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref, *rest,
               sm_scale, causal, has_seg, dropout_rate,
               has_offsets, with_lse):
    # Streaming grid (bh, q-tile, k-tile): q_ref/g_ref [1, BQ, D] fixed
    # per (bh, j); k_ref/v_ref [1, BK, D] = this step's tile;
    # lse_ref/delta_ref [1, 1, BQ]; optional glse [1, 1, BQ]; output
    # dq [1, BQ, D] written at the last k step; scratch dq accumulator.
    qseg_ref, kseg_ref, seed_ref, offs_ref, outs = _unpack_rest(
        rest, has_seg, dropout_rate, has_offsets)
    if with_lse:
        glse_ref, dq_ref, dq_s = outs
    else:
        glse_ref = None
        dq_ref, dq_s = outs

    q = q_ref[0]
    g = g_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    bq = q.shape[0]
    bk = k.shape[0]
    kk = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_off = pl.program_id(1) * bq
    k_off = kk * bk
    bh_idx = pl.program_id(0)
    seed = seed_ref[0, 0].astype(jnp.uint32) if seed_ref is not None else None
    goff_q = offs_ref[0, 0] if has_offsets else 0
    goff_k = offs_ref[0, 1] if has_offsets else 0

    @pl.when(kk == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    run = ((goff_q + q_off + bq - 1 >= goff_k + k_off)
           if causal else (kk >= 0))

    @pl.when(run)
    def _tile():
        q_pos = goff_q + q_off + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        k_pos = goff_k + k_off + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        seg_q = qseg_ref[0, 0] if has_seg else None
        seg_k = kseg_ref[0, 0] if has_seg else None
        mask = _mask_tile(causal, q_pos, k_pos, seg_q, seg_k)
        a = jnp.exp(s - lse[:, None])
        if mask is not None:
            a = jnp.where(mask, a, 0.0)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed, bh_idx, q_pos, k_pos, dropout_rate)
            da = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        else:
            da = dp
        ds = a * (da - delta[:, None]) * sm_scale
        if with_lse:
            ds = ds + a * glse_ref[0, 0][:, None] * sm_scale
        dq_s[...] = dq_s[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _finish():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _pallas_backward(q, k, v, out, lse, qseg, kseg, seed, offs, g, g_lse,
                     causal, sm_scale, block_q, block_k, dropout_rate,
                     interpret):
    b, tq, h, d = q.shape
    tk, hk = k.shape[1], k.shape[2]
    grp = h // hk  # q heads per kv head (GQA); dk/dv computed per q head
    scale = sm_scale if sm_scale is not None else d ** -0.5
    bq = min(block_q, tq)
    bk = min(block_k, tk)
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], d)
    qf, kf, vf, of, gf = fold(q), fold(k), fold(v), fold(out), fold(g)
    kv_row = lambda i: (i // h) * hk + (i % h) // grp
    # delta = rowsum(dO * O): cheap fused elementwise+reduce, XLA's job.
    # lse arrives as [B*H, 1, T] (see _forward's tiling note); delta gets
    # the same singleton-row layout.
    delta = (gf.astype(jnp.float32) * of.astype(jnp.float32)).sum(
        -1, keepdims=True).swapaxes(1, 2)
    has_seg = qseg is not None
    has_offsets = offs is not None
    with_lse = g_lse is not None
    kw = {} if _VMEM is None else {"memory_space": _VMEM}
    shape = lambda s, dt: _shape_like(qf, s, dt)
    seed_in = ([] if dropout_rate == 0.0 else [seed.reshape(1, 1)])
    seed_spec = ([] if dropout_rate == 0.0 else
                 [pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0), **kw)])
    offs_in = ([offs] if has_offsets else [])
    offs_spec = ([pl.BlockSpec((1, 2), lambda i, j, kk: (i // h, 0), **kw)]
                 if has_offsets else [])

    # dk/dv: grid (bh, k-tile, q-tile) — q/g/lse/delta stream over the
    # minor q dimension; the k/v tile and the scratch accumulators are
    # fixed per (bh, k-tile)
    dkv_kern = functools.partial(
        _dkv_kernel, sm_scale=scale, causal=causal,
        has_seg=has_seg, dropout_rate=dropout_rate,
        has_offsets=has_offsets, with_lse=with_lse)
    q_tile = lambda: pl.BlockSpec((1, bq, d), lambda i, j, qq: (i, qq, 0),
                                  **kw)
    vec_q = lambda: pl.BlockSpec((1, 1, bq), lambda i, j, qq: (i, 0, qq),
                                 **kw)
    ins = [qf, gf, kf, vf, lse, delta]
    in_specs = [q_tile(), q_tile(),
                pl.BlockSpec((1, bk, d),
                             lambda i, j, qq: (kv_row(i), j, 0), **kw),
                pl.BlockSpec((1, bk, d),
                             lambda i, j, qq: (kv_row(i), j, 0), **kw),
                vec_q(), vec_q()]
    if has_seg:
        ins += [qseg.reshape(b, 1, tq), kseg.reshape(b, 1, tk)]
        in_specs += [
            pl.BlockSpec((1, 1, bq), lambda i, j, qq: (i // h, 0, qq), **kw),
            pl.BlockSpec((1, 1, bk), lambda i, j, qq: (i // h, 0, j), **kw)]
    ins += seed_in
    in_specs += seed_spec
    ins += offs_in
    in_specs += offs_spec
    if with_lse:
        ins.append(g_lse)
        in_specs.append(vec_q())
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=(b * h, tk // bk, tq // bq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda i, j, qq: (i, j, 0), **kw),
            pl.BlockSpec((1, bk, d), lambda i, j, qq: (i, j, 0), **kw)],
        out_shape=[shape((b * h, tk, d), k.dtype),
                   shape((b * h, tk, d), v.dtype)],
        scratch_shapes=_scratch([((bk, d), jnp.float32),
                                 ((bk, d), jnp.float32)]),
        interpret=interpret,
    )(*ins)
    if grp > 1:
        # each kv head's gradient is the sum over its q-head group —
        # accumulated in f32 (the kernel's partials were cast to the
        # output dtype; summing them in bf16 would compound rounding the
        # blockwise oracle doesn't have)
        group_sum = lambda x: x.astype(jnp.float32).reshape(
            b, hk, grp, tk, d).sum(2).reshape(b * hk, tk, d).astype(x.dtype)
        dk, dv = group_sum(dk), group_sum(dv)

    # dq: grid (bh, q-tile, k-tile) — k/v stream over the minor k
    # dimension; the q/g/lse/delta tiles and the dq scratch are fixed
    dq_kern = functools.partial(
        _dq_kernel, sm_scale=scale, causal=causal,
        has_seg=has_seg, dropout_rate=dropout_rate,
        has_offsets=has_offsets, with_lse=with_lse)
    vec_j = lambda: pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, 0, j),
                                 **kw)
    ins = [qf, gf, kf, vf, lse, delta]
    in_specs = [pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0), **kw),
                pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0), **kw),
                pl.BlockSpec((1, bk, d),
                             lambda i, j, kk: (kv_row(i), kk, 0), **kw),
                pl.BlockSpec((1, bk, d),
                             lambda i, j, kk: (kv_row(i), kk, 0), **kw),
                vec_j(), vec_j()]
    if has_seg:
        ins += [qseg.reshape(b, 1, tq), kseg.reshape(b, 1, tk)]
        in_specs += [
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i // h, 0, j), **kw),
            pl.BlockSpec((1, 1, bk), lambda i, j, kk: (i // h, 0, kk), **kw)]
    ins += seed_in
    in_specs += seed_spec
    ins += offs_in
    in_specs += offs_spec
    if with_lse:
        ins.append(g_lse)
        in_specs.append(vec_j())
    dq = pl.pallas_call(
        dq_kern,
        grid=(b * h, tq // bq, tk // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0), **kw),
        out_shape=shape((b * h, tq, d), q.dtype),
        scratch_shapes=_scratch([((bq, d), jnp.float32)]),
        interpret=interpret,
    )(*ins)

    unfold = lambda x, t_, h_: x.reshape(b, h_, t_, d).transpose(0, 2, 1, 3)
    return unfold(dq, tq, h), unfold(dk, tk, hk), unfold(dv, tk, hk)


def _blockwise_backward(q, k, v, out, lse, qseg, kseg, seed, offs, g, g_lse,
                        causal, sm_scale, block_k, dropout_rate):
    """Pure-XLA blockwise flash backward — the gradient-parity oracle.

    Identical math to the Pallas kernels (saved-lse softmax, the same
    hash-based dropout mask), expressed as a `lax.scan` over K/V tiles so
    the [T, T] matrix is still never materialized.
    """
    b, tq, h, d = q.shape
    tk, hk = k.shape[1], k.shape[2]
    grp = h // hk
    scale = sm_scale if sm_scale is not None else d ** -0.5
    bk = min(block_k, tk)
    n = tk // bk
    # [B, T, H, D] -> [B, H, T, D] f32 working layout
    tr = lambda x: x.transpose(0, 2, 1, 3).astype(jnp.float32)
    qT, oT, gT = tr(q), tr(out), tr(g)
    kT, vT = tr(k), tr(v)
    if grp > 1:  # GQA: expand kv to one head per q head for the math
        rep = lambda x: jnp.repeat(x, grp, axis=1)
        kT, vT = rep(kT), rep(vT)
    lseT = lse.reshape(b, h, tq)  # lse arrives [B*H, 1, Tq]
    glseT = g_lse.reshape(b, h, tq) if g_lse is not None else None
    # offs is [B, 2] (per-sequence offsets); broadcast as [B, 1, T|S, 1]
    # planes so the mask/dropout math matches the per-program scalars the
    # Pallas kernels read
    if offs is not None:
        goff_q = offs[:, 0].reshape(b, 1, 1, 1)
        goff_k = offs[:, 1].reshape(b, 1, 1, 1)
    else:
        goff_q = goff_k = jnp.zeros((1, 1, 1, 1), jnp.int32)
    q_pos = goff_q + jnp.arange(tq).reshape(1, 1, tq, 1)   # [B|1,1,T,1]
    bh_idx = jnp.arange(b * h).reshape(b, h, 1, 1)
    D = (gT * oT).sum(-1)                                  # [B, H, T]
    inv = 1.0 / (1.0 - dropout_rate) if dropout_rate > 0.0 else 1.0

    def k_pos_tile(j):
        return goff_k + (j * bk + jnp.arange(bk)).reshape(1, 1, 1, bk)

    def tile_mask(j):
        mask = None
        if causal:
            mask = q_pos >= k_pos_tile(j)                  # [B|1,1,T,S]
        if qseg is not None:
            kseg_j = jax.lax.dynamic_slice_in_dim(kseg, j * bk, bk, axis=1)
            m2 = (qseg[:, None, :, None] == kseg_j[:, None, None, :])
            mask = m2 if mask is None else (mask & m2)
        return mask

    def keep(j):
        if dropout_rate == 0.0:
            return None
        return _keep_mask(seed.astype(jnp.uint32), bh_idx,
                          q_pos, k_pos_tile(j), dropout_rate)

    def grad_fold(dq, j):
        kb = jax.lax.dynamic_slice_in_dim(kT, j * bk, bk, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vT, j * bk, bk, axis=2)
        s = jnp.einsum("bhtd,bhsd->bhts", qT, kb,
                       preferred_element_type=jnp.float32) * scale
        a = jnp.exp(s - lseT[..., None])
        mask = tile_mask(j)
        if mask is not None:
            a = jnp.where(mask, a, 0.0)
        dp = jnp.einsum("bhtd,bhsd->bhts", gT, vb)
        km = keep(j)
        if km is not None:
            a_drop = jnp.where(km, a * inv, 0.0)
            da = jnp.where(km, dp * inv, 0.0)
        else:
            a_drop = a
            da = dp
        dv_j = jnp.einsum("bhts,bhtd->bhsd", a_drop, gT)
        ds = a * (da - D[..., None]) * scale
        if glseT is not None:
            ds = ds + a * glseT[..., None] * scale
        dq = dq + jnp.einsum("bhts,bhsd->bhtd", ds, kb)
        dk_j = jnp.einsum("bhts,bhtd->bhsd", ds, qT)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros_like(qT)
    dq, (dk_tiles, dv_tiles) = jax.lax.scan(grad_fold, dq0, jnp.arange(n))
    # [n, B, H, bk, D] -> [B, H, Tk, D]
    merge = lambda tiles: tiles.transpose(1, 2, 0, 3, 4).reshape(b, h, tk, d)
    back = lambda x, ref: x.transpose(0, 2, 1, 3).astype(ref.dtype)
    dk_full, dv_full = merge(dk_tiles), merge(dv_tiles)
    if grp > 1:  # sum each kv head's gradient over its q-head group
        gsum = lambda x: x.reshape(b, hk, grp, tk, d).sum(2)
        dk_full, dv_full = gsum(dk_full), gsum(dv_full)
    return (back(dq, q), back(dk_full, k), back(dv_full, v))


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def _flash(q, k, v, qseg, kseg, seed, offs, dropout_rate, causal, sm_scale,
           block_q, block_k, bwd_impl, with_lse):
    interpret = jax.default_backend() != "tpu"
    out, lse = _forward(q, k, v, qseg, kseg, seed, offs, causal, sm_scale,
                        block_q, block_k, dropout_rate, interpret)
    if with_lse:
        b, t, h, _ = q.shape
        return out, lse.reshape(b, h, t)
    return out


def _flash_fwd(q, k, v, qseg, kseg, seed, offs, dropout_rate, causal,
               sm_scale, block_q, block_k, bwd_impl, with_lse):
    interpret = jax.default_backend() != "tpu"
    out, lse = _forward(q, k, v, qseg, kseg, seed, offs, causal, sm_scale,
                        block_q, block_k, dropout_rate, interpret)
    res = (q, k, v, out, lse, qseg, kseg, seed, offs)
    if with_lse:
        b, t, h, _ = q.shape
        return (out, lse.reshape(b, h, t)), res
    return out, res


def _flash_bwd(dropout_rate, causal, sm_scale, block_q, block_k, bwd_impl,
               with_lse, res, g):
    q, k, v, out, lse, qseg, kseg, seed, offs = res
    if with_lse:
        g, g_lse_bht = g
        b, t, h, _ = q.shape
        g_lse = g_lse_bht.reshape(b * h, 1, t).astype(jnp.float32)
    else:
        g_lse = None
    if bwd_impl == "pallas":
        interpret = jax.default_backend() != "tpu"
        dq, dk, dv = _pallas_backward(
            q, k, v, out, lse, qseg, kseg, seed, offs, g, g_lse, causal,
            sm_scale, block_q, block_k, dropout_rate, interpret)
    elif bwd_impl == "blockwise":
        dq, dk, dv = _blockwise_backward(
            q, k, v, out, lse, qseg, kseg, seed, offs, g, g_lse, causal,
            sm_scale, block_k, dropout_rate)
    else:
        raise ValueError(f"unknown bwd_impl {bwd_impl!r} "
                         "(expected 'pallas' or 'blockwise')")
    return dq, dk, dv, None, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fit_block(t: int, requested: Optional[int], default: int) -> int:
    """Resolve a block size.  Explicit sizes are strict (must divide T, as
    before); the default auto-shrinks by halving until it divides — so the
    larger shipped default never rejects a T an older default accepted."""
    if requested is not None:
        b = min(int(requested), t)
        if t % b:
            raise ValueError(
                f"flash_attention needs seq len ({t}) divisible by its "
                f"tiles ({b}); pad the sequence or pass smaller block "
                f"sizes")
        return b
    b = min(default, t)
    while b > 1 and t % b:
        b //= 2
    if t % b:
        raise ValueError(
            f"flash_attention cannot tile seq len {t}; pass block_q/"
            f"block_k that divide it (or pad the sequence)")
    return b


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    *, q_segment_ids=None, kv_segment_ids=None,
                    dropout_rate: float = 0.0, dropout_seed=None,
                    q_offset=None, kv_offset=None,
                    return_lse: bool = False,
                    bwd_impl: str = "pallas"):
    """Fused softmax attention: q [B, Tq, H, D], k/v [B, Tkv, Hkv, D]
    -> [B, Tq, H, D].  ``Tq != Tkv`` is supported (cross-attention /
    decode-over-cache); with ``causal`` the mask compares GLOBAL
    positions (row ``q_offset+i`` sees column ``kv_offset+j`` iff
    ``i+q_offset >= j+kv_offset``).  ``Hkv`` may divide ``H``
    (grouped-query / multi-query attention): each kv head serves
    ``H/Hkv`` q heads — the kernels read the shared K/V tiles via index
    maps (no materialized repeat) and dk/dv sum over each group.

    Drop-in for :func:`chainermn_tpu.parallel.sequence.attention` (same
    signature minus offsets); pass as ``attn_fn=`` to
    ``ulysses_attention`` for a fused inner kernel.  ``block_q``/
    ``block_k`` tune the tile sizes: explicit values must divide the
    sequence length (or cover it in one tile); the default is
    dtype-aware (1024 for sub-4-byte q/k/v — the measured v5e optimum —
    and 512 when any operand is f32, whose tiles would overflow the
    backward's VMEM budget at 1024) and auto-halves until it divides,
    so any T a smaller default accepted still works.

    Extra keyword-only features:

    * ``q_segment_ids`` / ``kv_segment_ids`` — [B, T] int32 ids;
      position pairs attend only when ids match (packed sequences,
      padding).  Passing either defaults the other to zeros.
    * ``dropout_rate`` + ``dropout_seed`` — attention dropout; the seed
      is a traced uint32 scalar (vary it per training step).
    * ``q_offset`` / ``kv_offset`` — global positions of the first local
      row: traced int scalars (shared by the batch — ring attention's
      blocks of a longer sequence) or ``[B]`` int32 vectors giving every
      sequence its own offset (decode over a paged KV cache, where each
      batch row sits at a different cache length).  The causal mask and
      the dropout hash both use global positions.
    * ``return_lse`` — also return the per-row logsumexp [B, H, T]
      (float32; fully-masked rows hold the sentinel 1e30).  The lse is
      DIFFERENTIABLE: its cotangent adds ``a_ij * g_lse_i`` to the score
      gradients in both backward implementations, which is what lets
      downstream logsumexp merges (ring attention) backprop exactly.
    * ``bwd_impl`` — "pallas" (default, fused backward kernels) or
      "blockwise" (pure-XLA oracle with identical math).
    """
    if (q_segment_ids is not None) or (kv_segment_ids is not None):
        if q_segment_ids is None:
            q_segment_ids = jnp.zeros(q.shape[:2], jnp.int32)
        if kv_segment_ids is None:
            kv_segment_ids = jnp.zeros(k.shape[:2], jnp.int32)
        q_segment_ids = q_segment_ids.astype(jnp.int32)
        kv_segment_ids = kv_segment_ids.astype(jnp.int32)
    dropout_rate = float(dropout_rate)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        dropout_seed = jnp.asarray(dropout_seed, jnp.uint32)
    else:
        dropout_seed = None
    if (q_offset is not None) or (kv_offset is not None):
        # offsets ride as one [B, 2] int32 array: column 0 = q, column 1 =
        # kv.  Scalars broadcast over the batch (ring attention's shared
        # block offsets); [B] arrays give every sequence its own global
        # position — decode-over-a-paged-cache, where each row of the
        # batch sits at a different cache length.
        b = q.shape[0]

        def _off_vec(o, label):
            o = jnp.asarray(0 if o is None else o, jnp.int32)
            if o.ndim == 0:
                return jnp.broadcast_to(o, (b,))
            if o.shape != (b,):
                raise ValueError(
                    f"{label} must be a scalar or a [batch] vector; got "
                    f"shape {o.shape} for batch {b}")
            return o

        offs = jnp.stack([_off_vec(q_offset, "q_offset"),
                          _off_vec(kv_offset, "kv_offset")], axis=1)
    else:
        offs = None
    # cross-attention supported: Tq (from q) and Tkv (from k/v) may
    # differ; GQA/MQA supported: k/v head count may divide q's
    if k.shape != v.shape:
        raise ValueError(f"k and v shapes differ: {k.shape} vs {v.shape}")
    if (q.shape[0], q.shape[3]) != (k.shape[0], k.shape[3]):
        raise ValueError(
            f"q and k/v must share batch/dim: {q.shape} vs {k.shape}")
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"q head count ({q.shape[2]}) must be a multiple of the kv "
            f"head count ({k.shape[2]}) for grouped-query attention")
    # default blocks are dtype-aware: 1024x1024 is the measured bf16
    # optimum, but f32 tiles double every VMEM buffer and the backward's
    # scoped allocation overflows the 16 MB budget — 512 fits with room
    # (widest of q/k/v decides: any f32 operand inflates the tiles)
    if max(jnp.dtype(a.dtype).itemsize for a in (q, k, v)) >= 4:
        dq_def, dk_def = min(_BLOCK_Q, 512), min(_BLOCK_K, 512)
    else:
        dq_def, dk_def = _BLOCK_Q, _BLOCK_K
    bq = _fit_block(q.shape[1], block_q, dq_def)
    bk = _fit_block(k.shape[1], block_k, dk_def)
    return _flash(q, k, v, q_segment_ids, kv_segment_ids, dropout_seed,
                  offs, dropout_rate, bool(causal), sm_scale, bq, bk,
                  bwd_impl, bool(return_lse))


__all__ = ["flash_attention"]

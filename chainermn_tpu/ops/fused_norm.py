"""Fused BatchNorm(+ReLU) Pallas kernels for the normalization boundary.

ResNet-50 on TPU is memory-bound at its BatchNorm boundaries, not
MXU-bound (docs/performance.md rounds 2-5: deleting every BatchNorm
recovers ~19.5 ms of a 114 ms step, and the per-stage roofline analysis
puts stage 1/2 at the HBM ceiling).  The unfused flax path walks each
activation through HBM several times per boundary: the stat reductions
read x, the normalize chain reads x and round-trips intermediates, the
ReLU round-trips again, and the backward repeats the pattern for the
dγ/dβ reductions and dx.  These kernels collapse each direction to the
minimum number of full-activation traversals a batch-global
normalization permits:

  forward  (train): stats pass (read x once)  +  apply pass (read x,
                    write y)                      -> 3 traversals
  forward  (eval):  apply pass only               -> 2 traversals
  backward:         reduce pass (read x, g; emit dβ/dγ)  +
                    dx pass (read x, g; write dx) -> 5 traversals

BatchNorm's batch-global mean/var are a grid-wide barrier, so the stats
pass cannot fuse into the apply pass (every tile of y needs the *final*
statistics); the same holds for the backward sums feeding dx in train
mode.  Two passes per direction is therefore the floor, and the fused
kernels hit it.  ``fused_norm_traffic_bytes`` prices both sides of this
ledger so the reduction is a testable number (see its docstring for the
exact pass tables), analogously to ``planner.plan_wire_bytes``.

Numerics / parity notes (pinned by tests/test_fused_norm.py):

* All kernel arithmetic is float32 regardless of the activation dtype
  (free on the VPU).  flax's ``nn.BatchNorm`` instead *rounds the
  normalize chain to the promoted dtype* (bf16 when ``dtype=bf16``), so
  parity with flax is exact op-order in float32 and within bf16-ulp
  tolerance otherwise — the fused path is the numerically tighter one.
* Variance is the fast form mean(x^2) - mean(x)^2 clamped at 0, exactly
  as flax computes it.
* The backward is a ``jax.custom_vjp`` whose boundary encloses the
  statistics, so train-mode dx includes the full stats-gradient terms:
  dx = γ·invstd·(dz − Σdz/R − x̂·Σ(dz·x̂)/R).  The ReLU mask is
  recomputed in-kernel from x̂·γ+β (nothing extra is stored).  The
  cotangents of the returned batch mean/var are ignored and the
  gradients w.r.t. *running* stats are zero — matching flax, where
  running-stat updates are variable writes outside autodiff.

Kernels run in ``interpret=True`` on non-TPU backends so the CPU test
mesh exercises the identical kernel bodies (same pattern as
``ops.flash_attention``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import flax.linen as nn

from chainermn_tpu.ops.flash_attention import _scratch, _shape_like, _VMEM
from chainermn_tpu.utils import pvary

__all__ = [
    "fused_norm",
    "fused_norm_reference",
    "FusedBatchNormAct",
    "fused_norm_traffic_bytes",
    "resnet_bn_traffic_bytes",
]


# ---------------------------------------------------------------------------
# kernels: x is viewed as [R, C] (rows = every non-feature element), the grid
# streams row-tiles, and per-channel vectors ride as [1, C] blocks.
# ---------------------------------------------------------------------------


def _stats_kernel(x_ref, sum_ref, sq_ref, s_sum, s_sq):
    """Pass 1 (train fwd): accumulate Σx and Σx² per channel across tiles."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_sum[...] = jnp.zeros_like(s_sum)
        s_sq[...] = jnp.zeros_like(s_sq)

    x = x_ref[...].astype(jnp.float32)
    s_sum[...] += jnp.sum(x, axis=0, keepdims=True)
    s_sq[...] += jnp.sum(x * x, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        sum_ref[...] = s_sum[...]
        sq_ref[...] = s_sq[...]


def _apply_kernel(x_ref, mean_ref, invstd_ref, scale_ref, bias_ref, y_ref, *,
                  relu):
    """Pass 2 (fwd): y = relu?((x − μ)·(invstd·γ) + β), flax op order."""
    x = x_ref[...].astype(jnp.float32)
    mul = invstd_ref[...] * scale_ref[...]
    y = (x - mean_ref[...]) * mul + bias_ref[...]
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)


def _dz_xhat(x_ref, g_ref, mean_ref, invstd_ref, scale_ref, bias_ref, relu):
    """Shared bwd prologue: recompute x̂ and the masked upstream grad dz."""
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    xhat = (x - mean_ref[...]) * invstd_ref[...]
    if relu:
        z = xhat * scale_ref[...] + bias_ref[...]
        g = jnp.where(z > 0.0, g, 0.0)
    return g, xhat


def _bwd_reduce_kernel(x_ref, g_ref, mean_ref, invstd_ref, scale_ref,
                       bias_ref, dbeta_ref, dgamma_ref, s_db, s_dg, *, relu):
    """Bwd pass 1: dβ = Σdz and dγ = Σdz·x̂, fused into one traversal."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_db[...] = jnp.zeros_like(s_db)
        s_dg[...] = jnp.zeros_like(s_dg)

    dz, xhat = _dz_xhat(x_ref, g_ref, mean_ref, invstd_ref, scale_ref,
                        bias_ref, relu)
    s_db[...] += jnp.sum(dz, axis=0, keepdims=True)
    s_dg[...] += jnp.sum(dz * xhat, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        dbeta_ref[...] = s_db[...]
        dgamma_ref[...] = s_dg[...]


def _bwd_dx_kernel(x_ref, g_ref, mean_ref, invstd_ref, scale_ref, bias_ref,
                   dbeta_ref, dgamma_ref, dx_ref, *, relu, train, inv_rows):
    """Bwd pass 2: dx, with the stats-gradient terms folded in (train)."""
    dz, xhat = _dz_xhat(x_ref, g_ref, mean_ref, invstd_ref, scale_ref,
                        bias_ref, relu)
    k = scale_ref[...] * invstd_ref[...]
    if train:
        dx = k * (dz - dbeta_ref[...] * inv_rows
                  - xhat * (dgamma_ref[...] * inv_rows))
    else:
        dx = k * dz
    dx_ref[...] = dx.astype(dx_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _specs(br, c, n_vecs, kw):
    """x-tile spec followed by ``n_vecs`` per-channel [1, C] vector specs."""
    row = pl.BlockSpec((br, c), lambda i: (i, 0), **kw)
    vec = pl.BlockSpec((1, c), lambda i: (0, 0), **kw)
    return row, [vec] * n_vecs


def _stats_call(x2, block_rows, interpret):
    r, c = x2.shape
    kw = {} if _VMEM is None else {"memory_space": _VMEM}
    row, _ = _specs(block_rows, c, 0, kw)
    vec_out = pl.BlockSpec((1, c), lambda i: (0, 0), **kw)
    s_sum, s_sq = pl.pallas_call(
        _stats_kernel,
        grid=(r // block_rows,),
        in_specs=[row],
        out_specs=[vec_out, vec_out],
        out_shape=[_shape_like(x2, (1, c), jnp.float32),
                   _shape_like(x2, (1, c), jnp.float32)],
        scratch_shapes=_scratch([((1, c), jnp.float32),
                                 ((1, c), jnp.float32)]),
        interpret=interpret,
    )(x2)
    mean = s_sum / r
    var = jnp.maximum(s_sq / r - mean * mean, 0.0)  # fast variance, as flax
    return mean, var


def _apply_call(x2, mean, invstd, scale, bias, relu, block_rows, interpret):
    r, c = x2.shape
    kw = {} if _VMEM is None else {"memory_space": _VMEM}
    row, vecs = _specs(block_rows, c, 4, kw)
    return pl.pallas_call(
        functools.partial(_apply_kernel, relu=relu),
        grid=(r // block_rows,),
        in_specs=[row] + vecs,
        out_specs=row,
        out_shape=_shape_like(x2, (r, c), x2.dtype),
        interpret=interpret,
    )(x2, mean, invstd, scale, bias)


def _bwd_reduce_call(x2, g2, mean, invstd, scale, bias, relu, block_rows,
                     interpret):
    r, c = x2.shape
    kw = {} if _VMEM is None else {"memory_space": _VMEM}
    row, vecs = _specs(block_rows, c, 4, kw)
    vec_out = pl.BlockSpec((1, c), lambda i: (0, 0), **kw)
    return pl.pallas_call(
        functools.partial(_bwd_reduce_kernel, relu=relu),
        grid=(r // block_rows,),
        in_specs=[row, row] + vecs,
        out_specs=[vec_out, vec_out],
        out_shape=[_shape_like(x2, (1, c), jnp.float32),
                   _shape_like(x2, (1, c), jnp.float32)],
        scratch_shapes=_scratch([((1, c), jnp.float32),
                                 ((1, c), jnp.float32)]),
        interpret=interpret,
    )(x2, g2, mean, invstd, scale, bias)


def _bwd_dx_call(x2, g2, mean, invstd, scale, bias, dbeta, dgamma, relu,
                 train, block_rows, interpret):
    r, c = x2.shape
    kw = {} if _VMEM is None else {"memory_space": _VMEM}
    row, vecs = _specs(block_rows, c, 6, kw)
    return pl.pallas_call(
        functools.partial(_bwd_dx_kernel, relu=relu, train=train,
                          inv_rows=1.0 / r),
        grid=(r // block_rows,),
        in_specs=[row, row] + vecs,
        out_specs=row,
        out_shape=_shape_like(x2, (r, c), x2.dtype),
        interpret=interpret,
    )(x2, g2, mean, invstd, scale, bias, dbeta, dgamma)


# ---------------------------------------------------------------------------
# custom-VJP core over the flattened [R, C] view
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _fused_core(x2, scale, bias, mean_in, var_in, train, eps, relu,
                block_rows, interpret):
    (y2, mean, var), _ = _fused_core_fwd(x2, scale, bias, mean_in, var_in,
                                         train, eps, relu, block_rows,
                                         interpret)
    return y2, mean, var


def _fused_core_fwd(x2, scale, bias, mean_in, var_in, train, eps, relu,
                    block_rows, interpret):
    if train:
        mean, var = _stats_call(x2, block_rows, interpret)
    else:
        mean, var = mean_in, var_in
    invstd = jax.lax.rsqrt(var + eps)
    y2 = _apply_call(x2, mean, invstd, scale, bias, relu, block_rows,
                     interpret)
    return (y2, mean, var), (x2, scale, bias, mean, invstd, mean_in, var_in)


def _fused_core_bwd(train, eps, relu, block_rows, interpret, res, cts):
    # mean/var cotangents are dropped: running-stat updates sit outside
    # autodiff (flax variable writes), so nothing real flows through them.
    gy2, _, _ = cts
    x2, scale, bias, mean, invstd, mean_in, var_in = res
    dbeta, dgamma = _bwd_reduce_call(x2, gy2, mean, invstd, scale, bias,
                                     relu, block_rows, interpret)
    dx2 = _bwd_dx_call(x2, gy2, mean, invstd, scale, bias, dbeta, dgamma,
                       relu, train, block_rows, interpret)
    # Zero cotangents typed like the INPUTS they answer for: inside
    # shard_map the train-mode placeholders are invariant while the batch
    # statistics are device-varying, and a custom VJP must return each
    # input's own varying-axes type.
    def zero_like_input(ref):
        return pvary(jnp.zeros_like(ref), tuple(jax.typeof(ref).vma))

    return (dx2, dgamma.astype(scale.dtype), dbeta.astype(bias.dtype),
            zero_like_input(mean_in), zero_like_input(var_in))


_fused_core.defvjp(_fused_core_fwd, _fused_core_bwd)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


def _pick_block_rows(r, c):
    """Largest power-of-two row tile that divides R and keeps an f32 tile
    within ~1 MiB of VMEM (auto-halving, like flash_attention's defaults)."""
    budget = max(1, (1 << 20) // max(1, c * 4))
    br = 1
    while br * 2 <= min(budget, r):
        br *= 2
    while r % br:
        br //= 2
    return max(br, 1)


def fused_norm(x, scale, bias, mean=None, var=None, *,
               use_running_average=False, epsilon=1e-5, relu=True,
               block_rows=None, interpret=None):
    """Fused BatchNorm(+ReLU) over the last axis of ``x``.

    Returns ``(y, mean, var)`` where mean/var are the per-channel batch
    statistics actually used (in eval mode, the running stats passed in).
    Differentiable via a custom VJP whose backward fuses the dγ/dβ
    reductions with dx (two activation traversals total).

    Args:
      x: activations ``[..., C]`` (any rank; features last).
      scale, bias: per-channel ``[C]`` affine parameters (γ, β).
      mean, var: running statistics ``[C]`` — required when
        ``use_running_average=True``, ignored otherwise.
      use_running_average: eval mode — normalize with ``mean``/``var``
        instead of batch statistics.
      epsilon: added to variance before the rsqrt.
      relu: fuse ``max(y, 0)`` into the same traversal.
      block_rows: row-tile size (must divide the flattened row count);
        ``None`` auto-sizes to ~1 MiB f32 tiles.
      interpret: force Pallas interpret mode; ``None`` auto-selects
        (interpret off TPU).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret and jax.typeof(x).vma:
        # jax's Pallas interpreter is not vma-aware, so a kernel cannot
        # be interpreted inside a shard_map body (the CPU mesh).  There
        # the oracle's identical math stands in; on the chip the kernels
        # are compiled and this never runs.
        return fused_norm_reference(
            x, scale, bias, mean, var,
            use_running_average=use_running_average, epsilon=epsilon,
            relu=relu)
    c = x.shape[-1]
    r = x.size // c
    if r == 0:
        raise ValueError(f"fused_norm: empty activation batch for {x.shape}")
    if block_rows is None:
        block_rows = _pick_block_rows(r, c)
    elif r % block_rows:
        raise ValueError(
            f"block_rows={block_rows} must divide row count {r} "
            f"(x.shape={x.shape})")
    x2 = x.reshape(r, c)
    s2 = jnp.asarray(scale, jnp.float32).reshape(1, c)
    b2 = jnp.asarray(bias, jnp.float32).reshape(1, c)
    if use_running_average:
        if mean is None or var is None:
            raise ValueError(
                "fused_norm(use_running_average=True) needs mean= and var=")
        m2 = jnp.asarray(mean, jnp.float32).reshape(1, c)
        v2 = jnp.asarray(var, jnp.float32).reshape(1, c)
    else:
        # placeholders; train mode computes batch stats inside the VJP
        # boundary (they are dead inputs, kept for a stable signature).
        m2 = jnp.zeros((1, c), jnp.float32)
        v2 = jnp.ones((1, c), jnp.float32)
    y2, m, v = _fused_core(x2, s2, b2, m2, v2, not use_running_average,
                           float(epsilon), bool(relu), int(block_rows),
                           bool(interpret))
    return y2.reshape(x.shape), m.reshape(c), v.reshape(c)


def fused_norm_reference(x, scale, bias, mean=None, var=None, *,
                         use_running_average=False, epsilon=1e-5, relu=True):
    """Pure-XLA oracle with the kernels' exact math (f32, fast variance,
    flax op order) — the gradient-parity reference for the custom VJP."""
    c = x.shape[-1]
    x2 = x.reshape(-1, c).astype(jnp.float32)
    if use_running_average:
        m = jnp.asarray(mean, jnp.float32)
        v = jnp.asarray(var, jnp.float32)
    else:
        m = jnp.mean(x2, axis=0)
        v = jnp.maximum(jnp.mean(x2 * x2, axis=0) - m * m, 0.0)
    mul = jax.lax.rsqrt(v + epsilon) * jnp.asarray(scale, jnp.float32)
    y = (x2 - m) * mul + jnp.asarray(bias, jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.reshape(x.shape).astype(x.dtype), m, v


# ---------------------------------------------------------------------------
# flax module: drop-in for nn.BatchNorm at the resnet norm_cls seam
# ---------------------------------------------------------------------------


class FusedBatchNormAct(nn.Module):
    """``nn.BatchNorm``-compatible module backed by the fused kernels.

    Identical parameter/stat tree to ``nn.BatchNorm`` (params ``scale``/
    ``bias`` in ``param_dtype``; float32 ``batch_stats`` ``mean``/``var``
    with the same momentum update), so checkpoints and the resnet
    ``norm_cls`` seam swap over without surgery.  ``fuse_relu=True``
    folds the activation into the same kernel traversal; the resnet
    blocks request it through the ``supports_fused_relu`` marker.
    """

    use_running_average: Optional[bool] = None
    momentum: float = 0.99
    epsilon: float = 1e-5
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    use_bias: bool = True
    use_scale: bool = True
    bias_init: Callable = nn.initializers.zeros_init()
    scale_init: Callable = nn.initializers.ones_init()
    fuse_relu: bool = False
    block_rows: Optional[int] = None

    supports_fused_relu = True  # inspected by models.resnet (class attr,
    #                             not a dataclass field: no annotation)

    @nn.compact
    def __call__(self, x, use_running_average: Optional[bool] = None):
        use_ra = nn.merge_param("use_running_average",
                                self.use_running_average, use_running_average)
        feat = (x.shape[-1],)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, jnp.float32), feat)
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, jnp.float32), feat)
        scale = (self.param("scale", self.scale_init, feat, self.param_dtype)
                 if self.use_scale else jnp.ones(feat, jnp.float32))
        bias = (self.param("bias", self.bias_init, feat, self.param_dtype)
                if self.use_bias else jnp.zeros(feat, jnp.float32))
        odt = self.dtype or jnp.promote_types(x.dtype, self.param_dtype)
        y, mean, var = fused_norm(
            jnp.asarray(x, odt), scale, bias,
            mean=ra_mean.value, var=ra_var.value,
            use_running_average=use_ra, epsilon=self.epsilon,
            relu=self.fuse_relu, block_rows=self.block_rows)
        if not use_ra and not self.is_initializing():
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1.0 - m) * mean
            ra_var.value = m * ra_var.value + (1.0 - m) * var
        return y


# ---------------------------------------------------------------------------
# traffic model (the gateable number)
# ---------------------------------------------------------------------------


def fused_norm_traffic_bytes(shape, dtype=jnp.bfloat16, *, train=True,
                             relu=True, backward=True):
    """Modeled HBM bytes for one BN(+ReLU) boundary, fused vs unfused.

    The model counts full-activation HBM traversals (reads and writes of
    ``prod(shape)`` elements at ``dtype`` width) plus the per-channel
    float32 vectors each pass touches.  The *fused* side prices exactly
    what the kernels in this module do.  The *unfused* side prices
    flax's ``nn.BatchNorm`` + separate ReLU at one traversal per logical
    op — the no-inter-op-fusion roofline, the same convention
    ``planner.plan_wire_bytes`` uses for ring hops.  XLA does fuse some
    adjacent elementwise ops in practice, so the modeled ratio bounds
    the achievable saving from above; the *measured* delta is
    ``bench_resnet_probe.py``'s job (committed as RESNET_PROBE_r09).

    Pass tables (train, relu, fwd+bwd; R·C activation elements):

      unfused fwd: mean 1R · var 1R · normalize 1R+1W · scale/shift
                   1R+1W · relu 1R+1W                       = 8 acts
      unfused bwd: relu-bwd 2R+1W · dβ 1R · dγ 2R · dx 2R+1W = 9 acts
      fused   fwd: stats 1R · apply 1R+1W                    = 3 acts
      fused   bwd: reduce 2R · dx 2R+1W                      = 5 acts

    17 vs 8 traversals → 2.1× fewer modeled bytes per relu'd boundary
    (pinned ≥2× by tests).  Without relu: 11 vs 8; eval mode drops the
    stat passes on both sides.

    Returns a dict with both pass tables, totals, and the ratio.
    """
    shape = tuple(int(s) for s in shape)
    c = shape[-1]
    n = 1
    for s in shape:
        n *= s
    act = n * jnp.dtype(dtype).itemsize
    vec = c * 4  # per-channel f32 vectors

    def _table(passes):
        total = sum(b for _, b in passes)
        return {"passes": [[name, int(b)] for name, b in passes],
                "total_bytes": int(total)}

    fused = []
    if train:
        fused.append(("fwd_stats", act + 2 * vec))
    fused.append(("fwd_apply", 2 * act + 4 * vec))
    if backward:
        fused.append(("bwd_reduce", 2 * act + 6 * vec))
        fused.append(("bwd_dx", 3 * act + 6 * vec))

    unfused = []
    if train:
        unfused.append(("fwd_mean", act + vec))
        unfused.append(("fwd_var", act + vec))
    unfused.append(("fwd_normalize", 2 * act + 2 * vec))
    unfused.append(("fwd_scale_shift", 2 * act + 2 * vec))
    if relu:
        unfused.append(("fwd_relu", 2 * act))
    if backward:
        if relu:
            unfused.append(("bwd_relu", 3 * act))
        unfused.append(("bwd_dbeta", act + vec))
        unfused.append(("bwd_dgamma", 2 * act + vec))
        unfused.append(("bwd_dx", 3 * act + 4 * vec))

    f, u = _table(fused), _table(unfused)
    return {
        "shape": list(shape),
        "dtype": str(jnp.dtype(dtype)),
        "train": bool(train),
        "relu": bool(relu),
        "backward": bool(backward),
        "activation_bytes": int(act),
        "fused": f,
        "unfused": u,
        "ratio": u["total_bytes"] / f["total_bytes"],
    }


def resnet_bn_traffic_bytes(batch, *, image=224, stage_sizes=(3, 4, 6, 3),
                            num_filters=64, dtype=jnp.bfloat16, train=True):
    """Sum ``fused_norm_traffic_bytes`` over every BN boundary of a
    bottleneck ResNet (the shapes ``models.resnet.ResNet50`` emits).

    Boundaries per bottleneck block: norm1 (+relu, input spatial), norm2
    (+relu, output spatial), norm3 (no relu — the activation lands after
    the residual add) and, on shape-changing blocks, the no-relu
    ``norm_proj``.  Plus the stem's BN+relu.  Returns fused/unfused
    totals, the ratio, and the per-boundary list — the
    ``resnet_bn_traffic_bytes`` perf-gate budget reads
    ``fused_total_bytes``.
    """
    boundaries = []  # (name, shape, relu)
    s = image // 2  # stem conv 7x7 stride 2
    boundaries.append(("stem/bn_init", (batch, s, s, num_filters), True))
    s = s // 2  # 3x3 maxpool stride 2
    for i, blocks in enumerate(stage_sizes):
        f = num_filters * (2 ** i)
        for j in range(blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            s_in, s_out = s, s // stride
            tag = f"stage{i + 1}/block{j + 1}"
            boundaries.append((f"{tag}/norm1", (batch, s_in, s_in, f), True))
            boundaries.append((f"{tag}/norm2", (batch, s_out, s_out, f),
                               True))
            boundaries.append((f"{tag}/norm3",
                               (batch, s_out, s_out, 4 * f), False))
            if j == 0:  # channel (and possibly spatial) change: projection
                boundaries.append((f"{tag}/norm_proj",
                                   (batch, s_out, s_out, 4 * f), False))
            s = s_out
    rows, fused_total, unfused_total = [], 0, 0
    for name, shape, relu in boundaries:
        t = fused_norm_traffic_bytes(shape, dtype, train=train, relu=relu)
        fused_total += t["fused"]["total_bytes"]
        unfused_total += t["unfused"]["total_bytes"]
        rows.append({"name": name, "shape": list(shape), "relu": relu,
                     "fused_bytes": t["fused"]["total_bytes"],
                     "unfused_bytes": t["unfused"]["total_bytes"]})
    return {
        "batch": int(batch),
        "image": int(image),
        "stage_sizes": list(stage_sizes),
        "dtype": str(jnp.dtype(dtype)),
        "train": bool(train),
        "num_boundaries": len(rows),
        "fused_total_bytes": int(fused_total),
        "unfused_total_bytes": int(unfused_total),
        "ratio": unfused_total / fused_total,
        "boundaries": rows,
    }

"""QK-norm and rotation of the heads of q (or of k) in one pass.

``qk_norm_rope(x[B, T, H, D], scale[D], rotary=(cos, sin))`` computes, a
head at a time, ``y = R_t(x * rsqrt(mean(x^2) + eps) * scale)``: the RMS
norm over ``head_dim`` that an attention layer puts on q and k (one scale,
shared by the heads) and then the "rotate half" turn of rotary position
embedding by row t's angles, ``R_t(n) = n * cos_t + half_turn(n) * sin_t``
with ``half_turn(n) = concat(-n[D/2:], n[:D/2])``.  Without ``rotary`` (a
layer that does not rotate) it is the norm alone.  The mathematics is that
of ``models.lfm2.RMSNorm`` followed by ``models.lfm2.rope``, the tests'
oracle: statistics, scale, angles and the turn in float32, and ONE rounding
to ``dtype`` at the end where the two modules round twice.

Why a kernel (PERF.md section 6, PR 41): as ``jax.numpy`` the two modules
are float32 passes that XLA cannot keep in one fusion.  It writes the
normalised q as float32, then the two halves of the half-turn as float32
slices whose 64 lanes are padded to 128, and reads all of it back: 1,531 MB
for the forward of a q of 64 MB that needs 128.  Here a tile stays in VMEM,
where the half-turn is one lane rotation (``pltpu.roll``) times a sign that
is folded into the sine table.

**Forward**: x is taken as ``[B, T, H * D]`` (the projection's own output,
no transpose); a grid step holds ``tile`` rows of a block of whole heads
and takes it a head (one or more 128-lane columns) at a time.  The result is WRITTEN head-major, ``[B, H, T, D]``, as
the flash kernels read q and k, and handed back as its transpose, which XLA
cancels against the attention's own: written token-major it cost a
transposing copy of q before every flash kernel (PERF.md section 6, PR 41).
The tables are ``[T, D]`` float32, built once a call outside the kernel
(YaRN's blended frequencies and ``attention_factor`` are the caller's, in
the tables) and read by row tile; the blocks of heads are the innermost
grid dimension, so a row tile's tables are fetched once.

**Backward** (a ``jax.custom_vjp``): ONE pass that reads the saved INPUT
(x before the norm) and the incoming gradient and writes the input's
gradient (the incoming one read head-major, as the flash kernels leave
it).  The turn's transpose is the turn by the negative angle, ``g *
cos + half_turn^T(g * sin)``; the statistics are recomputed in VMEM, not
saved; with ``xhat = x * inv`` and ``d = g_n * scale`` the input's gradient
is ``inv * (d - xhat * mean(d * xhat))``.  The scale's gradient ``sum(g_n *
xhat)`` over rows and heads leaves the kernel as one float32 ``[8, D]``
partial sum a row tile (sublane groups added on the VPU, no cross-sublane
reduction) and is summed outside.  Nothing of x's size is written in
float32.

No scope is opened here: the chip's trace names a Pallas kernel after the
innermost scope around it, and the CALLER chooses that name
(``models.lfm2.qk_norm_and_rope`` calls under ``chainermn.rope``, which the
benchmark reads as ``norm_rope_ms``; docs/observability.md).  Off the TPU
the kernels run in Pallas' interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.utils import pvary

_LANE = 128
# A grid step holds 512 rows of up to 1 MiB of x: eight bf16 heads of 128,
# 1 MiB in and 1 MiB out of HBM a head-block of q (the tables beside it),
# ~2.6 us at the chip's rate against ~0.35 us a grid step.  Swept on the
# chip (PERF.md section 6, PR 41): walking a tile in chunks of 16-128 rows
# was 1.5-2.8 x slower than taking it whole, a head at a time (the lane
# reduction and the lane rotation have latencies that few rows do not hide);
# whole tiles of 256 x 16 heads, 512 x 8 and 1024 x 4 run within 4 % of each
# other.
_TILE_ROWS = 512
_BLOCK_BYTES = 1 << 20      # of x in a grid step: whole heads up to this


def _tiling(seq, heads, dim, itemsize):
    """``(rows of a grid step, heads of a block)``: whole rows of 16 (a
    bf16 register's), as many as fit the sequence up to ``_TILE_ROWS``."""
    tile = min(_TILE_ROWS, max(seq // 16, 1) * 16)
    fitting = [h for h in range(1, heads + 1) if heads % h == 0
               and tile * h * dim * itemsize <= _BLOCK_BYTES]
    return tile, max(fitting, default=1)


def _half_turn(n):
    """``n[(i + D/2) mod D]``: the half-turn without its sign (the sine
    table carries it).  A rotation by half the lanes is its own inverse and
    its own transpose."""
    return pltpu.roll(n, n.shape[-1] // 2, 1)


# What a step's set-up pays for these kernels is their tracing, sixteen to
# twenty of them a step (PERF.md section 6, PR 41): so a block's heads are a
# ``fori_loop`` (one head traced, not eight: 2 % slower on the chip than the
# heads unrolled), and the bodies bind ``lax`` primitives, not ``jax.numpy``
# calls, each of which costs several times the tracing of its primitive.
def _head_lanes(head, dim):
    """The lanes of a block's ``head``-th head (a traced index)."""
    return pl.ds(pl.multiple_of(head * dim, _LANE), dim)


def _spread(part, shape):
    """[rows, 1] or [1, D] as [rows, D]."""
    return lax.broadcast_in_dim(part, shape, (0, 1))


def _lane_mean(x):
    """The mean over a row's lanes, [rows, 1]."""
    total = lax.broadcast_in_dim(lax.reduce_sum(x, (1,)),
                                 (x.shape[0], 1), (0,))
    return lax.div(total, np.float32(x.shape[1]))


def _inverse_rms(x, eps):
    return _spread(
        lax.rsqrt(lax.add(_lane_mean(lax.mul(x, x)), np.float32(eps))),
        x.shape)


def _forward_kernel(x_ref, scale_ref, *rest, heads, dim, eps):
    *cos_sin, y_ref = rest
    tile = x_ref.shape[0]
    scale = _spread(scale_ref[...], (tile, dim))
    cos_sin = [table[...] for table in cos_sin]

    def one_head(head, _):
        x = lax.convert_element_type(x_ref[:, _head_lanes(head, dim)],
                                     jnp.float32)
        n = lax.mul(lax.mul(x, _inverse_rms(x, eps)), scale)
        if cos_sin:
            cos, sin = cos_sin
            n = lax.add(lax.mul(n, cos), lax.mul(_half_turn(n), sin))
        y_ref[head] = lax.convert_element_type(n, y_ref.dtype)

    lax.fori_loop(0, heads, one_head, None)


def _backward_kernel(x_ref, g_ref, scale_ref, *rest, heads, dim, eps, seq):
    *cos_sin, dx_ref, dscale_ref = rest
    tile = x_ref.shape[0]
    scale = _spread(scale_ref[...], (tile, dim))
    cos_sin = [table[...] for table in cos_sin]

    def one_head(head, by_scale):
        cols = _head_lanes(head, dim)
        x = lax.convert_element_type(x_ref[:, cols], jnp.float32)
        g = lax.convert_element_type(g_ref[head], jnp.float32)
        if cos_sin:
            cos, sin = cos_sin
            g = lax.add(lax.mul(g, cos), _half_turn(lax.mul(g, sin)))
        inv = _inverse_rms(x, eps)
        xhat = lax.mul(x, inv)
        g_xhat = lax.mul(g, xhat)
        along = _spread(_lane_mean(lax.mul(g_xhat, scale)), x.shape)
        dx = lax.mul(inv, lax.sub(lax.mul(g, scale), lax.mul(xhat, along)))
        dx_ref[:, cols] = lax.convert_element_type(dx, dx_ref.dtype)
        return lax.add(by_scale, g_xhat)

    by_scale = lax.fori_loop(0, heads, one_head,
                             lax.full((tile, dim), 0.0, jnp.float32))
    if seq % tile:
        # a last tile's rows past the sequence hold whatever was there:
        # their stores are dropped, their sums must be too
        row = pl.program_id(1) * tile + lax.broadcasted_iota(
            jnp.int32, (tile, dim), 0)
        by_scale = lax.select(lax.lt(row, np.int32(seq)), by_scale,
                              lax.full_like(by_scale, 0.0))
    # sublane groups added on the VPU: no reduction across sublanes here
    dscale = lax.reduce_sum(by_scale.reshape(tile // 8, 8, dim), (0,))
    block = pl.program_id(2)

    @pl.when(block == 0)
    def _first():
        dscale_ref[...] = dscale

    @pl.when(block > 0)
    def _add():
        dscale_ref[...] += dscale


def _call(x, g, scale, rotary, eps, out_dtype):
    """The forward kernel over ``x`` [B, T, H, D], giving y [B, H, T, D];
    or, with the result's gradient ``g`` [B, H, T, D], the backward kernel,
    giving x's gradient [B, T, H, D] and the scale's [D].  ``scale`` is
    [1, D], ``rotary`` the tables [T, D] or nothing."""
    batch, seq, heads, dim = x.shape
    tile, per_block = _tiling(seq, heads, dim, x.dtype.itemsize)
    grid = (batch, pl.cdiv(seq, tile), heads // per_block)
    token_major = pl.BlockSpec((None, tile, per_block * dim),
                               lambda b, t, h: (b, t, h))
    head_major = pl.BlockSpec((None, per_block, tile, dim),
                              lambda b, t, h: (b, h, t, 0))
    by_row = pl.BlockSpec((tile, dim), lambda b, t, h: (t, 0))
    whole = pl.BlockSpec((1, dim), lambda b, t, h: (0, 0))
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, vma=jax.typeof(x).vma)
    sizes = dict(heads=per_block, dim=dim, eps=eps)
    if g is None:
        kernel = functools.partial(_forward_kernel, **sizes)
        out_shape = shape((batch, heads, seq, dim), out_dtype)
        out_specs = head_major
    else:
        kernel = functools.partial(_backward_kernel, seq=seq, **sizes)
        out_shape = [shape((batch, seq, heads * dim), out_dtype),
                     shape((batch, grid[1], 8, dim), jnp.float32)]
        out_specs = [token_major, pl.BlockSpec(
            (None, None, 8, dim), lambda b, t, h: (b, t, 0, 0))]
    passes = 2 if g is None else 3
    out = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[token_major] + [head_major] * (g is not None) + [whole]
        + [by_row] * len(rotary),
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=12 * passes * x.size, transcendentals=x.size // dim,
            bytes_accessed=passes * x.size * x.dtype.itemsize
            + 4 * batch * seq * dim * len(rotary)),
        interpret=jax.default_backend() != "tpu",
    )(x.reshape(batch, seq, heads * dim), *(() if g is None else (g,)),
      scale, *rotary)
    if g is None:
        return out
    return out[0].reshape(x.shape), out[1].sum((0, 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm_rope(x, scale, rotary, eps, dtype):
    return _call(x, None, scale, rotary, eps, dtype)


def _norm_rope_fwd(x, scale, rotary, eps, dtype):
    return _norm_rope(x, scale, rotary, eps, dtype), (x, scale, rotary)


def _norm_rope_bwd(eps, dtype, residual, g):
    x, scale, rotary = residual
    dx, dscale = _call(x, g, scale, rotary, eps, x.dtype)
    return dx, dscale[None], None


_norm_rope.defvjp(_norm_rope_fwd, _norm_rope_bwd)


def qk_norm_rope(x, scale, rotary=None, *, eps, dtype=None):
    """``R(x * rsqrt(mean(x^2, -1) + eps) * scale)`` over the last axis of
    ``x`` [B, T, H, D], D a multiple of 128, in ``dtype`` (``x``'s unless
    given); differentiable in ``x`` and ``scale`` [D].

    ``rotary`` is None (no rotation) or ``(cos, sin)``, each [T, D] float32
    with the two halves of D alike (``cos(t * inv_freq)`` twice), whatever
    factor the caller scales them by included: row t of every head turns by
    ``n * cos[t] + concat(-n[D/2:], n[:D/2]) * sin[t]``."""
    dim = x.shape[-1]
    if x.ndim != 4 or dim % _LANE:
        raise ValueError("qk_norm_rope needs x [B, T, H, D] with D a "
                         f"multiple of {_LANE}, got {x.shape}")
    if scale.shape != (dim,):
        raise ValueError(f"scale {scale.shape} must be [{dim}]")
    if rotary is not None:
        cos, sin = rotary
        if cos.shape != (x.shape[1], dim) or sin.shape != cos.shape:
            raise ValueError(f"rotary tables {cos.shape}, {sin.shape} must "
                             f"both be [{x.shape[1]}, {dim}]")
        sign = jnp.where(jnp.arange(dim) < dim // 2, -1.0, 1.0)
        rotary = (cos.astype(jnp.float32), sin.astype(jnp.float32) * sign)
    # inside ``shard_map`` the kernels' results vary as x does; so must what
    # the custom VJP answers for (JAX sums the scale's gradient back itself)
    varying = tuple(jax.typeof(x).vma)
    constants = jax.tree.map(
        lambda a: pvary(a, varying),
        (scale.astype(jnp.float32)[None], rotary or ()))
    # written head-major (the module's text); the gradient's transpose
    # cancels likewise
    return _norm_rope(x, *constants, float(eps),
                      jnp.dtype(dtype or x.dtype)).transpose(0, 2, 1, 3)


__all__ = ["qk_norm_rope"]

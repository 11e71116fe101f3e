"""int8 / fp8 quantizers — per-chunk power-of-two scales, stochastic
rounding, in-wire summation.

The collective between ``compress`` and ``decompress`` SUMS wire values
in wire arithmetic (``lax.psum`` / ``lax.psum_scatter`` over int8 or
float8 buffers — XLA lowers both natively).  That forces two design
points:

* **Overflow-safe codes**: each rank clips its codes to ``max_code //
  world_size`` (int8: ``127 // W``; fp8 e4m3: ``448 / W``), so the
  summed wire value cannot overflow/saturate no matter how adversarial
  the addends.  What clipping loses, error feedback re-feeds next step.
* **Rank-identical scales**: summing codes is only meaningful when all
  ranks quantized with the same scale.  Scales are therefore *delayed*:
  step t uses the scales derived from step t-1's **summed** (hence
  globally identical) gradient, so every rank updates them identically
  with zero extra collectives.  Scales are powers of two (stored as
  exponents), exactly representable in any float wire — which the FSDP
  seam exploits to piggyback scale redistribution on the parameter
  all-gather.  A cold scale (init ``2**0``) converges geometrically:
  too-small scales clip (EF retries), all-zero codes shrink the
  exponent by 2 per step.
* **Saturation flags on the wire**: the summed amax *underestimates*
  per-rank amplitude whenever ranks cancel (random-sign gradients sum
  to ~``sqrt(W)`` x the per-rank scale), so an amax-only update can
  wedge the scale below the clip point forever — every rank clips,
  the clipped sum looks small, the scale never grows, and the EF
  residual diverges linearly.  Each rank therefore appends one 0/1
  flag per chunk ("did I clip anywhere in this chunk?") to the code
  buffer; the SAME collective sums them into a per-chunk clip count
  (bounded by ``world <= max_code/2``, so the in-wire sum cannot
  saturate and stays nonzero whenever any rank clipped), and any
  nonzero count forces the exponent up by at least 1.  Zero extra
  collectives, ~``1/chunk_size`` wire overhead.

Stochastic rounding (``floor(v/s + u)``, ``u ~ U[0,1)``) keeps the
quantizer unbiased; the PRNG stream is derived from an explicit
``(seed, step, rank)`` triple threaded through the step — deterministic
replay, no hidden RNG state.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.compression import error_feedback as _ef
from chainermn_tpu.compression.base import Compressor, register_compressor

_E_MIN, _E_MAX = -60.0, 60.0   # exponent clamp (2**±60 covers f32 grads)


class _ScaledQuantizer(Compressor):
    """Shared machinery of the int8/fp8 codecs (see module docstring).

    Subclasses pin ``wire`` (the collective dtype), ``max_code`` (the
    symmetric wire range), and ``_round`` (integer vs float-ulp
    stochastic rounding).
    """

    stateful = True
    wire: str = "?"
    max_code: float = 0.0

    def __init__(self, chunk_size: int = 1024, stochastic: bool = True,
                 seed: int = 0, headroom: float = 2.0):
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        self.chunk_size = int(chunk_size)
        self.stochastic = bool(stochastic)
        self.seed = int(seed)
        self.headroom = float(headroom)

    def config(self):
        return {"chunk_size": self.chunk_size,
                "stochastic": self.stochastic,
                "seed": self.seed, "headroom": self.headroom}

    def wire_dtype_for(self, dtype):
        return jnp.dtype(self.wire)

    # -- wire budget ---------------------------------------------------------
    def clip_limit(self, world_size: int) -> float:
        """Per-rank |code| bound so the in-wire sum cannot overflow."""
        c = self.max_code / world_size
        if c < 2.0:
            raise ValueError(
                f"{self.name} in-wire summation needs max_code/world >= 2 "
                f"(got {self.max_code}/{world_size}): too few code levels "
                f"per rank — use fp8 or an uncompressed wire at this world "
                f"size")
        return c

    def effective_clip(self, world_size: int) -> float:
        """The |code| bound :meth:`encode` actually applies (the int8
        codec floors :meth:`clip_limit` to the integer grid)."""
        return self.clip_limit(world_size)

    #: saturation-flag threshold, in multiples of the clip limit.  Mild
    #: tail clipping (a lone outlier a hair past the limit) is GOOD —
    #: EF re-feeds it and the finer scale helps every other coordinate
    #: — so the flag only fires past this margin.  A genuinely wedged
    #: scale blows through it within a step or two regardless: the
    #: clipped excess re-enters through the EF residual, so the
    #: pre-quantization value COMPOUNDS until the flag trips.
    sat_margin = 2.0

    def saturation_flags(self, v, scale_pos, world_size: int,
                         chunk_len: int):
        """Per-chunk 0/1 wire flags: did THIS rank clip past
        ``sat_margin`` x the clip limit anywhere in the chunk?
        Appended to the code buffer so the clip count rides the codes'
        own collective — the summed count tells every rank to escalate
        a wedged scale even when cancellation hides the clipping from
        the summed amax (see module docstring)."""
        c = self.sat_margin * self.effective_clip(world_size)
        over = jnp.abs(v / scale_pos) > c
        return jnp.any(over.reshape(-1, chunk_len),
                       axis=1).astype(jnp.dtype(self.wire))

    # -- PRNG ----------------------------------------------------------------
    def make_key(self, step, rank=None):
        """Stochastic-rounding key for (seed, step[, rank]) — explicit
        and replayable; ``rank`` decorrelates the per-rank dither."""
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                 jnp.asarray(step, jnp.int32))
        if rank is not None:
            key = jax.random.fold_in(key, jnp.asarray(rank, jnp.int32))
        return key

    # -- codec primitives (shared by both exchange seams) --------------------
    def encode(self, v, scale_pos, key, world_size: int):
        raise NotImplementedError

    def decode(self, codes, scale_pos):
        return codes.astype(jnp.float32) * scale_pos

    def next_exponent(self, e_prev, summed_amax, world_size: int,
                      sat_count=None):
        """Delayed pow2 scale update from the globally-identical SUMMED
        amax (per chunk): target per-rank amplitude ``amax/world`` at
        ``clip/headroom`` code levels; all-zero chunks shrink by 2**-2
        per step so a cold-started too-large scale converges fast.

        ``sat_count`` (the summed per-chunk clip flags, identical on
        every rank because the wire sum is) breaks the cancellation
        stall: any rank reporting heavy clipping (``sat_margin`` past
        the limit — mild tail clipping stays invisible, EF handles it)
        forces the exponent up by at least 1 that step.  A wedged scale
        re-trips the flag every couple of steps because the clipped
        excess compounds through the EF residual, so the scale climbs
        until the bulk of the mass fits."""
        c = self.clip_limit(world_size)
        target = (self.headroom * summed_amax) / (world_size * c)
        e_new = jnp.ceil(jnp.log2(jnp.maximum(target, 2.0 ** _E_MIN)))
        cand = jnp.where(summed_amax > 0, e_new, e_prev - 2.0)
        if sat_count is not None:
            cand = jnp.where(sat_count > 0,
                             jnp.maximum(cand, e_prev + 1.0), cand)
        return jnp.clip(cand, _E_MIN, _E_MAX)

    # -- allreduce-seam protocol --------------------------------------------
    def _padded(self, length: int) -> int:
        return length + (-length) % self.chunk_size

    def n_chunks(self, length: int) -> int:
        return self._padded(length) // self.chunk_size

    def init_state(self, length: int, world_size: int = 1, hop=None):
        del world_size  # shape-independent; kept for API symmetry
        return _ef.init_state(self, self._padded(int(length)),
                              self.n_chunks(int(length)), hop=hop)

    def scale_per_pos(self, scale_e):
        return jnp.repeat(jnp.exp2(scale_e), self.chunk_size)

    def compress(self, buf, state, rank=None, world_size: int = 1):
        """EF-compress one flat float buffer into wire codes (padded to
        the chunk grid, with one trailing saturation flag per chunk).
        Residual and step advance; scales are read only (they update in
        :meth:`decompress`, from summed data)."""
        m = int(buf.shape[0])
        mp = self._padded(m)
        v = jnp.zeros((mp,), jnp.float32).at[:m].set(
            buf.astype(jnp.float32))
        v = v + state.ef
        sp = self.scale_per_pos(state.scale)
        key = self.make_key(state.step[0], rank)
        codes = self.encode(v, sp, key, world_size)
        new_ef = v - self.decode(codes, sp)
        flags = self.saturation_flags(v, sp, world_size, self.chunk_size)
        return (jnp.concatenate([codes, flags]),
                state._replace(ef=new_ef, step=state.step + 1.0))

    def decompress(self, wire, state, world_size: int = 1, axes=None):
        """Decode the SUMMED wire buffer back to a float32 SUM (the
        caller divides by world for mean semantics) and advance the
        delayed scales from its per-chunk amax and summed clip count —
        identical on every rank because the summed wire is.

        ``axes``: the mesh axes the wire was summed over, when called
        inside ``shard_map``.  The scales are rank-identical over them
        by construction but are read from a per-rank state slot, which
        the varying-axes type system can only take for device-varying —
        and with them everything decoded, down to the updated
        parameters.  ``pmax`` is the identity on identical values and
        types the scales replicated (one exponent per chunk on the
        wire)."""
        mp = int(state.ef.shape[0])
        scale = state.scale if axes is None else jax.lax.pmax(
            state.scale, axes)
        sp = self.scale_per_pos(scale)
        out = self.decode(wire[:mp], sp)
        amax = jnp.max(jnp.abs(out).reshape(-1, self.chunk_size), axis=1)
        new_e = self.next_exponent(scale, amax, world_size,
                                   wire[mp:].astype(jnp.float32))
        return out, state._replace(scale=new_e)


class Int8Compressor(_ScaledQuantizer):
    """int8 wire: ``codes = clip(round(v / 2**e), ±(127 // W))``, summed
    across ranks in int8 arithmetic (~4x fewer wire bytes than f32)."""

    name = "int8"
    wire = "int8"
    max_code = 127.0

    def effective_clip(self, world_size: int) -> float:
        return float(int(self.clip_limit(world_size)))

    def encode(self, v, scale_pos, key, world_size: int):
        c = self.effective_clip(world_size)
        q = v / scale_pos
        if self.stochastic:
            q = jnp.floor(q + jax.random.uniform(key, q.shape))
        else:
            q = jnp.round(q)
        return jnp.clip(q, -c, c).astype(jnp.int8)


class Fp8Compressor(_ScaledQuantizer):
    """float8_e4m3 wire: scaled values cast to fp8 and summed in fp8
    arithmetic — coarser than int8 near the chunk amax (3 mantissa
    bits) but with ~2**15 dynamic range inside a chunk, so it tolerates
    heavy-tailed gradients that int8's uniform grid clips.  Stochastic
    rounding dithers by the value's own e4m3 ulp before the cast."""

    name = "fp8"
    wire = "float8_e4m3fn"
    max_code = 448.0

    def encode(self, v, scale_pos, key, world_size: int):
        c = self.clip_limit(world_size)
        q = jnp.clip(v / scale_pos, -c, c)
        if self.stochastic:
            # e4m3 has 3 mantissa bits: ulp(x) = 2**(floor(log2|x|) - 3);
            # frexp's exponent e has |x| in [2**(e-1), 2**e)
            _, e = jnp.frexp(q)
            ulp = jnp.exp2(jnp.asarray(e - 1 - 3, jnp.float32))
            q = q + (jax.random.uniform(key, q.shape) - 0.5) * ulp
        return jnp.clip(q, -c, c).astype(jnp.float8_e4m3fn)


register_compressor(Int8Compressor.name, Int8Compressor)
register_compressor(Fp8Compressor.name, Fp8Compressor)

# The quantizing codecs, for seams that must branch on "lossy or not".
QUANTIZERS = (Int8Compressor, Fp8Compressor)


def is_quantizing(comp) -> bool:
    return isinstance(comp, _ScaledQuantizer)


def wire_bits_per_param(comp, length: int, world_size: int = 1) -> float:
    """Achieved wire bits per parameter, counting the chunk-grid pad
    and the per-chunk saturation flags: a pure function of the
    shapes."""
    if not is_quantizing(comp):
        return float(np.dtype(jnp.float32).itemsize * 8)
    mp = comp._padded(int(length)) + comp.n_chunks(int(length))
    item_bits = jnp.dtype(comp.wire).itemsize * 8
    return item_bits * mp / max(int(length), 1)


# -- weight quantization (serving) -------------------------------------------
# Unlike the gradient codecs above (delayed pow2 scales, in-wire
# summation), inference weights are quantized ONCE, offline, with exact
# per-channel amax scales — no EF, no wire-sum overflow budget.


def quantize_per_channel_int8(w, channel_axis: int = -1):
    """Symmetric per-channel int8: ``codes = round(w / s)`` with
    ``s = amax / 127`` per slice along ``channel_axis`` (the output
    channel for a ``[in, out]`` kernel).  Returns ``(codes int8,
    scale f32)`` with ``scale`` shaped to broadcast against ``codes``.
    All-zero channels get scale 1 (codes are all zero anyway)."""
    w = jnp.asarray(w, jnp.float32)
    axes = tuple(a for a in range(w.ndim)
                 if a != channel_axis % w.ndim)
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return codes, scale


def quantize_per_tensor_int8(w):
    """One scale for the whole tensor — the baseline the per-channel
    property test beats (``tests/test_compression.py``)."""
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return codes, scale


def dequantize_int8(codes, scale):
    """Inverse of either weight quantizer (scale broadcasts)."""
    return codes.astype(jnp.float32) * scale


__all__ = ["Fp8Compressor", "Int8Compressor", "QUANTIZERS",
           "dequantize_int8", "is_quantizing",
           "quantize_per_channel_int8", "quantize_per_tensor_int8",
           "wire_bits_per_param"]

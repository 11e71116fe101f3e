"""LFM2-MoE decoder (``model_type: lfm2_moe``, e.g. LiquidAI/LFM2-8B-A1B).

A hybrid decoder: every layer is ``h = x + Op(RMSNorm(x))``, ``out = h +
FFN(RMSNorm(h))``, where ``Op`` is a **gated short convolution**
(:class:`ShortConv`) or **grouped-query attention with per-head QK RMSNorm
and RoPE** (:class:`Attention`) by ``layer_types[i]``, and ``FFN`` is a
dense SwiGLU (:class:`DenseFFN`) in the first ``num_dense_layers`` layers
and a **sparse mixture of experts** (:class:`SparseMoE`: sigmoid scores,
selection by score + ``expert_bias``, top-k renormalised) after them.  No
bias anywhere, RMSNorm throughout, no learned positions, and the output
head is the token embedding (tied).

The model is configured by the published ``config.json`` key names
(:class:`LFM2Config`).  Two of them may give this device's SHARE of a layer
instead of the whole of it: ``num_experts`` is the number of experts HELD
here (ids ``first_expert`` ... ``first_expert + num_experts - 1``) of the
``num_experts_routed`` the router scores, and ``vocab_size`` may be a slice
of the vocabulary.  The expert layer computes its own experts' part
of the result, droplessly (:func:`chainermn_tpu.parallel.expert.dropless_moe`),
and nothing stands in for the experts held elsewhere.

f32 parameters, ``dtype`` compute (bf16 on the chip).  Attention runs
through :func:`chainermn_tpu.ops.flash_attention` (``attention_impl="flash"``,
grouped kv heads read natively) or the unfused math (``"xla"``, the CPU
path); the experts through :func:`chainermn_tpu.ops.grouped_matmul`
(``moe_matmul_impl="pallas"`` on the chip, ``"ragged_dot"`` on the CPU).

Scopes (docs/observability.md): ``chainermn.shortconv``, ``chainermn.rope``,
``chainermn.moe.{route,dispatch,experts,combine}``; flax names
``layer_<n>/conv|attn`` and ``layer_<n>/ffn|moe`` keep a layer's operator
and feed-forward apart.  The Pallas calls sit directly under their flax
module, so the chip's trace names them after it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

LAYER_TYPES = ("conv", "full_attention")


@dataclasses.dataclass(frozen=True)
class LFM2Config:
    """The published ``config.json`` keys the model reads, under their own
    names, and the few this program adds (below the blank line)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    layer_types: Tuple[str, ...]
    num_dense_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    num_experts: int                 # held on this device
    num_experts_per_tok: int
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    rope_theta: float = 1_000_000.0
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True

    num_experts_routed: int = 0      # the router's width; 0: num_experts
    first_expert: int = 0            # id of the first expert held here
    attention_impl: str = "xla"      # flash | xla
    moe_matmul_impl: str = "ragged_dot"   # pallas | ragged_dot
    dtype: Any = jnp.float32         # compute dtype; parameters are float32

    def __post_init__(self):
        unknown = set(self.layer_types) - set(LAYER_TYPES)
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}; known: "
                             f"{LAYER_TYPES}")
        if self.hidden_size % self.num_attention_heads or (
                self.num_attention_heads % self.num_key_value_heads):
            raise ValueError(
                f"{self.num_attention_heads} heads must divide hidden_size "
                f"{self.hidden_size} and be a multiple of the "
                f"{self.num_key_value_heads} kv heads")

    @classmethod
    def from_dict(cls, sizes, **overrides):
        """From a mapping that holds these keys among others (a
        ``config.json``, a benchmark's sizes)."""
        names = {f.name for f in dataclasses.fields(cls)}
        picked = {k: v for k, v in sizes.items() if k in names}
        picked.update(overrides)
        picked["layer_types"] = tuple(picked["layer_types"])
        return cls(**picked)


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * scale``, statistics in float32."""

    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(self.dtype)


def _dense(features, dtype, name):
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32, name=name)


def rope(x, theta: float):
    """Rotary position embedding on [B, T, H, D], positions 0..T-1, pairing
    (i, i + D/2) ("rotate half"), angles in float32."""
    with jax.named_scope("chainermn.rope"):
        seq, dim = x.shape[1], x.shape[-1]
        inv_freq = 1.0 / (theta ** (
            jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
        angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
        cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
        sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
        x32 = x.astype(jnp.float32)
        half = dim // 2
        rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
        return (x32 * cos + rotated * sin).astype(x.dtype)


class ShortConv(nn.Module):
    """Gated short convolution: ``[B, C, z] = split3(W_in u)``; ``v = B * z``;
    ``c_t = sum_j w_j * v_{t-L+1+j}`` (depthwise, causal, zeros left of the
    sequence); ``y = W_out (C * c)``.  No activation, no bias."""

    config: LFM2Config

    @nn.compact
    def __call__(self, u):
        d = u.shape[-1]
        taps, dtype = self.config.conv_L_cache, self.config.dtype
        bcz = _dense(3 * d, dtype, "in_proj")(u)
        kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (taps, d), jnp.float32)
        with jax.named_scope("chainermn.shortconv"):
            gate_b, gate_c, z = (t.astype(jnp.float32)
                                 for t in jnp.split(bcz, 3, axis=-1))
            v = jnp.pad(gate_b * z, ((0, 0), (taps - 1, 0), (0, 0)))
            seq = u.shape[1]
            conv = sum(kernel[j] * v[:, j:j + seq] for j in range(taps))
            gated = (gate_c * conv).astype(dtype)
        return _dense(d, dtype, "out_proj")(gated)


class Attention(nn.Module):
    """Causal grouped-query attention; q and k are RMS-normalized over
    head_dim (one scale each, shared by the heads) and then rotated."""

    config: LFM2Config

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
        d = u.shape[-1]
        head_dim = d // heads
        split = lambda t, n: t.reshape(t.shape[:-1] + (n, head_dim))
        q = split(_dense(heads * head_dim, cfg.dtype, "q_proj")(u), heads)
        k = split(_dense(kv_heads * head_dim, cfg.dtype, "k_proj")(u),
                  kv_heads)
        v = split(_dense(kv_heads * head_dim, cfg.dtype, "v_proj")(u),
                  kv_heads)
        q = RMSNorm(cfg.norm_eps, cfg.dtype, name="q_layernorm")(q)
        k = RMSNorm(cfg.norm_eps, cfg.dtype, name="k_layernorm")(k)
        q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
        if cfg.attention_impl == "flash":
            from chainermn_tpu.ops.flash_attention import flash_attention

            out = flash_attention(q, k, v, causal=True)
        elif cfg.attention_impl == "xla":
            from chainermn_tpu.parallel.sequence import attention

            out = attention(q, jnp.repeat(k, heads // kv_heads, axis=-2),
                            jnp.repeat(v, heads // kv_heads, axis=-2),
                            causal=True)
        else:
            raise ValueError("attention_impl must be flash|xla, got "
                             f"{cfg.attention_impl!r}")
        return _dense(d, cfg.dtype, "out_proj")(out.reshape(u.shape))


class DenseFFN(nn.Module):
    """SwiGLU: ``W2(silu(W1 u) * W3 u)``."""

    config: LFM2Config

    @nn.compact
    def __call__(self, u):
        width, dtype = self.config.intermediate_size, self.config.dtype
        gate = _dense(width, dtype, "w1")(u)
        up = _dense(width, dtype, "w3")(u)
        return _dense(u.shape[-1], dtype, "w2")(nn.silu(gate) * up)


def _swiglu_experts(rows, group_sizes, w1, w3, w2, *, impl,
                    gate_and_up_as_one=False):
    """``W2_e(silu(W1_e u) * W3_e u)`` for every group e of ``rows``."""
    from chainermn_tpu.ops.grouped_matmul import grouped_matmul

    # the grouped products stay directly under the calling module (the trace
    # names their kernels after it); the scope holds what XLA computes
    # between them
    product = lambda a, w: grouped_matmul(a, w, group_sizes, impl)
    if gate_and_up_as_one:
        gate, up = jnp.split(
            product(rows, jnp.concatenate([w1, w3], axis=-1)), 2, axis=-1)
    else:
        gate, up = product(rows, w1), product(rows, w3)
    with jax.named_scope(
            "chainermn.moe.experts"):
        hidden = nn.silu(gate) * up
    return product(hidden, w2)


# one callable an implementation, the SAME object for every layer: the expert
# layer's remainder is traced once for all the layers that pass it
_EXPERTS = {impl: functools.partial(_swiglu_experts, impl=impl)
            for impl in ("pallas", "ragged_dot")}
# ... and for that guarded remainder XLA's own grouped product, ``W1`` and
# ``W3`` as one.  Every program carries the remainder, traced, differentiated
# and compiled, and almost no step runs it: what counts there is what it adds
# to set-up, and that goes by the number of kernels (PERF.md, PR 27)
_REMAINDER_EXPERTS = functools.partial(
    _swiglu_experts, impl="ragged_dot", gate_and_up_as_one=True)


class SparseMoE(nn.Module):
    """The held experts' part of the mixture: ``sum over the chosen experts
    held here of w_e * W2_e(silu(W1_e u) * W3_e u)``.  Parameters: the
    router over ALL ``num_experts_routed`` (``gate``, float32 product and
    scores), ``expert_bias`` over all of them (selection only, no gradient)
    and the ``[num_experts, ...]`` stacks of the experts held here, nothing
    of the others.  Returns ``(y, counters)``."""

    config: LFM2Config

    @nn.compact
    def __call__(self, u):
        from chainermn_tpu.parallel.expert import dropless_moe

        cfg = self.config
        d, width, held = u.shape[-1], cfg.moe_intermediate_size, \
            cfg.num_experts
        routed = cfg.num_experts_routed or held
        flat = u.reshape(-1, d)
        # the router's product in float32 at full precision: a score that
        # differs in the third digit chooses another expert
        logits = nn.Dense(routed, use_bias=False,
                          dtype=jnp.float32, param_dtype=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST,
                          name="gate")(flat.astype(jnp.float32))
        bias = self.param(
            "expert_bias", nn.initializers.zeros_init(),
            (routed,), jnp.float32) if cfg.use_expert_bias else None
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        w1 = self.param("w1", init, (held, d, width), jnp.float32)
        w3 = self.param("w3", init, (held, d, width), jnp.float32)
        w2 = self.param("w2", init, (held, width, d), jnp.float32)

        # cast once, out here: the remainder's gradient for a weight cast
        # inside it would be a float32 stack of zeros a step
        stacks = tuple(w.astype(cfg.dtype) for w in (w1, w3, w2))
        y, counters = dropless_moe(
            flat, logits, bias, _EXPERTS[cfg.moe_matmul_impl],
            expert_args=stacks,
            num_experts=routed, top_k=cfg.num_experts_per_tok,
            first_expert=cfg.first_expert, held_experts=held,
            normalize=cfg.norm_topk_prob,
            scaling_factor=cfg.routed_scaling_factor,
            # rows past the layer's bound, on the rare step that has any
            remainder_fn=_REMAINDER_EXPERTS)
        return y.reshape(u.shape), counters


class DecoderLayer(nn.Module):
    """Layer ``index``: its operator (``conv`` or ``attn``) and its
    feed-forward (``ffn`` or ``moe``), each behind its RMSNorm and added to
    the residual.  Returns ``(x, counters or None)``."""

    config: LFM2Config
    index: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)
        if cfg.layer_types[self.index] == "conv":
            operator = ShortConv(cfg, name="conv")
        else:
            operator = Attention(cfg, name="attn")
        x = x + operator(norm("operator_norm")(x))
        h = norm("ffn_norm")(x)
        if self.index < cfg.num_dense_layers:
            return x + DenseFFN(cfg, name="ffn")(h), None
        y, counters = SparseMoE(cfg, name="moe")(h)
        return x + y, counters


class LFM2MoE(nn.Module):
    """``apply(params, tokens[B, T]) -> logits[B, T, vocab_size]`` (float32);
    with ``with_counters=True`` also ``{"layer_<n>": counters}`` of every
    MoE layer (:func:`chainermn_tpu.parallel.expert.dropless_counters`), for
    ``make_train_step(has_aux=True)``."""

    config: LFM2Config

    @nn.compact
    def __call__(self, tokens, with_counters: bool = False):
        cfg = self.config
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, dtype=cfg.dtype,
                         name="embed_tokens")
        x = embed(tokens)
        counters = {}
        for index in range(len(cfg.layer_types)):
            x, counted = DecoderLayer(cfg, index, name=f"layer_{index}")(x)
            if counted is not None:
                counters[f"layer_{index}"] = counted
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="embedding_norm")(x)
        logits = embed.attend(x).astype(jnp.float32)
        return (logits, counters) if with_counters else logits


__all__ = ["Attention", "DecoderLayer", "DenseFFN", "LFM2Config", "LFM2MoE",
           "RMSNorm", "ShortConv", "SparseMoE", "rope"]

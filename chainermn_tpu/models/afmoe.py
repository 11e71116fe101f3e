"""AFMoE decoder (``model_type: afmoe``, e.g. arcee-ai/Trinity-Mini).

Every layer is a sandwich of four RMSNorms around two residual branches::

    x = x + post_attention_layernorm(Attn(input_layernorm(x)))
    x = x + post_mlp_layernorm(FFN(pre_mlp_layernorm(x)))

``Attn`` is grouped-query attention with per-head QK RMSNorm and a **sigmoid
output gate** (``o * sigmoid(W_g a)`` before the output projection), of two
kinds by ``layer_types[i]``: ``sliding_attention`` rotates q and k (RoPE)
and lets token i see ``sliding_window`` tokens back (``0 <= i - j <
sliding_window``); ``full_attention`` sees every earlier token and carries
**no positional encoding** (NoPE).  ``FFN`` is a dense SwiGLU in the first
``num_dense_layers`` layers and after them a sparse mixture of experts with
a **shared expert** beside it: sigmoid scores over all experts, selection
by score + ``expert_bias``, the chosen scores renormalised (``route_norm``)
and scaled by ``route_scale``.  The embedding is scaled by
``sqrt(hidden_size)`` (``mup_enabled``); the head is untied.  No bias
anywhere.

The model is configured by the published ``config.json`` key names
(:class:`AfmoeConfig`).  As in :mod:`chainermn_tpu.models.lfm2`,
``num_experts`` may be the number of experts HELD here (ids
``first_expert`` ...) of the ``num_experts_routed`` the router scores, and
``vocab_size`` a slice of the vocabulary: the expert layer computes its own
experts' part of the result plus the shared expert, and nothing stands in
for the experts held elsewhere.

What the mathematics shares with LFM2-MoE is that model's code:
:class:`~chainermn_tpu.models.lfm2.RMSNorm`, :func:`~chainermn_tpu.models.
lfm2.qk_norm_and_rope`, :class:`~chainermn_tpu.models.lfm2.DenseFFN` and
:class:`~chainermn_tpu.models.lfm2.SparseMoE` (so
:func:`chainermn_tpu.parallel.expert.dropless_moe` and the grouped-matmul
kernels), and :func:`chainermn_tpu.ops.flash_attention` with its ``window``.

Scopes (docs/observability.md): the attention modules are named ``swa``
(window layers) and ``nope`` (full layers), so the chip's trace names their
flash kernels ``swa.<k>`` and ``nope.<k>``; ``chainermn.attn_gate`` holds
the sigmoid gate, ``chainermn.rope`` the rotation (with the kernels on,
QK-norm and rotation as one kernel, ``chainermn.rope.<k>``, on the full
layers the norm alone), ``chainermn.moe.{dispatch,experts,combine,shared}`` the expert layer's parts, and
``chainermn.moe.afmoe_route`` the routing (its own name: the benchmark's
``moe_route_ms`` reads ``chainermn.moe.route`` wherever it runs, and an
accepted test lets only a cell with short convolutions report it).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from chainermn_tpu.models.lfm2 import (DenseFFN, RMSNorm, SparseMoE, _dense,
                                       causal_attention, config_from_dict,
                                       qk_norm_and_rope)

LAYER_TYPES = ("sliding_attention", "full_attention")


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
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
    head_dim: int
    num_experts: int                 # held on this device
    num_experts_per_tok: int
    sliding_window: int
    num_shared_experts: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10_000.0
    route_norm: bool = True
    route_scale: float = 1.0
    mup_enabled: bool = False
    score_func: str = "sigmoid"
    tie_word_embeddings: bool = False

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
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} heads must be a multiple of "
                f"the {self.num_key_value_heads} kv heads")
        if self.score_func != "sigmoid" or self.tie_word_embeddings:
            raise ValueError(
                "this model scores experts by sigmoid and has an untied "
                f"head; got score_func={self.score_func!r}, "
                f"tie_word_embeddings={self.tie_word_embeddings}")

    @classmethod
    def from_dict(cls, sizes, **overrides):
        """From a mapping that holds these keys among others (a
        ``config.json``, a benchmark's sizes)."""
        return config_from_dict(cls, sizes, overrides)

    # what lfm2's shared modules read, under that family's names
    moe_route_scope = "chainermn.moe.afmoe_route"
    moe_shared_scope = "chainermn.moe.shared"
    use_expert_bias = True
    norm_topk_eps = 1e-20

    @property
    def norm_topk_prob(self):
        return self.route_norm

    @property
    def routed_scaling_factor(self):
        return self.route_scale


class GatedAttention(nn.Module):
    """Causal grouped-query attention with QK RMSNorm over ``head_dim`` (one
    scale each, shared by the heads) and a sigmoid gate on its output.  A
    ``sliding`` layer rotates q and k and sees ``sliding_window`` tokens; a
    full layer does neither."""

    config: AfmoeConfig
    sliding: bool

    @nn.compact
    def __call__(self, a):
        cfg = self.config
        heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
        head_dim = cfg.head_dim
        split = lambda t, n: t.reshape(t.shape[:-1] + (n, head_dim))
        q = split(_dense(heads * head_dim, cfg.dtype, "q_proj")(a), heads)
        k = split(_dense(kv_heads * head_dim, cfg.dtype, "k_proj")(a),
                  kv_heads)
        v = split(_dense(kv_heads * head_dim, cfg.dtype, "v_proj")(a),
                  kv_heads)
        gate = _dense(heads * head_dim, cfg.dtype, "gate_proj")(a)
        q, k = qk_norm_and_rope(
            q, k, ("q_norm", "k_norm"), cfg.rms_norm_eps, cfg.dtype,
            cfg.attention_impl, cfg.rope_theta if self.sliding else None)
        window = cfg.sliding_window if self.sliding else None
        out = causal_attention(q, k, v, cfg.attention_impl, window)
        with jax.named_scope("chainermn.attn_gate"):
            gated = out.reshape(gate.shape) * nn.sigmoid(gate)
        return _dense(a.shape[-1], cfg.dtype, "o_proj")(gated)


class DecoderLayer(nn.Module):
    """Layer ``index``: attention (module ``swa`` or ``nope``) and
    feed-forward (``ffn`` or ``moe``), each between its two RMSNorms.
    Returns ``(x, counters or None)``."""

    config: AfmoeConfig
    index: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        sliding = cfg.layer_types[self.index] == "sliding_attention"
        attention = GatedAttention(cfg, sliding,
                                   name="swa" if sliding else "nope")
        x = x + norm("post_attention_layernorm")(
            attention(norm("input_layernorm")(x)))
        m = norm("pre_mlp_layernorm")(x)
        if self.index < cfg.num_dense_layers:
            f, counters = DenseFFN(cfg, name="ffn")(m), None
        else:
            f, counters = SparseMoE(cfg, name="moe")(m)
        return x + norm("post_mlp_layernorm")(f), counters


class AfmoeMoE(nn.Module):
    """``apply(params, tokens[B, T]) -> logits[B, T, vocab_size]`` (float32);
    with ``with_counters=True`` also ``{"layer_<n>": counters}`` of every
    MoE layer (:func:`chainermn_tpu.parallel.expert.dropless_counters`), for
    ``make_train_step(has_aux=True)``."""

    config: AfmoeConfig

    @nn.compact
    def __call__(self, tokens, with_counters: bool = False):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                     param_dtype=jnp.float32, dtype=cfg.dtype,
                     name="embed_tokens")(tokens)
        if cfg.mup_enabled:
            x = x * jnp.asarray(math.sqrt(cfg.hidden_size), x.dtype)
        counters = {}
        for index in range(len(cfg.layer_types)):
            x, counted = DecoderLayer(cfg, index, name=f"layer_{index}")(x)
            if counted is not None:
                counters[f"layer_{index}"] = counted
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        logits = _dense(cfg.vocab_size, cfg.dtype, "lm_head")(x).astype(
            jnp.float32)
        return (logits, counters) if with_counters else logits


__all__ = ["AfmoeConfig", "AfmoeMoE", "DecoderLayer", "GatedAttention"]

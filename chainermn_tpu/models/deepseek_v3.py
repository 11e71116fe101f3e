"""DeepSeek-V3-shaped decoder (``model_type: deepseek_v3``, e.g. the text
decoder of moonshotai/Kimi-VL-A3B-Instruct).

A plain pre-norm layer, two RMSNorms::

    x = x + MLA(input_layernorm(x))
    x = x + FFN(post_attention_layernorm(x))

``MLA`` is **multi-head latent attention**: keys and values are not
projected from the hidden state but from a low-rank latent of it::

    q            = h W_q                      [T, heads, nope + rope]
    c | k_pe     = h W_kva                    [T, kv_lora_rank | rope]
    k_nope | v   = RMSNorm(c) W_kvb           [T, heads, nope | v_head_dim]
    q_pe, k_pe   rotated (positions from 0); k_pe is ONE head, shared by all
    s            = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)
    o            = causal softmax(s) v        [T, heads, v_head_dim]

so the scores run over ``qk_nope_head_dim + qk_rope_head_dim`` (192) wide
keys and the sum over ``v_head_dim`` (128) wide values: the flash kernels
take a value head size of their own
(:func:`chainermn_tpu.ops.flash_attention`).  No QK-norm, no gate, no bias;
``q_lora_rank`` null: q comes straight from the hidden state (a low-rank
QUERY projection is not built).  ``FFN`` is a dense SwiGLU in the first
``first_k_dense_replace`` layers and after them a sparse mixture of experts
with ``n_shared_experts`` **shared experts** beside it (one SwiGLU
``n_shared_experts * moe_intermediate_size`` wide, added unweighted):
sigmoid scores over all routed experts, the top ``num_experts_per_tok`` of
score + ``e_score_correction_bias`` (``topk_method: noaux_tc``; one group:
no group limit), the chosen scores over their sum (``norm_topk_prob``) times
``routed_scaling_factor``.  An untied head, no embedding scale.  The vision
tower of a multimodal checkpoint is not built: this is the decoder on
tokens.

The model is configured by the published ``config.json`` key names
(:class:`DeepseekV3Config`).  As in :mod:`chainermn_tpu.models.lfm2`, the
count of experts (``n_routed_experts``) may be the number HELD here (ids
``first_expert`` ...) of the ``num_experts_routed`` the router scores, and
``vocab_size`` a slice of the vocabulary: the expert layer computes its own
experts' part of the result plus the shared experts, and nothing stands in
for the experts held elsewhere.

What the mathematics shares with LFM2-MoE and AFMoE is their code:
:class:`~chainermn_tpu.models.lfm2.RMSNorm`, :func:`~chainermn_tpu.models.
lfm2.rope`, :class:`~chainermn_tpu.models.lfm2.DenseFFN`,
:func:`~chainermn_tpu.models.lfm2.causal_attention` and
:class:`~chainermn_tpu.models.lfm2.SparseMoE` (AFMoE's router mathematics:
so :func:`chainermn_tpu.parallel.expert.dropless_moe` and the grouped-matmul
kernels).

Scopes (docs/observability.md): the attention module is named ``mla`` and
NO scope is opened between it and the ``pallas_call``, so the chip's trace
names its flash kernels ``mla.<k>``; ``chainermn.rope`` holds the rotation
of the 64-wide parts, ``chainermn.mla_key`` what builds the 192-wide keys
from the latent's ``k_nope`` and the shared ``k_pe``;
``chainermn.moe.{dispatch,experts,combine}`` the expert layer's parts,
``chainermn.moe.afmoe_route`` the routing (AFMoE's mathematics under
AFMoE's name) and ``chainermn.moe.shared_experts`` the shared experts (its
own name: the benchmark's ``moe_shared_ms`` reads ``chainermn.moe.shared``
wherever it runs, and an accepted test lets only AFMoE's cell report it).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from chainermn_tpu.models.lfm2 import (DenseFFN, RMSNorm, SparseMoE, _dense,
                                       causal_attention, config_from_dict,
                                       rope)

MLP_LAYER_TYPES = ("dense", "sparse")


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """The published ``config.json`` keys the model reads, under their own
    names, and the few this program adds (below the blank line)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int            # held on this device
    n_shared_experts: int
    num_experts_per_tok: int
    first_k_dense_replace: int = 1
    q_lora_rank: Optional[int] = None
    num_key_value_heads: Optional[int] = None
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10_000.0
    rope_scaling: Optional[Any] = None
    attention_bias: bool = False
    tie_word_embeddings: bool = False

    mlp_layer_types: Tuple[str, ...] = ()   # the layers built, one entry each
    num_experts_routed: int = 0      # the router's width; 0: n_routed_experts
    first_expert: int = 0            # id of the first expert held here
    attention_impl: str = "xla"      # flash | xla
    moe_matmul_impl: str = "ragged_dot"   # pallas | ragged_dot
    dtype: Any = jnp.float32         # compute dtype; parameters are float32

    def __post_init__(self):
        kinds = tuple(self.mlp_layer_types)
        object.__setattr__(self, "mlp_layer_types", kinds)
        dense = self.first_k_dense_replace
        if not kinds or kinds != (("dense",) * dense
                                  + ("sparse",) * (len(kinds) - dense)):
            raise ValueError(
                "mlp_layer_types names the layers built, one entry each: "
                f"the first first_k_dense_replace = {dense} dense, every "
                f"other sparse (of {MLP_LAYER_TYPES}); got {kinds}")
        if self.q_lora_rank is not None or self.rope_scaling is not None:
            raise ValueError(
                "a low-rank query projection and a scaled rotation are not "
                f"built; got q_lora_rank={self.q_lora_rank}, "
                f"rope_scaling={self.rope_scaling}")
        if self.num_key_value_heads not in (None, self.num_attention_heads):
            raise ValueError(
                "latent attention gives every head its own k_nope and v: "
                f"num_key_value_heads {self.num_key_value_heads} must be the "
                f"{self.num_attention_heads} attention heads")
        if (self.scoring_func, self.topk_method, self.n_group,
                self.topk_group) != ("sigmoid", "noaux_tc", 1, 1):
            raise ValueError(
                "this model scores experts by sigmoid and selects by score "
                "+ bias in one group; got "
                f"scoring_func={self.scoring_func!r}, "
                f"topk_method={self.topk_method!r}, n_group={self.n_group}, "
                f"topk_group={self.topk_group}")
        if self.attention_bias or self.tie_word_embeddings:
            raise ValueError(
                "this model has no bias and an untied head; got "
                f"attention_bias={self.attention_bias}, "
                f"tie_word_embeddings={self.tie_word_embeddings}")

    @classmethod
    def from_dict(cls, sizes, **overrides):
        """From a mapping that holds these keys among others (a
        ``config.json``, a benchmark's sizes)."""
        return config_from_dict(cls, sizes, overrides)

    # what lfm2's shared modules read, under that family's names: AFMoE's
    # routing (sigmoid scores, selection by score + bias, the chosen over
    # their sum + 1e-20, a scale), the shared experts under a scope of this
    # family's own
    moe_route_scope = "chainermn.moe.afmoe_route"
    moe_shared_scope = "chainermn.moe.shared_experts"
    score_func = "sigmoid"
    use_expert_bias = True
    norm_topk_eps = 1e-20

    @property
    def num_experts(self):
        return self.n_routed_experts

    @property
    def num_shared_experts(self):
        return self.n_shared_experts


class MLA(nn.Module):
    """Causal multi-head latent attention (the module's text has the
    equations): ``q_proj``, ``kv_a_proj_with_mqa``, ``kv_a_layernorm``,
    ``kv_b_proj``, ``o_proj``.  The rotated 64-wide part reaches the scores
    as the public modelling code has it: q and k are concatenated to
    ``nope + rope`` wide heads, the one ``k_pe`` head repeated for every
    head, and the kernels score 192-wide keys beside 128-wide values."""

    config: DeepseekV3Config

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, pe, value = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim)
        per_head = lambda t, width: t.reshape(t.shape[:-1] + (heads, width))
        q = per_head(_dense(heads * (nope + pe), cfg.dtype, "q_proj")(h),
                     nope + pe)
        latent = _dense(rank + pe, cfg.dtype, "kv_a_proj_with_mqa")(h)
        c = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="kv_a_layernorm")(
            latent[..., :rank])
        kv = per_head(
            _dense(heads * (nope + value), cfg.dtype, "kv_b_proj")(c),
            nope + value)
        q_pe = rope(q[..., nope:], cfg.rope_theta)
        k_pe = rope(latent[..., None, rank:], cfg.rope_theta)
        with jax.named_scope("chainermn.mla_key"):
            q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_pe, k_pe.shape[:2] + (heads, pe))],
                axis=-1)
        out = causal_attention(q, k, kv[..., nope:], cfg.attention_impl)
        return _dense(h.shape[-1], cfg.dtype, "o_proj")(
            out.reshape(h.shape[:-1] + (heads * value,)))


class DecoderLayer(nn.Module):
    """Layer ``index``: latent attention (module ``mla``) and its
    feed-forward (``ffn`` in a dense layer, ``moe`` after), each behind its
    RMSNorm and added to the residual.  Returns ``(x, counters or None)``."""

    config: DeepseekV3Config
    index: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + MLA(cfg, name="mla")(norm("input_layernorm")(x))
        m = norm("post_attention_layernorm")(x)
        if cfg.mlp_layer_types[self.index] == "dense":
            return x + DenseFFN(cfg, name="ffn")(m), None
        y, counters = SparseMoE(cfg, name="moe")(m)
        return x + y, counters


class DeepseekV3(nn.Module):
    """``apply(params, tokens[B, T]) -> logits[B, T, vocab_size]`` (float32);
    with ``with_counters=True`` also ``{"layer_<n>": counters}`` of every
    MoE layer (:func:`chainermn_tpu.parallel.expert.dropless_counters`), for
    ``make_train_step(has_aux=True)``."""

    config: DeepseekV3Config

    @nn.compact
    def __call__(self, tokens, with_counters: bool = False):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                     param_dtype=jnp.float32, dtype=cfg.dtype,
                     name="embed_tokens")(tokens)
        counters = {}
        for index in range(len(cfg.mlp_layer_types)):
            x, counted = DecoderLayer(cfg, index, name=f"layer_{index}")(x)
            if counted is not None:
                counters[f"layer_{index}"] = counted
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        logits = _dense(cfg.vocab_size, cfg.dtype, "lm_head")(x).astype(
            jnp.float32)
        return (logits, counters) if with_counters else logits


__all__ = ["DecoderLayer", "DeepseekV3", "DeepseekV3Config", "MLA"]

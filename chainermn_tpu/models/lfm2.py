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

What the backward pass makes again (``LFM2MoE``'s own layer; the modules
other families import are as they were): the layer puts the XLA-only
stretches between its matrix products under ``jax.checkpoint`` with a policy
that keeps every product's output (:func:`_between_products`): the norms,
the gated short convolution, the dense layer's ``silu(gate) * up`` and the
plain QK-norm and rotation are a pass over memory each to make again, and
keeping them cost the room in which XLA's own rematerialisation, which drops
the LARGEST buffer, ran ``in_proj`` and the dense ``w1`` / ``w3`` a second
time every step (three rows of 8,192 tokens on one chip: PERF.md, PR 47).
Products and Pallas kernels run once.  On the chip's trace the recomputation
lies under ``rematted_computation`` in the backward pass and reads as
``backward_ms``; without a gradient ``jax.checkpoint`` is the identity.

Scopes (docs/observability.md): ``chainermn.shortconv``, ``chainermn.rope``,
``chainermn.moe.{route,dispatch,experts,combine,shared}``; flax names
``layer_<n>/conv|attn`` and ``layer_<n>/ffn|moe`` keep a layer's operator
and feed-forward apart.  The Pallas calls sit directly under their flax
module, so the chip's trace names them after it; the one that normalises
and rotates q and k (:func:`qk_norm_and_rope`, heads of 128) is called under
``chainermn.rope`` and named after that.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

LAYER_TYPES = ("conv", "full_attention")


def config_from_dict(cls, sizes, overrides):
    """A config dataclass from the keys of ``sizes`` that are its fields."""
    names = {f.name for f in dataclasses.fields(cls)}
    picked = {k: v for k, v in sizes.items() if k in names}
    picked.update(overrides)
    if "layer_types" in picked:     # a flax module's attribute must hash
        picked["layer_types"] = tuple(picked["layer_types"])
    return cls(**picked)


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
    num_shared_experts: int = 0      # experts every token visits (none here)
    norm_topk_eps: float = 1e-6      # added to the chosen scores' sum
    attention_impl: str = "xla"      # flash | xla
    moe_matmul_impl: str = "ragged_dot"   # pallas | ragged_dot
    dtype: Any = jnp.float32         # compute dtype; parameters are float32

    # the scope SparseMoE routes under, the one its shared experts run
    # under and the scores it routes by (no fields: a model's, not a file's)
    moe_route_scope = "chainermn.moe.route"
    moe_shared_scope = "chainermn.moe.shared"
    score_func = "sigmoid"

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
        return config_from_dict(cls, sizes, overrides)


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


def yarn_inv_freq(dim: int, theta: float, scaling):
    """YaRN's blended rotary frequencies [dim // 2], float32, as the public
    Transformers ``rope_utils`` computes them: pair i turns ``r_i =
    original_max_position_embeddings * theta^(-2i/dim) / (2 pi)`` times over
    the original context; pairs that turn at least ``beta_fast`` times keep
    their frequency, pairs that turn at most ``beta_slow`` times have it
    divided by ``factor``, a linear ramp over the pair index between
    (``low = floor``, ``high = ceil`` of the two crossings, ``truncate``)."""
    def crossing(rotations):
        return (dim * math.log(scaling["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low, high = crossing(scaling["beta_fast"]), crossing(scaling["beta_slow"])
    if scaling.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / scaling["factor"] * ramp


def rotary_tables(seq: int, dim: int, theta: float, scaling=None,
                  positions=None):
    """``(cos, sin)`` of positions 0..seq-1 as :func:`rope` multiplies by
    them, each [1, seq, 1, dim] float32 with the angles ``t * inv_freq`` in
    both halves of ``dim``; under ``yarn`` :func:`yarn_inv_freq`'s
    frequencies, both times ``attention_factor`` (shape and order of
    operations are ``rope``'s of old: ``tests/test_mellum.py`` holds its
    jaxpr).  ``positions`` ([seq] integers, the same for every row of the
    batch) are the position ids where they are not 0..seq-1: positions that
    restart, as a sequence's two copies under block diffusion do."""
    kind = "default" if scaling is None else scaling.get(
        "rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(f"rope_type must be default|yarn, got {kind!r}")
    if kind == "yarn":
        inv_freq = yarn_inv_freq(dim, theta, scaling)
    else:
        inv_freq = 1.0 / (theta ** (
            jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if positions is None:
        at = jnp.arange(seq, dtype=jnp.float32)
    else:
        at = jnp.asarray(positions).astype(jnp.float32)
        if at.shape != (seq,):
            raise ValueError(f"positions must be [{seq}], got {at.shape}")
    angles = at[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    if kind == "yarn":
        factor = scaling.get("attention_factor") or (
            0.1 * math.log(scaling["factor"]) + 1.0)
        cos, sin = cos * jnp.float32(factor), sin * jnp.float32(factor)
    return cos, sin


def rope(x, theta: float, scaling=None, positions=None):
    """Rotary position embedding on [B, T, H, D], positions 0..T-1 (or the
    ``positions`` [T] given, the same for every row of the batch), pairing
    (i, i + D/2) ("rotate half"), angles in float32.  ``scaling`` is a
    layer's published ``rope_parameters`` / ``rope_scaling`` mapping: None
    or ``rope_type`` ``default`` rotates by ``theta^(-2i/D)``; ``yarn``
    rotates by :func:`yarn_inv_freq` and multiplies cos and sin by
    ``attention_factor`` (q and k alike: the scores scale by its square)."""
    with jax.named_scope("chainermn.rope"):
        dim = x.shape[-1]
        cos, sin = rotary_tables(x.shape[1], dim, theta, scaling, positions)
        x32 = x.astype(jnp.float32)
        half = dim // 2
        rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
        return (x32 * cos + rotated * sin).astype(x.dtype)


class _NormScale(nn.Module):
    """The parameter of the :class:`RMSNorm` of the same name, alone: what
    the fused kernel normalises by (the tree keeps ``<name>/scale``)."""

    @nn.compact
    def __call__(self, dim):
        return self.param("scale", nn.initializers.ones_init(), (dim,),
                          jnp.float32)


def qk_norm_and_rope(q, k, names, eps, dtype, impl: str,
                     theta: Optional[float] = None, scaling=None,
                     positions=None):
    """What an attention layer does to q [B, T, H, D] and k [B, T, G, D]
    before the scores: RMS-normalise each over D by the scale of its norm
    module (``names``: the two modules' names, one scale each, shared by the
    heads), then rotate both as :func:`rope` does (``theta`` None: a layer
    that does not rotate; ``positions``: its position ids).  Call it from the attention module's
    ``__call__``: the norm modules become its children.

    Which form runs is read off what is at hand: where the Pallas kernels
    are on (``impl == "flash"``) and D is a multiple of the chip's 128
    lanes, norm and rotation are ONE pass forward and one backward
    (:func:`chainermn_tpu.ops.qk_norm_rope.qk_norm_rope`, a kernel for q and
    one for k under ``chainermn.rope``, whose name the benchmark reads as
    ``norm_rope_ms``: docs/observability.md); elsewhere (the CPU, the
    ``xla`` references, heads of 64) the plain float32 modules, which are
    that kernel's oracle."""
    if impl == "flash" and q.shape[-1] % 128 == 0:
        from chainermn_tpu.ops.qk_norm_rope import qk_norm_rope

        seq, dim = q.shape[1], q.shape[-1]
        scales = [_NormScale(name=name)(dim) for name in names]
        with jax.named_scope("chainermn.rope"):
            rotary = None if theta is None else [
                table.reshape(seq, dim)
                for table in rotary_tables(seq, dim, theta, scaling,
                                           positions)]
            return [qk_norm_rope(x, scale, rotary, eps=eps, dtype=dtype)
                    for x, scale in zip((q, k), scales)]
    q, k = (RMSNorm(eps, dtype, name=name)(x)
            for x, name in zip((q, k), names))
    if theta is None:
        return q, k
    return (rope(q, theta, scaling, positions),
            rope(k, theta, scaling, positions))


def causal_attention(q, k, v, impl: str, window: Optional[int] = None):
    """Causal softmax attention of q [B, T, H, D] over k, v [B, T, G, D]
    (each kv head serves H // G query heads), a row seeing ``window`` keys
    back if given: the fused kernels (``impl="flash"``) or the unfused math
    (``"xla"``).  No scope is opened: the chip's trace names the kernels
    after the calling module."""
    if impl == "flash":
        from chainermn_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True, window=window)
    if impl == "xla":
        from chainermn_tpu.parallel.sequence import attention

        group = q.shape[-2] // k.shape[-2]
        return attention(q, jnp.repeat(k, group, axis=-2),
                         jnp.repeat(v, group, axis=-2), causal=True,
                         window=window)
    raise ValueError(f"attention_impl must be flash|xla, got {impl!r}")


def _between_products(fn):
    """``fn(module, *args)`` under flax's lifted :func:`jax.checkpoint` with
    a policy that KEEPS every matrix product's output: differentiated, the
    backward pass makes again what lies between the products (a norm, a
    gate, an activation: a pass over memory each) in place of keeping it,
    and runs no product twice.  The parameters keep their paths; without a
    gradient it is ``fn``.  Put no Pallas call inside: a kernel takes the
    name of the scope around it."""
    return nn.remat(fn, policy=jax.checkpoint_policies.dots_saveable)


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
    head_dim (one scale each, shared by the heads) and then rotated.
    ``norm`` is the layer's RMSNorm in front of it (the layer's own module,
    handed in and applied here): what lies in front of the kernels, that
    norm, the three products and the plain QK-norm and rotation, is ONE
    stretch under :func:`_between_products`."""

    config: LFM2Config

    @nn.compact
    def __call__(self, u, norm=None):
        cfg = self.config
        heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
        d = u.shape[-1]
        head_dim = d // heads
        split = lambda t, n: t.reshape(t.shape[:-1] + (n, head_dim))
        normed_and_rotated = lambda q, k: qk_norm_and_rope(
            q, k, ("q_layernorm", "k_layernorm"), cfg.norm_eps, cfg.dtype,
            cfg.attention_impl, cfg.rope_theta)
        # heads of 128 under the kernels: norm and rotation are a Pallas
        # kernel (qk_norm_and_rope's condition), and a kernel stays outside
        kernel = cfg.attention_impl == "flash" and head_dim % 128 == 0

        def in_front(attn, norm, u):
            u = u if norm is None else norm(u)
            q, k, v = (split(_dense(n * head_dim, cfg.dtype, name)(u), n)
                       for name, n in (("q_proj", heads), ("k_proj", kv_heads),
                                       ("v_proj", kv_heads)))
            return (q, k, v) if kernel else (*normed_and_rotated(q, k), v)

        q, k, v = _between_products(in_front)(self, norm, u)
        if kernel:
            q, k = normed_and_rotated(q, k)
        out = causal_attention(q, k, v, cfg.attention_impl)
        return _dense(d, cfg.dtype, "out_proj")(out.reshape(u.shape))


class DenseFFN(nn.Module):
    """SwiGLU: ``W2(silu(W1 u) * W3 u)``, ``width`` wide
    (``config.intermediate_size`` unless given)."""

    config: Any                      # reads intermediate_size, dtype
    width: Optional[int] = None

    @nn.compact
    def __call__(self, u):
        width, dtype = (self.width or self.config.intermediate_size,
                        self.config.dtype)
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
    scores, ``config.score_func``), ``expert_bias`` over all of them where
    the config has ``use_expert_bias`` (selection only, no gradient) and the
    ``[num_experts, ...]`` stacks of the experts held here, nothing of the
    others.  With ``num_shared_experts`` it adds ``shared(u)``, one
    SwiGLU ``num_shared_experts * moe_intermediate_size`` wide that every
    token visits and every device of a share computes alike (added up over
    the shares it counts ONCE), under the scope ``config.moe_shared_scope``
    (a family's own name where a benchmark reader of another family's holds
    ``chainermn.moe.shared``: docs/observability.md).  Returns ``(y,
    counters)``.

    ``config`` is an :class:`LFM2Config` or any object with its expert-layer
    attributes (``models/afmoe.py``'s, ``models/mellum.py``'s)."""

    config: Any

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
            normalize_eps=cfg.norm_topk_eps,
            score_func=cfg.score_func, route_scope=cfg.moe_route_scope,
            # rows past the layer's bound, on the rare step that has any
            remainder_fn=_REMAINDER_EXPERTS)
        y = y.reshape(u.shape)
        if cfg.num_shared_experts:
            with jax.named_scope(
                    cfg.moe_shared_scope):
                y = y + DenseFFN(
                    cfg, width * cfg.num_shared_experts, name="shared")(u)
        return y, counters


class DecoderLayer(nn.Module):
    """Layer ``index``: its operator (``conv`` or ``attn``) and its
    feed-forward (``ffn`` or ``moe``), each behind its RMSNorm and added to
    the residual.  Returns ``(x, counters or None)``.

    Each half that is XLA's alone runs under :func:`_between_products` WITH
    its norm, so that the residual stream and the products' outputs are what
    the backward pass keeps: the convolution operator whole, the dense
    feed-forward whole (its class is other families' too, so the call is
    wrapped here), the attention operator up to its kernels
    (:class:`Attention`).  The expert layer holds Pallas calls and stays
    outside; its norm alone is wrapped, which keeps the residual stream in
    front of it from being a value XLA drops and makes again by running the
    operator's ``out_proj``."""

    config: LFM2Config
    index: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)

        def operator(layer, x):
            return ShortConv(cfg, name="conv")(norm("operator_norm")(x))

        def feed_forward(layer, x):
            return DenseFFN(cfg, name="ffn")(norm("ffn_norm")(x))

        def moe_input(layer, x):
            return norm("ffn_norm")(x)

        if cfg.layer_types[self.index] == "conv":
            x = x + _between_products(operator)(self, x)
        else:
            # its kernels stay outside: the module wraps its own products
            x = x + Attention(cfg, name="attn")(x, norm("operator_norm"))
        if self.index < cfg.num_dense_layers:
            return x + _between_products(feed_forward)(self, x), None
        y, counters = SparseMoE(cfg, name="moe")(
            _between_products(moe_input)(self, x))
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

"""Plain reference of family ``lfm2_moe``: the LFM2-MoE decoder (RMSNorm,
gated short convolutions, grouped-query attention with QK RMSNorm and RoPE,
a dense SwiGLU layer, then sparse experts chosen by sigmoid score +
``expert_bias``) in straightforward float32 ``jax.numpy``: no kernels, no
flax, nothing of the program.  The layer equations are the configuration
file's (``assumed`` lists what the published ``config.json`` does not say).

It is given the same share as the program: experts ``first_expert`` ...
``first_expert + num_experts - 1`` of ``num_experts_published`` and the
first ``vocab_size`` rows of the vocabulary.  The router scores ALL experts
and picks ``num_experts_per_tok`` of them; what the experts not held would
add is left out.  The experts are applied the plain way: every held expert
to every token, weighted by that token's weight for it (zero where it was
not chosen) — no sorting, no grouping, so a fault in the program's
dispatch cannot be shared.

It reads the benchmark's seeded weight tree by name.  To fit a float32
backward pass at T=8192 beside nothing else on one chip, each layer and
each expert is rematerialized and attention is computed in blocks of query
rows against the whole context."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.references import common

# rows of queries whose scores against the whole context are held at once:
# [B, 32 heads, 256, 8192] float32 is 0.8 GB at three rows
QUERY_BLOCK = 256


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """[B, T, H, D], positions 0..T-1, pairs (i, i + D/2)."""
    seq, dim = x.shape[1], x.shape[-1]
    half = dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def causal_attention(q, k, v, products):
    """Causal softmax attention.  q [B, T, H, D]; k, v [B, T, G, D], each
    of the G kv heads serving H // G query heads."""
    batch, seq, heads, dim = q.shape
    k = jnp.repeat(k, heads // k.shape[2], axis=2)
    v = jnp.repeat(v, heads // v.shape[2], axis=2)
    block = min(QUERY_BLOCK, seq)
    if seq % block:
        block = seq

    @jax.checkpoint
    def rows(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = products.einsum("bqhd,bkhd->bhqk", q_rows, k) / jnp.sqrt(
            jnp.float32(dim))
        visible = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)[None]
        weights = jax.nn.softmax(
            jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
        return products.einsum("bhqk,bkhd->bqhd", weights, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, dim)


def short_conv(u, p, products):
    gate_b, gate_c, z = jnp.split(
        products.dot(u, p["in_proj"]["kernel"]), 3, axis=-1)
    v = gate_b * z
    taps = p["conv_kernel"].shape[0]
    conv = jnp.zeros_like(v)
    for j in range(taps):       # c_t = sum_j w_j v_{t - (taps - 1) + j}
        shift = taps - 1 - j
        shifted = jnp.pad(v, ((0, 0), (shift, 0), (0, 0)))[:, :v.shape[1]]
        conv = conv + p["conv_kernel"][j] * shifted
    return products.dot(gate_c * conv, p["out_proj"]["kernel"])


def attention(u, p, sizes, products):
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dim = sizes["hidden_size"] // heads
    eps = sizes["norm_eps"]
    shape = lambda t, n: t.reshape(t.shape[:2] + (n, dim))
    q = shape(products.dot(u, p["q_proj"]["kernel"]), heads)
    k = shape(products.dot(u, p["k_proj"]["kernel"]), kv_heads)
    v = shape(products.dot(u, p["v_proj"]["kernel"]), kv_heads)
    q = rope(_rms_norm(q, p["q_layernorm"]["scale"], eps), sizes["rope_theta"])
    k = rope(_rms_norm(k, p["k_layernorm"]["scale"], eps), sizes["rope_theta"])
    out = causal_attention(q, k, v, products).reshape(u.shape)
    return products.dot(out, p["out_proj"]["kernel"])


def _swiglu(u, w1, w3, w2, products):
    return products.dot(
        jax.nn.silu(products.dot(u, w1)) * products.dot(u, w3), w2)


def expert_weights(u, p, sizes, products):
    """[tokens, routed experts]: each token's weight for each expert, zero
    where the expert was not among its top ``num_experts_per_tok``."""
    scores = jax.nn.sigmoid(products.dot(u, p["gate"]["kernel"]))
    biased = scores + p["expert_bias"] if "expert_bias" in p else scores
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(biased),
                              sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    picked = picked * sizes["routed_scaling_factor"]
    onehot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=picked.dtype)
    return jnp.einsum("nk,nke->ne", picked, onehot)


def sparse_moe(u, p, sizes, products):
    flat = u.reshape(-1, u.shape[-1])
    weights = expert_weights(flat, p, sizes, products)
    first = sizes.get("first_expert", 0)
    out = jnp.zeros_like(flat)
    expert = jax.checkpoint(
        lambda x, w1, w3, w2: _swiglu(x, w1, w3, w2, products))
    for held in range(sizes["num_experts"]):
        out = out + weights[:, first + held, None] * expert(
            flat, p["w1"][held], p["w3"][held], p["w2"][held])
    return out.reshape(u.shape)


def layer(x, p, sizes, kind, dense, products):
    eps = sizes["norm_eps"]
    u = _rms_norm(x, p["operator_norm"]["scale"], eps)
    if kind == "conv":
        x = x + short_conv(u, p["conv"], products)
    else:
        x = x + attention(u, p["attn"], sizes, products)
    u = _rms_norm(x, p["ffn_norm"]["scale"], eps)
    if dense:
        f = p["ffn"]
        return x + _swiglu(u, f["w1"]["kernel"], f["w3"]["kernel"],
                           f["w2"]["kernel"], products)
    return x + sparse_moe(u, p["moe"], sizes, products)


def make_forward(sizes, precision="float32"):
    """``forward(params, tokens) -> logits`` over the vocabulary slice."""
    products = common.Products(precision)

    def forward(params, tokens):
        p = params["params"]
        table = p["embed_tokens"]["embedding"]
        x = table[tokens]
        for index, kind in enumerate(sizes["layer_types"]):
            dense = index < sizes["num_dense_layers"]
            x = jax.checkpoint(
                lambda x, q, kind=kind, dense=dense: layer(
                    x, q, sizes, kind, dense, products))(
                        x, p[f"layer_{index}"])
        x = _rms_norm(x, p["embedding_norm"]["scale"], sizes["norm_eps"])
        return products.dot(x, table.T)

    return forward


def make_loss(sizes, precision="float32"):
    """``loss(params, (tokens,))``: mean next-token cross-entropy."""
    forward = make_forward(sizes, precision)

    def loss(params, batch):
        (tokens,) = batch
        logp = jax.nn.log_softmax(forward(params, tokens)[:, :-1], axis=-1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(picked)

    return loss

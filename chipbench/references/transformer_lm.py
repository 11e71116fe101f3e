"""Plain reference of family ``transformer_lm``: a pre-LN decoder-only
transformer (GPTBigCode's shape: learned positions, LayerNorm, multi-query
attention, tanh-GELU MLP, biases) in straightforward float32 ``jax.numpy``:
no kernels, no flax, nothing of the program.  Departures from the published
model are the configuration file's: untied output head, LayerNorm epsilon
1e-6 (the program's), random weights.

It reads the benchmark's seeded weight tree by name.  To fit a float32
backward pass at T=8192 beside nothing else on one chip, each layer is
rematerialized and attention is computed in blocks of query rows against
the whole context."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.references import common

LN_EPSILON = 1e-6
QUERY_BLOCK = 1024


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPSILON) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, products):
    """Causal softmax attention.  q [B, T, H, D]; k, v [B, T, G, D] with G
    kv heads shared by H // G query heads each."""
    batch, seq, heads, dim = q.shape
    groups = k.shape[2]
    k = jnp.repeat(k, heads // groups, axis=2)
    v = jnp.repeat(v, heads // groups, axis=2)
    block = min(QUERY_BLOCK, seq)
    starts = jnp.arange(0, seq, block)

    @jax.checkpoint
    def rows(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = products.einsum("bqhd,bkhd->bhqk", q_rows, k) / jnp.sqrt(
            jnp.float32(dim))
        visible = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)[None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
        return products.einsum("bhqk,bkhd->bqhd", weights, v)

    out = jax.lax.map(rows, starts)            # [blocks, B, block, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, dim)


def _block(x, p, heads, kv_heads, products):
    dim = x.shape[-1] // heads
    h = _layer_norm(x, p["ln_attn"])
    qkv = products.dot(h, p["qkv"]["kernel"]) + p["qkv"]["bias"]
    d_q, d_kv = heads * dim, kv_heads * dim
    q = qkv[..., :d_q].reshape(x.shape[:2] + (heads, dim))
    k = qkv[..., d_q:d_q + d_kv].reshape(x.shape[:2] + (kv_heads, dim))
    v = qkv[..., d_q + d_kv:].reshape(x.shape[:2] + (kv_heads, dim))
    out = _attention(q, k, v, products).reshape(x.shape)
    x = x + products.dot(out, p["proj"]["kernel"]) + p["proj"]["bias"]
    h = _layer_norm(x, p["ln_mlp"])
    h = _gelu_tanh(products.dot(h, p["up"]["kernel"]) + p["up"]["bias"])
    return x + products.dot(h, p["down"]["kernel"]) + p["down"]["bias"]


def make_loss(sizes, precision="float32"):
    """``loss(params, (tokens,))``: mean next-token cross-entropy."""
    products = common.Products(precision)
    heads = sizes["n_head"]
    kv_heads = 1 if sizes["multi_query"] else heads

    def loss(params, batch):
        (tokens,) = batch
        p = params["params"]
        seq = tokens.shape[1]
        x = p["tok_emb"]["embedding"][tokens] + p["pos_emb"]["embedding"][:seq]
        for index in range(sizes["n_layer"]):
            x = jax.checkpoint(
                lambda x, q: _block(x, q, heads, kv_heads, products))(
                    x, p[f"block_{index}"])
        x = _layer_norm(x, p["ln_f"])
        logits = products.dot(x, p["head"]["kernel"]) + p["head"]["bias"]
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(picked)

    return loss

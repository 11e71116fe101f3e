"""Plain reference of family ``deepseek_v3`` (the text decoder of
moonshotai/Kimi-VL-A3B-Instruct) in straightforward float32 ``jax.numpy``:
no kernels, no flax, no cache, nothing of the program.  Written from the
layer equations of the configuration file (``assumed`` lists what the
published ``config.json`` does not say)::

    h = RMSNorm_in(x)
    q = h Wq                 -> [T, heads, 192] = q_nope[128] | q_pe[64]
    h Wkva                   -> [T, 576]        = c[512] | k_pe[64]
    RMSNorm_512(c) Wkvb      -> [T, heads, 256] = k_nope[128] | v[128]
    q_pe, k_pe rotated (theta, positions from 0); k_pe is ONE head for all
    s = (q_nope . k_nope + q_pe . k_pe) / sqrt(192);  token i sees every j <= i
    o = softmax(s) v         -> [T, heads, 128];  x = x + o Wo
    m = RMSNorm_post(x)
    layer 0:      x = x + Wdown(silu(Wgate m) * Wup m)             (11264 wide)
    layers >= 1:  p = sigmoid(m Wr) over all 64;  sel = top_6(p + bias)
                  w = p[sel] / (sum p[sel] + 1e-20) * 2.446
                  x = x + sum_{e in sel, held here} w_e expert_e(m)
                        + shared(m)          (one SwiGLU 2 x 1408 wide)
    h0 = embed(tokens);  logits = RMSNorm_f(h_L) Whead

The scores are the SUM of two products, the rotated one against the one
shared key head: nothing here concatenates q and k to 192 or repeats
``k_pe``, so how the program carries the rotated part into its kernels
cannot be shared with it.  It is given the same share as the program:
experts ``first_expert`` ... ``first_expert + num_experts - 1`` of
``num_experts_published`` and the first ``vocab_size`` rows of the
vocabulary.  The router scores ALL experts and picks
``num_experts_per_tok``; what the experts not held would add is left out;
the shared experts are whole.

Departures from the plainest form, none of the mathematics: every held
expert is applied to every token and weighted by that token's weight for it
(zero where it was not chosen) - no sorting, no grouping, the experts visited
one after another in a ``lax.scan``; to fit a float32 backward pass at
T=8192 beside three parameter-sized trees, each layer and each expert is
rematerialized and attention is computed in blocks of query rows against
the whole context under a dense mask (no tile is skipped here).  RMSNorm,
the rotation, one SwiGLU and the routing's weights are
``references/afmoe.py``'s, imported and not copied.

**Controls.**  ``make_loss(sizes, precision)`` takes the precisions of
``common.Products`` (``float32``, ``bfloat16``, ``int8``: ``python3 -m
chipbench.limits --controls``) and two more names, each the float32
reference with one piece of latent attention's mathematics left wrong, as a
program that treated it like plain attention would compute:
``nope_scale`` (the scores over ``sqrt(qk_nope_head_dim)`` = sqrt(128)
instead of sqrt(192)) and ``unrotated_key`` (``k_pe`` not rotated, ``q_pe``
rotated).  Each must fail a limit, or the limits cannot tell this attention
from a near miss."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.references import common
from chipbench.references.afmoe import rms_norm, rotate, swiglu
from chipbench.references.afmoe import token_weights as afmoe_token_weights

# rows of queries whose scores against the whole context are held at once:
# [1, 16 heads, 256, 8192] float32 is 0.13 GB
QUERY_BLOCK = 256
CONTROLS = ("nope_scale", "unrotated_key")


def latent_attention_scores(q_nope, q_pe, k_nope, k_pe, v, scale, products):
    """Causal softmax attention whose scores are ``q_nope . k_nope + q_pe .
    k_pe`` times ``scale``: q_nope, k_nope [B, T, H, N]; q_pe [B, T, H, R];
    k_pe [B, T, R], one head for all; v [B, T, H, V] -> [B, T, H, V]."""
    batch, seq, heads, _ = q_nope.shape
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq

    @jax.checkpoint
    def rows(start):
        take = lambda t: jax.lax.dynamic_slice_in_dim(t, start, block, axis=1)
        scores = (products.einsum("bqhd,bkhd->bhqk", take(q_nope), k_nope)
                  + products.einsum("bqhd,bkd->bhqk", take(q_pe), k_pe)
                  ) * scale
        visible = ((start + jnp.arange(block))[:, None]
                   >= jnp.arange(seq)[None])
        weights = jax.nn.softmax(
            jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
        return products.einsum("bhqk,bkhd->bqhd", weights, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, v.shape[-1])


def attention(h, p, sizes, products, control=None):
    heads, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, pe, value = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                       sizes["v_head_dim"])
    theta = sizes["rope_theta"]
    per_head = lambda t, width: t.reshape(t.shape[:2] + (heads, width))
    q = per_head(products.dot(h, p["q_proj"]["kernel"]), nope + pe)
    latent = products.dot(h, p["kv_a_proj_with_mqa"]["kernel"])
    c = rms_norm(latent[..., :rank], p["kv_a_layernorm"]["scale"],
                 sizes["rms_norm_eps"])
    kv = per_head(products.dot(c, p["kv_b_proj"]["kernel"]), nope + value)
    q_pe = rotate(q[..., nope:], theta)
    k_pe = latent[..., None, rank:]                      # one head
    if control != "unrotated_key":
        k_pe = rotate(k_pe, theta)
    width = nope if control == "nope_scale" else nope + pe
    out = latent_attention_scores(
        q[..., :nope], q_pe, kv[..., :nope], k_pe[:, :, 0], kv[..., nope:],
        1.0 / math.sqrt(width), products)
    return products.dot(out.reshape(h.shape[:2] + (heads * value,)),
                        p["o_proj"]["kernel"])


def token_weights(m, p, sizes, products):
    """[tokens, routed experts]: each token's weight for each expert, zero
    where the expert was not among its top ``num_experts_per_tok``.  The
    mathematics is ``references/afmoe.py``'s (sigmoid scores, selection by
    score + bias, the chosen scores over their sum + 1e-20, a scale) under
    this family's published key names."""
    return afmoe_token_weights(
        m, p, dict(sizes, route_norm=sizes["norm_topk_prob"],
                   route_scale=sizes["routed_scaling_factor"]), products)


def ffn(m, p, products):
    return swiglu(m, p["w1"]["kernel"], p["w3"]["kernel"], p["w2"]["kernel"],
                  products)


def sparse_moe(m, p, sizes, products):
    """``sum over held e of w_e expert_e(m) + shared(m)``."""
    flat = m.reshape(-1, m.shape[-1])
    weights = token_weights(flat, p, sizes, products)
    first, held = sizes.get("first_expert", 0), sizes["num_experts"]

    @jax.checkpoint
    def add_one(out, expert):
        gate, up, down, weight = expert
        return out + weight[:, None] * swiglu(flat, gate, up, down,
                                              products), None

    out, _ = jax.lax.scan(
        add_one, jnp.zeros_like(flat),
        (p["w1"], p["w3"], p["w2"], weights[:, first:first + held].T))
    return out.reshape(m.shape) + ffn(m, p["shared"], products)


def layer(x, p, sizes, kind, products, control):
    eps = sizes["rms_norm_eps"]
    x = x + attention(rms_norm(x, p["input_layernorm"]["scale"], eps),
                      p["mla"], sizes, products, control)
    m = rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    if kind == "dense":
        return x + ffn(m, p["ffn"], products)
    return x + sparse_moe(m, p["moe"], sizes, products)


def make_forward(sizes, precision="float32"):
    """``forward(params, tokens) -> logits`` over the vocabulary slice."""
    control = precision if precision in CONTROLS else None
    products = common.Products("float32" if control else precision)

    def forward(params, tokens):
        p = params["params"]
        x = p["embed_tokens"]["embedding"][tokens]
        for index, kind in enumerate(sizes["mlp_layer_types"]):
            x = jax.checkpoint(
                lambda x, q, kind=kind: layer(x, q, sizes, kind, products,
                                              control))(
                    x, p[f"layer_{index}"])
        x = rms_norm(x, p["norm"]["scale"], sizes["rms_norm_eps"])
        return products.dot(x, p["lm_head"]["kernel"])

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

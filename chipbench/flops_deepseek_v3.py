"""Operations and bytes that the ``deepseek_v3`` family needs, computed from
its shapes by ``chipbench/flops.py``'s rules: a multiply-add is TWO
operations, training is forward + weight gradient + input gradient (3 x
forward), recomputation and elementwise passes (the norms, the rotation of
the 64-wide parts, the concatenation that builds a 192-wide key, the
router's sigmoid and top-k, the gathers that order the rows) count nothing.

**Latent attention is counted as the mathematics needs it, whatever
implements it.**  The scores are ``q_nope . k_nope + q_pe . k_pe`` over
``qk_nope_head_dim + qk_rope_head_dim`` (192) wide keys, the sum over
``v_head_dim`` (128) wide values, ``T (T + 1) / 2`` (query, key) pairs a
head and row: forward ``2 x pairs x (192 + 128)``, backward dV and dP at 128
and dQ and dK at 192, twice that, so ``3 x 2 x pairs x (192 + 128)`` a head
and row.  A kernel that pads v to 192, or the keys to 256 lanes, does more
than is counted and reads lower.  The bytes are q, k and their gradients at
192 (k as every head's own: the kernels are handed one key head a query
head), v, the output and their gradients at 128, and one float32 logsumexp
a query and head.

**The routed experts are counted at their EXPECTED load**
(``flops_lfm2.expected_pairs``: tokens x top_k x held / routed pairs a step,
0.75 expert visits a token at 6 x 8 / 64); the shared experts see every
token.  The grouped kernels' need is the shared function's, which reads
generic keys: ``flops_lfm2.moe_gmm_train_flop_and_bytes(sizes)``."""

from __future__ import annotations

from chipbench import flops_lfm2


def causal_pairs(seq_len) -> float:
    """(query, key) pairs one head of one row attends over."""
    return seq_len * (seq_len + 1) / 2


def layer_forward_flop_per_token(sizes, kind) -> dict:
    """One layer's forward matmul operations a token, by part."""
    d, t = sizes["hidden_size"], sizes["seq_len"]
    heads, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, pe, value = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                       sizes["v_head_dim"])
    parts = {
        # q from the hidden state, the latent and the shared rotated key
        # from it, k_nope and v from the latent, the output back
        "latent_projections": (2 * d * heads * (nope + pe)
                               + 2 * d * (rank + pe)
                               + 2 * rank * heads * (nope + value)
                               + 2 * heads * value * d),
        # scores at nope + pe, values at v_head_dim, over the causal pairs,
        # averaged over the row
        "attention": (2 * causal_pairs(t) / t * heads
                      * ((nope + pe) + value)),
    }
    width = sizes["moe_intermediate_size"]
    if kind == "dense":
        parts["feed_forward"] = 3 * 2 * d * sizes["intermediate_size"]
    else:
        parts["router"] = 2 * d * sizes["num_experts_published"]
        parts["experts"] = (flops_lfm2.expected_pairs(1, sizes)
                            * 3 * 2 * d * width)
        parts["shared_experts"] = (sizes["n_shared_experts"]
                                   * 3 * 2 * d * width)
    return parts


def lm_train_flop_per_token(sizes) -> float:
    """Matmul operations of one training token for this layer mix, the
    routed experts at their expected load, the untied head over the
    vocabulary slice (the embedding is a gather); 3 x forward."""
    forward = 2 * sizes["hidden_size"] * sizes["vocab_size"]
    for kind in sizes["mlp_layer_types"]:
        forward += sum(layer_forward_flop_per_token(sizes, kind).values())
    return 3.0 * forward


def mla_flash_train_flop_and_bytes(sizes, itemsize=2):
    """The attention of one step's layers, scores and weighted sum, forward
    and backward (the backward's recomputed scores are not counted): what
    the ``mla.<k>`` kernels are there for."""
    heads, rows, t = (sizes["num_attention_heads"], sizes["batch_per_chip"],
                      sizes["seq_len"])
    key = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    value = sizes["v_head_dim"]
    layers = len(sizes["mlp_layer_types"])
    flop = 3.0 * 2 * rows * heads * causal_pairs(t) * (key + value)
    per_width = rows * t * heads * itemsize
    lse = rows * t * heads * 4
    # forward: reads q, k (key wide), v, writes the output (value wide) and
    # the logsumexp; backward: reads q, k, v, the output, its gradient and
    # the logsumexp, writes dq, dk (key wide) and dv (value wide)
    forward = per_width * (2 * key + 2 * value) + lse
    backward = per_width * (2 * key + 3 * value) + lse + per_width * (
        2 * key + value)
    return layers * flop, layers * float(forward + backward)

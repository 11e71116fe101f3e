"""Operations and bytes that the ``lfm2_moe`` family needs, computed from
its shapes, by ``chipbench/flops.py``'s rules: a multiply-add is TWO
operations, training is forward + weight gradient + input gradient (3 x
forward), recomputation and elementwise passes (norms, the short
convolution's three taps, RoPE, the router's sigmoid and top-k, the
gathers that order the rows) count nothing.

**The experts are counted at their EXPECTED load.**  A token visits
``num_experts_per_tok`` of the ``num_experts_published`` experts; the chip
holds ``num_experts`` of them, so under even routing it computes ``tokens x
top_k x held / routed`` (token, expert) pairs a step: one expert visit a
token at 4 x 8 / 32.  A seed whose routing sends more pairs to the held
experts does more work than is counted (``mfu`` and ``moe_gmm_roofline`` read
low), one that sends fewer does less (they read high); the step's counters
(``held_share``) say which.
"""

from __future__ import annotations

from chipbench import flops


def expected_pairs(tokens, sizes) -> float:
    """(token, expert) pairs the held experts compute at even routing."""
    return (tokens * sizes["num_experts_per_tok"] * sizes["num_experts"]
            / sizes["num_experts_published"])


def layer_forward_flop_per_token(sizes, kind, dense) -> dict:
    """One layer's forward matmul operations a token, by part."""
    d, t = sizes["hidden_size"], sizes["seq_len"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head_dim = d // heads
    if kind == "conv":
        parts = {"operator": 2 * d * 3 * d + 2 * d * d}
    else:
        parts = {"operator": 2 * d * (heads + 2 * kv) * head_dim + 2 * d * d,
                 # scores + values over the causal half: 2 x 2 x T/2 x d
                 "attention": 2 * 2 * (t / 2) * heads * head_dim}
    if dense:
        parts["feed_forward"] = 3 * 2 * d * sizes["intermediate_size"]
    else:
        parts["router"] = 2 * d * sizes["num_experts_published"]
        parts["experts"] = (expected_pairs(1, sizes)
                            * 3 * 2 * d * sizes["moe_intermediate_size"])
    return parts


def lm_train_flop_per_token(sizes) -> float:
    """Matmul operations of one training token for this layer mix, the
    experts at their expected load (module docstring), the tied head over
    the vocabulary slice; 3 x forward."""
    forward = 2 * sizes["hidden_size"] * sizes["vocab_size"]
    for index, kind in enumerate(sizes["layer_types"]):
        forward += sum(layer_forward_flop_per_token(
            sizes, kind, index < sizes["num_dense_layers"]).values())
    return 3.0 * forward


def moe_layers(sizes) -> int:
    return len(sizes["layer_types"]) - sizes["num_dense_layers"]


def grouped_matmul_train_flop(rows, k, n) -> float:
    """One grouped product over ``rows`` rows in all its groups: forward,
    ``dlhs`` and ``drhs``, 2 x rows x K x N each."""
    return 3.0 * 2 * rows * k * n


def grouped_matmul_train_bytes(rows, groups, k, n, itemsize=2) -> float:
    """The least the three kernels move: the forward reads the rows and
    every group's matrix and writes the result; ``dlhs`` reads the result's
    gradient and the matrices and writes the rows' gradient; ``drhs`` reads
    the rows and the result's gradient and writes the matrices' gradient."""
    lhs, out, rhs = rows * k, rows * n, groups * k * n
    return float(itemsize * ((lhs + rhs + out) + (out + rhs + lhs)
                             + (lhs + out + rhs)))


def moe_gmm_train_flop_and_bytes(sizes):
    """All grouped products of one step at the expected load: gate, up
    (hidden -> expert width) and down (expert width -> hidden) in every MoE
    layer."""
    tokens = sizes["batch_per_chip"] * sizes["seq_len"]
    rows = expected_pairs(tokens, sizes)
    d, width = sizes["hidden_size"], sizes["moe_intermediate_size"]
    held = sizes["num_experts"]
    flop = 3 * grouped_matmul_train_flop(rows, d, width)
    nbytes = (2 * grouped_matmul_train_bytes(rows, held, d, width)
              + grouped_matmul_train_bytes(rows, held, width, d))
    return moe_layers(sizes) * flop, moe_layers(sizes) * nbytes


def gqa_flash_train_flop_and_bytes(sizes):
    """The flash kernels of the ``full_attention`` layers of one step."""
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head_dim = sizes["hidden_size"] // heads
    layers = list(sizes["layer_types"]).count("full_attention")
    rows = sizes["batch_per_chip"]
    return (layers * flops.flash_train_flop(rows, sizes["seq_len"], heads,
                                            head_dim),
            layers * flops.flash_train_bytes(rows, sizes["seq_len"], heads,
                                             kv, head_dim))

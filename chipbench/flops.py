"""Operations and bytes that the benchmark's models and kernels need,
computed from their shapes.  A multiply-add is TWO operations throughout,
as in the peaks of ``peaks.json`` (Google Cloud, "TPU v5e": 197 TFLOP/s
bf16 counts a multiply-add as two).

Training counts what the forward and backward passes require: forward,
the gradient with respect to the weights and the gradient with respect to
the layer's input, one forward's worth of operations each.  Recomputed
operations (flash attention's second score matmul, a remat policy) are not
counted, and nor are elementwise passes (normalization, activation, the
optimizer): those cost bandwidth, and show as a lower share of the peak.

The 12.3 GFLOP an image that the repo's older records quote for ResNet-50
(``docs/performance.md``; the script that held it went at PR 28) is 3 x
4.1 G multiply-adds, a multiply-add counted once: half of what this module
counts, and so were the "14.7 % MFU ceiling" figures derived from it.
"""

from __future__ import annotations


def _same(size: int, stride: int) -> int:
    """Output size of a SAME-padded convolution or pooling."""
    return -(-size // stride)


def resnet_conv_layers(stage_sizes, num_filters, image_size, num_classes,
                       block="bottleneck"):
    """Every matmul-like layer of the program's ResNet (models/resnet.py:
    7x7/2 stem, 3x3/2 max-pool, stride on the block's 3x3) as ``(name,
    multiply_adds_per_image, needs_input_gradient)``."""
    layers = []
    size = _same(image_size, 2)
    layers.append(("conv_init", size * size * 7 * 7 * 3 * num_filters, False))
    size = _same(size, 2)  # max-pool
    channels = num_filters
    for stage, count in enumerate(stage_sizes):
        filters = num_filters * 2 ** stage
        for index in range(count):
            stride = 2 if stage > 0 and index == 0 else 1
            out = _same(size, stride)
            name = f"stage{stage}.block{index}"
            if block == "bottleneck":
                layers.append((name + ".conv1x1a",
                               size * size * channels * filters, True))
                layers.append((name + ".conv3x3",
                               out * out * 9 * filters * filters, True))
                layers.append((name + ".conv1x1b",
                               out * out * filters * filters * 4, True))
                width = filters * 4
            else:
                layers.append((name + ".conv3x3a",
                               out * out * 9 * channels * filters, True))
                layers.append((name + ".conv3x3b",
                               out * out * 9 * filters * filters, True))
                width = filters
            if stride != 1 or channels != width:
                layers.append((name + ".conv_proj",
                               out * out * channels * width, True))
            size, channels = out, width
    layers.append(("dense", channels * num_classes, True))
    return layers


def resnet_forward_multiply_adds(sizes) -> int:
    return sum(m for _, m, _ in resnet_conv_layers(
        sizes["stage_sizes"], sizes["num_filters"], sizes["image_size"],
        sizes["num_classes"], sizes.get("block", "bottleneck")))


def resnet_train_flop_per_image(sizes) -> float:
    """2 operations a multiply-add x (forward + weight gradient + input
    gradient); the stem needs no gradient with respect to the image."""
    total = 0
    for _, madds, needs_input_gradient in resnet_conv_layers(
            sizes["stage_sizes"], sizes["num_filters"], sizes["image_size"],
            sizes["num_classes"], sizes.get("block", "bottleneck")):
        total += 2 * madds * (3 if needs_input_gradient else 2)
    return float(total)


def lm_train_flop_per_token(seq_len, d, layers, vocab, n_heads,
                            n_kv_heads=None, d_inner=None) -> float:
    """Matmul operations of one training token at sequence length T: per
    matmul 2*M*N*K, attention counts the causal half for the score and the
    value matmul, grouped kv heads shrink only the kv projection, train =
    3 x forward, recompute not counted.  The embedding lookups are gathers
    and count nothing."""
    t = seq_len
    n_kv = n_kv_heads or n_heads
    d_kv = n_kv * (d // n_heads)
    d_inner = d_inner or 4 * d
    per_layer = (
        2 * t * d * (d + 2 * d_kv)      # qkv projection
        + 2 * t * d * d                 # output projection
        + 2 * t * d * d_inner * 2       # mlp up + down
    )
    attention = 2 * 2 * (t * t / 2) * d  # scores + values, causal half
    forward = layers * (per_layer + attention) + 2 * t * d * vocab
    return 3.0 * forward / t


def flash_train_flop(batch, seq_len, n_heads, head_dim) -> float:
    """Operations that causal attention needs for one layer's forward and
    backward: forward QK^T and PV, backward dV, dP, dQ, dK (six matmuls of
    2 * T^2/2 * head_dim per head; the backward's recomputed QK^T is not
    counted)."""
    return 6.0 * 2 * batch * n_heads * (seq_len * seq_len / 2) * head_dim


def flash_train_bytes(batch, seq_len, n_heads, n_kv_heads, head_dim,
                      itemsize=2) -> float:
    """Bytes that one layer's attention has to move at the least: the
    forward reads q, k, v and writes the output and one float32 logsumexp
    per query and head; the backward reads q, k, v, the output, its
    gradient and the logsumexp, and writes dq, dk, dv."""
    q = batch * seq_len * n_heads * head_dim * itemsize
    kv = batch * seq_len * n_kv_heads * head_dim * itemsize
    lse = batch * seq_len * n_heads * 4
    forward = q + 2 * kv + q + lse
    backward = (q + 2 * kv + q + q + lse) + (q + 2 * kv)
    return float(forward + backward)


def roofline_seconds(flop, nbytes, peaks):
    """The least time the chip could take, and which bound gives it."""
    by_compute = flop / (peaks["bf16_tflops"] * 1e12)
    by_memory = nbytes / (peaks["hbm_gbytes_per_s"] * 1e9)
    if by_compute >= by_memory:
        return by_compute, "compute"
    return by_memory, "memory"

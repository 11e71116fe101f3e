"""chipbench/flops.py against hand counts."""

import json
import os

from chipbench import flops, spec

CONFIGS = os.path.join(spec.HERE, "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as handle:
        return json.load(handle)


def test_resnet50_forward_multiply_adds_by_hand():
    # stem 7x7x3x64 at 112x112; per stage the bottlenecks of
    # (filters f, input size s -> output size o, input channels c):
    #   first block: 1x1 c->f at s, 3x3 f->f at o, 1x1 f->4f at o,
    #                projection c->4f at o
    #   other blocks: 1x1 4f->f, 3x3 f->f, 1x1 f->4f at o
    stem = 112 * 112 * 7 * 7 * 3 * 64
    total = stem
    size, channels = 56, 64
    for stage, (count, f) in enumerate(zip((3, 4, 6, 3),
                                           (64, 128, 256, 512))):
        out = size if stage == 0 else size // 2
        total += size * size * channels * f          # 1x1 (before stride)
        total += out * out * 9 * f * f               # 3x3 carries the stride
        total += out * out * f * 4 * f
        total += out * out * channels * 4 * f        # projection shortcut
        total += (count - 1) * out * out * (4 * f * f + 9 * f * f + 4 * f * f)
        size, channels = out, 4 * f
    total += 2048 * 1000
    assert total == 4_089_184_256        # the well-known 4.09 G multiply-adds
    assert flops.resnet_forward_multiply_adds(_config("resnet50")) == total


def test_resnet50_training_counts_two_operations_a_multiply_add():
    config = _config("resnet50")
    forward = flops.resnet_forward_multiply_adds(config)
    stem = 112 * 112 * 7 * 7 * 3 * 64
    # forward + weight gradient + input gradient, but no gradient flows
    # into the image
    assert flops.resnet_train_flop_per_image(config) == 2 * (
        3 * forward - stem)
    # the older records' 12.3 GFLOP/image counted a multiply-add once
    assert 24.0e9 < flops.resnet_train_flop_per_image(config) < 24.6e9


def test_lm_formula_at_multi_query_by_hand():
    t, d, layers, vocab, heads = 8192, 2048, 10, 49152, 16
    head_dim = d // heads
    qkv = 2 * d * (d + 2 * head_dim)            # one kv head
    proj = 2 * d * d
    mlp = 2 * 2 * d * 8192
    attention = 2 * 2 * (t / 2) * d             # per token, causal half
    forward = layers * (qkv + proj + mlp + attention) + 2 * d * vocab
    got = flops.lm_train_flop_per_token(t, d, layers, vocab, heads,
                                        n_kv_heads=1, d_inner=8192)
    assert got == 3 * forward
    # grouped kv heads shrink only the kv projection
    full = flops.lm_train_flop_per_token(t, d, layers, vocab, heads)
    assert full - got == 3 * layers * 2 * d * 2 * (d - head_dim)


def test_flash_share_of_a_layer_is_what_the_issue_says():
    # 6*T*d = 101 M attention FLOPs a token a layer against 255 M of matmuls
    t, d, heads = 8192, 2048, 16
    per_token = flops.flash_train_flop(1, t, heads, d // heads) / t
    assert per_token == 6 * t * d
    matmuls = 3 * (2 * d * (d + 2 * 128) + 2 * d * d + 2 * 2 * d * 8192)
    assert 0.27 < per_token / (per_token + matmuls) < 0.30


def test_roofline_names_its_bound():
    peaks = {"bf16_tflops": 197.0, "hbm_gbytes_per_s": 819.0}
    seconds, bound = flops.roofline_seconds(197e12, 1e9, peaks)
    assert bound == "compute" and abs(seconds - 1.0) < 1e-12
    seconds, bound = flops.roofline_seconds(1e9, 819e9, peaks)
    assert bound == "memory" and abs(seconds - 1.0) < 1e-12
    t, heads, dim = 8192, 16, 128
    _, bound = flops.roofline_seconds(
        flops.flash_train_flop(1, t, heads, dim),
        flops.flash_train_bytes(1, t, heads, 1, dim), peaks)
    assert bound == "compute"

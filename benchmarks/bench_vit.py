#!/usr/bin/env python
"""ViT training throughput — a model that is nearly all large matmuls.

Not a cell of the benchmark (`BENCHMARK.json`, `python3 -m chipbench.run`;
numbers in PERF.md and PERF_LEDGER.jsonl): no cell replaces it, so it
stays as a script.  The benchmark's ResNet-50 cell, whose 64/128-channel
early stages are memory- and lane-bound, runs at 32.2 % MFU (ledger,
PR 27).  This script asks the complementary question: what does the SAME
training machinery (`create_communicator` → `create_multi_node_optimizer`
→ `make_train_step`, bf16 compute, bf16 gradient allreduce, donated
buffers) sustain when the model is MXU-shaped?  ViT-B/16 is ~90% large matmuls (197-token attention + 4x
GELU MLPs at width 768), so its train step should land near the chip's
practical matmul ceiling rather than ResNet's HBM floor.

Prints ONE JSON line: {"metric": "vit_b16_synthetic_imagenet_train_throughput",
"value": img/s/chip, "unit": ..., "mfu": ...}.  CPU runs use a tiny ViT
smoke configuration (the contract stays exercisable anywhere).

FLOP accounting: fwd FLOPs counted exactly from the model config below
(patch embed + qkv/proj/mlp matmuls + attention score/value batches +
head), a multiply-add as two operations; train = 3x fwd (forward + a
backward of twice its cost), the convention of `chipbench/flops.py`.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def vit_train_gflop_per_image(image, patch, d, layers, n_classes,
                              mlp_ratio=4, pooling="cls"):
    """Exact matmul FLOPs (2*M*N*K) of one forward image, x3 for training.
    Head count does not change matmul FLOPs (the per-head dims multiply
    back out), so it is not a parameter here."""
    t = (image // patch) ** 2 + (1 if pooling == "cls" else 0)
    f = 2 * t * (patch * patch * 3) * d            # patch embed conv
    per_layer = (
        2 * t * d * 3 * d                          # qkv
        + 2 * t * t * d                            # scores  (q @ k^T, all heads)
        + 2 * t * t * d                            # probs @ v
        + 2 * t * d * d                            # proj
        + 2 * t * d * mlp_ratio * d * 2            # mlp up + down
    )
    f += layers * per_layer
    f += 2 * d * n_classes                         # head (one row)
    return 3 * f / 1e9


def run(args) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu.models import ViT
    from chainermn_tpu.optimizers import init_opt_state, make_train_step
    from chainermn_tpu.training import put_global_batch

    on_tpu = jax.default_backend() == "tpu"
    n_dev = jax.device_count()
    if on_tpu:
        n_classes, image, patch = 1000, 224, 16
        d, layers, heads = 768, 12, 12
        per_chip_batch, steps, warmup = args.batch, 20, 5
    else:  # CPU smoke
        n_classes, image, patch = 10, 32, 8
        d, layers, heads = 32, 2, 4
        per_chip_batch, steps, warmup = 8, 5, 2
    model = ViT(num_classes=n_classes, patch=patch, d_model=d,
                n_layers=layers, n_heads=heads, dtype=jnp.bfloat16,
                attention_impl=args.attention)
    gflop = vit_train_gflop_per_image(image, patch, d, layers, n_classes)

    comm = chainermn_tpu.create_communicator(
        "xla", allreduce_grad_dtype="bfloat16" if on_tpu else None)
    log(f"bench_vit: backend={jax.default_backend()} devices={n_dev} "
        f"batch/chip={per_chip_batch} image={image} attn={args.attention} "
        f"train GFLOP/img={gflop:.2f}")

    variables = model.init(
        jax.random.key(0), jnp.zeros((1, image, image, 3), jnp.float32))
    params = comm.bcast_data(variables["params"])
    # lr 3e-3: ResNet's 0.1 diverges on an unwarmed ViT within the 25
    # measured steps; throughput is unaffected but the artifact should
    # show a training-shaped (decreasing) loss
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(3e-3, momentum=0.9), comm, double_buffering=True)
    opt_state = init_opt_state(comm, optimizer, params)

    def loss_fn(p, batch):
        x, y = batch
        logits = model.apply({"params": p}, x, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    step = make_train_step(comm, loss_fn, optimizer)

    global_batch = per_chip_batch * comm.size
    rng = np.random.RandomState(0)
    x = rng.randn(global_batch, image, image, 3).astype(np.float32)
    y = (rng.rand(global_batch) * n_classes).astype(np.int32)
    batch = put_global_batch(comm, (x, y))

    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    log(f"bench_vit: warmup done, loss={float(loss):.3f}")

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, batch)
    # value read: every timed step has run (dispatch is asynchronous)
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    log(f"bench_vit: final loss {final_loss:.3f}")

    per_chip = global_batch * steps / dt / n_dev
    out = {
        "metric": "vit_b16_synthetic_imagenet_train_throughput"
                  if on_tpu else "tiny_vit_cpu_smoke_train_throughput",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "attention": args.attention,
        "train_gflop_per_image": round(gflop, 4),
    }
    if on_tpu:
        from chainermn_tpu.utils.tpu_info import peak_tflops
        from chainermn_tpu.utils.trace import device_time

        dev = jax.devices()[0]
        peak = peak_tflops(dev)
        out["mfu"] = round(per_chip * gflop / 1e3 / peak, 4)
        out["device_kind"] = dev.device_kind
        out["peak_tflops"] = peak
        out["step_ms"] = round(dt / steps * 1e3, 2)
        box = [(params, opt_state)]

        def one():
            p, s = box[0]
            p, s, l = step(p, s, batch)
            box[0] = (p, s)
            return l

        out["device_ms_per_step"] = round(
            device_time(one, (), steps=3, warmup=1), 2)
        log(f"bench_vit: MFU {out['mfu']:.1%} (peak {peak} TFLOP/s bf16)")
    else:
        out["smoke"] = True
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch", type=int, default=256,
                        help="per-chip batch (TPU path)")
    parser.add_argument("--attention", choices=["xla", "flash"],
                        default="xla",
                        help="encoder attention impl (197 tokens fit one "
                             "flash tile; xla default — measure both)")
    args = parser.parse_args()

    from chainermn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    out = run(args)
    from chainermn_tpu.observability.ledger import stamp_envelope
    stamp_envelope(out, "bench_vit/v1")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

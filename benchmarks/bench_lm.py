#!/usr/bin/env python
"""Transformer-LM training throughput — tokens/s through the full stack.

Completes the performance triptych: `bench.py` pins the reference's
flagship convnet (memory-bound, 14.7% MFU ceiling), `bench_vit.py` pins
the MXU-shaped image model (43.6% MFU), and this pins the LM family the
long-context machinery exists for — TransformerLM with the streaming
flash kernels, bf16 compute, bf16 gradient allreduce, double-buffered
optimizer, donated buffers: the identical `create_communicator` →
`create_multi_node_optimizer` → `make_train_step` path.

Prints ONE JSON line: {"metric": "transformer_lm_train_throughput",
"value": tokens/s/chip, ...}.  CPU runs use a tiny smoke config.

FLOP accounting is exact per matmul: embedding/head + per-layer
qkv/proj/mlp (2*M*N*K each) + causal attention (2 * 2 * T^2/2 * D per
head pair, fwd); train = 3x fwd (fwd + 2x-cost bwd).
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


def lm_train_gflop_per_token(seq_len, d, layers, vocab, n_heads,
                             n_kv_heads=None):
    """Exact matmul FLOPs of one forward TOKEN at sequence length T,
    x3 for training.  Attention counts the causal half (T^2/2) for both
    the score and value matmuls; GQA reduces only the kv projection."""
    t = seq_len
    n_kv = n_kv_heads or n_heads
    head_dim = d // n_heads
    d_kv = n_kv * head_dim
    per_layer_tokens = (
        2 * t * d * (d + 2 * d_kv)      # qkv projection
        + 2 * t * d * d                 # output projection
        + 2 * t * d * 4 * d * 2         # mlp up + down
    )
    attn = 2 * 2 * (t * t / 2) * d      # scores + values, causal half
    f = layers * (per_layer_tokens + attn)
    f += 2 * t * d * vocab              # head (tok_emb lookup is gatherless)
    return 3 * f / t / 1e9


def run(args) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.optimizers import init_opt_state, make_train_step
    from chainermn_tpu.training import put_global_batch

    on_tpu = jax.default_backend() == "tpu"
    n_dev = jax.device_count()
    if on_tpu:
        seq, d, layers, heads = args.seq_len, args.d_model, args.layers, 16
        vocab, batch, steps, warmup = 32768, args.batch, 10, 3
        attention = "flash"
    else:  # CPU smoke
        seq, d, layers, heads = 256, 64, 2, 4
        vocab, batch, steps, warmup = 512, 2, 3, 1
        attention = "xla"
    model = TransformerLM(
        vocab=vocab, d_model=d, n_layers=layers, n_heads=heads,
        max_len=seq, attention_impl=attention, dtype=jnp.bfloat16)
    gflop_tok = lm_train_gflop_per_token(seq, d, layers, vocab, heads)

    comm = chainermn_tpu.create_communicator(
        "xla", allreduce_grad_dtype="bfloat16" if on_tpu else None)
    log(f"bench_lm: backend={jax.default_backend()} devices={n_dev} "
        f"T={seq} d={d} L={layers} vocab={vocab} b={batch}/chip "
        f"attn={attention} train GFLOP/token={gflop_tok:.3f}")

    params = comm.bcast_data(model.init(
        jax.random.key(0), jnp.zeros((1, min(seq, 128)), jnp.int32)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"bench_lm: {n_params/1e6:.1f}M params")
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(1e-3, momentum=0.9), comm, double_buffering=True)
    opt_state = init_opt_state(comm, optimizer, params)

    def loss_fn(p, batch_):
        (tok,) = batch_
        logits = model.apply(p, tok)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tok[:, 1:]).mean()

    step = make_train_step(comm, loss_fn, optimizer)

    rng = np.random.RandomState(0)
    toks = (rng.rand(batch * comm.size, seq) * vocab).astype(np.int32)
    batch_dev = put_global_batch(comm, (toks,))

    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, batch_dev)
    jax.block_until_ready(loss)
    log(f"bench_lm: warmup done, loss={float(loss):.3f}")

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, batch_dev)
    final_loss = float(loss)  # value read: every timed step has run
    dt = time.perf_counter() - t0
    log(f"bench_lm: final loss {final_loss:.3f}")

    tok_per_sec = batch * comm.size * seq * steps / dt / n_dev
    out = {
        "metric": "transformer_lm_train_throughput"
                  if on_tpu else "tiny_lm_cpu_smoke_train_throughput",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec/chip",
        "seq_len": seq, "d_model": d, "layers": layers,
        "n_params_m": round(n_params / 1e6, 1),
        "train_gflop_per_token": round(gflop_tok, 4),
    }
    if on_tpu:
        from chainermn_tpu.utils.tpu_info import peak_tflops
        from chainermn_tpu.utils.trace import device_time

        dev = jax.devices()[0]
        peak = peak_tflops(dev)
        out["mfu"] = round(tok_per_sec * gflop_tok / 1e3 / peak, 4)
        out["device_kind"] = dev.device_kind
        out["peak_tflops"] = peak
        out["step_ms"] = round(dt / steps * 1e3, 2)
        box = [(params, opt_state)]

        def one():
            p, s = box[0]
            p, s, l = step(p, s, batch_dev)
            box[0] = (p, s)
            return l

        out["device_ms_per_step"] = round(
            device_time(one, (), steps=3, warmup=1), 2)
        log(f"bench_lm: MFU {out['mfu']:.1%} (peak {peak} TFLOP/s bf16)")
    else:
        out["smoke"] = True
    return out


def main():
    parser = argparse.ArgumentParser()
    # defaults won the round-5 on-chip sweep (LM_BENCH_r05.json): d=2048
    # fills the MXU (52.3% MFU vs 34% at d=1024); L=8 b=1 is the largest
    # config that fits 15.75 GB HBM with f32 master params + momentum
    # (L=12 OOMs by 176 MB; L=10 ties at 51.9%)
    parser.add_argument("--seq-len", type=int, default=8192)
    parser.add_argument("--d-model", type=int, default=2048)
    parser.add_argument("--layers", type=int, default=8)
    parser.add_argument("--batch", type=int, default=1,
                        help="per-chip batch (TPU path)")
    args = parser.parse_args()

    from chainermn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    out = run(args)
    from chainermn_tpu.observability.ledger import stamp_envelope
    stamp_envelope(out, "bench_lm/v1")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

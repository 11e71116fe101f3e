#!/usr/bin/env python
"""Sequence-parallel attention microbenchmark (beyond-reference extension).

Times ring and Ulysses attention on a sequence-sharded mesh vs. the
single-device baseline, at growing sequence lengths, reporting
tokens/sec and the longest length each path handles.

    python benchmarks/bench_ring_attention.py --seq-lens 2048,8192 --json
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq-lens", default="1024,4096",
                        help="comma-separated global sequence lengths")
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--batch", type=int, default=1)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args()

    from chainermn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from chainermn_tpu.parallel.sequence import (
        attention, ring_attention, ulysses_attention)
    from chainermn_tpu.utils.cpu_mesh import ensure_device_count

    # Keep a single real accelerator chip (degenerate 1-way "ring", but the
    # fused-vs-unfused single-device comparison is the interesting row
    # there); only fall back to the virtual CPU mesh when the current
    # backend is CPU with too few devices.
    try:
        devices = jax.devices()
        backend = jax.default_backend()
    except Exception:       # pre-initialized backend with no chip attached
        devices, backend = [], "cpu"
    if len(devices) < 2 and backend == "cpu":
        devices = ensure_device_count(8)
    n = len(devices)
    mesh = Mesh(np.array(devices), ("sp",))
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    sync_each = jax.default_backend() == "cpu"

    def spmd(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp")))

    from chainermn_tpu.ops.flash_attention import flash_attention

    impls = {
        "ring": spmd(lambda q, k, v: ring_attention(
            q, k, v, axis_name="sp", causal=True)),
        "ulysses": spmd(lambda q, k, v: ulysses_attention(
            q, k, v, axis_name="sp", causal=True)),
        "single_device": jax.jit(
            lambda q, k, v: attention(q, k, v, causal=True)),
        "single_device_flash": jax.jit(
            lambda q, k, v: flash_attention(q, k, v, True)),
    }

    results = []
    for t in (int(s) for s in args.seq_lens.split(",")):
        rng = np.random.RandomState(0)
        mk = lambda: jnp.asarray(
            rng.randn(args.batch, t, args.heads, args.head_dim), dtype) * 0.3
        q, k, v = mk(), mk(), mk()
        for name, fn in impls.items():
            try:
                # Value-read fence: the timed window ends when a result
                # value is on the host, so every call in it has run.
                fence = lambda o: float(jnp.sum(o[0, 0, 0]))
                out = fn(q, k, v)
                fence(out)
                for _ in range(args.warmup):
                    out = fn(q, k, v)
                    if sync_each:
                        jax.block_until_ready(out)
                fence(out)
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = fn(q, k, v)
                    if sync_each:
                        jax.block_until_ready(out)
                fence(out)
                dt = (time.perf_counter() - t0) / args.iters
                row = {"impl": name, "seq_len": t, "devices": n,
                       "time_ms": round(dt * 1e3, 3),
                       "tokens_per_sec": round(args.batch * t / dt, 1)}
            except Exception as e:  # e.g. single-device OOM at long T
                row = {"impl": name, "seq_len": t, "devices": n,
                       "error": type(e).__name__}
            results.append(row)
            if args.json:
                print(json.dumps(row), flush=True)
            else:
                print(row, file=sys.stderr, flush=True)
    return results


if __name__ == "__main__":
    main()

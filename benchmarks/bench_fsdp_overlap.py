#!/usr/bin/env python
"""Bucketed-FSDP overlap sweep — bucket count x prefetch depth.

Sweeps ``fsdp_init(num_buckets=K)`` x ``make_fsdp_train_step(prefetch=D)``
over an MLP and, for every config, (a) times the step and (b) pins the
SCHEDULE structurally: the compiled HLO must contain exactly K
all-gathers and K reduce-scatters, and the lowered StableHLO exactly
``2 * max(0, K - 1 - D)`` optimization barriers (each prefetch-window pin
appears once in the forward and once — via the custom VJP — on the
backward's reduce-scatter side).

The CPU pipeline executes collectives inline, so the TIMES here cannot
show gather/compute overlap — they validate the harness and catch
bucketing overhead regressions.  The structural asserts are the real
product on this mesh; run the same sweep on a multi-chip slice
(tools/multichip_day1.sh carries the leg) for the overlap measurement.

    python benchmarks/bench_fsdp_overlap.py --buckets 1,2,4 --prefetch 0,1
"""

import argparse
import json
import os
import re
import sys
import time

import numpy as np

# Runnable from a fresh clone without `pip install -e .`.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def collective_counts(compiled_hlo: str) -> dict:
    """Count the stage-3 collectives in optimized HLO text (the -start
    forms are the async TPU spellings)."""
    return {
        "all_gathers": len(re.findall(r"all-gather(?:-start)?\(",
                                      compiled_hlo)),
        "reduce_scatters": len(re.findall(r"reduce-scatter(?:-start)?\(",
                                          compiled_hlo)),
    }


def expected_barriers(num_buckets: int, prefetch: int) -> int:
    """Barrier census for one step: one pin per bucket beyond the
    prefetch window, mirrored onto the backward by the custom VJP."""
    if num_buckets <= 1:
        return 0
    return 2 * max(0, num_buckets - 1 - prefetch)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--buckets", default="1,2,4",
                        help="comma-separated num_buckets sweep")
    parser.add_argument("--prefetch", default="0,1",
                        help="comma-separated prefetch-depth sweep")
    parser.add_argument("--layers", type=int, default=8,
                        help="MLP depth (one leaf pair per layer)")
    parser.add_argument("--width", type=int, default=256,
                        help="MLP width (payload scales with width^2)")
    parser.add_argument("--batch", type=int, default=4,
                        help="per-device batch size")
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--wire-dtype", default=None,
                        help="wire dtype for both collective legs")
    parser.add_argument("--no-assert", action="store_true",
                        help="report the schedule census without asserting "
                             "it (debugging a changed partitioner)")
    parser.add_argument("--json", action="store_true",
                        help="emit one JSON line per config")
    parser.add_argument("--metrics", default=None, metavar="PATH",
                        help="append one record per config to this metrics "
                             "JSONL (shared observability schema)")
    args = parser.parse_args()

    from chainermn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()

    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu.parallel import buckets as bucket_mod
    from chainermn_tpu.parallel.fsdp import fsdp_init, make_fsdp_train_step
    from chainermn_tpu.training import put_global_batch
    from chainermn_tpu.utils.cpu_mesh import ensure_device_count

    ensure_device_count(8)
    comm = chainermn_tpu.create_communicator("flat")
    rng = np.random.RandomState(0)
    w = args.width
    params = {f"layer{i:02d}": {
        "w": jnp.asarray(rng.randn(w, w) / np.sqrt(w), jnp.float32),
        "b": jnp.zeros((w,), jnp.float32)} for i in range(args.layers)}
    n_layers = args.layers

    def loss_fn(p, batch_):
        x, y = batch_
        for i in range(n_layers):
            lp = p[f"layer{i:02d}"]
            x = jnp.tanh(x @ lp["w"] + lp["b"])
        return jnp.mean((x - y) ** 2)

    xs = np.asarray(rng.randn(comm.size * args.batch, w), np.float32)
    ys = np.asarray(rng.randn(comm.size * args.batch, w), np.float32)
    batch = put_global_batch(comm, (xs, ys))
    payload = sum(l.size * l.dtype.itemsize
                  for l in jax.tree.leaves(params))

    sync_each = jax.default_backend() == "cpu"
    results = []
    for K in [int(b) for b in args.buckets.split(",")]:
        state, meta = fsdp_init(comm, params, optax.adam(1e-3),
                                num_buckets=K)
        desc = bucket_mod.describe_buckets(
            bucket_mod.partition_buckets(jax.tree.leaves(params),
                                         num_buckets=K))
        for D in [int(d) for d in args.prefetch.split(",")]:
            step = make_fsdp_train_step(
                comm, loss_fn, optax.adam(1e-3), meta, donate=False,
                wire_dtype=args.wire_dtype, prefetch=D)
            lowered = step.lower(state, batch) if hasattr(step, "lower") \
                else jax.jit(step).lower(state, batch)
            n_bar = lowered.as_text().count("stablehlo.optimization_barrier")
            counts = collective_counts(lowered.compile().as_text())
            want_bar = expected_barriers(meta.num_buckets, D)
            ok = (counts["all_gathers"] == meta.num_buckets
                  and counts["reduce_scatters"] == meta.num_buckets
                  and n_bar == want_bar)
            if not args.no_assert:
                assert ok, (
                    f"schedule census mismatch at num_buckets={K} "
                    f"prefetch={D}: {counts} barriers={n_bar} "
                    f"(expected {meta.num_buckets} gathers, "
                    f"{meta.num_buckets} reduce-scatters, "
                    f"{want_bar} barriers)")
            st = state
            for _ in range(args.warmup):
                st, loss = step(st, batch)
                if sync_each:
                    jax.block_until_ready(loss)
            float(loss)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                st, loss = step(st, batch)
                if sync_each:
                    jax.block_until_ready(loss)
            float(loss)
            dt = (time.perf_counter() - t0) / args.iters
            row = {"num_buckets": meta.num_buckets, "prefetch": D,
                   "devices": comm.size,
                   "payload_mib": round(payload / (1 << 20), 3),
                   "step_ms": round(dt * 1e3, 3),
                   "all_gathers": counts["all_gathers"],
                   "reduce_scatters": counts["reduce_scatters"],
                   "barriers": n_bar,
                   "schedule_ok": ok,
                   "bucket_balance": round(desc["max_over_mean"], 3),
                   "backend": jax.default_backend()}
            results.append(row)
            if args.metrics:
                from chainermn_tpu.observability import append_jsonl

                append_jsonl(args.metrics,
                             dict(row, kind="bench_fsdp_overlap",
                                  ts=time.time()))
            if args.json:
                print(json.dumps(row), flush=True)
            else:
                print(f"K={meta.num_buckets} D={D}: {row['step_ms']} ms, "
                      f"{counts['all_gathers']} gathers / "
                      f"{counts['reduce_scatters']} scatters / "
                      f"{n_bar} barriers "
                      f"({'ok' if ok else 'MISMATCH'})", file=sys.stderr)
    if sync_each:
        print("note: CPU pipeline executes collectives inline — times "
              "validate the harness only; measure overlap on real chips "
              "(tools/multichip_day1.sh)", file=sys.stderr)
    return results


if __name__ == "__main__":
    main()

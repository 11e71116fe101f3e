#!/usr/bin/env python
"""Gradient-compression sweep — compressor x bucket count.

Sweeps ``fsdp_init(bucket_compressors=...)`` over an MLP and, for every
config, (a) times the step and (b) pins the WIRE structurally from the
compiled HLO: the program must carry exactly K all-gathers and K
reduce-scatters (compression adds NO collectives — scales ride the
existing legs), the same optimization-barrier census as the uncompressed
schedule (prefetch pinning composes), and the summed reduce-scatter
operand bytes must shrink by the wire ratio (>= 3.5x for int8 vs the
f32 baseline; padding to the chunk grid plus the piggybacked scale slot
cost the remaining fraction).

The CPU pipeline executes collectives inline, so the TIMES validate the
harness only; the HLO census is the product on this mesh.  Run the same
sweep on a multi-chip slice (tools/multichip_day1.sh COMPRESSION leg)
for the bandwidth measurement.

    python benchmarks/bench_compression.py --buckets 1,4
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

# HLO result-dtype -> wire bytes per element
_ITEMSIZE = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1,
             "f8e4m3fn": 1, "f8e5m2": 1}

# sweep axes: label -> bucket_compressors argument for fsdp_init
_COMPRESSORS = ["none", "none:bfloat16", "int8", "fp8"]


def _bucket_compressors(label):
    if label == "none":
        return None
    if label.startswith("none:"):
        from chainermn_tpu.compression import NoCompression
        return NoCompression(wire_dtype=label.split(":", 1)[1])
    return label  # registry name (int8 / fp8)


def collective_census(compiled_hlo: str) -> dict:
    """Collective counts plus summed reduce-scatter OPERAND bytes (the
    wire payload), parsed from the result dtype/shape of each
    reduce-scatter line: ``... = s8[512]{0} reduce-scatter(...)`` on a
    W-way mesh moves W x prod(shape) x itemsize input bytes."""
    gathers = len(re.findall(r"all-gather(?:-start)?\(", compiled_hlo))
    rs = re.findall(
        r"=\s*([a-z0-9]+)\[([\d,]*)\]\S*\s+reduce-scatter(?:-start)?\(",
        compiled_hlo)
    wire = 0
    dtypes = set()
    for dt, shape in rs:
        n = 1
        for d in shape.split(","):
            if d:
                n *= int(d)
        wire += n * _ITEMSIZE.get(dt, 4)
        dtypes.add(dt)
    return {"all_gathers": gathers, "reduce_scatters": len(rs),
            "rs_out_bytes": wire, "rs_dtypes": sorted(dtypes)}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--compressors", default=",".join(_COMPRESSORS),
                        help="comma-separated sweep: none, none:<dtype>, "
                             "int8, fp8")
    parser.add_argument("--buckets", default="1,4",
                        help="comma-separated num_buckets sweep")
    parser.add_argument("--prefetch", type=int, default=0,
                        help="prefetch depth (barrier census must match "
                             "the uncompressed schedule at this depth)")
    parser.add_argument("--layers", type=int, default=8)
    parser.add_argument("--width", type=int, default=256)
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--min-ratio", type=float, default=3.5,
                        help="required int8-vs-f32 reduce-scatter wire "
                             "shrink factor")
    parser.add_argument("--no-assert", action="store_true",
                        help="report the census without asserting it")
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
    from chainermn_tpu.parallel.fsdp import fsdp_init, make_fsdp_train_step
    from chainermn_tpu.training import put_global_batch
    from chainermn_tpu.utils.cpu_mesh import ensure_device_count

    from bench_fsdp_overlap import expected_barriers

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
    compressors = [c.strip() for c in args.compressors.split(",") if c]
    results = []
    for K in [int(b) for b in args.buckets.split(",")]:
        base = None  # the uncompressed census this K is held to
        for label in compressors:
            state, meta = fsdp_init(
                comm, params, optax.adam(1e-3), num_buckets=K,
                bucket_compressors=_bucket_compressors(label))
            step = make_fsdp_train_step(
                comm, loss_fn, optax.adam(1e-3), meta, donate=False,
                prefetch=args.prefetch)
            lowered = step.lower(state, batch) if hasattr(step, "lower") \
                else jax.jit(step).lower(state, batch)
            n_bar = lowered.as_text().count("stablehlo.optimization_barrier")
            census = collective_census(lowered.compile().as_text())
            if label == "none":
                base = dict(census, barriers=n_bar)
            want_bar = expected_barriers(meta.num_buckets, args.prefetch)
            ratio = (base["rs_out_bytes"] / census["rs_out_bytes"]
                     if base and census["rs_out_bytes"] else None)
            ok = (census["all_gathers"] == meta.num_buckets
                  and census["reduce_scatters"] == meta.num_buckets
                  and n_bar == want_bar)
            if base is not None:
                # compression must not change the collective schedule
                ok = ok and (
                    census["all_gathers"] == base["all_gathers"]
                    and census["reduce_scatters"] == base["reduce_scatters"]
                    and n_bar == base["barriers"])
            if label == "int8" and ratio is not None:
                ok = ok and ratio >= args.min_ratio
            if not args.no_assert:
                assert ok, (
                    f"wire census mismatch at compressor={label} "
                    f"num_buckets={K}: {census} barriers={n_bar} "
                    f"ratio={ratio} (expected {meta.num_buckets} gathers/"
                    f"scatters, {want_bar} barriers, int8 ratio >= "
                    f"{args.min_ratio}, schedule identical to "
                    f"uncompressed {base})")
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
            row = {"compressor": label, "num_buckets": meta.num_buckets,
                   "prefetch": args.prefetch, "devices": comm.size,
                   "payload_mib": round(payload / (1 << 20), 3),
                   "step_ms": round(dt * 1e3, 3),
                   "all_gathers": census["all_gathers"],
                   "reduce_scatters": census["reduce_scatters"],
                   "barriers": n_bar,
                   "rs_wire_bytes": census["rs_out_bytes"] * comm.size,
                   "rs_dtypes": ",".join(census["rs_dtypes"]),
                   "wire_ratio_vs_f32": round(ratio, 3) if ratio else None,
                   "census_ok": ok,
                   "backend": jax.default_backend()}
            results.append(row)
            if args.metrics:
                from chainermn_tpu.observability import append_jsonl

                append_jsonl(args.metrics,
                             dict(row, kind="bench_compression",
                                  ts=time.time()))
            if args.json:
                print(json.dumps(row), flush=True)
            else:
                print(f"K={meta.num_buckets} {label}: {row['step_ms']} ms, "
                      f"{census['all_gathers']}g/"
                      f"{census['reduce_scatters']}rs/{n_bar}bar, "
                      f"wire {row['rs_dtypes']} "
                      f"ratio={row['wire_ratio_vs_f32']} "
                      f"({'ok' if ok else 'MISMATCH'})", file=sys.stderr)
    if sync_each:
        print("note: CPU pipeline executes collectives inline — times "
              "validate the harness only; measure bandwidth on real chips "
              "(tools/multichip_day1.sh)", file=sys.stderr)
    return results


if __name__ == "__main__":
    main()

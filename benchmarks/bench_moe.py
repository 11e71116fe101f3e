#!/usr/bin/env python
"""MoE benchmark — the all-to-all plan sweep and the matched-loss leg.

Two modes:

``--sweep OUT.json`` times every candidate all-to-all plan
(``planner.candidate_plans(op="all-to-all")``: flat, hierarchical
ICI+DCN, narrow-DCN-wire, striped) across a payload ladder on the
(inter, intra) device grid and emits ``allreduce_sweep/v1`` rows — the
same schema the autotuner consumes, so ``tools/perf_gate.py --moe``
builds the MoE dispatch plan table from it.  ``--link-gbps ici=X,dcn=Y``
adds the per-link cost model's predicted wire time
(``planner.plan_modeled_time_s``) to each measured row so hierarchical
and narrow-wire candidates are priced on the heterogeneous links they
exist for (raw timings kept in ``us_measured``).  The artifact carries a
per-size DCN table: ``dcn_largest.bf16_dcn_bytes`` feeds the
``moe_alltoall_dcn_bytes`` perf budget (direction: lower).

``--out OUT.json`` (default mode) trains a FLOP-matched pair on the
8-way mesh: an MoE TransformerLM (E experts, top_k=1 — per-token MLP
compute identical to dense, E x the MLP parameters) against its dense
twin, on a mixture task (each sequence follows one of several affine
token maps) where expert specialization is the capacity that matters.
The artifact (``moe_bench/v1``) records both loss curves;
``perf_gate --moe --moe-bench`` requires MoE to land at or below the
dense baseline.

    python benchmarks/bench_moe.py --sweep ALLTOALL_SWEEP.json \
        --intra-size 4 --link-gbps ici=0.2,dcn=0.01
    python benchmarks/bench_moe.py --out MOE_BENCH.json
"""

import argparse
import json
import os
import sys
import time

import numpy as np

# Runnable from a fresh clone without `pip install -e .`.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SWEEP_SIZES_KB = "64,1024,4096"
MOE_BENCH_SCHEMA = "moe_bench/v1"


def _parse_link_gbps(spec):
    from benchmarks.bench_allreduce import _parse_link_gbps as parse

    return parse(spec)


def _time(fn, x, iters, warmup):
    """Seconds/iteration of ``fn(x)`` (same clock discipline as
    bench_allreduce._time_spmd: per-iteration sync on CPU, value fence)."""
    import jax

    out = fn(x)
    jax.block_until_ready(out)
    for _ in range(warmup):
        jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(x))
    return (time.perf_counter() - t0) / iters


def _sweep(args):
    """--sweep: time every candidate all-to-all plan across the payload
    ladder; rows are ``allreduce_sweep/v1`` (autotuner-compatible)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    import chainermn_tpu
    from chainermn_tpu.planner import (
        SWEEP_SCHEMA, candidate_plans, execute_alltoall, load_plan,
        plan_dcn_bytes, plan_modeled_time_s)

    kwargs = {}
    if args.intra_size is not None:
        kwargs["intra_size"] = args.intra_size
    comm = chainermn_tpu.create_communicator("naive", **kwargs)
    topo = comm.plan_topology()
    mesh = comm.mesh
    names = tuple(n for n, _ in topo.axes)
    axis_arg = names if len(names) > 1 else names[0]
    spec = P(names if len(names) > 1 else names[0])
    p = topo.size
    stripe_ratios = tuple(
        float(s) for s in args.stripe_ratios.split(",")
    ) if args.stripe_ratios else ()
    link_gbps = _parse_link_gbps(args.link_gbps) if args.link_gbps else None
    plans = list(candidate_plans(topo, op="all-to-all",
                                 stripe_ratios=stripe_ratios))
    if args.plan:
        plans.append(load_plan(args.plan))
    rows = []
    dcn_summary = []
    for kb in (float(s) for s in args.sweep_sizes_kb.split(",")):
        # the exchanged unit is the per-device [P, m] block buffer
        itemsize = np.dtype(args.dtype).itemsize
        m = max(int(kb * 1024 / itemsize) // p, 1)
        payload = p * m * itemsize
        # values in [0, 1): inside every narrow wire's range (fp8 e4m3
        # saturates at 448 — magnitude scaling is the CALLER's contract)
        x = jax.random.uniform(jax.random.key(0), (p * p, m),
                               dtype=args.dtype)

        def raw(b):
            return lax.all_to_all(b, axis_arg, 0, 0, tiled=True)

        want = np.asarray(jax.jit(jax.shard_map(
            raw, mesh=mesh, in_specs=spec, out_specs=spec,
            check_vma=False))(x))
        size_dcn = {}
        for plan in plans:
            def body(b, plan=plan):
                return execute_alltoall(plan, topo, b)

            fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                                   out_specs=spec, check_vma=False))
            got = np.asarray(fn(x))      # compile + correctness
            narrow = any(st.wire_dtype not in (None, args.dtype)
                         for grp in plan.stage_groups()
                         for st in grp.stages)
            if narrow:
                # narrow wires round (bf16: ~2^-8 relative, fp8: ~2^-2)
                np.testing.assert_allclose(got, want, atol=0.12)
            else:
                np.testing.assert_array_equal(got, want)
            dt = _time(fn, x, args.iters, args.warmup)
            dcn_bytes = plan_dcn_bytes(plan, topo, payload,
                                       dtype=args.dtype)
            us = dt * 1e6
            row = {"topology": topo.key(), "dtype": args.dtype,
                   "bytes": payload, "plan": plan.name,
                   "us": round(us, 3),
                   "dcn_bytes": round(dcn_bytes, 1),
                   "plan_spec": plan.to_dict()}
            if link_gbps:
                # selection metric = measurement + per-link modeled wire
                # time — on a CPU mesh the modeled term is what makes
                # the hierarchical/narrow candidates win the cells they
                # exist for
                modeled = plan_modeled_time_s(plan, topo, payload,
                                              link_gbps,
                                              dtype=args.dtype)
                row["us_measured"] = row["us"]
                row["us_modeled_wire"] = round(modeled * 1e6, 3)
                row["us"] = round(us + modeled * 1e6, 3)
            size_dcn[plan.name] = dcn_bytes
            rows.append(row)
            print(f"sweep {plan.name:>28} @ {payload:>10} B: "
                  f"{row['us']} us, dcn {row['dcn_bytes']} B",
                  file=sys.stderr)
        flat = size_dcn.get("alltoall_flat")
        bf16 = size_dcn.get("alltoall_hier_bfloat16_dcn")
        if flat and bf16:
            narrow = {n: b for n, b in size_dcn.items()
                      if n.startswith("alltoall_hier") and
                      n.endswith("_dcn")}
            best = min(narrow, key=lambda n: narrow[n])
            dcn_summary.append({
                "bytes": payload,
                "flat_dcn_bytes": round(flat, 1),
                "bf16_dcn_bytes": round(bf16, 1),
                "bf16_shrink_x": round(flat / bf16, 2),
                "best_narrow_plan": best,
                "best_narrow_dcn_bytes": round(narrow[best], 1)})
    doc = {"schema": SWEEP_SCHEMA,
           "collective": "all-to-all",
           "backend": jax.default_backend(),
           "n_devices": p,
           "topology": topo.key(),
           "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "rows": rows}
    if link_gbps:
        doc["link_gbps"] = link_gbps
    if stripe_ratios:
        doc["stripe_ratios"] = list(stripe_ratios)
    if dcn_summary:
        doc["dcn"] = dcn_summary
        # largest swept size, under the stable dotted path the
        # moe_alltoall_dcn_bytes perf budget digs into
        doc["dcn_largest"] = max(dcn_summary, key=lambda r: r["bytes"])
    from chainermn_tpu.observability.ledger import stamp_envelope
    stamp_envelope(doc)
    with open(args.sweep, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps({"rows": len(rows), "plans": len(plans),
                      "topology": topo.key()}), flush=True)
    return doc


def _mixture_batch(key, batch, seq, vocab, n_modes):
    """Token sequences, each following one of ``n_modes`` affine maps
    ``t_{i+1} = (a_m * t_i + c_m) mod vocab`` — next-token prediction is
    easy WITHIN a mode but the modes conflict, so per-mode expert
    capacity (not per-token compute) is what lowers the loss."""
    import jax
    import jax.numpy as jnp

    k1, k2, k3 = jax.random.split(key, 3)
    mode = jax.random.randint(k1, (batch,), 0, n_modes)
    a = 2 * jax.random.randint(k2, (n_modes,), 1, vocab // 2) + 1
    c = jax.random.randint(k2, (n_modes,), 0, vocab)
    t0 = jax.random.randint(k3, (batch,), 0, vocab)

    def step(t, _):
        nxt = (a[mode] * t + c[mode]) % vocab
        return nxt, nxt

    _, toks = jax.lax.scan(step, t0, None, length=seq - 1)
    return jnp.concatenate([t0[None], toks]).T.astype(jnp.int32)


def _train(model, toks_stream, steps, lr, aux_weight, mesh, axis):
    """SGD-with-momentum training loop over the sharded token stream;
    returns the per-step loss curve (pmean'd, so globally synchronous)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    is_moe = bool(model.moe_experts)

    def fwd(pp, tk):
        if is_moe:
            logits, mut = model.apply(pp, tk, mutable=["moe_stats"])
            aux = sum(jnp.sum(v[0])
                      for blk in mut["moe_stats"].values()
                      for k, v in blk.items() if k == "aux_loss")
        else:
            logits, aux = model.apply(pp, tk), 0.0
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        ce = -jnp.mean(jnp.take_along_axis(
            logp, tk[:, 1:, None], axis=-1))
        return jax.lax.pmean(ce + aux_weight * aux, axis), \
            jax.lax.pmean(ce, axis)

    def loss_fn(pp, tk):
        return jax.shard_map(fwd, mesh=mesh, in_specs=(P(), P(axis)),
                         out_specs=(P(), P()), check_vma=False)(pp, tk)

    params = jax.jit(jax.shard_map(
        lambda tk: model.init(jax.random.key(0), tk), mesh=mesh,
        in_specs=P(axis), out_specs=P(),
        check_vma=False))(toks_stream(0))
    mom = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def step(pp, mm, tk):
        (_, ce), g = jax.value_and_grad(loss_fn, has_aux=True)(pp, tk)
        mm = jax.tree.map(lambda m, d: 0.9 * m + d, mm, g)
        pp = jax.tree.map(lambda w, m: w - lr * m, pp, mm)
        return pp, mm, ce

    losses = []
    for i in range(steps):
        params, mom, ce = step(params, mom, toks_stream(i))
        losses.append(float(ce))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(params))
    return losses, n_params


def _moe_bench(args):
    """--out: the matched-loss leg — MoE (E experts, top_k=1, same
    per-token MLP FLOPs as dense) vs the dense twin on the mixture task."""
    import jax
    from jax.sharding import Mesh

    from chainermn_tpu.models.transformer import TransformerLM

    devs = jax.devices()[:args.devices]
    mesh = Mesh(np.array(devs), ("ep",))
    vocab, seq = args.vocab, args.seq
    batch = args.batch_per_device * len(devs)
    data_key = jax.random.key(args.seed)

    def toks_stream(i):
        return _mixture_batch(jax.random.fold_in(data_key, i), batch,
                              seq, vocab, args.modes)

    common = dict(vocab=vocab, d_model=args.d_model, n_layers=args.layers,
                  n_heads=args.heads, max_len=seq,
                  attention_impl="xla")
    moe = TransformerLM(moe_experts=args.experts, moe_top_k=1,
                        moe_axis="ep", **common)
    dense = TransformerLM(**common)
    t0 = time.perf_counter()
    moe_losses, moe_params = _train(moe, toks_stream, args.steps,
                                    args.lr, args.aux_weight, mesh, "ep")
    dense_losses, dense_params = _train(dense, toks_stream, args.steps,
                                        args.lr, 0.0, mesh, "ep")
    tail = max(args.steps // 8, 1)       # tail mean, not one lucky step
    moe_final = float(np.mean(moe_losses[-tail:]))
    dense_final = float(np.mean(dense_losses[-tail:]))
    doc = {"schema": MOE_BENCH_SCHEMA,
           "backend": jax.default_backend(),
           "n_devices": len(devs),
           "task": {"kind": "affine_mixture", "vocab": vocab, "seq": seq,
                    "modes": args.modes, "batch": batch,
                    "steps": args.steps},
           "flop_matched": {"moe_top_k": 1, "experts": args.experts,
                            "comment": "top_k=1 routes each token "
                            "through exactly one expert of the same "
                            "hidden width as the dense MLP — identical "
                            "per-token MLP FLOPs, E x the parameters"},
           "moe": {"losses": [round(l, 4) for l in moe_losses],
                   "final_loss": round(moe_final, 4),
                   "n_params": moe_params},
           "dense": {"losses": [round(l, 4) for l in dense_losses],
                     "final_loss": round(dense_final, 4),
                     "n_params": dense_params},
           "moe_at_or_below_dense": moe_final <= dense_final,
           "elapsed_s": round(time.perf_counter() - t0, 1),
           "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    from chainermn_tpu.observability.ledger import stamp_envelope
    stamp_envelope(doc)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps({"moe_final": doc["moe"]["final_loss"],
                      "dense_final": doc["dense"]["final_loss"],
                      "moe_at_or_below_dense":
                          doc["moe_at_or_below_dense"]}), flush=True)
    return doc


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--sweep", metavar="OUT.json", default=None,
                        help="all-to-all plan sweep mode (see module doc)")
    parser.add_argument("--sweep-sizes-kb", default=SWEEP_SIZES_KB,
                        help="comma-separated per-device payload sizes in "
                             "KiB for --sweep")
    parser.add_argument("--dtype", default="float32")
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--intra-size", type=int, default=None)
    parser.add_argument("--link-gbps", default=None, metavar="ici=X,dcn=Y",
                        help="add the per-link modeled wire time to each "
                             "swept row (raw timing kept in us_measured)")
    parser.add_argument("--stripe-ratios", default=None,
                        help="comma-separated ICI-stripe ratios to add "
                             "striped all-to-all candidates to the sweep")
    parser.add_argument("--plan", metavar="PLAN.json", default=None,
                        help="also sweep this explicit plan file")
    parser.add_argument("--out", metavar="OUT.json", default=None,
                        help="matched-loss mode: write the moe_bench/v1 "
                             "artifact here")
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--vocab", type=int, default=32)
    parser.add_argument("--seq", type=int, default=16)
    parser.add_argument("--d-model", type=int, default=16)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--heads", type=int, default=2)
    parser.add_argument("--experts", type=int, default=8)
    parser.add_argument("--modes", type=int, default=8)
    parser.add_argument("--batch-per-device", type=int, default=8)
    parser.add_argument("--steps", type=int, default=150)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--aux-weight", type=float, default=1e-2)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    from chainermn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    if bool(args.sweep) == bool(args.out):
        parser.error("pass exactly one of --sweep or --out")
    if args.sweep:
        return _sweep(args)
    return _moe_bench(args)


if __name__ == "__main__":
    main()

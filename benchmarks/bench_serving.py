#!/usr/bin/env python
"""Serving benchmark: continuous batching, prefix caching, speculative
decoding, and the multi-replica fleet — one artifact.

Four sections, each its own seeded workload:

* ``continuous`` / ``static`` — the original policy A/B: ONE open-loop
  trace replayed through both admission policies; the run fails unless
  continuous beats static on throughput (the v1 acceptance bar).
* ``prefix`` — a system-prompt-heavy closed-loop burst replayed with the
  prefix cache OFF then ON (``--prefix-share`` controls how much of each
  prompt is the shared prefix).  ``prefix.speedup`` is the
  cached/uncached throughput ratio the ``serving_prefix_cache_speedup``
  budget holds at >= 1.3.
* ``spec`` — a decode-heavy burst through the draft+verify fused step
  (``--spec-k`` draft tokens, truncated-layer draft sharing the target's
  bottom layers).  ``spec.accept_tokens_per_step`` is tokens landed per
  verify pass; the ``serving_spec_accept_tokens_per_step`` budget holds
  it > 1.0 — speculation must beat one-token-per-step decode.
* ``fleet`` — ``--replicas N`` engine replicas behind the session-affine
  :class:`~chainermn_tpu.serving.Router`: an open-loop sessionful trace,
  reporting p50/p99 TTFT and per-token percentiles plus the affinity
  check (every session served by exactly one replica).

Wall-clock is host-side only (arrival bookkeeping and latency stamps);
nothing traced reads time.  On the 8-device CPU mesh this validates the
harness and the scheduling/caching wins; on a TPU slice the same command
measures real serving throughput (``--tp`` shards the model over ICI).

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmarks/bench_serving.py --requests 16 --spec-k 2 \
          --replicas 2 --out SERVING.json
"""

import argparse
import json
import os
import sys
import time

import numpy as np

# Runnable from a fresh clone without `pip install -e .`.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_trace(args):
    """The shared request trace: (arrival_offset_s, prompt, max_new)."""
    rng = np.random.default_rng(args.seed)
    gaps = rng.exponential(1.0 / args.rate, size=args.requests)
    arrivals = np.cumsum(gaps)
    trace = []
    for t in arrivals:
        n = int(rng.integers(args.min_prompt, args.max_prompt + 1))
        prompt = list(map(int, rng.integers(1, args.vocab, size=n)))
        # decode lengths vary per request (real traffic is heavy-tailed);
        # the spread is exactly what continuous batching exploits — a
        # static batch drains at the pace of its longest member
        max_new = int(rng.integers(1, args.max_new + 1))
        trace.append((float(t), prompt, max_new))
    return trace


def _pct(a, q):
    return float(np.percentile(a, q)) if len(a) else None


def _latency_block(comps):
    ttfts = [c.ttft for c in comps if c.token_times]
    per_token = []
    for c in comps:
        per_token.extend(np.diff(c.token_times))
    return {
        "ttft_s": {"mean": float(np.mean(ttfts)) if ttfts else None,
                   "p50": _pct(ttfts, 50), "p99": _pct(ttfts, 99)},
        "per_token_s": {"mean": float(np.mean(per_token))
                        if per_token else None,
                        "p50": _pct(per_token, 50),
                        "p99": _pct(per_token, 99)},
    }


def run_policy(policy, model, params, trace, args):
    from chainermn_tpu.serving import InferenceEngine, ServingConfig

    cfg = ServingConfig(page_size=args.page_size, num_pages=args.num_pages,
                        max_seqs=args.max_seqs,
                        chunk_tokens=args.chunk_tokens,
                        max_pages_per_seq=args.max_pages_per_seq,
                        policy=policy, tp_size=args.tp)
    eng = InferenceEngine(model, params, cfg)
    # warmup: compile the fused forward outside the timed window
    eng.submit(trace[0][1], max_new_tokens=1)
    eng.run_until_idle()
    eng.completions.clear()

    # Span seam (--metrics runs have observability on, so the engine
    # recorded serving_step/serving_forward spans): remember where the
    # ring stands so the summary below covers only the timed window.
    from chainermn_tpu.observability import flight_recorder as _flight
    fr = _flight.get_flight_recorder()
    seq0 = -1
    if fr is not None:
        evs = fr.snapshot()
        seq0 = evs[-1]["seq"] if evs else -1

    t0 = time.perf_counter()
    pending = list(trace)
    steps = 0
    while pending or not eng.idle():
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            off, prompt, max_new = pending.pop(0)
            eng.submit(prompt, max_new_tokens=max_new,
                       arrival=t0 + off)
        if eng.idle():
            time.sleep(0.001)   # open loop: wait for the next arrival
            continue
        eng.step()
        steps += 1
        if steps > args.max_steps:
            raise RuntimeError(
                f"[{policy}] still busy after {args.max_steps} steps")
    wall = time.perf_counter() - t0

    comps = eng.completions
    n_tokens = sum(len(c.tokens) for c in comps)
    spans = None
    if fr is not None:
        try:
            from chainermn_tpu.observability import span_summary
            spans = span_summary(fr.events_since(seq0), rank=0, k=3)
        except Exception:  # noqa: BLE001 — supplementary only
            spans = None
    return {
        "policy": policy,
        **({"span_summary": spans} if spans else {}),
        "requests": len(comps),
        "generated_tokens": n_tokens,
        "steps": steps,
        "wall_s": wall,
        "tokens_per_sec": n_tokens / wall,
        **_latency_block(comps),
    }


# ---- prefix caching ---------------------------------------------------------

def build_prefix_trace(args):
    """System-prompt-heavy burst: every prompt = shared prefix + unique
    tail (``--prefix-share`` of ``--prefix-prompt`` tokens shared)."""
    rng = np.random.default_rng(args.seed + 1)
    sys_len = int(args.prefix_share * args.prefix_prompt)
    sys_prompt = list(map(int, rng.integers(1, args.vocab, size=sys_len)))
    trace = []
    for _ in range(args.requests):
        tail = list(map(int, rng.integers(
            1, args.vocab, size=args.prefix_prompt - sys_len)))
        trace.append((sys_prompt + tail, args.prefix_max_new))
    return trace, sys_len


def _drain_burst(eng, trace, max_steps):
    """Closed-loop: submit the whole burst at t0, drain, time it."""
    t0 = time.perf_counter()
    for prompt, max_new in trace:
        eng.submit(prompt, max_new_tokens=max_new, arrival=t0)
    steps = 0
    while not eng.idle():
        eng.step()
        steps += 1
        if steps > max_steps:
            raise RuntimeError(f"still busy after {max_steps} steps")
    wall = time.perf_counter() - t0
    n_tokens = sum(len(c.tokens) for c in eng.completions)
    return wall, steps, n_tokens


def run_prefix(model, params, args):
    """The prefix-cache A/B: identical burst, cache off vs on."""
    from chainermn_tpu.serving import InferenceEngine, ServingConfig

    trace, sys_len = build_prefix_trace(args)
    warm_rng = np.random.default_rng(args.seed + 1000)
    warm = list(map(int, warm_rng.integers(1, args.vocab,
                                           size=args.prefix_prompt)))
    out = {}
    for label, cached in (("uncached", False), ("cached", True)):
        cfg = ServingConfig(
            page_size=args.page_size, num_pages=args.num_pages,
            max_seqs=args.max_seqs, chunk_tokens=args.chunk_tokens,
            max_pages_per_seq=args.max_pages_per_seq, tp_size=args.tp,
            prefix_cache=cached)
        eng = InferenceEngine(model, params, cfg)
        # warmup compiles with a DISJOINT prompt so the cached run's
        # first request still pays its own cold prefill
        eng.submit(warm, max_new_tokens=1)
        eng.run_until_idle()
        eng.completions.clear()
        wall, steps, n_tokens = _drain_burst(eng, trace, args.max_steps)
        out[label] = {"wall_s": wall, "steps": steps,
                      "generated_tokens": n_tokens,
                      "tokens_per_sec": n_tokens / wall,
                      **({"stats": eng.scheduler.prefix_stats()}
                         if cached else {})}
    out["shared_prefix_tokens"] = sys_len
    out["speedup"] = (out["cached"]["tokens_per_sec"]
                      / out["uncached"]["tokens_per_sec"])
    return out


# ---- speculative decoding ---------------------------------------------------

def truncated_draft(model, params, n_draft_layers=1):
    """The bench's draft model: the target's bottom ``n_draft_layers``
    layers plus its embeddings/norm/head — correlated with the target
    (real accepts AND real rejects) at a fraction of the per-step cost,
    with no separate training."""
    from chainermn_tpu.models.transformer import TransformerLM

    dm = TransformerLM(vocab=model.vocab, d_model=model.d_model,
                       n_layers=n_draft_layers, n_heads=model.n_heads,
                       max_len=model.max_len, attention_impl="xla",
                       n_kv_heads=model.n_kv_heads)
    p = params["params"]
    dp = {"tok_emb": p["tok_emb"], "pos_emb": p["pos_emb"],
          "ln_f": p["ln_f"], "head": p["head"]}
    for i in range(n_draft_layers):
        dp[f"block_{i}"] = p[f"block_{i}"]
    return dm, {"params": dp}


def build_spec_trace(args):
    """Decode-heavy burst: short prompts, long fixed generations."""
    rng = np.random.default_rng(args.seed + 2)
    trace = []
    for _ in range(args.requests):
        n = int(rng.integers(args.min_prompt, args.max_prompt + 1))
        prompt = list(map(int, rng.integers(1, args.vocab, size=n)))
        trace.append((prompt, args.spec_max_new))
    return trace


def run_spec(model, params, args):
    """Vanilla vs draft+verify over the same decode-heavy burst."""
    from chainermn_tpu.serving import InferenceEngine, ServingConfig

    trace = build_spec_trace(args)
    dmodel, dparams = truncated_draft(model, params)
    base = dict(page_size=args.page_size, num_pages=args.num_pages,
                max_seqs=args.max_seqs, chunk_tokens=args.chunk_tokens,
                max_pages_per_seq=args.max_pages_per_seq, tp_size=args.tp)
    out = {}
    # vanilla baseline
    eng = InferenceEngine(model, params, ServingConfig(**base))
    eng.submit(trace[0][0], max_new_tokens=1)
    eng.run_until_idle()
    eng.completions.clear()
    wall, steps, n_tokens = _drain_burst(eng, trace, args.max_steps)
    out["vanilla"] = {"wall_s": wall, "steps": steps,
                      "generated_tokens": n_tokens,
                      "tokens_per_sec": n_tokens / wall}
    # draft + verify
    eng = InferenceEngine(model, params,
                          ServingConfig(**base, spec_k=args.spec_k),
                          draft_model=dmodel, draft_params=dparams)
    eng.submit(trace[0][0], max_new_tokens=1)
    eng.run_until_idle()
    eng.completions.clear()
    t0 = time.perf_counter()
    for prompt, max_new in trace:
        eng.submit(prompt, max_new_tokens=max_new, arrival=t0)
    steps = rows = proposed = accepted = out_tokens = 0
    while not eng.idle():
        res = eng.step()
        steps += 1
        if res.spec is not None:
            rows += res.spec["rows"]
            proposed += res.spec["proposed"]
            accepted += res.spec["accepted"]
            out_tokens += res.spec["out_tokens"]
        if steps > args.max_steps:
            raise RuntimeError(f"spec still busy after {steps} steps")
    wall = time.perf_counter() - t0
    n_tokens = sum(len(c.tokens) for c in eng.completions)
    out["spec"] = {"wall_s": wall, "steps": steps,
                   "generated_tokens": n_tokens,
                   "tokens_per_sec": n_tokens / wall,
                   "verify_rows": rows, "proposed_tokens": proposed,
                   "accepted_tokens": accepted,
                   "out_tokens": out_tokens}
    out["k"] = args.spec_k
    out["draft_layers"] = 1
    out["acceptance_rate"] = accepted / proposed if proposed else None
    # the budgeted number: tokens landed per verify pass (a+1 per row);
    # > 1.0 means speculation beats one-token-per-step decode
    out["accept_tokens_per_step"] = out_tokens / rows if rows else None
    out["speedup"] = (out["spec"]["tokens_per_sec"]
                      / out["vanilla"]["tokens_per_sec"])
    return out


# ---- multi-replica fleet ----------------------------------------------------

def run_fleet(model, params, trace, args):
    """Open-loop sessionful trace over ``--replicas`` engines behind the
    session-affine router (engines run the prefix cache: affinity is
    what makes the per-replica tries pay).  Every turn of a session
    opens with that session's own system prefix, so follow-up turns hit
    the pinned replica's trie — the ``prefix_hits`` field is the
    affinity payoff on the wire."""
    from chainermn_tpu.serving import (InferenceEngine, Router,
                                       ServingConfig)

    cfg = ServingConfig(page_size=args.page_size, num_pages=args.num_pages,
                        max_seqs=args.max_seqs,
                        chunk_tokens=args.chunk_tokens,
                        max_pages_per_seq=args.max_pages_per_seq,
                        tp_size=args.tp, prefix_cache=True)
    engines = [InferenceEngine(model, params, cfg)
               for _ in range(args.replicas)]
    for eng in engines:    # compile outside the timed window
        eng.submit(trace[0][1], max_new_tokens=1)
        eng.run_until_idle()
        eng.completions.clear()
    router = Router(engines)
    n_sessions = max(1, args.requests // 3)
    rng = np.random.default_rng(args.seed + 3)
    sys_len = 2 * args.page_size        # two full shared pages / session
    sys_prompts = [list(map(int, rng.integers(1, args.vocab,
                                              size=sys_len)))
                   for _ in range(n_sessions)]

    t0 = time.perf_counter()
    pending = [(off, sys_prompts[i % n_sessions] + prompt, max_new,
                f"s{i % n_sessions}")
               for i, (off, prompt, max_new) in enumerate(trace)]
    steps = 0
    while pending or not router.idle():
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            off, prompt, max_new, sess = pending.pop(0)
            router.submit(prompt, max_new, session=sess, arrival=t0 + off)
        if router.idle():
            time.sleep(0.001)
            continue
        router.step()
        steps += 1
        if steps > args.max_steps:
            raise RuntimeError(f"fleet still busy after {steps} steps")
    wall = time.perf_counter() - t0

    comps = [c for _, _, c in router.completions]
    n_tokens = sum(len(c.tokens) for c in comps)
    by_sess = {}
    per_replica = [0] * args.replicas
    for rid, sess, rep in router.dispatch_log:
        by_sess.setdefault(sess, set()).add(rep)
        per_replica[rep] += 1
    return {
        "replicas": args.replicas,
        "sessions": n_sessions,
        "requests": len(comps),
        "generated_tokens": n_tokens,
        "steps": steps,
        "wall_s": wall,
        "tokens_per_sec": n_tokens / wall,
        "requests_per_replica": per_replica,
        "session_affinity_ok": all(len(r) == 1 for r in by_sess.values()),
        "prefix_hits": sum(e.scheduler.prefix_stats()["hits"]
                           for e in engines),
        **_latency_block(comps),
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--requests", type=int, default=16)
    parser.add_argument("--rate", type=float, default=200.0,
                        help="open-loop arrival rate (requests/sec); the "
                             "default saturates the CPU-mesh toy model "
                             "so the run measures scheduling, not idle "
                             "arrival gaps")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--min-prompt", type=int, default=4)
    parser.add_argument("--max-prompt", type=int, default=24)
    parser.add_argument("--max-new", type=int, default=24)
    parser.add_argument("--vocab", type=int, default=256)
    parser.add_argument("--d-model", type=int, default=64)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--n-heads", type=int, default=4)
    parser.add_argument("--max-seqs", type=int, default=4)
    parser.add_argument("--chunk-tokens", type=int, default=8)
    parser.add_argument("--page-size", type=int, default=8)
    parser.add_argument("--num-pages", type=int, default=64)
    parser.add_argument("--max-pages-per-seq", type=int, default=8)
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel ways (devices)")
    parser.add_argument("--prefix-share", type=float, default=0.9,
                        help="fraction of each prefix-section prompt "
                             "that is the shared system prefix "
                             "(0 skips the prefix section)")
    parser.add_argument("--prefix-prompt", type=int, default=48,
                        help="prefix-section prompt length (tokens)")
    parser.add_argument("--prefix-max-new", type=int, default=4,
                        help="prefix-section decode length (short: the "
                             "section measures prefill savings)")
    parser.add_argument("--spec-k", type=int, default=0,
                        help="draft tokens per decode step (0 skips the "
                             "spec section)")
    parser.add_argument("--spec-max-new", type=int, default=16,
                        help="spec-section decode length (long: the "
                             "section measures decode acceleration)")
    parser.add_argument("--replicas", type=int, default=1,
                        help="router fleet size (>1 adds the fleet "
                             "section)")
    parser.add_argument("--max-steps", type=int, default=100000)
    parser.add_argument("--out", default=None, metavar="PATH",
                        help="write the bench_serving/v2 JSON artifact "
                             "(tools/perf_gate.py --budgets reads "
                             "continuous.tokens_per_sec, --serving gates "
                             "prefix.speedup and "
                             "spec.accept_tokens_per_step)")
    parser.add_argument("--metrics", default=None, metavar="PATH",
                        help="append records + a registry snapshot to "
                             "this metrics JSONL (render with "
                             "tools/obs_report.py --serving)")
    args = parser.parse_args()

    from chainermn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()

    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models.transformer import TransformerLM

    if args.metrics:
        from chainermn_tpu import observability as obs
        obs.enable()

    max_ctx = args.max_pages_per_seq * args.page_size
    if args.prefix_share > 0 and \
            args.prefix_prompt + args.prefix_max_new > max_ctx:
        parser.error(f"--prefix-prompt + --prefix-max-new exceeds the "
                     f"cache reach ({max_ctx} tokens)")
    model = TransformerLM(vocab=args.vocab, d_model=args.d_model,
                          n_layers=args.n_layers, n_heads=args.n_heads,
                          max_len=max_ctx + args.spec_k,
                          attention_impl="xla")
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 4), jnp.int32))
    trace = build_trace(args)

    results = {p: run_policy(p, model, params, trace, args)
               for p in ("continuous", "static")}
    speedup = (results["continuous"]["tokens_per_sec"]
               / results["static"]["tokens_per_sec"])
    report = {
        "schema": "bench_serving/v2",
        "config": {k: v for k, v in vars(args).items()
                   if k not in ("out", "metrics")},
        "devices": jax.device_count(),
        "continuous": results["continuous"],
        "static": results["static"],
        "speedup": speedup,
    }
    if args.prefix_share > 0:
        report["prefix"] = run_prefix(model, params, args)
    if args.spec_k > 0:
        report["spec"] = run_spec(model, params, args)
    if args.replicas > 1:
        report["fleet"] = run_fleet(model, params, trace, args)
    from chainermn_tpu.observability.ledger import stamp_envelope
    stamp_envelope(report, n_devices=report["devices"],
                   backend=jax.default_backend())
    print(json.dumps(report, indent=1))
    if args.out:
        from chainermn_tpu.observability.sinks import atomic_write_json
        atomic_write_json(args.out, report)
    if args.metrics:
        from chainermn_tpu.observability import get_registry
        from chainermn_tpu.observability.sinks import (append_jsonl,
                                                       write_snapshot_jsonl)
        for policy in ("continuous", "static"):
            append_jsonl(args.metrics, {"kind": "bench_serving",
                                        **results[policy]})
        if "prefix" in report:
            append_jsonl(args.metrics, {"kind": "bench_serving_prefix",
                                        **report["prefix"]})
        if "spec" in report:
            append_jsonl(args.metrics, {"kind": "bench_serving_spec",
                                        **report["spec"]})
        if "fleet" in report:
            append_jsonl(args.metrics, {"kind": "bench_serving_fleet",
                                        **report["fleet"]})
        write_snapshot_jsonl(args.metrics, get_registry().snapshot())

    rc = 0
    if speedup <= 1.0:
        print(f"FAIL: continuous batching did not beat static "
              f"({results['continuous']['tokens_per_sec']:.1f} vs "
              f"{results['static']['tokens_per_sec']:.1f} tok/s)",
              file=sys.stderr)
        rc = 1
    else:
        print(f"continuous beats static: {speedup:.2f}x "
              f"({results['continuous']['tokens_per_sec']:.1f} vs "
              f"{results['static']['tokens_per_sec']:.1f} tok/s)")
    if "prefix" in report:
        print(f"prefix cache: {report['prefix']['speedup']:.2f}x "
              f"({report['prefix']['cached']['tokens_per_sec']:.1f} vs "
              f"{report['prefix']['uncached']['tokens_per_sec']:.1f} "
              f"tok/s)")
    if "spec" in report:
        print(f"spec decode k={args.spec_k}: "
              f"{report['spec']['accept_tokens_per_step']:.2f} "
              f"tokens/verify pass "
              f"(acceptance {report['spec']['acceptance_rate']:.2f})")
    if "fleet" in report:
        f = report["fleet"]
        print(f"fleet x{f['replicas']}: {f['tokens_per_sec']:.1f} tok/s, "
              f"ttft p50={f['ttft_s']['p50']:.3f}s "
              f"p99={f['ttft_s']['p99']:.3f}s, affinity "
              f"{'ok' if f['session_affinity_ok'] else 'VIOLATED'}")
    return rc


if __name__ == "__main__":
    sys.exit(main())

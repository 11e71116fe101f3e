#!/usr/bin/env python
"""Decompose the ResNet-50 train step time on one chip.

Perf harness for the round-2 BN-statistics investigation (NEXT.md §1,
VERDICT round-1 "next #1").  Times variants of the b=256 ResNet-50 step
that surgically remove one cost at a time, so each feature's price is a
measured subtraction, not a guess from trace categories:

  full        — the resnet50-b256 cell's step (fwd+bwd+allreduce+update, bf16)
  nostats     — BatchNorm normalizes with CONSTANT mean/var (stat
                reductions + their backward vanish; everything else,
                including the normalize/scale elementwise math, stays)
  nonorm      — BatchNorm replaced by identity (all BN work vanishes)
  fwdonly     — forward pass only (no grad)
  fwdbwd      — fwd+bwd only (no allreduce/update)
  remat       — full step with every residual block rematerialized
                (nn.remat): prices whether trading HBM activation traffic
                for recompute moves the memory-bound stages
  fusednorm   — full step with every BatchNorm(+ReLU) boundary running
                the fused Pallas kernels (ops.FusedBatchNormAct): the
                round-9 countermeasure for the BN-boundary HBM traffic
                that rounds 2-5 pinned as the deficit

Run on the real chip:  python benchmarks/bench_resnet_probe.py
Each variant reports ms/step and img/s; deltas vs `full` are printed.
``--json``/``--out`` additionally emit a ``resnet_probe/v1`` artifact
(committed as RESNET_PROBE_r09.json) carrying the variant rows plus a
deterministic ``traffic`` section — ``ops.resnet_bn_traffic_bytes`` at
the canonical b=256/224 shapes — which the ``resnet_bn_traffic_bytes``
perf-gate budget reads (``traffic.fused_total_bytes``).  Timing rows off
TPU are marked ``smoke``; the traffic model is backend-independent.

``--stages`` switches to per-stage isolation mode: each ResNet-50 stage's
blocks run fwd+bwd alone on a synthetic activation (device-time ms +
TFLOP/s), plus a ``stage1_pad128`` row — the stage-1 shape widened from
64 to 128 channels, the MXU-lane-occupancy countermeasure (round-4 #2):
if 128-channel TFLOP/s ~= 2x the 64-channel rate, stage 1 is lane-bound
and padding could pay; if it only matches, the stage is at its memory
roofline and the 64-lane half-occupancy is not the binding constraint.

NOTE: nostats/nonorm change the numerics (loss is garbage) — they exist
only to price the memory traffic; they are never used for training.
"""

import argparse
import sys
import time
from functools import partial

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def time_step(step, args, steps, warmup):
    import jax

    for _ in range(warmup):
        out = step(*args)
    loss = out[-1]
    jax.block_until_ready(loss)
    float(np.asarray(loss))  # fence: value read (see SKILL.md timing gotcha)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = step(*args)
    loss = out[-1]
    jax.block_until_ready(loss)
    float(np.asarray(loss))
    return (time.perf_counter() - t0) / steps


def run_stage_isolation(args):
    """Per-stage fwd+bwd device time + TFLOP/s, and the pad128 lane probe.

    Each ResNet-50 stage's block sequence runs alone on a synthetic
    bf16 activation of the right shape (b=args.batch), timed by device
    timestamps.  `stage1_pad128` widens stage-1's bottleneck width from
    64 to 128 on the same 56x56 spatial grid: if its TFLOP/s is ~2x
    stage1's, the 64-channel shapes are MXU-lane-bound; if similar, the
    stage is memory-roofline-bound and lane padding cannot pay.
    """
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models.resnet import BottleneckBlock
    from chainermn_tpu.utils.trace import device_time

    b = args.batch

    class StageStack(nn.Module):
        filters: int
        count: int
        first_stride: int

        @nn.compact
        def __call__(self, x):
            from functools import partial
            conv = partial(nn.Conv, use_bias=False, dtype=jnp.bfloat16,
                           param_dtype=jnp.float32, padding="SAME")
            norm = partial(nn.BatchNorm, use_running_average=False,
                           momentum=0.9, epsilon=1e-5, dtype=jnp.bfloat16,
                           param_dtype=jnp.float32)
            for j in range(self.count):
                strides = ((self.first_stride,) * 2 if j == 0 else (1, 1))
                x = BottleneckBlock(self.filters, conv=conv, norm=norm,
                                    strides=strides)(x)
            return x

    def stage_flops_fwd(h_in, c_in, f, count, stride):
        """Forward conv FLOPs of a bottleneck stack (BN/relu excluded)."""
        total = 0
        c = c_in
        h = h_in
        for j in range(count):
            s = stride if j == 0 else 1
            h_out = h // s
            n_out = b * h_out * h_out
            n_in = b * h * h
            total += 2 * (n_in * c * f            # 1x1 reduce
                          + n_out * f * f * 9     # 3x3 (stride s)
                          + n_out * f * 4 * f)    # 1x1 expand
            if c != 4 * f or s != 1:
                total += 2 * n_out * c * 4 * f    # projection shortcut
            c, h = 4 * f, h_out
        return total

    # (name, spatial_in, c_in, filters, blocks, first_stride)
    rows = [
        ("stage1", 56, 64, 64, 3, 1),
        ("stage1_pad128", 56, 128, 128, 3, 1),
        ("stage2", 56, 256, 128, 4, 2),
        ("stage3", 28, 512, 256, 6, 2),
        ("stage4", 14, 1024, 512, 3, 2),
    ]
    rng = np.random.RandomState(0)
    for name, hw, c_in, f, count, stride in rows:
        model = StageStack(filters=f, count=count, first_stride=stride)
        x = jnp.asarray(rng.randn(b, hw, hw, c_in), jnp.bfloat16)
        variables = model.init(jax.random.key(0), x)

        def loss(p, xx, model=model):
            y, _ = model.apply({"params": p, "batch_stats":
                                variables["batch_stats"]}, xx,
                               mutable=["batch_stats"])
            return jnp.sum(y.astype(jnp.float32))

        g = jax.jit(jax.grad(loss, argnums=(0, 1)))
        ms = device_time(lambda: g(variables["params"], x), (), steps=5,
                         warmup=2)
        if ms <= 0:  # no TPU device track (CPU run): fall back to wall
            t0 = time.perf_counter()
            for _ in range(3):
                out = g(variables["params"], x)
            jax.block_until_ready(out)
            float(np.asarray(jax.tree.leaves(out)[0]).ravel()[0])
            ms = (time.perf_counter() - t0) / 3 * 1e3
        flops = 3 * stage_flops_fwd(hw, c_in, f, count, stride)  # fwd+bwd
        tflops = flops / (ms / 1e3) / 1e12
        log(f"{name:14s}  {ms:7.2f} ms  {tflops:6.1f} TFLOP/s "
            f"(fwd+bwd, {count} blocks @ {hw}x{hw}, width {f})")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--image", type=int, default=224)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--variants", default="full,nostats,nonorm,fwdonly,fwdbwd")
    p.add_argument("--stages", action="store_true",
                   help="per-stage isolation + pad128 lane probe instead "
                        "of step variants")
    p.add_argument("--json", action="store_true",
                   help="emit the resnet_probe/v1 artifact on stdout")
    p.add_argument("--out", default=None,
                   help="write the resnet_probe/v1 artifact to this path "
                        "(implies --json)")
    p.add_argument("--traffic-batch", type=int, default=256,
                   help="batch for the deterministic BN-traffic model "
                        "section (canonical 256 regardless of --batch so "
                        "the perf-gate budget is smoke-run independent)")
    args = p.parse_args()

    from chainermn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu.models import ResNet50
    from chainermn_tpu.optimizers import (
        init_model_state, init_opt_state, make_train_step)
    from chainermn_tpu.training import put_global_batch

    class ConstStatBN(nn.Module):
        """BatchNorm body with mean/var pinned to constants.

        Same gamma/beta params, same elementwise normalize math and dtype
        flow as nn.BatchNorm — minus the batch statistics (and their
        backward reductions).  Prices the stat computation alone.
        """
        use_running_average: bool = False
        momentum: float = 0.9
        epsilon: float = 1e-5
        dtype: object = None
        param_dtype: object = jnp.float32
        scale_init: object = nn.initializers.ones_init()

        @nn.compact
        def __call__(self, x):
            feat = x.shape[-1]
            scale = self.param("scale", self.scale_init, (feat,),
                               self.param_dtype)
            bias = self.param("bias", nn.initializers.zeros_init(), (feat,),
                              self.param_dtype)
            # constant "stats": mean 0, var 1 (inv-sqrt still applied)
            y = x * (scale * (1.0 / np.sqrt(1.0 + self.epsilon))).astype(
                x.dtype) + bias.astype(x.dtype)
            return y if self.dtype is None else y.astype(self.dtype)

    class IdentityNorm(nn.Module):
        use_running_average: bool = False
        momentum: float = 0.9
        epsilon: float = 1e-5
        dtype: object = None
        param_dtype: object = jnp.float32
        scale_init: object = nn.initializers.ones_init()

        @nn.compact
        def __call__(self, x):
            return x

    if args.stages:
        return run_stage_isolation(args)

    n_classes = 1000
    image = args.image
    comm = chainermn_tpu.create_communicator(
        "xla", allreduce_grad_dtype="bfloat16")

    rng = np.random.RandomState(0)
    x = rng.randn(args.batch, image, image, 3).astype(np.float32)
    y = (rng.rand(args.batch) * n_classes).astype(np.int32)
    batch = put_global_batch(comm, (x, y))

    known_variants = {"full", "nostats", "nonorm", "fwdonly", "fwdbwd",
                      "remat", "fusednorm"}
    wanted = args.variants.split(",")
    unknown = set(wanted) - known_variants
    if unknown:
        # A typo must not silently re-measure the full model under the
        # wrong label (a zero delta would read as "countermeasure inert").
        raise SystemExit(f"unknown variant(s) {sorted(unknown)}; "
                         f"available: {sorted(known_variants)}")
    results = {}
    for variant in wanted:
        from chainermn_tpu.ops import FusedBatchNormAct
        norm_cls = {"nostats": ConstStatBN, "nonorm": IdentityNorm,
                    "fusednorm": FusedBatchNormAct}.get(variant)
        kw = dict(num_classes=n_classes, dtype=jnp.bfloat16)
        if norm_cls is not None:
            kw["norm_cls"] = norm_cls
        if variant == "remat":
            from chainermn_tpu.models.resnet import BottleneckBlock
            kw["block_cls"] = nn.remat(BottleneckBlock)
        model = ResNet50(**kw)
        variables = model.init(
            jax.random.key(0), jnp.zeros((1, image, image, 3), jnp.float32))
        params = variables["params"]
        has_stats = "batch_stats" in variables
        stats = variables.get("batch_stats", {})

        def loss_fn(p, state, b, model=model, has_stats=has_stats):
            xb, yb = b
            if has_stats:
                logits, mut = model.apply(
                    {"params": p, "batch_stats": state}, xb, train=True,
                    mutable=["batch_stats"])
                new_state = mut["batch_stats"]
            else:
                logits = model.apply({"params": p}, xb, train=True)
                new_state = state
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, yb).mean()
            return loss, new_state

        if variant == "fwdonly":
            fn = jax.jit(lambda p, s, b: loss_fn(p, s, b)[0])
            step_args = (params, stats, batch)
            step = lambda p, s, b: (fn(p, s, b),)
        elif variant == "fwdbwd":
            grad_fn = jax.jit(jax.grad(lambda p, s, b: loss_fn(p, s, b)[0]))

            def step(p, s, b):
                g = grad_fn(p, s, b)
                return (jax.tree.leaves(g)[0].sum(),)
            step_args = (params, stats, batch)
        else:
            optimizer = chainermn_tpu.create_multi_node_optimizer(
                optax.sgd(0.1, momentum=0.9), comm, double_buffering=True)
            params = comm.bcast_data(params)
            model_state = init_model_state(comm, stats)
            opt_state = init_opt_state(comm, optimizer, params)
            train = make_train_step(comm, loss_fn, optimizer,
                                    with_model_state=True)
            state_box = [params, model_state, opt_state]

            def step(p_unused, s_unused, b):
                ps, ms, os_, loss = train(state_box[0], state_box[1],
                                          state_box[2], b)
                state_box[0], state_box[1], state_box[2] = ps, ms, os_
                return (loss,)
            step_args = (None, None, batch)

        dt = time_step(step, step_args, args.steps, warmup=4)
        img_s = args.batch / dt
        results[variant] = dt
        log(f"{variant:9s}  {dt*1e3:7.2f} ms/step   {img_s:8.1f} img/s")

    if "full" in results:
        base = results["full"]
        for v, dt in results.items():
            if v != "full":
                log(f"delta full-{v:9s} = {1e3*(base-dt):7.2f} ms")

    if args.json or args.out:
        import json

        from chainermn_tpu.ops import resnet_bn_traffic_bytes

        smoke = jax.default_backend() != "tpu"
        base = results.get("full")
        doc = {
            "schema": "resnet_probe/v1",
            "backend": jax.default_backend(),
            # timing rows off TPU are dispatch smoke, never official
            "smoke": smoke,
            "batch": args.batch,
            "image": image,
            "steps": args.steps,
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "variants": {
                v: {
                    "ms_per_step": round(dt * 1e3, 3),
                    "img_per_sec": round(args.batch / dt, 1),
                    **({"delta_vs_full_ms": round((base - dt) * 1e3, 3)}
                       if base is not None and v != "full" else {}),
                }
                for v, dt in results.items()
            },
            # deterministic modeled HBM bytes at the canonical ResNet-50
            # boundary shapes — what the resnet_bn_traffic_bytes perf-gate
            # budget reads (key: traffic.fused_total_bytes).
            "traffic": resnet_bn_traffic_bytes(args.traffic_batch),
        }
        from chainermn_tpu.observability.ledger import stamp_envelope
        stamp_envelope(doc, n_devices=jax.device_count())
        payload = json.dumps(doc, indent=2)
        if args.out:
            with open(args.out, "w") as f:
                f.write(payload + "\n")
            log(f"wrote {args.out}")
        else:
            print(payload)


if __name__ == "__main__":
    main()

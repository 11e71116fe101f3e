#!/usr/bin/env python
"""Chip smoke: the ChainerMN training path, end to end, on the attached TPU.

One process, no children.  Drives the public entry points a user calls —
``init_distributed`` -> ``create_communicator`` -> ``bcast_data`` ->
``create_multi_node_optimizer`` -> ``make_train_step`` ->
``put_global_batch`` — at the full width of the models the repo ships, and
checks what comes out.  Every phase prints one JSON line; the LAST line of
stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only if every phase passed.  A failing check raises: nothing
is caught and carried on from, and the exit status is non-zero.

    python chip_smoke.py              one chip: device, resnet50, lm_flash
    python chip_smoke.py --chips 4    four chips: device and four_chips only
    python chip_smoke.py --rehearse   CPU dress rehearsal at toy sizes (never
                                      prints an ok line that names a TPU)

Times printed here (compile seconds, ms per step) are smoke figures that say
the path ran; they are not benchmark results.
"""

import argparse
import json
import re
import sys
import time

import numpy as np

# Full-width configurations.  ResNet-50: the source paper's flagship, all 50
# layers, at the batch of the resnet50-b256 cell.  LM: a 2048-wide, 16-head
# flash-attention model at T=8192, two layers deep to keep the phase short.
FULL = {
    "resnet": {"depth": 50, "batch": 256, "image": 224, "classes": 1000,
               "warmup": 2, "steps": 5},
    "lm": {"vocab": 32768, "d_model": 2048, "n_heads": 16, "n_layers": 2,
           "seq": 8192, "attention": "flash", "lr": 0.01, "warmup": 1,
           "steps": 3},
    "parity_seq": 2048,
    "flavor_elems": 1 << 20,
}
# --rehearse: same control flow, toy shapes, Pallas interpreted.  The LM takes
# the unfused attention there: JAX's Pallas interpreter is not vma-aware, so a
# kernel cannot be interpreted inside the step's shard_map.  The flash
# kernels are rehearsed by the parity check, outside shard_map, and compiled
# for the described chip by tools/compile_for_chip.py and
# tests/test_chip_compile.py.
TOY = {
    "resnet": {"depth": 6, "batch": 8, "image": 32, "classes": 10,
               "warmup": 2, "steps": 5},
    "lm": {"vocab": 256, "d_model": 64, "n_heads": 2, "n_layers": 2,
           "seq": 256, "attention": "xla", "lr": 0.01, "warmup": 1,
           "steps": 3},
    "parity_seq": 256,
    "flavor_elems": 1 << 10,
}

# flash forward + the two backward kernels, per layer
FLASH_KERNELS_PER_LAYER = 3
# bf16 keeps 8 significant bits; outputs, probabilities and score gradients
# are each rounded to it once, so a few 2^-8 steps relative to the largest
# reference value is the expected gap to the float32 oracle.
PARITY_TOL = 3e-2
# 4 chips vs 1 chip: same model, seed and global batch.  The forward is the
# same per-sequence arithmetic; the gradient mean is rounded to the bf16 wire
# in a different order.  On the chip the losses agreed to 6e-6 relative, and
# one SGD step moves them by 1e-3.
DP_LOSS_RTOL = 2e-4
# the share of a four-chip step's all-reduced bytes that the compiler made
# asynchronous (``comm.exchange_compiler_options``): every matrix goes alone
# and is taken, the small vectors ride together in one that blocks
ASYNC_BYTE_SHARE_MIN = 0.75

FLAVORS = ("naive", "flat", "hierarchical", "two_dimensional", "single_node",
           "non_cuda_aware", "xla")


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


def emit(**record):
    print(json.dumps(record), flush=True)


class CacheEvents:
    """Counts the compile requests that consulted JAX's persistent
    compilation cache, and how many of them it served."""

    def __init__(self, placed):
        import jax

        self.placed = placed
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.requests, self.hits)

    def verdict(self, mark):
        requests, hits = self.requests - mark[0], self.hits - mark[1]
        if not self.placed:
            return "no cache placed"
        if not requests:
            return "not consulted"
        return "hit" if hits == requests else "miss"


def pallas_interpret_flags(jaxpr):
    """``interpret`` of every pallas_call reachable from ``jaxpr``."""
    flags = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            flags.append(bool(eqn.params["interpret"]))
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    flags.extend(pallas_interpret_flags(inner))
    return flags


def make_comm(devices=None):
    """The flagship communicator, as every benchmark cell builds it: XLA
    collectives, bf16 gradient wire."""
    import chainermn_tpu
    from chainermn_tpu.parallel.topology import init_topology

    topology = None if devices is None else init_topology(devices=devices)
    return chainermn_tpu.create_communicator(
        "xla", topology=topology, allreduce_grad_dtype="bfloat16")


def build_resnet(comm, cfg, state_comm=None):
    """ResNet through the public training path.  Returns ``(step, state,
    batch)``; ``state_comm`` places state and batch on another
    communicator's mesh (tools/compile_for_chip.py: state on the CPU, step
    for the described chip)."""
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu.models import ResNet, ResNet50
    from chainermn_tpu.models.resnet import BasicBlock
    from chainermn_tpu.optimizers import (
        init_model_state, init_opt_state, make_train_step)
    from chainermn_tpu.training import put_global_batch

    place = state_comm or comm
    if cfg["depth"] == 50:
        model = ResNet50(num_classes=cfg["classes"], dtype=jnp.bfloat16)
    else:
        model = ResNet(stage_sizes=(1, 1), block_cls=BasicBlock,
                       num_filters=8, num_classes=cfg["classes"],
                       dtype=jnp.bfloat16)
    image = cfg["image"]
    variables = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, image, image, 3), jnp.float32))
    params = place.bcast_data(variables["params"])
    model_state = init_model_state(place, variables["batch_stats"])
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm, double_buffering=True)
    opt_state = init_opt_state(place, optimizer, params)

    def loss_fn(p, state, batch):
        x, y = batch
        logits, mutated = model.apply(
            {"params": p, "batch_stats": state}, x, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, mutated["batch_stats"]

    step = make_train_step(comm, loss_fn, optimizer, with_model_state=True)
    rng = np.random.RandomState(0)
    n = cfg["batch"] * place.size
    x = rng.randn(n, image, image, 3).astype(np.float32)
    y = (rng.rand(n) * cfg["classes"]).astype(np.int32)
    batch = put_global_batch(place, (x, y))
    return step, (params, model_state, opt_state), batch


def build_lm(comm, cfg, state_comm=None, *, double_buffering=True,
             global_batch=None):
    """The flash-attention LM through the same path (no model state)."""
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.optimizers import init_opt_state, make_train_step
    from chainermn_tpu.training import put_global_batch

    place = state_comm or comm
    model = TransformerLM(
        vocab=cfg["vocab"], d_model=cfg["d_model"], n_layers=cfg["n_layers"],
        n_heads=cfg["n_heads"], max_len=cfg["seq"],
        attention_impl=cfg["attention"],
        dtype=jnp.bfloat16)
    params = place.bcast_data(jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, min(cfg["seq"], 128)), jnp.int32)))
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(cfg["lr"], momentum=0.9), comm,
        double_buffering=double_buffering)
    opt_state = init_opt_state(place, optimizer, params)

    def loss_fn(p, batch):
        (tok,) = batch
        logits = model.apply(p, tok)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tok[:, 1:]).mean()

    step = make_train_step(comm, loss_fn, optimizer)
    rng = np.random.RandomState(0)
    n = global_batch or place.size
    toks = (rng.rand(n, cfg["seq"]) * cfg["vocab"]).astype(np.int32)
    batch = put_global_batch(place, (toks,))
    return step, (params, opt_state), batch


def _leaves_changed(before, after):
    """Per parameter leaf, on the device: does any element differ?"""
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.jit(lambda a, b: jnp.stack(
        [jnp.any(x != y) for x, y in zip(jax.tree.leaves(a),
                                         jax.tree.leaves(b))]))(before, after))


def compile_and_step(name, step, state, batch, *, warmup, steps, kernels,
                     on_chip, cache):
    """Compile ``step`` twice ahead of time (timed, the second time to see
    the persistent cache serve it; program text checked), then take
    ``warmup + steps`` steps through the jitted entry point itself.
    Returns the phase record."""
    import jax
    import jax.numpy as jnp

    # Two compiles of the same step, each from empty in-memory caches so
    # that the second is served by the persistent cache or not at all.  They
    # come from ONE call site: a Pallas kernel's payload carries the source
    # lines of the Python frames that traced it, the cache key does not
    # strip them, and the same step traced from two lines is two entries.
    compiles = []
    for _ in range(2):
        jax.clear_caches()
        mark = cache.mark()
        t0 = time.perf_counter()
        traced = step.trace(*state, batch)
        compiled = traced.lower().compile()
        compiles.append((time.perf_counter() - t0, cache.verdict(mark)))
    (compile_s, first_compile), (second_s, second_compile) = compiles
    interpreted = pallas_interpret_flags(traced.jaxpr)
    found = compiled.as_text().count("tpu_custom_call")
    if on_chip:
        check(found == kernels,
              f"{name}: {found} tpu_custom_call in the compiled step, "
              f"expected {kernels} — a Pallas kernel gave way to XLA or to "
              "the interpreter, or one came that the step should not hold")
        check(not any(interpreted),
              f"{name}: {sum(interpreted)} of {len(interpreted)} Pallas "
              "calls were traced in interpret mode")
    memory = compiled.memory_analysis()
    del traced, compiled

    # the steps donate their state: keep a copy of the parameters to
    # compare against afterwards
    before = jax.jit(lambda p: jax.tree.map(jnp.copy, p))(state[0])
    losses = []
    t0 = time.perf_counter()
    for i in range(warmup):
        *state, loss = step(*state, batch)
        losses.append(float(loss))      # value read: the step has run
        if i == 0:
            # the jitted entry point finds the program compiled above
            first_step_s = time.perf_counter() - t0
    pending = []
    t0 = time.perf_counter()
    for _ in range(steps):
        *state, loss = step(*state, batch)
        pending.append(loss)
    losses += [float(l) for l in pending]   # fence: read every loss value
    step_ms = (time.perf_counter() - t0) / steps * 1e3

    check(all(np.isfinite(losses)), f"{name}: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"{name}: loss on the fixed batch did not fall: {losses}")
    finite = bool(jax.jit(lambda p: jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(leaf)) for leaf in jax.tree.leaves(p)])))(
            state[0]))
    check(finite, f"{name}: non-finite parameters after {len(losses)} steps")
    changed = _leaves_changed(before, state[0])
    check(changed.sum() > changed.size // 2,
          f"{name}: only {changed.sum()} of {changed.size} parameter "
          "leaves changed")
    stats = jax.devices()[0].memory_stats() or {}
    return {
        "losses": [round(l, 5) for l in losses],
        "param_leaves_changed": f"{changed.sum()}/{changed.size}",
        "tpu_custom_calls": found,
        "pallas_calls_interpreted": f"{sum(interpreted)}/{len(interpreted)}",
        "compile_s": round(compile_s, 2),
        "first_compile_cache": first_compile,
        "second_compile_s": round(second_s, 2),
        "second_compile_cache": second_compile,
        "first_step_s": round(first_step_s, 2),
        "smoke_wall_ms_per_step": round(step_ms, 2),
        "compiled_argument_bytes": memory.argument_size_in_bytes,
        "compiled_temp_bytes": memory.temp_size_in_bytes,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(chips, rehearse):
    import jax
    import jaxlib

    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    devices = jax.devices()
    emit(phase="device", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu_version, devices=[str(d) for d in devices],
         rehearsal=rehearse)
    platforms = sorted({d.platform for d in devices})
    if rehearse:
        check("tpu" not in platforms,
              "--rehearse is the CPU dress rehearsal; on the chip run "
              "chip_smoke.py without it")
    else:
        check(platforms == ["tpu"],
              f"no accelerator: JAX found {platforms} devices, need tpu")
    check(len(devices) == chips,
          f"{len(devices)} devices, --chips {chips} (for a rehearsal on "
          f"the CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
          f"{chips})")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def phase_resnet50(sizes, on_chip, cache):
    cfg = sizes["resnet"]
    comm = make_comm()
    step, state, batch = build_resnet(comm, cfg)
    record = compile_and_step(
        "resnet50", step, state, batch, warmup=cfg["warmup"],
        steps=cfg["steps"], kernels=0, on_chip=on_chip, cache=cache)
    emit(phase="resnet50",
         config=f"ResNet-{cfg['depth']} b={cfg['batch']} "
                f"{cfg['image']}x{cfg['image']} bf16, xla communicator, bf16 "
                f"gradient wire, double-buffered SGD",
         **record)


def phase_lm_flash(sizes, on_chip, cache):
    cfg = sizes["lm"]
    comm = make_comm()
    step, state, batch = build_lm(comm, cfg)
    record = compile_and_step(
        "lm_flash", step, state, batch, warmup=cfg["warmup"],
        steps=cfg["steps"],
        kernels=FLASH_KERNELS_PER_LAYER * cfg["n_layers"],
        on_chip=on_chip, cache=cache)
    emit(phase="lm_flash",
         config=f"TransformerLM vocab={cfg['vocab']} d={cfg['d_model']} "
                f"heads={cfg['n_heads']} T={cfg['seq']} b=1 bf16 "
                f"attention={cfg['attention']}, {cfg['n_layers']} layers",
         **record)
    emit(phase="flash_parity", **flash_parity(cfg, sizes["parity_seq"]))


def flash_parity(cfg, seq):
    """flash_attention forward and gradients against the float32 oracle
    (chainermn_tpu.parallel.sequence.attention) at the LM's head geometry."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.flash_attention import flash_attention
    from chainermn_tpu.parallel.sequence import attention

    heads, dim = cfg["n_heads"], cfg["d_model"] // cfg["n_heads"]
    keys = jax.random.split(jax.random.key(1), 4)
    q, k, v, g = (jax.random.normal(key, (1, seq, heads, dim), jnp.bfloat16)
                  for key in keys)

    def weighted(fn, cast):
        def loss(q, k, v):
            out = fn(cast(q), cast(k), cast(v), causal=True)
            return jnp.sum(out.astype(jnp.float32)
                           * g.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, out), grads = weighted(flash_attention, lambda a: a)(q, k, v)
    (_, ref), ref_grads = weighted(
        attention, lambda a: a.astype(jnp.float32))(q, k, v)

    def gap(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    errs = {"out": gap(out, ref)}
    errs.update({f"d{n}": gap(a, b)
                 for n, a, b in zip("qkv", grads, ref_grads)})
    check(all(np.isfinite(e) and e <= PARITY_TOL for e in errs.values()),
          f"flash_attention vs float32 oracle at T={seq}: {errs} exceeds "
          f"{PARITY_TOL}")
    return {"shape": [1, seq, heads, dim], "causal": True,
            "max_err_over_max_ref": {n: round(e, 5) for n, e in errs.items()},
            "tolerance": PARITY_TOL}


def phase_four_chips(sizes, on_chip, cache):
    import jax

    import chainermn_tpu

    devices = jax.devices()
    n = len(devices)

    # (a) every communicator flavor reduces and broadcasts over real links
    ranks = np.arange(n, dtype=np.float32)
    grads = {"w": np.broadcast_to(ranks[:, None],
                                  (n, sizes["flavor_elems"])).copy(),
             "b": ranks.reshape(n, 1) * np.ones((n, 8), np.float32)}
    want_mean = float(ranks.mean())
    for flavor in FLAVORS:
        two_level = flavor in ("hierarchical", "two_dimensional")
        comm = chainermn_tpu.create_communicator(
            flavor, intra_size=2 if two_level else None)
        check(comm.size == n, f"{flavor}: size {comm.size}, {n} devices")
        mean = comm.run_spmd(lambda g: comm.allreduce_grad(g), grads)
        got = {k: np.asarray(a) for k, a in mean.items()}
        check(all(np.all(a == want_mean) for a in got.values()),
              f"{flavor}: allreduce_grad of ranks 0..{n - 1} gave "
              f"{ {k: np.unique(a).tolist() for k, a in got.items()} }, "
              f"expected {want_mean} everywhere")
        sent = np.asarray(comm.run_spmd(
            lambda x: comm.bcast_data(x), 10.0 + ranks.reshape(n, 1)))
        check(np.all(sent == 10.0),
              f"{flavor}: bcast_data delivered {sent.ravel().tolist()}, "
              "expected rank 0's 10.0 everywhere")
        emit(phase="four_chips", part="flavor", flavor=flavor,
             inter=comm.inter_size, intra=comm.intra_size,
             allreduce_grad=want_mean, bcast_data=10.0)

    # (b) the lm_flash model data-parallel over all chips against the same
    # model, seed and global batch on one chip of this process
    cfg = sizes["lm"]
    losses = {}
    for label, devs in (("dp", None), ("one_chip", devices[:1])):
        comm = make_comm(devs)
        step, state, batch = build_lm(comm, cfg, double_buffering=False,
                                      global_batch=n)
        if label == "dp":
            placement = check_placement(comm, cfg, step, state, batch,
                                        on_chip)
        run = []
        for _ in range(3):
            *state, loss = step(*state, batch)
            run.append(float(loss))
        check(all(np.isfinite(run)), f"{label}: non-finite loss {run}")
        losses[label] = run
        if label == "dp":
            placement["bytes_in_use"] = bytes_in_use(devices, on_chip)
        del step, state, batch
    check(np.allclose(losses["dp"], losses["one_chip"], rtol=DP_LOSS_RTOL,
                      atol=0),
          f"{n}-chip and 1-chip losses differ beyond rtol {DP_LOSS_RTOL}: "
          f"{losses}")
    check(losses["dp"][-1] < losses["dp"][0],
          f"data-parallel loss did not fall: {losses['dp']}")
    emit(phase="four_chips", part="lm_data_parallel",
         config=f"lm_flash model, global batch {n} (one sequence a chip), "
                "plain SGD+momentum, bf16 wire",
         losses_dp=[round(l, 5) for l in losses["dp"]],
         losses_one_chip=[round(l, 5) for l in losses["one_chip"]],
         rtol=DP_LOSS_RTOL)
    emit(phase="four_chips", part="placement", **placement)


def check_placement(comm, cfg, step, state, batch, on_chip):
    """Where the data-parallel step's operands live and what the compiled
    program exchanges."""
    import jax

    from chainermn_tpu.analysis.hlo import (all_reduce_overlap_census,
                                            parse_hlo_collectives)

    n = comm.size
    for leaf in jax.tree.leaves(batch):
        rows = sorted(s.data.shape[0] for s in leaf.addressable_shards)
        check(len(leaf.sharding.device_set) == n
              and rows == [leaf.shape[0] // n] * n,
              f"batch leaf {leaf.shape} on {len(leaf.sharding.device_set)} "
              f"devices with shard rows {rows}")
    params = state[0]
    for leaf in jax.tree.leaves(params):
        check(len(leaf.sharding.device_set) == n and leaf.is_fully_replicated,
              f"parameter leaf {leaf.shape} is not replicated on all {n} "
              f"devices: {leaf.sharding}")
    compiled = step.lower(*state, batch).compile()
    text = compiled.as_text()
    reduces = [c for c in parse_hlo_collectives(text).ops
               if c.op in ("all-reduce", "reduce-scatter")]
    widths = sorted({_group_width(c.groups, n) for c in reduces})
    check(n in widths,
          f"no all-reduce or reduce-scatter over a group of {n} in the "
          f"compiled step (group widths seen: {widths})")
    kernels = text.count("tpu_custom_call")
    # the engagement counter of the asynchronous gradient exchange
    # (``comm.exchange_compiler_options``: None on the CPU rehearsal)
    census = all_reduce_overlap_census(text)
    if on_chip:
        want = FLASH_KERNELS_PER_LAYER * cfg["n_layers"]
        check(kernels == want,
              f"{kernels} tpu_custom_call in the {n}-chip step, expected "
              f"{want}")
        check(census["asynchronous_byte_share"] > ASYNC_BYTE_SHARE_MIN,
              f"the {n}-chip step reduces {census} - under "
              f"{ASYNC_BYTE_SHARE_MIN:.0%} of the wire bytes asynchronously")
    return {"batch_shard_devices": n, "params_replicated_on": n,
            "reduce_group_widths": widths, "reduces": len(reduces),
            "tpu_custom_calls": kernels, "all_reduce_census": census}


def _group_width(groups, world):
    """Devices per replica group, from either HLO rendering: the explicit
    ``{{0,1,2,3}}`` list or the iota form ``[groups,width]<=[world]``."""
    groups = groups or ""
    iota = re.match(r"\[(\d+),(\d+)\]<=", groups)
    if iota:
        return int(iota.group(2))
    first = re.search(r"\{([\d,\s]+)\}", groups)
    if first:
        return len([t for t in first.group(1).split(",") if t.strip()])
    return world  # no groups attribute: one group of every device


def bytes_in_use(devices, on_chip):
    """``bytes_in_use`` per device after the step (None where the backend
    does not report memory, i.e. the CPU rehearsal)."""
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if on_chip:
        check(all(u and u > 0 for u in used),
              f"a chip holds nothing after the step: bytes_in_use={used}")
    return used


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="chips the run must find (4: only the device "
                             "and four_chips phases)")
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU dress rehearsal at toy sizes")
    args = parser.parse_args()
    sizes = TOY if args.rehearse else FULL
    on_chip = not args.rehearse

    import chainermn_tpu
    from chainermn_tpu.utils.compile_cache import place_compile_cache

    # A rehearsal's CPU programs are of no use to a chip run: cache nothing.
    cache_dir = None if args.rehearse else place_compile_cache()
    chainermn_tpu.init_distributed()
    device = phase_device(args.chips, args.rehearse)
    cache = CacheEvents(placed=cache_dir is not None)
    emit(phase="setup", compile_cache_dir=cache_dir)
    if args.chips == 4:
        phase_four_chips(sizes, on_chip, cache)
    else:
        phase_resnet50(sizes, on_chip, cache)
        phase_lm_flash(sizes, on_chip, cache)
    if args.rehearse:
        emit(ok=True, rehearsal=True, device=device)
    else:
        emit(ok=True, device=device)


if __name__ == "__main__":
    sys.exit(main())

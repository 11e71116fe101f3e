#!/usr/bin/env python
"""Long-context LM training with sequence parallelism.

**Beyond-reference example** (the reference predates transformers and
sequence parallelism — SURVEY.md §5.7): a decoder-only LM whose sequence
dimension is sharded across the mesh, attention computed with ring
attention (`--attention ring`, ppermute KV rotation) or Ulysses
all-to-all (`--attention ulysses`); single-shard runs can use the fused
Pallas kernel (`--attention flash`) or the unfused math (`--attention
xla`).

Data is a synthetic "repeated motif" task (the sequence repeats a short
motif with noise — long-range next-token prediction that a causal LM can
learn quickly).

    python examples/long_context/train_lm.py --attention ring --seq-len 2048
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chainermn_tpu.models import TransformerLM
from chainermn_tpu.analysis import assert_no_captured_constants


def make_motif_task(n, seq_len, vocab, motif_len=16, seed=0):
    rng = np.random.RandomState(seed)
    motifs = (rng.rand(n, motif_len) * vocab).astype(np.int32)
    reps = -(-seq_len // motif_len)
    seqs = np.tile(motifs, (1, reps))[:, :seq_len]
    noise = rng.rand(n, seq_len) < 0.02
    seqs = np.where(noise, (rng.rand(n, seq_len) * vocab).astype(np.int32),
                    seqs)
    return jnp.asarray(seqs)


def main():
    p = argparse.ArgumentParser(description="chainermn_tpu long-context LM")
    p.add_argument("--attention", default="ring",
                   choices=["ring", "ring_flash", "ulysses", "flash", "xla"])
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--batchsize", "-b", type=int, default=4)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--vocab", type=int, default=128)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=None,
                   help="GQA/MQA: kv head count (must divide --heads; "
                        "flash/ring_flash read grouped kv natively)")
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--fsdp", action="store_true",
                   help="shard params + optimizer state over the SAME "
                        "sequence-parallel axis (ZeRO-3 over the sp "
                        "group: gather params, compute the local "
                        "sequence shard, reduce-scatter grads — "
                        "parallel/fsdp.py); requires a sequence-parallel "
                        "--attention")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    if args.kv_heads is not None and (
            args.kv_heads < 1 or args.heads % args.kv_heads):
        p.error(f"--kv-heads ({args.kv_heads}) must be >= 1 and divide "
                f"--heads ({args.heads})")
    if args.fsdp and args.attention not in ("ring", "ring_flash",
                                            "ulysses"):
        p.error("--fsdp composes with the sequence-parallel attentions "
                "(ring/ring_flash/ulysses); single-shard runs have no "
                "axis to shard over")

    devices = jax.devices()
    seq_parallel = args.attention in ("ring", "ring_flash", "ulysses")
    n_sp = len(devices) if seq_parallel else 1
    if args.seq_len % max(n_sp, 1):
        p.error(f"--seq-len must be divisible by {n_sp} devices")
    mesh = Mesh(np.array(devices[:n_sp]), ("sp",))
    t_local = args.seq_len // n_sp

    model = TransformerLM(
        vocab=args.vocab, d_model=args.d_model, n_layers=args.layers,
        n_heads=args.heads, n_kv_heads=args.kv_heads,
        max_len=args.seq_len, attention_impl=args.attention,
        axis_name="sp" if seq_parallel else None)
    ref_init = TransformerLM(
        vocab=args.vocab, d_model=args.d_model, n_layers=args.layers,
        n_heads=args.heads, n_kv_heads=args.kv_heads,
        max_len=args.seq_len, attention_impl="xla")

    toks = make_motif_task(args.batchsize, args.seq_len, args.vocab,
                           seed=args.seed)
    params = ref_init.init(jax.random.key(args.seed), toks[:, :64])
    opt = optax.adam(args.lr)
    # replicated Adam state only without --fsdp (with it, the sharded
    # state lives inside FsdpState — a full replica here would erase
    # exactly the memory the flag sheds)
    opt_state = None if args.fsdp else opt.init(params)

    def sp_body(pp, tkk):
        """Per-device objective on the LOCAL sequence shard — must run
        inside an SPMD region over the 'sp' axis."""
        me = jax.lax.axis_index("sp")
        logits = model.apply(pp, tkk, pos_offset=me * t_local)
        # global next-token objective: each shard also predicts the
        # FIRST token of the next shard (fetched with one ppermute),
        # so the loss matches the single-device xla/flash objective
        # exactly (every position supervised except the global last)
        nxt = jax.lax.ppermute(
            tkk[:, :1], "sp",
            perm=[(i, (i - 1) % n_sp) for i in range(n_sp)])
        targets = jnp.concatenate([tkk[:, 1:], nxt], axis=1)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets)
        mask = jnp.ones_like(ce)
        mask = mask.at[:, -1].set(
            jnp.where(me == n_sp - 1, 0.0, 1.0))
        total = jax.lax.psum((ce * mask).sum(), "sp")
        count = jax.lax.psum(mask.sum(), "sp")
        return total / count

    if seq_parallel:
        def loss_fn(p_, tk):
            # check_vma=False: the Pallas interpret-mode interpreter (CPU
            # path of --attention ring_flash/flash) trips a dynamic_slice
            # vma check inside shard_map; on TPU the kernel is compiled and
            # no check is skipped.
            return jax.shard_map(sp_body, mesh=mesh,
                             in_specs=(P(), P(None, "sp")),
                             out_specs=P(),
                             check_vma=False)(p_, tk)
        toks = jax.device_put(toks, NamedSharding(mesh, P(None, "sp")))
    else:
        def loss_fn(p_, tk):
            logits = model.apply(p_, tk)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tk[:, 1:]).mean()

    sync_each = jax.default_backend() == "cpu"
    print(f"attention={args.attention} devices={n_sp} "
          f"seq={args.seq_len} (local {t_local}) "
          f"fsdp={args.fsdp} backend={jax.default_backend()}", flush=True)
    t0 = time.time()
    if args.fsdp:
        # FSDP over the sequence-parallel group: params + Adam state live
        # as 1/n_sp flat shards; the step gathers them, runs sp_body on
        # the local sequence shard, and the gather's autodiff transpose
        # reduce-scatters the gradients.  global_loss=True because
        # sp_body already psums to the global objective.
        import chainermn_tpu
        from chainermn_tpu.parallel.fsdp import (
            fsdp_full_params, fsdp_init, make_fsdp_train_step)

        comm = chainermn_tpu.create_communicator("xla", mesh=mesh)
        fsdp_state, meta = fsdp_init(comm, params, opt)
        fsdp_step = make_fsdp_train_step(
            comm, sp_body, opt, meta, batch_spec=P(None, "sp"),
            global_loss=True, check_vma=False)
        # every operand (state, batch) must be an explicit step argument;
        # a capture here would bake device arrays into the compiled
        # program as constants (analysis/captured.py)
        assert_no_captured_constants(fsdp_step, fsdp_state, toks,
                                     name="fsdp_step")
        for i in range(args.steps):
            fsdp_state, loss = fsdp_step(fsdp_state, toks)
            if sync_each or i % 10 == 0 or i == args.steps - 1:
                print(f"step {i}: loss {float(loss):.4f}", flush=True)
        # anyone extending the example (checkpoint/eval) gets the
        # TRAINED weights, not the init replica
        params = fsdp_full_params(fsdp_state, meta)
    else:
        @jax.jit
        def step(p_, s_, tk):
            l, g = jax.value_and_grad(loss_fn)(p_, tk)
            updates, s_ = opt.update(g, s_, p_)
            return optax.apply_updates(p_, updates), s_, l

        # params/opt_state/toks are explicit jit args; audit that nothing
        # device-resident is closure-captured (such arrays become
        # constants of the compiled program)
        assert_no_captured_constants(step, params, opt_state, toks,
                                     name="step")
        for i in range(args.steps):
            params, opt_state, loss = step(params, opt_state, toks)
            if sync_each or i % 10 == 0 or i == args.steps - 1:
                print(f"step {i}: loss {float(loss):.4f}", flush=True)
    print(f"done in {time.time() - t0:.1f}s; "
          f"final loss {float(loss):.4f}", flush=True)


if __name__ == "__main__":
    main()

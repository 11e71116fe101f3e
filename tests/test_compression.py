"""Gradient-compression subsystem tests — resolver/spec round-trips,
NoCompression bit-exactness against the raw wire-dtype paths (allreduce
and bucketed FSDP), int8/fp8 error-feedback convergence, the optimizer
seam (the raw wire dtype's two spellings, rejected combinations, per-hop
compressed plans), checkpoint config guards (incl. the per-hop ``hops`` sidecar),
the compression_* observability family, and the bench census as a
subprocess (chainermn_tpu/compression/ + the three seams)."""

import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu.compression import (
    CompressionState,
    Fp8Compressor,
    Int8Compressor,
    NoCompression,
    available_compressors,
    compression_layout,
    resolve_compressor,
)
from chainermn_tpu.optimizers import init_opt_state, make_train_step
from chainermn_tpu.planner.plans import compressed_two_dimensional
from chainermn_tpu.parallel.fsdp import (
    fsdp_full_params, fsdp_init, fsdp_layout, make_fsdp_train_step)
from chainermn_tpu.training import put_global_batch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def comm():
    return chainermn_tpu.create_communicator("flat")


def per_rank_grads(size):
    """Stacked per-rank gradient pytrees with a pad-forcing odd leaf:
    rank r holds r * ones, so the exact mean is (size-1)/2."""
    ranks = jnp.arange(size, dtype=jnp.float32).reshape(size, 1, 1)
    return {
        "w": ranks * jnp.ones((size, 3, 4), jnp.float32),
        "b": ranks[:, 0].astype(jnp.bfloat16)
        * jnp.ones((size, 5), jnp.bfloat16),
        "odd": ranks[:, 0] * jnp.ones((size, 7), jnp.float32),
    }


def _mlp_problem(comm, n_layers=4, width=16, seed=0):
    rng = np.random.RandomState(seed)
    params = {f"layer{i}": {
        "w": jnp.asarray(rng.randn(width, width) / 4.0, jnp.float32),
        "b": jnp.asarray(rng.randn(width) / 4.0, jnp.float32)}
        for i in range(n_layers)}

    def loss_fn(p, batch):
        x, y = batch
        for i in range(n_layers):
            x = jnp.tanh(x @ p[f"layer{i}"]["w"] + p[f"layer{i}"]["b"])
        return jnp.mean((x - y) ** 2)

    xs = np.asarray(rng.randn(comm.size * 4, width), np.float32)
    ys = np.asarray(np.tanh(rng.randn(comm.size * 4, width)), np.float32)
    return params, loss_fn, (xs, ys)


# ---- resolver / spec round-trips --------------------------------------------

class TestResolve:
    def test_registry_names(self):
        names = available_compressors()
        for want in ("none", "int8", "fp8"):
            assert want in names, names

    def test_resolve_forms(self):
        assert resolve_compressor(None) is None
        c = Int8Compressor(chunk_size=256, stochastic=False)
        assert resolve_compressor(c) is c
        assert isinstance(resolve_compressor("int8"), Int8Compressor)
        assert isinstance(resolve_compressor("fp8"), Fp8Compressor)
        # a bare dtype string means "cast the wire" (the old knob)
        nc = resolve_compressor("bfloat16")
        assert isinstance(nc, NoCompression)
        assert nc.wire == jnp.bfloat16

    def test_spec_round_trip(self):
        for c in (NoCompression(), NoCompression(wire_dtype="bfloat16"),
                  Int8Compressor(chunk_size=256, stochastic=False, seed=3),
                  Fp8Compressor()):
            again = resolve_compressor(c.spec)
            assert again == c and again.spec == c.spec

    def test_unknown_name_raises(self):
        with pytest.raises(ValueError):
            resolve_compressor("zstd")

    def test_nocompression_rejects_int_wire(self):
        with pytest.raises(ValueError):
            NoCompression(wire_dtype="int8")


# ---- NoCompression == the raw wire-dtype program, bit for bit ---------------

class TestNoCompressionBitExact:
    def test_allreduce_matches_dtype_knob(self):
        """allreduce_grad(compressor=NoCompression(bf16)) on a plain
        communicator is bit-for-bit the allreduce_grad_dtype='bfloat16'
        program (the same plan through the one compiler: cast, psum a
        leaf, cast back, scale)."""
        c_knob = chainermn_tpu.create_communicator(
            "xla", intra_size=4, allreduce_grad_dtype="bfloat16")
        c_plain = chainermn_tpu.create_communicator("xla", intra_size=4)
        grads = per_rank_grads(c_knob.size)
        nc = NoCompression(wire_dtype="bfloat16")
        out_knob = c_knob.run_spmd(
            lambda g: c_knob.allreduce_grad(g), grads)
        out_comp = c_plain.run_spmd(
            lambda g: c_plain.allreduce_grad(g, compressor=nc), grads)
        for a, b in zip(jax.tree.leaves(out_knob),
                        jax.tree.leaves(out_comp)):
            assert a.dtype == b.dtype
            assert np.array_equal(np.asarray(a), np.asarray(b)), (a, b)

    def test_bare_nocompression_is_identity_path(self, comm):
        """NoCompression() without a wire dtype lowers to the exact
        default allreduce program."""
        grads = per_rank_grads(comm.size)
        out_plain = comm.run_spmd(lambda g: comm.allreduce_grad(g), grads)
        out_nc = comm.run_spmd(
            lambda g: comm.allreduce_grad(g, compressor=NoCompression()),
            grads)
        for a, b in zip(jax.tree.leaves(out_plain),
                        jax.tree.leaves(out_nc)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_communicator_compression_kwarg_folds_to_wire(self):
        """create_communicator(compression='bfloat16') is the
        allreduce_grad_dtype knob under the new spelling."""
        c = chainermn_tpu.create_communicator(
            "xla", intra_size=4, compression="bfloat16")
        assert c.allreduce_grad_dtype == jnp.bfloat16
        with pytest.raises(ValueError, match="allreduce_grad_dtype"):
            chainermn_tpu.create_communicator(
                "xla", intra_size=4, allreduce_grad_dtype="float16",
                compression="bfloat16")

    def test_fsdp_bucket_compressors_match_wire_dtypes(self, comm):
        """num_buckets=4 with bucket_compressors=NoCompression(bf16) is
        bit-for-bit the bucket_wire_dtypes=['bfloat16']*4 trajectory."""
        params, loss_fn, data = _mlp_problem(comm)
        batch = put_global_batch(comm, data)
        trajs = {}
        for key, kw in (("wire", dict(bucket_wire_dtypes=["bfloat16"] * 4)),
                        ("comp", dict(bucket_compressors=NoCompression(
                            wire_dtype="bfloat16")))):
            state, meta = fsdp_init(comm, params, optax.adam(0.01),
                                    num_buckets=4, **kw)
            step = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01),
                                        meta, donate=False)
            losses = []
            for _ in range(5):
                state, loss = step(state, batch)
                losses.append(float(loss))
            trajs[key] = (losses, fsdp_full_params(state, meta))
        assert trajs["wire"][0] == trajs["comp"][0]
        for a, b in zip(jax.tree.leaves(trajs["wire"][1]),
                        jax.tree.leaves(trajs["comp"][1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- error feedback: convergence semantics ----------------------------------

class TestErrorFeedback:
    def test_int8_time_averaged_error_decays(self, comm):
        """EF on a constant gradient stream: the per-step residual stays
        BOUNDED (it oscillates at quantization granularity), while the
        time-averaged applied gradient converges to the true mean at
        1/t — the textbook EF guarantee.  Deterministic rounding makes
        the decay exactly monotone."""
        comp = Int8Compressor(stochastic=False)
        rng = np.random.RandomState(0)
        grads = {
            "w": jnp.asarray(rng.randn(comm.size, 3, 4), jnp.float32),
            "b": jnp.asarray(rng.randn(comm.size, 7), jnp.float32),
        }
        state0 = comm.init_compression_state(grads, comp)
        assert isinstance(state0, CompressionState)
        st = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (comm.size,) + a.shape), state0)
        fn = lambda g, s: comm.allreduce_grad(g, compressor=comp, state=s)
        ref = {k: np.asarray(v, np.float32).mean(axis=0)
               for k, v in grads.items()}
        acc = jax.tree.map(lambda _: 0.0, ref)
        errs = []
        checkpoints = (1, 4, 16, 64)
        for t in range(1, checkpoints[-1] + 1):
            out, st = comm.run_spmd(fn, grads, st)
            acc = {k: acc[k] + np.asarray(out[k][0], np.float32)
                   for k in ref}
            if t in checkpoints:
                errs.append(max(
                    np.max(np.abs(acc[k] / t - ref[k])) for k in ref))
        assert all(a > b for a, b in zip(errs, errs[1:])), errs
        assert errs[0] / errs[-1] >= 8.0, errs
        # residual bounded, not growing: one rank's EF norm stays finite
        # and small relative to the gradient scale
        ef = np.asarray(st.ef[0], np.float32)
        assert np.isfinite(ef).all() and np.linalg.norm(ef) < 10.0

    def test_quantizer_without_state_raises(self, comm):
        grads = per_rank_grads(comm.size)
        with pytest.raises(ValueError, match="init_compression_state"):
            comm.run_spmd(
                lambda g: comm.allreduce_grad(g, compressor="int8"), grads)

    def test_state_shape_mismatch_raises(self, comm):
        # > chunk_size elements so the padded EF length actually differs
        # from the tiny tree's (both would otherwise pad to one chunk)
        grads = {"w": jnp.zeros((comm.size, 40, 40), jnp.float32)}
        wrong = comm.init_compression_state(
            {"tiny": jnp.zeros(3)}, Int8Compressor())
        st = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (comm.size,) + a.shape), wrong)
        with pytest.raises(ValueError, match="init_compression_state"):
            comm.run_spmd(
                lambda g, s: comm.allreduce_grad(
                    g, compressor="int8", state=s), grads, st)

    def test_world_size_clip_limit(self):
        """int8's in-wire summation runs out of code levels at W > 63;
        the error points at fp8 / uncompressed."""
        Int8Compressor().clip_limit(8)  # fine
        with pytest.raises(ValueError, match="fp8"):
            Int8Compressor().clip_limit(64)
        Fp8Compressor().clip_limit(64)  # fp8 still has headroom there

    def test_stochastic_rounding_unbiased(self, comm):
        """With stochastic rounding the quantizer is unbiased: averaging
        many independent rounds of the SAME gradient converges to the
        true mean even without exploiting the EF recursion."""
        comp = Int8Compressor(stochastic=True, seed=7)
        grads = per_rank_grads(comm.size)
        state0 = comm.init_compression_state(grads, comp)
        st = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (comm.size,) + a.shape), state0)
        fn = lambda g, s: comm.allreduce_grad(g, compressor=comp, state=s)
        ref = np.asarray(grads["w"], np.float32).mean(axis=0)
        acc = 0.0
        for _ in range(32):
            out, st = comm.run_spmd(fn, grads, st)
            acc = acc + np.asarray(out["w"][0], np.float32)
        assert np.max(np.abs(acc / 32 - ref)) < 0.05


# ---- the optimizer seam -----------------------------------------------------

class TestOptimizerSeam:
    def _train(self, comm, optimizer, steps=6):
        params, loss_fn, data = _mlp_problem(comm)
        opt_state = init_opt_state(comm, optimizer, params)
        step = make_train_step(comm, loss_fn, optimizer)
        batch = put_global_batch(comm, data)
        losses = []
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
        return losses, params

    def test_nocompression_matches_dtype_knob_trajectory(self):
        """compression=NoCompression(bf16) through the optimizer seam
        reproduces the allreduce_grad_dtype communicator knob bit for
        bit over a full training trajectory."""
        c_knob = chainermn_tpu.create_communicator(
            "xla", intra_size=4, allreduce_grad_dtype="bfloat16")
        c_plain = chainermn_tpu.create_communicator("xla", intra_size=4)
        with warnings.catch_warnings():
            # the reference's own spelling is not deprecated
            warnings.simplefilter("error")
            opt_knob = chainermn_tpu.create_multi_node_optimizer(
                optax.adam(1e-2), c_knob)
        opt_comp = chainermn_tpu.create_multi_node_optimizer(
            optax.adam(1e-2), c_plain,
            compression=NoCompression(wire_dtype="bfloat16"))
        l_knob, p_knob = self._train(c_knob, opt_knob)
        l_comp, p_comp = self._train(c_plain, opt_comp)
        assert l_knob == l_comp
        for a, b in zip(jax.tree.leaves(p_knob), jax.tree.leaves(p_comp)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_raw_dtype_knob_lowers_to_the_codec_program(self):
        """``allreduce_grad_dtype`` on the communicator (the reference's
        spelling, what every benchmark cell passes) warns of nothing and
        lowers to the program ``NoCompression(wire_dtype=...)`` lowers
        to."""
        c_knob = chainermn_tpu.create_communicator(
            "xla", intra_size=4, allreduce_grad_dtype="bfloat16")
        c_plain = chainermn_tpu.create_communicator("xla", intra_size=4)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            opt_knob = chainermn_tpu.create_multi_node_optimizer(
                optax.sgd(1e-2, momentum=0.9), c_knob)
        opt_comp = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(1e-2, momentum=0.9), c_plain,
            compression=NoCompression(wire_dtype="bfloat16"))
        texts = []
        for c, opt in ((c_knob, opt_knob), (c_plain, opt_comp)):
            params, loss_fn, data = _mlp_problem(c)
            step = make_train_step(c, loss_fn, opt)
            texts.append(step.lower(
                params, init_opt_state(c, opt, params),
                put_global_batch(c, data)).as_text())
        assert "xbf16>" in texts[0]
        assert texts[0] == texts[1]

    def test_int8_trains_close_to_uncompressed(self, comm):
        l_base, _ = self._train(
            comm, chainermn_tpu.create_multi_node_optimizer(
                optax.adam(1e-2), comm), steps=12)
        l_q, _ = self._train(
            comm, chainermn_tpu.create_multi_node_optimizer(
                optax.adam(1e-2), comm, compression="int8"), steps=12)
        assert l_q[-1] < l_q[0]  # it trains
        # same trajectory within quantization tolerance
        assert abs(l_q[-1] - l_base[-1]) < 0.1 * abs(l_base[0]), (
            l_base, l_q)

    def test_rejected_combinations(self, comm):
        with pytest.raises(NotImplementedError, match="bucket_compressors"):
            chainermn_tpu.create_multi_node_optimizer(
                optax.adam(1e-3), comm, compression="int8", zero=True)
        with pytest.raises(NotImplementedError, match="error feedback"):
            chainermn_tpu.create_multi_node_optimizer(
                optax.adam(1e-3), comm, compression="int8",
                double_buffering=True)
        with pytest.raises(NotImplementedError,
                           match="allreduce_grad_dtype"):
            chainermn_tpu.create_multi_node_optimizer(
                optax.adam(1e-3), comm,
                compression=NoCompression(wire_dtype="bfloat16"),
                double_buffering=True)


# ---- the optimizer seam, per-hop: compression=<Plan> ------------------------

class TestPerHopOptimizerSeam:
    """``compression=<Plan>`` through ``create_multi_node_optimizer``:
    only the DCN hop quantizes (the ICI hops ride a bf16 wire), and the
    per-hop EF states ride the optimizer state as a stage-indexed
    dict."""

    def _train(self, comm, optimizer, steps=12):
        params, loss_fn, data = _mlp_problem(comm)
        opt_state = init_opt_state(comm, optimizer, params)
        step = make_train_step(comm, loss_fn, optimizer, donate=False)
        batch = put_global_batch(comm, data)
        losses = []
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
        return losses, params, opt_state

    def test_int8_dcn_plan_trains_and_threads_state(self):
        comm = chainermn_tpu.create_communicator("xla", intra_size=4)
        plan = compressed_two_dimensional(
            {"name": "int8", "stochastic": False})
        l_base, _, _ = self._train(
            comm, chainermn_tpu.create_multi_node_optimizer(
                optax.adam(1e-2), comm))
        l_q, _, opt_state = self._train(
            comm, chainermn_tpu.create_multi_node_optimizer(
                optax.adam(1e-2), comm, compression=plan))
        assert l_q[-1] < l_q[0]  # it trains
        # same trajectory within quantization tolerance
        assert abs(l_q[-1] - l_base[-1]) < 0.1 * abs(l_base[0]), (
            l_base, l_q)
        # exactly one EF state, keyed by the quantizing stage index,
        # tagged for the checkpoint sidecar, advanced every step
        assert set(opt_state.comp) == {1}
        cs = opt_state.comp[1]
        assert isinstance(cs, CompressionState)
        assert cs.hop == 1 and "int8" in str(cs.spec)
        assert float(np.asarray(cs.step).max()) == 12.0

    def test_plan_without_quantizing_hops_rejected(self):
        from chainermn_tpu.planner.plans import flavor_plan

        comm = chainermn_tpu.create_communicator("xla", intra_size=4)
        with pytest.raises(ValueError, match="no quantizing"):
            chainermn_tpu.create_multi_node_optimizer(
                optax.adam(1e-3), comm,
                compression=flavor_plan("two_dimensional"))

    def test_plan_composition_rejected(self):
        comm = chainermn_tpu.create_communicator("xla", intra_size=4)
        plan = compressed_two_dimensional(
            {"name": "int8", "stochastic": False})
        for kw in (dict(zero=True), dict(double_buffering=True)):
            with pytest.raises(NotImplementedError, match="per-hop"):
                chainermn_tpu.create_multi_node_optimizer(
                    optax.adam(1e-3), comm, compression=plan, **kw)


# ---- the FSDP seam ----------------------------------------------------------

class TestFsdpSeam:
    def test_int8_buckets_train_and_report_layout(self, comm):
        params, loss_fn, data = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.adam(0.01),
                                num_buckets=4, bucket_compressors="int8")
        assert all(bl.compressor for bl in meta.buckets)
        step = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01), meta,
                                    donate=False)
        batch = put_global_batch(comm, data)
        losses = []
        for _ in range(12):
            state, loss = step(state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        layout = fsdp_layout(state)
        assert "compression" in layout
        assert any("int8" in s for s in layout["compression"]["specs"])
        # the EF step counter advanced on every bucket
        for cs in state.comp:
            assert float(np.asarray(cs.step).max()) == 12.0

    def test_mixed_buckets_quantize_only_where_asked(self, comm):
        """Per-bucket config: one int8 bucket, the rest on a plain f32
        wire — and the step still trains."""
        params, loss_fn, data = _mlp_problem(comm)
        state, meta = fsdp_init(
            comm, params, optax.adam(0.01), num_buckets=2,
            bucket_compressors=["int8", None])
        assert meta.buckets[0].compressor and not meta.buckets[1].compressor
        step = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01), meta,
                                    donate=False)
        batch = put_global_batch(comm, data)
        losses = []
        for _ in range(8):
            state, loss = step(state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_quantizer_with_accum_rejected(self, comm):
        params, loss_fn, _ = _mlp_problem(comm)
        _, meta = fsdp_init(comm, params, optax.adam(0.01), num_buckets=2,
                            bucket_compressors="int8")
        with pytest.raises(NotImplementedError, match="accum"):
            make_fsdp_train_step(comm, loss_fn, optax.adam(0.01), meta,
                                 donate=False, accum_steps=2)

    def test_bucket_compressors_length_mismatch_raises(self, comm):
        params, _, _ = _mlp_problem(comm)
        with pytest.raises(ValueError, match="bucket_compressors"):
            fsdp_init(comm, params, optax.adam(0.01), num_buckets=3,
                      bucket_compressors=["int8"])

    def test_wire_conflict_raises(self, comm):
        params, _, _ = _mlp_problem(comm)
        with pytest.raises(ValueError, match="wire"):
            fsdp_init(comm, params, optax.adam(0.01), num_buckets=1,
                      bucket_wire_dtypes=["float16"],
                      bucket_compressors=NoCompression(
                          wire_dtype="bfloat16"))


# ---- checkpoint guards ------------------------------------------------------

class TestCheckpointGuards:
    def _states(self, comm, **kw):
        params, loss_fn, data = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.adam(0.01),
                                num_buckets=2, **kw)
        step = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01), meta,
                                    donate=False)
        return state, meta, step, put_global_batch(comm, data)

    def test_compressed_state_roundtrips_and_continues(self, comm,
                                                       tmp_path):
        from chainermn_tpu.extensions import create_multi_node_checkpointer

        state, meta, step, batch = self._states(
            comm, bucket_compressors="int8")
        state, _ = step(state, batch)
        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "cmp")
        ckpt.save({"fsdp": state}, 1)
        restored, gen = ckpt.resume(
            jax.tree.map(jnp.zeros_like, {"fsdp": state}))
        assert gen == 1
        s2, l2 = step(restored["fsdp"], batch)
        s3, l3 = step(state, batch)
        assert float(l2) == float(l3)
        for a, b in zip(jax.tree.leaves(s2), jax.tree.leaves(s3)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_saved_compressed_live_plain_refused(self, comm, tmp_path):
        from chainermn_tpu.extensions import create_multi_node_checkpointer

        state_c, _, _, _ = self._states(comm, bucket_compressors="int8")
        state_p, _, _, _ = self._states(comm)
        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "cmp")
        ckpt.save({"fsdp": state_c}, 1)
        with pytest.raises(ValueError, match="no compression configured"):
            ckpt.resume(jax.tree.map(jnp.zeros_like, {"fsdp": state_p}))

    def test_saved_plain_live_compressed_refused(self, comm, tmp_path):
        from chainermn_tpu.extensions import create_multi_node_checkpointer

        state_c, _, _, _ = self._states(comm, bucket_compressors="int8")
        state_p, _, _, _ = self._states(comm)
        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "cmp")
        ckpt.save({"fsdp": state_p}, 1)
        with pytest.raises(ValueError, match="no compression state"):
            ckpt.resume(jax.tree.map(jnp.zeros_like, {"fsdp": state_c}))

    def test_config_mismatch_refused(self, comm, tmp_path):
        from chainermn_tpu.extensions import create_multi_node_checkpointer

        state_a, _, _, _ = self._states(comm, bucket_compressors="int8")
        state_b, _, _, _ = self._states(comm, bucket_compressors="fp8")
        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "cmp")
        ckpt.save({"fsdp": state_a}, 1)
        with pytest.raises(ValueError, match="does not match the live"):
            ckpt.resume(jax.tree.map(jnp.zeros_like, {"fsdp": state_b}))


# ---- checkpoint guards, per-hop: the "hops" sidecar -------------------------

class TestPerHopCheckpointGuards:
    """The multi-node checkpointer's compression sidecar pins WHICH plan
    stage carries WHICH codec: matched per-hop specs restore the EF
    residual of every stage exactly; a resume under a different per-hop
    spec refuses loudly instead of silently re-quantizing with stale
    residuals."""

    def _opt_state(self, comm, spec, steps=2):
        params, loss_fn, data = _mlp_problem(comm)
        plan = compressed_two_dimensional(dict(spec))
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.adam(1e-2), comm, compression=plan)
        opt_state = init_opt_state(comm, opt, params)
        step = make_train_step(comm, loss_fn, opt, donate=False)
        batch = put_global_batch(comm, data)
        for _ in range(steps):
            params, opt_state, _ = step(params, opt_state, batch)
        return opt_state, (params, step, batch)

    def test_layout_pins_stage_to_spec(self):
        comm = chainermn_tpu.create_communicator("xla", intra_size=4)
        state, _ = self._opt_state(
            comm, {"name": "int8", "stochastic": False})
        layout = compression_layout({"opt": state})
        assert layout["n_states"] == 1
        (hop,) = layout["hops"]
        assert hop.startswith("1:") and "int8" in hop

    def test_per_hop_state_roundtrips_and_continues(self, tmp_path):
        from chainermn_tpu.extensions import create_multi_node_checkpointer

        comm = chainermn_tpu.create_communicator("xla", intra_size=4)
        state, (params, step, batch) = self._opt_state(
            comm, {"name": "int8", "stochastic": False})
        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "hop")
        ckpt.save({"opt": state}, 1)
        restored, gen = ckpt.resume(
            jax.tree.map(jnp.zeros_like, {"opt": state}))
        assert gen == 1
        # the per-stage EF residual (a real, nonzero array after two
        # quantized steps) came back bit for bit
        assert float(jnp.abs(state.comp[1].ef).max()) > 0.0
        for a, b in zip(jax.tree.leaves(state),
                        jax.tree.leaves(restored["opt"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and the resumed state continues the exact trajectory
        _, s2, l2 = step(params, restored["opt"], batch)
        _, s3, l3 = step(params, state, batch)
        assert float(l2) == float(l3)
        for a, b in zip(jax.tree.leaves(s2), jax.tree.leaves(s3)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_per_hop_spec_mismatch_refused(self, tmp_path):
        from chainermn_tpu.extensions import create_multi_node_checkpointer

        comm = chainermn_tpu.create_communicator("xla", intra_size=4)
        state_a, _ = self._opt_state(
            comm, {"name": "int8", "stochastic": False})
        state_b, _ = self._opt_state(
            comm, {"name": "fp8", "stochastic": False})
        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "hop")
        ckpt.save({"opt": state_a}, 1)
        with pytest.raises(ValueError, match="does not match the live"):
            ckpt.resume(jax.tree.map(jnp.zeros_like, {"opt": state_b}))


# ---- per-channel int8 weight quantization (serving) -------------------------

class TestPerChannelInt8Weights:
    """Property tests for the serving weight codec
    (``quantize_per_channel_int8``): the round-trip error is bounded by
    half a quantization step PER CHANNEL, which is never worse — and on
    scale-skewed matrices strictly better — than one per-tensor step."""

    @pytest.mark.parametrize("seed", range(5))
    @pytest.mark.parametrize("shape", [(16, 8), (7, 33), (4, 4, 12)])
    def test_roundtrip_error_bounded_by_channel_step(self, seed, shape):
        from chainermn_tpu.compression.quantize import (
            dequantize_int8, quantize_per_channel_int8)

        rng = np.random.default_rng(seed)
        # skew channel scales over 4 orders of magnitude — the regime
        # per-tensor quantization loses small channels entirely
        scales = 10.0 ** rng.uniform(-2, 2, size=shape[-1])
        w = rng.normal(size=shape) * scales
        codes, scale = quantize_per_channel_int8(jnp.asarray(w))
        assert codes.dtype == jnp.int8
        err = np.abs(np.asarray(dequantize_int8(codes, scale)) - w)
        # |err| <= scale/2 per channel (round-to-nearest on amax/127)
        bound = np.broadcast_to(np.asarray(scale) / 2 + 1e-12, shape)
        assert (err <= bound).all(), float((err - bound).max())

    @pytest.mark.parametrize("seed", range(3))
    def test_beats_per_tensor_on_skewed_channels(self, seed):
        from chainermn_tpu.compression.quantize import (
            dequantize_int8, quantize_per_channel_int8,
            quantize_per_tensor_int8)

        rng = np.random.default_rng(100 + seed)
        scales = 10.0 ** rng.uniform(-3, 1, size=32)
        w = jnp.asarray(rng.normal(size=(64, 32)) * scales)
        cc, cs = quantize_per_channel_int8(w)
        tc, ts = quantize_per_tensor_int8(w)
        err_c = float(jnp.abs(dequantize_int8(cc, cs) - w).max())
        err_t = float(jnp.abs(dequantize_int8(tc, ts) - w).max())
        # per-channel max error also respects the PER-TENSOR bound...
        assert err_c <= float(ts) / 2 + 1e-12
        # ...and the mean error is strictly better on skewed channels
        mean_c = float(jnp.abs(dequantize_int8(cc, cs) - w).mean())
        mean_t = float(jnp.abs(dequantize_int8(tc, ts) - w).mean())
        assert mean_c < mean_t

    def test_zero_and_constant_channels(self):
        from chainermn_tpu.compression.quantize import (
            dequantize_int8, quantize_per_channel_int8)

        w = jnp.stack([jnp.zeros((8,)), jnp.full((8,), 3.0)], axis=-1)
        codes, scale = quantize_per_channel_int8(w)
        out = np.asarray(dequantize_int8(codes, scale))
        assert (out[:, 0] == 0).all()
        np.testing.assert_allclose(out[:, 1], 3.0, rtol=1e-6)


# ---- observability: compression_* family + report lane ----------------------

class TestObservability:
    @pytest.fixture(autouse=True)
    def clean(self):
        from chainermn_tpu import observability as obs
        from chainermn_tpu.observability import (
            get_registry, reset_flight_recorder)

        reset_flight_recorder()
        obs.disable()
        get_registry().reset()
        yield
        reset_flight_recorder()
        obs.disable()
        get_registry().reset()

    def test_instrumented_proxy_passes_codec_through(self, comm):
        """Regression: the observability proxy once pinned the old
        ``allreduce_grad(grads)`` signature, so ``--compression`` +
        ``--observability`` together crashed at the optimizer seam."""
        from chainermn_tpu import observability as obs
        from chainermn_tpu.observability import instrument_communicator

        obs.enable()
        icomm = instrument_communicator(comm)
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.1), icomm, compression="int8")
        params = {"w": jnp.ones((16,))}
        opt_state = init_opt_state(icomm, opt, params)
        step = make_train_step(
            icomm, lambda p, b: jnp.mean((p["w"] - b[0]) ** 2), opt,
            donate=False)
        batch = (jnp.ones((comm.size, 16)),)
        params, opt_state, loss = step(params, opt_state, batch)
        assert np.isfinite(float(loss))
        # the eager/default path through the proxy must also still work
        out = icomm.run_spmd(
            lambda g: icomm.allreduce_grad(g),
            {"w": jnp.ones((comm.size, 4))})
        assert float(out["w"][0][0]) == 1.0

    def test_disabled_observability_keeps_program_clean(self, comm):
        params, loss_fn, data = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.adam(0.01),
                                num_buckets=2, bucket_compressors="int8")
        step = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01), meta,
                                    donate=False)
        batch = put_global_batch(comm, data)
        assert "callback" not in step.lower(state, batch).as_text()

    def test_obs_report_compression_lane(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import obs_report
        finally:
            sys.path.pop(0)
        records = [
            {"kind": "metric", "name": "compression_bits_per_param",
             "labels": {"seam": "fsdp", "bucket": "0",
                        "compressor": "int8"}, "value": 8.25},
            {"kind": "metric", "name": "compression_wire_bytes_saved",
             "labels": {"seam": "fsdp", "bucket": "0",
                        "compressor": "int8"}, "value": 123456.0},
            {"kind": "metric", "name": "compression_residual_norm",
             "labels": {"seam": "fsdp", "bucket": "0",
                        "compressor": "int8"}, "value": 0.5},
        ]
        out = obs_report.compression_section(records)
        assert "int8" in out and "8.25" in out and "3.88x" in out
        empty = obs_report.compression_section([])
        assert "no compression_* metrics" in empty

    def test_obs_report_flight_compute_straggler(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import obs_report
        finally:
            sys.path.pop(0)
        dump = {"kind": "flight_dump", "rank": 0, "reason": "watchdog",
                "events": [],
                "collective_state": {
                    "last_completed": {}, "steps": 0, "event_seq": 1,
                    "ts": 0.0,
                    "open": [{"kind": "compute", "op": "compress:fsdp",
                              "op_seq": 1, "ts": 0.0, "age_s": 42.0}]}}
        path = tmp_path / "flight_0.json"
        path.write_text(json.dumps(dump))
        dumps = obs_report.load_flight_dumps([str(tmp_path)])
        section = obs_report.flight_desync_section(dumps)
        assert "compute straggler" in section
        assert "compress:fsdp" in section


# ---- the sweep as a subprocess (slow tier) ----------------------------------

@pytest.mark.slow
def test_bench_compression_sweep_runs():
    """End-to-end: the compressor x bucket sweep passes its own wire
    census asserts (>=3.5x int8 shrink, no extra collectives, barriers
    preserved) on the 8-device CPU mesh and emits valid JSON."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "bench_compression.py"),
         "--json", "--iters", "2", "--warmup", "1",
         "--layers", "4", "--width", "64",
         "--compressors", "none,none:bfloat16,int8,fp8",
         "--buckets", "1,4"],
        capture_output=True, text=True, timeout=480, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    assert len(rows) == 8
    assert all(r["census_ok"] for r in rows)
    int8 = [r for r in rows if r["compressor"] == "int8"]
    assert all(r["wire_ratio_vs_f32"] >= 3.5 for r in int8)


# ---- 2-process world: int8 EF on MNIST (acceptance criterion) ---------------

_MNIST_WORLD_WORKER = r"""
import json, os, sys
sys.path.insert(0, os.environ["CHAINERMN_TPU_REPO"])
import chainermn_tpu

chainermn_tpu.init_distributed(local_device_count=4)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chainermn_tpu.datasets import make_classification
from chainermn_tpu.models import MLP
from chainermn_tpu.optimizers import init_opt_state, make_train_step
from chainermn_tpu.training import put_global_batch

assert jax.process_count() == 2 and jax.device_count() == 8
comm = chainermn_tpu.create_communicator("hierarchical")

model = MLP(64, 10)
params0 = model.init(jax.random.key(0), jnp.zeros((1, 784)))
params0 = comm.bcast_data(params0)

# MNIST shapes, synthetic blobs (the example's no-download path); each
# controller trains on its own half so the allreduce is load-bearing
data = make_classification(n=1024, dim=784, n_classes=10, noise=4.0, seed=0)
xs = np.stack([data[i][0] for i in range(len(data))]).astype(np.float32)
ys = np.asarray([data[i][1] for i in range(len(data))], np.int32)
half = len(xs) // 2
sl = slice(comm.host_rank * half, (comm.host_rank + 1) * half)
x_local, y_local = xs[sl], ys[sl]


def loss_fn(p, batch):
    x, y = batch
    logits = model.apply(p, x)
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def train_run(compression):
    params = jax.tree.map(jnp.copy, params0)  # the step donates its args
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(1e-3), comm, compression=compression)
    opt_state = init_opt_state(comm, opt, params)
    step = make_train_step(comm, loss_fn, opt)
    batch = put_global_batch(comm, (x_local, y_local))
    losses, ef_norms = [], []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
        if compression is not None:
            ef = np.asarray(
                opt_state.comp.ef.addressable_shards[0].data, np.float32)
            ef_norms.append(float(np.linalg.norm(ef)))
    return losses, ef_norms


base, _ = train_run(None)
q, ef_norms = train_run("int8")
print("RESULT " + json.dumps({"rank": comm.host_rank, "base": base,
                              "int8": q, "ef": ef_norms}))
"""


@pytest.mark.slow
def test_two_process_mnist_int8_matches_uncompressed():
    """The acceptance run: int8-EF gradient exchange across a REAL
    2-process world (XLA cross-process collectives) tracks the
    uncompressed loss trajectory within quantization tolerance on the
    MNIST-shaped problem, stays globally synchronous (both controllers
    see the same losses), and the EF residual settles instead of
    growing."""
    from chainermn_tpu.utils.proc_world import spawn_world

    results = spawn_world(_MNIST_WORLD_WORKER, n_procs=2, local_devices=4,
                          timeout=300, repo=REPO)
    r0, r1 = results[0], results[1]
    # globally synchronous on both runs
    assert r0["base"] == pytest.approx(r1["base"], rel=1e-6)
    assert r0["int8"] == pytest.approx(r1["int8"], rel=1e-6)
    # both train, and int8 tracks the uncompressed trajectory
    assert r0["base"][-1] < r0["base"][0]
    assert r0["int8"][-1] < r0["int8"][0]
    assert abs(r0["int8"][-1] - r0["base"][-1]) < 0.1 * abs(r0["base"][0]), (
        r0["base"], r0["int8"])
    # EF residual bounded: the scale controller settles, so the tail of
    # the residual-norm series is no larger than its global peak would
    # be under divergence (strictly: last <= max, and the last quarter
    # does not exceed the first three quarters' peak)
    ef = r0["ef"]
    assert all(np.isfinite(ef))
    assert max(ef[15:]) <= max(ef[:15]), ef


# ---- 2-process world: int8 on the DCN hop only (acceptance criterion) -------

_PERHOP_WORLD_WORKER = r"""
import json, os, sys
sys.path.insert(0, os.environ["CHAINERMN_TPU_REPO"])
import chainermn_tpu

chainermn_tpu.init_distributed(local_device_count=4)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chainermn_tpu.datasets import make_classification
from chainermn_tpu.models import MLP
from chainermn_tpu.optimizers import init_opt_state, make_train_step
from chainermn_tpu.planner.plans import compressed_two_dimensional
from chainermn_tpu.training import put_global_batch

assert jax.process_count() == 2 and jax.device_count() == 8
comm = chainermn_tpu.create_communicator("hierarchical")

model = MLP(64, 10)
params0 = model.init(jax.random.key(0), jnp.zeros((1, 784)))
params0 = comm.bcast_data(params0)

data = make_classification(n=1024, dim=784, n_classes=10, noise=4.0, seed=0)
xs = np.stack([data[i][0] for i in range(len(data))]).astype(np.float32)
ys = np.asarray([data[i][1] for i in range(len(data))], np.int32)
half = len(xs) // 2
sl = slice(comm.host_rank * half, (comm.host_rank + 1) * half)
x_local, y_local = xs[sl], ys[sl]


def loss_fn(p, batch):
    x, y = batch
    logits = model.apply(p, x)
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def train_run(compression):
    params = jax.tree.map(jnp.copy, params0)  # the step donates its args
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(1e-3), comm, compression=compression)
    opt_state = init_opt_state(comm, opt, params)
    step = make_train_step(comm, loss_fn, opt)
    batch = put_global_batch(comm, (x_local, y_local))
    losses, ef_norms = [], []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
        if compression is not None:
            ef = np.asarray(
                opt_state.comp[1].ef.addressable_shards[0].data, np.float32)
            ef_norms.append(float(np.linalg.norm(ef)))
    return losses, ef_norms


# int8 on the inter (cross-process == DCN) hop, bf16 on the ICI hops
plan = compressed_two_dimensional({"name": "int8", "stochastic": False})
assert plan.stages[1].compression["name"] == "int8"
base, _ = train_run(None)
q, ef_norms = train_run(plan)
print("RESULT " + json.dumps({"rank": comm.host_rank, "base": base,
                              "int8_dcn": q, "ef": ef_norms}))
"""


@pytest.mark.slow
def test_two_process_mnist_int8_dcn_plan_matches_uncompressed():
    """The per-hop acceptance run: a plan that quantizes ONLY the
    cross-process (DCN) hop to int8 — reduce-scatter and gather stay on
    the intra bf16 wire — tracks the uncompressed loss trajectory
    within quantization tolerance across a REAL 2-process world, stays
    globally synchronous, and its single per-hop EF residual settles."""
    from chainermn_tpu.utils.proc_world import spawn_world

    results = spawn_world(_PERHOP_WORLD_WORKER, n_procs=2, local_devices=4,
                          timeout=300, repo=REPO)
    r0, r1 = results[0], results[1]
    # globally synchronous on both runs
    assert r0["base"] == pytest.approx(r1["base"], rel=1e-6)
    assert r0["int8_dcn"] == pytest.approx(r1["int8_dcn"], rel=1e-6)
    # both train, and the compressed-hop run tracks the uncompressed one
    assert r0["base"][-1] < r0["base"][0]
    assert r0["int8_dcn"][-1] < r0["int8_dcn"][0]
    assert abs(r0["int8_dcn"][-1] - r0["base"][-1]) < \
        0.1 * abs(r0["base"][0]), (r0["base"], r0["int8_dcn"])
    # the per-hop EF residual stays bounded (same settle criterion as
    # the whole-collective test above)
    ef = r0["ef"]
    assert all(np.isfinite(ef))
    assert max(ef[15:]) <= max(ef[:15]), ef

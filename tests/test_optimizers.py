"""Multi-node optimizer tests.

Reference strategy (SURVEY.md §4): grads after ``update()`` equal the mean
of per-rank grads; double buffering applies 1-step-stale averaged gradients
(first update is a zero update).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.optimizers import (
    _DoubleBufferState,
    init_opt_state,
    make_train_step,
)


@pytest.fixture
def comm():
    return chainermn_tpu.create_communicator("xla", intra_size=4)


def quad_loss(params, batch):
    # loss = 0.5 * sum((w - target)^2); grad = w - target
    (target,) = batch
    w = params["w"]
    return 0.5 * jnp.sum((w - target.mean(axis=0)) ** 2)


class TestMultiNodeOptimizer:
    def test_update_applies_mean_grad(self, comm):
        opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(1.0), comm)
        params = {"w": jnp.zeros((3,))}
        opt_state = init_opt_state(comm, opt, params)
        step = make_train_step(comm, quad_loss, opt, donate=False)
        # rank r sees target = r -> local grad = w - r = -r
        # mean grad = -3.5; sgd(lr=1) -> w = w - mean_grad = 3.5
        targets = jnp.arange(comm.size, dtype=jnp.float32).reshape(
            comm.size, 1, 1) * jnp.ones((comm.size, 1, 3))
        batch = (targets.reshape(comm.size, 3),)
        params2, _, loss = step(params, opt_state, batch)
        np.testing.assert_allclose(np.asarray(params2["w"]), 3.5, rtol=1e-6)

    def test_loss_is_global_mean(self, comm):
        opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.0), comm)
        params = {"w": jnp.zeros((1,))}
        opt_state = init_opt_state(comm, opt, params)
        step = make_train_step(comm, quad_loss, opt, donate=False)
        targets = jnp.arange(comm.size, dtype=jnp.float32).reshape(
            comm.size, 1)
        batch = (targets.reshape(comm.size, 1),)
        _, _, loss = step(params, opt_state, batch)
        expected = np.mean([0.5 * r * r for r in range(comm.size)])
        np.testing.assert_allclose(float(loss), expected, rtol=1e-6)


@pytest.mark.parametrize("flavor", [
    "naive", "flat", "hierarchical", "two_dimensional", "non_cuda_aware",
    "xla", "single_node"])
def test_train_step_compiles_for_every_flavor(flavor):
    """Regression: the FULL train step (replicated params out_spec) must
    compile and produce the mean-gradient update for every communicator
    decomposition.  two_dimensional's all_gather leg once produced
    vma-varying gradients that poisoned the replicated out_spec — caught
    only when the whole step was jitted, not by collective-level tests.
    single_node (inter_size must be 1 -> intra_size=8) once left the
    trivial inter axis's variance uncleared, failing the same check on
    1-device worlds."""
    comm = chainermn_tpu.create_communicator(
        flavor, intra_size=8 if flavor == "single_node" else 4)
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(1.0), comm)
    params = {"w": jnp.zeros((3,))}
    opt_state = init_opt_state(comm, opt, params)
    step = make_train_step(comm, quad_loss, opt, donate=False)
    targets = jnp.arange(comm.size, dtype=jnp.float32).reshape(
        comm.size, 1, 1) * jnp.ones((comm.size, 1, 3))
    batch = (targets.reshape(comm.size, 3),)
    params2, _, loss = step(params, opt_state, batch)
    np.testing.assert_allclose(np.asarray(params2["w"]), 3.5, rtol=1e-5)


@pytest.mark.parametrize("flavor", [
    "naive", "flat", "hierarchical", "two_dimensional", "non_cuda_aware",
    "xla", "single_node"])
def test_train_step_compiles_on_one_device_world(flavor):
    """A 1-device world (the real single-TPU-chip deployment, exercised by
    tools/tpu_smoke.py) builds a (1, 1) mesh where every collective is an
    identity — but the variance types still have to be cleared for the
    replicated out_specs.  single_node once failed exactly here."""
    from chainermn_tpu.parallel.topology import init_topology

    topo = init_topology(devices=jax.devices()[:1])
    comm = chainermn_tpu.create_communicator(flavor, topology=topo)
    assert comm.size == 1
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(1.0), comm, double_buffering=True)
    params = {"w": jnp.zeros((3,))}
    opt_state = init_opt_state(comm, opt, params)
    step = make_train_step(comm, quad_loss, opt, donate=False)
    batch = (jnp.ones((1, 3)),)
    params1, opt_state, _ = step(params, opt_state, batch)
    params2, _, _ = step(params1, opt_state, batch)
    # double-buffered semantics hold even at world size 1: step 1 applies
    # zeros, step 2 applies step-1 grads (grad = w - 1 = -1 -> w = 1)
    np.testing.assert_allclose(np.asarray(params1["w"]), 0.0, atol=1e-7)
    np.testing.assert_allclose(np.asarray(params2["w"]), 1.0, rtol=1e-6)


@pytest.mark.parametrize("double_buffering", [True, False])
def test_cpu_mesh_step_is_jitted_with_no_compile_options(double_buffering):
    """``exchange_compiler_options`` names options of the TPU compiler, and
    the CPU compiler refuses a name it does not know; so on a CPU mesh of
    four devices the communicator gives none, ``make_train_step`` hands
    ``jax.jit`` none, and the step compiles and reduces as it always did
    (every multi-device test of this suite stands on that)."""
    import unittest.mock

    from chainermn_tpu.parallel.topology import init_topology

    comm = chainermn_tpu.create_communicator(
        "xla", topology=init_topology(devices=jax.devices()[:4]),
        allreduce_grad_dtype="bfloat16")
    assert comm.size == 4 and comm.exchange_compiler_options() is None
    with pytest.raises(Exception, match="No such compile option"):
        jax.jit(lambda x: x + 1, compiler_options={
            "xla_tpu_enable_async_collective_fusion": True,
        }).lower(jnp.zeros(3)).compile()
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(1.0), comm, double_buffering=double_buffering)
    with unittest.mock.patch.object(jax, "jit", wraps=jax.jit) as jit:
        step = make_train_step(comm, quad_loss, opt, donate=False)
    (_, keywords), = jit.call_args_list
    assert keywords["compiler_options"] is None
    params = {"w": jnp.zeros((3,))}
    opt_state = init_opt_state(comm, opt, params)
    batch = (jnp.arange(4.0)[:, None] * jnp.ones((4, 3)),)
    for _ in range(2 if double_buffering else 1):
        params, opt_state, _ = step(params, opt_state, batch)
    # mean gradient of ranks 0..3 at w = 0 is -1.5 (one step late under
    # the double buffer)
    np.testing.assert_allclose(np.asarray(params["w"]), 1.5, rtol=1e-6)


class TestDoubleBuffering:
    def test_one_step_staleness_exact(self, comm):
        """The fork's signature semantics (SURVEY.md §3.4): update t applies
        averaged grads of t-1; update 0 applies zeros."""
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(1.0), comm, double_buffering=True)
        params = {"w": jnp.zeros((3,))}
        opt_state = init_opt_state(comm, opt, params)
        assert isinstance(opt_state, _DoubleBufferState)
        step = make_train_step(comm, quad_loss, opt, donate=False)

        targets = jnp.arange(comm.size, dtype=jnp.float32).reshape(
            comm.size, 1) * jnp.ones((comm.size, 3))
        batch = (targets,)
        # step 1: pending=0 -> zero update; w stays 0; pending <- grads(w=0)
        params1, opt_state, _ = step(params, opt_state, batch)
        np.testing.assert_allclose(np.asarray(params1["w"]), 0.0, atol=1e-7)
        # step 2: applies mean grads from step 1: grad_r = w - r = -r,
        # mean = -3.5 -> w = 3.5
        params2, opt_state, _ = step(params1, opt_state, batch)
        np.testing.assert_allclose(np.asarray(params2["w"]), 3.5, rtol=1e-6)
        # step 3: applies grads computed at step 2 (w=0 still at compute
        # time... w was 0 -> same grads) -> w = 3.5 + 3.5 = 7? No: grads at
        # step 2 were computed at w=0 BEFORE update (update uses step-1
        # grads) -> pending at step 3 = -3.5 again -> w = 7.0
        params3, _, _ = step(params2, opt_state, batch)
        np.testing.assert_allclose(np.asarray(params3["w"]), 7.0, rtol=1e-6)

    def test_state_counter_advances(self, comm):
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.adam(1e-3), comm, double_buffering=True)
        params = {"w": jnp.ones((2, 2))}
        opt_state = init_opt_state(comm, opt, params)
        step = make_train_step(
            comm, lambda p, b: jnp.sum(p["w"] ** 2) + 0.0 * b[0].sum(),
            opt, donate=False)
        batch = (jnp.ones((comm.size, 1)),)
        _, opt_state2, _ = step(params, opt_state, batch)
        assert int(opt_state2.step) == 1

    def test_pending_sharded_over_devices(self, comm):
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.1), comm, double_buffering=True)
        params = {"w": jnp.ones((4,))}
        state = init_opt_state(comm, opt, params)
        leaf = state.pending["w"]
        assert leaf.shape == (comm.size, 4)
        assert not leaf.sharding.is_fully_replicated


@pytest.mark.parametrize("build", [
    lambda comm, opt: chainermn_tpu.create_communicator(
        "xla", intra_size=4, use_pallas_cast=True),
    lambda comm, opt: make_train_step(comm, quad_loss, opt, scan_steps=2),
    lambda comm, opt: __import__("chainermn_tpu.models").models.ResNet50(
        stem="s2d"),
], ids=["use_pallas_cast", "scan_steps", "stem"])
def test_removed_options_are_rejected(comm, build):
    """The exchange has one lowering, the step one loop and ResNet one
    stem (PERF.md, PR 28): the options that selected another are gone,
    not ignored."""
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
    with pytest.raises(TypeError, match="unexpected keyword"):
        build(comm, opt)


class TestConvergence:
    def test_training_reduces_loss(self, comm):
        """End-to-end sanity: a tiny MLP learns a separable problem."""
        import flax.linen as nn

        model = nn.Dense(4)
        key = jax.random.key(0)
        xs = jax.random.normal(key, (64, 8))
        w_true = jax.random.normal(jax.random.key(1), (8, 4))
        ys = xs @ w_true
        params = model.init(key, xs[:1])
        params = comm.bcast_data(params)

        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((model.apply(p, x) - y) ** 2)

        opt = chainermn_tpu.create_multi_node_optimizer(optax.adam(0.1), comm)
        opt_state = init_opt_state(comm, opt, params)
        step = make_train_step(comm, loss_fn, opt)
        losses = []
        for _ in range(80):
            params, opt_state, loss = step(params, opt_state, (xs, ys))
            losses.append(float(loss))
        assert losses[-1] < 0.05 * losses[0]


class TestZero1Optimizer:
    """ZeRO-1 optimizer-state sharding (beyond-reference extension)."""

    def _train(self, comm, make_opt, steps=6):
        import numpy as np
        from chainermn_tpu.models import MLP
        from chainermn_tpu.training import put_global_batch

        model = MLP(n_units=16, n_out=4)
        params = model.init(jax.random.key(0), jnp.zeros((1, 8)))["params"]
        params = comm.bcast_data(params)
        optimizer = make_opt()
        opt_state = init_opt_state(comm, optimizer, params)

        def loss_fn(p, batch):
            x, y = batch
            logits = model.apply({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        step = make_train_step(comm, loss_fn, optimizer)
        rng = np.random.RandomState(0)
        x = rng.randn(32, 8).astype(np.float32)
        y = (rng.rand(32) * 4).astype(np.int32)
        batch = put_global_batch(comm, (x, y))
        losses = []
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
        return losses, params, opt_state

    def test_matches_unsharded_adam(self, comm):
        import chainermn_tpu

        base, base_params, _ = self._train(
            comm, lambda: chainermn_tpu.create_multi_node_optimizer(
                optax.adam(5e-2), comm))
        zero, zero_params, _ = self._train(
            comm, lambda: chainermn_tpu.create_multi_node_optimizer(
                optax.adam(5e-2), comm, zero=True))
        # identical math up to reduce-scatter/gather float reassociation
        assert zero == pytest.approx(base, rel=1e-5)
        for a, b in zip(jax.tree.leaves(base_params),
                        jax.tree.leaves(zero_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_state_is_sharded_per_device(self, comm):
        import chainermn_tpu
        from chainermn_tpu.optimizers import _ZeroState

        _, params, opt_state = self._train(
            comm, lambda: chainermn_tpu.create_multi_node_optimizer(
                optax.adam(5e-2), comm, zero=True), steps=1)
        assert isinstance(opt_state, _ZeroState)
        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree.leaves(params))
        # Adam m/v buffers: stacked [size, ceil(G/size)] — each DEVICE
        # holds ~G/size state per buffer, not G
        flat_leaves = [l for l in jax.tree.leaves(opt_state.inner)
                       if l.ndim == 2]
        assert flat_leaves, "expected flat shard buffers in the state"
        for leaf in flat_leaves:
            assert leaf.shape[0] == comm.size
            assert leaf.shape[1] <= (n_params + comm.size) // comm.size

    def test_zero_and_double_buffering_exclusive(self, comm):
        import chainermn_tpu

        with pytest.raises(ValueError, match="mutually"):
            chainermn_tpu.create_multi_node_optimizer(
                optax.adam(1e-2), comm, double_buffering=True, zero=True)

    def test_matches_unsharded_adamw(self, comm):
        """adamw's weight decay READS params, so this pins the params-shard
        alignment (reduce_scatter ordering vs axis_index slicing) that a
        params-ignoring optimizer like adam never exercises."""
        import chainermn_tpu

        base, base_params, _ = self._train(
            comm, lambda: chainermn_tpu.create_multi_node_optimizer(
                optax.adamw(5e-2, weight_decay=1e-2), comm))
        zero, zero_params, _ = self._train(
            comm, lambda: chainermn_tpu.create_multi_node_optimizer(
                optax.adamw(5e-2, weight_decay=1e-2), comm, zero=True))
        assert zero == pytest.approx(base, rel=1e-5)
        for a, b in zip(jax.tree.leaves(base_params),
                        jax.tree.leaves(zero_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_honors_wire_dtype(self, comm_xla_bf16=None):
        """zero=True must route gradients through the communicator's
        allreduce_grad_dtype exactly like allreduce_grad does."""
        import chainermn_tpu

        c = chainermn_tpu.create_communicator(
            "xla", allreduce_grad_dtype="bfloat16")
        base, _, _ = self._train(
            c, lambda: chainermn_tpu.create_multi_node_optimizer(
                optax.adam(5e-2), c), steps=3)
        zero, _, _ = self._train(
            c, lambda: chainermn_tpu.create_multi_node_optimizer(
                optax.adam(5e-2), c, zero=True), steps=3)
        # both paths quantize grads to bf16 on the wire -> same curve
        # within bf16 tolerance of each other
        assert zero == pytest.approx(base, rel=5e-3)


def sample_mean_loss(params, batch):
    # per-SAMPLE mean loss (grad accumulation's equivalence class: the
    # average of equal-slice microbatch means equals the full-shard mean)
    (t,) = batch
    return 0.5 * jnp.mean(jnp.sum((params["w"] - t) ** 2, axis=-1))


class TestAccumSteps:
    """``accum_steps=K`` microbatches the local shard and averages the K
    gradients before the single allreduce+update — numerically the same
    step as ``accum_steps=1`` at ~1/K the activation memory."""

    def _batch(self, comm, per_dev=8):
        rng = np.random.RandomState(0)
        return (jnp.asarray(
            rng.randn(comm.size * per_dev, 3).astype(np.float32)),)

    @pytest.mark.parametrize("wrapper", ["plain", "double_buffering", "zero"])
    def test_accum_matches_full_batch(self, comm, wrapper):
        def make(accum_steps):
            opt = chainermn_tpu.create_multi_node_optimizer(
                optax.adam(0.05), comm,
                double_buffering=wrapper == "double_buffering",
                zero=wrapper == "zero")
            params = {"w": jnp.zeros((3,))}
            state = init_opt_state(comm, opt, params)
            step = make_train_step(comm, sample_mean_loss, opt,
                                   donate=False, accum_steps=accum_steps)
            return params, state, step

        batch = self._batch(comm)
        params_a, state_a, step_a = make(1)
        params_b, state_b, step_b = make(4)
        for _ in range(3):
            params_a, state_a, loss_a = step_a(params_a, state_a, batch)
            params_b, state_b, loss_b = step_b(params_b, state_b, batch)
        np.testing.assert_allclose(np.asarray(params_b["w"]),
                                   np.asarray(params_a["w"]),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(float(loss_b), float(loss_a), rtol=1e-6)

    def test_accum_with_aux(self, comm):
        def loss_fn(params, batch):
            (t,) = batch
            loss = 0.5 * jnp.mean(jnp.sum((params["w"] - t) ** 2, axis=-1))
            return loss, {"tmean": t.mean()}

        def make(accum_steps):
            opt = chainermn_tpu.create_multi_node_optimizer(
                optax.sgd(0.1), comm)
            params = {"w": jnp.zeros((3,))}
            state = init_opt_state(comm, opt, params)
            return params, state, make_train_step(
                comm, loss_fn, opt, donate=False, has_aux=True,
                accum_steps=accum_steps)

        batch = self._batch(comm)
        pa, sa, step_a = make(1)
        pb, sb, step_b = make(4)
        _, _, loss_a, aux_a = step_a(pa, sa, batch)
        _, _, loss_b, aux_b = step_b(pb, sb, batch)
        np.testing.assert_allclose(float(aux_b["tmean"]),
                                   float(aux_a["tmean"]), rtol=1e-6)
        np.testing.assert_allclose(float(loss_b), float(loss_a), rtol=1e-6)

    def test_accum_with_model_state(self, comm):
        """model_state advances once per MICROBATCH (sequential-BN
        semantics, documented)."""
        def loss_fn(params, state, batch):
            (t,) = batch
            loss = 0.5 * jnp.mean(jnp.sum((params["w"] - t) ** 2, axis=-1))
            return loss, {"count": state["count"] + 1}

        from chainermn_tpu.optimizers import init_model_state

        opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
        params = {"w": jnp.zeros((3,))}
        mstate = init_model_state(comm, {"count": jnp.zeros(())})
        state = init_opt_state(comm, opt, params)
        step = make_train_step(comm, loss_fn, opt, donate=False,
                               with_model_state=True, accum_steps=4)
        params, mstate, state, loss = step(params, mstate, state,
                                           self._batch(comm))
        np.testing.assert_allclose(np.asarray(mstate["count"]), 4.0)

    def test_bad_accum_rejected(self, comm):
        opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
        params = {"w": jnp.zeros((3,))}
        state = init_opt_state(comm, opt, params)
        with pytest.raises(ValueError, match="accum_steps"):
            make_train_step(comm, sample_mean_loss, opt, accum_steps=0)
        step = make_train_step(comm, sample_mean_loss, opt, donate=False,
                               accum_steps=3)
        with pytest.raises(ValueError, match="divide"):
            step(params, state, self._batch(comm, per_dev=8))


class TestLargeBatchRecipe:
    """LARS + warmup-cosine — the large-global-batch recipe the reference
    lineage's 15-min-ImageNet result evolved into — composes with the
    multi-node wrappers."""

    @pytest.mark.parametrize("double_buffering", [False, True])
    def test_lars_trains_through_multi_node(self, comm, double_buffering):
        import flax.linen as nn

        model = nn.Dense(4)
        xs = np.random.RandomState(0).randn(comm.size * 8, 8).astype(
            np.float32)
        ys = xs @ np.random.RandomState(1).randn(8, 4).astype(np.float32)
        params = comm.bcast_data(model.init(jax.random.key(0), xs[:1]))

        schedule = optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=0.5, warmup_steps=3, decay_steps=20)
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.lars(schedule, momentum=0.9), comm,
            double_buffering=double_buffering)
        state = init_opt_state(comm, opt, params)

        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((model.apply(p, x) - y) ** 2)

        step = make_train_step(comm, loss_fn, opt, donate=False)
        from chainermn_tpu.training import put_global_batch

        batch = put_global_batch(comm, (xs, ys))
        losses = []
        for _ in range(12):
            params, state, loss = step(params, state, batch)
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        # double buffering sees zero grads at step 0; compare after warmup
        assert losses[-1] < losses[3]

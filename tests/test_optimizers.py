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


# ---- the pending gradients are held in the wire's dtype (PR 46) -------------

def _wire_comm(devices, **wire):
    from chainermn_tpu.parallel.topology import init_topology

    return chainermn_tpu.create_communicator(
        "xla", topology=init_topology(devices=jax.devices()[:devices]),
        **wire)


def _toy_params():
    keys = jax.random.split(jax.random.key(3), 3)
    return {"in": 0.5 * jax.random.normal(keys[0], (8, 16)),
            "bias": 0.1 * jax.random.normal(keys[1], (16,)),
            "out": 0.5 * jax.random.normal(keys[2], (16, 4))}


def _toy_loss(params, batch):
    x, y = batch
    hidden = jnp.tanh(x @ params["in"] + params["bias"])
    return jnp.mean((hidden @ params["out"] - y) ** 2)


def _toy_batches(comm, steps, rows=2):
    """A batch a step, each rank's rows its own (so that the mean over the
    ranks is a real sum: on 3 of them 1/3 is no power of two)."""
    keys = jax.random.split(jax.random.key(11), 2 * steps)
    sharding = NamedSharding(comm.mesh, P(comm.data_axes))
    return [tuple(jax.device_put(3.0 * jax.random.normal(k, shape), sharding)
                  for k, shape in ((keys[2 * i], (rows * comm.size, 8)),
                                   (keys[2 * i + 1], (rows * comm.size, 4))))
            for i in range(steps)]


def _float32_pending_step(comm, wire, tx):
    """The double-buffered step as it was before PR 46, written plainly:
    ``pending`` is float32, the exchange casts it to the wire at the READ,
    sums it there, casts back and scales in float32; the update applies that
    mean; the fresh local gradients, taken at the parameters the step was
    given, are stored as they are."""
    from chainermn_tpu.utils import pvary

    axes = comm.data_axes
    scale = 1.0 / comm.size

    def body(params, inner, pending, batch):
        mean = jax.tree.map(
            lambda g: jax.lax.psum(g[0].astype(wire), axes).astype(g.dtype)
            * jnp.asarray(scale, g.dtype), pending)
        loss, grads = jax.value_and_grad(_toy_loss)(
            jax.tree.map(lambda p: pvary(p, axes), params), batch)
        updates, inner = tx.update(mean, inner, params)
        return (optax.apply_updates(params, updates), inner,
                jax.tree.map(lambda g: g[None], grads),
                jax.lax.psum(loss, axes) / comm.size)

    return jax.jit(jax.shard_map(
        body, mesh=comm.mesh, in_specs=(P(), P(), P(axes), P(axes)),
        out_specs=(P(), P(), P(axes), P())))


def _run_float32_pending(comm, wire, tx, batches):
    """``[(params, momentum, loss)]`` a step, and the state after the last."""
    params, inner = _toy_params(), tx.init(_toy_params())
    pending = jax.tree.map(
        lambda p: jnp.zeros((comm.size,) + p.shape, p.dtype), params)
    step = _float32_pending_step(comm, wire, tx)
    trail = []
    for batch in batches:
        params, inner, pending, loss = step(params, inner, pending, batch)
        trail.append((params, inner[0].trace, loss))
    return trail, (params, inner, pending)


class _Float32PendingOptimizer(
        chainermn_tpu.optimizers._DoubleBufferingOptimizer):
    """The optimizer's two methods as they were before PR 46: ``pending``
    is the gradients as they are, and the exchange is handed that."""

    def init(self, params):
        return _DoubleBufferState(
            inner=self.actual_optimizer.init(params),
            pending=jax.tree.map(jnp.zeros_like, params),
            step=jnp.zeros((), jnp.int32))

    def update(self, grads, state, params=None, **kwargs):
        comm_grads = self.communicator.allreduce_grad(state.pending)
        with jax.named_scope("chainermn.update"):
            updates, inner = self.actual_optimizer.update(
                comm_grads, state.inner, params, **kwargs)
        return updates, _DoubleBufferState(
            inner=inner, pending=grads, step=state.step + 1)


def _assert_same_bits(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _held_dtypes(opt_state):
    return {name: str(leaf.dtype) for name, leaf in opt_state.pending.items()}


class TestPendingInTheWireDtype:
    @pytest.mark.parametrize("wire", ["bfloat16", "float16"])
    @pytest.mark.parametrize("devices", [1, 3, 4, 8])
    def test_five_steps_equal_a_float32_pending_bit_for_bit(
            self, devices, wire):
        """Rounding ``pending`` to the wire's dtype where it is written
        gives the update the values that rounding it where it is read gave:
        parameters, momentum and loss of five steps on 1, 3, 4 and 8 devices
        (3: a mean that is no power of two, so a scale that multiplied in
        the wire's dtype would show).  The matrices of ``pending`` are held
        in the wire's dtype, stacked a device a slice; a vector keeps the
        parameters' dtype."""
        comm = _wire_comm(devices, allreduce_grad_dtype=wire)
        tx = optax.sgd(0.05, momentum=0.9)
        batches = _toy_batches(comm, 5)
        want, _ = _run_float32_pending(comm, jnp.dtype(wire), tx, batches)

        opt = chainermn_tpu.create_multi_node_optimizer(
            tx, comm, double_buffering=True)
        params = _toy_params()
        opt_state = init_opt_state(comm, opt, params)
        step = make_train_step(comm, _toy_loss, opt, donate=False)
        for batch, step_wanted in zip(batches, want):
            params, opt_state, loss = step(params, opt_state, batch)
            _assert_same_bits(
                (params, opt_state.inner[0].trace, loss), step_wanted)
            assert _held_dtypes(opt_state) == {
                "in": wire, "out": wire, "bias": "float32"}
            for name, leaf in opt_state.pending.items():
                assert leaf.shape == (devices,) + params[name].shape
                assert leaf.sharding.is_equivalent_to(
                    NamedSharding(comm.mesh, P(comm.data_axes)), leaf.ndim)
        _assert_same_bits(params, want[-1][0])
        assert any(np.any(np.asarray(a) != np.asarray(b)) for a, b in zip(
            jax.tree.leaves(params), jax.tree.leaves(_toy_params())))

    @pytest.mark.parametrize("devices", [3, 4])
    def test_accumulated_microbatches_round_once(self, devices):
        """``accum_steps=2`` averages its two gradients in float32 and the
        state's write rounds the average once, as the exchange's read did:
        equal to the step that keeps a float32 ``pending``, bit for bit."""
        comm = _wire_comm(devices, allreduce_grad_dtype="bfloat16")
        tx = optax.sgd(0.05, momentum=0.9)
        batches = _toy_batches(comm, 4, rows=4)
        trails = []
        for opt in (
                _Float32PendingOptimizer(tx, comm),
                chainermn_tpu.create_multi_node_optimizer(
                    tx, comm, double_buffering=True)):
            params = _toy_params()
            opt_state = init_opt_state(comm, opt, params)
            step = make_train_step(comm, _toy_loss, opt, donate=False,
                                   accum_steps=2)
            trail = []
            for batch in batches:
                params, opt_state, loss = step(params, opt_state, batch)
                trail.append((params, opt_state.inner, loss))
            trails.append(trail)
            held = _held_dtypes(opt_state)
        assert held == {"in": "bfloat16", "out": "bfloat16",
                        "bias": "float32"}
        _assert_same_bits(trails[1], trails[0])

    @pytest.mark.parametrize("wire,expected", [
        ({"allreduce_grad_dtype": "bfloat16"}, "bfloat16"),
        ({"allreduce_grad_dtype": "float16"}, "float16"),
        ({"compression": "bfloat16"}, "bfloat16"),
        ({}, None)])
    def test_pending_dtype_is_the_wires_else_the_parameters(
            self, wire, expected):
        """``init`` reads the dtype off the communicator: its
        ``allreduce_grad_dtype`` (a communicator's ``NoCompression(wire)``
        folds into it) for every leaf of two dimensions or more, each
        parameter's own for a vector, and for all where there is none.  A
        step keeps it: the state that comes out goes back in."""
        from chainermn_tpu.compression import NoCompression

        if "compression" in wire:
            wire = {"compression": NoCompression(wire["compression"])}
        comm = _wire_comm(4, **wire)
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.1), comm, double_buffering=True)
        params = {"w": jnp.ones((4, 2)), "filters": jnp.ones((2, 2, 3, 3)),
                  "half": jnp.ones((3, 2), jnp.bfloat16),
                  "other_half": jnp.ones((3, 2), jnp.float16),
                  "bias": jnp.ones((3,)), "scale": jnp.ones((), jnp.bfloat16)}
        for state in (opt.init(params), init_opt_state(comm, opt, params)):
            for name, leaf in state.pending.items():
                want = expected if expected and params[name].ndim >= 2 \
                    else params[name].dtype
                assert leaf.dtype == jnp.dtype(want), (name, leaf.dtype)
        step = make_train_step(
            comm, lambda p, b: sum(jnp.sum(v.astype(jnp.float32) ** 2)
                                   for v in p.values()) + 0.0 * b[0].sum(),
            opt, donate=False)
        state = init_opt_state(comm, opt, params)
        new_params, new_state, _ = step(params, state, (jnp.ones((4, 1)),))
        assert jax.tree.map(lambda a: (a.shape, a.dtype), new_state) == \
            jax.tree.map(lambda a: (a.shape, a.dtype), state)
        assert jax.tree.map(lambda a: a.dtype, new_params) == \
            jax.tree.map(lambda a: a.dtype, params)

    @pytest.mark.parametrize("accum_steps", [1, 2])
    def test_without_a_wire_dtype_the_step_traces_what_it_traced(
            self, accum_steps):
        """A communicator with no wire dtype bypasses all of it: ``pending``
        keeps the parameters' dtype and the step's jaxpr is the one that the
        optimizer's methods as they were before PR 46 trace, to the letter
        (no ``convert_element_type`` of a gradient, no select)."""
        comm = _wire_comm(4)
        tx = optax.sgd(0.05, momentum=0.9)
        (batch,) = _toy_batches(comm, 1, rows=2 * accum_steps)
        texts = []
        for opt in (
                _Float32PendingOptimizer(tx, comm),
                chainermn_tpu.create_multi_node_optimizer(
                    tx, comm, double_buffering=True)):
            opt_state = init_opt_state(comm, opt, _toy_params())
            assert set(_held_dtypes(opt_state).values()) == {"float32"}
            step = make_train_step(comm, _toy_loss, opt, donate=False,
                                   accum_steps=accum_steps)
            texts.append(str(step.trace(
                _toy_params(), opt_state, batch).jaxpr))
        assert texts[1] == texts[0]
        assert "convert_element_type" not in texts[1]
        assert "select_n" not in texts[1]

    def test_step_zero_applies_zeros_and_stores_rounded_gradients(self):
        comm = _wire_comm(4, allreduce_grad_dtype="bfloat16")
        tx = optax.sgd(0.05, momentum=0.9)
        opt = chainermn_tpu.create_multi_node_optimizer(
            tx, comm, double_buffering=True)
        params = _toy_params()
        opt_state = init_opt_state(comm, opt, params)
        step = make_train_step(comm, _toy_loss, opt, donate=False)
        (batch,) = _toy_batches(comm, 1)
        params1, state1, _ = step(params, opt_state, batch)
        _assert_same_bits(params1, params)
        _assert_same_bits(state1.inner, jax.tree.map(
            jnp.zeros_like, state1.inner))
        # the local gradients the plain float32 step stored, rounded
        _, (_, _, local) = _run_float32_pending(
            comm, jnp.bfloat16, tx, [batch])
        _assert_same_bits(state1.pending, jax.tree.map(
            lambda g: g.astype(jnp.bfloat16 if g.ndim > 2 else g.dtype),
            local))
        assert all(float(jnp.abs(g.astype(jnp.float32)).max()) > 0
                   for g in jax.tree.leaves(state1.pending))

    @pytest.mark.parametrize("model,new,old", [
        ("matrices", 0, 2), ("with_a_bias", 1, 3)])
    def test_wire_casts_counts_what_stands_before_the_exchange(
            self, model, new, old):
        """``all_reduce_overlap_census``'s ``wire_casts``, on the lowered
        step: none where every leaf of ``pending`` is held in the wire's
        dtype (a vector still brings its own), one a leaf where the
        exchange is handed float32 gradients."""
        from chainermn_tpu.analysis import all_reduce_overlap_census

        comm = _wire_comm(4, allreduce_grad_dtype="bfloat16")
        tx = optax.sgd(0.05, momentum=0.9)
        params = _toy_params()
        if model == "matrices":
            del params["bias"]
        loss = lambda p, b: _toy_loss(dict({"bias": jnp.zeros(16)}, **p), b)
        (batch,) = _toy_batches(comm, 1)
        counts = []
        for opt in (
                chainermn_tpu.create_multi_node_optimizer(
                    tx, comm, double_buffering=True),
                _Float32PendingOptimizer(tx, comm)):
            step = make_train_step(comm, loss, opt, donate=False)
            lowered = step.lower(
                params, init_opt_state(comm, opt, params), batch)
            for text in (lowered.as_text(dialect="hlo", debug_info=True),
                         lowered.compile().as_text()):
                counts.append(all_reduce_overlap_census(text)["wire_casts"])
        assert counts[0] == new and counts[2] == old, counts
        # the CPU's compiler keeps them where they are
        assert counts[1] == new and counts[3] == old, counts

    @pytest.mark.parametrize("backend", ["npz", "orbax"])
    def test_a_float32_pending_checkpoint_resumes_bit_for_bit(
            self, backend, tmp_path):
        """A run saved before PR 46 holds ``pending`` in float32.  Resumed
        into today's state it is cast on load (what the exchange would have
        done to it at its first read) and the run goes on as the run that
        was never interrupted: three steps, a save, three more."""
        from chainermn_tpu.extensions import create_multi_node_checkpointer

        if backend == "orbax":
            pytest.importorskip("orbax.checkpoint")
        comm = _wire_comm(4, allreduce_grad_dtype="bfloat16")
        tx = optax.sgd(0.05, momentum=0.9)
        batches = _toy_batches(comm, 6)
        want, _ = _run_float32_pending(comm, jnp.bfloat16, tx, batches)
        _, (params, inner, pending) = _run_float32_pending(
            comm, jnp.bfloat16, tx, batches[:3])
        assert pending["in"].dtype == jnp.float32
        ckpt = create_multi_node_checkpointer(
            comm, str(tmp_path), "old", backend=backend)
        ckpt.save({"params": params, "opt_state": _DoubleBufferState(
            inner=inner, pending=pending,
            step=jnp.asarray(3, jnp.int32))}, iteration=3)
        ckpt.finalize()

        opt = chainermn_tpu.create_multi_node_optimizer(
            tx, comm, double_buffering=True)
        blank = {"params": jax.device_put(
                     _toy_params(), NamedSharding(comm.mesh, P())),
                 "opt_state": init_opt_state(comm, opt, _toy_params())}
        restored, generation = ckpt.resume(blank)
        assert generation == 3
        opt_state = restored["opt_state"]
        assert isinstance(opt_state, _DoubleBufferState)
        for leaf, live in zip(jax.tree.leaves(opt_state.pending),
                              jax.tree.leaves(blank["opt_state"].pending)):
            assert leaf.dtype == live.dtype
            assert leaf.sharding.is_equivalent_to(live.sharding, leaf.ndim)
        assert _held_dtypes(opt_state) == {
            "in": "bfloat16", "out": "bfloat16", "bias": "float32"}
        _assert_same_bits(opt_state.pending, jax.tree.map(
            lambda g, live: g.astype(live.dtype), pending,
            blank["opt_state"].pending))
        step = make_train_step(comm, _toy_loss, opt, donate=False)
        params = restored["params"]
        for batch, step_wanted in zip(batches[3:], want[3:]):
            params, opt_state, loss = step(params, opt_state, batch)
            _assert_same_bits(
                (params, opt_state.inner[0].trace, loss), step_wanted)

    def test_a_wire_dtype_pending_checkpoint_round_trips(self, tmp_path):
        """An npz keeps no bfloat16 (numpy reads it back as two-byte void):
        the live leaf's dtype restores it, bit for bit; read into a state
        that holds the leaf in four bytes it is refused by name."""
        from chainermn_tpu.extensions import create_multi_node_checkpointer

        comm = _wire_comm(4, allreduce_grad_dtype="bfloat16")
        tx = optax.sgd(0.05, momentum=0.9)
        opt = chainermn_tpu.create_multi_node_optimizer(
            tx, comm, double_buffering=True)
        params = _toy_params()
        opt_state = init_opt_state(comm, opt, params)
        step = make_train_step(comm, _toy_loss, opt, donate=False)
        for batch in _toy_batches(comm, 2):
            params, opt_state, _ = step(params, opt_state, batch)
        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "new")
        ckpt.save(opt_state, iteration=2)
        restored, _ = ckpt.resume(init_opt_state(comm, opt, params))
        _assert_same_bits(restored, opt_state)
        with pytest.raises(ValueError, match=r"leaf_\d+ \(\.pending\['in'\]"):
            ckpt.resume(init_opt_state(
                comm, _Float32PendingOptimizer(tx, comm), params))

    def test_an_exchange_says_its_results_dtypes(self):
        """``allreduce_grad(like=)``: leaves that arrive in the wire's dtype
        come back in ``like``'s, cast back BEFORE the scale, through each of
        the compiler's three lowerings (over the leaves, over a packed
        buffer, leaf by leaf), eagerly, and through a quantizer (which is
        handed them widened)."""
        from chainermn_tpu.planner.compiler import execute_plan
        from chainermn_tpu.planner.plans import flavor_plan

        comm = _wire_comm(3, allreduce_grad_dtype="bfloat16")
        grads = {"w": jax.random.normal(jax.random.key(5), (3, 5, 3)),
                 "b": jax.random.normal(jax.random.key(6), (3, 7))}
        rounded = jax.tree.map(
            lambda g: g.astype(jnp.bfloat16).astype(g.dtype), grads)
        held = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
        want = comm.run_spmd(comm.allreduce_grad, grads)
        got = comm.run_spmd(
            lambda h, g: comm.allreduce_grad(h, like=g), held, grads)
        _assert_same_bits(got, want)
        for name in ("two_dimensional", "hierarchical"):
            plan = flavor_plan(name)
            want = comm.run_spmd(
                lambda g: execute_plan(plan, comm, g), rounded)
            got = comm.run_spmd(
                lambda h, g: execute_plan(plan, comm, h, like=g), held, grads)
            _assert_same_bits(got, want)
        _assert_same_bits(comm.allreduce_grad(held, like=grads), rounded)
        state = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (comm.size,) + a.shape),
            comm.init_compression_state(
                jax.tree.map(lambda g: g[0], grads), "int8"))
        want, _ = comm.run_spmd(
            lambda g, s: comm.allreduce_grad(g, compressor="int8", state=s),
            rounded, state)
        got, _ = comm.run_spmd(
            lambda h, g, s: comm.allreduce_grad(
                h, compressor="int8", state=s, like=g), held, grads, state)
        _assert_same_bits(got, want)


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

"""ZeRO-3 / FSDP tests — stage-3 trajectory parity with plain DP, shard
storage properties, BN-model support, and the full-params round trip
(beyond-reference extension, chainermn_tpu/parallel/fsdp.py)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu.optimizers import (
    init_model_state, init_opt_state, make_train_step)
from chainermn_tpu.parallel.fsdp import (
    fsdp_full_params, fsdp_init, make_fsdp_train_step)
from chainermn_tpu.training import put_global_batch


@pytest.fixture
def comm():
    return chainermn_tpu.create_communicator("hierarchical", intra_size=4)


def _mlp_problem(comm, seed=0):
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(16)(x))
            return nn.Dense(4)(x)

    model = MLP()
    rng = np.random.RandomState(seed)
    xs = rng.randn(comm.size * 8, 8).astype(np.float32)
    ys = (xs @ rng.randn(8, 4)).astype(np.float32)
    params = model.init(jax.random.key(seed), xs[:1])

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((model.apply(p, x) - y) ** 2)

    return params, loss_fn, (xs, ys)


class TestParity:
    def test_matches_plain_dp_trajectory(self, comm):
        """5 adam steps: FSDP == replicated multi-node DP, step by step."""
        params, loss_fn, data = _mlp_problem(comm)
        batch = put_global_batch(comm, data)

        # reference trajectory: plain multi-node optimizer
        opt_ref = chainermn_tpu.create_multi_node_optimizer(
            optax.adam(0.01), comm)
        p_ref = comm.bcast_data(params)
        s_ref = init_opt_state(comm, opt_ref, p_ref)
        step_ref = make_train_step(comm, loss_fn, opt_ref, donate=False)

        state, meta = fsdp_init(comm, params, optax.adam(0.01))
        step = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01), meta,
                                    donate=False)
        for i in range(5):
            p_ref, s_ref, loss_ref = step_ref(p_ref, s_ref, batch)
            state, loss = step(state, batch)
            np.testing.assert_allclose(float(loss), float(loss_ref),
                                       rtol=1e-5, err_msg=f"step {i}")
        full = fsdp_full_params(state, meta)
        for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(p_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)

    def test_full_params_round_trip(self, comm):
        params, _, _ = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.sgd(0.1))
        full = fsdp_full_params(state, meta)
        assert jax.tree.structure(full) == jax.tree.structure(params)
        for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestSharding:
    def test_persistent_state_is_sharded(self, comm):
        """Each device persistently stores ~1/size of params AND of the
        Adam state — the stage-3 property."""
        params, _, _ = _mlp_problem(comm)
        n_params = sum(l.size for l in jax.tree.leaves(params))
        state, meta = fsdp_init(comm, params, optax.adam(0.01))
        assert sum(meta.shard_lens) * comm.size >= n_params
        assert sum(meta.shard_lens) <= n_params // comm.size + comm.size
        for leaf in jax.tree.leaves(state.shards):
            assert leaf.shape[0] == comm.size
            assert not leaf.sharding.is_fully_replicated
        # adam m/v live at shard size too
        for leaf in jax.tree.leaves(state.inner):
            assert leaf.shape[0] == comm.size
            assert not leaf.sharding.is_fully_replicated

    def test_gather_scatter_collectives_present(self, comm):
        """The compiled step contains the stage-3 collective pair:
        an all-gather (params) and a reduce-scatter transpose (grads)."""
        params, loss_fn, data = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.sgd(0.1))
        step = make_fsdp_train_step(comm, loss_fn, optax.sgd(0.1), meta,
                                    donate=False)
        batch = put_global_batch(comm, data)
        hlo = jax.jit(step).lower(state, batch).compile().as_text()
        assert "all-gather" in hlo
        assert "reduce-scatter" in hlo


class TestVariants:
    def test_has_aux(self, comm):
        params, _, data = _mlp_problem(comm)

        def loss_fn(p, batch):
            x, y = batch
            # params belong to _mlp_problem's MLP; recompute loss directly
            h = jnp.maximum(x @ p["params"]["Dense_0"]["kernel"]
                            + p["params"]["Dense_0"]["bias"], 0)
            pred = h @ p["params"]["Dense_1"]["kernel"] \
                + p["params"]["Dense_1"]["bias"]
            loss = jnp.mean((pred - y) ** 2)
            return loss, {"mae": jnp.mean(jnp.abs(pred - y))}

        state, meta = fsdp_init(comm, params, optax.sgd(0.05))
        step = make_fsdp_train_step(comm, loss_fn, optax.sgd(0.05), meta,
                                    has_aux=True, donate=False)
        batch = put_global_batch(comm, data)
        state, loss, aux = step(state, batch)
        assert np.isfinite(float(loss)) and np.isfinite(float(aux["mae"]))

    def test_with_model_state_local_bn_analogue(self, comm):
        """model_state slot (local-BN semantics) composes with FSDP."""
        params = {"w": jnp.arange(10, dtype=jnp.float32)}

        def loss_fn(p, state, batch):
            (t,) = batch
            loss = 0.5 * jnp.mean(jnp.sum(
                (p["w"] - t.mean(axis=0)) ** 2, keepdims=True))
            return loss, {"count": state["count"] + 1}

        mstate = init_model_state(comm, {"count": jnp.zeros(())})
        state, meta = fsdp_init(comm, params, optax.sgd(0.1))
        step = make_fsdp_train_step(comm, loss_fn, optax.sgd(0.1), meta,
                                    with_model_state=True, donate=False)
        t = jnp.ones((comm.size * 2, 10))
        state, mstate, loss = step(state, mstate, (t,))
        np.testing.assert_allclose(np.asarray(mstate["count"]),
                                   np.ones(comm.size))
        assert np.isfinite(float(loss))

    def test_training_reduces_loss(self, comm):
        params, loss_fn, data = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.adam(0.01))
        step = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01), meta,
                                    donate=False)
        batch = put_global_batch(comm, data)
        losses = []
        for _ in range(25):
            state, loss = step(state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5

    def test_rejects_multi_node_wrapper(self, comm):
        params = {"w": jnp.zeros((4,))}
        wrapped = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.1), comm)
        with pytest.raises(TypeError, match="plain optax"):
            fsdp_init(comm, params, wrapped)


class TestLayerwiseOptimizers:
    """LARS/LAMB compute trust ratios from parameter-tensor norms; FSDP
    shards flatten tensors across ranks, so the ratios would silently be
    computed per SHARD, not per layer (ADVICE r5).  fsdp_init must refuse
    unless the caller opts in."""

    def test_lars_rejected(self, comm):
        params = {"w": jnp.zeros((8, 4))}
        with pytest.raises(ValueError, match="allow_layerwise"):
            fsdp_init(comm, params, optax.lars(0.1))

    def test_lamb_rejected(self, comm):
        params = {"w": jnp.zeros((8, 4))}
        with pytest.raises(ValueError, match="layer-wise"):
            fsdp_init(comm, params, optax.lamb(1e-3))

    def test_chained_lamb_rejected(self, comm):
        params = {"w": jnp.zeros((8, 4))}
        opt = optax.chain(optax.clip_by_global_norm(1.0), optax.lamb(1e-3))
        with pytest.raises(ValueError, match="allow_layerwise"):
            fsdp_init(comm, params, opt)

    def test_escape_hatch(self, comm):
        params = {"w": jnp.zeros((comm.size * 2,), jnp.float32)}
        state, meta = fsdp_init(comm, params, optax.lars(0.1),
                                allow_layerwise=True)
        assert state.shards[0][0].shape[0] == comm.size

    def test_plain_optimizers_pass(self, comm):
        params = {"w": jnp.zeros((comm.size * 2,), jnp.float32)}
        for opt in (optax.adam(1e-3), optax.sgd(0.1, momentum=0.9),
                    optax.chain(optax.clip_by_global_norm(1.0),
                                optax.adamw(1e-3))):
            fsdp_init(comm, params, opt)


class TestCheckpoint:
    def test_fsdp_state_roundtrips(self, comm, tmp_path):
        """FsdpState (stacked param shards + sharded inner state) survives
        the multi-node checkpointer with mesh placement preserved, and
        training continues bit-for-bit from the restored state."""
        from chainermn_tpu.extensions import create_multi_node_checkpointer
        from chainermn_tpu.parallel.fsdp import FsdpState

        params, loss_fn, data = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.adam(1e-2))
        step = make_fsdp_train_step(comm, loss_fn, optax.adam(1e-2), meta,
                                    donate=False)
        batch = put_global_batch(comm, data)
        state, _ = step(state, batch)

        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "fsdp")
        ckpt.save({"fsdp": state}, 1)
        zeros = jax.tree.map(jnp.zeros_like, {"fsdp": state})
        restored, gen = ckpt.resume(zeros)
        assert gen == 1
        assert isinstance(restored["fsdp"], FsdpState)
        for a, b in zip(jax.tree.leaves(restored["fsdp"]),
                        jax.tree.leaves(state)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
            assert a.sharding == b.sharding
        s2, l2 = step(restored["fsdp"], batch)
        s3, l3 = step(state, batch)
        assert float(l2) == float(l3)
        for a, b in zip(jax.tree.leaves(s2), jax.tree.leaves(s3)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_world_size_mismatch_raises(self, comm, tmp_path):
        """An FSDP checkpoint from an 8-way world refuses to resume into
        a different comm.size (ADVICE r5: shard layouts are bound to the
        world size; silently reloading trains on garbage shards).  The
        error must name fsdp_full_params as the supported cross-size
        export path."""
        from chainermn_tpu.extensions import create_multi_node_checkpointer
        from chainermn_tpu.extensions.checkpoint import _FSDP_META_KEY

        params, _, _ = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.sgd(0.1))
        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "fsdp")
        ckpt.save({"fsdp": state}, 1)

        # rewrite the persisted sidecar as if saved by a 4-way world
        path = [p for p in os.listdir(tmp_path) if p.endswith(".npz")][0]
        full = os.path.join(str(tmp_path), path)
        arrays = dict(np.load(full, allow_pickle=False))
        saved = json.loads(str(arrays[_FSDP_META_KEY]))
        assert saved["world_size"] == comm.size
        saved["world_size"] = comm.size // 2
        arrays[_FSDP_META_KEY] = np.array(json.dumps(saved))
        np.savez(full.removesuffix(".npz"), **arrays)

        with pytest.raises(ValueError, match="fsdp_full_params"):
            ckpt.resume(jax.tree.map(jnp.zeros_like, {"fsdp": state}))

    def test_sharded_checkpoint_into_unsharded_target_raises(
            self, comm, tmp_path):
        """A sharded save resumed into a plain (unsharded) params tree is
        a mode mismatch, not a shape coincidence to stumble into."""
        from chainermn_tpu.extensions import create_multi_node_checkpointer

        params, _, _ = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.sgd(0.1))
        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "fsdp")
        ckpt.save({"fsdp": state}, 1)
        with pytest.raises(ValueError, match="unsharded"):
            ckpt.resume({"fsdp": jax.tree.map(jnp.zeros_like, params)})

    def test_plain_checkpoint_leaf_mismatch_raises(self, comm, tmp_path):
        """Generic validation (no FSDP sidecar): resuming into a state
        with a different leaf count fails with a descriptive error
        instead of a cryptic unflatten."""
        from chainermn_tpu.extensions import create_multi_node_checkpointer

        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "plain")
        ckpt.save({"a": jnp.zeros((4,)), "b": jnp.ones((2,))}, 1)
        with pytest.raises(ValueError, match="leaves"):
            ckpt.resume({"a": jnp.zeros((4,))})
        with pytest.raises(ValueError, match="shape"):
            ckpt.resume({"a": jnp.zeros((4,)), "b": jnp.ones((3,))})


class TestWireDtype:
    def test_bf16_wire_collectives(self, comm):
        """wire_dtype='bfloat16' puts BOTH stage-3 collectives on a bf16
        wire (the fork's fp16-allreduce idea), numerics within bf16
        tolerance of the f32 wire."""
        params, loss_fn, data = _mlp_problem(comm)
        batch = put_global_batch(comm, data)

        state_a, meta = fsdp_init(comm, params, optax.sgd(0.05))
        step_a = make_fsdp_train_step(comm, loss_fn, optax.sgd(0.05), meta,
                                      donate=False)
        state_b, _ = fsdp_init(comm, params, optax.sgd(0.05))
        step_b = make_fsdp_train_step(comm, loss_fn, optax.sgd(0.05), meta,
                                      donate=False, wire_dtype="bfloat16")

        # the LOWERED program hands XLA a bf16-wire gather and scatter
        # (assert on StableHLO, not the compiled HLO: the CPU pipeline
        # folds the casts back into f32 collectives — the same CPU-vs-TPU
        # pass divergence docs/performance.md records for the
        # double-buffer barrier; the TPU pipeline keeps bf16 wires, as
        # the collective census pinned for the xla communicator's AR)
        txt = jax.jit(step_b).lower(state_b, batch).as_text()
        assert any("all_gather" in l and "xbf16>" in l
                   for l in txt.splitlines())
        import re
        rs = re.search(r"reduce_scatter[^\n]*\n[^\n]*bf16", txt)
        assert rs or any("reduce_scatter" in l and "xbf16>" in l
                         for l in txt.splitlines())

        for _ in range(3):
            state_a, loss_a = step_a(state_a, batch)
            state_b, loss_b = step_b(state_b, batch)
        np.testing.assert_allclose(float(loss_b), float(loss_a),
                                   rtol=3e-2)
        full_a = fsdp_full_params(state_a, meta)
        full_b = fsdp_full_params(state_b, meta)
        for a, b in zip(jax.tree.leaves(full_a), jax.tree.leaves(full_b)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=5e-2, atol=5e-3)

    def test_non_float_wire_rejected(self, comm):
        params = {"w": jnp.zeros((4,))}
        _, meta = fsdp_init(comm, params, optax.sgd(0.1))
        with pytest.raises(ValueError, match="floating"):
            make_fsdp_train_step(comm, lambda p, b: 0.0, optax.sgd(0.1),
                                 meta, wire_dtype="int8")


class TestAccumSteps:
    def test_accum_matches_full_batch(self, comm):
        """accum_steps=4 reproduces the accum=1 trajectory exactly
        (batch-decomposable loss), with shard-sized accumulators."""
        params, loss_fn, data = _mlp_problem(comm)
        batch = put_global_batch(comm, data)

        state_a, meta = fsdp_init(comm, params, optax.adam(0.01))
        step_a = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01),
                                      meta, donate=False)
        state_b, _ = fsdp_init(comm, params, optax.adam(0.01))
        step_b = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01),
                                      meta, donate=False, accum_steps=4)
        for _ in range(3):
            state_a, loss_a = step_a(state_a, batch)
            state_b, loss_b = step_b(state_b, batch)
        np.testing.assert_allclose(float(loss_b), float(loss_a), rtol=1e-6)
        fa = fsdp_full_params(state_a, meta)
        fb = fsdp_full_params(state_b, meta)
        for a, b in zip(jax.tree.leaves(fa), jax.tree.leaves(fb)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-6, atol=1e-7)

    def test_bad_accum_rejected(self, comm):
        params, loss_fn, data = _mlp_problem(comm)
        _, meta = fsdp_init(comm, params, optax.sgd(0.1))
        with pytest.raises(ValueError, match="accum_steps"):
            make_fsdp_train_step(comm, loss_fn, optax.sgd(0.1), meta,
                                 accum_steps=0)
        step = make_fsdp_train_step(comm, loss_fn, optax.sgd(0.1), meta,
                                    donate=False, accum_steps=3)
        with pytest.raises(ValueError, match="divide"):
            step(fsdp_init(comm, params, optax.sgd(0.1))[0],
                 put_global_batch(comm, data))


class TestSequenceParallelComposition:
    """batch_spec + global_loss: FSDP over a non-leading-axis-sharded
    batch whose loss_fn psums to the global objective itself (the
    FSDP x sequence-parallel composition; examples/long_context
    --fsdp pins it end to end)."""

    @pytest.mark.parametrize("check_vma", [True, False])
    def test_global_loss_matches_replicated(self, comm, check_vma):
        """With vma tracking on, the loss's psum transposes to the
        identity; with it off (what Pallas interpret mode forces on the
        CPU) psum transposes to psum and the step divides the world size
        back out — the same trained weights either way."""
        from jax.sharding import PartitionSpec as P

        # params [D]; batch [B, T] sharded over T; global objective =
        # mean over ALL (b, t) of (w[t mod D] - x)^2 via psum
        D = 6
        params = {"w": jnp.arange(D, dtype=jnp.float32)}
        rng = np.random.RandomState(0)
        T = comm.size * 4
        x = jnp.asarray(rng.randn(2, T).astype(np.float32))

        axes = comm.data_axes

        def loss_fn(p, batch):
            (xb,) = batch   # [B, T/size] local sequence shard
            me = comm.axis_index()
            t_loc = xb.shape[1]
            pos = me * t_loc + jnp.arange(t_loc)
            w = p["w"][pos % D]
            total = jax.lax.psum(((w[None, :] - xb) ** 2).sum(), axes)
            count = jax.lax.psum(jnp.float32(xb.size), axes)
            return total / count

        state, meta = fsdp_init(comm, params, optax.sgd(0.1))
        step = make_fsdp_train_step(
            comm, loss_fn, optax.sgd(0.1), meta,
            batch_spec=P(None, axes), global_loss=True, donate=False,
            check_vma=check_vma)

        # replicated reference: same objective, plain jit
        def ref_loss(p):
            w = p["w"][jnp.arange(T) % D]
            return jnp.mean((w[None, :] - x) ** 2)

        p_ref = {"w": params["w"]}
        for i in range(4):
            state, loss = step(state, (x,))
            l_ref, g_ref = jax.value_and_grad(ref_loss)(p_ref)
            p_ref = jax.tree.map(lambda a, g: a - 0.1 * g, p_ref, g_ref)
            np.testing.assert_allclose(float(loss), float(l_ref),
                                       rtol=1e-6, err_msg=f"step {i}")
        full = fsdp_full_params(state, meta)
        np.testing.assert_allclose(np.asarray(full["w"]),
                                   np.asarray(p_ref["w"]),
                                   rtol=1e-6, atol=1e-7)

"""Model-family tests: ResNet / VGG shapes, local-BN state, training step.

Reference strategy analogue (SURVEY.md §4): the ImageNet example's models
are exercised at tiny widths on the CPU mesh — same model code, small
shapes — just as the reference's CPU CI ran the naive path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu.extensions.allreduce_persistent import allreduce_persistent
from chainermn_tpu.models import MLP, ResNet, ResNet50, VGG, VGG16
from chainermn_tpu.models.resnet import BasicBlock
from chainermn_tpu.optimizers import (
    init_model_state,
    init_opt_state,
    make_train_step,
)

TinyResNet = lambda **kw: ResNet(stage_sizes=(1, 1), block_cls=BasicBlock,
                                 num_filters=8, num_classes=5, **kw)
TinyVGG = lambda **kw: VGG(cfg=(8, "M", 16, "M"), num_classes=5, hidden=16,
                           dropout_rate=0.0, **kw)


@pytest.fixture
def comm():
    return chainermn_tpu.create_communicator("hierarchical", intra_size=4)


class TestForwardShapes:
    def test_resnet50_structure(self):
        model = ResNet50(num_classes=1000)
        # 1000-class head and the bottleneck layout exist; init on a tiny
        # spatial size to keep the CPU test fast.
        variables = model.init(jax.random.key(0), jnp.zeros((1, 64, 64, 3)),
                               train=False)
        assert "params" in variables and "batch_stats" in variables
        n_params = sum(x.size for x in jax.tree.leaves(variables["params"]))
        assert 24e6 < n_params < 27e6, f"ResNet-50 should have ~25.5M params, got {n_params}"

    def test_tiny_resnet_forward(self):
        model = TinyResNet()
        variables = model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)))
        logits, mutated = model.apply(
            variables, jnp.ones((2, 32, 32, 3)), train=True,
            mutable=["batch_stats"])
        assert logits.shape == (2, 5)
        assert logits.dtype == jnp.float32
        assert "batch_stats" in mutated

    def test_vgg16_structure(self):
        model = VGG16(num_classes=10)
        variables = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)),
                               train=False)
        n_params = sum(x.size for x in jax.tree.leaves(variables["params"]))
        assert 14e6 < n_params < 16e6, f"VGG-16/CIFAR ~15M params, got {n_params}"

    def test_bf16_compute_fp32_params(self):
        model = TinyResNet(dtype=jnp.bfloat16)
        variables = model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)))
        for leaf in jax.tree.leaves(variables["params"]):
            assert leaf.dtype == jnp.float32
        logits = model.apply(variables, jnp.ones((2, 32, 32, 3)), train=False)
        assert logits.dtype == jnp.float32


def build_state_training(comm, model, shape, double_buffering=False):
    variables = model.init(jax.random.key(0), jnp.zeros((1,) + shape))
    params = comm.bcast_data(variables["params"])
    model_state = init_model_state(comm, variables["batch_stats"])
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.05), comm, double_buffering=double_buffering)
    opt_state = init_opt_state(comm, optimizer, params)

    def loss_fn(p, state, batch):
        x, y = batch
        logits, mutated = model.apply(
            {"params": p, "batch_stats": state}, x, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        return loss, mutated["batch_stats"]

    step = make_train_step(comm, loss_fn, optimizer, with_model_state=True)
    return params, model_state, opt_state, step


class TestStatefulTrainStep:
    @pytest.mark.parametrize("model_fn,shape", [
        (TinyResNet, (32, 32, 3)),
        (TinyVGG, (16, 16, 3)),
    ])
    def test_loss_decreases_and_state_updates(self, comm, model_fn, shape):
        model = model_fn()
        params, model_state, opt_state, step = build_state_training(
            comm, model, shape)
        rng = np.random.RandomState(0)
        x = rng.randn(16, *shape).astype(np.float32)
        y = (rng.rand(16) * 5).astype(np.int32)
        from chainermn_tpu.training import put_global_batch
        batch = put_global_batch(comm, (x, y))
        state0 = jax.tree.leaves(model_state)[0].copy()
        losses = []
        for _ in range(6):
            params, model_state, opt_state, loss = step(
                params, model_state, opt_state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        # batch_stats must have moved off their init values
        state1 = jax.tree.leaves(model_state)[0]
        assert not np.allclose(np.asarray(state0), np.asarray(state1))

    def test_batch_stats_stay_local(self, comm):
        """Different per-device batch shards => different local BN stats
        (the reference's local-BN semantics), until AllreducePersistent."""
        model = TinyResNet()
        params, model_state, opt_state, step = build_state_training(
            comm, model, (32, 32, 3))
        rng = np.random.RandomState(0)
        # Strongly device-dependent data: device i sees mean ~ 3*i.
        x = np.concatenate([
            3.0 * i + rng.randn(2, 32, 32, 3).astype(np.float32)
            for i in range(comm.size)])
        y = (rng.rand(2 * comm.size) * 5).astype(np.int32)
        from chainermn_tpu.training import put_global_batch
        batch = put_global_batch(comm, (x, y))
        params, model_state, opt_state, _ = step(
            params, model_state, opt_state, batch)
        mean_leaf = np.asarray(
            model_state["bn_init"]["mean"])  # [size, channels]
        per_device = mean_leaf.reshape(comm.size, -1).mean(axis=1)
        assert np.std(per_device) > 0.05, "BN stats should differ across devices"
        synced = allreduce_persistent(model_state, comm)
        mean_leaf = np.asarray(synced["bn_init"]["mean"])
        per_device = mean_leaf.reshape(comm.size, -1).mean(axis=1)
        np.testing.assert_allclose(per_device, per_device[0], rtol=1e-5)

    def test_double_buffered_stateful(self, comm):
        model = TinyVGG()
        params, model_state, opt_state, step = build_state_training(
            comm, model, (16, 16, 3), double_buffering=True)
        rng = np.random.RandomState(0)
        x = rng.randn(16, 16, 16, 3).astype(np.float32)
        y = (rng.rand(16) * 5).astype(np.int32)
        from chainermn_tpu.training import put_global_batch
        batch = put_global_batch(comm, (x, y))
        losses = []
        for _ in range(8):
            params, model_state, opt_state, loss = step(
                params, model_state, opt_state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[1]


def test_googlenet_aux_heads():
    """aux_heads=True: two auxiliary classifiers exist, return train-time
    logits of the right shape, and receive gradients (the reference
    example's 0.3-weighted recipe)."""
    import jax
    import jax.numpy as jnp
    import optax

    from chainermn_tpu.models import GoogLeNetBN

    model = GoogLeNetBN(num_classes=10, aux_heads=True)
    x = jnp.ones((2, 64, 64, 3), jnp.float32)
    variables = model.init(
        {"params": jax.random.key(0), "dropout": jax.random.key(1)},
        x, train=True)
    assert "aux4a" in variables["params"] and "aux4d" in variables["params"]

    def loss(p):
        (logits, aux), _ = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
            rngs={"dropout": jax.random.key(2)})
        assert logits.shape == (2, 10)
        assert len(aux) == 2 and all(a.shape == (2, 10) for a in aux)
        y = jnp.zeros((2,), jnp.int32)
        ce = lambda lg: optax.softmax_cross_entropy_with_integer_labels(
            lg, y).mean()
        return ce(logits) + 0.3 * sum(ce(a) for a in aux)

    g = jax.grad(loss)(variables["params"])
    for head in ("aux4a", "aux4d"):
        leaves = jax.tree.leaves(g[head])
        assert any(float(jnp.abs(l).sum()) > 0 for l in leaves)

    # eval path returns plain logits
    out = model.apply(
        {"params": variables["params"],
         "batch_stats": variables["batch_stats"]}, x, train=False)
    assert out.shape == (2, 10)

"""Family ``resnet``: the program's ``ResNet`` (BatchNorm statistics local
to each device) through ``create_communicator`` -> ``bcast_data`` ->
``create_multi_node_optimizer`` -> ``make_train_step(with_model_state=True)``:
the source paper's flagship, trained as the paper trains it."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import flops, weights
from chipbench.families import common

THROUGHPUT_METRIC = "images_per_s"
make_comm = common.make_comm
first_gradient_after = common.first_gradient_after


def _model(sizes):
    from chainermn_tpu.models import ResNet
    from chainermn_tpu.models.resnet import BasicBlock, BottleneckBlock

    blocks = {"bottleneck": BottleneckBlock, "basic": BasicBlock}
    return ResNet(stage_sizes=tuple(sizes["stage_sizes"]),
                  block_cls=blocks[sizes["block"]],
                  num_filters=sizes["num_filters"],
                  num_classes=sizes["num_classes"],
                  dtype=jnp.dtype(sizes["compute_dtype"]))


def _variable_shapes(sizes):
    image = sizes["image_size"]
    return jax.eval_shape(
        _model(sizes).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, image, image, 3), jnp.float32))


def make_params(sizes, key):
    """The benchmark's own seeded weights, in the program's tree (every
    BatchNorm scale 1: see the configuration's departures)."""
    params = weights.make_tree(_variable_shapes(sizes)["params"], key)
    last = {"bottleneck": "BatchNorm_2", "basic": "BatchNorm_1"}[
        sizes["block"]]
    damp = float(sizes.get("residual_norm_scale", 1.0))
    for name, block in params.items():
        if last in block:
            block[last]["scale"] = damp * block[last]["scale"]
    if "classifier_std" in sizes:
        kernel = params["Dense_0"]["kernel"]
        params["Dense_0"]["kernel"] = kernel * (
            sizes["classifier_std"] / jnp.sqrt(2.0 / kernel.shape[0]))
    return params


def build(comm, sizes, params, state_comm=None):
    """``(step, state)``; state is ``(params, batch_stats, opt_state)``."""
    import optax

    from chainermn_tpu.optimizers import init_model_state, make_train_step

    model = _model(sizes)
    place = state_comm or comm   # fit.py: state on the CPU, step for the described chip
    params = place.bcast_data(params)
    batch_stats = weights.make_tree(
        _variable_shapes(sizes)["batch_stats"], jax.random.key(0))
    model_state = init_model_state(place, batch_stats)
    optimizer = common.make_optimizer(sizes, comm)
    opt_state = common.init_opt_state(place, optimizer, params)

    def loss_fn(p, state, batch):
        x, y = batch
        logits, mutated = model.apply(
            {"params": p, "batch_stats": state}, x, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, mutated["batch_stats"]

    step = make_train_step(comm, loss_fn, optimizer, with_model_state=True)
    return step, (params, model_state, opt_state)


def params_of(state):
    return state[0]


def first_gradient_of(state):
    return common.momentum_trace(state[2])


def units_per_step(sizes, chips):
    return sizes["batch_per_chip"] * chips


def flop_per_unit(sizes):
    return flops.resnet_train_flop_per_image(sizes)


def min_kernels(sizes):
    """The default step holds no Pallas kernel (the wire cast is XLA's)."""
    return 0

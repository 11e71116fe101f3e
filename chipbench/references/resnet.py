"""Plain reference of family ``resnet``: He et al.'s residual network with
bottleneck (or basic) blocks, BatchNorm in training mode on the rows it is
given, ReLU, a 7x7/2 stem, 3x3/2 max-pooling, global average pooling and a
dense classifier, in straightforward float32 ``jax.numpy`` / ``jax.lax``
convolutions: no kernels, no flax, nothing of the program.  As the program
computes it, the stride of a down-sampling block sits on its 3x3
convolution (the configuration file's departure).

It reads the benchmark's seeded weight tree by name, and rematerializes
each block so that a float32 backward pass at batch 256 fits one chip."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.references import common

BN_EPSILON = 1e-5


def _batch_norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPSILON) * p["scale"] + p["bias"]


def _max_pool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")


def _bottleneck(x, p, stride, products):
    y = jax.nn.relu(_batch_norm(
        products.conv(x, p["Conv_0"]["kernel"], 1), p["BatchNorm_0"]))
    y = jax.nn.relu(_batch_norm(
        products.conv(y, p["Conv_1"]["kernel"], stride), p["BatchNorm_1"]))
    y = _batch_norm(products.conv(y, p["Conv_2"]["kernel"], 1),
                    p["BatchNorm_2"])
    if "conv_proj" in p:
        x = _batch_norm(products.conv(x, p["conv_proj"]["kernel"], stride),
                        p["norm_proj"])
    return jax.nn.relu(x + y)


def _basic(x, p, stride, products):
    y = jax.nn.relu(_batch_norm(
        products.conv(x, p["Conv_0"]["kernel"], stride), p["BatchNorm_0"]))
    y = _batch_norm(products.conv(y, p["Conv_1"]["kernel"], 1),
                    p["BatchNorm_1"])
    if "conv_proj" in p:
        x = _batch_norm(products.conv(x, p["conv_proj"]["kernel"], stride),
                        p["norm_proj"])
    return jax.nn.relu(x + y)


def make_loss(sizes, precision="float32"):
    """``loss(params, (images, labels))``: mean softmax cross-entropy, with
    BatchNorm statistics taken over the rows given (one device's)."""
    products = common.Products(precision)
    block, prefix = {"bottleneck": (_bottleneck, "BottleneckBlock"),
                     "basic": (_basic, "BasicBlock")}[sizes["block"]]

    def loss(params, batch):
        images, labels = batch
        x = products.conv(images, params["conv_init"]["kernel"], 2)
        x = _max_pool(jax.nn.relu(_batch_norm(x, params["bn_init"])))
        index = 0
        for stage, count in enumerate(sizes["stage_sizes"]):
            for position in range(count):
                stride = 2 if stage > 0 and position == 0 else 1
                x = jax.checkpoint(
                    lambda x, p, stride=stride: block(x, p, stride, products))(
                        x, params[f"{prefix}_{index}"])
                index += 1
        x = jnp.mean(x, axis=(1, 2))
        logits = (products.dot(x, params["Dense_0"]["kernel"])
                  + params["Dense_0"]["bias"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))

    return loss

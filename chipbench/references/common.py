"""What the plain references share: matrix products at a stated precision,
and the three optimizer steps that the check follows.

The reference imports nothing of the program.  Its products run in float32
under ``precision=HIGHEST`` (on a TPU a float32 product is otherwise
computed in bfloat16 passes).  The CONTROL of "How correct is decided" is
this same reference computed one precision below the configuration's
bfloat16: ``int8``, per-tensor symmetric rounding of both operands of every
product and of the gradient that flows back into it (the v5e's other MXU
type, and so the step that would tempt a later PR).  ``bfloat16`` rounds
the same places to bfloat16 and is there to see what sound rounding costs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, precision):
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "int8":
        scale = jnp.max(jnp.abs(x)) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_cotangent(y, precision):
    return y


def _round_cotangent_fwd(y, precision):
    return y, None


def _round_cotangent_bwd(precision, _, g):
    return (_round(g, precision),)


_round_cotangent.defvjp(_round_cotangent_fwd, _round_cotangent_bwd)


class Products:
    """``dot`` and ``conv`` at one precision: ``float32`` (the reference),
    ``bfloat16`` or ``int8`` (rounded operands and cotangents, products and
    sums still exact in float32)."""

    def __init__(self, precision="float32"):
        self.precision = precision

    def _operand(self, x):
        if self.precision == "float32":
            return x
        # the value is the rounded one; the gradient passes straight through
        return x + jax.lax.stop_gradient(_round(x, self.precision) - x)

    def _result(self, y):
        if self.precision == "float32":
            return y
        return _round_cotangent(y, self.precision)

    def dot(self, a, b):
        return self._result(jnp.matmul(
            self._operand(a), self._operand(b), precision=HIGHEST))

    def einsum(self, spec, a, b):
        return self._result(jnp.einsum(
            spec, self._operand(a), self._operand(b), precision=HIGHEST))

    def conv(self, x, kernel, stride):
        return self._result(jax.lax.conv_general_dilated(
            self._operand(x), self._operand(kernel), (stride, stride),
            "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST))


def leaf_norms(tree):
    """The l2 norm of every leaf, in flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def follow_three_steps(loss_fn, make_params, batches, optimizer, devices):
    """The first three training steps, as the configuration's optimizer takes
    them: SGD with momentum, with the double buffer applying each gradient
    one step late (its first update applies zeros).  ``loss_fn(params,
    batch)`` is the plain loss of one device's rows; a global batch is one
    equal block of rows per device of ``devices``, whose losses and gradients
    are averaged (data parallelism's semantics).  Each block is computed on
    its own device, all at once, and the rest on the first.
    ``make_params()`` makes the seeded weights; it is called again at the end
    rather than a copy kept, so that three parameter-sized trees are the most
    that live beside a backward pass.

    Returns ``{"losses": [3 floats], "grad_norms": per-leaf norms of the
    first gradient, "delta_norms": per-leaf norms of params_after_3 -
    params_before}``."""
    if optimizer["rule"] != "sgd":
        raise ValueError(f"unknown optimizer rule {optimizer['rule']!r}")
    lr, mu = optimizer["learning_rate"], optimizer["momentum"]
    stale = bool(optimizer["double_buffering"])
    value_and_grad = jax.jit(jax.value_and_grad(loss_fn))
    value = jax.jit(loss_fn)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    scale = jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a),
                    donate_argnums=(0,))
    momentum = jax.jit(
        lambda m, g: jax.tree.map(lambda a, b: mu * a + b, m, g),
        donate_argnums=(0,))
    descend = jax.jit(
        lambda p, m: jax.tree.map(lambda a, b: a - lr * b, p, m),
        donate_argnums=(0,))
    norms = jax.jit(leaf_norms)
    difference = jax.jit(
        lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))

    shards = len(devices)

    def split(batch):
        return [jax.device_put(
            tuple(leaf[i * (leaf.shape[0] // shards):
                       (i + 1) * (leaf.shape[0] // shards)]
                  for leaf in batch), device)
            for i, device in enumerate(devices)]

    def global_loss(p, batch, with_grad):
        copies = [p] + [jax.device_put(p, d) for d in devices[1:]]
        outs = [(value_and_grad if with_grad else value)(copy, block)
                for copy, block in zip(copies, split(batch))]  # all at once
        del copies
        if not with_grad:
            return sum(float(loss) for loss in outs) / shards, None
        grads = None
        for _, g in outs:
            g = jax.device_put(g, devices[0])
            grads = g if grads is None else add(grads, g)
        if shards > 1:
            grads = scale(grads, 1.0 / shards)
        return sum(float(loss) for loss, _ in outs) / shards, grads

    p = make_params()
    trace = pending = None
    losses, grad_norms = [], None
    for index, batch in enumerate(batches[:3]):
        # the third gradient is stashed by the double buffer, never applied
        with_grad = not (stale and index == 2)
        loss, grads = global_loss(p, batch, with_grad)
        losses.append(loss)
        if index == 0:
            grad_norms = np.asarray(norms(grads))
        applied, pending = (pending, grads) if stale else (grads, None)
        if applied is None:       # the double buffer's first update: zeros
            continue
        trace = applied if trace is None else momentum(trace, applied)
        p = descend(p, trace)
    del trace, pending
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": np.asarray(difference(p, make_params()))}

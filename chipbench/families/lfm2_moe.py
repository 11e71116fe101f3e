"""Family ``lfm2_moe``: the program's ``LFM2MoE`` through
``create_communicator`` -> ``bcast_data`` -> ``create_multi_node_optimizer``
-> ``make_train_step``, at the sizes an ``lfm2_moe`` configuration file
gives under the published key names.  ``num_experts`` experts (ids from
``first_expert``) of the ``num_experts_published`` the router scores are
held here, and ``vocab_size`` rows of the vocabulary: one chip's share of
the deployment the file states."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import flops_lfm2
from chipbench.families import common

THROUGHPUT_METRIC = "tokens_per_s"
make_comm = common.make_comm
first_gradient_after = common.first_gradient_after


def _model(sizes):
    from chainermn_tpu.models.lfm2 import LFM2Config, LFM2MoE

    return LFM2MoE(LFM2Config.from_dict(
        sizes, num_experts_routed=sizes["num_experts_published"],
        dtype=jnp.dtype(sizes["compute_dtype"])))


def param_shapes(sizes):
    return jax.eval_shape(
        _model(dict(sizes, attention_impl="xla",
                    moe_matmul_impl="ragged_dot")).init,
        jax.random.key(0),
        jax.ShapeDtypeStruct((1, min(sizes["seq_len"], 128)), jnp.int32))


def make_params(sizes, key):
    """The benchmark's own seeded weights, in the program's tree: RMSNorm
    scales one; ``expert_bias`` ~ normal(0, expert_bias_std) (it steers the
    selection, gets no gradient and stays as drawn); every other leaf, the
    expert stacks and the convolution taps among them, normal(0,
    initializer_range).  Every leaf draws from its own fold of ``key``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(sizes))

    def leaf(index, path, shape):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "scale":
            return jnp.ones(shape.shape, jnp.float32)
        std = (sizes["expert_bias_std"] if name == "expert_bias"
               else sizes["initializer_range"])
        return std * jax.random.normal(
            jax.random.fold_in(key, index), shape.shape, jnp.float32)

    return jax.tree_util.tree_unflatten(
        treedef, [leaf(i, path, shape)
                  for i, (path, shape) in enumerate(leaves)])


def loss_fn(sizes, with_counters=False):
    """Mean next-token cross-entropy over the vocabulary slice; with
    ``with_counters`` also the MoE layers' routing counters
    (``make_train_step(has_aux=True)``)."""
    import optax

    model = _model(sizes)

    def loss(params, batch):
        (tokens,) = batch
        out = model.apply(params, tokens, with_counters=with_counters)
        logits, counters = out if with_counters else (out, None)
        value = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]).mean()
        return (value, counters) if with_counters else value

    return loss


def build(comm, sizes, params, state_comm=None, with_counters=False):
    """``(step, state)``: the jitted train step and ``(params, opt_state)``
    placed as the program places them.  The timed step runs without the
    counters; ``with_counters`` builds the step that also returns them."""
    from chainermn_tpu.optimizers import make_train_step

    place = state_comm or comm   # fit.py: state on the CPU, step for the described chip
    params = place.bcast_data(params)
    optimizer = common.make_optimizer(sizes, comm)
    opt_state = common.init_opt_state(place, optimizer, params)
    step = make_train_step(comm, loss_fn(sizes, with_counters), optimizer,
                           has_aux=with_counters)
    return step, (params, opt_state)


def params_of(state):
    return state[0]


def first_gradient_of(state):
    return common.momentum_trace(state[1])


def units_per_step(sizes, chips):
    return sizes["batch_per_chip"] * chips * sizes["seq_len"]


def flop_per_unit(sizes):
    return flops_lfm2.lm_train_flop_per_token(sizes)


def min_kernels(sizes):
    """flash forward and its two backward kernels in every attention layer;
    in every MoE layer the three grouped products (gate, up, down), each
    forward, ``dlhs`` and ``drhs``."""
    kinds = sizes["layer_types"]
    moe_layers = len(kinds) - sizes["num_dense_layers"]
    flash = (3 * kinds.count("full_attention")
             if sizes["attention_impl"] == "flash" else 0)
    grouped = 9 * moe_layers if sizes["moe_matmul_impl"] == "pallas" else 0
    return flash + grouped

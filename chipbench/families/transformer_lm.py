"""Family ``transformer_lm``: the program's ``TransformerLM`` through
``create_communicator`` -> ``bcast_data`` -> ``create_multi_node_optimizer``
-> ``make_train_step``, at the sizes a GPTBigCode-style configuration file
gives (``n_embd``, ``n_head``, ``multi_query``, ``n_inner``, ``n_layer``,
``n_positions``, ``vocab_size``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import flops, weights
from chipbench.families import common

THROUGHPUT_METRIC = "tokens_per_s"
make_comm = common.make_comm
first_gradient_after = common.first_gradient_after


def _model(sizes):
    from chainermn_tpu.models import TransformerLM

    if sizes["n_inner"] != 4 * sizes["n_embd"]:
        raise ValueError("TransformerLM's MLP is 4 x d_model wide")
    return TransformerLM(
        vocab=sizes["vocab_size"], d_model=sizes["n_embd"],
        n_layers=sizes["n_layer"], n_heads=sizes["n_head"],
        n_kv_heads=1 if sizes["multi_query"] else None,
        max_len=sizes["n_positions"], attention_impl=sizes["attention_impl"],
        dtype=jnp.dtype(sizes["compute_dtype"]))


def param_shapes(sizes):
    return jax.eval_shape(
        _model(sizes).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, min(sizes["seq_len"], 128)), jnp.int32))


def make_params(sizes, key):
    """The benchmark's own seeded weights, in the program's tree."""
    return weights.make_tree(param_shapes(sizes), key,
                             kernel_std=sizes["initializer_range"])


def build(comm, sizes, params, state_comm=None):
    """``(step, state)``: the jitted train step and ``(params, opt_state)``
    placed as the program places them."""
    import optax

    from chainermn_tpu.optimizers import make_train_step

    model = _model(sizes)
    place = state_comm or comm   # fit.py: state on the CPU, step for the described chip
    params = place.bcast_data(params)
    optimizer = common.make_optimizer(sizes, comm)
    opt_state = common.init_opt_state(place, optimizer, params)

    def loss_fn(p, batch):
        (tokens,) = batch
        logits = model.apply(p, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]).mean()

    return make_train_step(comm, loss_fn, optimizer), (params, opt_state)


def params_of(state):
    return state[0]


def first_gradient_of(state):
    return common.momentum_trace(state[1])


def units_per_step(sizes, chips):
    return sizes["batch_per_chip"] * chips * sizes["seq_len"]


def flop_per_unit(sizes):
    return flops.lm_train_flop_per_token(
        sizes["seq_len"], sizes["n_embd"], sizes["n_layer"],
        sizes["vocab_size"], sizes["n_head"],
        n_kv_heads=1 if sizes["multi_query"] else None,
        d_inner=sizes["n_inner"])


def min_kernels(sizes):
    """flash forward and its two backward kernels in every layer."""
    return 3 * sizes["n_layer"] if sizes["attention_impl"] == "flash" else 0

"""Family ``deepseek_v3``: the program's ``DeepseekV3`` (the text decoder of
moonshotai/Kimi-VL-A3B-Instruct: latent attention, shared experts beside a
dropless sigmoid top-k) through ``create_communicator`` -> ``bcast_data`` ->
``create_multi_node_optimizer`` -> ``make_train_step``, at the sizes a
``deepseek_v3`` configuration file gives under the published key names,
driven exactly as families ``lfm2_moe``, ``afmoe`` and ``mellum`` are.
``n_routed_experts`` experts (ids from ``first_expert``) of the
``num_experts_published`` the router scores are held here, and
``vocab_size`` rows of the vocabulary: one chip's share of the deployment
the file states.  The shared experts are held whole."""

from __future__ import annotations

import importlib.util

import jax
import jax.numpy as jnp

from chipbench import flops_deepseek_v3, spec
from chipbench.families import common

if importlib.util.find_spec("chainermn_tpu.models.deepseek_v3") is None:
    # a program from before the model: the cell cannot run, and says so
    raise spec.SpecError(
        "this checkout's program has no chainermn_tpu.models.deepseek_v3: "
        "family deepseek_v3 cannot be built")

THROUGHPUT_METRIC = "tokens_per_s"
make_comm = common.make_comm
first_gradient_after = common.first_gradient_after


def _model(sizes):
    from chainermn_tpu.models.deepseek_v3 import DeepseekV3, DeepseekV3Config

    return DeepseekV3(DeepseekV3Config.from_dict(
        sizes, num_experts_routed=sizes["num_experts_published"],
        dtype=jnp.dtype(sizes["compute_dtype"])))


def param_shapes(sizes):
    return jax.eval_shape(
        _model(dict(sizes, attention_impl="xla",
                    moe_matmul_impl="ragged_dot")).init,
        jax.random.key(0),
        jax.ShapeDtypeStruct((1, min(sizes["seq_len"], 128)), jnp.int32))


def make_params(sizes, key):
    """The benchmark's own weights, in the program's tree, as family
    ``mellum``'s: RMSNorm scales one; the embedding's rows normal(0,
    ``embedding_std`` = 1), unit rows, so that a token routes by itself and
    not by the near-constant vector an untrained attention layer adds
    (families/mellum.py has the readings); ``expert_bias`` (the published
    ``e_score_correction_bias``) ~ normal(0, ``expert_bias_std``): it steers
    the selection, gets no gradient and stays as drawn; every other leaf,
    the expert stacks, the router and the head among them, normal(0,
    ``initializer_range``).  Every leaf draws from its own fold of the key.

    **They are drawn from the configuration's ``weights_key``, not from the
    run's seed** (``key`` is not used; the seed makes the DATA), as families
    ``afmoe``'s and ``mellum``'s are: every reading of this cell is on that
    draw."""
    del key
    key = jax.random.key(int(sizes["weights_key"]))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(sizes))
    stds = {"embedding": sizes["embedding_std"],
            "expert_bias": sizes["expert_bias_std"]}

    def leaf(index, path, shape):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "scale":
            return jnp.ones(shape.shape, jnp.float32)
        return stds.get(name, sizes["initializer_range"]) * jax.random.normal(
            jax.random.fold_in(key, index), shape.shape, jnp.float32)

    return jax.tree_util.tree_unflatten(
        treedef, [leaf(i, path, shape)
                  for i, (path, shape) in enumerate(leaves)])


def loss_fn(sizes, with_counters=False):
    """Mean next-token cross-entropy over the vocabulary slice; with
    ``with_counters`` also the MoE layers' routing counters
    (``make_train_step(has_aux=True)``).  As family ``afmoe``'s: every
    position scores its next token but the last, which has none and counts
    nothing, with no slice of the float32 ``[B, T, vocab]`` logits."""
    import optax

    model = _model(sizes)

    def loss(params, batch):
        (tokens,) = batch
        out = model.apply(params, tokens, with_counters=with_counters)
        logits, counters = out if with_counters else (out, None)
        each = optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.roll(tokens, -1, axis=1))
        has_next = jnp.arange(tokens.shape[1]) < tokens.shape[1] - 1
        value = jnp.where(has_next, each, 0.0).sum() / (
            tokens.shape[0] * (tokens.shape[1] - 1))
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
    return flops_deepseek_v3.lm_train_flop_per_token(sizes)


def min_kernels(sizes):
    """flash forward and its two backward kernels in every layer; in every
    MoE layer the three grouped products (gate, up, down), each forward,
    ``dlhs`` and ``drhs``."""
    kinds = list(sizes["mlp_layer_types"])
    flash = 3 * len(kinds) if sizes["attention_impl"] == "flash" else 0
    grouped = (9 * kinds.count("sparse")
               if sizes["moe_matmul_impl"] == "pallas" else 0)
    return flash + grouped

"""The dropless expert layer holds a SHARE of the experts: the shares add
up to the whole layer, nothing is dropped at any imbalance, the bias steers
the selection only, and it holds only its own experts' weights."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models.lfm2 import LFM2Config, SparseMoE
from chainermn_tpu.parallel import expert
from chipbench import spec

ROUTED, TOP_K, HIDDEN, WIDTH, TOKENS = 8, 2, 32, 16, 96


def _reference():
    return spec.load_module(spec.CHECKOUT, "references", "lfm2_moe")


def _sizes(first, held):
    return {"num_experts": held, "first_expert": first,
            "num_experts_per_tok": TOP_K, "norm_topk_prob": True,
            "routed_scaling_factor": 1.0}


def _whole_layer(seed=0, bias_std=0.5):
    """Weights of the UNCUT layer in the reference's tree, and tokens."""
    key = jax.random.key(seed)
    draw = lambda i, *shape: jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32)
    params = {"gate": {"kernel": 0.2 * draw(0, HIDDEN, ROUTED)},
              "expert_bias": bias_std * draw(1, ROUTED),
              "w1": 0.3 * draw(2, ROUTED, HIDDEN, WIDTH),
              "w3": 0.3 * draw(3, ROUTED, HIDDEN, WIDTH),
              "w2": 0.3 * draw(4, ROUTED, WIDTH, HIDDEN)}
    return params, draw(5, 1, TOKENS, HIDDEN)


def _share(params, first, held):
    """What one chip holds: the router whole, its experts' rows alone."""
    cut = lambda w: w[first:first + held]
    return dict(params, w1=cut(params["w1"]), w3=cut(params["w3"]),
                w2=cut(params["w2"]))


def _module(first, held):
    return SparseMoE(LFM2Config(
        vocab_size=8, hidden_size=HIDDEN, intermediate_size=4 * HIDDEN,
        moe_intermediate_size=WIDTH, layer_types=("conv",),
        num_dense_layers=0, num_attention_heads=2, num_key_value_heads=1,
        num_experts=held, num_experts_per_tok=TOP_K,
        num_experts_routed=ROUTED, first_expert=first))


def test_the_shares_add_up_to_the_uncut_layer():
    from chipbench.references.common import Products

    params, x = _whole_layer()
    whole = _reference().sparse_moe(x, params, _sizes(0, ROUTED),
                                    Products())
    total = jnp.zeros_like(x)
    held_pairs = 0.0
    for first in range(0, ROUTED, 2):
        y, counters = _module(first, 2).apply(
            {"params": _share(params, first, 2)}, x)
        # each share is the reference's share too
        np.testing.assert_allclose(
            y, _reference().sparse_moe(x, _share(params, first, 2),
                                       _sizes(first, 2), Products()),
            rtol=1e-5, atol=1e-5)
        total = total + y
        held_pairs += float(counters["tokens_per_held_expert"].sum())
        assert float(counters["dropped_pairs"]) == 0.0
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    assert held_pairs == TOKENS * TOP_K     # every pair computed once


def test_a_share_holds_only_its_own_experts():
    params, x = _whole_layer()
    shapes = jax.eval_shape(_module(2, 2).init, jax.random.key(0), x)
    held = shapes["params"]
    assert held["w1"].shape == (2, HIDDEN, WIDTH)
    assert held["w2"].shape == (2, WIDTH, HIDDEN)
    assert held["gate"]["kernel"].shape == (HIDDEN, ROUTED)
    assert held["expert_bias"].shape == (ROUTED,)


@pytest.mark.parametrize("target", [0, 1])
def test_no_pair_is_dropped_when_every_token_picks_one_expert(target):
    """Every token's first choice is one held expert: a capacity of
    ``2 k N / E`` would drop three quarters of them."""
    params, x = _whole_layer()
    bias = jnp.full((ROUTED,), -10.0).at[2 + target].set(10.0)
    share = dict(_share(params, 2, 2), expert_bias=bias)
    y, counters = _module(2, 2).apply({"params": share}, x)
    sizes = np.asarray(counters["tokens_per_held_expert"])
    assert sizes[target] == TOKENS and float(counters["dropped_pairs"]) == 0
    from chipbench.references.common import Products
    np.testing.assert_allclose(
        y, _reference().sparse_moe(x, share, _sizes(2, 2), Products()),
        rtol=1e-5, atol=1e-5)
    assert float(counters["load_max_over_mean"]) > 1.0


def test_selection_uses_score_plus_bias_and_weights_use_the_score():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([-5.0, 0.0, 0.0, 5.0])
    chosen, weights = expert.dropless_route(logits, bias, 2)
    assert sorted(np.asarray(chosen)[0].tolist()) == [1, 3]
    scores = np.asarray(jax.nn.sigmoid(logits))[0]
    picked = scores[np.asarray(chosen)[0]]
    np.testing.assert_allclose(np.asarray(weights)[0],
                               picked / (picked.sum() + 1e-6), rtol=1e-6)
    unbiased, _ = expert.dropless_route(logits, None, 2)
    assert sorted(np.asarray(unbiased)[0].tolist()) == [0, 1]


def test_expert_bias_has_zero_gradient_and_the_router_does_not():
    params, x = _whole_layer(bias_std=0.05)   # a bias that leaves 0-1 some
    share = _share(params, 0, 2)
    grads = jax.grad(lambda p: jnp.sum(jnp.square(
        _module(0, 2).apply({"params": p}, x)[0])))(share)
    assert not np.asarray(grads["expert_bias"]).any()
    assert np.abs(np.asarray(grads["gate"]["kernel"])).sum() > 0
    assert all(np.abs(np.asarray(grads[w])).sum() > 0
               for w in ("w1", "w2", "w3"))


def test_dispatch_orders_the_held_pairs_first_and_combine_undoes_it():
    chosen = jnp.asarray([[3, 0], [1, 3], [2, 1], [0, 2]], jnp.int32)
    x = jnp.arange(4, dtype=jnp.float32)[:, None] * jnp.ones((4, 3))
    rows, dispatch = expert.dropless_dispatch(x, chosen, 1, 2)
    # held experts 1 and 2: tokens (1, 2) then (2, 3); the rest go last
    assert np.asarray(dispatch.group_sizes).tolist() == [2, 2]
    assert np.asarray(rows)[:4, 0].tolist() == [1.0, 2.0, 2.0, 3.0]
    weights = jnp.ones((4, 2))
    expert_rows = jnp.where(jnp.arange(8)[:, None] < 4, rows, 0.0)
    back = expert.dropless_combine(expert_rows, weights, dispatch)
    # token t gets its own row once per held choice: 0, 1, 2 x 2, 3
    assert np.asarray(back)[:, 0].tolist() == [0.0, 1.0, 4.0, 3.0]


def test_the_dispatch_gradient_is_the_scatter_add_autodiff_would_give():
    key = jax.random.key(3)
    x = jax.random.normal(key, (TOKENS, HIDDEN))
    chosen = jax.random.randint(jax.random.fold_in(key, 1),
                                (TOKENS, TOP_K), 0, ROUTED)
    weights = jax.random.uniform(jax.random.fold_in(key, 2),
                                 (TOKENS, TOP_K))

    def through(x, weights):
        rows, dispatch = expert.dropless_dispatch(x, chosen, 2, 3)
        kept = jnp.arange(rows.shape[0])[:, None] < dispatch.group_sizes.sum()
        return jnp.sum(jnp.sin(expert.dropless_combine(
            jnp.where(kept, 2.0 * rows, 0.0), weights, dispatch)))

    def plain(x, weights):
        held = (chosen >= 2) & (chosen < 5)
        return jnp.sum(jnp.sin(
            (2.0 * x[:, None, :] * jnp.where(held, weights, 0.0)[..., None]
             ).sum(1)))

    for got, want in zip(jax.grad(through, (0, 1))(x, weights),
                         jax.grad(plain, (0, 1))(x, weights)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_an_exchange_axis_and_a_range_outside_the_model_are_refused():
    x, logits = jnp.zeros((4, 8)), jnp.zeros((4, ROUTED))
    call = lambda **kw: expert.dropless_moe(
        x, logits, None, lambda rows, sizes: rows, num_experts=ROUTED,
        top_k=TOP_K, **kw)
    with pytest.raises(NotImplementedError):
        call(axis_name="ep")
    with pytest.raises(ValueError):
        call(first_expert=6, held_experts=4)


def test_the_configuration_states_its_share_and_the_deployment():
    with open(os.path.join(spec.HERE, "configs", "lfm2-8b-a1b.json")) as f:
        config = json.load(f)
    assert (config["num_experts"], config["num_experts_published"],
            config["num_experts_per_tok"]) == (8, 32, 4)
    assert config["vocab_size"] * 4 == config["vocab_size_published"]
    assert "4 chips" in config["deployment"]
    assert config["layer_types"] == config["layer_types_published"][1:6]

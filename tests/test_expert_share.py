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
# at 96 tokens the layer's row bound is all 192 pairs; at 1024 tokens a
# 2-expert share materialises 1024 of its 2048 sorted rows
MANY = 1024


def _reference():
    return spec.load_module(spec.CHECKOUT, "references", "lfm2_moe")


def _sizes(first, held):
    return {"num_experts": held, "first_expert": first,
            "num_experts_per_tok": TOP_K, "norm_topk_prob": True,
            "routed_scaling_factor": 1.0}


def _whole_layer(seed=0, bias_std=0.5, tokens=TOKENS):
    """Weights of the UNCUT layer in the reference's tree, and tokens."""
    key = jax.random.key(seed)
    draw = lambda i, *shape: jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32)
    params = {"gate": {"kernel": 0.2 * draw(0, HIDDEN, ROUTED)},
              "expert_bias": bias_std * draw(1, ROUTED),
              "w1": 0.3 * draw(2, ROUTED, HIDDEN, WIDTH),
              "w3": 0.3 * draw(3, ROUTED, HIDDEN, WIDTH),
              "w2": 0.3 * draw(4, ROUTED, WIDTH, HIDDEN)}
    return params, draw(5, 1, tokens, HIDDEN)


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


@pytest.mark.parametrize("tokens", [TOKENS, MANY])
def test_the_shares_add_up_to_the_uncut_layer(tokens):
    from chipbench.references.common import Products

    params, x = _whole_layer(tokens=tokens)
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
    assert held_pairs == tokens * TOP_K     # every pair computed once


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


# ---------------------------------------------------------------------------
# the row bound and its guarded remainder
# ---------------------------------------------------------------------------

def test_the_bound_is_the_even_share_and_a_half_in_whole_tiles():
    bound = expert.dropless_rows_bound
    assert bound(3 * 8192 * 4, 8, 32) == 36864          # the cell: 72 tiles
    assert bound(MANY * TOP_K, 2, ROUTED) == 1024       # 768 -> two tiles
    assert bound(TOKENS * TOP_K, 2, ROUTED) == TOKENS * TOP_K
    assert bound(3 * 8192 * 4, 32, 32) == 3 * 8192 * 4  # all held: no bound
    assert bound(4096, 1, 3) == 2048


def _unbounded(monkeypatch):
    """The layer as it was before the bound: every pair a row."""
    monkeypatch.setattr(expert, "dropless_rows_bound",
                        lambda pairs, held, routed: pairs)


def _share_outputs(share, x):
    """``y``, the counters, and the gradients of a scalar of ``y`` by the
    tokens and by every leaf of the share."""
    def scalar(p, x):
        y, counters = _module(2, 2).apply({"params": p}, x)
        return jnp.sum(jnp.sin(y)), (y, counters)

    grads, (y, counters) = jax.grad(scalar, (0, 1), has_aux=True)(share, x)
    return y, counters, grads


def test_a_routing_forced_past_the_bound_runs_the_remainder_and_drops_nothing(
        monkeypatch):
    """Every token picks the two held experts: 2048 held pairs against a
    bound of 1024 rows.  ``y``, ``dx``, the router's, the weights' and the
    expert stacks' gradients are the unbounded layer's and the reference's
    (which applies every held expert to every token)."""
    from chipbench.references.common import Products

    params, x = _whole_layer(tokens=MANY)
    bias = jnp.full((ROUTED,), -10.0).at[2].set(10.0).at[3].set(10.0)
    share = dict(_share(params, 2, 2), expert_bias=bias)
    y, counters, grads = _share_outputs(share, x)
    assert float(counters["rows_bound"]) == 1024.0
    assert float(counters["rows_past_bound"]) == MANY * TOP_K - 1024.0
    assert float(counters["dropped_pairs"]) == 0.0
    assert float(counters["held_share"]) == 1.0

    reference = lambda p, x: jnp.sum(jnp.sin(_reference().sparse_moe(
        x, p, _sizes(2, 2), Products())))
    want = jax.grad(reference, (0, 1))(share, x)
    np.testing.assert_allclose(
        y, _reference().sparse_moe(x, share, _sizes(2, 2), Products()),
        rtol=1e-5, atol=1e-5)
    _unbounded(monkeypatch)
    y_all, counters_all, grads_all = _share_outputs(share, x)
    assert float(counters_all["rows_past_bound"]) == 0.0
    # (the remainder takes W1 and W3 as one product: float32 sums in another
    # order)
    np.testing.assert_allclose(y, y_all, rtol=1e-5, atol=1e-5)
    for got, unbounded, ref in zip(*map(jax.tree.leaves,
                                        (grads, grads_all, want))):
        np.testing.assert_allclose(got, unbounded, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    assert not np.asarray(grads[0]["expert_bias"]).any()


def _crafted_layer(held_pairs, x, stacks):
    """``dropless_moe`` on logits that send EXACTLY ``held_pairs`` pairs to
    the held experts 2 and 3: the first ``held_pairs // 2`` tokens pick
    both, one more picks expert 2 and an absent one if the count is odd,
    the rest pick the absent experts 0 and 1."""
    from chainermn_tpu.ops.grouped_matmul import grouped_matmul

    token = np.arange(x.shape[0])[:, None]
    both, odd = held_pairs // 2, held_pairs % 2
    favoured = np.where(token < both, [[2, 3]],
                        np.where(token < both + odd, [[2, 0]], [[0, 1]]))
    logits = jnp.asarray(
        (np.arange(ROUTED)[None, None, :] == favoured[:, :, None]).any(1)
        * 6.0 - 3.0) + 0.1 * x[:, :ROUTED]

    def experts(rows, sizes):
        product = lambda a, w: grouped_matmul(a, w, sizes, "ragged_dot")
        return product(jax.nn.silu(product(rows, stacks["w1"]))
                       * product(rows, stacks["w3"]), stacks["w2"])

    return expert.dropless_moe(
        x, logits, None, experts, num_experts=ROUTED, top_k=TOP_K,
        first_expert=2, held_experts=2)


@pytest.mark.parametrize("past", [0, 1, 7])
def test_a_routing_at_the_bound_and_just_past_it(monkeypatch, past):
    """1024 held pairs fill the bound exactly and the remainder stays out;
    one more and it runs over the one row."""
    params, x = _whole_layer(tokens=MANY)
    x, stacks = x[0], _share(params, 2, 2)

    def outputs():
        def scalar(x, stacks):
            y, counters = _crafted_layer(1024 + past, x, stacks)
            return jnp.sum(jnp.sin(y)), (y, counters)

        return jax.grad(scalar, (0, 1), has_aux=True)(x, stacks)

    grads, (y, counters) = outputs()
    assert float(counters["tokens_per_held_expert"].sum()) == 1024.0 + past
    assert float(counters["rows_past_bound"]) == past
    assert float(counters["dropped_pairs"]) == 0.0
    _unbounded(monkeypatch)
    grads_all, (y_all, _) = outputs()
    np.testing.assert_allclose(y, y_all, rtol=1e-6, atol=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_all)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(y_all)).sum() > 0


def _equations(jaxpr, into_cond):
    """Every equation of ``jaxpr`` and of the jaxprs inside its equations;
    a ``cond``'s branches only with ``into_cond``."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, into_cond)


def _layer_gradient_jaxpr(held, tokens):
    params, x = _whole_layer(tokens=tokens)
    share = _share(params, 0, held)
    return jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(jnp.sin(
        _module(0, held).apply({"params": p}, x)[0])), (0, 1)))(share, x)


def test_an_even_routing_stays_under_the_bound_in_rows_of_the_bound():
    """No value of the main pass, forward or backward, has a row for every
    pair and a feature dimension: what carries features is ``bound`` rows
    (the remainder's, inside the ``cond``, are the rest)."""
    params, x = _whole_layer(tokens=MANY, bias_std=0.05)
    _, counters = _module(2, 2).apply({"params": _share(params, 2, 2)}, x)
    assert 0.0 < float(counters["held_share"]) < 0.5
    assert float(counters["rows_past_bound"]) == 0.0

    pairs, bound = MANY * TOP_K, 1024
    jaxpr = _layer_gradient_jaxpr(2, MANY).jaxpr
    main = list(_equations(jaxpr, into_cond=False))
    shapes = {tuple(v.aval.shape) for eqn in main for v in eqn.outvars}
    per_pair = {s for s in shapes if len(s) >= 2 and s[-1] > TOP_K and (
        s[0] == pairs or s[:2] == (MANY, TOP_K))}
    assert not per_pair, per_pair
    assert (bound, HIDDEN) in shapes and (bound, WIDTH) in shapes
    # ... and the remainder is there, under its guard, with the other rows
    assert sum(eqn.primitive.name == "cond" for eqn in main) >= 1
    everything = {tuple(v.aval.shape)
                  for eqn in _equations(jaxpr, into_cond=True)
                  for v in eqn.outvars}
    assert (pairs - bound, WIDTH) in everything


def test_a_layer_that_holds_every_expert_traces_no_second_pass():
    eqns = list(_equations(_layer_gradient_jaxpr(ROUTED, MANY).jaxpr,
                           into_cond=True))
    assert not any(eqn.primitive.name == "cond" for eqn in eqns)
    shapes = {tuple(v.aval.shape) for eqn in eqns for v in eqn.outvars}
    assert (MANY * TOP_K, WIDTH) in shapes


_TRACED_ROWS = []


def _counted_experts(rows, group_sizes, w1, w3, w2):
    """A module-level ``expert_fn`` (one object for every layer) that notes
    each time it is traced."""
    from chainermn_tpu.ops.grouped_matmul import grouped_matmul

    _TRACED_ROWS.append(rows.shape[0])
    product = lambda a, w: grouped_matmul(a, w, group_sizes, "ragged_dot")
    return product(jax.nn.silu(product(rows, w1)) * product(rows, w3), w2)


def test_layers_that_pass_one_callable_share_the_remainders_trace():
    """Every program pays for tracing the remainder, differentiated under
    its ``cond``: two layers with one ``expert_fn`` object and equal shapes
    trace it ONCE (it is one jitted function of the weights), where a
    closure made per layer would trace it for each."""
    params, x = _whole_layer(tokens=MANY)
    stacks = [tuple(0.3 * jax.random.normal(jax.random.key(layer + i),
                                            params[name][2:4].shape)
                    for i, name in enumerate(("w1", "w3", "w2")))
              for layer in (10, 20)]

    def two_layers(x, stacks):
        for layer in stacks:
            y, _ = expert.dropless_moe(
                x, x @ params["gate"]["kernel"], None, _counted_experts,
                expert_args=layer, num_experts=ROUTED, top_k=TOP_K,
                first_expert=2, held_experts=2)
            x = x + y
        return jnp.sum(jnp.sin(x))

    del _TRACED_ROWS[:]
    grads = jax.jit(jax.grad(two_layers, (0, 1)))(x[0], stacks)
    # the main pass of each layer, and the remainder's one window once
    assert _TRACED_ROWS == [1024, 1024, 1024], _TRACED_ROWS
    assert all(np.abs(np.asarray(g)).sum() > 0
               for g in jax.tree.leaves(grads))

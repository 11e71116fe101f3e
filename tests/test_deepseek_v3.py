"""The program's DeepSeek-V3-shaped decoder (``chainermn_tpu/models/
deepseek_v3.py``: latent attention, shared experts beside a dropless sigmoid
top-k) against the plain reference (``chipbench/references/
deepseek_v3.py``) on seeded random weights at toy sizes in float32: a dense
layer, a sparse layer, the whole model with a share of the experts and with
every expert held, each with logits, loss and every gradient leaf; three
optimizer steps through ``make_train_step``; the eight shares of a layer
adding up to the uncut layer with the shared experts counted once; the
vocabulary slice; the controls of the new mathematics; the names the
benchmark's readers go by."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu.models import deepseek_v3, lfm2
from chainermn_tpu.optimizers import init_opt_state, make_train_step
from chainermn_tpu.parallel.topology import init_topology
from chainermn_tpu.training.trainer import put_global_batch
from chipbench import spec

BATCH, SEQ, ROUTED = 2, 32, 8
SIZES = dict(
    vocab_size=96, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_attention_heads=4, num_key_value_heads=4,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=3, num_experts=3, num_experts_published=ROUTED,
    first_expert=2, n_shared_experts=2, num_experts_per_tok=2,
    first_k_dense_replace=1, mlp_layer_types=["dense", "sparse", "sparse"],
    rms_norm_eps=1e-5, rope_theta=100.0, norm_topk_prob=True,
    routed_scaling_factor=2.446)
# case -> what it changes of SIZES
CASES = {
    "dense_layer": dict(mlp_layer_types=["dense"]),
    "sparse_layer": dict(mlp_layer_types=["sparse"], first_k_dense_replace=0),
    "whole_model_a_share": {},
    "whole_model_every_expert": dict(n_routed_experts=ROUTED,
                                     num_experts=ROUTED, first_expert=0),
}


def _reference():
    return spec.load_module(spec.CHECKOUT, "references", "deepseek_v3")


def _config(sizes, **overrides):
    return deepseek_v3.DeepseekV3Config.from_dict(
        sizes, num_experts_routed=sizes["num_experts_published"], **overrides)


def _seeded(sizes, seed=0, batch=BATCH):
    """Random weights in the program's tree (every leaf drawn, the norms'
    scales around one) and a batch of tokens."""
    model = deepseek_v3.DeepseekV3(_config(sizes))
    tokens = jax.random.randint(jax.random.key(seed), (batch, SEQ), 0,
                                sizes["vocab_size"])
    shapes = jax.eval_shape(model.init, jax.random.key(0), tokens)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def leaf(index, path, shape):
        drawn = jax.random.normal(jax.random.fold_in(
            jax.random.key(seed + 1), index), shape.shape, jnp.float32)
        return (1.0 + 0.1 * drawn if str(path[-1].key) == "scale"
                else 0.2 * drawn)

    params = jax.tree_util.tree_unflatten(
        treedef, [leaf(i, p, s) for i, (p, s) in enumerate(leaves)])
    return model, params, tokens


def _loss_of(forward):
    def loss(params, tokens):
        logits = forward(params, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]).mean()
    return loss


@pytest.mark.parametrize("case", sorted(CASES))
def test_logits_loss_and_every_gradient_match_the_plain_reference(case):
    sizes = dict(SIZES, **CASES[case])
    model, params, tokens = _seeded(sizes)
    plain = _reference().make_forward(sizes)
    np.testing.assert_allclose(jax.jit(model.apply)(params, tokens),
                               jax.jit(plain)(params, tokens),
                               rtol=2e-4, atol=2e-5)
    got = jax.jit(jax.value_and_grad(_loss_of(model.apply)))(params, tokens)
    want = jax.jit(jax.value_and_grad(_loss_of(plain)))(params, tokens)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    np.testing.assert_allclose(
        got[0], jax.jit(_reference().make_loss(sizes))(params, (tokens,)),
        rtol=1e-5)
    got_leaves, treedef = jax.tree_util.tree_flatten(got[1])
    assert treedef == jax.tree_util.tree_structure(want[1])
    for a, b in zip(got_leaves, jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    for layer in got[1]["params"].values():     # a share is held, and trains
        if "moe" in layer:
            assert layer["moe"]["w1"].shape[0] == sizes["num_experts"]
            assert np.abs(np.asarray(layer["moe"]["gate"]["kernel"])).sum() > 0
            assert float(np.abs(layer["moe"]["expert_bias"]).sum()) == 0.0


def test_the_flash_kernels_carry_the_same_model():
    """The path the chip runs, ``attention_impl="flash"``: 24-wide keys
    beside 16-wide values through the kernels (Pallas' interpreter here,
    outside ``shard_map``), against the unfused path."""
    model, params, tokens = _seeded(SIZES)
    fused = deepseek_v3.DeepseekV3(_config(SIZES, attention_impl="flash"))
    np.testing.assert_allclose(fused.apply(params, tokens),
                               model.apply(params, tokens),
                               rtol=2e-4, atol=2e-5)
    got = jax.grad(_loss_of(fused.apply))(params, tokens)
    want = jax.grad(_loss_of(model.apply))(params, tokens)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("control", ["nope_scale", "unrotated_key"])
def test_a_control_of_the_new_mathematics_is_another_function(control):
    """The reference with the scores over sqrt(128) instead of sqrt(192), or
    with the shared key head left unrotated: what a program that took latent
    attention for plain attention would compute."""
    reference = _reference()
    assert control in reference.CONTROLS
    _, params, tokens = _seeded(SIZES)
    sound = jax.jit(reference.make_forward(SIZES))(params, tokens)
    wrong = jax.jit(reference.make_forward(SIZES, control))(params, tokens)
    assert float(jnp.abs(sound - wrong).max()) > 1e-2 * float(
        jnp.abs(sound).max())


def test_the_scores_are_two_products_over_one_scale_and_one_shared_key():
    """Latent attention by hand, for one layer's module: the rotated part of
    every head scores against ONE key head, the scale is over nope + rope,
    values are v_head_dim wide."""
    sizes = dict(SIZES, **CASES["dense_layer"])
    cfg = _config(sizes)
    x = jax.random.normal(jax.random.key(3), (1, SEQ, 32), jnp.float32)
    module = deepseek_v3.MLA(cfg)
    params = module.init(jax.random.key(4), x)
    p = params["params"]
    assert {k: v["kernel"].shape for k, v in p.items() if "kernel" in v} == {
        "q_proj": (32, 4 * 24), "kv_a_proj_with_mqa": (32, 16 + 8),
        "kv_b_proj": (16, 4 * 32), "o_proj": (4 * 16, 32)}
    assert p["kv_a_layernorm"]["scale"].shape == (16,)
    q = (x @ p["q_proj"]["kernel"]).reshape(1, SEQ, 4, 24)
    latent = x @ p["kv_a_proj_with_mqa"]["kernel"]
    c = latent[..., :16]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-5)
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(1, SEQ, 4, 32)
    q_pe = lfm2.rope(q[..., 16:], 100.0)
    k_pe = lfm2.rope(latent[:, :, None, 16:], 100.0)[:, :, 0]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :16], kv[..., :16])
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe)) / math.sqrt(24)
    causal = jnp.arange(SEQ)[:, None] >= jnp.arange(SEQ)[None]
    weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, kv[..., 16:])
    np.testing.assert_allclose(
        module.apply(params, x),
        out.reshape(1, SEQ, 64) @ p["o_proj"]["kernel"], rtol=2e-4,
        atol=2e-5)


# ---- the share ties to the model --------------------------------------------

def _whole_layer(seed=0, tokens=96):
    """Weights of the UNCUT expert layer (all eight experts and the shared
    pair) in the reference's tree, and tokens."""
    hidden, width = 32, 16
    draw = lambda i, *shape: jax.random.normal(
        jax.random.fold_in(jax.random.key(seed), i), shape, jnp.float32)
    dense = lambda i, rows, cols: {"kernel": 0.3 * draw(i, rows, cols)}
    params = {"gate": {"kernel": 0.2 * draw(0, hidden, ROUTED)},
              "expert_bias": 0.5 * draw(1, ROUTED),
              "w1": 0.3 * draw(2, ROUTED, hidden, width),
              "w3": 0.3 * draw(3, ROUTED, hidden, width),
              "w2": 0.3 * draw(4, ROUTED, width, hidden),
              "shared": {"w1": dense(6, hidden, 2 * width),
                         "w3": dense(7, hidden, 2 * width),
                         "w2": dense(8, 2 * width, hidden)}}
    return params, draw(5, 1, tokens, hidden)


def _share_sizes(first, held):
    return dict(SIZES, n_routed_experts=held, num_experts=held,
                first_expert=first)


@pytest.mark.parametrize("tokens", [96, 1024])
@pytest.mark.parametrize("held", [1, 2])
def test_the_shares_add_up_to_the_uncut_layer(held, tokens):
    """Experts 0, 1, ... 7 (or 0-1, 2-3, ...) on eight (four) chips: the
    shares' routed parts and ONE copy of the shared experts are the uncut
    reference's layer output; every (token, expert) pair is computed once."""
    from chipbench.references.common import Products

    reference = _reference()
    params, x = _whole_layer(tokens=tokens)
    cut = lambda w, first: w[first:first + held]
    whole = reference.sparse_moe(x, params, _share_sizes(0, ROUTED),
                                 Products())
    alike = reference.ffn(x, params["shared"], Products())
    total, held_pairs = alike, 0.0
    for first in range(0, ROUTED, held):
        share = dict(params, **{name: cut(params[name], first)
                                for name in ("w1", "w3", "w2")})
        y, counters = lfm2.SparseMoE(
            _config(_share_sizes(first, held))).apply({"params": share}, x)
        np.testing.assert_allclose(     # each share is the reference's too
            y, reference.sparse_moe(x, share, _share_sizes(first, held),
                                    Products()), rtol=1e-5, atol=1e-5)
        total = total + (y - alike)     # the share's routed part
        held_pairs += float(counters["tokens_per_held_expert"].sum())
        assert float(counters["dropped_pairs"]) == 0.0
    np.testing.assert_allclose(total, whole, rtol=3e-5, atol=3e-5)
    assert float(jnp.abs(alike).mean()) > 0.1 * float(jnp.abs(whole).mean())
    assert held_pairs == tokens * SIZES["num_experts_per_tok"]


def test_the_loss_over_a_vocabulary_slice_is_the_references_over_the_slice():
    """The chip holds the first rows of the vocabulary: the embedding's rows
    and the head's columns.  Its loss is the uncut model's cross-entropy
    with the softmax taken over the slice, on tokens of the slice."""
    whole = dict(SIZES, vocab_size=96)
    held = dict(SIZES, vocab_size=40)
    _, params, _ = _seeded(whole)
    tokens = jax.random.randint(jax.random.key(5), (BATCH, SEQ), 0, 40)
    sliced = jax.tree.map(lambda x: x, params)
    sliced["params"]["embed_tokens"]["embedding"] = (
        params["params"]["embed_tokens"]["embedding"][:40])
    sliced["params"]["lm_head"]["kernel"] = (
        params["params"]["lm_head"]["kernel"][:, :40])
    model = deepseek_v3.DeepseekV3(_config(held))
    got = _loss_of(model.apply)(sliced, tokens)
    uncut = _reference().make_forward(whole)(params, tokens)
    want = optax.softmax_cross_entropy_with_integer_labels(
        uncut[:, :-1, :40], tokens[:, 1:]).mean()
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(
        got, _reference().make_loss(held)(sliced, (tokens,)), rtol=1e-5)


# ---- through the program's entry points -------------------------------------

OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
SCOPES = ("chainermn.moe.afmoe_route", "chainermn.moe.dispatch",
          "chainermn.moe.experts", "chainermn.moe.combine",
          "chainermn.moe.shared_experts", "chainermn.rope",
          "chainermn.mla_key")
LEARNING_RATE, MOMENTUM = 0.01, 0.9


@pytest.fixture(scope="module")
def train_step():
    """The model through create_communicator -> bcast_data -> the
    double-buffered create_multi_node_optimizer -> make_train_step, with its
    counters, on one device (the benchmark's cell is one chip)."""
    comm = chainermn_tpu.create_communicator(
        "xla", topology=init_topology(devices=jax.devices()[:1]))
    model, params, _ = _seeded(SIZES)
    batches = [jax.random.randint(jax.random.key(9 + i), (BATCH, SEQ), 0,
                                  SIZES["vocab_size"]) for i in range(3)]
    placed = comm.bcast_data(params)
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(LEARNING_RATE, momentum=MOMENTUM), comm,
        double_buffering=True)
    state = init_opt_state(comm, optimizer, placed)

    def loss_fn(p, batch):
        (t,) = batch
        logits, counters = model.apply(p, t, with_counters=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], t[:, 1:]).mean(), counters

    step = make_train_step(comm, loss_fn, optimizer, has_aux=True,
                           donate=False)
    return (step, params, placed, state,
            [put_global_batch(comm, (t,)) for t in batches], batches)


def test_three_optimizer_steps_are_the_references(train_step):
    """Three steps of the double-buffered SGD through ``make_train_step``
    against the plain reference's gradients applied by hand: the first
    update applies zeros, the second the first gradient, the third the
    second (the third gradient is stashed)."""
    step, params, placed, state, placed_batches, batches = train_step
    losses = []
    for batch in placed_batches:
        placed, state, loss, _ = step(placed, state, batch)
        losses.append(float(loss))
    plain = jax.jit(jax.value_and_grad(
        _loss_of(_reference().make_forward(SIZES))))
    p, trace, pending, want_losses = params, None, None, []
    for tokens in batches:
        loss, grads = plain(p, tokens)
        want_losses.append(float(loss))
        applied, pending = pending, grads
        if applied is None:
            continue
        trace = applied if trace is None else jax.tree.map(
            lambda m, g: MOMENTUM * m + g, trace, applied)
        p = jax.tree.map(lambda a, m: a - LEARNING_RATE * m, p, trace)
    np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
    moved = 0
    for got, want, start in zip(jax.tree.leaves(placed), jax.tree.leaves(p),
                                jax.tree.leaves(params)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
        moved += bool(np.abs(np.asarray(want) - np.asarray(start)).max() > 0)
    # every leaf but the two layers' expert_bias, which gets no gradient
    assert moved == len(jax.tree.leaves(params)) - 2


def test_the_scopes_and_the_module_names_are_in_the_compiled_step(
        train_step):
    step, _, placed, state, placed_batches, _ = train_step
    text = step.lower(placed, state, placed_batches[0]).compile().as_text()
    names = OP_NAME.findall(text)
    for scope in SCOPES:
        assert any(scope in name for name in names), scope
    for part in ("layer_0/mla/q_proj", "layer_0/mla/kv_a_proj_with_mqa",
                 "layer_0/mla/kv_a_layernorm", "layer_0/mla/kv_b_proj",
                 "layer_0/mla/o_proj", "layer_0/ffn", "layer_1/moe/gate",
                 "layer_2/moe/chainermn.moe.shared_experts/shared",
                 "layer_2/mla/chainermn.rope",
                 "layer_2/mla/chainermn.mla_key"):
        assert any(part in name for name in names), part
    # no reader of another family may take this model's modules for its own:
    # no attention module of theirs, and the shared experts NOT under
    # AFMoE's ``chainermn.moe.shared`` (an accepted test holds
    # ``moe_shared_ms`` to None on every other cell's recording)
    assert not any(re.search(r"\b(attn|swa|nope|full|sliding|block_\d+)/",
                             name) for name in names)
    assert not any(re.search(r"chainermn\.moe\.(shared|route|softmax_route)"
                             r"(/|$|\))", name) for name in names)


def test_the_step_reports_its_counters(train_step):
    step, _, placed, state, placed_batches, _ = train_step
    _, _, _, counters = step(placed, state, placed_batches[0])
    assert set(counters) == {"layer_1", "layer_2"}     # layer 0 is dense
    for counted in counters.values():
        assert set(counted) >= {"held_share", "dropped_pairs",
                                "load_max_over_mean", "rows_bound",
                                "rows_past_bound", "remainder_chunks"}
        assert counted["tokens_per_held_expert"].shape == (3,)
        assert float(counted["dropped_pairs"]) == 0.0


def test_a_config_that_is_not_this_model_is_refused():
    config = _config(SIZES)
    assert (config.score_func, config.use_expert_bias, config.norm_topk_eps,
            config.num_shared_experts, config.num_experts,
            config.moe_route_scope, config.moe_shared_scope) == (
                "sigmoid", True, 1e-20, 2, 3, "chainermn.moe.afmoe_route",
                "chainermn.moe.shared_experts")
    assert hash(config) == hash(_config(SIZES))     # a module's attribute
    for wrong in (dict(mlp_layer_types=["sparse", "dense", "sparse"]),
                  dict(mlp_layer_types=[]),
                  dict(mlp_layer_types=["dense", "dense", "sparse"]),
                  dict(q_lora_rank=1536), dict(rope_scaling={"factor": 4}),
                  dict(num_key_value_heads=2), dict(scoring_func="softmax"),
                  dict(topk_method="greedy"), dict(n_group=8, topk_group=4),
                  dict(tie_word_embeddings=True), dict(attention_bias=True)):
        with pytest.raises(ValueError):
            _config(dict(SIZES, **wrong))


def test_the_other_families_shared_expert_keeps_its_scope():
    """``SparseMoE`` reads the scope of its shared experts off the config,
    as it reads the routing's: AFMoE's stays ``chainermn.moe.shared``."""
    from chainermn_tpu.models.afmoe import AfmoeConfig

    assert lfm2.LFM2Config.moe_shared_scope == "chainermn.moe.shared"
    assert AfmoeConfig.moe_shared_scope == "chainermn.moe.shared"
    assert "moe_shared_scope" not in {
        f.name for f in dataclasses.fields(AfmoeConfig)}

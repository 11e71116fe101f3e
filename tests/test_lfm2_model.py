"""The program's LFM2-MoE (``chainermn_tpu/models/lfm2.py``) against the
plain reference (``chipbench/references/lfm2_moe.py``) on seeded random
weights at toy sizes in float32: one case a layer kind, one for the whole
model with its loss and gradients; the short convolution's causality; RoPE
and QK-norm; the scopes in the compiled step; the counters through
``make_train_step(has_aux=True)``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu.models import lfm2
from chainermn_tpu.optimizers import init_opt_state, make_train_step
from chainermn_tpu.training.trainer import put_global_batch
from chipbench import spec
from chipbench.references.common import Products

SIZES = dict(
    vocab_size=96, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, layer_types=["conv", "full_attention", "conv"],
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    num_experts=3, num_experts_published=8, first_expert=2,
    num_experts_per_tok=2, conv_L_cache=3, norm_eps=1e-5,
    norm_topk_prob=True, rope_theta=1_000_000.0, routed_scaling_factor=1.0,
    use_expert_bias=True)
BATCH, SEQ = 2, 24
# layer kind -> (layer_types, num_dense_layers) of a one-layer model
KINDS = {
    "conv_dense": (["conv"], 1),
    "conv_moe": (["conv"], 0),
    "attention_dense": (["full_attention"], 1),
    "attention_moe": (["full_attention"], 0),
}


def _reference():
    return spec.load_module(spec.CHECKOUT, "references", "lfm2_moe")


def _config(sizes):
    return lfm2.LFM2Config.from_dict(
        sizes, num_experts_routed=sizes["num_experts_published"])


def _seeded(sizes, seed=0):
    """Random weights in the program's tree (every leaf but the norms'
    scales drawn, ``expert_bias`` too) and a batch of tokens."""
    model = lfm2.LFM2MoE(_config(sizes))
    tokens = jax.random.randint(jax.random.key(seed), (BATCH, SEQ), 0,
                                sizes["vocab_size"])
    shapes = jax.eval_shape(model.init, jax.random.key(0), tokens)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def leaf(index, path, shape):
        name = str(getattr(path[-1], "key", path[-1]))
        drawn = jax.random.normal(jax.random.fold_in(
            jax.random.key(seed + 1), index), shape.shape, jnp.float32)
        return 1.0 + 0.1 * drawn if name == "scale" else 0.2 * drawn

    params = jax.tree_util.tree_unflatten(
        treedef, [leaf(i, p, s) for i, (p, s) in enumerate(leaves)])
    return model, params, tokens


def _loss_of(forward):
    def loss(params, tokens):
        logits = forward(params, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]).mean()
    return loss


def _assert_trees_close(got, want, rtol=2e-4, atol=2e-5):
    got_leaves, treedef = jax.tree_util.tree_flatten(got)
    assert treedef == jax.tree_util.tree_structure(want)
    for a, b in zip(got_leaves, jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)


@pytest.mark.parametrize("kind", sorted(KINDS) + ["whole_model"])
def test_forward_loss_and_gradients_match_the_plain_reference(kind):
    sizes = dict(SIZES)
    if kind != "whole_model":
        sizes["layer_types"], sizes["num_dense_layers"] = KINDS[kind]
    model, params, tokens = _seeded(sizes)
    plain = _reference().make_forward(sizes)
    np.testing.assert_allclose(model.apply(params, tokens),
                               plain(params, tokens), rtol=2e-4, atol=2e-5)
    got = jax.value_and_grad(_loss_of(model.apply))(params, tokens)
    want = jax.value_and_grad(_loss_of(plain))(params, tokens)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    _assert_trees_close(got[1], want[1])
    if kind.endswith("moe") or kind == "whole_model":
        moe = [layer["moe"] for layer in got[1]["params"].values()
               if "moe" in layer]
        assert moe and all(not np.asarray(m["expert_bias"]).any()
                           for m in moe)
        assert all(m["w1"].shape[0] == sizes["num_experts"] for m in moe)


def test_the_reference_loss_is_the_programs_at_the_whole_model():
    model, params, tokens = _seeded(SIZES)
    plain = _reference().make_loss(SIZES)(params, (tokens,))
    np.testing.assert_allclose(_loss_of(model.apply)(params, tokens), plain,
                               rtol=1e-5)


def test_the_tied_head_has_no_weights_of_its_own():
    _, params, _ = _seeded(SIZES)
    assert set(params["params"]) == {
        "embed_tokens", "embedding_norm", "layer_0", "layer_1", "layer_2"}
    assert set(params["params"]["layer_0"]) == {
        "operator_norm", "conv", "ffn_norm", "ffn"}
    assert set(params["params"]["layer_1"]) == {
        "operator_norm", "attn", "ffn_norm", "moe"}
    names = {"/".join(str(k.key) for k in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert not any(name.endswith("bias") and "expert_bias" not in name
                   for name in names)


@pytest.mark.parametrize("position", [0, 5, SEQ - 1])
def test_a_change_at_position_t_moves_no_output_before_t(position):
    """Causality of the short convolution (and of the whole conv layer)."""
    config = _config(dict(SIZES, layer_types=["conv"]))
    module = lfm2.ShortConv(config)
    u = jax.random.normal(jax.random.key(0), (BATCH, SEQ, 32))
    params = module.init(jax.random.key(1), u)
    moved = u.at[:, position].add(1.0)
    before, after = module.apply(params, u), module.apply(params, moved)
    changed = np.abs(np.asarray(after - before)).max(axis=(0, 2)) > 0
    assert not changed[:position].any()
    # ... and reaches exactly the conv_L_cache positions from t on
    assert changed[position:position + 3].all()
    assert not changed[position + 3:].any()


def test_rope_and_qk_norm_match_the_reference():
    x = jax.random.normal(jax.random.key(0), (BATCH, SEQ, 4, 16))
    np.testing.assert_allclose(lfm2.rope(x, 1e6),
                               _reference().rope(x, 1e6), rtol=1e-5,
                               atol=1e-6)
    # position 0 is not rotated; a rotation keeps every pair's length
    np.testing.assert_allclose(lfm2.rope(x, 1e6)[:, 0], x[:, 0], rtol=1e-6)
    pairs = lambda t: np.hypot(np.asarray(t)[..., :8], np.asarray(t)[..., 8:])
    np.testing.assert_allclose(pairs(lfm2.rope(x, 1e6)), pairs(x), rtol=1e-5)
    # the whole attention operator: QK-norm over head_dim, then RoPE
    sizes = dict(SIZES, layer_types=["full_attention"], num_dense_layers=1)
    config = _config(sizes)
    module = lfm2.Attention(config)
    u = jax.random.normal(jax.random.key(2), (BATCH, SEQ, 32))
    params = module.init(jax.random.key(3), u)
    scale = lambda key: 1.0 + 0.3 * jax.random.normal(
        jax.random.key(key), (8,))
    params = {"params": dict(params["params"],
                             q_layernorm={"scale": scale(4)},
                             k_layernorm={"scale": scale(5)})}
    np.testing.assert_allclose(
        module.apply(params, u),
        _reference().attention(u, params["params"], sizes, Products()),
        rtol=2e-4, atol=2e-5)


OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
SCOPES = ("chainermn.moe.route", "chainermn.moe.dispatch",
          "chainermn.moe.experts", "chainermn.moe.combine",
          "chainermn.shortconv", "chainermn.rope")


@pytest.fixture(scope="module")
def train_step():
    """The model through create_communicator -> bcast_data -> the
    double-buffered create_multi_node_optimizer -> make_train_step, with its
    counters."""
    comm = chainermn_tpu.create_communicator(
        "xla", allreduce_grad_dtype="bfloat16")
    model, params, _ = _seeded(SIZES)
    tokens = jax.random.randint(jax.random.key(9), (comm.size, SEQ), 0,
                                SIZES["vocab_size"])
    params = comm.bcast_data(params)
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.01, momentum=0.9), comm, double_buffering=True)
    state = init_opt_state(comm, optimizer, params)

    def loss_fn(p, batch):
        (t,) = batch
        logits, counters = model.apply(p, t, with_counters=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], t[:, 1:]).mean(), counters

    step = make_train_step(comm, loss_fn, optimizer, has_aux=True,
                           donate=False)
    return step, params, state, put_global_batch(comm, (tokens,)), comm


def test_the_six_scopes_and_the_layer_names_are_in_the_compiled_step(
        train_step):
    step, params, state, batch, _ = train_step
    text = step.lower(params, state, batch).compile().as_text()
    names = OP_NAME.findall(text)
    for scope in SCOPES:
        assert any(scope in name for name in names), scope
    # flax's names keep a layer's operator and its feed-forward apart
    for part in ("layer_0/conv", "layer_0/ffn", "layer_1/attn",
                 "layer_1/moe", "layer_2/conv", "layer_2/moe"):
        assert any(part in name for name in names), part
    # the experts' scope lies under the module, not around it
    assert any("layer_1/moe/chainermn.moe.experts" in name for name in names)


def test_the_step_trains_and_reports_its_counters(train_step):
    step, params, state, batch, comm = train_step
    losses = []
    for _ in range(6):
        params, state, loss, counters = step(params, state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert set(counters) == {"layer_1", "layer_2"}
    tokens = batch[0].size // comm.size          # a device's tokens
    for counted in counters.values():
        assert counted["tokens_per_held_expert"].shape == (3,)
        assert float(counted["dropped_pairs"]) == 0.0
        np.testing.assert_allclose(
            float(counted["held_share"]),
            float(counted["tokens_per_held_expert"].sum()) / (2 * tokens),
            rtol=1e-6)
        assert float(counted["load_max_over_mean"]) >= 1.0

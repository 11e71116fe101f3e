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
    # flax's names keep a layer's operator and its feed-forward apart (a
    # checkpoint's own scopes, ``checkpoint/layer_0.operator``, may lie
    # between the layer and the module: a reader goes by the segments)
    for layer, module in (("layer_0", "conv"), ("layer_0", "ffn"),
                          ("layer_1", "attn"), ("layer_1", "moe"),
                          ("layer_2", "conv"), ("layer_2", "moe")):
        inside = re.compile(rf"/{layer}/(?:[^/]+/)*?{module}/")
        assert any(inside.search(name) for name in names), (layer, module)
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


# ---- what the backward pass makes again (PR 47) ----------------------------
# The model's layers as they were composed before the checkpoints, from the
# same modules under the same names: the plain composition every case below
# holds the checkpointed model to.

class PlainAttention(lfm2.nn.Module):
    config: lfm2.LFM2Config

    @lfm2.nn.compact
    def __call__(self, u):
        cfg = self.config
        heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads
        head_dim = u.shape[-1] // heads
        split = lambda t, n: t.reshape(t.shape[:-1] + (n, head_dim))
        q, k, v = (split(lfm2._dense(n * head_dim, cfg.dtype, name)(u), n)
                   for name, n in (("q_proj", heads), ("k_proj", kv_heads),
                                   ("v_proj", kv_heads)))
        q, k = lfm2.qk_norm_and_rope(
            q, k, ("q_layernorm", "k_layernorm"), cfg.norm_eps, cfg.dtype,
            cfg.attention_impl, cfg.rope_theta)
        out = lfm2.causal_attention(q, k, v, cfg.attention_impl)
        return lfm2._dense(u.shape[-1], cfg.dtype, "out_proj")(
            out.reshape(u.shape))


class PlainLayer(lfm2.nn.Module):
    config: lfm2.LFM2Config
    index: int

    @lfm2.nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: lfm2.RMSNorm(cfg.norm_eps, cfg.dtype, name=name)
        if cfg.layer_types[self.index] == "conv":
            operator = lfm2.ShortConv(cfg, name="conv")
        else:
            operator = PlainAttention(cfg, name="attn")
        x = x + operator(norm("operator_norm")(x))
        h = norm("ffn_norm")(x)
        if self.index < cfg.num_dense_layers:
            return x + lfm2.DenseFFN(cfg, name="ffn")(h)
        return x + lfm2.SparseMoE(cfg, name="moe")(h)[0]


class PlainModel(lfm2.nn.Module):
    config: lfm2.LFM2Config

    @lfm2.nn.compact
    def __call__(self, tokens):
        cfg = self.config
        embed = lfm2.nn.Embed(cfg.vocab_size, cfg.hidden_size,
                              param_dtype=jnp.float32, dtype=cfg.dtype,
                              name="embed_tokens")
        x = embed(tokens)
        for index in range(len(cfg.layer_types)):
            x = PlainLayer(cfg, index, name=f"layer_{index}")(x)
        x = lfm2.RMSNorm(cfg.norm_eps, cfg.dtype, name="embedding_norm")(x)
        return embed.attend(x).astype(jnp.float32)


def _both(kind, seq=SEQ, **more):
    """The checkpointed model, the plain composition, weights, tokens."""
    sizes = dict(SIZES, **more)
    if kind != "whole_model":
        sizes["layer_types"], sizes["num_dense_layers"] = KINDS[kind]
    # the weights do not depend on what computes the products
    _, params, tokens = _seeded(dict(sizes, attention_impl="xla",
                                     moe_matmul_impl="ragged_dot"))
    tokens = jnp.tile(tokens, (1, -(-seq // SEQ)))[:, :seq]
    config = _config(sizes)
    return lfm2.LFM2MoE(config), PlainModel(config), params, tokens


@pytest.mark.parametrize("kind", sorted(KINDS) + ["whole_model"])
def test_loss_and_gradients_are_the_plain_compositions(kind):
    """Making a value again is the same operations on the same inputs: the
    loss is the plain composition's BIT FOR BIT, and so is every gradient
    behind the last checkpoint.  Further back a gradient differs in its last
    bits, by ORDER alone: a value read twice inside a checkpoint (a norm's
    input: by the mean of squares and by the product) gets its two
    cotangents added inside and the residual's after, where the plain
    program adds them as it meets them (1-4e-7 of a leaf's largest entry in
    float32, every kind).  Run operation by operation: compiled as a whole,
    XLA's CPU pipeline fuses the two programs differently."""
    model, plain, params, tokens = _both(kind)
    with jax.disable_jit():
        got = jax.value_and_grad(_loss_of(model.apply))(params, tokens)
        want = jax.value_and_grad(_loss_of(plain.apply))(params, tokens)
    same = lambda a, b: np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert same(got[0], want[0])
    flat, treedef = jax.tree_util.tree_flatten_with_path(got[1])
    assert treedef == jax.tree_util.tree_structure(want[1])
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want[1])):
        assert np.abs(a - b).max() <= 1e-6 * np.abs(b).max(), path
    # behind the last checkpoint: the final norm, and the last layer's
    # feed-forward where it is the dense one
    last = got[1]["params"], want[1]["params"]
    assert same(*(tree["embedding_norm"]["scale"] for tree in last))
    if kind.endswith("dense"):
        assert all(same(*(tree["layer_0"]["ffn"][w]["kernel"]
                          for tree in last)) for w in ("w1", "w3", "w2"))


# every leaf of the toy model's tree as the parent of PR 47 built it
PARENTS_TREE = {
    "embed_tokens/embedding": (96, 32),
    "embedding_norm/scale": (32,),
    **{f"layer_{n}/{leaf}": shape for n in (0, 2) for leaf, shape in {
        "operator_norm/scale": (32,), "conv/in_proj/kernel": (32, 96),
        "conv/conv_kernel": (3, 32), "conv/out_proj/kernel": (32, 32),
        "ffn_norm/scale": (32,)}.items()},
    **{f"layer_1/{leaf}": shape for leaf, shape in {
        "operator_norm/scale": (32,), "attn/q_proj/kernel": (32, 32),
        "attn/k_proj/kernel": (32, 16), "attn/v_proj/kernel": (32, 16),
        "attn/q_layernorm/scale": (8,), "attn/k_layernorm/scale": (8,),
        "attn/out_proj/kernel": (32, 32), "ffn_norm/scale": (32,)}.items()},
    **{f"layer_0/ffn/{w}/kernel": shape for w, shape in
       (("w1", (32, 64)), ("w3", (32, 64)), ("w2", (64, 32)))},
    **{f"layer_{n}/moe/{leaf}": shape for n in (1, 2) for leaf, shape in {
        "gate/kernel": (32, 8), "expert_bias": (8,), "w1": (3, 32, 16),
        "w3": (3, 32, 16), "w2": (3, 16, 32)}.items()},
}


def test_the_parameter_tree_is_the_parents():
    """The lifted transform keeps every parameter where it was: the
    benchmark's ``make_params`` and the reference's leaf names stand."""
    model, plain, params, tokens = _both("whole_model")
    shapes = lambda tree: {
        "/".join(str(k.key) for k in path[1:]): leaf.shape for path, leaf
        in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert shapes(params) == PARENTS_TREE
    assert shapes(jax.eval_shape(plain.init, jax.random.key(0),
                                 tokens)) == PARENTS_TREE


CHECKPOINT = "remat2"          # ``jax.checkpoint``'s primitive, by name


def _equations(jaxpr, inside=()):
    """``(primitive name, the checkpoints around it)`` of every equation,
    sub-jaxprs walked; a checkpoint is its equation's parameters."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside
        within = inside + (eqn.params,) if (
            eqn.primitive.name == CHECKPOINT) else inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, within)


def _histogram(jaxpr):
    counted = {}
    for name, _ in _equations(jaxpr):
        if name != CHECKPOINT:
            counted[name] = counted.get(name, 0) + 1
    return counted


@pytest.mark.parametrize("kind", sorted(KINDS) + ["whole_model"])
def test_without_a_gradient_nothing_is_made_again(kind):
    """Inference traces what it traced: the forward pass holds the plain
    composition's operations, each once, and no checkpoint of the model's is
    a differentiated one."""
    model, plain, params, tokens = _both(kind)
    traced = jax.make_jaxpr(model.apply)(params, tokens).jaxpr
    assert _histogram(traced) == _histogram(
        jax.make_jaxpr(plain.apply)(params, tokens).jaxpr)
    own = [params for _, inside in _equations(traced) for params in inside
           if params["policy"] is jax.checkpoint_policies.dots_saveable]
    assert own and not any(params["differentiated"] for params in own)


PRODUCTS = ("dot_general", "ragged_dot", "pallas_call")
# the Pallas kernels on, heads of 128: flash attention, the fused QK-norm and
# rotation, the grouped products (traced alone; nothing here is lowered)
KERNELS_ON = dict(hidden_size=256, num_attention_heads=2,
                  num_key_value_heads=1, attention_impl="flash",
                  moe_matmul_impl="pallas", seq=256)


@pytest.mark.parametrize("kind,more", [
    (kind, {}) for kind in sorted(KINDS) + ["whole_model"]
] + [("whole_model", KERNELS_ON)],
    ids=sorted(KINDS) + ["whole_model", "whole_model_kernels_on"])
def test_the_backward_pass_runs_no_product_twice(kind, more):
    """The gradient holds as many products and kernels as the plain
    composition's, the model's checkpoints hold no kernel, and what they add
    to the program is elementwise work and reductions alone."""
    model, plain, params, tokens = _both(kind, **more)
    gradient = lambda net: jax.make_jaxpr(jax.grad(_loss_of(net.apply)))(
        params, tokens).jaxpr
    got, want = gradient(model), gradient(plain)
    ours = lambda inside: any(
        params["policy"] is jax.checkpoint_policies.dots_saveable
        for params in inside)
    assert any(ours(inside) for _, inside in _equations(got))
    assert not any(name in ("pallas_call", "ragged_dot") and ours(inside)
                   for name, inside in _equations(got))
    counted, plainly = _histogram(got), _histogram(want)
    for name in PRODUCTS:
        assert counted.get(name, 0) == plainly.get(name, 0), name
    if more:
        assert counted["pallas_call"] >= 3 + 2 + 9 * 2
    made_again = {name for name in counted
                  if counted[name] > plainly.get(name, 0)}
    assert made_again and not made_again & {
        "dot_general", "ragged_dot", "pallas_call", "conv_general_dilated",
        "gather", "scatter-add", "sort", "cumsum", "while", "cond"}

"""QK-norm and rotation as one kernel (``chainermn_tpu/ops/qk_norm_rope.py``,
PR 41) against the plain form it stands in for, ``models.lfm2.RMSNorm``
followed by ``models.lfm2.rope``: the kernel in Pallas' interpreter, value
and gradients with respect to the input and the scale, with and without the
rotation, under plain and YaRN frequencies, at 32 query / 4 kv heads of 128
on a sequence that is no multiple of the row tile.  In float32 the two forms
agree to rounding; in bfloat16 the kernel rounds once where the plain form
rounds twice, so it may lie closer to the float32 oracle and never further.
And which form ``qk_norm_and_rope`` traces: the kernel only where the Pallas
kernels are on and a head fills the lanes, under ``chainermn.rope``, over the
same parameter tree."""

import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import afmoe, lfm2
from chainermn_tpu.ops.qk_norm_rope import qk_norm_rope

HEADS, KV_HEADS, DIM, SEQ, EPS = 32, 4, 128, 100, 1e-6
YARN = {"rope_type": "yarn", "rope_theta": 5e5, "factor": 16,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2773}
# rotation -> (theta, scaling) of ``qk_norm_and_rope``
ROTATIONS = {"none": (None, None), "default": (1e4, None),
             "yarn": (5e5, YARN)}
NAMES = ("q_norm", "k_norm")


class Layer(nn.Module):
    """What an attention module does to q and k before the scores."""

    impl: str
    dtype: jnp.dtype
    theta: float = None
    scaling: dict = None

    @nn.compact
    def __call__(self, q, k):
        return lfm2.qk_norm_and_rope(q, k, NAMES, EPS, self.dtype, self.impl,
                                     self.theta, self.scaling)


def _layer(impl, dtype, rotation):
    theta, scaling = ROTATIONS[rotation]
    return Layer(impl, dtype, theta,
                 None if scaling is None else nn.FrozenDict(scaling))


def _inputs(batch, dtype, seq=SEQ, dim=DIM):
    """q, k, a weight on each result (all exact in ``dtype``) and the two
    scales."""
    keys = jax.random.split(jax.random.key(batch), 6)
    exact = lambda key, heads: jax.random.normal(
        key, (batch, seq, heads, dim), jnp.float32).astype(dtype)
    q, wq = exact(keys[0], HEADS), exact(keys[1], HEADS)
    k, wk = exact(keys[2], KV_HEADS), exact(keys[3], KV_HEADS)
    params = {"params": {
        name: {"scale": 1.0 + 0.2 * jax.random.normal(key, (dim,))}
        for name, key in zip(NAMES, keys[4:])}}
    return params, (q, k), (wq, wk)


def _value_and_grads(layer, params, qk, weights):
    """The two results, and the gradient of their weighted sum with respect
    to the scales and to q and k."""
    def loss(params, qk):
        out = layer.apply(params, *qk)
        return sum((y.astype(jnp.float32) * w.astype(jnp.float32)).sum()
                   for y, w in zip(out, weights)), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
        params, qk)
    return jax.tree.map(lambda a: np.asarray(a, np.float32), (out, grads))


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("rotation", sorted(ROTATIONS))
def test_float32_kernel_agrees_with_the_plain_form(rotation, batch):
    params, qk, weights = _inputs(batch, jnp.float32)
    fused = _value_and_grads(_layer("flash", jnp.float32, rotation),
                             params, qk, weights)
    plain = _value_and_grads(_layer("xla", jnp.float32, rotation),
                             params, qk, weights)
    for got, want in zip(jax.tree.leaves(fused), jax.tree.leaves(plain)):
        # the scales' gradients are sums over 100 x 32 products
        np.testing.assert_allclose(got, want, rtol=2e-5,
                                   atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("rotation", sorted(ROTATIONS))
def test_bfloat16_kernel_is_no_further_from_float32_than_the_plain_form(
        rotation, batch):
    """On bfloat16 q, k and incoming gradients, against the float32 oracle on
    the same values: every result within bfloat16's rounding of it, and the
    kernel's error (one rounding) at most the plain form's (two)."""
    params, qk, weights = _inputs(batch, jnp.bfloat16)
    fused = _value_and_grads(_layer("flash", jnp.bfloat16, rotation),
                             params, qk, weights)
    plain = _value_and_grads(_layer("xla", jnp.bfloat16, rotation),
                             params, qk, weights)
    as32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    oracle = _value_and_grads(_layer("xla", jnp.float32, rotation),
                              params, as32(qk), as32(weights))
    for got, twice, want in zip(*map(jax.tree.leaves,
                                     (fused, plain, oracle))):
        largest = np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=2 ** -8,
                                   atol=2 ** -8 * largest)
        error, plain_error = np.abs(got - want), np.abs(twice - want)
        assert error.mean() <= 1.001 * plain_error.mean() + 1e-6 * largest
        assert error.max() <= 1.001 * plain_error.max() + 1e-6 * largest


@pytest.fixture
def small_tiles(monkeypatch):
    """Row tiles of 32, eight heads a block."""
    kernels = sys.modules["chainermn_tpu.ops.qk_norm_rope"]
    monkeypatch.setattr(kernels, "_TILE_ROWS", 32)
    monkeypatch.setattr(kernels, "_BLOCK_BYTES", 32 * 8 * DIM * 2)
    assert kernels._tiling(SEQ, HEADS, DIM, 2) == (32, 8)


@pytest.mark.parametrize("rotation", ["none", "yarn"])
def test_tiles_and_blocks_of_heads_add_up(small_tiles, rotation):
    """Four row tiles (the last with four rows of the sequence) and four
    blocks of heads: the scale's gradient is summed over all
    of them and over the batch, the rows past the sequence count nothing."""
    params, qk, weights = _inputs(2, jnp.bfloat16)
    fused = _value_and_grads(_layer("flash", jnp.bfloat16, rotation),
                             params, qk, weights)
    as32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    oracle = _value_and_grads(_layer("xla", jnp.float32, rotation),
                              params, as32(qk), as32(weights))
    for got, want in zip(jax.tree.leaves(fused), jax.tree.leaves(oracle)):
        np.testing.assert_allclose(got, want, rtol=2 ** -8,
                                   atol=2 ** -8 * np.abs(want).max())


def test_kernel_takes_the_tables_it_is_given():
    """The function under the module-level one: ``(cos, sin)`` [T, D] as the
    plain form multiplies by them, whatever they hold."""
    params, (q, _), _ = _inputs(1, jnp.float32, seq=48)
    scale = params["params"]["q_norm"]["scale"]
    cos, sin = (table.reshape(48, DIM)
                for table in lfm2.rotary_tables(48, DIM, 1e4))
    normed = lfm2.RMSNorm(EPS).apply({"params": {"scale": scale}}, q)
    np.testing.assert_allclose(
        qk_norm_rope(q, scale, (cos, sin), eps=EPS), lfm2.rope(normed, 1e4),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(qk_norm_rope(q, scale, eps=EPS), normed,
                               rtol=1e-5, atol=1e-6)
    assert qk_norm_rope(q, scale, eps=EPS, dtype=jnp.bfloat16).dtype == (
        jnp.bfloat16)
    with pytest.raises(ValueError):
        qk_norm_rope(q[..., :64], scale[:64], eps=EPS)
    with pytest.raises(ValueError):
        qk_norm_rope(q, scale, (cos[:8], sin[:8]), eps=EPS)


def _traced(impl, dim, rotation="default"):
    """The text of the jaxpr of ``qk_norm_and_rope`` with its gradient, and
    the parameters' names."""
    layer = _layer(impl, jnp.bfloat16, rotation)
    params, qk, weights = _inputs(1, jnp.bfloat16, seq=32, dim=dim)
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(
                 jax.eval_shape(layer.init, jax.random.key(0), *qk))[0]]
    grads = jax.grad(lambda p, qk: sum(
        (y.astype(jnp.float32) * w).sum()
        for y, w in zip(layer.apply(p, *qk), weights)), argnums=(0, 1))
    return str(jax.make_jaxpr(grads)(params, qk)), names


@pytest.mark.parametrize("impl,dim,rotation,kernels", [
    # q and k, forward and backward
    ("flash", 128, "default", 4), ("flash", 128, "none", 4),
    ("flash", 256, "yarn", 4),
    # the CPU's and the references' path; lfm2's heads of 64
    ("xla", 128, "default", 0), ("flash", 64, "default", 0),
    ("xla", 64, "none", 0)])
def test_which_form_is_traced(impl, dim, rotation, kernels):
    """The kernel where the Pallas kernels are on (``attention_impl ==
    "flash"``) and ``head_dim`` is a multiple of 128, the plain modules
    elsewhere; the parameters keep their names either way."""
    text, names = _traced(impl, dim, rotation)
    assert text.count("pallas_call") == kernels, text
    assert names == ["['params']['k_norm']['scale']",
                     "['params']['q_norm']['scale']"]


SIZES = dict(
    vocab_size=64, hidden_size=64, intermediate_size=64,
    moe_intermediate_size=32, head_dim=128, num_attention_heads=4,
    num_key_value_heads=2, layer_types=["sliding_attention",
                                        "full_attention"],
    num_dense_layers=1, num_experts=2, num_experts_published=4,
    first_expert=1, num_experts_per_tok=2, num_shared_experts=1,
    sliding_window=8, rms_norm_eps=1e-6, rope_theta=1e4,
    route_norm=True, route_scale=2.0, moe_matmul_impl="ragged_dot")


def test_a_model_keeps_its_tree_and_its_logits_with_the_kernels_on():
    """AFMoE at heads of 128, a window layer that rotates and a full layer
    that does not: with ``attention_impl="flash"`` (the kernels interpreted)
    the parameter tree is the ``xla`` model's by name and shape, and on the
    same weights the logits and the gradients agree."""
    tokens = jax.random.randint(jax.random.key(0), (1, 16), 0, 64)
    models = {impl: afmoe.AfmoeMoE(afmoe.AfmoeConfig.from_dict(
        dict(SIZES, attention_impl=impl), num_experts_routed=4))
        for impl in ("xla", "flash")}
    shapes = {impl: jax.eval_shape(model.init, jax.random.key(0), tokens)
              for impl, model in models.items()}
    assert (jax.tree_util.tree_flatten_with_path(shapes["flash"])
            == jax.tree_util.tree_flatten_with_path(shapes["xla"]))
    params = models["xla"].init(jax.random.key(1), tokens)
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(2), a.shape),
        params)

    def loss(impl):
        return jax.value_and_grad(lambda p: (
            models[impl].apply(p, tokens) ** 2).mean())(params)

    (plain, plain_grads), (fused, fused_grads) = loss("xla"), loss("flash")
    assert fused == pytest.approx(plain, rel=1e-5)
    for got, want in zip(jax.tree.leaves(fused_grads),
                         jax.tree.leaves(plain_grads)):
        np.testing.assert_allclose(got, want, rtol=1e-3,
                                   atol=1e-4 * np.abs(want).max())

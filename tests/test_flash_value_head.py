"""``flash_attention`` with a VALUE head size unlike the query's and key's
(latent attention: 192-wide keys beside 128-wide values, and the toy's 24 /
16): values against plain ``jax.numpy`` and all three gradients against the
blockwise oracle and against autodiff of the plain form, causal, with a
window, with grouped kv heads and without, with ``return_lse``, offsets and
segment ids; v is neither padded nor copied; what must agree is said when it
does not.  Off the TPU the kernels run in Pallas' interpreter."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops import flash_attention, flash_tile_census

BATCH, SEQ, TILE = 2, 64, 32


def _plain(q, k, v, window=None, q_offset=0, kv_offset=0, segments=None):
    """Causal softmax attention in plain float32, ``(out, lse)``."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / math.sqrt(q.shape[-1])
    gap = ((q_offset + jnp.arange(q.shape[1]))[:, None]
           - (kv_offset + jnp.arange(k.shape[1]))[None])
    visible = gap >= 0
    if window is not None:
        visible = visible & (gap < window)
    visible = jnp.broadcast_to(visible[None, None], scores.shape)
    if segments is not None:
        visible = visible & (segments[:, None, :, None]
                             == segments[:, None, None, :])
    scores = jnp.where(visible, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
                     precision="highest")
    return out, jax.nn.logsumexp(scores, axis=-1)


def _operands(heads, kv_heads, dim, value_dim, seq=SEQ, seed=0):
    draw = lambda i, *shape: 0.5 * jax.random.normal(
        jax.random.fold_in(jax.random.key(seed), i), shape, jnp.float32)
    return (draw(0, BATCH, seq, heads, dim), draw(1, BATCH, seq, kv_heads, dim),
            draw(2, BATCH, seq, kv_heads, value_dim),
            draw(3, BATCH, seq, heads, value_dim))


SIZES = [(192, 128), (24, 16)]
HEADS = [(2, 2), (4, 2), (4, 1)]            # MHA, GQA 2:1, MQA


# every size x grouping with no window and with one that cuts a tile; a window
# of exactly one tile (every tile an edge tile) at the toy's size
CASES = [(dim, value_dim, heads, kv_heads, window)
         for dim, value_dim in SIZES for heads, kv_heads in HEADS
         for window in (None, 20)] + [
             (24, 16, heads, kv_heads, TILE) for heads, kv_heads in HEADS]


@pytest.mark.parametrize("dim,value_dim,heads,kv_heads,window", CASES)
def test_values_and_all_three_gradients(dim, value_dim, heads, kv_heads,
                                        window):
    q, k, v, weight = _operands(heads, kv_heads, dim, value_dim)
    fused = lambda impl: lambda q, k, v: (flash_attention(
        q, k, v, causal=True, window=window, block_q=TILE, block_k=TILE,
        bwd_impl=impl) * weight).sum()
    plain = lambda q, k, v: (_plain(q, k, v, window)[0] * weight).sum()
    out = flash_attention(q, k, v, causal=True, window=window, block_q=TILE,
                          block_k=TILE)
    assert out.shape == (BATCH, SEQ, heads, value_dim)
    np.testing.assert_allclose(out, _plain(q, k, v, window)[0], rtol=2e-5,
                               atol=2e-5)
    want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    oracle = jax.grad(fused("blockwise"), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(fused("pallas"), argnums=(0, 1, 2))(q, k, v)
    for name, a, b, c in zip("qkv", got, oracle, want):
        assert a.shape == c.shape, name
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("dim,value_dim", SIZES)
def test_the_logsumexp_and_its_gradient(dim, value_dim):
    q, k, v, weight = _operands(4, 2, dim, value_dim)

    def fused(q, k, v):
        out, lse = flash_attention(q, k, v, causal=True, return_lse=True,
                                   block_q=TILE, block_k=TILE)
        return (out * weight).sum() + (lse ** 2).sum()

    def plain(q, k, v):
        out, lse = _plain(q, k, v)
        return (out * weight).sum() + (lse ** 2).sum()

    _, lse = flash_attention(q, k, v, causal=True, return_lse=True,
                             block_q=TILE, block_k=TILE)
    assert lse.shape == (BATCH, 4, SEQ)
    np.testing.assert_allclose(lse, _plain(q, k, v)[1], rtol=2e-5, atol=2e-5)
    for a, b in zip(jax.grad(fused, argnums=(0, 1, 2))(q, k, v),
                    jax.grad(plain, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dim,value_dim", SIZES)
def test_offsets_and_segment_ids_take_the_generic_body_at_both_sizes(
        dim, value_dim):
    q, k, v, weight = _operands(2, 1, dim, value_dim)
    segments = jnp.asarray(np.repeat([[0, 1, 1, 2], [3, 3, 4, 4]], SEQ // 4,
                                     axis=1), jnp.int32)
    cases = {
        "offsets": (dict(q_offset=SEQ, kv_offset=TILE),
                    dict(q_offset=SEQ, kv_offset=TILE)),
        "segments": (dict(q_segment_ids=segments, kv_segment_ids=segments),
                     dict(segments=segments)),
    }
    for name, (given, plainly) in cases.items():
        fused = lambda q, k, v: (flash_attention(
            q, k, v, causal=True, block_q=TILE, block_k=TILE, **given)
            * weight).sum()
        plain = lambda q, k, v: (_plain(q, k, v, **plainly)[0]
                                 * weight).sum()
        np.testing.assert_allclose(fused(q, k, v), plain(q, k, v), rtol=1e-5,
                                   err_msg=name)
        for a, b in zip(jax.grad(fused, argnums=(0, 1, 2))(q, k, v),
                        jax.grad(plain, argnums=(0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4,
                                       err_msg=name)


def _pallas_calls(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found.extend(_pallas_calls(inner))
    return found


def test_v_is_neither_padded_nor_copied_to_the_key_size():
    """Every kernel is handed v, the output and their gradients at the VALUE
    head size and q, k and theirs at the key's: no operand of 192 + 128 or of
    a padded 192 where 128 belongs."""
    q, k, v, weight = _operands(4, 2, 192, 128)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, causal=True, block_q=TILE,
                                         block_k=TILE) * weight).sum(),
        argnums=(0, 1, 2)))(q, k, v).jaxpr
    forward, dkv, dq = _pallas_calls(jaxpr)
    widths = lambda avals: [a.aval.shape[-1] for a in avals
                            if len(a.aval.shape) == 3 and a.aval.shape[1] > 1]
    assert widths(forward.invars) == [192, 192, 128]          # q, k, v
    assert widths(forward.outvars) == [128]                   # the output
    assert widths(dkv.invars) == [192, 128, 192, 128]         # q, g, k, v
    assert widths(dkv.outvars) == [192, 128]                  # dk, dv
    assert widths(dq.invars) == [192, 128, 192, 128]
    assert widths(dq.outvars) == [192]


def test_what_must_agree_is_said():
    q, k, v, _ = _operands(4, 2, 24, 16)
    with pytest.raises(ValueError, match="kv length and kv heads"):
        flash_attention(q, k, v[:, :, :1], causal=True)
    with pytest.raises(ValueError, match="kv length and kv heads"):
        flash_attention(q, k, v[:, :SEQ // 2], causal=True)
    with pytest.raises(ValueError, match="q and k must share batch and head"):
        flash_attention(q[..., :16], k, v, causal=True)
    # the default scale is the KEY's head size's
    scaled = flash_attention(q, k, v, causal=True, sm_scale=24 ** -0.5,
                             block_q=TILE, block_k=TILE)
    np.testing.assert_array_equal(
        scaled, flash_attention(q, k, v, causal=True, block_q=TILE,
                                block_k=TILE))


def test_the_census_counts_the_operations_at_both_head_sizes():
    plain = flash_tile_census(8192, 8192)
    sized = flash_tile_census(8192, 8192, head_dim=192, value_head_dim=128)
    assert {k: sized[k] for k in plain} == plain     # nothing else moved
    pairs = 8192 * 8193 // 2
    assert sized["forward_flop_visible"] == 2 * pairs * (192 + 128)
    np.testing.assert_allclose(
        sized["forward_flop_visible"] / sized["forward_flop_run"],
        sized["visible_pair_share"])
    same = flash_tile_census(8192, 8192, head_dim=128)
    assert same["forward_flop_visible"] == 2 * pairs * 256

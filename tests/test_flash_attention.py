"""Pallas fused attention vs. the XLA reference implementation.

Tolerances are calibrated against float64 ground truth: both the fused
kernel and the unfused XLA path sit ~1e-4 from f64 at T=512/f32 (inherent
f32 online-softmax noise), so pairwise agreement is asserted at 3e-4.
Off-TPU the kernel runs in Pallas interpret mode — the same code path the
TPU compiles.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops.flash_attention import (flash_attention,
                                               flash_tile_census)
from chainermn_tpu.parallel.sequence import attention, ulysses_attention

# the package exports the function under the module's name
_flash_module = sys.modules["chainermn_tpu.ops.flash_attention"]

B, T, H, D = 2, 512, 4, 64


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D), jnp.float32) * 0.3
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_matches_xla_attention(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal)
    want = attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_single_tile_short_sequence():
    rng = np.random.RandomState(1)
    mk = lambda: jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32) * 0.3
    q, k, v = mk(), mk(), mk()
    got = flash_attention(q, k, v, True)
    want = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_gradients_match_unfused(seed=2):
    q, k, v = _qkv(seed)

    def loss_fused(a, b, c):
        return (flash_attention(a, b, c, True) ** 2).sum()

    def loss_ref(a, b, c):
        return (attention(a, b, c, causal=True) ** 2).sum()

    got = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"grad wrt {name}")


def _masked_reference(q, k, v, allow, causal=False):
    """Dense-mask oracle: softmax attention with an explicit [B,T,T] mask."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bthd,bshd->bhts", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        t = q.shape[1]
        allow = allow & (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])[
            None]
    s = jnp.where(allow[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows -> zero output
    return jnp.einsum("bhts,bshd->bthd", p, v).astype(q.dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_segment_mask_matches_dense_oracle(causal):
    q, k, v = _qkv(5)
    rng = np.random.RandomState(6)
    seg = jnp.asarray(rng.randint(0, 3, size=(B, T)), jnp.int32)
    got = flash_attention(q, k, v, causal,
                          q_segment_ids=seg, kv_segment_ids=seg)
    allow = seg[:, :, None] == seg[:, None, :]
    want = _masked_reference(q, k, v, allow, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_fully_masked_rows_zero_output_and_grads():
    q, k, v = _qkv(7)
    # q rows with segment id 9 match nothing on the kv side
    qseg = jnp.zeros((B, T), jnp.int32).at[:, :64].set(9)
    kseg = jnp.zeros((B, T), jnp.int32)

    def loss(a, b, c):
        return (flash_attention(a, b, c, False, q_segment_ids=qseg,
                                kv_segment_ids=kseg) ** 2).sum()

    out = flash_attention(q, k, v, False, q_segment_ids=qseg,
                          kv_segment_ids=kseg)
    assert np.allclose(np.asarray(out[:, :64]), 0.0)
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))
    # masked q rows contribute no gradient to q
    assert np.allclose(np.asarray(grads[0][:, :64]), 0.0)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_seg", [False, True])
def test_pallas_bwd_matches_blockwise_oracle(causal, with_seg):
    """The fused backward kernels against the pure-XLA blockwise path."""
    q, k, v = _qkv(8)
    kw = {}
    if with_seg:
        rng = np.random.RandomState(9)
        seg = jnp.asarray(rng.randint(0, 2, size=(B, T)), jnp.int32)
        kw = dict(q_segment_ids=seg, kv_segment_ids=seg)

    def loss(impl):
        def f(a, b, c):
            return (flash_attention(a, b, c, causal, bwd_impl=impl,
                                    **kw) ** 2).sum()
        return f

    got = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss("blockwise"), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"grad wrt {name}")


def test_dropout_deterministic_and_scaled():
    q, k, v = _qkv(10)
    a1 = flash_attention(q, k, v, False, dropout_rate=0.3, dropout_seed=42)
    a2 = flash_attention(q, k, v, False, dropout_rate=0.3, dropout_seed=42)
    b1 = flash_attention(q, k, v, False, dropout_rate=0.3, dropout_seed=43)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    assert not np.allclose(np.asarray(a1), np.asarray(b1))
    # inverted scaling keeps the output mean roughly unchanged
    base = flash_attention(q, k, v, False)
    assert abs(float(jnp.mean(a1)) - float(jnp.mean(base))) < 5e-3


def test_dropout_grads_match_blockwise_oracle():
    q, k, v = _qkv(11)

    def loss(impl):
        def f(a, b, c):
            return (flash_attention(a, b, c, True, dropout_rate=0.25,
                                    dropout_seed=7, bwd_impl=impl) ** 2).sum()
        return f

    got = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss("blockwise"), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"grad wrt {name}")


def test_causal_offsets_match_unfused():
    """q_offset/kv_offset reproduce attention()'s global-position causal
    mask for blocks of a longer sequence."""
    rng = np.random.RandomState(12)
    mk = lambda t: jnp.asarray(rng.randn(1, t, 2, 32), jnp.float32) * 0.3
    q, k, v = mk(128), mk(128), mk(128)
    # q block sits at global rows 256.., kv block at 128..
    got = flash_attention(q, k, v, True, q_offset=256, kv_offset=128)
    want = attention(q, k, v, causal=True, q_offset=256, k_offset=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)
    # kv strictly in the future -> fully masked -> zero output
    got = flash_attention(q, k, v, True, q_offset=0, kv_offset=512)
    np.testing.assert_allclose(np.asarray(got), 0.0)


def test_per_sequence_offset_vectors_match_per_row():
    """q_offset/kv_offset accept [B] vectors (the serving decode path:
    each sequence sits at its own KV-cache length) — every batch row
    must get its own global-position causal mask, forward and backward,
    in both backward implementations."""
    rng = np.random.RandomState(14)
    b, t, h, d = 3, 64, 2, 32
    mk = lambda: jnp.asarray(rng.randn(b, t, h, d), jnp.float32) * 0.3
    q, k, v = mk(), mk(), mk()
    qo = jnp.array([0, 5, 128], jnp.int32)
    ko = jnp.array([0, 3, 128], jnp.int32)

    got = flash_attention(q, k, v, True, q_offset=qo, kv_offset=ko)
    for i in range(b):
        want = attention(q[i:i + 1], k[i:i + 1], v[i:i + 1], causal=True,
                         q_offset=int(qo[i]), k_offset=int(ko[i]))
        np.testing.assert_allclose(np.asarray(got[i:i + 1]),
                                   np.asarray(want), rtol=3e-4,
                                   atol=3e-4, err_msg=f"row {i}")

    def loss_ref(a, bb, c):
        return sum((attention(a[i:i + 1], bb[i:i + 1], c[i:i + 1],
                              causal=True, q_offset=int(qo[i]),
                              k_offset=int(ko[i])) ** 2).sum()
                   for i in range(b))

    want_g = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for impl in ("pallas", "blockwise"):
        got_g = jax.grad(
            lambda a, bb, c: (flash_attention(
                a, bb, c, True, q_offset=qo, kv_offset=ko,
                bwd_impl=impl) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got_g, want_g, "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-3, atol=2e-3,
                                       err_msg=f"[{impl}] grad wrt {name}")


def test_offset_vector_shape_validated():
    rng = np.random.RandomState(15)
    mk = lambda: jnp.asarray(rng.randn(2, 64, 2, 32), jnp.float32)
    q, k, v = mk(), mk(), mk()
    with pytest.raises(ValueError, match="q_offset"):
        flash_attention(q, k, v, True,
                        q_offset=jnp.zeros((3,), jnp.int32))
    with pytest.raises(ValueError, match="kv_offset"):
        flash_attention(q, k, v, True,
                        kv_offset=jnp.zeros((5,), jnp.int32))


def test_return_lse_value_and_gradient():
    """The lse output equals the dense logsumexp and is differentiable —
    grads through (out, lse) match the pure-XLA computation."""
    rng = np.random.RandomState(13)
    mk = lambda: jnp.asarray(rng.randn(1, 256, 2, 32), jnp.float32) * 0.3
    q, k, v = mk(), mk(), mk()
    scale = 32 ** -0.5

    out, lse = flash_attention(q, k, v, False, return_lse=True)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    want_lse = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=1e-4, atol=1e-4)

    def loss_flash(a, b, c, impl):
        o, l = flash_attention(a, b, c, False, return_lse=True,
                               bwd_impl=impl)
        return (o ** 2).sum() + (l ** 2).sum()

    def loss_ref(a, b, c):
        ss = jnp.einsum("bthd,bshd->bhts", a, b) * scale
        p = jax.nn.softmax(ss, axis=-1)
        o = jnp.einsum("bhts,bshd->bthd", p, c)
        l = jax.scipy.special.logsumexp(ss, axis=-1)
        return (o ** 2).sum() + (l ** 2).sum()

    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for impl in ("pallas", "blockwise"):
        got = jax.grad(lambda a, b, c: loss_flash(a, b, c, impl),
                       argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-3, atol=2e-3,
                                       err_msg=f"[{impl}] grad wrt {name}")


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_with_flash_kernel(devices, causal):
    """Ring attention folding fused-kernel (out, lse) blocks equals the
    single-device reference, forward and backward."""
    from jax.sharding import Mesh, PartitionSpec as P
    from chainermn_tpu.parallel.sequence import ring_attention

    mesh = Mesh(np.array(devices[:8]), ("sp",))
    rng = np.random.RandomState(14)
    mk = lambda: jnp.asarray(rng.randn(1, 1024, 2, 32), jnp.float32) * 0.3
    q, k, v = mk(), mk(), mk()

    def ring(a, b, c):
        return jax.shard_map(
            lambda x, y, z: ring_attention(
                x, y, z, axis_name="sp", causal=causal,
                attn_fn=flash_attention),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)(a, b, c)

    got = jax.jit(ring)(q, k, v)
    want = attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)

    g_got = jax.grad(lambda a: (ring(a, k, v) ** 2).sum())(q)
    g_want = jax.grad(lambda a: (attention(a, k, v, causal=causal) ** 2
                                 ).sum())(q)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=2e-3, atol=2e-3)


def test_rejects_indivisible_sequence():
    rng = np.random.RandomState(3)
    # T <= block size runs as one tile (any T); T > block size must divide
    x = jnp.asarray(rng.randn(1, 300, 2, 32), jnp.float32)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(x, x, x, False, block_q=256, block_k=256)


def test_as_ulysses_inner_kernel(devices):
    """flash_attention plugs into the sequence-parallel path as attn_fn."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(devices[:8]), ("sp",))
    rng = np.random.RandomState(4)
    mk = lambda: jnp.asarray(rng.randn(1, 1024, 8, 32), jnp.float32) * 0.3
    q, k, v = mk(), mk(), mk()
    # check_vma=False: the Pallas interpret-mode interpreter (CPU-only
    # path) trips a dynamic_slice vma check inside shard_map; on real TPU
    # the kernel is compiled, not interpreted, and no check is skipped.
    got = jax.jit(jax.shard_map(
        lambda a, b, c: ulysses_attention(
            a, b, c, axis_name="sp", causal=True,
            attn_fn=lambda *xs, **kw: flash_attention(
                xs[0], xs[1], xs[2], kw.get("causal", False),
                kw.get("sm_scale"))),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False))(q, k, v)
    want = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_asymmetric_blocks_with_offsets():
    """block_q != block_k together with offsets: the tile-skip bounds must
    stay exact (regression for the offset-aware causal trim)."""
    rng = np.random.RandomState(15)
    mk = lambda: jnp.asarray(rng.randn(1, 512, 2, 32), jnp.float32) * 0.3
    q, k, v = mk(), mk(), mk()
    got = flash_attention(q, k, v, True, None, 128, 64,
                          q_offset=512, kv_offset=0)
    want = attention(q, k, v, causal=True, q_offset=512, k_offset=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)

    def loss(a):
        return (flash_attention(a, k, v, True, None, 64, 128,
                                q_offset=256, kv_offset=256) ** 2).sum()

    def loss_ref(a):
        return (attention(a, k, v, causal=True, q_offset=256,
                          k_offset=256) ** 2).sum()

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss)(q)), np.asarray(jax.grad(loss_ref)(q)),
        rtol=2e-3, atol=2e-3)


def test_default_blocks_auto_fit_any_old_t():
    """The 1024 default block (round 3) auto-halves until it divides T, so
    sequences the old 256 default accepted keep working without args."""
    from chainermn_tpu.ops.flash_attention import _fit_block

    assert _fit_block(1536, None, 1024) == 512
    assert _fit_block(4864, None, 1024) == 256
    assert _fit_block(300, None, 1024) == 300   # single tile
    assert _fit_block(8192, None, 1024) == 1024
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(1, 1536, 2, 16), jnp.float32) * 0.3
    out = flash_attention(x, x, x, True)
    ref = attention(x, x, x, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_cross_attention_rectangular(causal):
    """Tq != Tkv (round 3): forward and both backward implementations on
    rectangular shapes, vs the unfused oracle."""
    rng = np.random.RandomState(21)
    mk = lambda t: jnp.asarray(rng.randn(2, t, 2, 32), jnp.float32) * 0.3
    q, k, v = mk(384), mk(640), mk(640)

    got = flash_attention(q, k, v, causal, block_q=128, block_k=128)
    want = attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)

    for impl in ("pallas", "blockwise"):
        def loss(a, b_, c):
            return (flash_attention(a, b_, c, causal, block_q=128,
                                    block_k=128, bwd_impl=impl) ** 2).sum()

        def loss_ref(a, b_, c):
            return (attention(a, b_, c, causal=causal) ** 2).sum()

        got_g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want_g = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got_g, want_g, "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-3, atol=2e-3,
                                       err_msg=f"{impl} grad wrt {name}")


def test_cross_attention_shape_validation():
    rng = np.random.RandomState(22)
    q = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    # MORE kv heads than q heads is not a valid GQA grouping either
    k = jnp.asarray(rng.randn(1, 128, 4, 32), jnp.float32)
    with pytest.raises(ValueError, match="multiple of the kv"):
        flash_attention(q, k, k, False)
    d_mismatch = jnp.asarray(rng.randn(1, 128, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="batch and head size"):
        flash_attention(q, d_mismatch, d_mismatch, False)
    v = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)
    with pytest.raises(ValueError, match="k and v"):
        flash_attention(q, q, v, False)


@pytest.mark.parametrize("hk", [1, 2])
def test_grouped_query_attention(hk):
    """GQA/MQA (round 3): 4 q heads over hk kv heads, forward + both
    backward impls vs the repeated-kv oracle (jnp.repeat's transpose sums
    over the group — exactly the dk/dv group reduction)."""
    rng = np.random.RandomState(31)
    q = jnp.asarray(rng.randn(2, 256, 4, 32), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(2, 256, hk, 32), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(2, 256, hk, 32), jnp.float32) * 0.3
    grp = 4 // hk

    got = flash_attention(q, k, v, True, block_q=128, block_k=128)
    want = attention(q, jnp.repeat(k, grp, 2), jnp.repeat(v, grp, 2),
                     causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)

    for impl in ("pallas", "blockwise"):
        def loss(a, b_, c):
            return (flash_attention(a, b_, c, True, block_q=128,
                                    block_k=128, bwd_impl=impl) ** 2).sum()

        def loss_ref(a, b_, c):
            return (attention(a, jnp.repeat(b_, grp, 2),
                              jnp.repeat(c, grp, 2), causal=True) ** 2).sum()

        got_g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want_g = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got_g, want_g, "qkv"):
            assert g.shape == w.shape
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-3, atol=2e-3,
                                       err_msg=f"{impl} grad wrt {name}")


def test_gqa_head_count_validation():
    rng = np.random.RandomState(32)
    q = jnp.asarray(rng.randn(1, 128, 4, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 3, 32), jnp.float32)
    with pytest.raises(ValueError, match="multiple of the kv"):
        flash_attention(q, k, k, False)


def test_gqa_with_all_optional_features():
    """GQA combined with dropout + segment ids + rectangular Tq/Tkv +
    return_lse: pins the kv_row index maps against the optional-input
    BlockSpec threading in every kernel (pallas vs blockwise parity)."""
    rng = np.random.RandomState(33)
    q = jnp.asarray(rng.randn(2, 128, 4, 32), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(2, 256, 2, 32), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(2, 256, 2, 32), jnp.float32) * 0.3
    qseg = jnp.asarray(rng.randint(0, 2, size=(2, 128)), jnp.int32)
    kseg = jnp.asarray(rng.randint(0, 2, size=(2, 256)), jnp.int32)

    def loss(impl):
        def f(a, b_, c):
            out, lse = flash_attention(
                a, b_, c, True, block_q=128, block_k=128,
                q_segment_ids=qseg, kv_segment_ids=kseg,
                dropout_rate=0.2, dropout_seed=11, q_offset=128,
                return_lse=True, bwd_impl=impl)
            lse_f = jnp.where(jnp.abs(lse) > 1e29, 0.0, lse)  # sentinel rows
            return (out ** 2).sum() + 0.1 * (lse_f ** 2).sum()
        return f

    got = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss("blockwise"), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"grad wrt {name}")


# ---------------------------------------------------------------------------
# the sliding window
# ---------------------------------------------------------------------------

def _window_case(heads, kv_heads, seed=41, t=256, d=32):
    rng = np.random.RandomState(seed)
    mk = lambda n: jnp.asarray(rng.randn(1, t, n, d), jnp.float32) * 0.3
    return mk(heads), mk(kv_heads), mk(kv_heads)


def _dense_window(q, k, v, window, seg=None):
    """Softmax over a dense [T, T] mask: 0 <= i - j < window, same segment."""
    t, grp = q.shape[1], q.shape[2] // k.shape[2]
    gap = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    allow = jnp.broadcast_to((gap >= 0) & (gap < window), (q.shape[0], t, t))
    if seg is not None:
        allow = allow & (seg[:, :, None] == seg[:, None, :])
    return _masked_reference(q, jnp.repeat(k, grp, 2), jnp.repeat(v, grp, 2),
                             allow)


# window: under the tile, no multiple of it, several tiles wide; tiles of
# 64 over T=256 leave whole tiles outside the band on both sides
@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (8, 2)])
@pytest.mark.parametrize("window", [40, 100, 150])
@pytest.mark.parametrize("with_seg", [False, True])
def test_window_matches_dense_masked_softmax(window, heads, kv_heads,
                                             with_seg):
    q, k, v = _window_case(heads, kv_heads)
    seg = (jnp.asarray(np.random.RandomState(42).randint(0, 2, (1, 256)),
                       jnp.int32) if with_seg else None)
    kw = ({} if seg is None
          else dict(q_segment_ids=seg, kv_segment_ids=seg))

    def fused(a, b, c):
        return flash_attention(a, b, c, True, window=window, block_q=64,
                               block_k=64, **kw)

    want_fn = lambda a, b, c: _dense_window(a, b, c, window, seg)
    np.testing.assert_allclose(fused(q, k, v), want_fn(q, k, v),
                               rtol=3e-4, atol=3e-4)
    cot = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    got = jax.grad(lambda *a: (fused(*a) * cot).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (want_fn(*a) * cot).sum(), (0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3,
                                   err_msg=f"grad wrt {name}")


@pytest.mark.parametrize("window", [256, 1000])
def test_a_window_as_long_as_the_sequence_is_causal(window):
    q, k, v = _window_case(4, 2)
    loss = lambda w: lambda *a: (flash_attention(
        *a, True, window=w, block_q=64, block_k=64) ** 2).sum()
    np.testing.assert_array_equal(
        flash_attention(q, k, v, True, window=window, block_q=64,
                        block_k=64),
        flash_attention(q, k, v, True, block_q=64, block_k=64))
    for g, w in zip(jax.grad(loss(window), (0, 1, 2))(q, k, v),
                    jax.grad(loss(None), (0, 1, 2))(q, k, v)):
        np.testing.assert_array_equal(g, w)


def test_window_with_offsets_rectangular_tiles_and_the_blockwise_oracle():
    """Offsets (no index clamp: the map cannot see them), tiles of two
    sizes, and the XLA paths: the blockwise backward and ``attention``."""
    q, k, v = _window_case(4, 2, seed=43)
    want = attention(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2),
                     causal=True, window=70)
    np.testing.assert_allclose(want, _dense_window(q, k, v, 70),
                               rtol=1e-5, atol=1e-5)
    for kw in (dict(block_q=128, block_k=32), dict(block_q=32, block_k=128),
               dict(block_q=64, block_k=64, q_offset=512, kv_offset=512)):
        np.testing.assert_allclose(
            flash_attention(q, k, v, True, window=70, **kw), want,
            rtol=3e-4, atol=3e-4, err_msg=str(kw))
        loss = lambda impl: lambda *a: (flash_attention(
            *a, True, window=70, bwd_impl=impl, **kw) ** 2).sum()
        for g, w in zip(jax.grad(loss("pallas"), (0, 1, 2))(q, k, v),
                        jax.grad(loss("blockwise"), (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3,
                                       err_msg=str(kw))


def test_a_window_is_refused_without_causal_or_under_one():
    q, k, v = _window_case(2, 2, t=64)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, window=8)
    with pytest.raises(ValueError, match="at least 1"):
        flash_attention(q, k, v, True, window=0)
    with pytest.raises(ValueError, match="causal"):
        attention(q, k, v, window=8)


def _pallas_calls(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


# ---------------------------------------------------------------------------
# the tile classes: interior tiles run no mask, edge tiles their visible
# sub-tiles.  Tiles of 32 with a sub-tile a quarter of the tile.
# ---------------------------------------------------------------------------

_TILE, _QUARTER = 32, 8


@pytest.fixture
def quarter_sub(monkeypatch):
    monkeypatch.setattr(_flash_module, "_SUB", _QUARTER)


def _classified_case(window, heads, kv_heads, d, tiles, seed):
    """Forward against the dense masked softmax, gradients against the
    blockwise oracle, on a call the kernels classify."""
    t = _TILE * tiles
    census = flash_tile_census(t, t, _TILE, _TILE, window, _QUARTER)
    assert census["classified"] and census["sub"] == _QUARTER
    assert census["subtiles_run"] < census["subtiles_total"]
    q, k, v = _window_case(heads, kv_heads, seed=seed, t=t, d=d)
    fused = lambda impl: lambda a, b, c: flash_attention(
        a, b, c, True, window=window, block_q=_TILE, block_k=_TILE,
        bwd_impl=impl)
    np.testing.assert_allclose(
        fused("pallas")(q, k, v), _dense_window(q, k, v, window or t),
        rtol=3e-4, atol=3e-4)
    cot = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    grads = lambda impl: jax.grad(
        lambda *a: (fused(impl)(*a) * cot).sum(), (0, 1, 2))(q, k, v)
    for g, w, name in zip(grads("pallas"), grads("blockwise"), "qkv"):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3,
                                   err_msg=f"grad wrt {name}")


# full causal; a window of four tiles, of a tile, of sub-tiles alone (80 =
# 10 x 8), of neither (100), and under a tile (24: the diagonal tile holds
# both edges); MHA, GQA 4:1, MQA
@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (8, 2), (4, 1)])
@pytest.mark.parametrize("window", [None, 128, 32, 80, 100, 24])
def test_classified_tiles_match_the_oracles(quarter_sub, window, heads,
                                            kv_heads):
    _classified_case(window, heads, kv_heads, d=64, tiles=8, seed=51)


# one, two and eight tiles a side at both head sizes (one tile: the window
# is as long as the sequence and the call is plain causal)
@pytest.mark.parametrize("tiles", [1, 2, 8])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("window", [None, 40])
def test_classified_tiles_by_head_size_and_tiles_a_side(quarter_sub, window,
                                                        d, tiles):
    _classified_case(window, 4, 1, d=d, tiles=tiles, seed=52)


def test_a_window_of_exactly_one_tile_as_the_mellum_cell_has_it(monkeypatch):
    """Mellum's window layers: ``window`` = the tile, sub-tiles of half a
    tile, 8 query heads a kv head of 128.  The band of a q tile is the
    diagonal tile and one window-edge tile: EVERY working tile is an edge
    tile and none is mask-free.  Forward against the dense masked softmax,
    the three gradients against its derivative (not the blockwise
    oracle)."""
    monkeypatch.setattr(_flash_module, "_SUB", _TILE // 2)
    t = 4 * _TILE
    census = flash_tile_census(t, t, _TILE, _TILE, _TILE, _TILE // 2)
    assert census["classified"] and census["interior"] == 0
    assert census["visited"] == census["edge"] == 2 * 4 - 1
    assert census["subtiles_run"] == 3 * census["edge"]     # three of four
    q, k, v = _window_case(8, 1, seed=61, t=t, d=128)
    fused = lambda a, b, c: flash_attention(
        a, b, c, True, window=_TILE, block_q=_TILE, block_k=_TILE)
    dense = lambda a, b, c: _dense_window(a, b, c, _TILE)
    np.testing.assert_allclose(fused(q, k, v), dense(q, k, v), rtol=3e-4,
                               atol=3e-4)
    cot = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    got = jax.grad(lambda *a: (fused(*a) * cot).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (dense(*a) * cot).sum(), (0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3,
                                   err_msg=f"grad wrt {name}")


def _brute_census(t, tile, window, sub):
    gap = np.arange(t)[:, None] - np.arange(t)[None, :]
    seen = (gap >= 0) & (gap < (window or t))
    blocks = lambda size: seen.reshape(t // size, size, t // size,
                                       size).transpose(0, 2, 1, 3)
    tiles = blocks(tile)
    visited = tiles.any((2, 3))
    interior = tiles.all((2, 3))
    subs = blocks(sub)
    per_side = tile // sub
    in_visited = np.kron(visited, np.ones((per_side, per_side), bool))
    run = subs.any((2, 3)) & in_visited
    return {"classified": True, "sub": sub, "visited": int(visited.sum()),
            "interior": int(interior.sum()),
            "edge": int((visited & ~interior).sum()),
            "outside": int((~visited).sum()),
            "subtiles_run": int(run.sum()),
            "subtiles_total": int(visited.sum()) * per_side ** 2,
            "visible_pair_share": seen.sum() / (run.sum() * sub * sub)}


@pytest.mark.parametrize("t,tile,window,sub", [
    (256, 32, None, 8), (256, 32, 128, 8), (256, 32, 80, 8),
    (256, 32, 100, 8), (256, 32, 24, 8), (64, 32, None, 16),
    (512, 64, 200, 16), (512, 64, 64, 64), (32, 32, 7, 8)])
def test_the_census_counts_what_a_brute_force_count_finds(t, tile, window,
                                                          sub):
    got = flash_tile_census(t, t, tile, tile, window, sub)
    want = _brute_census(t, tile, window, sub)
    assert got.pop("visible_pair_share") == pytest.approx(
        want.pop("visible_pair_share"), rel=1e-12)
    assert got == want


# the benchmark's cells at the kernels' own tiles (1024) and sub-tile (512)
@pytest.mark.parametrize("t,window,visited,interior,edge,tile_equivalents", [
    (8192, None, 36, 28, 8, 34.0),       # the StarCoder, lfm2, nope layers
    (2048, None, 3, 1, 2, 2.5),          # starcoder1b-t2048
    (8192, 2048, 21, 7, 14, 17.5),       # trinity's window layers
    (8192, 1024, 15, 0, 15, 11.25)])     # mellum's: every tile an edge tile
def test_the_census_at_the_cells_shapes(t, window, visited, interior, edge,
                                        tile_equivalents):
    census = flash_tile_census(t, t, window=window)
    assert census["classified"] and census["sub"] == 512
    assert (census["visited"], census["interior"], census["edge"]) == (
        visited, interior, edge)
    assert census["subtiles_run"] / 4 == tile_equivalents
    assert census["subtiles_total"] == 4 * visited
    # a call the kernels cannot classify does every visited tile's work
    generic = flash_tile_census(t, t, window=window, segment_ids=True)
    assert not generic["classified"]
    assert generic["subtiles_run"] == generic["subtiles_total"] == 4 * visited
    assert generic["visible_pair_share"] < census["visible_pair_share"] < 1


def test_three_kernels_under_their_names_and_who_walks_sub_tiles(
        quarter_sub):
    """Three ``pallas_call``s under the same three names on the same grid
    with and without a window (``window=None`` and a window as long as the
    sequence trace what a call without the argument traces, equation for
    equation); a shorter window walks a band.  A classified call's kernels
    walk sub-tiles on their edge tiles; a call with offsets, segment ids or
    dropout takes the generic masked body on every tile."""
    q, k, v = _window_case(4, 2)
    traced = lambda **kw: jax.make_jaxpr(jax.grad(
        lambda *a: (flash_attention(*a, True, block_q=64, block_k=64,
                                    **kw) ** 2).sum(), (0, 1, 2)))(q, k, v)
    plain, none, windowed = traced(), traced(window=None), traced(window=100)
    assert str(plain) == str(none) == str(traced(window=256)) != str(windowed)
    names = lambda jaxpr: [eqn.params["jaxpr"].debug_info.func_name
                           for eqn in _pallas_calls(jaxpr.jaxpr)]
    grids = lambda jaxpr: [tuple(eqn.params["grid_mapping"].grid)
                           for eqn in _pallas_calls(jaxpr.jaxpr)]
    index_maps = lambda jaxpr: "".join(
        str(block.index_map_jaxpr)
        for eqn in _pallas_calls(jaxpr.jaxpr)
        for block in eqn.params["grid_mapping"].block_mappings)
    # the whole grid, where a classified call's index maps hold the
    # diagonal's tile through the steps past it (k tiles: min; q tiles: max);
    # under the window the streamed dimension is the band's three tiles of
    # the four, found by the index maps
    assert grids(plain) == 3 * [(4, 4, 4)]
    assert "min" in index_maps(plain) and "max" in index_maps(plain)
    assert grids(windowed) == 3 * [(4, 4, 3)] and "min" in index_maps(windowed)
    # a sub-tile walk tests row - column inside a sub-tile of 8 x 8; the
    # generic body tests global positions over the whole tile of 64 x 64
    walks = lambda jaxpr: ["i32[8,8]" in str(eqn.params["jaxpr"])
                           for eqn in _pallas_calls(jaxpr.jaxpr)]
    whole = lambda jaxpr: ["i32[64,64]" in str(eqn.params["jaxpr"])
                           for eqn in _pallas_calls(jaxpr.jaxpr)]
    for classified in (plain, windowed):
        assert names(classified) == ["_fwd_kernel", "_dkv_kernel",
                                     "_dq_kernel"]
        assert walks(classified) == 3 * [True]
        assert whole(classified) == 3 * [False]
    seg = jnp.zeros((1, 256), jnp.int32)
    for kw, census_kw in (
            (dict(q_offset=0, kv_offset=0), dict(offsets=True)),
            (dict(q_segment_ids=seg, kv_segment_ids=seg),
             dict(segment_ids=True)),
            (dict(dropout_rate=0.1, dropout_seed=3),
             dict(dropout_rate=0.1))):
        for window in (None, 100):
            generic = traced(window=window, **kw)
            assert names(generic) == ["_fwd_kernel", "_dkv_kernel",
                                      "_dq_kernel"], kw
            assert walks(generic) == 3 * [False], kw
            assert whole(generic) == 3 * [True], kw
            assert grids(generic) == (
                grids(plain) if window is None or "q_offset" in kw
                else grids(windowed)), kw
            if window is None:      # every step fetches its own tile
                assert "min" not in index_maps(generic), kw
                assert "max" not in index_maps(generic), kw
            census = flash_tile_census(256, 256, 64, 64, window, _QUARTER,
                                       **census_kw)
            assert not census["classified"], kw
            assert census["subtiles_run"] == census["subtiles_total"]
    assert flash_tile_census(256, 256, 64, 64, 100, _QUARTER)["classified"]

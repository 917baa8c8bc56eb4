"""Flash-attention kernel tests (interpret mode on CPU).

Parity bar: forward and all three gradients match the XLA reference
attention to float32 tolerance, causal and non-causal, across block
tilings — including tilings smaller than the sequence (the streaming path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipe_tpu.ops.pallas_attention import flash_attention, supports
from pipe_tpu.ops.ring_attention import blockwise_attention_reference


def qkv(key, b=2, s=64, h=2, d=16, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(kk, (b, s, h, d), dtype) for kk in ks)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(64, 64), (32, 32), (16, 32), (32, 16)])
def test_forward_parity(causal, blocks):
    q, k, v = qkv(jax.random.key(0))
    bq, bk = blocks
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    exp = blockwise_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_gradient_parity(causal):
    q, k, v = qkv(jax.random.key(1), s=32)

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        return jnp.sum(o ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(blockwise_attention_reference(
            q, k, v, causal=causal) ** 2)

    g1 = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-5)


def test_jit_value_and_grad():
    q, k, v = qkv(jax.random.key(2), s=32)

    @jax.jit
    def step(q, k, v):
        return jax.value_and_grad(
            lambda q: jnp.sum(flash_attention(q, k, v, block_q=16,
                                              block_k=16)))(q)

    val, g = step(q, k, v)
    assert np.isfinite(float(val)) and g.shape == q.shape


def test_bf16_forward():
    q, k, v = qkv(jax.random.key(3), dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True)
    exp = blockwise_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=5e-2, atol=5e-2)
    assert got.dtype == jnp.bfloat16


def test_supports_gate():
    assert supports(128)
    assert supports(96, block=32)
    assert not supports(100)   # not divisible by min tile
    assert not supports(4)     # below min tile
    q, k, v = qkv(jax.random.key(4), s=24)
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k, v, block_q=16, block_k=16)


def test_matches_layers_attention():
    """Same semantics as the MHA building block's attention (no dropout)."""
    from pipe_tpu.ops.layers import dot_product_attention
    q, k, v = qkv(jax.random.key(5), s=32)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    exp = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=2e-5, atol=2e-6)


def test_dropout_requires_tpu_in_interpret_mode():
    q, k, v = qkv(jax.random.key(6), s=32)
    with pytest.raises(NotImplementedError, match="TPU PRNG"):
        flash_attention(q, k, v, dropout_rate=0.2,
                        dropout_key=jax.random.key(0), interpret=True)
    with pytest.raises(ValueError, match="requires dropout_key"):
        flash_attention(q, k, v, dropout_rate=0.2, interpret=False)


def test_mha_flash_by_name_runs_or_raises():
    """impl="flash" never takes the XLA path quietly: with dropout on the
    CPU (interpret mode has no PRNG) or a sequence the tiling cannot cover
    it raises, while impl="auto" may still choose XLA."""
    from pipe_tpu.core.partition import StageCtx
    from pipe_tpu.ops.layers import MultiHeadAttention
    x = jax.random.normal(jax.random.key(0), (2, 32, 64))
    ctx = StageCtx(key=jax.random.key(2), train=True)
    flash = MultiHeadAttention(64, 4, dropout=0.5, impl="flash")
    p = flash.init(jax.random.key(1), x)
    with pytest.raises(ValueError, match="needs the TPU PRNG"):
        flash.apply(p, x, ctx=ctx)
    with pytest.raises(ValueError, match="cannot tile seq_len 20"):
        flash.apply(p, x[:, :20], ctx=StageCtx(train=False))
    # no dropout active: the kernel runs (interpreted here)
    assert np.isfinite(np.asarray(
        flash.apply(p, x, ctx=StageCtx(train=False)))).all()
    auto = MultiHeadAttention(64, 4, dropout=0.5, impl="auto")
    assert np.isfinite(np.asarray(auto.apply(p, x, ctx=ctx))).all()


@pytest.mark.parametrize("causal", [False, True])
def test_dropout_vjp_algebra_with_stub_mask(monkeypatch, causal):
    """Full dropout fwd+bwd algebra on CPU via a deterministic mask stub.

    Replaces the TPU PRNG mask with a pure jnp function of
    (seed, bh, iq, ik), reconstructs the identical full-matrix mask for an
    XLA oracle `(softmax(s) [causal-masked]) * mask @ v`, and checks forward
    and all three gradients — covering the seeding consistency of the three
    kernels and the pre-dropout-normalizer gradient algebra that only ever
    runs compiled on TPU.
    """
    import math as _math

    from pipe_tpu.ops import pallas_attention as pa

    rate = 0.3

    def fake_mask(seed, bh, iq, ik, shape, r):
        a = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        b = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        z = (a * 7 + b * 13 + bh * 31 + iq * 17 + ik * 11 + seed) % 10
        keep = z >= jnp.int32(r * 10)
        return jnp.where(keep, 1.0 / (1.0 - r), 0.0).astype(jnp.float32)

    monkeypatch.setattr(pa, "_drop_mask", fake_mask)
    pa._make.cache_clear()

    b, s, h, d = 1, 32, 2, 8
    bq = bk = 16
    key = jax.random.key(0)
    q, k, v = qkv(key, b=b, s=s, h=h, d=d)

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    scale = 1.0 / _math.sqrt(d)
    attend = pa._make(causal, scale, bq, bk, True, rate)
    seed0 = jnp.zeros((1,), jnp.int32)

    # oracle: assemble the identical full mask per (bh, q-block, k-block)
    mask_full = np.zeros((b * h, s, s), np.float32)
    for bh_i in range(b * h):
        for iq in range(s // bq):
            for ik in range(s // bk):
                blk = fake_mask(0, bh_i, iq, ik, (bq, bk), rate)
                mask_full[bh_i, iq * bq:(iq + 1) * bq,
                          ik * bk:(ik + 1) * bk] = np.asarray(blk)
    mask_full = jnp.asarray(mask_full)

    def oracle(q3, k3, v3):
        sc = jnp.einsum("zqd,zkd->zqk", q3, k3) * scale
        if causal:
            cm = jnp.tril(jnp.ones((s, s), bool))
            sc = jnp.where(cm, sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("zqk,zkd->zqd", w * mask_full, v3)

    q3, k3, v3 = to3(q), to3(k), to3(v)
    got = attend(q3, k3, v3, seed0)
    exp = oracle(q3, k3, v3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=2e-5, atol=2e-6)

    g_got = jax.grad(lambda q3, k3, v3: jnp.sum(
        attend(q3, k3, v3, seed0) ** 2), argnums=(0, 1, 2))(q3, k3, v3)
    g_exp = jax.grad(lambda q3, k3, v3: jnp.sum(
        oracle(q3, k3, v3) ** 2), argnums=(0, 1, 2))(q3, k3, v3)
    for a, e in zip(g_got, g_exp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=5e-4, atol=1e-5)
    pa._make.cache_clear()


def test_dropout_rate_validation():
    q, k, v = qkv(jax.random.key(8), s=16)
    for bad in (1.0, 1.5, -0.1):
        with pytest.raises(ValueError, match="dropout_rate"):
            flash_attention(q, k, v, dropout_rate=bad,
                            dropout_key=jax.random.key(0))

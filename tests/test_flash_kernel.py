"""Pallas flash-attention kernel vs the jnp oracle (shapes x GQA x causal)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_bshd
from repro.models.layers import flash_attention as flash_jnp


def _naive(q, k, v, causal):
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * (d ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((s, k.shape[1]), bool))
        scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, -1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return out.reshape(b, s, hq, d)


@pytest.mark.parametrize("shape", [
    # (B, S, T, Hq, Hkv, D)
    (2, 64, 64, 4, 4, 32),       # MHA
    (2, 64, 64, 8, 2, 32),       # GQA 4:1
    (1, 128, 128, 4, 1, 64),     # MQA
    (2, 96, 96, 2, 2, 16),       # non-pow2 seq
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_matches_naive(shape, causal):
    b, s, t, hq, hkv, d = shape
    ks = jax.random.split(jax.random.PRNGKey(sum(shape)), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
    got = flash_attention_bshd(q, k, v, causal=causal, block_q=32,
                               block_k=32)
    want = _naive(q, k, v, causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_kernel_matches_jnp_flash():
    """Kernel vs the framework's chunked-jnp path (used under pjit)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 128, 8, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
    got = flash_attention_bshd(q, k, v, causal=True, block_q=32, block_k=64)
    want = flash_jnp(q, k, v, causal=True, q_chunk=32, kv_chunk=32)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_kernel_bf16():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 64, 4, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 64, 4, 32), jnp.bfloat16)
    got = flash_attention_bshd(q, k, v, causal=True, block_q=32, block_k=32)
    want = _naive(q.astype(jnp.float32), k.astype(jnp.float32),
                  v.astype(jnp.float32), True)
    np.testing.assert_allclose(got.astype(np.float32), want, rtol=2e-2,
                               atol=2e-2)

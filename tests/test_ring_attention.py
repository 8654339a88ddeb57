"""Context parallelism: ring attention and Ulysses vs dense reference,
on the 8-virtual-device CPU mesh (conftest)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import (make_mesh, ring_attention_sharded,
                                 ulysses_attention_sharded)


def dense_attention(q, k, v, causal=False):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    rng = np.random.RandomState(0)
    B, S, H, D = 2, 64, 4, 8
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    mesh = make_mesh({"sp": 8})
    out = ring_attention_sharded(q, k, v, mesh, seq_axis="sp",
                                 causal=causal)
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    rng = np.random.RandomState(1)
    B, S, H, D = 2, 64, 8, 4
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    mesh = make_mesh({"sp": 8})
    out = ulysses_attention_sharded(q, k, v, mesh, seq_axis="sp",
                                    causal=causal)
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("heads", [16, 24])
def test_ulysses_more_heads_than_devices(heads):
    """H > sp degree: head2seq's received device axis is head-group-major;
    regression test for the head-permutation bug (round-1 advisor)."""
    rng = np.random.RandomState(3)
    B, S, D = 2, 64, 4
    q = jnp.asarray(rng.randn(B, S, heads, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, heads, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, heads, D), jnp.float32)
    mesh = make_mesh({"sp": 8})
    out = ulysses_attention_sharded(q, k, v, mesh, seq_axis="sp",
                                    causal=True)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_grad_flows():
    rng = np.random.RandomState(2)
    B, S, H, D = 1, 32, 2, 4
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    mesh = make_mesh({"sp": 8})

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, "sp",
                                              causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_ring_attention_bf16():
    """bf16 q/k/v through the flash ring path: carry dtype stays stable and
    the result matches the f32 dense reference at bf16 tolerance."""
    mesh = make_mesh({"sp": 8})
    rng = np.random.RandomState(5)
    B, S, H, D = 2, 64, 4, 8
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
    out = ring_attention_sharded(q, k, v, mesh, seq_axis="sp", causal=True)
    assert out.dtype == jnp.bfloat16
    want = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                  v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=0.1, atol=0.05)

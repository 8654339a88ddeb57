"""The flash kernels with grouped k/v heads and a sliding window against the
dense composition (interpret mode on the CPU): groups {1, 4, 8} x every kind
of window x every pair of block widths, and groups of 7 on rows past 8,192
positions. A file of its own so that it runs beside
``test_flash_attention.py`` (which holds the tile-by-tile checks of the same
segments), not after it."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import tune
from paddle_tpu.kernels.flash_attention import flash_attention_with_lse

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

GW_S = 512
WINDOWS = [None, 100, 128, 300, GW_S + 5]   # none, < block, = block, several
#                                             blocks' worth, > S


def _dense_grouped(q, k, v, window):
    """o [B, S, H, D], lse [B, H, S] of the dense composition: a group's
    k/v head repeated, the band a mask."""
    B, S, H, D = q.shape
    group = H // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    s = jnp.where(seen[None, None], s, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v),
            jax.nn.logsumexp(s, axis=-1))


@pytest.mark.parametrize("bk", [128, 256, 512])
@pytest.mark.parametrize("bq", [128, 256, 512])
@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("group", [1, 4, 8])
def test_grouped_heads_and_windows_match_dense(group, window, bq, bk):
    """o, lse, dq, dk, dv (a cotangent on lse too) against the dense
    composition: q heads in groups on one k/v head each, under every kind
    of window, at every pair of block widths; k and v enter and their
    gradients leave with the k/v heads' own shape."""
    rng = np.random.RandomState(group * 1000 + (window or 0) + bq + 2 * bk)
    S, Hkv, D = GW_S, 1, 16
    H = Hkv * group
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    q, k, v = mk(1, S, H, D), mk(1, S, Hkv, D), mk(1, S, Hkv, D)
    co, cl = mk(1, S, H, D), mk(1, H, S)
    cfg = {"block_q": bq, "block_k": bk}

    def both(f):
        def loss(q, k, v):
            o, lse = f(q, k, v)
            return jnp.sum(o * co) + jnp.sum(lse * cl), (o, lse)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    tune.reset_counters()
    (_, got), g1 = both(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=True, config=cfg, window=window))(q, k, v)
    (_, want), g2 = both(lambda q, k, v: _dense_grouped(
        q, k, v, window))(q, k, v)
    for name, a, b in zip(("o", "lse"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)
    assert g1[1].shape == k.shape and g1[2].shape == v.shape
    for name, a, b in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4, err_msg="d" + name)
    tag = "%dx%d" % (bq, bk) + (" g%d" % group if group > 1 else "") + (
        " w%d" % window if window else "")
    counted = tune.counters()
    assert set(counted["flash_blocks"]) == {
        "%s %s" % (kernel, tag) for kernel in fa.KERNELS}
    assert set(counted["flash_tiles"]) == set(counted["flash_blocks"])


def _dense_in_blocks(q, k, v, window, rows=512):
    """``_dense_grouped`` of one batch row, ``rows`` query rows at a time
    (each block rematerialised): the [S, S] scores of 16,384 positions are
    never alive at once."""
    _B, S, H, D = q.shape
    group = H // k.shape[2]
    k, v = (jnp.repeat(a[0], group, axis=1).transpose(1, 0, 2)
            for a in (k, v))                                # [H, S, D]

    def block(args):
        qb, start = args                                    # [rows, H, D]
        s = jnp.einsum("qhd,hkd->hqk", qb, k) * D ** -0.5
        i, j = start + jnp.arange(rows)[:, None], jnp.arange(S)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        s = jnp.where(seen[None], s, -jnp.inf)
        return (jnp.einsum("hqk,hkd->qhd", jax.nn.softmax(s, axis=-1), v),
                jax.nn.logsumexp(s, axis=-1))

    o, lse = jax.lax.map(jax.checkpoint(block),
                         (q[0].reshape(S // rows, rows, H, D),
                          jnp.arange(S // rows) * rows))
    return (o.reshape(1, S, H, D),
            lse.transpose(1, 0, 2).reshape(1, H, S))


@pytest.mark.parametrize("S,window,bq,bk", [
    (16384, 4096, 512, 512), (16384, None, 512, 512),
    (9216, 4096, 256, 512), (9216, 4096, 512, 256)])
def test_groups_of_7_past_8192_positions_match_dense(S, window, bq, bk):
    """The published heads' group (28 q heads on 4 k/v heads: 7 q heads on
    one here, 16 wide) on rows past the 8,192 positions the kernels took
    before their scoped VMEM could be raised, under the 4,096-key window
    and under none: o, lse, dq, dk, dv against the dense composition."""
    rng = np.random.RandomState(S + (window or 0) + bq + 2 * bk)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    q, k, v = mk(1, S, 7, 16), mk(1, S, 1, 16), mk(1, S, 1, 16)
    co, cl = mk(1, S, 7, 16), mk(1, 7, S)

    def both(f):
        def loss(q, k, v):
            o, lse = f(q, k, v)
            return jnp.sum(o * co) + jnp.sum(lse * cl), (o, lse)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    cfg = {"block_q": bq, "block_k": bk}
    (_, got), g1 = both(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=True, config=cfg, window=window))(q, k, v)
    (_, want), g2 = both(lambda q, k, v: _dense_in_blocks(
        q, k, v, window))(q, k, v)
    for name, a, b in zip(("o", "lse"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)
    for name, a, b in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4, err_msg="d" + name)


@pytest.mark.parametrize("S,bq,bk", [(1024, 512, 512), (2048, 512, 256),
                                     (2048, 256, 512)])
def test_groups_of_16_without_positions_match_dense(S, bq, bk):
    """The hybrid cell's group (32 q heads on 2 k/v heads: 16 q heads on
    one here, 16 wide), causal, no window, nothing turned by positions
    before the kernels: o, lse, dq, dk, dv against the dense
    composition."""
    rng = np.random.RandomState(S + bq + 2 * bk)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    q, k, v = mk(1, S, 16, 16), mk(1, S, 1, 16), mk(1, S, 1, 16)
    co, cl = mk(1, S, 16, 16), mk(1, 16, S)

    def both(f):
        def loss(q, k, v):
            o, lse = f(q, k, v)
            return jnp.sum(o * co) + jnp.sum(lse * cl), (o, lse)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    cfg = {"block_q": bq, "block_k": bk}
    tune.reset_counters()
    (_, got), g1 = both(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=True, config=cfg))(q, k, v)
    (_, want), g2 = both(lambda q, k, v: _dense_grouped(
        q, k, v, None))(q, k, v)
    for name, a, b in zip(("o", "lse"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)
    for name, a, b in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4, err_msg="d" + name)
    assert set(tune.counters()["flash_blocks"]) == {
        "%s %dx%d g16" % (kernel, bq, bk) for kernel in fa.KERNELS}

"""``kernels/ssd_scan.py``: the chunked state-space scan against the token
recurrence it computes (CPU, float32): the output and the gradients of x,
B, C, dt, A_log, dt_bias and D, at chunks of the whole row, 8 and 4
positions, with one B/C group and with two groups of 4 heads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import tune
from paddle_tpu.kernels import ssd_scan

S, H, P, N = 32, 8, 4, 6


def recurrence(x, dt, b, c, dt_bias, a_log, d):
    """y of s[t] = exp(delta[t] a) s[t-1] + delta[t] x[t] B[t]^T, y[t] =
    s[t] C[t] + d x[t], one position at a time; x [B, S, H, P]."""
    H_, G = x.shape[2], b.shape[2]
    delta = jax.nn.softplus(dt + dt_bias)
    a = -jnp.exp(a_log)
    rep = lambda v: jnp.repeat(v, H_ // G, axis=1)        # [B, H, N]

    def step(s, t):
        xt, dtt, bt, ct = t
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * rep(bt)[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, rep(ct))

    s0 = jnp.zeros(x.shape[:1] + (H_, x.shape[3], b.shape[3]))
    _s, y = jax.lax.scan(step, s0, tuple(jnp.moveaxis(v, 1, 0)
                                         for v in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def operands(groups, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda *shape, s=1.0: jnp.asarray(s * rng.randn(*shape), jnp.float32)
    return (mk(2, S, H, P), mk(2, S, H), mk(2, S, groups, N, s=0.5),
            mk(2, S, groups, N, s=0.5), mk(H, s=0.5),
            jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), mk(H))


NAMES = ("x", "dt", "b", "c", "dt_bias", "a_log", "d")


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunk", [S, 8, 4], ids=["row", "8", "4"])
def test_the_chunked_scan_is_the_token_recurrence(chunk, groups):
    args = operands(groups, seed=chunk + groups)
    cot = jnp.asarray(np.random.RandomState(7).randn(2, S, H, P), jnp.float32)

    def loss(f):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a) * cot), argnums=tuple(range(7))))
    tune.reset_counters()
    got, g_got = loss(lambda *a: ssd_scan.mixer_scan(*a, chunk=chunk))(*args)
    want, g_want = loss(recurrence)(*args)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    y = ssd_scan.mixer_scan(*args, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(recurrence(*args)),
                               rtol=1e-4, atol=1e-5)
    for name, a, b in zip(NAMES, g_got, g_want):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=name)
    assert tune.counters()["ssm_scans"] == {
        "Q%d x%d h%d g%d n%d p%d xla" % (chunk, S // chunk, H, groups, N, P):
        2}


def test_the_state_crosses_every_chunk_boundary():
    """A step whose decay is 1 (a = 0 by a huge negative a_log) keeps what
    the first position wrote: the last position reads it four chunks on."""
    x, dt, b, c, dt_bias, a_log, d = operands(1, seed=3)
    a_log = jnp.full((H,), -60.0)
    x = x.at[:, 1:].set(0.0)
    y = ssd_scan.mixer_scan(x, dt, b, c, dt_bias, a_log, d * 0, chunk=8)
    want = recurrence(x, dt, b, c, dt_bias, a_log, d * 0)
    assert float(jnp.max(jnp.abs(want[:, -1]))) > 1e-3
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-6)


def test_a_chunk_that_does_not_divide_the_row_raises():
    with pytest.raises(ValueError, match="chunk"):
        ssd_scan.mixer_scan(*operands(1), chunk=12)

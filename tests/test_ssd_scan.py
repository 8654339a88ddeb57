"""``kernels/ssd_scan.py``: the chunked state-space scan's Pallas kernels (in
interpret mode on the CPU) against the token recurrence they compute: the
output and the gradients of x, B, C, dt, A_log, dt_bias and D, at chunks of
the whole row, 8 and 4 positions, with one B/C group and with two groups of
4 heads (float32); heads of other widths than the state; bf16 operands;
the primal launch against the forward rule's."""
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


def operands(groups, seed=0, seq=S, head_dim=P, state=N):
    rng = np.random.RandomState(seed)
    mk = lambda *shape, s=1.0: jnp.asarray(s * rng.randn(*shape), jnp.float32)
    return (mk(2, seq, H, head_dim), mk(2, seq, H),
            mk(2, seq, groups, state, s=0.5),
            mk(2, seq, groups, state, s=0.5), mk(H, s=0.5),
            jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), mk(H))


NAMES = ("x", "dt", "b", "c", "dt_bias", "a_log", "d")


def loss_and_grads(f, args):
    """jit(value_and_grad) of sum(f(*args) * a fixed cotangent)."""
    cot = jnp.asarray(np.random.RandomState(7).randn(*args[0].shape),
                      jnp.float32)
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(f(*a) * cot), argnums=tuple(range(7))))(*args)


def assert_is_the_recurrence(chunk, args):
    got, g_got = loss_and_grads(
        lambda *a: ssd_scan.mixer_scan(*a, chunk=chunk), args)
    want, g_want = loss_and_grads(recurrence, args)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    y = ssd_scan.mixer_scan(*args, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(recurrence(*args)),
                               rtol=1e-4, atol=1e-5)
    for name, a, b in zip(NAMES, g_got, g_want):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=name)


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunk", [S, 8, 4], ids=["row", "8", "4"])
def test_the_chunked_scan_is_the_token_recurrence(chunk, groups):
    tune.reset_counters()
    assert_is_the_recurrence(chunk, operands(groups, seed=chunk + groups))
    assert tune.counters()["ssm_scans"] == {
        "Q%d x%d h%d g%d n%d p%d pallas" % (chunk, S // chunk, H, groups, N,
                                            P): 2}


@pytest.mark.parametrize("head_dim", [48, 160])
def test_heads_and_groups_are_not_mixed_up(head_dim):
    """Two groups of 4 heads, heads of 48 or 160 against a state of 6:
    a head's rows read from another's, its state written to another's, or
    B / C read from the wrong group gives another y and gradients."""
    assert_is_the_recurrence(8, operands(2, seed=head_dim, seq=16,
                                         head_dim=head_dim))


def test_bf16_operands_stay_within_two_percent_of_the_f32_recurrence():
    """x, B and C in bf16 and the products' operands bf16 (as under AMP):
    y and every gradient within 2% of its largest magnitude of the f32
    recurrence on the same values (0.1-0.8% read over four seeds)."""
    bf16 = jnp.bfloat16
    args = operands(2, seed=11)

    def low(x, dt, b, c, *rest):
        return ssd_scan.mixer_scan(x.astype(bf16), dt, b.astype(bf16),
                                   c.astype(bf16), *rest, chunk=8,
                                   operand=bf16)
    pairs = [(low(*args), recurrence(*args))]
    pairs += zip(loss_and_grads(low, args)[1],
                 loss_and_grads(recurrence, args)[1])
    for name, (a, b) in zip(("y",) + NAMES, pairs):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
        assert err <= 0.02 * float(jnp.max(jnp.abs(b))), name


def _pallas_outputs(fn, *args):
    """The number of outputs of every pallas_call in fn's jaxpr."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(len(eqn.outvars))
            for v in eqn.params.values():
                for j in (v if isinstance(v, (list, tuple)) else [v]):
                    if isinstance(j, ClosedJaxpr):
                        walk(j.jaxpr)
                    elif isinstance(j, Jaxpr):
                        walk(j)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return sorted(found)


def test_the_primal_launch_writes_y_alone_and_gives_the_vjp_launch_s_y():
    x, dt, b, c, dt_bias, a_log, _d = operands(2, seed=4)
    delta = jax.nn.softplus(dt + dt_bias)
    a = -jnp.exp(a_log)
    scan = lambda *v: ssd_scan.chunked_scan(*v, 8, jnp.float32)
    args = (x, delta, a, b, c)
    y = jax.jit(scan)(*args)
    y_vjp, back = jax.vjp(scan, *args)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_vjp))
    assert _pallas_outputs(scan, *args) == [1]
    # the forward rule's launch keeps the entering states; the backward's
    # writes dx, dB, dC, d delta and d la o delta
    assert _pallas_outputs(lambda *v: jax.vjp(scan, *v)[1](y), *args) \
        == [2, 5]


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

"""The state-space scan of a Mamba-2 mixer (the SSD form: a scalar decay a
head), computed chunk by chunk, forward and backward.

For one row, head h reading B/C group g(h), the recurrence over positions t
is

    s[t] = exp(delta[t] a) s[t-1] + delta[t] x[t] B[t]^T     ([P, N], s[-1] = 0)
    y[t] = s[t] C[t]

with x [P] the head's input, B, C [N] the group's, delta > 0 the step and
a < 0 the head's decay. ``chunked_scan`` gives the same y from chunks of
``chunk`` positions in three steps:

1. inside each chunk, in parallel over chunks: the masked product
   ``(L o C B^T) (delta x)`` with ``L[i, j] = exp(cum[i] - cum[j])`` for j <= i
   (``cum`` the chunk's cumulative sum of delta a), and the chunk's own end
   state ``sum_j exp(cum[-1] - cum[j]) delta[j] x[j] B[j]^T``;
2. a pass over the chunks' end states, the only sequential step: the state
   entering chunk c is ``S[c-1] = exp(cum_{c-1}[-1]) S[c-2] + end state of
   chunk c-1``;
3. in parallel again: ``exp(cum[i]) C[i] S[c-1]`` added to each position.

The backward (one ``custom_vjp``) is the same three steps reversed: step 3's
gradient gives the states' gradient, a reverse pass over the chunks carries
it back (``dS[c] = dS_in[c+1] + exp(cum_{c+1}[-1]) dS[c+1]``), and step 1's
gradient is taken chunk by chunk from the inputs again. Its residuals are
the inputs and the f32 states entering the chunks, ``[B, chunks, H, P, N]``:
nothing of size chunk x chunk is kept between the passes. B and C are never
repeated per head: a group's heads are one axis of the products.

Precision: the products take ``operand`` (bf16 under AMP) and accumulate in
f32; the cumulative decays, ``exp`` and the state carried between chunks
are f32.

``mixer_scan`` is what a mixer calls: the step ``delta = softplus(dt +
dt_bias)``, the decay ``a = -exp(a_log)``, the chunked scan and the skip
``D x``, counted in ``tune.counters()["ssm_scans"]``. No reference
equivalent: the 2018 codebase has no state-space layers. The scan is jnp
(XLA fuses it); no Pallas kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

IMPLEMENTATION = "xla"


def _split(x, delta, b, c, chunk):
    """x [B, S, H, P], delta [B, S, H], b / c [B, S, G, N] -> chunked
    [B, n, Q, G, R, P], [B, n, Q, G, R], [B, n, Q, G, N] (R heads a
    group)."""
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    n = S // chunk
    return (x.reshape(B, n, chunk, G, H // G, P),
            delta.reshape(B, n, chunk, G, H // G),
            b.reshape(B, n, chunk, G, N), c.reshape(B, n, chunk, G, N))


def _cumulative(delta, a):
    """(cum [B, n, G, R, Q], total [B, n, G, R]): the chunk's cumulative
    log decay and its sum, f32."""
    G, R = delta.shape[3:]
    la = delta * a.reshape(G, R)
    cum = jnp.cumsum(jnp.moveaxis(la, 2, -1), axis=-1)
    return cum, cum[..., -1]


def _inside(x, delta, a, b, c, operand):
    """Step 1, every chunk at once: (y inside the chunk [B, n, Q, G, R, P],
    the chunk's end state [B, n, G, R, P, N], total, cum), f32."""
    cum, total = _cumulative(delta, a)
    Q = cum.shape[-1]
    cb = jnp.einsum("bcign,bcjgn->bcgij", c.astype(operand),
                    b.astype(operand), preferred_element_type=jnp.float32)
    diff = cum[..., :, None] - cum[..., None, :]
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(seen, diff, -jnp.inf))       # [B,n,G,R,Q,Q]
    dt_j = jnp.moveaxis(delta, 2, -1)[..., None, :]
    scores = (cb[:, :, :, None] * decay * dt_j).astype(operand)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", scores, x.astype(operand),
                   preferred_element_type=jnp.float32)
    to_end = jnp.exp(total[..., None] - cum) * jnp.moveaxis(delta, 2, -1)
    xw = x.astype(jnp.float32) * jnp.moveaxis(to_end, -1, 2)[..., None]
    end = jnp.einsum("bcjgrp,bcjgn->bcgrpn", xw.astype(operand),
                     b.astype(operand), preferred_element_type=jnp.float32)
    return y, end, total, cum


def _carried(c, cum, prev, operand):
    """Step 3: exp(cum[i]) C[i] S[c-1] a position, [B, n, Q, G, R, P]."""
    y = jnp.einsum("bcign,bcgrpn->bcigrp", c.astype(operand),
                   prev.astype(operand), preferred_element_type=jnp.float32)
    return y * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]


def _states(end, total):
    """Step 2: the f32 state entering each chunk, [B, n, G, R, P, N]."""
    def step(s, et):
        e, t = et
        return jnp.exp(t)[..., None, None] * s + e, s
    _last, prev = jax.lax.scan(step, jnp.zeros_like(end[:, 0]),
                               (jnp.moveaxis(end, 1, 0),
                                jnp.moveaxis(total, 1, 0)))
    return jnp.moveaxis(prev, 0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def chunked_scan(x, delta, a, b, c, chunk, operand=jnp.float32):
    """y [B, S, H, P] f32 of the recurrence in the module docstring: x [B,
    S, H, P], delta [B, S, H] f32 (> 0), a [H] f32 (< 0), b / c [B, S, G,
    N] (head h reads group h // (H / G)); ``chunk`` divides S."""
    return _scan_fwd(x, delta, a, b, c, chunk, operand)[0]


def _scan_fwd(x, delta, a, b, c, chunk, operand):
    shape = x.shape
    xs, ds, bs, cs = _split(x, delta, b, c, chunk)
    y, end, total, cum = _inside(xs, ds, a, bs, cs, operand)
    prev = _states(end, total)
    y = y + _carried(cs, cum, prev, operand)
    return y.reshape(shape), (x, delta, a, b, c, prev)


def _scan_bwd(chunk, operand, res, dy):
    x, delta, a, b, c, prev = res
    xs, ds, bs, cs = _split(x, delta, b, c, chunk)
    dy = dy.reshape(xs.shape).astype(jnp.float32)
    cum, total = _cumulative(ds, a)
    # step 3 reversed: the gradients of C, of cum and of the entering states
    _y, carried_vjp = jax.vjp(
        lambda c_, cum_, prev_: _carried(c_, cum_, prev_, operand),
        cs, cum, prev)
    dc3, dcum3, dprev = carried_vjp(dy)

    # step 2 reversed: dS[c] = dprev[c+1] + exp(total[c+1]) dS[c+1], the
    # last chunk's end state read by nothing
    def back(g, nxt):
        dp, t = nxt
        g = dp + jnp.exp(t)[..., None, None] * g
        return g, g
    shift = lambda v: jnp.concatenate(
        [v[:, 1:], jnp.zeros_like(v[:, :1])], axis=1)
    _first, dend = jax.lax.scan(
        back, jnp.zeros_like(prev[:, 0]),
        (jnp.moveaxis(shift(dprev), 1, 0), jnp.moveaxis(shift(total), 1, 0)),
        reverse=True)
    dend = jnp.moveaxis(dend, 0, 1)
    # d exp(total[c]) = sum(dS[c] * S[c-1]): the decay's share
    dtotal = jnp.sum(dend * prev, axis=(-2, -1)) * jnp.exp(total)

    # step 1 reversed, every chunk at once, from the inputs again
    _out, inside_vjp = jax.vjp(
        lambda x_, d_, a_, b_, c_: _inside(x_, d_, a_, b_, c_, operand),
        xs, ds, a, bs, cs)
    dx, dd, da, db, dc = inside_vjp((dy, dend, dtotal, dcum3))
    dc = dc + dc3
    return (dx.reshape(x.shape).astype(x.dtype),
            dd.reshape(delta.shape).astype(delta.dtype), da.astype(a.dtype),
            db.reshape(b.shape).astype(b.dtype),
            dc.reshape(c.shape).astype(c.dtype))


chunked_scan.defvjp(_scan_fwd, _scan_bwd)


def mixer_scan(x, dt, b, c, dt_bias, a_log, d, chunk, operand=jnp.float32):
    """The scan part of a Mamba-2 mixer: x [B, S, H, P], dt [B, S, H] (the
    in-projection's step, before its bias), b / c [B, S, G, N], dt_bias,
    a_log, d [H] -> y [B, S, H, P] f32 = scan(x, softplus(dt + dt_bias),
    -exp(a_log), b, c) + d x. ``chunk`` is cut to the row where the row
    is shorter; it has to divide the row otherwise."""
    from .. import tune
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError("a chunk of %d positions does not divide a row of "
                         "%d" % (chunk, S))
    if H % G:
        raise ValueError("%d heads cannot share %d groups" % (H, G))
    tune.count_ssm_scan(chunk, S // chunk, H, G, N, P, IMPLEMENTATION)
    f32 = jnp.float32
    delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    a = -jnp.exp(a_log.astype(f32))
    y = chunked_scan(x, delta, a, b, c, chunk, operand)
    return y + d.astype(f32)[:, None] * x.astype(f32)

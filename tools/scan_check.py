"""Kernel-only timing and a check of the mixers' scan kernels (needs the chip).

    python3 tools/scan_check.py [--seed 1] [--grad_seq 1024]

At the hybrid cell's widths (one row of 8,192 positions in chunks of 128,
64 heads of 64 over 8 B/C groups of a 128-wide state; x, B and C bf16 and
bf16 operands, as under AMP): the device time of each launch the scan
makes in a training step (``kernels/ssd_scan.py``):

- ``primal``: the forward kernel writing y alone (a forward op's launch);
- ``forward_rule``: the forward kernel writing y and the entering states
  (the launch of the ``custom_vjp`` forward rule, in the recomputed
  forward of the backward pass);
- ``backward``: the backward kernel from those states.

Then the largest error of y against the token recurrence (f32, highest
precision) over the whole row, and of the gradients of x, B, C, the step
and the decay over the first ``--grad_seq`` positions (autodiff through
the recurrence keeps every position's state), each over that quantity's
largest magnitude. One JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if not any(os.path.isdir(os.path.join(p, "paddle_tpu")) for p in sys.path
           if p):
    sys.path.insert(0, ROOT)

REPEATS = 10
SEQ, CHUNK, HEADS, HEAD_DIM, GROUPS, STATE = 8192, 128, 64, 64, 8, 128


def _time(jax, fn, args):
    """ms a call: the best of three batches of REPEATS back-to-back calls."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / REPEATS * 1e3)
    return best


def operands(jax, jnp, seed, seq):
    """x, delta, a, b, c of one row: the step softplus(dt + dt_bias) with
    dt_bias at Mamba-2's initial 0.001-0.1, a = -(1..16)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    bf16, f32 = jnp.bfloat16, jnp.float32
    x = jax.random.normal(keys[0], (1, seq, HEADS, HEAD_DIM), f32)
    b, c = (0.3 * jax.random.normal(k, (1, seq, GROUPS, STATE), f32)
            for k in keys[1:3])
    dt0 = jnp.exp(jax.random.uniform(keys[3], (HEADS,), f32, jnp.log(1e-3),
                                     jnp.log(1e-1)))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    dt = 0.5 * jax.random.normal(keys[4], (1, seq, HEADS), f32)
    delta = jax.nn.softplus(dt + dt_bias)
    a = -jnp.linspace(1.0, 16.0, HEADS, dtype=f32)
    return x.astype(bf16), delta, a, b.astype(bf16), c.astype(bf16)


def recurrence(jax, jnp, x, delta, a, b, c):
    """y of s[t] = exp(delta[t] a) s[t-1] + delta[t] x[t] B[t]^T, y[t] =
    s[t] C[t], one position at a time, f32."""
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    rep = lambda v: jnp.repeat(v.astype(f32), HEADS // GROUPS, axis=1)

    def step(s, t):
        xt, dtt, bt, ct = t
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + (dtt[..., None] * xt.astype(f32))[..., None]
             * rep(bt)[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, rep(ct), precision=hi)

    s0 = jnp.zeros((x.shape[0], HEADS, HEAD_DIM, STATE), f32)
    _s, y = jax.lax.scan(step, s0, tuple(jnp.moveaxis(v, 1, 0)
                                         for v in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--grad_seq", type=int, default=1024)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import ssd_scan as ss
    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing measured", file=sys.stderr)
        return 2
    bf16 = jnp.bfloat16
    ops = operands(jax, jnp, args.seed, SEQ)
    primal = jax.jit(lambda *v: ss.chunked_scan(*v, CHUNK, bf16))
    rule = jax.jit(lambda *v: ss._scan_fwd(*v, CHUNK, bf16))
    back = jax.jit(lambda res, dy: ss._scan_bwd(CHUNK, bf16, res, dy))
    y, res = rule(*ops)
    out = {"device": jax.devices()[0].device_kind,
           "shape": "S%d Q%d h%d p%d g%d n%d" % (SEQ, CHUNK, HEADS, HEAD_DIM,
                                                 GROUPS, STATE),
           "ms": {"primal": _time(jax, primal, ops),
                  "forward_rule": _time(jax, rule, ops),
                  "backward": _time(jax, back, (res, y))}}
    rel = lambda got, want: float(
        jnp.max(jnp.abs(got.astype(jnp.float32) - want))
        / jnp.max(jnp.abs(want)))
    want = jax.jit(lambda *v: recurrence(jax, jnp, *v))(*ops)
    errors = {"y": rel(primal(*ops), want)}
    short = tuple(v[:, :args.grad_seq] if v.ndim > 1 else v for v in ops)
    cot = jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                            short[0].shape, jnp.float32)
    grads = lambda f: jax.jit(jax.grad(lambda *v: jnp.sum(f(*v) * cot),
                                       argnums=(0, 1, 2, 3, 4)))(*short)
    got = grads(lambda *v: ss.chunked_scan(*v, CHUNK, bf16))
    ref = grads(lambda *v: recurrence(jax, jnp, *v))
    for name, g, r in zip(("x", "delta", "a", "b", "c"), got, ref):
        errors["d" + name] = rel(g, r)
    out["errors"] = errors
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

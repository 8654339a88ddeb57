"""The state-space scan of a Mamba-2 mixer (the SSD form: a scalar decay a
head), computed chunk by chunk in Pallas TPU kernels, forward and backward.

For one row, head h reading B/C group g(h), the recurrence over positions t
is

    s[t] = exp(delta[t] a) s[t-1] + delta[t] x[t] B[t]^T     ([P, N], s[-1] = 0)
    y[t] = s[t] C[t]

with x [P] the head's input, B, C [N] the group's, delta > 0 the step and
a < 0 the head's decay. ``chunked_scan`` gives the same y from chunks of
``chunk`` positions in three steps:

1. inside each chunk: the masked product ``(L o C B^T) (delta x)`` with
   ``L[i, j] = exp(cum[i] - cum[j])`` for j <= i (``cum`` the chunk's
   cumulative sum of delta a), and the chunk's own end state ``sum_j
   exp(cum[-1] - cum[j]) delta[j] x[j] B[j]^T``;
2. the pass over the chunks: the state entering chunk c is ``S[c-1] =
   exp(cum_{c-1}[-1]) S[c-2] + end state of chunk c-1``;
3. ``exp(cum[i]) C[i] S[c-1]`` added to each position.

Two kernels run them (``KERNELS``), on a grid of (row, B/C group, chunk):
row and group are parallel, the chunk axis runs in order and carries the
state of the group's R = H / G heads, ``[R x P, N]`` f32, in a VMEM
scratch. The kernels read and write positions last (``[B, H x P, S]``,
``[B, G x N, S]``): the layout XLA keeps a mixer's activations in, so that
the views cost no copy. A step reads one chunk of x for the group's heads
as one ``[R x P, Q]`` block, the chunk's B and C ``[N, Q]`` and its steps
``[R, Q]``, computes ``C B^T`` once for the group, and per head (a slice
of P rows) the cumulative log decay (a masked lane sum, f32), the decay
block L and the scores ``C B^T o L o delta_j``, which never leave VMEM.
Every other per-position quantity is a row of the chunk's positions.

- ``fwd``: y ``[B, S, H, P]`` f32 and, for the launch of the ``custom_vjp``
  forward rule, the f32 state entering each chunk, ``[B, chunks, H x P,
  N]``: the backward's residual. The primal launch writes y alone (a
  Pallas output is never dropped as dead code, so a recomputed forward
  that keeps nothing must not make it).
- ``bwd``: the same grid with the chunk axis reversed and the state's
  gradient carried in VMEM: step 3's gradient gives the entering state's,
  the reverse pass carries it back (``dS[c-1] = exp(cum_c[-1]) dS[c] +
  ...``), and step 1's gradient is taken per chunk from the inputs again.
  It writes dx, dB and dC (the group's heads summed in the kernel), the
  step's gradient and ``d(la) o delta`` (la = delta a; summed outside for
  a's gradient). Nothing of size chunk x chunk leaves VMEM.

Precision: the products take ``operand`` (bf16 under AMP) and accumulate
in f32; the cumulative decays, ``exp`` and the state (and its gradient)
carried between chunks are f32, cast to ``operand`` where they enter a
product. Blocks follow from the call's shape (the chunk and a group's
heads); a launch asks Mosaic for more scoped VMEM only where
``vmem_bytes`` exceeds its default (``vmem_limit``). Each launch of one
shape is traced once (``_launch``). Kernels on the TPU, interpret mode
elsewhere.

``mixer_scan`` is what a mixer calls: the step ``delta = softplus(dt +
dt_bias)``, the decay ``a = -exp(a_log)``, the chunked scan and the skip
``D x``, counted in ``tune.counters()["ssm_scans"]``. No reference
equivalent: the 2018 codebase has no state-space layers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..place import on_tpu
from .grouped_matmul import (VMEM_LIMIT, VMEM_MOST, _NN, _NT, _TN,
                             _block_bytes)

IMPLEMENTATION = "pallas"
KERNELS = ("fwd", "bwd")

_F32 = jnp.float32


def vmem_bytes(kernel, chunk, heads, head_dim, state, itemsize):
    """What ``kernel`` keeps in VMEM for a group of ``heads`` heads of
    ``head_dim`` and B/C of ``state``, x / B / C of ``itemsize`` bytes:
    every block twice (the pipeline's two buffers; ``fwd`` with the kept
    states), the carried f32 state (or its gradient), and the f32
    temporaries live at once, counted in chunk x chunk blocks and a head's
    [head_dim, chunk] blocks: ``fwd`` 4 and 8, ``bwd`` 8 and 20. At the
    hybrid cell's widths Mosaic needs 1.97 MiB (``fwd``, the kept states'
    launch) and 2.96 (``bwd``) where this reckons 2.15 and 3.16; at twice
    the chunk, the heads or the head width 3.33-6.03, 3-13% under this."""
    Q, W = chunk, heads * head_dim
    blk = functools.partial(_block_bytes, itemsize=itemsize)
    f32 = functools.partial(_block_bytes, itemsize=4)
    io = blk(W, Q) + 2 * blk(state, Q) + 2 * f32(heads, Q) + f32(heads, 1)
    if kernel == "fwd":
        io += f32(W, Q) + f32(W, state)             # y, the kept state
        temps = 4 * f32(Q, Q) + 8 * f32(head_dim, Q)
    elif kernel == "bwd":
        io += (f32(W, state) + f32(W, Q)            # kept state, dy
               + blk(W, Q) + 2 * blk(state, Q)      # dx, dB, dC
               + 2 * f32(heads, Q))                 # the step's gradients
        temps = 8 * f32(Q, Q) + 20 * f32(head_dim, Q)
    else:
        raise ValueError("no scan kernel %r" % (kernel,))
    return 2 * io + f32(W, state) + temps


def vmem_limit(kernel, chunk, heads, head_dim, state, itemsize):
    """The scoped VMEM a launch asks Mosaic for: None (Mosaic's default,
    VMEM_LIMIT) where ``vmem_bytes`` fits under it, else the reckoning
    rounded up to a MiB; raises past VMEM_MOST."""
    need = vmem_bytes(kernel, chunk, heads, head_dim, state, itemsize)
    if need > VMEM_MOST:
        raise ValueError(
            "a scan of chunk %d over %d heads of %d, state %d, needs %d B of "
            "VMEM, past the %d a launch may ask for"
            % (chunk, heads, head_dim, state, need, VMEM_MOST))
    return None if need <= VMEM_LIMIT else -(-need // 2 ** 20) * 2 ** 20


def _dot(a, b, dims, operand):
    return lax.dot_general(a.astype(operand), b.astype(operand), dims,
                           preferred_element_type=_F32)


class _Masks:
    """The masks of a chunk's [Q, Q] block, j <= i (``seen``) and j == i
    (``eye``), and of its last position; a head's rows and columns of
    positions are turned into each other by a masked sum over the
    diagonal, exact in f32."""

    def __init__(self, Q):
        i = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        j = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        self.seen, self.eye = j <= i, j == i
        self.last = lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1

    def to_col(self, row):
        return _lane_sum(jnp.where(self.eye, row, 0.0))

    def to_row(self, col):
        return _sublane_sum(jnp.where(self.eye, col, 0.0))


def _lane_sum(v):
    return jnp.sum(v, axis=1, keepdims=True)


def _sublane_sum(v):
    return jnp.sum(v, axis=0, keepdims=True)


class _Head:
    """What both kernels derive from head r's steps in one chunk, f32: the
    step ``delta`` [1, Q], the cumulative log decay as a column ``cum``
    [Q, 1] (a masked lane sum of ``delta a``) and a row ``cum_row``, the
    chunk's ``total`` [1, 1] and the head's decay ``a`` [1, 1]."""

    def __init__(self, masks, dl_ref, a_ref, r):
        self.masks = masks
        self.delta = dl_ref[r:r + 1, :].astype(_F32)
        self.a = a_ref[r:r + 1, :].astype(_F32)
        self.cum = _lane_sum(jnp.where(masks.seen, self.delta * self.a, 0.0))
        self.cum_row = masks.to_row(self.cum)
        self.total = _sublane_sum(jnp.where(masks.last, self.cum, 0.0))

    def decay(self):
        """L: exp(cum[i] - cum[j]) for j <= i, else 0; [Q, Q]."""
        return jnp.exp(jnp.where(self.masks.seen, self.cum - self.cum_row,
                                 -jnp.inf))

    def to_end(self):
        """exp(total - cum[j]), a row."""
        return jnp.exp(self.total - self.cum_row)


def _fwd_kernel(x_ref, dl_ref, a_ref, b_ref, c_ref, y_ref, *rest, heads,
                head_dim, operand, keep):
    """One chunk of a group, in order: x^T [R P, Q], B^T / C^T [N, Q] ->
    y^T [R P, Q]; ``st_ref`` [R P, N] carries the heads' state."""
    from jax.experimental import pallas as pl
    prev_ref, st_ref = (rest[0], rest[1]) if keep else (None, rest[0])

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        st_ref[...] = jnp.zeros(st_ref.shape, _F32)

    if keep:
        prev_ref[...] = st_ref[...]
    masks = _Masks(x_ref.shape[1])
    bt = b_ref[...].astype(operand)
    ct = c_ref[...].astype(operand)
    cb = _dot(ct, bt, _TN, operand)                             # C B^T
    for r in range(heads):
        rows = slice(r * head_dim, (r + 1) * head_dim)
        e = _Head(masks, dl_ref, a_ref, r)
        xt = x_ref[rows, :].astype(operand)                     # [P, Q]
        st = st_ref[rows, :]                                    # [P, N]
        # step 1 inside the chunk, and step 3: the carried state's read
        scores = cb * e.decay() * e.delta
        y_ref[rows, :] = (_dot(xt, scores, _NT, operand)
                          + _dot(st, ct, _NN, operand) * jnp.exp(e.cum_row))
        # step 2: the state leaving the chunk
        xw = xt.astype(_F32) * (e.to_end() * e.delta)
        st_ref[rows, :] = st * jnp.exp(e.total) + _dot(xw, bt, _NT, operand)


def _bwd_kernel(x_ref, dl_ref, a_ref, b_ref, c_ref, prev_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dd_ref, dad_ref, ds_ref, *, heads,
                head_dim, operand):
    """One chunk of a group, last chunk first: ``ds_ref`` [R P, N] holds
    the gradient of the heads' state leaving this chunk and leaves with
    that of the state entering it."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        ds_ref[...] = jnp.zeros(ds_ref.shape, _F32)

    N, Q = b_ref.shape
    masks = _Masks(Q)
    bt = b_ref[...].astype(operand)
    ct = c_ref[...].astype(operand)
    cb = _dot(ct, bt, _TN, operand)
    dcb = jnp.zeros((Q, Q), _F32)
    dbt = jnp.zeros((N, Q), _F32)
    dct = jnp.zeros((N, Q), _F32)
    for r in range(heads):
        rows = slice(r * head_dim, (r + 1) * head_dim)
        e = _Head(masks, dl_ref, a_ref, r)
        xt = x_ref[rows, :]
        x32 = xt.astype(_F32)
        dy = dy_ref[rows, :].astype(_F32)                       # [P, Q]
        prev = prev_ref[rows, :]                                # [P, N]
        dst = ds_ref[rows, :]
        # step 3 reversed: y[i] += exp(cum[i]) (prev C^T)[:, i]
        dye = dy * jnp.exp(e.cum_row)
        dct = dct + _dot(prev, dye, _TN, operand)
        d_prev = _dot(dye, ct, _NT, operand)
        carried = dye * _dot(prev, ct, _NN, operand)
        # step 2 reversed: S_next = exp(total) prev + (x o w) B
        to_end = e.to_end()
        w = to_end * e.delta
        dxw = _dot(dst, bt, _NN, operand)                       # [P, Q]
        dx = dxw * w
        dbt = dbt + _dot(dst, x32 * w, _TN, operand)
        etot = jnp.exp(e.total)
        ds_ref[rows, :] = dst * etot + d_prev
        dw = _sublane_sum(dxw * x32)
        dtotal = (etot * _sublane_sum(_lane_sum(dst * prev))
                  + _lane_sum(dw * w))
        # d cum and d delta at each position, rows
        dcum = _sublane_sum(carried) - dw * w
        dd = dw * to_end
        # step 1 reversed
        decay = e.decay()
        scores = cb * decay * e.delta
        dx = dx + _dot(dy, scores, _NN, operand)
        p = _dot(dy, xt, _TN, operand) * decay                  # [Q, Q]
        dcb = dcb + p * e.delta
        n = p * cb
        dd = dd + _sublane_sum(n)
        m = n * e.delta
        # the cumulative sum reversed: d la[k] = sum over i >= k of
        # d cum[i] (the chunk's total is cum[Q - 1]); la = delta a
        dcum = (_lane_sum(m) + masks.to_col(dcum - _sublane_sum(m))
                + jnp.where(masks.last, dtotal, 0.0))
        dla = _sublane_sum(jnp.where(masks.seen, dcum, 0.0))
        dd_ref[r:r + 1, :] = dd + dla * e.a
        dad_ref[r:r + 1, :] = dla * e.delta
        dx_ref[rows, :] = dx.astype(dx_ref.dtype)
    dc_ref[...] = (dct + _dot(bt, dcb, _NT, operand)).astype(dc_ref.dtype)
    db_ref[...] = (dbt + _dot(ct, dcb, _NN, operand)).astype(db_ref.dtype)


@functools.lru_cache(maxsize=None)
def _launch(kernel, keep, B, S, H, P, G, N, chunk, dtype, bc_dtype, operand,
            interpret):
    """``kernel``'s launch at these shapes and dtypes, traced ONCE, on the
    transposed views (positions last). ``fwd``: ``fn(x^T [B, H P, S],
    delta [B, G, R, S], a [G, R, 1], b^T, c^T [B, G N, S]) -> y^T [B, H
    P, S] f32`` (and with ``keep`` the entering states [B, chunks, H P, N]
    f32); ``bwd``: ``fn(x^T, delta, a, b^T, c^T, states, dy^T [B, H P, S]
    f32) -> dx^T, db^T, dc^T, d delta [B, G, R, S] f32, d la o delta (the
    same)``."""
    import jax.extend
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    R, Q, n = H // G, chunk, S // chunk
    W = R * P
    if kernel == "bwd":
        at = lambda f: (lambda b, g, c: f(b, g, n - 1 - c))
    else:
        at = lambda f: f
    rows = pl.BlockSpec((None, W, Q), at(lambda b, g, c: (b, g, c)))
    bc = pl.BlockSpec((None, N, Q), at(lambda b, g, c: (b, g, c)))
    steps = pl.BlockSpec((None, None, R, Q), at(lambda b, g, c: (b, g, 0, c)))
    states = pl.BlockSpec((None, None, W, N),
                          at(lambda b, g, c: (b, c, g, 0)))
    in_specs = [rows, steps, pl.BlockSpec((None, R, 1), lambda b, g, c:
                                          (g, 0, 0)), bc, bc]
    arg_shapes = [((B, H * P, S), dtype), ((B, G, R, S), _F32),
                  ((G, R, 1), _F32), ((B, G * N, S), bc_dtype),
                  ((B, G * N, S), bc_dtype)]
    opts = dict(heads=R, head_dim=P, operand=operand)
    flops = 2 * B * S * (Q * N * G + 2 * Q * P * H + 2 * N * P * H)
    if kernel == "fwd":
        body = functools.partial(_fwd_kernel, keep=keep, **opts)
        out_specs = [rows] + ([states] if keep else [])
        out_shape = [((B, H * P, S), _F32)] + (
            [((B, n, H * P, N), _F32)] if keep else [])
    else:
        body = functools.partial(_bwd_kernel, **opts)
        in_specs += [states, rows]
        arg_shapes += [((B, n, H * P, N), _F32), ((B, H * P, S), _F32)]
        out_specs = [rows, bc, bc, steps, steps]
        out_shape = [((B, H * P, S), dtype), ((B, G * N, S), bc_dtype),
                     ((B, G * N, S), bc_dtype), ((B, G, R, S), _F32),
                     ((B, G, R, S), _F32)]
        flops *= 2
    nbytes = sum(math.prod(s) * jnp.dtype(d).itemsize
                 for s, d in arg_shapes + out_shape)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit(kernel, Q, R, P, N,
                                    jnp.dtype(dtype).itemsize))

    def fn(*args):
        return pl.pallas_call(
            body,
            out_shape=[jax.ShapeDtypeStruct(s, d) for s, d in out_shape],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=0, grid=(B, G, n), in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM((W, N), _F32)]),
            compiler_params=params,
            cost_estimate=pl.CostEstimate(
                flops=flops, transcendentals=B * S * H * (Q + 4),
                bytes_accessed=nbytes),
            interpret=interpret,
            name="ssd_scan_" + kernel)(*args)

    traced = jax.make_jaxpr(fn)(*[jax.ShapeDtypeStruct(s, d)
                                  for s, d in arg_shapes])
    return jax.extend.core.jaxpr_as_fun(traced)


def _positions_last(v):
    """[B, S, ...] -> [B, prod(...), S]."""
    return jnp.swapaxes(v.reshape(v.shape[0], v.shape[1], -1), 1, 2)


def _views(x, delta, a, b, c):
    """The kernels' views, positions last: x^T [B, H P, S], the steps [B,
    G, R, S], a [G, R, 1], b^T / c^T [B, G N, S]."""
    B, S, H, _P = x.shape
    G = b.shape[2]
    return (_positions_last(x),
            _positions_last(delta.astype(_F32)).reshape(B, G, H // G, S),
            a.astype(_F32).reshape(G, H // G, 1), _positions_last(b),
            _positions_last(c))


def _positions_back(vt, shape):
    """[B, C, S] -> ``shape`` [B, S, ...]."""
    return jnp.swapaxes(vt, 1, 2).reshape(shape)


def _forward(x, delta, a, b, c, chunk, operand, keep):
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    launch = _launch("fwd", keep, B, S, H, P, G, N, chunk,
                     jnp.dtype(x.dtype), jnp.dtype(b.dtype),
                     jnp.dtype(operand), not on_tpu())
    out = launch(*_views(x, delta, a, b, c))
    return _positions_back(out[0], x.shape), (out[1] if keep else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def chunked_scan(x, delta, a, b, c, chunk, operand=jnp.float32):
    """y [B, S, H, P] f32 of the recurrence in the module docstring: x [B,
    S, H, P], delta [B, S, H] f32 (> 0), a [H] f32 (< 0), b / c [B, S, G,
    N] (head h reads group h // (H / G)); ``chunk`` divides S."""
    return _forward(x, delta, a, b, c, chunk, operand, keep=False)[0]


def _scan_fwd(x, delta, a, b, c, chunk, operand):
    y, prev = _forward(x, delta, a, b, c, chunk, operand, keep=True)
    return y, (x, delta, a, b, c, prev)


def _scan_bwd(chunk, operand, res, dy):
    x, delta, a, b, c, prev = res
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    launch = _launch("bwd", True, B, S, H, P, G, N, chunk,
                     jnp.dtype(x.dtype), jnp.dtype(b.dtype),
                     jnp.dtype(operand), not on_tpu())
    dx, db, dc, dd, dad = launch(*_views(x, delta, a, b, c), prev,
                                 _positions_last(dy.astype(_F32)))
    return (_positions_back(dx, x.shape),
            _positions_back(dd.reshape(B, H, S), delta.shape).astype(
                delta.dtype),
            jnp.sum(dad, axis=(0, 3)).reshape(H).astype(a.dtype),
            _positions_back(db, b.shape), _positions_back(dc, c.shape))


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

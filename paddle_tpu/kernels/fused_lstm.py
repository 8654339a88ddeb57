"""Fused whole-sequence LSTM as a Pallas TPU kernel.

The role of the legacy fused LSTM kernels (reference:
cuda/include/hl_lstm.h:42 hl_lstm_parallel_forward — one launch computes
the whole recurrence with gate math fused) and of operators/math/
lstm_compute.*: here ONE pallas_call runs the full time loop. The grid is
(T,); TPU grids execute sequentially, so the hidden/cell state lives in
VMEM scratch across grid steps and the recurrent weight block stays
VMEM-resident for the entire sequence — the per-step HBM traffic is just
x_t in and h_t/c_t out, while the scan-based lowering reloads weights and
round-trips the carry through HBM every step.

Scope: the standard gate set (sigmoid gates, tanh cell/candidate), no
peepholes; ``ops/sequence_ops.py`` falls back to the lax.scan path
otherwise (flags.lstm_impl selects). Backward is the recompute scheme: a
plain-jax reversed scan re-derives the gates from the saved h/c sequence
(one [N,D]x[D,4D] matmul per step, the flash-attention-style
recompute-inside-backward tradeoff).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..place import on_tpu


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_lstm(xs, w, h0, c0, mask, interpret=None):
    """xs [T,N,4D] pre-projected gate inputs (bias folded in), gate slab
    order (c̃, i, f, o); w [D,4D] recurrent weights; h0/c0 [N,D]; mask
    [T,N] (1 inside the sequence). Returns (hs, cs), each [T,N,D], with
    masked steps carrying the previous state through (ragged batches)."""
    return _forward(xs, w, h0, c0, mask, interpret)[:2]


def _kernel(x_ref, w_ref, h0_ref, c0_ref, m_ref, h_out, c_out, h_scr,
            c_scr):
    from jax.experimental import pallas as pl

    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    h_prev = h_scr[...]
    c_prev = c_scr[...]
    g = x_ref[0].astype(jnp.float32) + jnp.dot(
        h_prev, w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)        # [N, 4D] on the MXU
    D = h_prev.shape[-1]
    c_t = jnp.tanh(g[:, 0 * D:1 * D])
    i = jax.nn.sigmoid(g[:, 1 * D:2 * D])
    f = jax.nn.sigmoid(g[:, 2 * D:3 * D])
    o = jax.nn.sigmoid(g[:, 3 * D:4 * D])
    c_new = f * c_prev + i * c_t
    h_new = o * jnp.tanh(c_new)
    m = m_ref[0].astype(jnp.float32)               # [N, 1]
    h = h_new * m + h_prev * (1.0 - m)
    c = c_new * m + c_prev * (1.0 - m)
    h_scr[...] = h
    c_scr[...] = c
    h_out[0] = h.astype(h_out.dtype)
    c_out[0] = c.astype(c_out.dtype)


def _forward(xs, w, h0, c0, mask, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not on_tpu()
    T, N, D4 = xs.shape
    D = D4 // 4
    hs, cs = pl.pallas_call(
        _kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, D4), lambda t: (t, 0, 0)),   # x_t
            pl.BlockSpec((D, D4), lambda t: (0, 0)),         # w (resident)
            pl.BlockSpec((N, D), lambda t: (0, 0)),          # h0
            pl.BlockSpec((N, D), lambda t: (0, 0)),          # c0
            # mask rides as [T, N, 1]: a (1, N) block over [T, N] is not a
            # legal Mosaic tile, (N, 1) equal to the array's last two dims is
            pl.BlockSpec((1, N, 1), lambda t: (t, 0, 0)),    # mask_t
        ],
        out_specs=[
            pl.BlockSpec((1, N, D), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, N, D), lambda t: (t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, N, D), xs.dtype),
            jax.ShapeDtypeStruct((T, N, D), xs.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((N, D), jnp.float32),
            pltpu.VMEM((N, D), jnp.float32),
        ],
        interpret=interpret,
    )(xs, w, h0, c0, mask[:, :, None])
    return hs, cs, (xs, w, h0, c0, mask, hs, cs)


def _fwd(xs, w, h0, c0, mask, interpret):
    hs, cs, res = _forward(xs, w, h0, c0, mask, interpret)
    return (hs, cs), res


def _bwd(interpret, res, grads):
    xs, w, h0, c0, mask, hs, cs = res
    dhs, dcs = grads
    T = xs.shape[0]
    f32 = jnp.float32
    wf = w.astype(f32)

    # previous-state sequences: h_prev[t] = hs[t-1] (h0 at t=0)
    hprev = jnp.concatenate([h0[None].astype(hs.dtype), hs[:-1]], axis=0)
    cprev = jnp.concatenate([c0[None].astype(cs.dtype), cs[:-1]], axis=0)

    def step(carry, inp):
        dh_c, dc_c, dw_c = carry
        x_t, hp, cp, dh_out, dc_out, m = inp
        m = m.astype(f32)[:, None]
        hp = hp.astype(f32)
        cp = cp.astype(f32)
        # recompute the gates (the recompute-in-backward tradeoff)
        g = x_t.astype(f32) + jnp.dot(hp, wf,
                                      preferred_element_type=f32)
        D = hp.shape[-1]
        cand = jnp.tanh(g[:, 0 * D:1 * D])
        i = jax.nn.sigmoid(g[:, 1 * D:2 * D])
        f = jax.nn.sigmoid(g[:, 2 * D:3 * D])
        o = jax.nn.sigmoid(g[:, 3 * D:4 * D])
        c_new = f * cp + i * cand
        tanh_c = jnp.tanh(c_new)

        dh_t = dh_out.astype(f32) + dh_c
        dc_t = dc_out.astype(f32) + dc_c
        dh_new = dh_t * m
        dc_new = dc_t * m + dh_new * o * (1.0 - tanh_c * tanh_c)
        do = dh_new * tanh_c
        dft = dc_new * cp * f * (1.0 - f)
        dit = dc_new * cand * i * (1.0 - i)
        dcand = dc_new * i * (1.0 - cand * cand)
        dot_ = do * o * (1.0 - o)
        dg = jnp.concatenate([dcand, dit, dft, dot_], axis=-1)
        # dw accumulates in the CARRY: stacking per-step [D,4D] grads and
        # summing after would transiently cost T*D*4D memory (~420MB at
        # T=100, D=512)
        dw_acc = dw_c + jnp.dot(hp.T, dg, preferred_element_type=f32)
        dh_prev = dh_t * (1.0 - m) + jnp.dot(
            dg, wf.T, preferred_element_type=f32)
        dc_prev = dc_new * f + dc_t * (1.0 - m)
        return (dh_prev, dc_prev, dw_acc), dg

    init = (jnp.zeros_like(h0, f32), jnp.zeros_like(c0, f32),
            jnp.zeros(w.shape, f32))
    (dh0, dc0, dw), dgs = jax.lax.scan(
        step, init, (xs, hprev, cprev, dhs, dcs, mask), reverse=True)
    return (dgs.astype(xs.dtype), dw.astype(w.dtype),
            dh0.astype(h0.dtype), dc0.astype(c0.dtype),
            jnp.zeros_like(mask))


fused_lstm.defvjp(_fwd, _bwd)

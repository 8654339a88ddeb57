"""Fused whole-sequence GRU as a Pallas TPU kernel.

Companion of kernels/fused_lstm.py (see its header for the design): one
pallas_call runs the entire recurrence — sequential (T,) grid, hidden
state in VMEM scratch, both recurrent weight blocks VMEM-resident. The
role of the reference's fused GRU compute (reference:
operators/math/gru_compute.*, cuda/include/hl_gpu_gru.cuh).

Gate math (reference gru_kernel.h): with pre-projected input g [N,3D],
``u,r = sigmoid(g[:, :2D] + h_prev @ W_ur)``,
``cand = tanh(g[:, 2D:] + (r*h_prev) @ W_c)``,
``h = (1-u)*h_prev + u*cand``. Standard activations only; masked steps
carry the previous state (ragged batches). Backward recomputes the gates
from the saved h sequence in a reversed scan, weight grads accumulated in
the carry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..place import on_tpu


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_gru(xs, w, h0, mask, interpret=None):
    """xs [T,N,3D] pre-projected (bias folded); w [D,3D] (update|reset
    recurrent block then candidate block); h0 [N,D]; mask [T,N] float.
    Returns hs [T,N,D]."""
    return _forward(xs, w, h0, mask, interpret)[0]


def _kernel(x_ref, w_ref, h0_ref, m_ref, h_out, h_scr):
    from jax.experimental import pallas as pl

    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    h_prev = h_scr[...]
    w = w_ref[...].astype(jnp.float32)
    D = h_prev.shape[-1]
    x = x_ref[0].astype(jnp.float32)
    ur = jax.nn.sigmoid(x[:, :2 * D] + jnp.dot(
        h_prev, w[:, :2 * D], preferred_element_type=jnp.float32))
    u = ur[:, :D]
    r = ur[:, D:]
    cand = jnp.tanh(x[:, 2 * D:] + jnp.dot(
        r * h_prev, w[:, 2 * D:], preferred_element_type=jnp.float32))
    h_new = (1.0 - u) * h_prev + u * cand
    m = m_ref[0].astype(jnp.float32)               # [N, 1]
    h = h_new * m + h_prev * (1.0 - m)
    h_scr[...] = h
    h_out[0] = h.astype(h_out.dtype)


def _forward(xs, w, h0, mask, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not on_tpu()
    T, N, D3 = xs.shape
    D = D3 // 3
    hs = pl.pallas_call(
        _kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, D3), lambda t: (t, 0, 0)),
            pl.BlockSpec((D, D3), lambda t: (0, 0)),
            pl.BlockSpec((N, D), lambda t: (0, 0)),
            # mask as [T, N, 1] columns (see fused_lstm._forward)
            pl.BlockSpec((1, N, 1), lambda t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, N, D), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, N, D), xs.dtype),
        scratch_shapes=[pltpu.VMEM((N, D), jnp.float32)],
        interpret=interpret,
    )(xs, w, h0, mask[:, :, None])
    return hs, (xs, w, h0, mask, hs)


def _fwd(xs, w, h0, mask, interpret):
    hs, res = _forward(xs, w, h0, mask, interpret)
    return hs, res


def _bwd(interpret, res, dhs):
    xs, w, h0, mask, hs = res
    f32 = jnp.float32
    wf = w.astype(f32)
    D = w.shape[0]
    w_ur = wf[:, :2 * D]
    w_c = wf[:, 2 * D:]
    hprev = jnp.concatenate([h0[None].astype(hs.dtype), hs[:-1]], axis=0)

    def step(carry, inp):
        dh_c, dw_c = carry
        x_t, hp, dh_out, m = inp
        m = m.astype(f32)[:, None]
        hp = hp.astype(f32)
        x_t = x_t.astype(f32)
        ur = jax.nn.sigmoid(x_t[:, :2 * D] + jnp.dot(
            hp, w_ur, preferred_element_type=f32))
        u = ur[:, :D]
        r = ur[:, D:]
        rh = r * hp
        cand = jnp.tanh(x_t[:, 2 * D:] + jnp.dot(
            rh, w_c, preferred_element_type=f32))

        dh_t = dh_out.astype(f32) + dh_c
        dh_new = dh_t * m
        du = dh_new * (cand - hp)
        dcand = dh_new * u
        dct = dcand * (1.0 - cand * cand)        # pre-activation candidate
        drh = jnp.dot(dct, w_c.T, preferred_element_type=f32)
        dr = drh * hp
        dut = du * u * (1.0 - u)
        drt = dr * r * (1.0 - r)
        durt = jnp.concatenate([dut, drt], axis=-1)
        dx = jnp.concatenate([durt, dct], axis=-1)
        dw_ur = jnp.dot(hp.T, durt, preferred_element_type=f32)
        dw_cand = jnp.dot(rh.T, dct, preferred_element_type=f32)
        dh_prev = (dh_t * (1.0 - m) + dh_new * (1.0 - u) + drh * r
                   + jnp.dot(durt, w_ur.T, preferred_element_type=f32))
        dw_acc = dw_c + jnp.concatenate([dw_ur, dw_cand], axis=-1)
        return (dh_prev, dw_acc), dx

    init = (jnp.zeros_like(h0, f32), jnp.zeros(w.shape, f32))
    (dh0, dw), dxs = jax.lax.scan(
        step, init, (xs, hprev, dhs, mask), reverse=True)
    return (dxs.astype(xs.dtype), dw.astype(w.dtype),
            dh0.astype(h0.dtype), jnp.zeros_like(mask))


fused_gru.defvjp(_fwd, _bwd)

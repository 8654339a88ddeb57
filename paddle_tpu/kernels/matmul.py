"""Blocked Pallas matmul — the fused-optimizer/FC gemm counterfactual.

reference role: operators/math/math_function.cc routing gemm to cuBLAS
(and mul_op.cc flattening to one gemm): the library picks its own tiling
per shape. XLA:TPU's dot emitter usually matches it; this kernel makes
the tiling an explicit, *searchable* parameter
so paddle_tpu.tune can time (block_m, block_n, block_k) variants per
shape and bank winners, CUDA-L2 style (PAPERS.md: searched tilings
beating cuBLAS).

Schedule: grid (M/bm, N/bn, K/bk) with k innermost — TPU grids execute
sequentially, so a VMEM f32 scratch accumulates partial products across
the k steps and writes the output tile once on the last one. Default
config is the whole-problem single tile (correct everywhere, only
sensible for small operands); real tilings come from the tuner.

Dispatch: ops/math_ops.py routes ``mul`` here ONLY when the winner cache
holds a tuned pick for the (device, shape) — stock XLA stays the default
lowering, so an untuned process is bit-identical to the pre-tune build.
Backward is stock XLA (two transposed gemms via jnp.dot): the tuner
times forward+backward through jax.grad, so a winner prices the whole
step, not just the forward tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..place import on_tpu

__all__ = ["matmul", "supports_matmul", "DEFAULT_CONFIG"]

DEFAULT_CONFIG = {"block_m": 0, "block_n": 0, "block_k": 0}


def supports_matmul(x_shape, y_shape, dtype):
    """True for the 2-D gemm population the kernel targets: MXU-friendly
    dims (lane axis multiple of 128, sublane multiple of 8) and floating
    operands. Everything else stays on stock XLA."""
    if len(x_shape) != 2 or len(y_shape) != 2:
        return False
    M, K = x_shape
    K2, N = y_shape
    if K != K2:
        return False
    if str(jnp.dtype(dtype)) not in ("float32", "bfloat16"):
        return False
    return M % 8 == 0 and K % 128 == 0 and N % 128 == 0


def normalize_config(config, M, N, K):
    """Resolve (bm, bn, bk) against the call shape; 0 = full extent.
    Non-dividing blocks fall back to the full extent (a stale cache
    entry must degrade to a correct schedule, never fail the call)."""
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(dict(config) if config else {})
    bm = int(cfg["block_m"]) or M
    bn = int(cfg["block_n"]) or N
    bk = int(cfg["block_k"]) or K
    if bm < 1 or M % bm:
        bm = M
    if bn < 1 or N % bn:
        bn = N
    if bk < 1 or K % bk:
        bk = K
    return bm, bn, bk


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "interpret", "config"))
def _matmul_fwd(x, w, out_dtype=None, interpret=None, config=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = w.shape[1]
    out_dtype = out_dtype or x.dtype
    if interpret is None:
        interpret = not on_tpu()
    bm, bn, bk = normalize_config(config, M, N, K)
    n_k = K // bk
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * M * N * K, transcendentals=0,
            bytes_accessed=x.size * x.dtype.itemsize
            + w.size * w.dtype.itemsize
            + M * N * jnp.dtype(out_dtype).itemsize),
        interpret=interpret,
    )(x, w)


def matmul(x, w, out_dtype=None, config=None):
    """x [M, K] @ w [K, N] -> [M, N], f32 accumulation in VMEM scratch.

    Differentiable (custom vjp; backward = stock transposed gemms).
    ``config`` is a paddle_tpu.tune "matmul" tiling; None runs the
    single-tile default."""
    frozen = tuple(sorted(dict(config).items())) if config else None
    return _matmul(x, w, out_dtype, frozen)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matmul(x, w, out_dtype, config):
    return _matmul_fwd(x, w, out_dtype=out_dtype, config=config)


def _vjp_fwd(x, w, out_dtype, config):
    return _matmul_fwd(x, w, out_dtype=out_dtype, config=config), (x, w)


def _vjp_bwd(out_dtype, config, res, g):
    x, w = res
    gf = g.astype(jnp.float32)
    dx = jnp.dot(gf, w.astype(jnp.float32).T,
                 preferred_element_type=jnp.float32).astype(x.dtype)
    dw = jnp.dot(x.astype(jnp.float32).T, gf,
                 preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dw


_matmul.defvjp(_vjp_fwd, _vjp_bwd)

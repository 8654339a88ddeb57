"""Pallas 3x3/stride-1 convolution — the custom-kernel counterfactual for
ResNet's mid-network convs.

reference role: paddle/fluid/operators/conv_cudnn_op.cu.cc — the
reference answers a slow generic conv with a specialised kernel path
(cuDNN per-shape algorithm search). The TPU-first analog: a fused
im2col-matmul in VMEM. The 9 taps of a 3x3 kernel are 9 MXU matmuls of
(H*W, C) @ (C, O) accumulated in f32 registers — no HBM im2col buffer,
no intermediate writes between taps (the failure mode of the lax-level
shifted-einsum impl that regressed 3x end-to-end in r4: XLA materialised
tap intermediates. Here the accumulation never leaves VMEM).

Layout: NHWC activations (C on the 128-lane axis), HWIO weights — the
MXU-native conv layout. The default tiling is one grid step per image:
the whole padded feature map sits in VMEM (ResNet-50's largest 3x3 slab
is 58x58x64xbf16 = 430 KB; the largest weight block 3*3*512*512xbf16 =
4.6 MB — both comfortably inside the ~16 MB VMEM with double
buffering). Weights use a constant index map, so the pipeline keeps
them resident across the batch grid — weight-stationary.

The tiling is no longer hard-coded: ``config`` selects images per grid
step (``block_n``), the output-channel tile (``block_o``) and the grid
order (``grid_order`` — 'no' iterates batch outer / weight-stationary,
'on' iterates output-channel outer / activation-stationary). The
search space, the VMEM-footprint validity model, and the winner cache
live in ``paddle_tpu.tune`` (space "conv3x3"); this file only executes
whatever config it is handed. Accumulation stays f32 for every config —
tile shape must never move numerics.

Backward is a jax.custom_vjp: dx reuses the same kernel with spatially
rotated, io-swapped weights (a 3x3/s1 conv again); dw is the 9-tap
correlation done as einsums (one (C, N*H*W) @ (N*H*W, O) contraction
per tap — MXU-shaped, and XLA handles the cross-batch reduction well).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..place import on_tpu

__all__ = ["conv3x3_s1_nhwc", "supports_conv3x3"]


def supports_conv3x3(w_shape, strides, paddings, dilations, groups):
    """True when (kh, kw)=(3, 3), stride 1, pad 1, no dilation/groups —
    the ResNet mid-network conv population this kernel targets."""
    return (groups == 1 and tuple(dilations) == (1, 1)
            and tuple(strides) == (1, 1) and tuple(paddings) == (1, 1)
            and tuple(w_shape[-2:]) in ((3, 3),))


def _kernel(x_ref, w_ref, o_ref, *, H, W, C, BN, BO, out_dtype):
    # x_ref: (BN, H+2, W+2, C) padded images; w_ref: (3, 3, C, BO)
    for b in range(BN):
        acc = jnp.zeros((H * W, BO), jnp.float32)
        for dy in range(3):
            for dx in range(3):
                xs = x_ref[b, dy:dy + H, dx:dx + W, :].reshape(H * W, C)
                acc += jnp.dot(xs, w_ref[dy, dx],
                               preferred_element_type=jnp.float32)
        o_ref[b] = acc.reshape(H, W, BO).astype(out_dtype)


DEFAULT_CONFIG = {"block_n": 1, "block_o": 0, "grid_order": "no"}


def normalize_config(config, N, O):
    """Resolve a (possibly partial / frozen-tuple) config against the
    call shape; block_o=0 means the full output-channel extent. Invalid
    block sizes fall back to the default rather than failing the call —
    a stale cache entry for a changed shape must not kill training."""
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(dict(config) if config else {})
    bn, bo = int(cfg["block_n"]), int(cfg["block_o"]) or O
    if bn < 1 or N % bn:
        bn = 1
    if bo < 1 or O % bo:
        bo = O
    order = cfg.get("grid_order", "no")
    return bn, bo, order if order in ("no", "on") else "no"


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "interpret", "config"))
def _conv3x3_fwd(x, w, out_dtype=None, interpret=None, config=None):
    """x: (N, H, W, C); w: (3, 3, C, O) -> (N, H, W, O)."""
    N, H, W, C = x.shape
    O = w.shape[3]
    out_dtype = out_dtype or x.dtype
    if interpret is None:
        interpret = not on_tpu()
    BN, BO, order = normalize_config(config, N, O)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    kern = functools.partial(_kernel, H=H, W=W, C=C, BN=BN, BO=BO,
                             out_dtype=out_dtype)
    flops = 2 * N * H * W * C * O * 9
    if order == "no":
        # batch outer: the weight tile's index map is constant along the
        # inner axis only when output channels iterate fastest — with
        # BO == O this is the original weight-stationary schedule
        grid = (N // BN, O // BO)
        x_map = lambda n, o: (n, 0, 0, 0)
        w_map = lambda n, o: (0, 0, 0, o)
        o_map = lambda n, o: (n, 0, 0, o)
    else:
        # output-channel outer: the activation tile stays resident while
        # one weight block streams the whole batch (activation-stationary
        # — wins when weights dwarf the feature map)
        grid = (O // BO, N // BN)
        x_map = lambda o, n: (n, 0, 0, 0)
        w_map = lambda o, n: (0, 0, 0, o)
        o_map = lambda o, n: (n, 0, 0, o)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BN, H + 2, W + 2, C), x_map),
            pl.BlockSpec((3, 3, C, BO), w_map),
        ],
        out_specs=pl.BlockSpec((BN, H, W, BO), o_map),
        out_shape=jax.ShapeDtypeStruct((N, H, W, O), out_dtype),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=0,
            bytes_accessed=x.size * x.dtype.itemsize
            + w.size * w.dtype.itemsize
            + N * H * W * O * jnp.dtype(out_dtype).itemsize),
        interpret=interpret,
    )(xp, w)


def conv3x3_s1_nhwc(x, w, out_dtype=None, config=None):
    """3x3/s1/p1 convolution, NHWC x HWIO -> NHWC, f32 accumulation.

    Differentiable (custom vjp); on the CPU backend the kernel runs
    in pallas interpret mode (place.on_tpu), so tests stay correct
    (slowly) while the TPU gets compiled Mosaic.
    ``config`` is a paddle_tpu.tune "conv3x3" tiling (dict or frozen
    item-tuple); None runs the default single-image weight-stationary
    schedule."""
    frozen = tuple(sorted(dict(config).items())) if config else None
    return _conv3x3(x, w, out_dtype, frozen)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv3x3(x, w, out_dtype, config):
    return _conv3x3_fwd(x, w, out_dtype=out_dtype, config=config)


def _vjp_fwd(x, w, out_dtype, config):
    return _conv3x3_fwd(x, w, out_dtype=out_dtype, config=config), (x, w)


def _vjp_bwd(out_dtype, config, res, g):
    x, w = res
    # dx: full-correlation of g with the rotated kernel — another
    # 3x3/s1/p1 conv, so the pallas kernel serves its own backward.
    # The forward's tiling config does not transfer (output channels
    # swap roles with input channels), so the backward runs the default
    # schedule — the tuner times forward+backward together through
    # jax.grad, so a winner already prices this.
    w_rot = jnp.transpose(w[::-1, ::-1], (0, 1, 3, 2))   # (3,3,O,C)
    dx = _conv3x3_fwd(g.astype(x.dtype), w_rot, out_dtype=None)
    # dw[dy,dx,c,o] = sum_{n,h,w} xpad[n,h+dy,w+dx,c] g[n,h,w,o]
    N, H, W, C = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    taps = []
    for dy in range(3):
        row = []
        for dxx in range(3):
            patch = xp[:, dy:dy + H, dxx:dxx + W, :]
            row.append(jnp.einsum("nhwc,nhwo->co", patch, g,
                                  preferred_element_type=jnp.float32))
        taps.append(jnp.stack(row))
    dw = jnp.stack(taps).astype(w.dtype)                 # (3,3,C,O)
    return dx.astype(x.dtype), dw


_conv3x3.defvjp(_vjp_fwd, _vjp_bwd)

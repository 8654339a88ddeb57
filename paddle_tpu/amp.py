"""Automatic mixed precision: bf16 compute on the MXU, f32 accumulation.

Role of the reference's float16 support (reference:
paddle/fluid/platform/float16.h:71 and the cudnn fp16 kernel registrations)
— on TPU the native reduced precision is bfloat16 (same exponent range as
f32, so no loss scaling needed, unlike fp16). Enabling AMP on a program
makes the matmul/conv lowerings cast operands to bf16 and accumulate in f32
(preferred_element_type), roughly doubling MXU throughput; parameters and
optimizer state stay f32.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

from . import profiler
from .core import ir
from .place import on_tpu

__all__ = ["enable", "disable", "amp_guard", "cast_inputs", "force",
           "active", "keep_bf16"]


@profiler.setup_span("build")
def enable(program=None, pure=False):
    """``pure=True`` additionally keeps matmul/conv OUTPUTS in bf16, so
    the whole activation stream (the dominant HBM traffic) is half-width
    — parameters, optimizer state, batch-norm statistics and loss math
    stay f32 (master-weights pattern). Plain AMP only narrows the
    matmul/conv operands and writes activations back at f32."""
    program = program or ir.default_main_program()
    program._amp = True
    program._amp_pure = bool(pure)
    return program


def disable(program=None):
    program = program or ir.default_main_program()
    program._amp = False
    return program


@contextlib.contextmanager
def amp_guard(program=None):
    program = program or ir.default_main_program()
    old = getattr(program, "_amp", False)
    program._amp = True
    try:
        yield
    finally:
        program._amp = old


_ON_TPU = None
_FORCE = None  # tri-state: None = auto (device probe), True/False = pinned


def force(mode):
    """Pin the cast decision: ``force(True)`` applies bf16 casts even on
    the CPU backend (numerics tests), ``force(False)`` disables them,
    ``force(None)`` restores the device probe. Returns the previous pin
    so callers can restore an outer pin instead of clobbering it."""
    global _FORCE
    prev = _FORCE
    _FORCE = mode
    return prev


def active(ctx):
    """Whether AMP casting applies for this op's program on this backend.
    No-op off TPU (unless ``force(True)``): AMP targets the MXU; CPU XLA
    lacks the mixed bf16->f32 dot emitter."""
    global _ON_TPU
    if not getattr(ctx.block.program, "_amp", False):
        return False
    if _FORCE is not None:
        return bool(_FORCE)
    if _ON_TPU is None:
        _ON_TPU = on_tpu()
    return _ON_TPU


def keep_bf16(ctx, out_dtype=None):
    """True when matmul/conv outputs should stay bf16 (pure AMP mode)
    instead of being cast back to the declared activation dtype.
    ``out_dtype``: the op's declared output dtype — narrowing only
    applies to f32/bf16 activations (ints and f64 stay exact)."""
    if out_dtype is not None and out_dtype not in (jnp.float32,
                                                   jnp.bfloat16):
        return False
    return getattr(ctx.block.program, "_amp_pure", False) and active(ctx)


def cast_inputs(ctx, *arrays):
    """bf16-cast float operands when the op's program runs under AMP."""
    if not active(ctx):
        return arrays
    return tuple(
        a.astype(jnp.bfloat16)
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)
        and a.dtype != jnp.bfloat16 else a
        for a in arrays)

"""Attention ops backed by the Pallas flash kernel.

No 2018 reference equivalent (attention postdates the codebase); these ops
give the layers DSL a fused attention primitive the transformer-era models
use, with the Pallas kernel on TPU and dense fallback elsewhere.

Block sizes route through paddle_tpu.tune: a cached per-(device, shape)
winner runs the kernel with the winning {block_q, block_k}; a miss leaves
the blocks to the kernel's own rule for the call's shape
(``flash_attention.default_blocks``; the flash kernel IS this op's default
lowering, so the site is always 'enabled'); a winner that says stock XLA
is fastest lowers through the dense einsum-softmax composition instead.
"""
from __future__ import annotations

from ..core.executor import raw_data
from ..core.registry import register_op
from ..kernels import flash_attention as _flash
from ..kernels.flash_attention import _dense_reference


def _dense_attention(q, k, v, causal, scale, window=None):
    B, S, H, D = q.shape
    Dv = v.shape[3]
    t = lambda a: a.transpose(0, 2, 1, 3).reshape(
        B * a.shape[2], S, a.shape[3])
    o = _dense_reference(t(q), t(k), t(v), causal, scale, window)
    return o.reshape(B, H, S, Dv).transpose(0, 2, 1, 3).astype(q.dtype)


def attention(q, k, v, causal=False, scale=None, window=None):
    """q [batch, seq, heads, D], k [batch, seq, kv heads, D], v [batch,
    seq, kv heads, Dv] -> [batch, seq, heads, Dv]: the flash kernel at a
    tuned winner's blocks or at its own rule's, or the dense composition
    where a tuned winner says so. ``window``: a causal call's band (None:
    the whole triangle). The one place an op's lowering reaches the kernel
    from (``flash_attention``, ``latent_attention``,
    ``grouped_attention``)."""
    from .. import tune
    B, S, H, D = q.shape
    scale = D ** -0.5 if scale is None else float(scale)
    key = {"b": int(B), "s": int(S), "h": int(H), "d": int(D),
           "causal": bool(causal), "dtype": str(q.dtype)}
    # a field only where it differs from the plain call's: a winner cached
    # before the key had it is still found
    if v.shape[3] != D:
        key["dv"] = int(v.shape[3])
    if k.shape[2] != H:
        key["hkv"] = int(k.shape[2])
    if window is not None:
        key["window"] = int(window)
    cfg = tune.lookup("flash_attention", key, enabled=True)
    if cfg is None:
        # a tuned winner decided the dense lowering beats the streamed
        # kernel for this (device, shape) — e.g. short sequences where
        # the [S, S] tile fits VMEM anyway
        return _dense_attention(q, k, v, causal, scale, window)
    return _flash(q, k, v, causal=causal, scale=scale, config=cfg or None,
                  window=window)


@register_op("flash_attention")
def flash_attention_op(ctx):
    """Q: [batch, seq, heads, D], K: [batch, seq, kv heads, D], V: [batch,
    seq, kv heads, Dv] dense tensors; attr ``scale`` multiplies the scores
    (absent: D ** -0.5), ``window`` bounds a causal call's keys."""
    ctx.set_output("Out", attention(
        raw_data(ctx.input("Q")), raw_data(ctx.input("K")),
        raw_data(ctx.input("V")), causal=bool(ctx.attr("causal", False)),
        scale=ctx.attr("scale", None), window=ctx.attr("window", None)))

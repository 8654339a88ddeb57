"""Ops of a modern decoder block: ``rms_norm``, ``rotary_embedding``, and
the half-layers ``latent_attention`` (the DeepSeek-V3 block's, arXiv:
2412.19437 section 2.1), ``grouped_attention`` (grouped-query heads, a
sliding window, QK norms and an output gate where asked for),
``ssm_mixer`` (a Mamba-2 mixer: conv, chunked state-space scan, gated
grouped norm), ``gated_ffn`` and ``moe_ffn`` (a sigmoid router with a
selection bias or a softmax one, SiLU- or ReLU-gated or squared-ReLU
experts, shared experts or none, a router that reads its own half-layer's
input or another block input's norm).

No 2018 reference equivalent. Each half-layer (pre-norm, the products, the
residual add, and where its ``PostNormScale`` is given a norm of the
products' result BEFORE the add: the sandwich norm) is ONE op whose lowering
is a pure jax function, and all five are in ``memory_optimize``'s default
``remat_types``: ``generic_grad`` then recomputes the half-layer under
``jax.checkpoint`` and a layer keeps its two ``[tokens, hidden]`` inputs for
the backward pass instead of every product's operands; an attention
half-layer keeps its flash call's ``o`` and ``lse`` too (``[rows x heads,
tokens, v head]`` and ``[rows x heads, tokens]`` f32), which the backward
kernels read, so that its backward re-runs the norms, products, rotary
positions and gate but not the attention kernel. Inside a lowering
``profiler.part_scope`` names the parts (``proj``, ``rope``, ``attn``,
``attn_window``, ``attn_full``, ``gate``, ``conv``, ``scan``, ``norm``,
``route``, ``experts``, ``shared``, ``post_norm``), and
``profiler.device_scopes()`` keeps that second level
(``forward/latent_attention/attn``).

Precision: under AMP the matrix products take bf16 operands and accumulate
in f32; under pure AMP the residual stream and what a half-layer hands on
is bf16. RMS statistics, rotary angles, the softmax (inside the attention
kernel), the router's product, its sigmoid or softmax and the pick weights
are f32 always; in ``ssm_mixer`` also the conv, the step's softplus, the
cumulative decays, the state carried between chunks and the grouped norm.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.executor import raw_data
from ..core.registry import register_op
from ..kernels.grouped_matmul import grouped_matmul
from ..kernels.pick_sum import pick_sum
from ..kernels.ssd_scan import mixer_scan
from ..profiler import part_scope
from .attention_ops import attention


def _dtypes(ctx, x):
    """(operand dtype of the matrix products, dtype of the stream)."""
    from .. import amp
    operand = jnp.bfloat16 if amp.active(ctx) else x.dtype
    stream = jnp.bfloat16 if amp.keep_bf16(ctx, x.dtype) else x.dtype
    return operand, stream


def _mm(a, w, operand):
    """a [.., K] @ w [K, N] with f32 accumulation; the result stays f32."""
    return jnp.matmul(a.astype(operand), w.astype(operand),
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps, out_dtype=None):
    """x / sqrt(mean(x^2) + eps) * w over the last axis, statistics f32."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(out_dtype or x.dtype)


def rotary(x, theta, layout="interleaved"):
    """x [B, S, H, R]: pair i of row s turned by the angle
    s * theta^(-2i/R), positions 0..S-1. ``layout`` says which two
    elements are pair i: ``"interleaved"`` (2i, 2i+1), ``"half"``
    (i, i + R/2: HF ``rotate_half``)."""
    B, S, H, R = x.shape
    inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    if layout == "half":
        a, b = xf[..., :R // 2], xf[..., R // 2:]
        out = jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                              axis=-1)
    elif layout == "interleaved":
        xf = xf.reshape(B, S, H, R // 2, 2)
        a, b = xf[..., 0], xf[..., 1]
        out = jnp.stack([a * cos - b * sin, a * sin + b * cos],
                        axis=-1).reshape(B, S, H, R)
    else:
        raise ValueError("no rotary layout %r" % (layout,))
    return out.astype(x.dtype)


ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
               "relu2": lambda v: jnp.square(jax.nn.relu(v))}


def gated(h, w_gate, w_up, w_down, operand):
    """(silu(h W_gate) * (h W_up)) W_down, f32 out."""
    act = jax.nn.silu(_mm(h, w_gate, operand)) * _mm(h, w_up, operand)
    return _mm(act, w_down, operand)


def squared_relu(h, w_up, w_down, operand):
    """relu(h W_up)^2 W_down (non-gated), f32 out."""
    return _mm(ACTIVATIONS["relu2"](_mm(h, w_up, operand)), w_down, operand)


def _infer_out_like_x(op, block):
    xv = block._find_var_recursive(op.input("X")[0])
    slot = "Out" if op.output("Out") else "Y"
    ov = block._find_var_recursive(op.output(slot)[0])
    if xv is not None and ov is not None:
        ov.shape, ov.dtype = xv.shape, xv.dtype


@register_op("rms_norm", infer_shape=_infer_out_like_x)
def rms_norm_op(ctx):
    x = raw_data(ctx.input("X"))
    _operand, stream = _dtypes(ctx, x)
    ctx.set_output("Y", rms_norm(x, raw_data(ctx.input("Scale")),
                                 ctx.attr("epsilon", 1e-6), stream))


@register_op("rotary_embedding", infer_shape=_infer_out_like_x)
def rotary_embedding_op(ctx):
    """X [batch, seq, heads, rotary size] -> the same, rows turned by
    their positions 0..seq-1; attr ``layout`` as ``rotary`` has it."""
    ctx.set_output("Out", rotary(raw_data(ctx.input("X")),
                                 float(ctx.attr("theta", 10000.0)),
                                 ctx.attr("layout", "interleaved")))


@register_op("latent_attention", infer_shape=_infer_out_like_x)
def latent_attention_op(ctx):
    """Out = X + W_o attention(...) of RMSNorm(X): queries straight from
    the stream, keys and values through a ``kv_rank``-wide latent and one
    rotary key shared by all heads (multi-head latent attention without a
    query latent)."""
    x = raw_data(ctx.input("X"))
    operand, stream = _dtypes(ctx, x)
    w_in, wq, wkva, w_kv, wkvb, wo = (
        raw_data(ctx.input(s)) for s in
        ("NormScale", "WQ", "WKVA", "KVNormScale", "WKVB", "WO"))
    H, nope, rope, vdim = (int(ctx.attr(a)) for a in
                           ("heads", "nope_dim", "rope_dim", "v_dim"))
    rank = int(ctx.attr("kv_rank"))
    eps, theta = ctx.attr("epsilon"), float(ctx.attr("theta"))
    B, S, _d = x.shape
    x = x.astype(stream)
    with part_scope("proj"):
        h = rms_norm(x, w_in, eps)
        q = _mm(h, wq, operand).astype(stream).reshape(B, S, H, nope + rope)
        c = _mm(h, wkva, operand).astype(stream)
        kv = _mm(rms_norm(c[..., :rank], w_kv, eps), wkvb, operand)
        kv = kv.astype(stream).reshape(B, S, H, nope + vdim)
    with part_scope("rope"):
        q = jnp.concatenate(
            [q[..., :nope], rotary(q[..., nope:], theta)], axis=-1)
        k_rope = rotary(c[:, :, None, rank:], theta)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, rope))],
            axis=-1)
    with part_scope("attn"):
        o = attention(q, k, kv[..., nope:], causal=True,
                      scale=(nope + rope) ** -0.5)
    with part_scope("proj"):
        a = _mm(o.reshape(B, S, H * vdim), wo, operand)
        ctx.set_output("Out", (x.astype(jnp.float32) + a).astype(stream))


def _add_normed(x, y, stream, eps, scale=None):
    """x + y, or x + RMSNorm(y) under ``scale`` (the sandwich norm: applied
    to the half-layer's result BEFORE the add); y f32, the sum in f32,
    handed on in the stream's dtype."""
    if scale is not None:
        with part_scope("post_norm"):
            y = rms_norm(y, scale, eps)
    return (x.astype(jnp.float32) + y).astype(stream)


def _residual(ctx, x, y, stream):
    """``_add_normed`` with the norm where the op was given
    ``PostNormScale``."""
    scale = raw_data(ctx.input("PostNormScale")) \
        if ctx.has_input("PostNormScale") else None
    return _add_normed(x, y, stream, ctx.attr("epsilon"), scale)


@register_op("grouped_attention", infer_shape=_infer_out_like_x)
def grouped_attention_op(ctx):
    """Out = X + N_post(W_o (o * sigmoid(h W_g))), h = RMSNorm(X): q = h
    W_q as ``heads`` heads, k = h W_k and v = h W_v as ``kv_heads`` heads
    (q head j reads k/v head j // (heads / kv_heads)); with ``qk_norm`` q
    and k each RMS-normed over the head with a learned scale; with
    ``rotary`` their whole heads turned by their positions (pairs (i, i +
    head_dim / 2)); causal softmax(q k^T head_dim^-1/2) v, with ``window``
    > 0 over the last ``window`` keys only (itself included); the gate only
    with ``gate``. The attention kernels run under the part scope
    ``attn_window`` (a window) or ``attn_full``."""
    x = raw_data(ctx.input("X"))
    operand, stream = _dtypes(ctx, x)
    w_in, wq, wk, wv, wo = (raw_data(ctx.input(s)) for s in
                            ("NormScale", "WQ", "WK", "WV", "WO"))
    H, Hkv, D = (int(ctx.attr(a)) for a in ("heads", "kv_heads", "head_dim"))
    window = int(ctx.attr("window", 0)) or None
    eps = ctx.attr("epsilon")
    B, S, _d = x.shape
    x = x.astype(stream)
    with part_scope("proj"):
        h = rms_norm(x, w_in, eps)

        def heads(w, n, scale):
            y = _mm(h, raw_data(ctx.input(w)), operand).reshape(B, S, n, D)
            if not ctx.attr("qk_norm", True):
                return y.astype(stream)
            return rms_norm(y, raw_data(ctx.input(scale)), eps, stream)
        q, k = heads("WQ", H, "QNormScale"), heads("WK", Hkv, "KNormScale")
        v = _mm(h, wv, operand).astype(stream).reshape(B, S, Hkv, D)
    if ctx.attr("rotary", True):
        with part_scope("rope"):
            theta = float(ctx.attr("theta"))
            q, k = rotary(q, theta, "half"), rotary(k, theta, "half")
    with part_scope("attn_window" if window else "attn_full"):
        o = attention(q, k, v, causal=True, scale=D ** -0.5, window=window)
    o = o.reshape(B, S, H * D)
    if ctx.attr("gate", True):
        with part_scope("gate"):
            gate = jax.nn.sigmoid(_mm(h, raw_data(ctx.input("WG")), operand))
            o = (o.astype(jnp.float32) * gate).astype(stream)
    with part_scope("proj"):
        a = _mm(o, wo, operand)
    ctx.set_output("Out", _residual(ctx, x, a, stream))


def causal_conv(u, w, b):
    """u [B, S, C] -> b + sum_j w[j] u[t - K + 1 + j] over the K taps of w
    [K, C], zeros before position 0; f32."""
    K, S = w.shape[0], u.shape[1]
    up = jnp.pad(u.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    acc = b.astype(jnp.float32)
    for j in range(K):
        acc = acc + w[j].astype(jnp.float32) * up[:, j:j + S]
    return acc


@register_op("ssm_mixer", infer_shape=_infer_out_like_x)
def ssm_mixer_op(ctx):
    """Out = X + W_out N_g(y * silu(z)), h = RMSNorm(X), a Mamba-2 mixer:
    [z | xBC | dt] = h W_in; xBC = silu(causal conv of ``ConvW`` / ``ConvB``
    over xBC) split into x [heads, head_dim], B and C [groups, state]; y =
    the chunked state-space scan (``kernels/ssd_scan.py: mixer_scan``: step
    softplus(dt + ``DtBias``), decay -exp(``ALog``), chunks of ``chunk``)
    plus ``D`` x; N_g an RMS norm over each group's heads x head_dim
    channels under ``GateNormScale``. Part scopes: ``proj`` (both products
    and the pre-norm), ``conv``, ``scan`` (step, scan, skip) and ``norm``
    (the gate and the grouped norm)."""
    x = raw_data(ctx.input("X"))
    operand, stream = _dtypes(ctx, x)
    H, P, G, N = (int(ctx.attr(a)) for a in
                  ("heads", "head_dim", "groups", "state"))
    eps = ctx.attr("epsilon")
    B, S, d = x.shape
    inner = H * P
    x = x.astype(stream)
    with part_scope("proj"):
        h = rms_norm(x, raw_data(ctx.input("NormScale")), eps)
        zxbcdt = _mm(h, raw_data(ctx.input("WIn")), operand)
        z = zxbcdt[..., :inner].astype(stream)
        xbc = zxbcdt[..., inner:2 * inner + 2 * G * N].astype(stream)
        dt = zxbcdt[..., 2 * inner + 2 * G * N:]
    with part_scope("conv"):
        xbc = jax.nn.silu(causal_conv(xbc, raw_data(ctx.input("ConvW")),
                                      raw_data(ctx.input("ConvB"))))
        xbc = xbc.astype(stream)
        xs = xbc[..., :inner].reshape(B, S, H, P)
        bs = xbc[..., inner:inner + G * N].reshape(B, S, G, N)
        cs = xbc[..., inner + G * N:].reshape(B, S, G, N)
    with part_scope("scan"):
        y = mixer_scan(xs, dt, bs, cs, raw_data(ctx.input("DtBias")),
                       raw_data(ctx.input("ALog")), raw_data(ctx.input("D")),
                       int(ctx.attr("chunk")), operand)
    with part_scope("norm"):
        u = y.reshape(B, S, inner) * jax.nn.silu(z.astype(jnp.float32))
        u = rms_norm(u.reshape(B, S, G, inner // G),
                     raw_data(ctx.input("GateNormScale")).reshape(
                         G, inner // G), eps, stream)
    with part_scope("proj"):
        a = _mm(u.reshape(B, S, inner), raw_data(ctx.input("WOut")), operand)
    ctx.set_output("Out", (x.astype(jnp.float32) + a).astype(stream))


@register_op("gated_ffn", infer_shape=_infer_out_like_x)
def gated_ffn_op(ctx):
    """Out = X + (silu(h W_gate) * (h W_up)) W_down, h = RMSNorm(X); with
    ``PostNormScale`` the products' result is normed before the add."""
    x = raw_data(ctx.input("X"))
    operand, stream = _dtypes(ctx, x)
    x = x.astype(stream)
    h = rms_norm(x, raw_data(ctx.input("NormScale")), ctx.attr("epsilon"))
    y = gated(h, raw_data(ctx.input("WGate")), raw_data(ctx.input("WUp")),
              raw_data(ctx.input("WDown")), operand)
    ctx.set_output("Out", _residual(ctx, x, y, stream))


def route(h, w_router, bias, top_k, scaling, score="sigmoid"):
    """Scores over ALL experts, f32: a sigmoid of each, or a softmax over
    them (``score``). The picks are the ``top_k`` largest of score + bias
    (``bias`` None: of the scores; ties: the lower index); their weights
    are the scores WITHOUT the bias, normalised over the picks and scaled.
    Returns (idx [T, k] int32, g [T, k] f32)."""
    logits = jnp.matmul(h.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if score == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError("no router score %r" % (score,))
    chosen_by = s if bias is None else s + bias.astype(jnp.float32)[None, :]
    _top, idx = jax.lax.top_k(chosen_by, top_k)
    g = jnp.take_along_axis(s, idx, axis=1)
    g = g / (jnp.sum(g, axis=1, keepdims=True) + 1e-20) * scaling
    return idx, g


def held_rungs(pairs, count, n_experts):
    """The static row counts an expert layer that holds ``count`` of
    ``n_experts`` compiles its held part at, ascending: 2x and 4x the
    expected held share of the ``pairs`` (token, pick) pairs, each rounded
    up to a multiple of 512 and capped at ``pairs``, then ``pairs`` itself
    (so no pair is ever left out), duplicates dropped. A layer that holds
    every expert has one rung."""
    expected = pairs * count / float(n_experts)
    rungs = [min(pairs, -(-int(math.ceil(expected * f)) // 512) * 512)
             for f in (2, 4)] + [pairs]
    return tuple(sorted(set(rungs)))


def _held_rung(rows, k, operand, stream, activation):
    """The held experts' part of the routed sum over the first ``rows`` of
    the sorted (token, pick) pairs, experts gated by ``activation``
    (``ACTIVATIONS``; ``eg`` None for the non-gated "relu2": two products,
    not three): ``fn(h, g, eg, eu, ed, order, inv, sizes) -> [T, d]
    f32``. Right whenever ``sum(sizes) <= rows``: the rows from
    ``sum(sizes)`` on are pairs of no held expert, where the grouped
    products (``kernels.grouped_matmul``) and their left operand's gradient
    are exactly 0, so those pairs add nothing either way. Its two
    row movements are gathers in both directions (``inv`` is the inverse
    of the permutation ``order``, which autodiff cannot know: it would
    scatter-add): the rows handed to the experts are a ``rows``-row gather
    of the tokens, and a token's held picks are summed back from the
    sorted rows by ``kernels.pick_sum`` (the forward's results and the
    backward's cotangent of the handed rows alike), which reads only the
    held picks' rows, ``sum(sizes)`` of them, whatever the rung. (A
    ``rows``-row scatter-add into [T, d] was timed beside the gathers: 1.5
    ms a layer ahead at the first rung, behind at the last, and XLA:TPU
    adds its f32 rows at bf16's precision, which is another sum.)"""

    @jax.custom_vjp
    def dispatch(h, o, inv, sizes):         # [T, d] -> [rows, d], sorted
        return h[o // k]

    def dispatch_fwd(h, o, inv, sizes):
        return dispatch(h, o, inv, sizes), (inv, sizes)

    def dispatch_bwd(res, ct):
        inv, sizes = res
        return (pick_sum(ct, inv.reshape(-1, k), sizes, ct.dtype), None,
                None, None)

    dispatch.defvjp(dispatch_fwd, dispatch_bwd)

    @jax.custom_vjp
    def combine(ys, o, inv, sizes):         # sorted [rows, d] -> [T, d] f32
        return pick_sum(ys, inv.reshape(-1, k), sizes)

    def combine_fwd(ys, o, inv, sizes):
        return combine(ys, o, inv, sizes), o

    def combine_bwd(o, ct):
        # row j of the sorted pairs is pick o[j] % k of token o[j] // k, and
        # every pick of a token has the token's cotangent; ``ys`` is of the
        # stream's dtype
        return ct[o // k].astype(stream), None, None, None

    combine.defvjp(combine_fwd, combine_bwd)

    def fn(h, g, eg, eu, ed, order, inv, sizes):
        o = order[:rows]
        with part_scope("route"):
            xs = dispatch(h, o, inv, sizes)
        with part_scope("experts"):
            # rows past the held pairs belong to no group: the kernels'
            # results and left-operand gradients are exactly 0 there, so
            # neither the values nor their gradients meet what the buffer
            # held in those rows. The weights enter as they are stored
            # (f32 masters under AMP): the kernels cast them to the
            # operand dtype in VMEM and write their gradients in f32
            rd = lambda a, w: grouped_matmul(a.astype(operand), w, sizes)
            if eg is None:
                ys = rd(ACTIVATIONS[activation](rd(xs, eu)), ed)
            else:
                ys = rd(ACTIVATIONS[activation](rd(xs, eg)) * rd(xs, eu), ed)
        with part_scope("route"):
            ys = (ys * g.reshape(-1)[o][:, None]).astype(stream)
            return combine(ys, o, inv, sizes)

    return fn


@functools.lru_cache(maxsize=None)
def _held_part(rungs, k, operand, stream, epsilon, activation="silu"):
    """``fn(moved, order, inv, sizes)`` -> the layer's result [B, S, d],
    where ``moved = (h, g, eg, eu, ed, x, shared[, post norm scale])`` are
    the operands a gradient flows to (``shared`` None: the layer has no
    shared expert): ``x + N(shared + routed)`` with
    ``routed`` [T, d] f32 ``_held_rung`` at the smallest of ``rungs``
    (ascending, the last one all the pairs) that holds the step's
    ``sum(sizes)``, chosen on the device.
    One rung: that rung, no conditional. More: ONE ``custom_vjp`` whose
    forward is a ``lax.switch`` and whose backward is a second one, each
    branch taking ``jax.vjp`` of its own rung on the operands, which are
    the only residuals. (Left to autodiff, ``cond``'s partial evaluation
    makes every branch return the union of all branches' residuals,
    zero-filled for the others: the small rung would write the large
    rungs' arrays.) The layer's sum, post norm and residual add sit inside
    the branches so that nothing after the switch needs the forward's
    result in the backward pass: the recomputed forward's switch is dead
    there and the backward runs a rung's forward once.
    A function of arrays only, kept one per key, each rung and each
    rung's gradient an inlined ``jax.jit``: jax's own tracing cache then
    answers every further layer of these shapes, the recomputed forward
    and the backward's ``jax.vjp`` from the FIRST trace of the rung's
    Python in the step's trace context and the first under
    ``jax.checkpoint``'s (four body traces a layer otherwise, and a program
    has several such layers). ``inline=True`` leaves no call in the lowered module:
    every layer's and both phases' instructions keep their own scope."""

    def whole(rows):
        rung = _held_rung(rows, k, operand, stream, activation)

        def fn(moved, order, inv, sizes):
            x, shared = moved[5:7]
            y = rung(*moved[:5], order, inv, sizes)
            if shared is not None:
                y = shared + y
            return _add_normed(x, y.reshape(x.shape), stream, epsilon,
                               *moved[7:])
        return jax.jit(fn, inline=True)
    fns = [whole(rows) for rows in rungs]
    if len(fns) == 1:
        return fns[0]
    steps = np.asarray(rungs[:-1], np.int32)
    rung_of = lambda sizes: jnp.sum(jnp.sum(sizes) > steps)

    def grads(fn):
        def branch(moved, order, inv, sizes, ct):
            _out, vjp = jax.vjp(
                lambda moved: fn(moved, order, inv, sizes), moved)
            return vjp(ct)[0]
        return jax.jit(branch, inline=True)
    bwds = [grads(fn) for fn in fns]

    @jax.custom_vjp
    def held(moved, order, inv, sizes):
        return jax.lax.switch(rung_of(sizes), fns, moved, order, inv, sizes)

    def held_fwd(*args):
        return held(*args), args

    def held_bwd(args, ct):
        return jax.lax.switch(rung_of(args[-1]), bwds, *args,
                              ct), None, None, None

    held.defvjp(held_fwd, held_bwd)
    return held


@register_op("moe_ffn", infer_shape=_infer_out_like_x)
def moe_ffn_op(ctx):
    """Out = X + shared(h) + sum over the picks that fall on a HELD expert
    of g_k E_k(h), h = RMSNorm(X), E_k gated by ``activation`` (silu or
    relu), or with "relu2" relu(h W_up)^2 W_down, no gate (no
    ``ExpertGate``; the shared expert likewise, no ``SharedGate``). The
    router scores all ``n_experts`` (``score``: sigmoid, picked
    by score + ``RouterBias``; softmax, no bias) from h or, where the op is
    given ``RouterX`` and ``RouterNormScale``, from RMSNorm(RouterX) under
    that scale (a router placed before the attention half-layer reads that
    half-layer's input norm; its own product, norm, top-k and sort all sit
    in the part scope ``route``). Without ``SharedUp`` there is no shared
    expert. This op holds the experts ``[first, first + count)`` (stacked weights
    ``[count, ...]``) and leaves out what the others would add: g stays
    normalised over all the picks; no capacity is set, nothing is dropped;
    the held part runs over the smallest rung that holds the step's
    ``RowsHeld``. The (token, pick) pairs are sorted by expert, held
    experts first, so the held ones are a prefix of the sorted order, and
    only a prefix moves: the gathers and the grouped products (Pallas
    kernels, ``kernels.grouped_matmul``) run over the first ``C`` sorted
    pairs, ``C`` the smallest of ``held_rungs`` (static sizes, the last one
    every pair) that holds this step's ``RowsHeld``, and the weighted sum
    back reads the ``RowsHeld`` rows alone (``kernels.pick_sum``); ``C`` is
    picked on the device by a ``lax.switch`` (``_held_part``, which every
    expert layer of one shape shares: a rung's Python is traced once, not
    once a layer and pass). A layer that holds every expert has one rung and no
    switch. With ``PostNormScale`` the sum shared + held part is normed
    before the add: the norm of a partial sum is no part of the whole
    layer's, so shares that are to add up leave it off and norm their sum.
    ``Load`` int32[n_experts]: picks per expert this step; ``RowsHeld``
    int32[1]: pairs that fell on held experts."""
    x = raw_data(ctx.input("X"))
    operand, stream = _dtypes(ctx, x)
    w_post, w_router, eu, ed = (
        raw_data(ctx.input(s)) for s in
        ("NormScale", "WRouter", "ExpertUp", "ExpertDown"))
    eg = raw_data(ctx.input("ExpertGate")) \
        if ctx.has_input("ExpertGate") else None
    k, first = int(ctx.attr("top_k")), int(ctx.attr("first_expert"))
    count, n_experts = eu.shape[0], w_router.shape[1]
    eps = ctx.attr("epsilon")
    B, S, d = x.shape
    T = B * S
    x = x.astype(stream)
    h = rms_norm(x, w_post, eps).reshape(T, d)
    from .. import tune
    rungs = held_rungs(T * k, count, n_experts)
    tune.count_moe_rungs(T * k, rungs)
    with part_scope("route"):
        hr = h
        if ctx.has_input("RouterX"):
            hr = rms_norm(raw_data(ctx.input("RouterX")).astype(stream),
                          raw_data(ctx.input("RouterNormScale")),
                          eps).reshape(T, d)
        bias = raw_data(ctx.input("RouterBias")) \
            if ctx.has_input("RouterBias") else None
        idx, g = route(hr, w_router, bias, k, ctx.attr("scaling"),
                       ctx.attr("score", "sigmoid"))
        flat = idx.reshape(T * k)
        load = jnp.sum(flat[:, None] == jnp.arange(n_experts)[None, :],
                       axis=0, dtype=jnp.int32)
        sizes = load[first:first + count]
        held = (flat >= first) & (flat < first + count)
        order = jnp.argsort(jnp.where(held, flat - first, count),
                            stable=True)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=order.dtype))
    shared = None
    if ctx.has_input("SharedGate"):
        with part_scope("shared"):
            shared = gated(h, *(raw_data(ctx.input(s)) for s in
                                ("SharedGate", "SharedUp", "SharedDown")),
                           operand)
    elif ctx.has_input("SharedUp"):
        with part_scope("shared"):
            shared = squared_relu(h, raw_data(ctx.input("SharedUp")),
                                  raw_data(ctx.input("SharedDown")), operand)
    post = (raw_data(ctx.input("PostNormScale")),) \
        if ctx.has_input("PostNormScale") else ()
    ctx.set_output("Out", _held_part(
        rungs, k, operand, stream, float(eps),
        ctx.attr("activation", "silu"))(
        (h, g, eg, eu, ed, x, shared) + post, order, inv, sizes))
    ctx.set_output("Load", load)
    ctx.set_output("RowsHeld", jnp.sum(sizes).reshape(1))

"""Ops of a modern decoder block: ``rms_norm``, ``rotary_embedding``, and
the half-layers ``latent_attention`` (the DeepSeek-V3 block's, arXiv:
2412.19437 section 2.1), ``grouped_attention`` (grouped-query heads with
QK norms, a sliding window, an output gate), ``gated_ffn`` and ``moe_ffn``.

No 2018 reference equivalent. Each half-layer (pre-norm, the products, the
residual add, and where its ``PostNormScale`` is given a norm of the
products' result BEFORE the add: the sandwich norm) is ONE op whose lowering
is a pure jax function, and all four are in ``memory_optimize``'s default
``remat_types``: ``generic_grad`` then recomputes the half-layer under
``jax.checkpoint`` and a layer keeps its two ``[tokens, hidden]`` inputs for
the backward pass instead of every product's operands. Inside a lowering
``profiler.part_scope`` names the parts (``proj``, ``rope``, ``attn``,
``attn_window``, ``attn_full``, ``gate``, ``route``, ``experts``,
``shared``, ``post_norm``), and ``profiler.device_scopes()`` keeps that
second level (``forward/latent_attention/attn``).

Precision: under AMP the matrix products take bf16 operands and accumulate
in f32; under pure AMP the residual stream and what a half-layer hands on
is bf16. RMS statistics, rotary angles, the softmax (inside the attention
kernel), the router's product, its sigmoid and the pick weights are f32
always.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.executor import raw_data
from ..core.registry import register_op
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


def gated(h, w_gate, w_up, w_down, operand):
    """(silu(h W_gate) * (h W_up)) W_down, f32 out."""
    act = jax.nn.silu(_mm(h, w_gate, operand)) * _mm(h, w_up, operand)
    return _mm(act, w_down, operand)


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
    (q head j reads k/v head j // (heads / kv_heads)), q and k each
    RMS-normed over the head with a learned scale; with ``rotary`` their
    whole heads turned by their positions (pairs (i, i + head_dim / 2));
    causal softmax(q k^T head_dim^-1/2) v, with ``window`` > 0 over the
    last ``window`` keys only (itself included). The attention kernels run
    under the part scope ``attn_window`` (a window) or ``attn_full``."""
    x = raw_data(ctx.input("X"))
    operand, stream = _dtypes(ctx, x)
    w_in, wq, wk, wv, w_qn, w_kn, wg, wo = (
        raw_data(ctx.input(s)) for s in
        ("NormScale", "WQ", "WK", "WV", "QNormScale", "KNormScale", "WG",
         "WO"))
    H, Hkv, D = (int(ctx.attr(a)) for a in ("heads", "kv_heads", "head_dim"))
    window = int(ctx.attr("window", 0)) or None
    eps = ctx.attr("epsilon")
    B, S, _d = x.shape
    x = x.astype(stream)
    with part_scope("proj"):
        h = rms_norm(x, w_in, eps)
        normed = lambda w, n, scale: rms_norm(
            _mm(h, w, operand).reshape(B, S, n, D), scale, eps, stream)
        q, k = normed(wq, H, w_qn), normed(wk, Hkv, w_kn)
        v = _mm(h, wv, operand).astype(stream).reshape(B, S, Hkv, D)
    if ctx.attr("rotary", True):
        with part_scope("rope"):
            theta = float(ctx.attr("theta"))
            q, k = rotary(q, theta, "half"), rotary(k, theta, "half")
    with part_scope("attn_window" if window else "attn_full"):
        o = attention(q, k, v, causal=True, scale=D ** -0.5, window=window)
    with part_scope("gate"):
        gate = jax.nn.sigmoid(_mm(h, wg, operand))
        o = (o.reshape(B, S, H * D).astype(jnp.float32) * gate).astype(stream)
    with part_scope("proj"):
        a = _mm(o, wo, operand)
    ctx.set_output("Out", _residual(ctx, x, a, stream))


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


def route(h, w_router, bias, top_k, scaling):
    """Sigmoid scores over ALL experts, f32. The picks are the ``top_k``
    largest of score + bias (ties: the lower index); their weights are the
    scores WITHOUT the bias, normalised over the picks and scaled.
    Returns (idx [T, k] int32, g [T, k] f32)."""
    s = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _top, idx = jax.lax.top_k(s + bias.astype(jnp.float32)[None, :], top_k)
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


def _held_rung(rows, k, operand, stream):
    """The held experts' part of the routed sum over the first ``rows`` of
    the sorted (token, pick) pairs: ``fn(h, g, eg, eu, ed, order, inv,
    sizes) -> [T, d] f32``. Right whenever ``sum(sizes) <= rows``. Its two
    row movements are gathers in both directions (``inv`` is the inverse
    of the permutation ``order``, which autodiff cannot know: it would
    scatter-add): a token's picks read row ``inv`` of the ``rows``
    results, and a pick whose row lies past them (it is not held) reads
    the zero it added when every pair moved. (A ``rows``-row scatter-add
    into [T, d] was timed beside it, ``PERF.md`` section 6, PR 35: 1.5 ms
    a layer ahead at the first rung, behind at the last, and XLA:TPU adds
    its f32 rows at bf16's precision, which is another sum.)"""

    def summed_picks(a, inv):    # sorted [rows, d] -> [T, d] f32
        # (indexed [T, k] at once: a [T * k, d] result reshaped to
        # [T, k, d] is a copy on the chip where k is no multiple of 8)
        inv = inv.reshape(-1, k)
        got = a[jnp.minimum(inv, rows - 1)]
        if rows < inv.size:
            got = jnp.where((inv < rows)[..., None], got, 0)
        return got.astype(jnp.float32).sum(axis=1)

    @jax.custom_vjp
    def dispatch(h, o, inv, live):          # [T, d] -> [rows, d], sorted
        return h[o // k]

    def dispatch_fwd(h, o, inv, live):
        return dispatch(h, o, inv, live), (inv, live)

    def dispatch_bwd(res, ct):
        inv, live = res
        back = summed_picks(jnp.where(live, ct, 0), inv)
        return back.astype(ct.dtype), None, None, None

    dispatch.defvjp(dispatch_fwd, dispatch_bwd)

    @jax.custom_vjp
    def combine(ys, o, inv):                # sorted [rows, d] -> [T, d] f32
        return summed_picks(ys, inv)

    def combine_fwd(ys, o, inv):
        return combine(ys, o, inv), o

    def combine_bwd(o, ct):
        # row j of the sorted pairs is pick o[j] % k of token o[j] // k, and
        # every pick of a token has the token's cotangent; ``ys`` is of the
        # stream's dtype
        return ct[o // k].astype(stream), None, None

    combine.defvjp(combine_fwd, combine_bwd)

    def fn(h, g, eg, eu, ed, order, inv, sizes):
        o = order[:rows]
        live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
        with part_scope("route"):
            xs = dispatch(h, o, inv, live)
        # (XLA:TPU makes ``ragged-dot-*`` kernels of the ragged dots under
        # an ``op_name`` of its own: the scope table calls those unscoped)
        with part_scope("experts"):
            # rows past the held pairs belong to no group and a grouped
            # product leaves there whatever the buffer held (NaN on the
            # chip): every result is SELECTED by ``live`` before anything
            # multiplies it, so that neither the values nor their gradients
            # meet the garbage
            rd = lambda a, w: jnp.where(live, jax.lax.ragged_dot(
                a.astype(operand), w.astype(operand), sizes,
                preferred_element_type=jnp.float32), 0.0)
            ys = rd(jax.nn.silu(rd(xs, eg)) * rd(xs, eu), ed)
        with part_scope("route"):
            ys = (ys * g.reshape(-1)[o][:, None]).astype(stream)
            return combine(ys, o, inv)

    return fn


@functools.lru_cache(maxsize=None)
def _held_part(rungs, k, operand, stream, epsilon):
    """``fn(moved, order, inv, sizes)`` -> the layer's result [B, S, d],
    where ``moved = (h, g, eg, eu, ed, x, shared[, post norm scale])`` are
    the operands a gradient flows to: ``x + N(shared + routed)`` with
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
        rung = _held_rung(rows, k, operand, stream)

        def fn(moved, order, inv, sizes):
            x, shared = moved[5:7]
            routed = rung(*moved[:5], order, inv, sizes)
            return _add_normed(x, (shared + routed).reshape(x.shape), stream,
                               epsilon, *moved[7:])
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
    of g_k E_k(h), h = RMSNorm(X). The router scores all ``n_experts``;
    this op holds the experts ``[first, first + count)`` (stacked weights
    ``[count, ...]``) and leaves out what the others would add: g stays
    normalised over all the picks; no capacity is set, nothing is dropped;
    the held part runs over the smallest rung that holds the step's
    ``RowsHeld``. The (token, pick) pairs are sorted by expert, held
    experts first, so the held ones are a prefix of the sorted order, and
    only a prefix moves: the gathers, the grouped products
    (``jax.lax.ragged_dot``) and the weighted sum back run over the first
    ``C`` sorted pairs, ``C`` the smallest of ``held_rungs`` (static sizes,
    the last one every pair) that holds this step's ``RowsHeld``, picked on
    the device by a ``lax.switch`` (``_held_part``, which every expert
    layer of one shape shares: a rung's Python is traced once, not once a
    layer and pass). A layer that holds every expert has one rung and no
    switch. With ``PostNormScale`` the sum shared + held part is normed
    before the add: the norm of a partial sum is no part of the whole
    layer's, so shares that are to add up leave it off and norm their sum.
    ``Load`` int32[n_experts]: picks per expert this step; ``RowsHeld``
    int32[1]: pairs that fell on held experts."""
    x = raw_data(ctx.input("X"))
    operand, stream = _dtypes(ctx, x)
    w_post, w_router, bias, eg, eu, ed, sg, su, sd = (
        raw_data(ctx.input(s)) for s in
        ("NormScale", "WRouter", "RouterBias", "ExpertGate", "ExpertUp",
         "ExpertDown", "SharedGate", "SharedUp", "SharedDown"))
    k, first = int(ctx.attr("top_k")), int(ctx.attr("first_expert"))
    count, n_experts = eg.shape[0], w_router.shape[1]
    B, S, d = x.shape
    T = B * S
    x = x.astype(stream)
    h = rms_norm(x, w_post, ctx.attr("epsilon")).reshape(T, d)
    from .. import tune
    rungs = held_rungs(T * k, count, n_experts)
    tune.count_moe_rungs(T * k, rungs)
    with part_scope("route"):
        idx, g = route(h, w_router, bias, k, ctx.attr("scaling"))
        flat = idx.reshape(T * k)
        load = jnp.sum(flat[:, None] == jnp.arange(n_experts)[None, :],
                       axis=0, dtype=jnp.int32)
        sizes = load[first:first + count]
        held = (flat >= first) & (flat < first + count)
        order = jnp.argsort(jnp.where(held, flat - first, count),
                            stable=True)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=order.dtype))
    with part_scope("shared"):
        shared = gated(h, sg, su, sd, operand)
    post = (raw_data(ctx.input("PostNormScale")),) \
        if ctx.has_input("PostNormScale") else ()
    ctx.set_output("Out", _held_part(
        rungs, k, operand, stream, float(ctx.attr("epsilon")))(
        (h, g, eg, eu, ed, x, shared) + post, order, inv, sizes))
    ctx.set_output("Load", load)
    ctx.set_output("RowsHeld", jnp.sum(sizes).reshape(1))

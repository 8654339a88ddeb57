"""Layers of a modern decoder block: ``flash_attention``, ``rms_norm``,
``rotary_embedding`` and the half-layers ``latent_attention``,
``grouped_attention``, ``ssm_mixer``, ``gated_ffn`` and ``moe_ffn``
(ops/decoder_ops.py; doc/decoder_layers.md).

The half-layers take the residual stream ``x`` [batch, seq, hidden] and
return ``x + f(rms_norm(x))`` (with ``post_norm`` ``x + rms_norm(f(
rms_norm(x)))``, the sandwich norm): norms, products and residual add are
one op each, so that ``memory_optimize`` can recompute a whole half-layer in
the backward pass.
"""
from __future__ import annotations

import numpy as np

from ..initializer import (ConstantInitializer, NumpyArrayInitializer,
                           UniformInitializer)
from ..param_attr import ParamAttr
from .layer_helper import LayerHelper

__all__ = ["flash_attention", "rms_norm", "rotary_embedding",
           "latent_attention", "grouped_attention", "ssm_mixer", "gated_ffn",
           "moe_ffn", "moe_load_stats", "moe_rows_moved"]


def _named(prefix, name):
    return ParamAttr(name=None if prefix is None else prefix + "." + name)


def _out_like(helper, x):
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    return out


def flash_attention(q, k, v, causal=False, scale=None, window=None):
    """q [batch, seq, heads, D], k [batch, seq, kv heads, D], v [batch,
    seq, kv heads, Dv] -> [batch, seq, heads, Dv] through the
    ``flash_attention`` op (``heads`` a multiple of ``kv heads``: q head j
    reads k/v head j // (heads / kv heads)). ``scale`` multiplies the
    scores (None: D ** -0.5); ``window``: a causal call sees only that
    many keys back, itself included."""
    helper = LayerHelper("flash_attention")
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    out.shape = tuple(q.shape[:3]) + (v.shape[3],)
    attrs = {"causal": bool(causal)}
    if scale is not None:
        attrs["scale"] = float(scale)
    if window is not None:
        attrs["window"] = int(window)
    helper.append_op(type="flash_attention",
                     inputs={"Q": [q], "K": [k], "V": [v]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def rms_norm(x, epsilon=1e-6, param_attr=None, name=None):
    """x / sqrt(mean(x^2) + epsilon) * scale over the last axis; the scale
    starts at 1 and the statistics are f32."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    scale = helper.create_parameter(
        helper.param_attr, shape=[x.shape[-1]], dtype=x.dtype,
        default_initializer=ConstantInitializer(1.0))
    out = _out_like(helper, x)
    helper.append_op(type="rms_norm", inputs={"X": [x], "Scale": [scale]},
                     outputs={"Y": [out]}, attrs={"epsilon": epsilon})
    return out


def rotary_embedding(x, theta=10000.0, layout="interleaved"):
    """x [batch, seq, heads, size]: pair i of row s turned by
    s * theta^(-2i/size), positions 0..seq-1; the pair is (2i, 2i+1)
    (``"interleaved"``) or (i, i + size/2) (``"half"``)."""
    helper = LayerHelper("rotary_embedding")
    out = _out_like(helper, x)
    helper.append_op(type="rotary_embedding", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"theta": float(theta), "layout": layout})
    return out


def _params(helper, prefix, dtype, shapes):
    """{slot: [parameter]} for ``shapes``: {slot: (name, shape)}; a 1-D
    shape is a norm scale and starts at 1."""
    return {slot: [helper.create_parameter(
        _named(prefix, name), shape=list(shape), dtype=dtype,
        default_initializer=(ConstantInitializer(1.0) if len(shape) == 1
                             else None))]
            for slot, (name, shape) in shapes.items()}


def latent_attention(x, num_heads, nope_dim, rope_dim, v_dim, kv_rank,
                     theta=10000.0, epsilon=1e-6, prefix=None):
    """x + W_o attn(q, k, v) of h = rms_norm(x): q = h W_q (heads of
    nope_dim + rope_dim); [c_kv, k_rope] = h W_kva (kv_rank + rope_dim);
    [k_nope, v] = rms_norm(c_kv) W_kvb (heads of nope_dim + v_dim); rotary
    positions on q's last rope_dim and on k_rope, which all heads share;
    causal softmax(q k^T (nope_dim + rope_dim)^-1/2) v. Parameters are named
    ``<prefix>.{norm,wq,wkva,kv_norm,wkvb,wo}``."""
    helper = LayerHelper("latent_attention")
    d = x.shape[-1]
    inputs = _params(helper, prefix, x.dtype, {
        "NormScale": ("norm", (d,)),
        "WQ": ("wq", (d, num_heads * (nope_dim + rope_dim))),
        "WKVA": ("wkva", (d, kv_rank + rope_dim)),
        "KVNormScale": ("kv_norm", (kv_rank,)),
        "WKVB": ("wkvb", (kv_rank, num_heads * (nope_dim + v_dim))),
        "WO": ("wo", (num_heads * v_dim, d))})
    inputs["X"] = [x]
    out = _out_like(helper, x)
    helper.append_op(
        type="latent_attention", inputs=inputs, outputs={"Out": [out]},
        attrs={"heads": num_heads, "nope_dim": nope_dim,
               "rope_dim": rope_dim, "v_dim": v_dim, "kv_rank": kv_rank,
               "theta": float(theta), "epsilon": epsilon})
    return out


def _post_norm(post_norm, d):
    """The slot of a half-layer's sandwich norm, where it has one."""
    return {"PostNormScale": ("post_norm", (d,))} if post_norm else {}


def grouped_attention(x, num_heads, num_kv_heads, head_dim, window=None,
                      rotary=True, theta=10000.0, epsilon=1e-6,
                      post_norm=False, qk_norm=True, gate=True, prefix=None):
    """x + [rms_norm] W_o (attn(q, k, v) * sigmoid(h W_g)) of h =
    rms_norm(x): q = h W_q (``num_heads`` heads), k = h W_k and v = h W_v
    (``num_kv_heads`` heads, each read by num_heads / num_kv_heads q
    heads, never repeated); with ``qk_norm`` q and k RMS-normed over
    ``head_dim`` with a learned scale each; with ``rotary`` the whole heads
    turned by their positions, pairs (i, i + head_dim/2); causal
    softmax(q k^T head_dim^-1/2) v, with ``window`` over the last
    ``window`` keys only (itself included); the sigmoid gate only with
    ``gate``. Parameters ``<prefix>.{norm,wq,wk,wv,q_norm,k_norm,wg,wo}``
    (``q_norm`` / ``k_norm`` only with ``qk_norm``, ``wg`` only with
    ``gate``) and, with ``post_norm``, ``post_norm``."""
    if num_heads % num_kv_heads:
        raise ValueError("%d query heads cannot share %d key and value "
                         "heads" % (num_heads, num_kv_heads))
    helper = LayerHelper("grouped_attention")
    d = x.shape[-1]
    shapes = {"NormScale": ("norm", (d,)),
              "WQ": ("wq", (d, num_heads * head_dim)),
              "WK": ("wk", (d, num_kv_heads * head_dim)),
              "WV": ("wv", (d, num_kv_heads * head_dim))}
    if qk_norm:
        shapes.update(QNormScale=("q_norm", (head_dim,)),
                      KNormScale=("k_norm", (head_dim,)))
    if gate:
        shapes["WG"] = ("wg", (d, num_heads * head_dim))
    shapes["WO"] = ("wo", (num_heads * head_dim, d))
    inputs = _params(helper, prefix, x.dtype,
                     dict(shapes, **_post_norm(post_norm, d)))
    inputs["X"] = [x]
    out = _out_like(helper, x)
    helper.append_op(
        type="grouped_attention", inputs=inputs, outputs={"Out": [out]},
        attrs={"heads": num_heads, "kv_heads": num_kv_heads,
               "head_dim": head_dim, "window": int(window or 0),
               "rotary": bool(rotary), "theta": float(theta),
               "epsilon": epsilon, "qk_norm": bool(qk_norm),
               "gate": bool(gate)})
    return out


def ssm_mixer(x, num_heads, head_dim, n_groups, state_size, conv_kernel=4,
              chunk_size=128, epsilon=1e-5, prefix=None):
    """x + W_out N_g(y * silu(z)) of h = rms_norm(x), a Mamba-2 mixer:
    [z | xBC | dt] = h W_in (z ``num_heads`` x ``head_dim`` wide, xBC that
    and 2 x ``n_groups`` x ``state_size``, dt ``num_heads``); xBC through a
    causal depthwise conv of ``conv_kernel`` taps with a bias and a SiLU,
    split into x (heads of ``head_dim``), B and C (``n_groups`` groups of
    ``state_size``; head j reads group j // (num_heads / n_groups)); y the
    state-space scan of x with the step softplus(dt + dt_bias) and the decay
    -exp(a_log) a head, plus d x (``kernels/ssd_scan.py``, computed in
    chunks of ``chunk_size``); N_g an RMS norm over each of ``n_groups``
    groups of channels with one learned scale. Parameters
    ``<prefix>.{norm,w_in,conv_w,conv_b,dt_bias,a_log,d,gate_norm,w_out}``
    (``conv_w`` [conv_kernel, channels]: tap j reads the position
    conv_kernel - 1 - j back)."""
    if num_heads % n_groups:
        raise ValueError("%d heads cannot share %d groups"
                         % (num_heads, n_groups))
    helper = LayerHelper("ssm_mixer")
    d = x.shape[-1]
    inner = num_heads * head_dim
    channels = inner + 2 * n_groups * state_size
    named = lambda name, shape, init=None: [helper.create_parameter(
        _named(prefix, name), shape=list(shape), dtype=x.dtype,
        default_initializer=init)]
    inputs = {
        "NormScale": named("norm", (d,), ConstantInitializer(1.0)),
        "WIn": named("w_in", (d, inner + channels + num_heads)),
        "ConvW": named("conv_w", (conv_kernel, channels),
                       UniformInitializer(-0.5, 0.5)),
        "ConvB": named("conv_b", (channels,), UniformInitializer(-0.5, 0.5)),
        # a step of 0.01 at the start, decays 1..num_heads
        "DtBias": named("dt_bias", (num_heads,), ConstantInitializer(
            float(np.log(np.expm1(0.01))))),
        "ALog": named("a_log", (num_heads,), NumpyArrayInitializer(
            np.log(np.arange(1, num_heads + 1, dtype=np.float32)))),
        "D": named("d", (num_heads,), ConstantInitializer(1.0)),
        "GateNormScale": named("gate_norm", (inner,),
                               ConstantInitializer(1.0)),
        "WOut": named("w_out", (inner, d)),
        "X": [x]}
    out = _out_like(helper, x)
    helper.append_op(
        type="ssm_mixer", inputs=inputs, outputs={"Out": [out]},
        attrs={"heads": num_heads, "head_dim": head_dim,
               "groups": n_groups, "state": state_size,
               "chunk": chunk_size, "epsilon": epsilon})
    return out


def gated_ffn(x, size, epsilon=1e-6, post_norm=False, prefix=None):
    """x + (silu(h W_gate) * (h W_up)) W_down, h = rms_norm(x), ``size``
    wide; with ``post_norm`` the products' result is normed before the
    add. Parameters ``<prefix>.{norm,gate,up,down}`` (and ``post_norm``)."""
    helper = LayerHelper("gated_ffn")
    d = x.shape[-1]
    inputs = _params(helper, prefix, x.dtype, dict({
        "NormScale": ("norm", (d,)), "WGate": ("gate", (d, size)),
        "WUp": ("up", (d, size)), "WDown": ("down", (size, d))},
        **_post_norm(post_norm, d)))
    inputs["X"] = [x]
    out = _out_like(helper, x)
    helper.append_op(type="gated_ffn", inputs=inputs,
                     outputs={"Out": [out]}, attrs={"epsilon": epsilon})
    return out


def moe_ffn(x, num_experts, top_k, expert_size, shared_size,
            experts_held=None, scaling=1.0, epsilon=1e-6, post_norm=False,
            score="sigmoid", activation="silu", router_input=None,
            prefix=None):
    """x + shared(h) + the held experts' part of the routed sum, h =
    rms_norm(x). The router scores ALL ``num_experts`` with a sigmoid and
    picks the ``top_k`` largest of score + bias (the bias is a persistable
    variable with no gradient), or (``score="softmax"``) with a softmax
    over them and picks the ``top_k`` largest scores (no bias is made); the
    weights are the picked scores without the bias, normalised over the
    picks, times ``scaling``. ``router_input = (y, norm_scale)``: the
    router reads rms_norm(y) under that existing scale parameter instead
    of h (a router placed before attention: the block's input and the
    attention norm's scale, which then gets the gradient of both readers).
    An expert is (act(h W_gate) * (h W_up)) W_down, ``activation`` "silu"
    or "relu", or with ``activation="relu2"`` the non-gated relu(h W_up)^2
    W_down (no ``expert_gate`` / ``shared_gate`` is made); the shared
    expert is of the experts' kind; ``shared_size`` 0 makes no shared
    expert.

    ``experts_held = (first, count)`` says which experts THIS program
    holds (None: all): their weights are three stacked parameters
    ``[count, ...]``, and what the absent experts would add is left out;
    no capacity is set, nothing is dropped; the held part runs over the
    smallest rung that holds the step's ``RowsHeld``: the held pairs are a
    prefix of the pairs sorted by expert, and the gathers and grouped
    products run over the shortest of a few static lengths that holds this
    step's prefix (``ops/decoder_ops.py: held_rungs``: 2x and 4x the
    expected share, then all the pairs), picked on the device each step.
    Returns ``(out, load, rows_held)``: ``load`` int32[num_experts] counts
    this step's picks per expert, ``rows_held`` int32[1] the (token, pick)
    pairs on held experts. With ``post_norm`` shared + held part is normed before the
    add (``<prefix>.post_norm``); the norm of a share's partial sum is no
    part of the whole layer's norm, so shares that are to add up leave it
    off. Parameters ``<prefix>.{norm,router,router_bias,expert_gate,
    expert_up,expert_down,shared_gate,shared_up,shared_down}``
    (``router_bias`` only for a sigmoid router, ``shared_*`` only with a
    ``shared_size``, the two ``*_gate`` only for a gated ``activation``)."""
    first, count = experts_held or (0, num_experts)
    if not (0 <= first and count >= 1 and first + count <= num_experts):
        raise ValueError("experts_held %r lies outside the %d experts"
                         % (experts_held, num_experts))
    if score not in ("sigmoid", "softmax"):
        raise ValueError("no router score %r" % (score,))
    if activation not in ("silu", "relu", "relu2"):
        raise ValueError("no expert activation %r" % (activation,))
    gated = activation != "relu2"
    helper = LayerHelper("moe_ffn")
    d = x.shape[-1]
    shapes = {"NormScale": ("norm", (d,)),
              "WRouter": ("router", (d, num_experts))}
    if gated:
        shapes["ExpertGate"] = ("expert_gate", (count, d, expert_size))
    shapes.update(ExpertUp=("expert_up", (count, d, expert_size)),
                  ExpertDown=("expert_down", (count, expert_size, d)))
    if shared_size:
        if gated:
            shapes["SharedGate"] = ("shared_gate", (d, shared_size))
        shapes.update(SharedUp=("shared_up", (d, shared_size)),
                      SharedDown=("shared_down", (shared_size, d)))
    inputs = _params(helper, prefix, x.dtype,
                     dict(shapes, **_post_norm(post_norm, d)))
    inputs["X"] = [x]
    if router_input is not None:
        inputs.update(RouterX=[router_input[0]],
                      RouterNormScale=[router_input[1]])
    if score == "sigmoid":
        bias_attr = _named(prefix, "router_bias")
        bias_attr.trainable = False
        bias = helper.create_parameter(
            bias_attr, shape=[num_experts], dtype=x.dtype,
            default_initializer=ConstantInitializer(0.0))
        bias.stop_gradient = True
        inputs["RouterBias"] = [bias]
    out = _out_like(helper, x)
    load = helper.create_variable_for_type_inference("int32", True)
    load.shape = (num_experts,)
    rows = helper.create_variable_for_type_inference("int32", True)
    rows.shape = (1,)
    helper.append_op(
        type="moe_ffn", inputs=inputs,
        outputs={"Out": [out], "Load": [load], "RowsHeld": [rows]},
        attrs={"top_k": top_k, "first_expert": first,
               "scaling": float(scaling), "epsilon": epsilon,
               "score": score, "activation": activation})
    return out, load, rows


def moe_load_stats(load, rows_held):
    """What one fetched ``load`` / ``rows_held`` pair of ``moe_ffn`` says
    about the step's routing, on the host: ``moe_rows_held`` (the (token,
    pick) pairs that fell on held experts) and ``moe_max_over_mean_load``
    (the fullest expert's picks over the mean of all: 1 is even)."""
    load = np.asarray(load, np.float64)
    return {"moe_rows_held": int(np.asarray(rows_held).sum()),
            "moe_max_over_mean_load": float(load.max()
                                            / max(load.mean(), 1e-30))}


def moe_rows_moved(rows_held, pairs, count, num_experts):
    """The rows the held part of a ``moe_ffn`` over ``pairs`` (token, pick)
    pairs that holds ``count`` of ``num_experts`` moved in a step whose
    fetched ``rows_held`` this is: the first of ``held_rungs`` that holds
    them, as the step's own ``lax.switch`` picked it."""
    from ..ops.decoder_ops import held_rungs
    held = int(np.asarray(rows_held).sum())
    return next(r for r in held_rungs(pairs, count, num_experts)
                if r >= held)

"""NN ops: activations, softmax, conv, pooling, normalization, dropout.

reference: paddle/fluid/operators/{activation,softmax,conv,pool,batch_norm,
dropout,lrn,prelu}_op.* (+ cudnn variants conv_cudnn_op.cu.cc etc.). The cudnn
library axis disappears: XLA's conv emitter targets the MXU directly; NCHW
semantics are preserved at the API (reference layout) and XLA re-lays-out
internally for TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import registry
from ..core.executor import raw_data, with_lod_of
from ..core.registry import register_op
from .common import jdt, prod


# -- activations ------------------------------------------------------------
# reference: operators/activation_op.cc (~20 in one file) — same here.

def _act(ctx, fn):
    x = ctx.input("X")
    ctx.set_output("Out", with_lod_of(x, fn(raw_data(x))))


def _infer_same(op, block):
    names = op.input("X")
    if not names:
        return
    xv = block._find_var_recursive(names[0])
    for n in op.output("Out"):
        ov = block._find_var_recursive(n)
        if ov is not None and xv is not None:
            ov.shape = xv.shape
            ov.dtype = xv.dtype
            ov.lod_level = xv.lod_level


_ACTIVATIONS = {
    "sigmoid": jax.nn.sigmoid,
    "logsigmoid": jax.nn.log_sigmoid,
    "tanh": jnp.tanh,
    "relu": jax.nn.relu,
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
    "exp": jnp.exp,
    "abs": jnp.abs,
    "ceil": jnp.ceil,
    "floor": jnp.floor,
    "round": jnp.round,
    "log": jnp.log,
    "square": jnp.square,
    "sqrt": jnp.sqrt,
    "reciprocal": lambda x: 1.0 / x,
    "softplus": jax.nn.softplus,
    "softsign": lambda x: x / (1.0 + jnp.abs(x)),
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tanh_shrink": lambda x: x - jnp.tanh(x),
    "softshrink": lambda x: jnp.sign(x) * jnp.maximum(jnp.abs(x) - 0.5, 0.0),
    "sign": jnp.sign,
}
for _name, _fn in _ACTIVATIONS.items():
    register_op(_name, infer_shape=_infer_same)(
        functools.partial(lambda ctx, f: _act(ctx, f), f=_fn))


@register_op("hard_shrink", infer_shape=_infer_same)
def hard_shrink(ctx):
    """reference: operators/activation_op.cc HardShrinkFunctor — pass x
    through only outside [-threshold, threshold]."""
    t = ctx.attr("threshold", 0.5)
    _act(ctx, lambda x: jnp.where((x > t) | (x < -t), x,
                                  jnp.zeros((), x.dtype)))


@register_op("leaky_relu", infer_shape=_infer_same)
def leaky_relu(ctx):
    a = ctx.attr("alpha", 0.02)
    _act(ctx, lambda x: jnp.where(x > 0, x, a * x))


@register_op("elu", infer_shape=_infer_same)
def elu(ctx):
    a = ctx.attr("alpha", 1.0)
    _act(ctx, lambda x: jnp.where(x > 0, x, a * (jnp.exp(x) - 1.0)))


@register_op("brelu", infer_shape=_infer_same)
def brelu(ctx):
    lo, hi = ctx.attr("t_min", 0.0), ctx.attr("t_max", 24.0)
    _act(ctx, lambda x: jnp.clip(x, lo, hi))


@register_op("soft_relu", infer_shape=_infer_same)
def soft_relu(ctx):
    t = ctx.attr("threshold", 40.0)
    _act(ctx, lambda x: jnp.log1p(jnp.exp(jnp.clip(x, -t, t))))


@register_op("hard_sigmoid", infer_shape=_infer_same)
def hard_sigmoid(ctx):
    s = ctx.attr("slope", 0.2)
    o = ctx.attr("offset", 0.5)
    _act(ctx, lambda x: jnp.clip(s * x + o, 0.0, 1.0))


@register_op("swish", infer_shape=_infer_same)
def swish(ctx):
    b = ctx.attr("beta", 1.0)
    _act(ctx, lambda x: x * jax.nn.sigmoid(b * x))


@register_op("thresholded_relu", infer_shape=_infer_same)
def thresholded_relu(ctx):
    t = ctx.attr("threshold", 1.0)
    _act(ctx, lambda x: jnp.where(x > t, x, 0.0))


@register_op("stanh", infer_shape=_infer_same)
def stanh(ctx):
    a = ctx.attr("scale_a", 0.67)
    b = ctx.attr("scale_b", 1.7159)
    _act(ctx, lambda x: b * jnp.tanh(a * x))


@register_op("pow", infer_shape=_infer_same)
def pow_op(ctx):
    f = ctx.attr("factor", 1.0)
    _act(ctx, lambda x: jnp.power(x, f))


@register_op("prelu", infer_shape=_infer_same)
def prelu(ctx):
    x = raw_data(ctx.input("X"))
    alpha = raw_data(ctx.input("Alpha"))
    mode = ctx.attr("mode", "all")
    if mode == "channel" and alpha.ndim == 1:
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    ctx.set_output("Out", jnp.where(x > 0, x, alpha * x))


@register_op("softmax", infer_shape=_infer_same)
def softmax(ctx):
    x = ctx.input("X")
    ctx.set_output("Out", with_lod_of(x, jax.nn.softmax(raw_data(x), axis=-1)))


@register_op("log_softmax", infer_shape=_infer_same)
def log_softmax(ctx):
    x = ctx.input("X")
    ctx.set_output("Out", with_lod_of(x, jax.nn.log_softmax(raw_data(x), axis=-1)))


@register_op("maxout")
def maxout(ctx):
    x = raw_data(ctx.input("X"))
    g = ctx.attr("groups")
    n, c, h, w = x.shape
    ctx.set_output("Out", x.reshape(n, c // g, g, h, w).max(axis=2))


# -- dropout (custom grad: uses the saved mask) ------------------------------

def _dropout_grad_maker(op, block, grad_of, no_grad):
    gout = grad_of.get(op.output("Out")[0])
    if gout is None:
        return None
    xname = op.input("X")[0]
    if xname in no_grad:
        return None
    return [("dropout_grad",
             {"Mask": op.output("Mask"), "Out@GRAD": [gout]},
             {"X@GRAD": [xname + "@GRAD"]},
             dict(op.attrs))]


@register_op("dropout", grad_maker=_dropout_grad_maker, infer_shape=_infer_same)
def dropout(ctx):
    """reference: operators/dropout_op.* — train: x*mask; test: x*(1-p)."""
    x = ctx.input("X")
    xd = raw_data(x)
    p = ctx.attr("dropout_prob", 0.5)
    if ctx.attr("is_test", False):
        ctx.set_output("Out", with_lod_of(x, xd * (1.0 - p)))
        ctx.set_output("Mask", jnp.ones_like(xd))
        return
    key = ctx.next_rng()
    mask = (jax.random.uniform(key, xd.shape) >= p).astype(xd.dtype)
    ctx.set_output("Out", with_lod_of(x, xd * mask))
    ctx.set_output("Mask", mask)


@register_op("dropout_grad")
def dropout_grad(ctx):
    mask = raw_data(ctx.input("Mask"))
    dy = raw_data(ctx.input("Out@GRAD"))
    ctx.set_output("X@GRAD", dy * mask)


# -- conv / pool ------------------------------------------------------------

def _conv_out_dim(i, k, p, s, d=1):
    ke = (k - 1) * d + 1
    return (i + 2 * p - ke) // s + 1


def _infer_conv2d(op, block):
    xv = block._find_var_recursive(op.input("Input")[0])
    fv = block._find_var_recursive(op.input("Filter")[0])
    ov = block._find_var_recursive(op.output("Output")[0])
    if None in (xv, fv, ov) or xv.shape is None or fv.shape is None:
        return
    s = op.attr("strides", [1, 1])
    p = op.attr("paddings", [0, 0])
    d = op.attr("dilations", [1, 1])
    n, _, h, w = xv.shape
    oc, _, kh, kw = fv.shape
    ov.shape = (n, oc, _conv_out_dim(h, kh, p[0], s[0], d[0]),
                _conv_out_dim(w, kw, p[1], s[1], d[1]))
    ov.dtype = xv.dtype


def conv_impl():
    """Which dense-conv lowering to use: 'conv' = lax.conv_general_dilated
    (XLA:TPU's native conv->MXU path, the default) or 'matmul' = KH*KW
    shifted einsums (the im2col+gemm role of reference
    operators/math/im2col.* + conv_op.h GemmConvKernel). The
    PADDLE_TPU_CONV_IMPL env var overrides the flag."""
    import os
    env = os.environ.get("PADDLE_TPU_CONV_IMPL")
    if env:
        return env
    from ..flags import FLAGS
    return FLAGS.conv_impl


def conv_layout():
    """Internal conv execution layout ('nchw' passthrough or 'nhwc'
    transposed). The op API contract stays NCHW either way; 'nhwc' wraps
    each conv in transposes that XLA's algebraic simplifier cancels
    between adjacent convs (elementwise ops in between are layout-moved).
    The PADDLE_TPU_CONV_LAYOUT env var overrides the flag."""
    import os
    env = os.environ.get("PADDLE_TPU_CONV_LAYOUT")
    if env:
        return env
    from ..flags import FLAGS
    return FLAGS.conv_layout


def conv_first_s2d():
    import os
    env = os.environ.get("PADDLE_TPU_CONV_S2D")
    if env is not None:
        return env not in ("0", "false", "False", "")
    from ..flags import FLAGS
    return FLAGS.conv_first_s2d


def _conv_native(x, w, s, p, d, groups, pe):
    """lax.conv in the selected internal layout (x NCHW, w OIHW in/out)."""
    if conv_layout() == "nhwc":
        out = jax.lax.conv_general_dilated(
            jnp.transpose(x, (0, 2, 3, 1)), jnp.transpose(w, (2, 3, 1, 0)),
            window_strides=tuple(s), padding=[(p[0], p[0]), (p[1], p[1])],
            rhs_dilation=tuple(d),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, preferred_element_type=pe)
        return jnp.transpose(out, (0, 3, 1, 2))
    return jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(s),
        padding=[(p[0], p[0]), (p[1], p[1])], rhs_dilation=tuple(d),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups, preferred_element_type=pe)


def _conv_stem_s2d(x, w, pe):
    """ImageNet stem conv (7x7 / stride 2 / pad 3) as space-to-depth(2) +
    4x4 / stride 1 conv — numerically exact, 4x the input channels for the
    MXU's lanes (C=3 pads to the same tile as C=12; the 7x7-on-3-channels
    stem is the classic TPU under-utilization case, public MLPerf ResNet
    technique).

    Derivation: out[h'] = sum_{ky=0..6} k[ky] * x[2h'+ky-3]. Substitute
    m = ky+1 (zero-pad the kernel to 8 taps, leading zero) and split
    m = 2a+dy: x[2(h'-2+a)+dy], i.e. the s2d plane dy sampled at h'-2+a —
    a 4-tap stride-1 conv over the s2d image with spatial padding (2,1)."""
    B, C, H, W = x.shape
    O = w.shape[0]
    xr = x.reshape(B, C, H // 2, 2, W // 2, 2)
    xs = jnp.transpose(xr, (0, 1, 3, 5, 2, 4)).reshape(
        B, C * 4, H // 2, W // 2)
    k8 = jnp.pad(w, ((0, 0), (0, 0), (1, 0), (1, 0)))
    k4 = k8.reshape(O, C, 4, 2, 4, 2)           # [o, c, ay, dy, ax, dx]
    k4 = jnp.transpose(k4, (0, 1, 3, 5, 2, 4)).reshape(O, C * 4, 4, 4)
    if conv_layout() == "nhwc":
        out = jax.lax.conv_general_dilated(
            jnp.transpose(xs, (0, 2, 3, 1)),
            jnp.transpose(k4, (2, 3, 1, 0)),
            window_strides=(1, 1), padding=[(2, 1), (2, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=pe)
        return jnp.transpose(out, (0, 3, 1, 2))
    return jax.lax.conv_general_dilated(
        xs, k4, window_strides=(1, 1), padding=[(2, 1), (2, 1)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=pe)


def _conv_shifted_matmul(x, w, s, p):
    """Convolution as KH*KW shifted einsums — each one a clean MXU matmul.
    Same FLOPs as the native conv; XLA fuses the adds. Kept selectable for
    stacks where the conv emitter underperforms dot_general."""
    B, C, H, W = x.shape
    O, _, KH, KW = w.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])))
    OH = (H + 2 * p[0] - KH) // s[0] + 1
    OW = (W + 2 * p[1] - KW) // s[1] + 1
    out = None
    for ky in range(KH):
        for kx in range(KW):
            patch = jax.lax.slice(
                xp, (0, 0, ky, kx),
                (B, C, ky + (OH - 1) * s[0] + 1, kx + (OW - 1) * s[1] + 1),
                (1, 1, s[0], s[1]))
            t = jnp.einsum("bchw,oc->bohw", patch, w[:, :, ky, kx],
                           preferred_element_type=jnp.float32)
            out = t if out is None else out + t
    return out


def _conv2d_is_s2d_stem(x, w, s, p, d, groups):
    return (conv_first_s2d() and groups == 1 and tuple(d) == (1, 1)
            and x.shape[1] <= 4 and w.shape[2:] == (7, 7)
            and tuple(s) == (2, 2) and tuple(p) == (3, 3)
            and x.shape[2] % 2 == 0 and x.shape[3] % 2 == 0)


def conv2d_apply(x, w, s, p, d, groups, pe):
    """Pure conv2d forward dispatch (layout / impl / s2d-stem aware),
    shared by the lowering below AND by explicit_grads.conv2d_grad's vjp
    replay — one definition, so the backward always runs in the same
    layout/impl the autotuner picked for the forward (and XLA can CSE the
    replayed primitive with the real forward).

    Kernel adoption routes through paddle_tpu.tune: a cached per-(device,
    shape) winner activates the pallas conv3x3 with the winning tiling; a
    miss keeps the legacy flag behavior (conv_impl=pallas3x3 runs the
    default config); no applicable kernel (or a winner that says stock
    XLA is fastest) lowers through lax.conv with a recorded
    tune_fallback."""
    if _conv2d_is_s2d_stem(x, w, s, p, d, groups):
        # the stem rewrite outranks conv_impl: the tuner times the stem
        # candidates specifically, so an enabled s2d pick must execute
        return _conv_stem_s2d(x, w, pe)
    from ..kernels.conv3x3 import conv3x3_s1_nhwc, supports_conv3x3
    from .. import tune
    if supports_conv3x3(w.shape, s, p, d, groups):
        N, C, H, W = x.shape
        cfg = tune.lookup(
            "conv3x3",
            {"n": int(N), "h": int(H), "w": int(W), "c": int(C),
             "o": int(w.shape[0]), "dtype": str(x.dtype)},
            enabled=conv_impl() == "pallas3x3")
        if cfg is not None:
            # fused im2col-matmul in VMEM (kernels/conv3x3.py); only the
            # 3x3/s1/p1 population routes here — everything else stays
            # on the native lax.conv path
            out_dt = jnp.float32 if pe == jnp.float32 else None
            out = conv3x3_s1_nhwc(jnp.transpose(x, (0, 2, 3, 1)),
                                  jnp.transpose(w, (2, 3, 1, 0)),
                                  out_dt, cfg or None)
            return jnp.transpose(out, (0, 3, 1, 2))
    else:
        tune.record_fallback("conv3x3")
    if groups == 1 and tuple(d) == (1, 1) and conv_impl() == "matmul":
        return _conv_shifted_matmul(x, w, s, p)
    return _conv_native(x, w, s, p, d, groups, pe)


@register_op("conv2d", infer_shape=_infer_conv2d)
def conv2d(ctx):
    """reference: operators/conv_op.cc + conv_cudnn_op.cu.cc. NCHW/OIHW.
    Under AMP, operands cast to bf16 with f32 accumulation (MXU-native).
    The dense common case lowers to shifted matmuls (see
    _conv_shifted_matmul); dilated/grouped convs fall back to lax.conv."""
    from .. import amp
    x = raw_data(ctx.input("Input"))
    w = raw_data(ctx.input("Filter"))
    out_dtype = x.dtype
    amp_on = getattr(ctx.block.program, "_amp", False)
    x, w = amp.cast_inputs(ctx, x, w)
    s = ctx.attr("strides", [1, 1])
    p = ctx.attr("paddings", [0, 0])
    d = ctx.attr("dilations", [1, 1])
    groups = ctx.attr("groups", 1) or 1
    # under AMP the conv stays uniformly bf16 (the conv transpose rule
    # can't mix an f32 preferred output with bf16 operands)
    pe = (jnp.float32 if (not amp_on and x.dtype in (jnp.bfloat16,))
          else None)
    out = conv2d_apply(x, w, s, p, d, groups, pe)
    out = out.astype(jnp.bfloat16 if amp.keep_bf16(ctx, out_dtype)
                     else out_dtype)
    ctx.set_output("Output", out)


@register_op("depthwise_conv2d", infer_shape=_infer_conv2d)
def depthwise_conv2d(ctx):
    ctx.op.attrs.setdefault("groups", None)
    x = raw_data(ctx.input("Input"))
    w = raw_data(ctx.input("Filter"))
    groups = ctx.attr("groups") or x.shape[1]
    s = ctx.attr("strides", [1, 1])
    p = ctx.attr("paddings", [0, 0])
    d = ctx.attr("dilations", [1, 1])
    out = _conv_native(x, w, s, p, d, groups, None)
    ctx.set_output("Output", out)


def _infer_conv2d_transpose(op, block):
    xv = block._find_var_recursive(op.input("Input")[0])
    fv = block._find_var_recursive(op.input("Filter")[0])
    ov = block._find_var_recursive(op.output("Output")[0])
    if None in (xv, fv, ov) or xv.shape is None or fv.shape is None:
        return
    s = op.attr("strides", [1, 1])
    p = op.attr("paddings", [0, 0])
    d = op.attr("dilations", [1, 1])
    n, _, h, w = xv.shape
    _, oc, kh, kw = fv.shape
    oc *= int(op.attr("groups", 1) or 1)
    ov.shape = (n, oc,
                (h - 1) * s[0] - 2 * p[0] + (kh - 1) * d[0] + 1,
                (w - 1) * s[1] - 2 * p[1] + (kw - 1) * d[1] + 1)
    ov.dtype = xv.dtype


@register_op("conv2d_transpose", infer_shape=_infer_conv2d_transpose)
def conv2d_transpose(ctx):
    """reference: operators/conv_transpose_op.cc. Filter layout IOHW
    ([deconv-input channels, num_filters, KH, KW]).

    Lowered as the gradient-of-conv formulation: dilate the input by the
    stride (lhs_dilation), pad by KH-1-p, and convolve with the spatially
    flipped filter — output size (H-1)*s - 2p + KH, the reference's deconv
    contract. (jax.lax.conv_transpose's transpose_kernel path expects the
    forward-conv kernel layout and mis-shapes under this filter layout.)"""
    x = raw_data(ctx.input("Input"))
    w = raw_data(ctx.input("Filter"))
    s = ctx.attr("strides", [1, 1])
    p = ctx.attr("paddings", [0, 0])
    d = ctx.attr("dilations", [1, 1])
    g = int(ctx.attr("groups", 1) or 1)
    kh, kw = w.shape[2], w.shape[3]
    keh = (kh - 1) * d[0] + 1  # effective (dilated) kernel extents
    kew = (kw - 1) * d[1] + 1
    out = jax.lax.conv_general_dilated(
        x, jnp.flip(_regroup_transpose_filter(w, g), (2, 3)),
        window_strides=(1, 1),
        padding=[(keh - 1 - p[0], keh - 1 - p[0]),
                 (kew - 1 - p[1], kew - 1 - p[1])],
        lhs_dilation=tuple(s),
        rhs_dilation=tuple(d),
        dimension_numbers=("NCHW", "IOHW", "NCHW"),
        feature_group_count=g)
    ctx.set_output("Output", out)


def _regroup_transpose_filter(w, groups):
    """Paddle transpose-conv filters are [C_in, F/G, k...]; lax's grouped
    conv wants [C_in/G, F, k...] with output chunks group-major —
    W_lax[i, g*(F/G)+j] = W[g*(C_in/G)+i, j]."""
    if groups in (None, 1):
        return w
    c, fg = w.shape[0], w.shape[1]
    rest = tuple(w.shape[2:])
    w = w.reshape((groups, c // groups, fg) + rest)
    w = jnp.moveaxis(w, 0, 1)
    return w.reshape((c // groups, groups * fg) + rest)


def _infer_conv3d_transpose(op, block):
    xv = block._find_var_recursive(op.input("Input")[0])
    fv = block._find_var_recursive(op.input("Filter")[0])
    ov = block._find_var_recursive(op.output("Output")[0])
    if None in (xv, fv, ov) or xv.shape is None or fv.shape is None:
        return
    s = op.attr("strides", [1, 1, 1])
    p = op.attr("paddings", [0, 0, 0])
    d = op.attr("dilations", [1, 1, 1])
    n = xv.shape[0]
    oc = fv.shape[1] * int(op.attr("groups", 1) or 1)
    spatial = tuple(
        (xv.shape[2 + i] - 1) * s[i] - 2 * p[i]
        + (fv.shape[2 + i] - 1) * d[i] + 1 for i in range(3))
    ov.shape = (n, oc) + spatial
    ov.dtype = xv.dtype


@register_op("conv3d_transpose", infer_shape=_infer_conv3d_transpose)
def conv3d_transpose(ctx):
    """reference: operators/conv_transpose_op.cc (3d registration).
    Filter layout IODHW; same gradient-of-conv formulation as
    conv2d_transpose above, one spatial dim up."""
    x = raw_data(ctx.input("Input"))
    w = raw_data(ctx.input("Filter"))
    s = ctx.attr("strides", [1, 1, 1])
    p = ctx.attr("paddings", [0, 0, 0])
    d = ctx.attr("dilations", [1, 1, 1])
    g = int(ctx.attr("groups", 1) or 1)
    ke = [(w.shape[2 + i] - 1) * d[i] + 1 for i in range(3)]
    out = jax.lax.conv_general_dilated(
        x, jnp.flip(_regroup_transpose_filter(w, g), (2, 3, 4)),
        window_strides=(1, 1, 1),
        padding=[(ke[i] - 1 - p[i], ke[i] - 1 - p[i]) for i in range(3)],
        lhs_dilation=tuple(s),
        rhs_dilation=tuple(d),
        dimension_numbers=("NCDHW", "IODHW", "NCDHW"),
        feature_group_count=g)
    ctx.set_output("Output", out)


def _infer_conv3d(op, block):
    xv = block._find_var_recursive(op.input("Input")[0])
    fv = block._find_var_recursive(op.input("Filter")[0])
    ov = block._find_var_recursive(op.output("Output")[0])
    if None in (xv, fv, ov) or xv.shape is None or fv.shape is None:
        return
    s = op.attr("strides", [1, 1, 1])
    p = op.attr("paddings", [0, 0, 0])
    d = op.attr("dilations", [1, 1, 1])
    n = xv.shape[0]
    oc = fv.shape[0]
    spatial = tuple(_conv_out_dim(xv.shape[2 + i], fv.shape[2 + i],
                                  p[i], s[i], d[i]) for i in range(3))
    ov.shape = (n, oc) + spatial
    ov.dtype = xv.dtype


@register_op("conv3d", infer_shape=_infer_conv3d)
def conv3d(ctx):
    x = raw_data(ctx.input("Input"))
    w = raw_data(ctx.input("Filter"))
    s = ctx.attr("strides", [1, 1, 1])
    p = ctx.attr("paddings", [0, 0, 0])
    d = ctx.attr("dilations", [1, 1, 1])
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(s),
        padding=[(pi, pi) for pi in p], rhs_dilation=tuple(d),
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=ctx.attr("groups", 1) or 1)
    ctx.set_output("Output", out)


def _infer_pool2d(op, block):
    xv = block._find_var_recursive(op.input("X")[0])
    ov = block._find_var_recursive(op.output("Out")[0])
    if None in (xv, ov) or xv.shape is None:
        return
    if op.attr("global_pooling", False):
        ov.shape = (xv.shape[0], xv.shape[1], 1, 1)
        ov.dtype = xv.dtype
        return
    k = op.attr("ksize")
    s = op.attr("strides", [1, 1])
    p = op.attr("paddings", [0, 0])
    ceil = op.attr("ceil_mode", False)

    def od(i, kk, pp, ss):
        num = i + 2 * pp - kk
        return (num + ss - 1) // ss + 1 if ceil else num // ss + 1

    n, c, h, w = xv.shape
    ov.shape = (n, c, od(h, k[0], p[0], s[0]), od(w, k[1], p[1], s[1]))
    ov.dtype = xv.dtype


def pool2d_apply(x, ptype, k, s, p, ceil, exclusive):
    """Pure pool2d forward shared by the lowering below AND by
    explicit_grads.pool2d_grad's jax.vjp replay — one definition, so the
    forward and the gradient can never disagree on padding/ceil semantics
    (reference: operators/pool_op.cc + math/pooling.cc)."""
    dims = (1, 1, k[0], k[1])
    strides = (1, 1, s[0], s[1])
    # ceil_mode covers the partial trailing window with extra right/bottom
    # padding: out = ceil((i+2p-k)/s)+1 (reference: math/pooling.cc; the
    # v1 img_pool_layer defaults to ceil)
    extra = [0, 0]
    if ceil:
        for a, i in ((0, x.shape[2]), (1, x.shape[3])):
            num = i + 2 * p[a] - k[a]
            out_d = (num + s[a] - 1) // s[a] + 1
            extra[a] = max((out_d - 1) * s[a] + k[a] - (i + 2 * p[a]), 0)
    pads = ((0, 0), (0, 0), (p[0], p[0] + extra[0]),
            (p[1], p[1] + extra[1]))
    if ptype == "max":
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims,
                                     strides, pads)
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides, pads)
    if exclusive and (p[0] or p[1] or any(extra)):
        ones = jnp.ones_like(x)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, dims,
                                       strides, pads)
        return summed / counts
    return summed / float(k[0] * k[1])


@register_op("pool2d", infer_shape=_infer_pool2d)
def pool2d(ctx):
    """reference: operators/pool_op.cc + math/pooling.*"""
    x = raw_data(ctx.input("X"))
    ptype = ctx.attr("pooling_type", "max")
    if ctx.attr("global_pooling", False):
        if ptype == "max":
            out = jnp.max(x, axis=(2, 3), keepdims=True)
        else:
            out = jnp.mean(x, axis=(2, 3), keepdims=True)
        ctx.set_output("Out", out)
        return
    out = pool2d_apply(x, ptype, ctx.attr("ksize"),
                       ctx.attr("strides", [1, 1]),
                       ctx.attr("paddings", [0, 0]),
                       bool(ctx.attr("ceil_mode", False)),
                       ctx.attr("exclusive", True))
    ctx.set_output("Out", out)


def _infer_pool3d(op, block):
    xv = block._find_var_recursive(op.input("X")[0])
    ov = block._find_var_recursive(op.output("Out")[0])
    if None in (xv, ov) or xv.shape is None:
        return
    if op.attr("global_pooling", False):
        ov.shape = xv.shape[:2] + (1, 1, 1)
        ov.dtype = xv.dtype
        return
    k = op.attr("ksize")
    s = op.attr("strides", [1, 1, 1])
    p = op.attr("paddings", [0, 0, 0])
    ceil = op.attr("ceil_mode", False)

    def od(i, kk, pp, ss):
        num = i + 2 * pp - kk
        return (num + ss - 1) // ss + 1 if ceil else num // ss + 1

    ov.shape = xv.shape[:2] + tuple(
        od(xv.shape[2 + i], k[i], p[i], s[i]) for i in range(3))
    ov.dtype = xv.dtype


@register_op("pool3d", infer_shape=_infer_pool3d)
def pool3d(ctx):
    x = raw_data(ctx.input("X"))
    ptype = ctx.attr("pooling_type", "max")
    if ctx.attr("global_pooling", False):
        red = jnp.max if ptype == "max" else jnp.mean
        ctx.set_output("Out", red(x, axis=(2, 3, 4), keepdims=True))
        return
    k = ctx.attr("ksize")
    s = ctx.attr("strides", [1, 1, 1])
    p = ctx.attr("paddings", [0, 0, 0])
    ceil = bool(ctx.attr("ceil_mode", False))
    dims = (1, 1) + tuple(k)
    strides = (1, 1) + tuple(s)
    extra = [0, 0, 0]
    if ceil:
        for a in range(3):
            i = x.shape[2 + a]
            num = i + 2 * p[a] - k[a]
            out_d = (num + s[a] - 1) // s[a] + 1
            extra[a] = max((out_d - 1) * s[a] + k[a] - (i + 2 * p[a]), 0)
    pads = ((0, 0), (0, 0)) + tuple(
        (p[a], p[a] + extra[a]) for a in range(3))
    if ptype == "max":
        out = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims, strides, pads)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides,
                                       pads)
        if any(p) or any(extra):
            counts = jax.lax.reduce_window(jnp.ones_like(x), 0.0,
                                           jax.lax.add, dims, strides,
                                           pads)
            out = summed / counts
        else:
            out = summed / float(prod(k))
    ctx.set_output("Out", out)


# -- normalization ----------------------------------------------------------

@register_op("batch_norm", infer_shape=_infer_same)
def batch_norm(ctx):
    """reference: operators/batch_norm_op.cc. NCHW; running stats update in
    the program (MeanOut/VarianceOut alias the persistable Mean/Variance vars,
    so the executor's state pass-through carries them across steps)."""
    x = raw_data(ctx.input("X"))
    scale = raw_data(ctx.input("Scale"))
    bias = raw_data(ctx.input("Bias"))
    mean = raw_data(ctx.input("Mean"))
    var = raw_data(ctx.input("Variance"))
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    is_test = ctx.attr("is_test", False)
    layout = ctx.attr("data_layout", "NCHW")
    axes = (0, 2, 3) if (x.ndim == 4 and layout == "NCHW") else \
           (0, 1, 2) if (x.ndim == 4) else (0,)
    cshape = [1] * x.ndim
    caxis = 1 if (x.ndim == 4 and layout == "NCHW") else x.ndim - 1
    cshape[caxis] = x.shape[caxis]

    # statistics always accumulate in >=f32: a bf16 mean over N*H*W
    # elements (pure-AMP activations) loses most of its mantissa. Only
    # the narrow dtypes are widened — f64 input stays f64 end-to-end
    xs = (x.astype(jnp.float32)
          if x.dtype in (jnp.bfloat16, jnp.float16) else x)
    if is_test:
        use_mean, use_var = mean, var
        saved_mean, saved_var = mean, var
        new_mean, new_var = mean, var
    else:
        # both sums in ONE read of x: neither takes a value from the other,
        # so XLA can carry the pair where x is made (a conv's epilogue).
        # The shift by the running mean, known before the pass, is exact
        # algebra and keeps E[d^2] - E[d]^2 from cancelling in f32 when
        # |mean| >> sigma; a shift computed from x would chain the reads.
        # The trade: the variance's relative error grows as ~1e-5 x
        # ((batch mean - running mean) / sigma)^2, the two-pass form's
        # within a sigma, 1e-3 at 10 sigma, noise past ~100 sigma until
        # the running mean has come close (a fresh layer over uncentred
        # features: |mean| x momentum^k). The mean holds everywhere
        # (tests/test_batch_norm_stats.py pins both)
        n = prod(x.shape[a] for a in axes)
        shift = mean.astype(xs.dtype)
        d = xs - shift.reshape(cshape)
        m1 = jnp.sum(d, axis=axes) / n
        bm = shift + m1
        bv = jnp.maximum(jnp.sum(d * d, axis=axes) / n - m1 * m1, 0.0)
        use_mean, use_var = bm, bv
        saved_mean = bm
        saved_var = 1.0 / jnp.sqrt(bv + eps)
        new_mean = momentum * mean + (1.0 - momentum) * bm
        new_var = momentum * var + (1.0 - momentum) * bv
    inv = 1.0 / jnp.sqrt(use_var + eps)
    y = (xs - use_mean.reshape(cshape)) * (inv * scale).reshape(cshape) \
        + bias.reshape(cshape)
    ctx.set_output("Y", y.astype(x.dtype))
    ctx.set_output("MeanOut", new_mean)
    ctx.set_output("VarianceOut", new_var)
    ctx.set_output("SavedMean", saved_mean)
    ctx.set_output("SavedVariance", saved_var)


def _bn_grad_maker(op, block, grad_of, no_grad):
    """batch_norm grad must not differentiate through the running-stat
    update; restrict the vjp to (X, Scale, Bias) -> Y."""
    g = grad_of.get(op.output("Y")[0])
    if g is None:
        return None
    inputs = {"X": list(op.input("X")), "Scale": list(op.input("Scale")),
              "Bias": list(op.input("Bias")), "Mean": list(op.input("Mean")),
              "Variance": list(op.input("Variance")),
              "Y": list(op.output("Y")), "Y@GRAD": [g]}
    outputs = {}
    diff = {}
    for slot in ("X", "Scale", "Bias"):
        n = op.input(slot)[0]
        if n not in no_grad:
            outputs[slot + "@GRAD"] = [n + "@GRAD"]
            diff[slot] = [True]
    if not outputs:
        return None
    attrs = dict(op.attrs)
    attrs["__fwd_type__"] = "batch_norm"
    attrs["__fwd_input_slots__"] = ["X", "Scale", "Bias", "Mean", "Variance"]
    attrs["__fwd_output_slots__"] = ["Y"]
    attrs["__diff_slots__"] = diff
    return [("generic_grad", inputs, outputs, attrs)]


registry.lookup("batch_norm").grad_maker = _bn_grad_maker


@register_op("layer_norm", infer_shape=_infer_same)
def layer_norm(ctx):
    x = raw_data(ctx.input("X"))
    begin = ctx.attr("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    eps = ctx.attr("epsilon", 1e-5)
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    if ctx.has_input("Scale"):
        y = y * raw_data(ctx.input("Scale")).reshape((1,) * begin + x.shape[begin:])
    if ctx.has_input("Bias"):
        y = y + raw_data(ctx.input("Bias")).reshape((1,) * begin + x.shape[begin:])
    ctx.set_output("Y", y)
    ctx.set_output("Mean", mean.reshape(x.shape[:begin] + (1,) * 0).reshape(-1))
    ctx.set_output("Variance", var.reshape(-1))


@register_op("lrn", infer_shape=_infer_same)
def lrn(ctx):
    """reference: operators/lrn_op.cc — cross-channel local response norm."""
    x = raw_data(ctx.input("X"))
    n = ctx.attr("n", 5)
    k = ctx.attr("k", 2.0)
    alpha = ctx.attr("alpha", 1e-4)
    beta = ctx.attr("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    ctx.set_output("Out", x / jnp.power(mid, beta))
    ctx.set_output("MidOut", mid)


@register_op("l2_normalize", infer_shape=_infer_same)
def l2_normalize(ctx):
    x = raw_data(ctx.input("X"))
    axis = ctx.attr("axis", 1)
    eps = ctx.attr("epsilon", 1e-12)
    ctx.set_output("Out", x / jnp.sqrt(
        jnp.maximum(jnp.sum(x * x, axis=axis, keepdims=True), eps)))


@register_op("im2sequence")
def im2sequence(ctx):
    x = raw_data(ctx.input("X"))
    k = ctx.attr("kernels")
    s = ctx.attr("strides", [1, 1])
    p = ctx.attr("paddings", [0, 0, 0, 0])
    n, c, h, w = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (p[0], p[2]), (p[1], p[3])))
    oh = (xp.shape[2] - k[0]) // s[0] + 1
    ow = (xp.shape[3] - k[1]) // s[1] + 1
    patches = jax.lax.conv_general_dilated_patches(
        xp, filter_shape=tuple(k), window_strides=tuple(s), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    # patches: [N, C*kh*kw, oh, ow] -> [N*oh*ow, C*kh*kw]
    out = patches.transpose(0, 2, 3, 1).reshape(n * oh * ow, c * k[0] * k[1])
    ctx.set_output("Out", out)


@register_op("scale_sub_region", infer_shape=_infer_same)
def scale_sub_region(ctx):
    """reference: operators/scale_sub_region_op.* / gserver
    ScaleSubRegionLayer: multiply the [c1..c2, h1..h2, w1..w2] region of
    each [C, H, W] image by ``value``; Indices is [N, 6] one-based
    inclusive (c1, c2, h1, h2, w1, w2). Branch-free: a broadcasted iota
    mask, differentiable w.r.t. X."""
    x = raw_data(ctx.input("X"))
    idx = raw_data(ctx.input("Indices")).astype(jnp.int32)
    value = ctx.attr("value", 1.0)
    n, c, h, w = x.shape
    mask = jnp.ones((n, 1, 1, 1), jnp.bool_)
    for a, dim in ((0, c), (1, h), (2, w)):
        r = jnp.arange(dim, dtype=jnp.int32)
        shape = [1, 1, 1, 1]
        shape[a + 1] = dim
        r = r.reshape(shape)
        lo = (idx[:, 2 * a] - 1).reshape(n, 1, 1, 1)
        hi = (idx[:, 2 * a + 1] - 1).reshape(n, 1, 1, 1)
        mask = mask & (r >= lo) & (r <= hi)
    out = jnp.where(mask, x * value, x)
    ctx.set_output("Out", out)

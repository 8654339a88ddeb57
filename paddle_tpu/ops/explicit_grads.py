"""Explicit grad lowerings for the hot ops.

The default backward path (ops/generic_grad.py) replays an op's forward
lowering under ``jax.vjp`` — correct for the long tail, but it traces the
forward computation *twice* (once in the step function, once inside the
vjp), doubling trace/compile time for graph-heavy models like ResNet-50
(53 convs x KH*KW einsums each). The ops here register dedicated grad ops
with closed-form lowerings, so the traced backward graph contains only the
actual gradient math — the role the reference's hand-written ``*_grad``
kernels play (reference: paddle/fluid/operators/conv_op.h GemmConvGradKernel,
mul_op.h MulGradKernel, batch_norm_op.cc BatchNormGradKernel,
activation_op.h ReluGradFunctor etc., wired via each op's GradOpDescMaker,
op_registry.h:148).

Coverage: activations (out-based), softmax, mul/matmul, elementwise add/sub/
mul, conv2d, pool2d, batch_norm, cross_entropy, softmax_with_cross_entropy,
mean, scale — the complete op set of the CNN benchmarks (ResNet/VGG/LeNet)
plus the matmul/sigmoid/tanh core of the RNN models.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import registry
from ..core.ir import grad_var_name
from ..core.executor import raw_data, with_lod_of
from ..core.registry import register_op
from .common import bcast_y_to_x, flatten_to_2d, prod


def _is_diffable(block, name, no_grad):
    from ..core.types import is_floating
    var = block._find_var_recursive(name)
    return (name not in no_grad and var is not None
            and not var.stop_gradient
            and (var.dtype is None or is_floating(var.dtype)))


def simple_grad_maker(grad_type, need_inputs=(), need_outputs=(),
                      diff_slots=("X",), out_slot="Out"):
    """Grad maker emitting one ``grad_type`` op.

    Grad-op inputs: the listed forward input slots, forward output slots,
    and ``<out_slot>@GRAD``. Outputs: ``<slot>@GRAD`` for each diff_slot
    whose var wants a gradient. Forward attrs are copied through.
    """

    def maker(op, block, grad_of, no_grad):
        g = grad_of.get(op.output(out_slot)[0]) \
            if op.output(out_slot) else None
        if g is None:
            return None
        # any *other* forward output consumed downstream needs the full
        # generic path (e.g. someone differentiates through Softmax out)
        for s, names in op.outputs.items():
            if s == out_slot:
                continue
            if any(grad_of.get(n) is not None for n in names):
                from ..core.backward import default_grad_maker
                return default_grad_maker(op, block, grad_of, no_grad)
        inputs = {s: list(op.inputs[s]) for s in need_inputs if s in op.inputs}
        for s in need_outputs:
            if s in op.outputs:
                inputs[s] = list(op.outputs[s])
        inputs[out_slot + "@GRAD"] = [g]
        outputs = {}
        for s in diff_slots:
            names = op.input(s)
            if names and _is_diffable(block, names[0], no_grad):
                outputs[s + "@GRAD"] = [grad_var_name(names[0])]
        if not outputs:
            return None
        attrs = dict(op.attrs)
        return [(grad_type, inputs, outputs, attrs)]

    return maker


def _attach(fwd_type, grad_type, **maker_kw):
    opdef = registry.lookup(fwd_type)
    if opdef is not None:
        opdef.grad_maker = simple_grad_maker(grad_type, **maker_kw)


# -- activations (gradient from the output) ----------------------------------

_ACT_GRADS = {
    # dx = dy * f'(x) expressed through out where possible
    "relu": lambda dy, out: dy * (out > 0),
    "sigmoid": lambda dy, out: dy * out * (1.0 - out),
    "tanh": lambda dy, out: dy * (1.0 - out * out),
    "exp": lambda dy, out: dy * out,
    "sqrt": lambda dy, out: dy * 0.5 / out,
    "reciprocal": lambda dy, out: -dy * out * out,
}


def _act_grad(ctx, fn):
    out = ctx.input("Out")
    dy = raw_data(ctx.input("Out@GRAD"))
    ctx.set_output("X@GRAD", with_lod_of(out, fn(dy, raw_data(out))))


for _name, _fn in _ACT_GRADS.items():
    register_op(_name + "_grad", no_gradient=True)(
        functools.partial(lambda ctx, f: _act_grad(ctx, f), f=_fn))
    _attach(_name, _name + "_grad", need_outputs=("Out",))


@register_op("softmax_grad", no_gradient=True)
def softmax_grad(ctx):
    out = raw_data(ctx.input("Out"))
    dy = raw_data(ctx.input("Out@GRAD"))
    dot = jnp.sum(dy * out, axis=-1, keepdims=True)
    ctx.set_output("X@GRAD", out * (dy - dot))


_attach("softmax", "softmax_grad", need_outputs=("Out",))


# -- mul / matmul ------------------------------------------------------------

def _maybe_bf16(ctx, *arrays):
    from .. import amp
    return amp.cast_inputs(ctx, *arrays)


@register_op("mul_grad", no_gradient=True)
def mul_grad(ctx):
    """reference: operators/mul_op.h MulGradKernel — gemms on the flattened
    2-D views; here with the same bf16 AMP policy as the forward."""
    x_v = ctx.input("X")
    x = raw_data(x_v)
    y = raw_data(ctx.input("Y"))
    dy = raw_data(ctx.input("Out@GRAD"))
    xdt, ydt = x.dtype, y.dtype
    x, y, dy = _maybe_bf16(ctx, x, y, dy)
    xn = ctx.attr("x_num_col_dims", 1)
    yn = ctx.attr("y_num_col_dims", 1)
    x2 = flatten_to_2d(x, xn)
    y2 = flatten_to_2d(y, yn)
    dy2 = dy.reshape(x2.shape[0], y2.shape[1])
    acc = jnp.float32 if x2.dtype != jnp.float64 else jnp.float64
    if ctx.op.output("X@GRAD"):
        dx = jnp.matmul(dy2, y2.T, preferred_element_type=acc)
        ctx.set_output("X@GRAD",
                       with_lod_of(x_v, dx.astype(xdt).reshape(x.shape)))
    if ctx.op.output("Y@GRAD"):
        dw = jnp.matmul(x2.T, dy2, preferred_element_type=acc)
        ctx.set_output("Y@GRAD", dw.astype(ydt).reshape(y.shape))


_attach("mul", "mul_grad", need_inputs=("X", "Y"), diff_slots=("X", "Y"))


@register_op("matmul_grad", no_gradient=True)
def matmul_grad(ctx):
    """reference: operators/matmul_op.cc grad — with transpose_X/Y attrs and
    batch-dim broadcasting (grads of broadcast operands sum over the
    broadcast leading dims)."""
    x = raw_data(ctx.input("X"))
    y = raw_data(ctx.input("Y"))
    dy = raw_data(ctx.input("Out@GRAD"))
    xdt, ydt = x.dtype, y.dtype
    x, y, dy = _maybe_bf16(ctx, x, y, dy)
    tx = ctx.attr("transpose_X", False)
    ty = ctx.attr("transpose_Y", False)
    alpha = ctx.attr("alpha", 1.0)
    if alpha != 1.0:
        dy = dy * alpha
    acc = jnp.float32 if x.dtype != jnp.float64 else jnp.float64
    sw = lambda a: jnp.swapaxes(a, -1, -2) if a.ndim > 1 else a
    xo = sw(x) if tx else x
    yo = sw(y) if ty else y
    mm = functools.partial(jnp.matmul, preferred_element_type=acc)
    dxo = mm(dy, sw(yo))            # grad wrt xo
    dyo = mm(sw(xo), dy)            # grad wrt yo
    dx = sw(dxo) if tx else dxo
    dw = sw(dyo) if ty else dyo

    def unbcast(g, shape):
        extra = g.ndim - len(shape)
        if extra > 0:
            g = jnp.sum(g, axis=tuple(range(extra)))
        for i, (gs, s) in enumerate(zip(g.shape, shape)):
            if s == 1 and gs != 1:
                g = jnp.sum(g, axis=i, keepdims=True)
        return g.reshape(shape)

    if ctx.op.output("X@GRAD"):
        ctx.set_output("X@GRAD", unbcast(dx, x.shape).astype(xdt))
    if ctx.op.output("Y@GRAD"):
        ctx.set_output("Y@GRAD", unbcast(dw, y.shape).astype(ydt))


def _matmul_grad_maker(op, block, grad_of, no_grad):
    xv = block._find_var_recursive(op.input("X")[0])
    yv = block._find_var_recursive(op.input("Y")[0])
    # 1-D operands take jnp.matmul's vector semantics; leave those to the
    # generic vjp rather than special-casing the closed form
    if (xv is None or yv is None or xv.shape is None or yv.shape is None
            or len(xv.shape) < 2 or len(yv.shape) < 2):
        from ..core.backward import default_grad_maker
        return default_grad_maker(op, block, grad_of, no_grad)
    return simple_grad_maker("matmul_grad", need_inputs=("X", "Y"),
                             diff_slots=("X", "Y"))(op, block, grad_of,
                                                    no_grad)


if registry.lookup("matmul") is not None:
    registry.lookup("matmul").grad_maker = _matmul_grad_maker


# -- elementwise -------------------------------------------------------------

def _unbcast_to(g, shape, axis):
    """Reduce ``g`` (shape of X) back to Y's ``shape`` under paddle's
    sub-sequence broadcasting at ``axis``."""
    if tuple(g.shape) == tuple(shape):
        return g
    if axis is None or axis == -1:
        axis = g.ndim - len(shape)
    yshape = list(shape)
    while yshape and yshape[-1] == 1 and len(yshape) > g.ndim - axis:
        yshape = yshape[:-1]
    red = tuple(range(axis)) + tuple(range(axis + len(yshape), g.ndim))
    g = jnp.sum(g, axis=red)
    # inner size-1 dims of y broadcast too
    for i, s in enumerate(yshape):
        if s == 1 and g.shape[i] != 1:
            g = jnp.sum(g, axis=i, keepdims=True)
    return g.reshape(shape)


@register_op("elementwise_add_grad", no_gradient=True)
def elementwise_add_grad(ctx):
    x_v = ctx.input("X")
    x = raw_data(x_v)
    y = raw_data(ctx.input("Y"))
    dy = raw_data(ctx.input("Out@GRAD"))
    axis = ctx.attr("axis", -1)
    if ctx.op.output("X@GRAD"):
        ctx.set_output("X@GRAD", with_lod_of(x_v, dy.astype(x.dtype)))
    if ctx.op.output("Y@GRAD"):
        ctx.set_output("Y@GRAD",
                       _unbcast_to(dy, y.shape, axis).astype(y.dtype))


@register_op("elementwise_sub_grad", no_gradient=True)
def elementwise_sub_grad(ctx):
    x_v = ctx.input("X")
    y = raw_data(ctx.input("Y"))
    dy = raw_data(ctx.input("Out@GRAD"))
    axis = ctx.attr("axis", -1)
    if ctx.op.output("X@GRAD"):
        ctx.set_output("X@GRAD", with_lod_of(x_v, dy))
    if ctx.op.output("Y@GRAD"):
        ctx.set_output("Y@GRAD", -_unbcast_to(dy, y.shape, axis))


@register_op("elementwise_mul_grad", no_gradient=True)
def elementwise_mul_grad(ctx):
    x_v = ctx.input("X")
    x = raw_data(x_v)
    y = raw_data(ctx.input("Y"))
    dy = raw_data(ctx.input("Out@GRAD"))
    axis = ctx.attr("axis", -1)
    yb = bcast_y_to_x(x, y, axis)
    if ctx.op.output("X@GRAD"):
        ctx.set_output("X@GRAD", with_lod_of(x_v, dy * yb))
    if ctx.op.output("Y@GRAD"):
        ctx.set_output("Y@GRAD", _unbcast_to(dy * x, y.shape, axis))


for _n in ("elementwise_add", "elementwise_sub", "elementwise_mul"):
    _attach(_n, _n + "_grad", need_inputs=("X", "Y"),
            diff_slots=("X", "Y"))


# -- conv2d ------------------------------------------------------------------

@register_op("conv2d_grad", no_gradient=True)
def conv2d_grad(ctx):
    """reference: operators/conv_op.h GemmConvGradKernel (im2col + gemm for
    both dInput and dFilter). Same per-tap matmul decomposition as the
    forward (_conv_shifted_matmul): dW as one einsum per tap, dX as one
    einsum + strided scatter-add per tap — MXU-shaped, compile-light."""
    x = raw_data(ctx.input("Input"))
    w = raw_data(ctx.input("Filter"))
    dy = raw_data(ctx.input("Output@GRAD"))
    xdt, wdt = x.dtype, w.dtype
    x, w, dy = _maybe_bf16(ctx, x, w, dy)
    s = ctx.attr("strides", [1, 1])
    p = ctx.attr("paddings", [0, 0])
    d = ctx.attr("dilations", [1, 1])
    groups = ctx.attr("groups", 1) or 1
    want_dx = bool(ctx.op.output("Input@GRAD"))
    want_dw = bool(ctx.op.output("Filter@GRAD"))
    acc = jnp.float32

    from .nn_ops import _conv2d_is_s2d_stem, conv2d_apply, conv_impl
    use_taps = (groups == 1 and tuple(d) == (1, 1)
                and conv_impl() == "matmul"
                and not _conv2d_is_s2d_stem(x, w, s, p, d, groups))
    if not use_taps:
        # replay the production forward dispatch (layout/impl/s2d as
        # autotuned) under jax.vjp: XLA's conv transpose rules emit the
        # native backprop convs in the same layout. pe stays None here
        # even though the forward lowering uses f32 accumulation for
        # bf16 operands outside AMP: lax.conv's TRANSPOSE rule rejects
        # an f32 cotangent against bf16 operands (same limitation the
        # forward's AMP comment records), so a pe-carrying replay cannot
        # be differentiated at all. The MXU still accumulates in f32
        # internally; only the replayed output's dtype differs, and the
        # primal is dead code here (vjp keeps x/w as residuals).

        def f(x_, w_):
            return conv2d_apply(x_, w_, s, p, d, groups, None)
        _, vjp = jax.vjp(f, x, w)
        dx, dw = vjp(dy.astype(x.dtype))
        if want_dx:
            ctx.set_output("Input@GRAD", dx.astype(xdt))
        if want_dw:
            ctx.set_output("Filter@GRAD", dw.astype(wdt))
        return

    B, C, H, W = x.shape
    O, _, KH, KW = w.shape
    OH, OW = dy.shape[2], dy.shape[3]
    xp = jnp.pad(x, ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])))
    dxp = jnp.zeros(xp.shape, acc) if want_dx else None
    dw_taps = []
    for ky in range(KH):
        for kx in range(KW):
            lim_h = ky + (OH - 1) * s[0] + 1
            lim_w = kx + (OW - 1) * s[1] + 1
            if want_dw:
                patch = jax.lax.slice(xp, (0, 0, ky, kx),
                                      (B, C, lim_h, lim_w),
                                      (1, 1, s[0], s[1]))
                dw_taps.append(jnp.einsum(
                    "bohw,bchw->oc", dy, patch,
                    preferred_element_type=acc))
            if want_dx:
                t = jnp.einsum("bohw,oc->bchw", dy, w[:, :, ky, kx],
                               preferred_element_type=acc)
                dxp = dxp.at[:, :, ky:lim_h:s[0], kx:lim_w:s[1]].add(t)
    if want_dw:
        dw = jnp.stack(dw_taps, axis=-1).reshape(O, C, KH, KW)
        ctx.set_output("Filter@GRAD", dw.astype(wdt))
    if want_dx:
        dx = dxp[:, :, p[0]:p[0] + H, p[1]:p[1] + W]
        ctx.set_output("Input@GRAD", dx.astype(xdt))


for _conv in ("conv2d", "depthwise_conv2d"):
    _attach(_conv, "conv2d_grad", need_inputs=("Input", "Filter"),
            diff_slots=("Input", "Filter"), out_slot="Output")


# -- pool2d ------------------------------------------------------------------

@register_op("pool2d_grad", no_gradient=True)
def pool2d_grad(ctx):
    """reference: operators/pool_op.cc grad + math/pooling.*. The vjp
    replays nn_ops.pool2d_apply — the exact function the forward lowering
    uses (incl. ceil_mode extra padding) — so forward/grad shapes cannot
    diverge; XLA lowers the reduce_window transpose to select-and-scatter
    natively."""
    x = raw_data(ctx.input("X"))
    dy = raw_data(ctx.input("Out@GRAD"))
    ptype = ctx.attr("pooling_type", "max")
    if ctx.attr("global_pooling", False):
        if ptype == "max":
            out = jnp.max(x, axis=(2, 3), keepdims=True)
            mask = (x == out).astype(x.dtype)
            mask = mask / jnp.maximum(jnp.sum(mask, axis=(2, 3),
                                              keepdims=True), 1.0)
            ctx.set_output("X@GRAD", mask * dy)
        else:
            n = x.shape[2] * x.shape[3]
            ctx.set_output("X@GRAD",
                           jnp.broadcast_to(dy / n, x.shape).astype(x.dtype))
        return
    from .nn_ops import pool2d_apply
    k = ctx.attr("ksize")
    s = ctx.attr("strides", [1, 1])
    p = ctx.attr("paddings", [0, 0])
    ceil = bool(ctx.attr("ceil_mode", False))
    exclusive = ctx.attr("exclusive", True)

    def f(x_):
        return pool2d_apply(x_, ptype, k, s, p, ceil, exclusive)

    _, vjp = jax.vjp(f, x)
    dx, = vjp(dy.astype(x.dtype))
    ctx.set_output("X@GRAD", dx)


_attach("pool2d", "pool2d_grad", need_inputs=("X",))


# -- batch_norm --------------------------------------------------------------

@register_op("batch_norm_grad", no_gradient=True)
def batch_norm_grad(ctx):
    """reference: operators/batch_norm_op.cc BatchNormGradKernel — the
    closed-form dX/dScale/dBias using the saved batch statistics."""
    x = raw_data(ctx.input("X"))
    scale = raw_data(ctx.input("Scale"))
    dy = raw_data(ctx.input("Y@GRAD"))
    eps = ctx.attr("epsilon", 1e-5)
    is_test = ctx.attr("is_test", False)
    layout = ctx.attr("data_layout", "NCHW")
    axes = (0, 2, 3) if (x.ndim == 4 and layout == "NCHW") else \
           (0, 1, 2) if (x.ndim == 4) else (0,)
    caxis = 1 if (x.ndim == 4 and layout == "NCHW") else x.ndim - 1
    cshape = [1] * x.ndim
    cshape[caxis] = x.shape[caxis]
    saved_mean = raw_data(ctx.input("SavedMean"))
    saved_var = raw_data(ctx.input("SavedVariance"))
    if is_test:
        mean, inv = saved_mean, 1.0 / jnp.sqrt(saved_var + eps)
    else:
        mean, inv = saved_mean, saved_var  # SavedVariance holds inv-std
    # both sums accumulate in >=f32 and in ONE dtype: siblings over the
    # same operands that XLA serves from one read of dy and x (a bf16 dbias
    # beside an f32 dscale takes a pass of its own, and enters dx rounded)
    if dy.dtype in (jnp.bfloat16, jnp.float16):
        dy = dy.astype(jnp.float32)
    xhat = (x - mean.reshape(cshape)) * inv.reshape(cshape)
    dscale = jnp.sum(dy * xhat, axis=axes)
    dbias = jnp.sum(dy, axis=axes)
    if ctx.op.output("Scale@GRAD"):
        ctx.set_output("Scale@GRAD", dscale.astype(scale.dtype))
    if ctx.op.output("Bias@GRAD"):
        ctx.set_output("Bias@GRAD", dbias.astype(scale.dtype))
    if ctx.op.output("X@GRAD"):
        if is_test:
            dx = dy * (scale * inv).reshape(cshape)
        else:
            n = prod(x.shape[a] for a in axes)
            dx = (scale * inv).reshape(cshape) / n * (
                n * dy - dbias.reshape(cshape) - xhat * dscale.reshape(cshape))
        ctx.set_output("X@GRAD", dx.astype(x.dtype))


def _bn_explicit_grad_maker(op, block, grad_of, no_grad):
    g = grad_of.get(op.output("Y")[0])
    if g is None:
        return None
    if not (op.output("SavedMean") and op.output("SavedVariance")):
        # saved stats not wired (bare-op program): replay under the
        # restricted vjp maker — (X, Scale, Bias) -> Y only, so the
        # running-stat update is never differentiated
        from .nn_ops import _bn_grad_maker
        return _bn_grad_maker(op, block, grad_of, no_grad)
    inputs = {"X": list(op.input("X")), "Scale": list(op.input("Scale")),
              "SavedMean": list(op.output("SavedMean")),
              "SavedVariance": list(op.output("SavedVariance")),
              "Y@GRAD": [g]}
    outputs = {}
    for slot in ("X", "Scale", "Bias"):
        n = op.input(slot)[0]
        if _is_diffable(block, n, no_grad):
            outputs[slot + "@GRAD"] = [grad_var_name(n)]
    if not outputs:
        return None
    return [("batch_norm_grad", inputs, outputs, dict(op.attrs))]


if registry.lookup("batch_norm") is not None:
    registry.lookup("batch_norm").grad_maker = _bn_explicit_grad_maker


# -- losses / reductions -----------------------------------------------------

@register_op("cross_entropy_grad", no_gradient=True)
def cross_entropy_grad(ctx):
    """reference: operators/cross_entropy_op.* grad. X holds probabilities;
    the forward clips to [1e-15, 1], so the grad masks outside that range."""
    x_v = ctx.input("X")
    x = raw_data(x_v)
    label = raw_data(ctx.input("Label"))
    dy = raw_data(ctx.input("Y@GRAD"))
    clipped = jnp.clip(x, 1e-15, 1.0)
    in_range = ((x >= 1e-15) & (x <= 1.0)).astype(x.dtype)
    if ctx.attr("soft_label", False):
        dx = -dy * label.astype(x.dtype) / clipped * in_range
    else:
        lab = label.astype(jnp.int32).reshape(label.shape[0])
        onehot = jax.nn.one_hot(lab, x.shape[-1], dtype=x.dtype)
        dx = -dy * onehot / clipped * in_range
    ctx.set_output("X@GRAD", with_lod_of(x_v, dx))


_attach("cross_entropy", "cross_entropy_grad",
        need_inputs=("X", "Label"), out_slot="Y")


@register_op("softmax_with_cross_entropy_grad", no_gradient=True)
def softmax_with_cross_entropy_grad(ctx):
    softmax = raw_data(ctx.input("Softmax"))
    label = raw_data(ctx.input("Label"))
    dy = raw_data(ctx.input("Loss@GRAD"))
    if ctx.attr("soft_label", False):
        lab = label.astype(softmax.dtype)
        dlogits = dy * (softmax * jnp.sum(lab, axis=-1, keepdims=True) - lab)
    else:
        labi = label.astype(jnp.int32).reshape(label.shape[0])
        onehot = jax.nn.one_hot(labi, softmax.shape[-1],
                                dtype=softmax.dtype)
        dlogits = dy * (softmax - onehot)
    ctx.set_output("Logits@GRAD", dlogits)


_attach("softmax_with_cross_entropy", "softmax_with_cross_entropy_grad",
        need_inputs=("Label",), need_outputs=("Softmax",), out_slot="Loss",
        diff_slots=("Logits",))


@register_op("mean_grad", no_gradient=True)
def mean_grad(ctx):
    x = raw_data(ctx.input("X"))
    dy = raw_data(ctx.input("Out@GRAD"))
    n = 1
    for s_ in x.shape:
        n *= s_
    ctx.set_output("X@GRAD",
                   jnp.broadcast_to(dy.reshape(()) / n, x.shape)
                   .astype(x.dtype))


_attach("mean", "mean_grad", need_inputs=("X",))


@register_op("scale_grad", no_gradient=True)
def scale_grad(ctx):
    dy_v = ctx.input("Out@GRAD")
    dy = raw_data(dy_v)
    ctx.set_output("X@GRAD",
                   with_lod_of(dy_v, dy * ctx.attr("scale", 1.0)))


_attach("scale", "scale_grad", need_inputs=())

"""Plain reference: ResNet-50 (He et al., arXiv:1512.03385, table 1, 50-layer
column) with its loss, gradients and a Momentum step, in straightforward
``jax.numpy``. Imports nothing of the program under test.

NCHW / OIHW, bottleneck blocks 1x1 -> 3x3 -> 1x1(x4) with the stride on the
first 1x1 (the reference repo's ``benchmark/paddle/image/resnet.py``),
batch norm with batch statistics after every convolution, 3x3/2 max pool
after the stem, global average pool, one dense layer, softmax, mean
cross entropy of ``-log(clip(p, 1e-15, 1))``.

``precision`` chooses what the matrix units are fed:

- ``"f32"``: float32 operands at ``highest`` matmul precision. THE reference.
- ``"bf16"``: what the configuration states ("pure AMP"): operands AND the
  activation stream (every convolution, batch-norm, add and dense output)
  rounded to bfloat16, the gradients that flow back along that stream
  rounded to bfloat16 too; float32 accumulation, master weights, batch-norm
  statistics and loss. Kept to tell a precision gap from a program fault.
- ``"fp8"``: the same recipe one precision down, the CONTROL that has to
  come out as not correct: operands and activation stream in float8_e4m3,
  the gradients along the stream in float8_e5m2, each under a per-tensor
  scale (amax -> the format's largest value).

Each block is rematerialised (``jax.checkpoint``) so that a float32 step
at batch 256 fits one 16 GB chip beside nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))
BN_EPS = 1e-5
_HI = jax.lax.Precision.HIGHEST


def conv_shapes(image=224, classes=1000):
    """Every convolution of the network in execution order as
    ``(c_in, c_out, k, stride, pad, h_in)`` and the dense layer as
    ``(2048, classes)``. Used by the FLOP/byte counters too."""
    convs = [(3, 64, 7, 2, 3, image)]
    h = image // 4                       # stem /2, max pool /2
    c_in = 64
    for i, (count, width) in enumerate(STAGES):
        for b in range(count):
            stride = 2 if (i > 0 and b == 0) else 1
            if b == 0:
                convs.append((c_in, width * 4, 1, stride, 0, h))   # shortcut
            convs.append((c_in, width, 1, stride, 0, h))
            h_out = h // stride
            convs.append((width, width, 3, 1, 1, h_out))
            convs.append((width, width * 4, 1, 1, 0, h_out))
            c_in, h = width * 4, h_out
    return convs, (c_in, classes)


def leaf_shapes(image=224, classes=1000):
    """Trainable leaves in the order the network creates them: per
    convolution its filter, then the batch norm's scale and bias; last the
    dense layer's weight ``[2048, classes]`` and bias."""
    convs, (c, k) = conv_shapes(image, classes)
    shapes = []
    for (ci, co, ks, _s, _p, _h) in convs:
        shapes += [(co, ci, ks, ks), (co,), (co,)]
    return shapes + [(c, k), (k,)]


def key_data(seed):
    """A threefry key from any whole-number seed (also above 2**31)."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    dtype=np.uint32)


def _last_of_block():
    """Indices (into the convolutions) of each block's last convolution."""
    last, ci = set(), 1
    for count, _width in STAGES:
        for b in range(count):
            ci += 4 if b == 0 else 3
            last.add(ci - 1)
    return last


@functools.partial(jax.jit, static_argnames=("image", "classes"))
def init_leaves(key_words, image=224, classes=1000):
    """All trainable leaves from the seed in ONE jitted call, float32:
    filters N(0, 2/fan_in); batch-norm scale 1 + 0.1 N, but 0.25 (1 + 0.1 N)
    on each block's last batch norm (the usual small-gamma start of the
    residual branch: with 1 everywhere the gradient grows a thousandfold
    from the head to the stem and any rounding is amplified to tens of
    percent there); batch-norm bias 0.1 N; dense weight N(0, 1/fan_in),
    dense bias 0.01 N."""
    key = jax.random.wrap_key_data(key_words)
    shapes = leaf_shapes(image, classes)
    last = _last_of_block()
    leaves = []
    for i, shape in enumerate(shapes):
        n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if len(shape) == 4:
            leaves.append(n * np.sqrt(2.0 / (shape[1] * shape[2] * shape[3])))
        elif len(shape) == 2:
            leaves.append(n * np.sqrt(1.0 / shape[0]))
        elif i == len(shapes) - 1:
            leaves.append(0.01 * n)
        elif i % 3 == 1:
            gamma = 0.25 if (i // 3) in last else 1.0
            leaves.append(gamma * (1.0 + 0.1 * n))
        else:
            leaves.append(0.1 * n)
    return leaves


_FORMATS = {"bf16": (jnp.bfloat16, None, jnp.bfloat16, None),
            "fp8": (jnp.float8_e4m3fn, 448.0, jnp.float8_e5m2, 57344.0)}


def _round(a, dtype, largest):
    """``a`` rounded to ``dtype`` and back; under a per-tensor scale that
    puts the largest magnitude at ``largest`` where the format needs one."""
    if largest is None:
        return a.astype(dtype).astype(jnp.float32)
    scale = largest / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(dtype).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _stream(a, precision):
    """One point of the activation stream: the value rounded to the forward
    format on the way up, its gradient to the backward format on the way
    down."""
    fwd, fmax, _bwd, _bmax = _FORMATS[precision]
    return _round(a, fwd, fmax)


def _stream_fwd(a, precision):
    return _stream(a, precision), None


def _stream_bwd(precision, _res, g):
    _fwd, _fmax, bwd, bmax = _FORMATS[precision]
    return (_round(g, bwd, bmax),)


_stream.defvjp(_stream_fwd, _stream_bwd)


def _at(a, precision):
    if precision == "f32":
        return a
    if precision not in _FORMATS:
        raise ValueError("unknown precision %r" % (precision,))
    return _stream(a, precision)


def _operands(x, w, precision):
    """What the matrix unit is fed: the activation is a point of the stream
    already; the master weight is rounded here, its gradient kept whole."""
    if precision == "f32":
        return x, w
    fwd, fmax, _bwd, _bmax = _FORMATS[precision]
    return x, w + jax.lax.stop_gradient(_round(w, fwd, fmax) - w)


def _conv(x, w, stride, pad, precision):
    x, w = _operands(x, w, precision)
    return _at(jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=_HI,
        preferred_element_type=jnp.float32), precision)


def _bn(x, scale, bias):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    return ((x - mean) / jnp.sqrt(var + BN_EPS) * scale.reshape(1, -1, 1, 1)
            + bias.reshape(1, -1, 1, 1))


def _conv_bn(x, leaves, spec, precision, relu=True):
    w, scale, bias = leaves
    _ci, _co, _k, stride, pad, _h = spec
    y = _bn(_conv(x, w, stride, pad, precision), scale, bias)
    return _at(jnp.maximum(y, 0.0) if relu else y, precision)


def _block(x, leaves, specs, precision):
    """One bottleneck; ``leaves``/``specs`` hold the shortcut first when
    the block has one."""
    short = x
    if len(specs) == 4:
        short = _conv_bn(x, leaves[0:3], specs[0], precision, relu=False)
        leaves, specs = leaves[3:], specs[1:]
    y = _conv_bn(x, leaves[0:3], specs[0], precision)
    y = _conv_bn(y, leaves[3:6], specs[1], precision)
    y = _conv_bn(y, leaves[6:9], specs[2], precision, relu=False)
    return _at(jnp.maximum(short + y, 0.0), precision)


def loss_fn(leaves, images, labels, precision="f32"):
    """Mean cross entropy of the batch. ``images`` [N,3,H,W] float32,
    ``labels`` [N] int."""
    convs, _fc = conv_shapes(images.shape[2], leaves[-1].shape[0])
    x = _conv_bn(_at(images, precision), leaves[0:3], convs[0], precision)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))
    at, ci = 3, 1
    for _i, (count, _width) in enumerate(STAGES):
        for b in range(count):
            n = 4 if b == 0 else 3
            blk = jax.checkpoint(
                functools.partial(_block, specs=tuple(convs[ci:ci + n]),
                                  precision=precision))
            x = blk(x, leaves[at:at + 3 * n])
            at, ci = at + 3 * n, ci + n
    pooled = _at(jnp.mean(x, axis=(2, 3)), precision)
    a, w = _operands(pooled, leaves[-2], precision)
    logits = _at(_at(jnp.matmul(a, w, precision=_HI,
                                preferred_element_type=jnp.float32),
                     precision) + leaves[-1], precision)
    prob = jax.nn.softmax(logits, axis=-1)
    picked = jnp.take_along_axis(prob, labels.astype(jnp.int32)[:, None],
                                 axis=-1)
    return jnp.mean(-jnp.log(jnp.clip(picked, 1e-15, 1.0)))


def _norms(leaves):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in leaves])


@functools.partial(jax.jit, static_argnames=("precision",),
                   donate_argnums=(0, 1))
def momentum_step(leaves, velocity, images, labels, lr, mu, precision="f32"):
    """One step of plain Momentum (v = mu v + g; p = p - lr v). Returns
    the new leaves and velocity, the loss, and the per-leaf norm of the
    gradient."""
    loss, grads = jax.value_and_grad(loss_fn)(leaves, images, labels,
                                              precision)
    velocity = [mu * v + g for v, g in zip(velocity, grads)]
    leaves = [p - lr * v for p, v in zip(leaves, velocity)]
    return leaves, velocity, loss, _norms(grads)


@jax.jit
def delta_norms(leaves, start):
    return _norms([a - b for a, b in zip(leaves, start)])


def follow(seed, batches, lr, mu, precision="f32", image=224, classes=1000,
           rows=None):
    """Follow the first ``len(batches)`` steps from the seed's weights.
    ``batches`` is a list of (images [N,3,H,W] float32, labels [N]) host
    arrays. ``rows`` keeps only the first ``rows`` of each batch (the
    planted fault "half of the batch left out"). Returns host values:
    ``losses`` per step, ``grad_norms`` per leaf of the first gradient,
    ``delta_norms`` per leaf of the parameters' change after all steps."""
    start = init_leaves(key_data(seed), image=image, classes=classes)
    leaves = [jnp.array(l) for l in start]          # donated below
    velocity = [jnp.zeros_like(l) for l in leaves]
    losses, grad_norms = [], None
    for images, labels in batches:
        if rows is not None:
            images, labels = images[:rows], labels[:rows]
        leaves, velocity, loss, gn = momentum_step(
            leaves, velocity, jnp.asarray(images), jnp.asarray(labels),
            jnp.float32(lr), jnp.float32(mu), precision=precision)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = np.asarray(gn, dtype=np.float64)
    out = {"losses": losses, "grad_norms": grad_norms,
           "delta_norms": np.asarray(delta_norms(leaves, start),
                                     dtype=np.float64)}
    del leaves, velocity, start
    return out

"""batch_norm / batch_norm_grad read the activation once for their
statistics: every sum is taken over the raw tensor(s) and none takes a value
from another. Numerics against a float64 two-pass NumPy reference for
{f32, bf16} x {NCHW, NHWC, 2-D}; the shift by the running mean keeps f32
honest where |mean| >> sigma; a structure test pins, on the lowered text of
an Executor step, the property that lets one pass serve on the chip.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import amp, layers
from paddle_tpu.core import executor as executor_mod

EPS = 1e-5
MOMENTUM = 0.9
LAYOUTS = {"NCHW": ((6, 5, 7, 7), (0, 2, 3), 1),
           "NHWC": ((6, 7, 7, 5), (0, 1, 2), 3),
           "2D": ((48, 5), (0,), 1)}
DTYPES = {"f32": np.float32, "bf16": ml_dtypes.bfloat16}
# bf16 rounds Y and X@GRAD to 8 bits; the statistics stay f32 either way
TOL = {"f32": dict(rtol=2e-5, atol=2e-5), "bf16": dict(rtol=2e-2, atol=2e-2)}
STAT_TOL = dict(rtol=2e-5, atol=2e-6)


def run_op(op_type, inputs, outputs, attrs):
    """One op in a program of its own on the CPU: {slot: array} in,
    {slot: array} out."""
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        block = prog.global_block()
        for slot, a in inputs.items():
            block.create_var(name=slot, shape=a.shape, dtype=str(a.dtype))
        for slot in outputs:
            block.create_var(name=slot)
        block.append_op(type=op_type, inputs={s: [s] for s in inputs},
                        outputs={s: [s] for s in outputs}, attrs=attrs)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        got = exe.run(prog, feed=dict(inputs), fetch_list=list(outputs))
    return dict(zip(outputs, got))


def make_inputs(layout, dtype, seed=0, offset=0.0):
    shape, _axes, caxis = LAYOUTS[layout]
    rs = np.random.RandomState(seed)
    c = shape[caxis]
    x = (rs.randn(*shape) + offset).astype(DTYPES[dtype])
    return {"X": x,
            "Scale": (rs.rand(c) + 0.5).astype(np.float32),
            "Bias": rs.randn(c).astype(np.float32),
            "Mean": (rs.randn(c) * 0.1).astype(np.float32),
            "Variance": (rs.rand(c) + 0.5).astype(np.float32)}


def bshape(layout, x):
    caxis = LAYOUTS[layout][2]
    return [x.shape[caxis] if i == caxis else 1 for i in range(x.ndim)]


def reference_forward(inp, layout):
    """float64, two passes: the mean, then the centred squares."""
    _shape, axes, _c = LAYOUTS[layout]
    bs = bshape(layout, inp["X"])
    x = inp["X"].astype(np.float64)
    mean = x.mean(axis=axes)
    var = np.square(x - mean.reshape(bs)).mean(axis=axes)
    inv = 1.0 / np.sqrt(var + EPS)
    y = (x - mean.reshape(bs)) * (inv * inp["Scale"]).reshape(bs) \
        + inp["Bias"].reshape(bs)
    return {"Y": y, "SavedMean": mean, "SavedVariance": inv,
            "MeanOut": MOMENTUM * inp["Mean"] + (1 - MOMENTUM) * mean,
            "VarianceOut": MOMENTUM * inp["Variance"] + (1 - MOMENTUM) * var,
            "var": var}


def reference_backward(x, scale, dy, layout):
    _shape, axes, _c = LAYOUTS[layout]
    bs = bshape(layout, x)
    x, dy = x.astype(np.float64), dy.astype(np.float64)
    n = x.size // x.shape[LAYOUTS[layout][2]]
    mean = x.mean(axis=axes)
    inv = 1.0 / np.sqrt(np.square(x - mean.reshape(bs)).mean(axis=axes) + EPS)
    xhat = (x - mean.reshape(bs)) * inv.reshape(bs)
    dscale = (dy * xhat).sum(axis=axes)
    dbias = dy.sum(axis=axes)
    dx = (scale * inv).reshape(bs) / n * (
        n * dy - dbias.reshape(bs) - xhat * dscale.reshape(bs))
    return {"X@GRAD": dx, "Scale@GRAD": dscale, "Bias@GRAD": dbias,
            "mean": mean, "inv": inv}


FORWARD_OUTS = ("Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance")
BACKWARD_OUTS = ("X@GRAD", "Scale@GRAD", "Bias@GRAD")


def train_attrs(layout, is_test=False):
    return {"is_test": is_test, "epsilon": EPS, "momentum": MOMENTUM,
            "data_layout": "NHWC" if layout == "NHWC" else "NCHW"}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("out", FORWARD_OUTS)
def test_forward_against_float64_two_pass(out, dtype, layout):
    inp = make_inputs(layout, dtype, seed=3, offset=0.7)
    got = run_op("batch_norm", inp, FORWARD_OUTS, train_attrs(layout))[out]
    want = reference_forward(inp, layout)[out]
    assert got.dtype == (inp["X"].dtype if out == "Y" else np.float32)
    assert got.shape == want.shape
    np.testing.assert_allclose(got.astype(np.float64), want,
                               **(TOL[dtype] if out == "Y" else STAT_TOL))


def backward_inputs(layout, dtype, seed=5):
    inp = make_inputs(layout, dtype, seed=seed, offset=-0.4)
    dy = np.random.RandomState(seed + 1).randn(
        *inp["X"].shape).astype(DTYPES[dtype])
    want = reference_backward(inp["X"], inp["Scale"], dy, layout)
    fed = {"X": inp["X"], "Scale": inp["Scale"],
           "SavedMean": want["mean"].astype(np.float32),
           "SavedVariance": want["inv"].astype(np.float32), "Y@GRAD": dy}
    return fed, want


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("out", BACKWARD_OUTS)
def test_backward_against_float64(out, dtype, layout):
    fed, want = backward_inputs(layout, dtype)
    got = run_op("batch_norm_grad", fed, BACKWARD_OUTS,
                 train_attrs(layout))[out]
    assert got.dtype == (fed["X"].dtype if out == "X@GRAD" else np.float32)
    np.testing.assert_allclose(
        got.astype(np.float64), want[out],
        **(TOL[dtype] if out == "X@GRAD" else dict(rtol=1e-4, atol=1e-4)))


# The contract of the one-pass variance: its relative error grows with the
# square of the distance, in sigmas, between the channel's mean and the
# shift (the running mean): ~1e-5 x (distance / sigma)^2 on XLA:CPU over
# 50,176 f32 terms. Within a sigma it is the two-pass form's; a fresh layer
# (running mean 0) over a channel 10 sigma from zero reads 1e-3 to 2e-3 off,
# and beyond ~100 sigma the variance is noise until the running mean has
# come close (momentum 0.9: |mean| x 0.9^k). The mean itself holds
# everywhere. The last rows pin that trade: who needs more feeds centred
# data or starts ``moving_mean`` near the data's mean.
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("offset_sigmas,running_off,var_rtol", [
    (30.0, 1.0, 1e-3),  # far from zero, the running mean within a sigma
    (-30.0, -1.0, 1e-3),
    (2.0, None, 1e-3),  # a fresh layer: the running mean still at zero
    (0.5, None, 1e-3),
    (10.0, None, 5e-3),     # a fresh layer far from zero: the cold start
    (-10.0, None, 5e-3),
])
def test_variance_holds_where_the_mean_dwarfs_sigma(offset_sigmas,
                                                    running_off, var_rtol,
                                                    dtype):
    sigma = 0.5
    shape = (64, 32, 28, 28)
    rs = np.random.RandomState(11)
    x = (rs.randn(*shape) * sigma + offset_sigmas * sigma).astype(
        DTYPES[dtype])
    c = shape[1]
    true_mean = x.astype(np.float64).mean(axis=(0, 2, 3))
    running = (np.zeros(c) if running_off is None
               else true_mean + running_off * sigma).astype(np.float32)
    inp = {"X": x, "Scale": np.ones(c, np.float32),
           "Bias": np.zeros(c, np.float32), "Mean": running,
           "Variance": np.ones(c, np.float32)}
    got = run_op("batch_norm", inp, FORWARD_OUTS, train_attrs("NCHW"))
    want = reference_forward(inp, "NCHW")
    var = 1.0 / np.square(got["SavedVariance"].astype(np.float64)) - EPS
    np.testing.assert_allclose(var, want["var"], rtol=var_rtol)
    # a thousandth of a sigma: XLA:CPU adds 50,176 f32 terms in a row
    np.testing.assert_allclose(got["SavedMean"], want["SavedMean"],
                               rtol=0, atol=1e-3 * sigma)


def test_bias_grad_of_a_bf16_dy_is_the_f32_sum():
    fed, _want = backward_inputs("NCHW", "bf16", seed=21)
    dy = fed["Y@GRAD"]
    got = run_op("batch_norm_grad", fed, BACKWARD_OUTS,
                 train_attrs("NCHW"))["Bias@GRAD"]
    f32_sum = np.asarray(jnp.sum(jnp.asarray(dy).astype(jnp.float32),
                                 axis=(0, 2, 3)))
    bf16_sum = np.asarray(jnp.sum(jnp.asarray(dy), axis=(0, 2, 3))).astype(
        np.float32)
    assert not np.array_equal(f32_sum, bf16_sum)     # the case can tell
    np.testing.assert_allclose(got, f32_sum, rtol=1e-6, atol=1e-6)
    exact = dy.astype(np.float64).sum(axis=(0, 2, 3))
    assert np.abs(got - exact).max() < np.abs(bf16_sum - exact).max()


# what the parent's lowering (``1 / sqrt(var + eps)`` over the running
# statistics, nothing reduced) gives for these inputs: the inference branch
# is not this change's to move, to the bit
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_is_test_outputs_are_bit_equal_to_the_inference_formula(dtype,
                                                                layout):
    inp = make_inputs(layout, dtype, seed=8)
    got = run_op("batch_norm", inp, FORWARD_OUTS,
                 train_attrs(layout, is_test=True))
    bs = bshape(layout, inp["X"])

    @jax.jit
    def infer(x, scale, bias, mean, var):
        inv = 1.0 / jnp.sqrt(var + EPS)
        y = (x.astype(jnp.float32) - mean.reshape(bs)) \
            * (inv * scale).reshape(bs) + bias.reshape(bs)
        return y.astype(x.dtype)

    want = infer(*(inp[k] for k in ("X", "Scale", "Bias", "Mean",
                                    "Variance")))
    assert np.array_equal(got["Y"], np.asarray(want))
    for out, slot in (("MeanOut", "Mean"), ("SavedMean", "Mean"),
                      ("VarianceOut", "Variance"),
                      ("SavedVariance", "Variance")):
        assert np.array_equal(got[out], inp[slot])


def conv_bn_program(saved_stats, pure_amp=False):
    """conv2d -> batch_norm -> relu -> mean(. * W) with its backward;
    without ``saved_stats`` the batch norm is a bare op whose saved
    statistics are not wired, which the generic-vjp replay differentiates."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = layers.data("img", shape=[3, 8, 8], dtype="float32")
        w = layers.data("w", shape=[4, 8, 8], dtype="float32")
        conv = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                             bias_attr=False)
        if saved_stats:
            bn = layers.batch_norm(conv)
        else:
            helper_inputs = {}
            block = main.global_block()
            for slot, init in (("Scale", 1.0), ("Bias", 0.0), ("Mean", 0.0),
                               ("Variance", 1.0)):
                helper_inputs[slot] = layers.create_parameter(
                    [4], "float32", name="bare_bn_" + slot.lower(),
                    default_initializer=fluid.initializer.Constant(init))
            for slot in ("Mean", "Variance"):
                helper_inputs[slot].stop_gradient = True
            bn = block.create_var(name="bare_bn_y", shape=conv.shape,
                                  dtype=conv.dtype)
            block.append_op(
                type="batch_norm",
                inputs={"X": [conv.name],
                        **{s: [v.name] for s, v in helper_inputs.items()}},
                outputs={"Y": [bn.name],
                         "MeanOut": [helper_inputs["Mean"].name],
                         "VarianceOut": [helper_inputs["Variance"].name]},
                attrs={"is_test": False, "epsilon": EPS,
                       "momentum": MOMENTUM})
        loss = layers.mean(layers.elementwise_mul(layers.relu(bn), w))
        if pure_amp:
            amp.enable(main, pure=True)
        fluid.SGD(learning_rate=0.0).minimize(loss)
    return main, startup, loss, conv


def grads_of(main, startup, loss, names, seed=0):
    rs = np.random.RandomState(seed)
    feed = {"img": rs.randn(6, 3, 8, 8).astype(np.float32),
            "w": rs.randn(6, 4, 8, 8).astype(np.float32)}
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        conv_w = [p.name for p in main.all_parameters()
                  if len(p.shape) == 4][0]
        scope = fluid.global_scope()
        scope.set_var(conv_w, np.random.RandomState(1).randn(
            *scope.find_var(conv_w).shape).astype(np.float32) * 0.3)
        return exe.run(main, feed=feed,
                       fetch_list=[loss.name] + list(names))


def test_generic_vjp_replay_agrees_with_the_explicit_grad():
    explicit = conv_bn_program(saved_stats=True)
    replay = conv_bn_program(saved_stats=False)
    kinds = [{op.type for op in p[0].global_block().ops}
             for p in (explicit, replay)]
    assert "batch_norm_grad" in kinds[0] and "generic_grad" not in kinds[0]
    assert "generic_grad" in kinds[1] and "batch_norm_grad" not in kinds[1]
    a = grads_of(*explicit[:3], names=[explicit[3].name + "@GRAD"])
    b = grads_of(*replay[:3], names=[replay[3].name + "@GRAD"])
    np.testing.assert_allclose(a[0], b[0], rtol=1e-6)
    assert np.abs(a[1]).max() > 1e-4
    np.testing.assert_allclose(a[1], b[1], rtol=2e-4, atol=1e-7)


# -- structure: one read can serve --------------------------------------------

_DEF = re.compile(r"^\s*(%[\w.\-]+)(?::\d+)?\s*=\s*(.*)$")
_VALUE = re.compile(r"%[\w.\-]+")
_FUNC = re.compile(r"func\.func\s+(?:\w+\s+)?@([\w.\-]+)\((.*)$")
_LOC_REF = re.compile(r"loc\((#loc\d*)\)\s*$")
_LOC_DEF = re.compile(r"^(#loc\d*) = loc\((.*)\)\s*$")


def _rank4_reduce(rest):
    """Result element types of a ``stablehlo.reduce`` over rank-4
    operand(s), else None."""
    if not rest.startswith("stablehlo.reduce("):
        return None
    sig = rest.rsplit(" : ", 1)[1]
    ins, outs = sig.split(" -> ")
    first = re.search(r"tensor<([^>]*)>", ins).group(1).split("x")
    if len(first) - 1 != 4:
        return None
    return [t.split("x")[-1] for t in re.findall(r"tensor<([^>]*)>", outs)]


def activation_reduces(text, scope=""):
    """Reads the lowered (StableHLO) text of a step. Of the reductions over
    a rank-4 tensor whose location names ``scope``: ([result dtypes] of
    each, [descriptions of those that take a value computed from another
    of them]). Calls are followed through a per-function summary: which
    parameters a function reduces, and whether its results carry a
    reduction's value."""
    locs = dict(m.groups() for m in map(_LOC_DEF.match, text.splitlines())
                if m)

    def scope_of(line):
        m = _LOC_REF.search(line)
        seen, name = set(), (m.group(1) if m else "")
        while name in locs and name not in seen:    # named locs can nest
            seen.add(name)
            inner = re.match(r'"([^"]*)"', locs[name])
            if inner:
                return inner.group(1)
            nxt = re.search(r"#loc\d*", locs[name])
            name = nxt.group(0) if nxt else ""
        return ""

    funcs, current = {}, None
    for line in text.splitlines():
        m = _FUNC.search(line)
        if m:
            current = funcs.setdefault(m.group(1), {
                "params": re.findall(r"(%arg\d+):", m.group(2)), "body": []})
        elif current is not None:
            current["body"].append(line)

    summaries = {}

    def analyse(name):
        """{"reduces": param indices whose value reaches a rank-4
        reduction, "taints": a result carries a reduction's value}; as a
        side effect records the function's own reductions and faults."""
        if name in summaries:
            return summaries[name]
        summaries[name] = {"reduces": set(), "taints": False}   # recursion
        f = funcs[name]
        origin = {p: {i} for i, p in enumerate(f["params"])}
        tainted, reduces, taints = set(), set(), False
        for line in f["body"]:
            m = _DEF.match(line)
            if m is None:
                if line.strip().startswith(("return", "func.return",
                                            "stablehlo.return")):
                    taints |= any(v in tainted
                                  for v in _VALUE.findall(line))
                continue
            res, rest = m.groups()
            used = _VALUE.findall(rest.split(" : ")[0])
            from_params = set().union(*(origin.get(v, set()) for v in used))
            carries = any(v in tainted for v in used)
            callee = re.search(r"call @([\w.\-]+)\(", rest)
            dtypes = _rank4_reduce(rest)
            if dtypes is not None and scope not in scope_of(line):
                dtypes = None
            if dtypes is not None:
                found.append(dtypes)
                if carries:
                    faults.append("%s in @%s: %s" % (res, name,
                                                     rest[:120]))
                reduces |= from_params
                carries = True
            elif callee and callee.group(1) in funcs:
                s = analyse(callee.group(1))
                args = _VALUE.findall(rest.split("(", 1)[1].split(")")[0])
                for i in s["reduces"]:
                    if i < len(args):
                        if args[i] in tainted:
                            faults.append("%s in @%s: call @%s reduces a "
                                          "reduction's value"
                                          % (res, name, callee.group(1)))
                        reduces |= origin.get(args[i], set())
                carries = carries or s["taints"]
            if carries:
                tainted.add(res)
            origin[res] = from_params
        summaries[name] = {"reduces": reduces, "taints": taints}
        return summaries[name]

    found, faults = [], []
    for name in funcs:
        analyse(name)
    return found, faults


@pytest.fixture
def lowered_step_text():
    """The lowered text of the Executor's step for conv2d -> batch_norm ->
    relu -> mean with its backward, activations in bf16 (pure AMP, pinned on
    for the CPU) as the chip runs them."""
    prev = amp.force(True)
    executor_mod.clear_warm_cache()
    try:
        main, startup, loss, _conv = conv_bn_program(saved_stats=True,
                                                     pure_amp=True)
        grads_of(main, startup, loss, names=[])
        texts = [s.fn.lower(*s._avals).as_text(debug_info=True)
                 for s in executor_mod.compiled_steps()]
        text, = [t for t in texts if "forward/batch_norm" in t]
        yield text
    finally:
        amp.force(prev)
        executor_mod.clear_warm_cache()


def test_no_statistic_reduction_waits_for_another(lowered_step_text):
    # forward: sum(d), sum(d * d); backward: dbias, dscale (which takes the
    # forward's saved mean, known by then, and nothing of its sibling)
    for scope in ("forward/batch_norm", "backward/batch_norm_grad"):
        found, faults = activation_reduces(lowered_step_text, scope)
        assert sum(map(len, found)) == 2, (scope, found)
        assert faults == [], scope
        assert {t for d in found for t in d} == {"f32"}, scope


def test_the_structure_reader_sees_a_chained_reduction():
    """The reader's own check: mean-then-centred-squares, direct and
    through a call, is reported; two sibling sums are not."""
    def chained(x):
        m = jnp.sum(x, axis=(0, 2, 3)) / x[:, 0].size
        return jnp.sum(jnp.square(x - m.reshape(1, -1, 1, 1)),
                       axis=(0, 2, 3))

    def siblings(x):
        return jnp.sum(x, axis=(0, 2, 3)), jnp.sum(x * x, axis=(0, 2, 3))

    x = jax.ShapeDtypeStruct((4, 3, 5, 5), jnp.float32)
    for fn, n_faults in ((chained, 1), (lambda x: jnp.var(x, axis=(0, 2, 3)),
                                        1), (siblings, 0)):
        text = jax.jit(fn).lower(x).as_text(debug_info=True)
        found, faults = activation_reduces(text)
        assert len(found) == 2, text
        assert len(faults) == n_faults, (faults, text)


# tools/scope_table.py over the slice of the parent's chip trace that the
# repo keeps: the stand-alone statistic passes this file's property removes,
# by count, as PERF.md's section 5 gives them (no chip needed to read it)
@pytest.mark.parametrize("scope,kind,count", [
    ("forward/batch_norm", r"fusion f32\[\d+\]\S* kLoop", 53),
    ("backward/batch_norm_grad", r"reduce bf16\[\d+\]\S*", 47),
    ("backward/batch_norm_grad", r"fusion (f32|bf16)\[\d+\]\S* kLoop", 4),
])
def test_scope_table_counts_the_recorded_statistic_passes(scope, kind,
                                                          count, capsys):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "scope_table", os.path.join(root, "tools", "scope_table.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--recorded", os.path.join(
        root, "chipbench", "testdata", "train_program_trace.json")]) == 0
    out = capsys.readouterr().out
    inside = out.split("inside %s:\n" % scope)[1]
    inside = re.split(r"^(?:inside |the 12 longest)", inside, flags=re.M)[0]
    found = sum(int(m.group(1)) for m in re.finditer(
        r"^\s*(\d+) x %s\s+[\d.]+ ms$" % kind, inside, flags=re.M))
    assert found == count

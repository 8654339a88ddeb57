"""``models.latent_moe_lm`` (latent attention, a sigmoid top-k router with a
selection bias and shared experts, an expert layer that is told which
experts it holds) against the plain reference
``chipbench/reference/latent_moe_lm.py`` on seeded weights, CPU, float32,
at a small size; whole and as one chip's share of an expert-parallel
group."""
import collections
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu.models.latent_moe_lm import latent_moe_lm
from paddle_tpu.ops import decoder_ops

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chipbench", "reference", "latent_moe_lm.py")
_spec = importlib.util.spec_from_file_location("ref_latent_moe_lm", _REF)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

SEQ, ROWS = 32, 2
HALVES = ("latent_attention", "gated_ffn", "moe_ffn")
OPT = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}


def tiny(share):
    """d 64, 4 heads of 24 + 8 / 16, 8 experts top-2, 2 dense + 1 expert
    layers, vocabulary 256; ``share``: 4 experts and 64 rows held, neither
    range starting at 0."""
    config = dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        qk_nope_head_dim=24, qk_rope_head_dim=8, qk_head_dim=32,
        v_head_dim=16, kv_lora_rank=32, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
        n_shared_experts=2, routed_scaling_factor=2.448,
        first_k_dense_replace=2, num_hidden_layers=3, rms_norm_eps=1e-6,
        rope_theta=1e6, vocab_size=256, q_lora_rank=None, n_group=1,
        topk_group=1, scoring_func="sigmoid", rope_scaling=None,
        topk_method="noaux_tc", norm_topk_prob=True)
    if share:
        config.update(experts_held=[4, 4], vocab_held=[64, 64])
    return config


def batches(config, seed, n):
    first, count = ref.held(config, "vocab_held", config["vocab_size"])
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        ids = rng.integers(first, first + count, (ROWS, SEQ + 1),
                           dtype=np.int64)
        out.append((ids[:, :-1], ids[:, 1:]))
    return out


def program(config, seed, remat=True, amp=False):
    """(exe, main, scope, loss, names, model outputs): the model with Adam,
    the reference's seeded leaves and router biases in the scope."""
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        tokens = L.data("tokens", shape=[SEQ], dtype="int64")
        labels = L.data("labels", shape=[SEQ], dtype="int64")
        out = latent_moe_lm(tokens, config, labels=labels)
        if amp:
            pt.amp.enable(main, pure=True)
        pt.optimizer.AdamOptimizer(
            learning_rate=OPT["learning_rate"], beta1=OPT["beta1"],
            beta2=OPT["beta2"], epsilon=OPT["epsilon"]).minimize(out["loss"])
        if remat:
            pt.memory_optimize(main, remat_types=HALVES)
    exe = pt.Executor(pt.CPUPlace())
    specs = ref.leaf_specs(config)
    words = ref.key_data(seed)
    with pt.scope_guard(scope):
        exe.run(startup)
        trainable = [p.name for p in main.all_parameters() if p.trainable]
        assert trainable == [n for n, _s in specs]
        for (name, shape), leaf in zip(specs,
                                       ref.init_leaves(words, config)):
            assert tuple(scope.find_var(name).shape) == tuple(shape)
            scope.set_var(name, np.asarray(leaf))
        dense = config["first_k_dense_replace"]
        for i, b in enumerate(ref.init_router_biases(words, config)):
            scope.set_var("L%d.ffn.router_bias" % (dense + i), np.asarray(b))
    return exe, main, scope, out, [n for n, _s in specs]


def feed_of(batch):
    return {"tokens": batch[0], "labels": batch[1]}


@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_loss_and_every_gradient_match_the_reference(share):
    config = tiny(share)
    exe, main, scope, out, names = program(config, 11)
    batch = batches(config, 5, 1)[0]
    with pt.scope_guard(scope):
        got = exe.run(main, feed=feed_of(batch),
                      fetch_list=[out["loss"]] + [n + "@GRAD"
                                                  for n in names])
    words = ref.key_data(11)
    leaves = ref.init_leaves(words, config)
    biases = ref.init_router_biases(words, config)
    (loss, _picks), grads = jax.value_and_grad(ref.loss_fn, has_aux=True)(
        leaves, biases, jnp.asarray(batch[0]), jnp.asarray(batch[1]), config)
    np.testing.assert_allclose(float(np.asarray(got[0]).reshape(())),
                               float(loss), rtol=2e-6)
    for name, g, want in zip(names, got[1:], grads):
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   rtol=2e-4, atol=2e-5 * scale,
                                   err_msg=name)


@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_state_after_two_adam_steps_matches_the_reference(share):
    config = tiny(share)
    exe, main, scope, out, names = program(config, 12)
    two = batches(config, 6, 2)
    with pt.scope_guard(scope):
        for b in two:
            exe.run(main, feed=feed_of(b), fetch_list=[out["loss"]])
        got = [np.asarray(scope.find_var(n)) for n in names]
        moments = [np.asarray(scope.find_var(op.input("Moment1")[0]))
                   for op in main.global_block().ops if op.type == "adam"]
    want = ref.follow(12, two, OPT, config)
    start = ref.init_leaves(ref.key_data(12), config)
    deltas = np.array([np.linalg.norm(g - np.asarray(s))
                       for g, s in zip(got, start)])
    np.testing.assert_allclose(deltas, want["delta_norms"], rtol=2e-3)
    assert len(moments) == len(names)
    assert all(np.isfinite(m).all() for m in moments)
    # and leaf by leaf, not only in the norms: follow the reference's own
    # two steps again and compare the values
    frozen = ref._frozen(config)
    leaves = [jnp.array(l) for l in start]
    m1 = [jnp.zeros_like(l) for l in leaves]
    m2 = [jnp.zeros_like(l) for l in leaves]
    biases = ref.init_router_biases(ref.key_data(12), config)
    for t, (tok, lab) in enumerate(two, 1):
        leaves, m1, m2, _loss, _gn, _loads = ref.adam_step(
            leaves, m1, m2, biases, jnp.asarray(tok), jnp.asarray(lab),
            *(jnp.float32(OPT[k]) for k in
              ("learning_rate", "beta1", "beta2", "epsilon")),
            jnp.float32(t), frozen=frozen)
    for name, g, w, s in zip(names, got, leaves, start):
        # Adam's first steps move every element by about the rate whatever
        # its gradient's size, and one whose gradient is nought to rounding
        # by its SIGN: the change is compared to a fiftieth of the rate in
        # all but a thousandth of the elements, and nowhere by more than
        # the two steps' full swing
        gap = np.abs((g - np.asarray(s)) - np.asarray(w - s))
        assert np.mean(gap > OPT["learning_rate"] * 0.02) < 1e-3, name
        assert gap.max() <= OPT["learning_rate"] * 4.0, name


def test_the_eight_shares_add_up_to_the_whole_layer():
    """Each of 8 chips holds 1 of 8 experts: the routed parts of the eight
    shares plus the shared expert counted once are the uncut layer."""
    config = tiny(False)
    words = ref.key_data(21)
    specs = ref.leaf_specs(config)
    leaves = dict(zip([n for n, _s in specs],
                      ref.init_leaves(words, config)))
    bias = ref.init_router_biases(words, config)[0]
    ffn = [leaves["L2.ffn." + n] for n in (
        "norm", "router", "expert_gate", "expert_up", "expert_down",
        "shared_gate", "shared_up", "shared_down")]
    x = jax.random.normal(jax.random.PRNGKey(3), (ROWS, SEQ, 64)) * 0.7
    whole, _picks = ref.expert_ffn(x.reshape(-1, 64), ffn, bias, config,
                                   "f32", None)
    h2 = ref.rms_norm(x.reshape(-1, 64), ffn[0], config["rms_norm_eps"])
    shared = ref.gated(h2, ffn[5], ffn[6], ffn[7], "f32")
    total = x.reshape(-1, 64) + shared
    rows = 0
    for share in range(8):
        main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
        with pt.program_guard(main, startup):
            xv = L.data("x", shape=[SEQ, 64], dtype="float32")
            out, load, held = L.moe_ffn(
                xv, 8, 2, 32, 64, experts_held=(share, 1), scaling=2.448,
                prefix="m")
        exe = pt.Executor(pt.CPUPlace())
        with pt.scope_guard(scope):
            exe.run(startup)
            for name, leaf in zip(
                    ("norm", "router", "expert_gate", "expert_up",
                     "expert_down", "shared_gate", "shared_up",
                     "shared_down"), ffn):
                leaf = np.asarray(leaf)
                if name.startswith("expert_"):
                    leaf = leaf[share:share + 1]
                scope.set_var("m." + name, leaf)
            scope.set_var("m.router_bias", np.asarray(bias))
            got, n_load, n_held = exe.run(
                main, feed={"x": np.asarray(x)}, fetch_list=[out, load, held])
        assert int(n_load.sum()) == ROWS * SEQ * 2
        assert int(n_held.sum()) == int(n_load[share])
        rows += int(n_held.sum())
        total = total + (got.reshape(-1, 64) - np.asarray(
            x.reshape(-1, 64) + shared))
    assert rows == ROWS * SEQ * 2       # every pair landed on one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-6)


def _scores(seed, tokens=64, experts=8):
    h = jax.random.normal(jax.random.PRNGKey(seed), (tokens, 16))
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (16, experts)) * 0.5
    return h, w


def test_the_bias_moves_the_selection_and_not_the_weights():
    h, w = _scores(0)
    bias = jnp.zeros(8).at[5].set(10.0)         # expert 5 always picked
    idx0, g0 = decoder_ops.route(h, w, jnp.zeros(8), 2, 2.448)
    idx, g = decoder_ops.route(h, w, bias, 2, 2.448)
    assert bool(jnp.all(jnp.any(idx == 5, axis=1)))
    assert not bool(jnp.all(jnp.any(idx0 == 5, axis=1)))
    s = jax.nn.sigmoid(h @ w)
    raw = jnp.take_along_axis(s, idx, axis=1)       # the scores WITHOUT b
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(raw / raw.sum(1, keepdims=True) * 2.448),
        rtol=1e-6)


def test_the_pick_weights_sum_to_the_scaling_factor():
    h, w = _scores(2)
    bias = jax.random.normal(jax.random.PRNGKey(9), (8,)) * 0.02
    _idx, g = decoder_ops.route(h, w, bias, 2, 2.448)
    np.testing.assert_allclose(np.asarray(g.sum(axis=1)), 2.448, rtol=1e-6)


def test_ties_are_broken_as_the_reference_breaks_them():
    """Equal scores (a zero router) : the lower index wins, in the op and
    in the reference."""
    h, _w = _scores(4)
    w = jnp.zeros((16, 8))
    bias = jnp.array([0., 1., 1., 0., 1., 0., 0., 0.])     # 1, 2, 4 tie
    idx, _g = decoder_ops.route(h, w, bias, 2, 1.0)
    config = dict(num_experts_per_tok=2, routed_scaling_factor=1.0)
    picks, _gr = ref.route(h, w, bias, config, None)
    assert np.asarray(idx).tolist() == np.asarray(picks).tolist()
    assert set(np.asarray(idx).reshape(-1).tolist()) == {1, 2}


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("scoring_func", "softmax"),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("topk_method", "greedy"), ("norm_topk_prob", False)])
def test_a_key_the_block_cannot_honour_raises(key, value):
    config = dict(tiny(False), **{key: value})
    with pt.program_guard(pt.Program(), pt.Program()):
        tokens = L.data("tokens", shape=[SEQ], dtype="int64")
        with pytest.raises(NotImplementedError, match=key):
            latent_moe_lm(tokens, config)


def test_experts_held_outside_the_experts_raises():
    config = dict(tiny(False), experts_held=[6, 4])
    with pt.program_guard(pt.Program(), pt.Program()):
        tokens = L.data("tokens", shape=[SEQ], dtype="int64")
        with pytest.raises(ValueError, match="experts_held"):
            latent_moe_lm(tokens, config)


def test_recomputed_half_layers_give_the_gradients_of_kept_ones():
    """``memory_optimize`` recomputes each half-layer in the backward pass
    (they are in its default set): same gradients as keeping everything."""
    from paddle_tpu.memory_optimization_transpiler import DEFAULT_REMAT_TYPES
    assert set(HALVES) <= DEFAULT_REMAT_TYPES
    config = tiny(True)
    batch = batches(config, 8, 1)[0]
    grads = []
    for remat in (False, True):
        exe, main, scope, out, names = program(config, 13, remat=remat)
        with pt.scope_guard(scope):
            grads.append(exe.run(main, feed=feed_of(batch),
                                 fetch_list=[n + "@GRAD" for n in names]))
    for name, a, b in zip(names, *grads):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8, err_msg=name)


def test_a_compiled_step_has_the_part_scopes_and_load_counts_every_pick():
    from paddle_tpu import profiler
    config = tiny(True)
    exe, main, scope, out, _names = program(config, 14)
    batch = batches(config, 9, 1)[0]
    with pt.scope_guard(scope):
        _loss, load, held = exe.run(
            main, feed=feed_of(batch),
            fetch_list=[out["loss"], out["loads"][0], out["rows_held"][0]])
    assert load.shape == (8,) and int(load.sum()) == ROWS * SEQ * 2
    assert int(held.sum()) == int(load[4:8].sum())
    stats = pt.layers.moe_load_stats(load, held)
    assert stats["moe_rows_held"] == int(held.sum())
    assert stats["moe_max_over_mean_load"] == pytest.approx(
        float(load.max() / load.mean()))
    scopes = set()
    for table in profiler.device_scopes().values():
        scopes |= set(table.values())
    for want in ("forward/latent_attention/proj",
                 "forward/latent_attention/rope",
                 "forward/latent_attention/attn",
                 "backward/latent_attention/attn",
                 "forward/moe_ffn/route", "forward/moe_ffn/experts",
                 "forward/moe_ffn/shared", "backward/moe_ffn/route",
                 "backward/moe_ffn/experts", "backward/moe_ffn/shared",
                 "forward/gated_ffn", "backward/gated_ffn", "update/adam"):
        assert want in scopes, (want, sorted(scopes))
    # and where the held part is a switch over rungs, on the instructions
    # INSIDE its branches, forward and backward
    text = held_layer(True)["text"]
    _module, table = profiler.scopes_of_module(text)
    inside = set()
    for branch in _branch_computations(text):
        inside |= {table[i] for i in _instructions_of(text, branch)
                   if i in table}
    for want in ("forward/moe_ffn/route", "forward/moe_ffn/experts",
                 "backward/moe_ffn/route", "backward/moe_ffn/experts"):
        assert want in inside, (want, sorted(inside))


def test_pure_amp_keeps_the_stream_bf16_and_the_masters_f32():
    """Under pure AMP (forced on the CPU) a half-layer hands on bf16, the
    loss and the parameters stay f32, and the loss is the f32 program's to
    bf16's precision."""
    config = tiny(True)
    batch = batches(config, 10, 1)[0]
    losses = []
    for amp in (False, True):
        prev = pt.amp.force(True) if amp else None
        try:
            exe, main, scope, out, names = program(config, 15, amp=amp)
            block = main.global_block()
            stream = [op.output("Out")[0] for op in block.ops
                      if op.type in HALVES][-1]
            with pt.scope_guard(scope):
                loss, x = exe.run(main, feed=feed_of(batch),
                                  fetch_list=[out["loss"], stream],
                                  return_numpy=False)
                assert np.asarray(scope.find_var(names[1])).dtype \
                    == np.float32
        finally:
            if amp:
                pt.amp.force(prev)
        assert str(x.dtype) == ("bfloat16" if amp else "float32")
        losses.append(float(np.asarray(loss, np.float32).reshape(())))
    assert abs(losses[1] - losses[0]) < 0.05 * abs(losses[0])


def test_rms_norm_and_rotary_layers_match_the_reference():
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        x = L.data("x", shape=[SEQ, 4, 8], dtype="float32")
        normed = L.rms_norm(x, epsilon=1e-6,
                            param_attr=pt.ParamAttr(name="w"))
        turned = L.rotary_embedding(x, theta=1e6)
    exe = pt.Executor(pt.CPUPlace())
    xv = np.random.default_rng(0).standard_normal(
        (ROWS, SEQ, 4, 8)).astype(np.float32)
    w = np.linspace(0.5, 1.5, 8).astype(np.float32)
    with pt.scope_guard(scope):
        exe.run(startup)
        scope.set_var("w", w)
        got_n, got_t = exe.run(main, feed={"x": xv},
                               fetch_list=[normed, turned])
    np.testing.assert_allclose(got_n, ref.rms_norm(xv, w, 1e-6), rtol=1e-5,
                               atol=1e-6)
    for r in range(ROWS):
        np.testing.assert_allclose(got_t[r], ref.rotary(xv[r], 1e6),
                                   rtol=1e-5, atol=1e-6)
    # a rotation: norms of the pairs are kept, and position 0 is untouched
    np.testing.assert_allclose(got_t[:, 0], xv[:, 0], rtol=1e-6)


def test_a_compiler_made_kernel_stays_unscoped_beside_the_part_scopes():
    """XLA:TPU turns ``ragged_dot`` into ``ragged-dot-*`` custom calls whose
    ``op_name`` keeps nothing of the program's: the table calls them
    unscoped, like anything else the compiler made, whatever made their
    operands (readers count them by name); a part is read through the
    wrappers autodiff puts around it."""
    from paddle_tpu import profiler
    program(tiny(True), 16)         # traces moe_ffn: its parts are known
    text = "\n".join([
        "HloModule jit_paddle_tpu_step_0, is_scheduled=true",
        "ENTRY %main {",
        '  %fusion.1 = bf16[64,8]{1,0} fusion(%p.0), kind=kLoop, metadata='
        '{op_name="jit(s)/forward/moe_ffn/route/gather"}',
        "  %copy-done.7 = bf16[4,8,8]{2,1,0} copy-done(%copy-start.7)",
        '  %ragged-dot-metadata.1 = (s32[5]{0}) custom-call(%fusion.1), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"ragged-dot-metadata"}',
        '  %ragged-dot-none.3 = f32[64,8]{1,0} custom-call(%ragged-dot-'
        'metadata.1, %fusion.1, %copy-done.7), custom_call_target='
        '"tpu_custom_call", metadata={op_name="ragged-dot-none"}',
        '  %fusion.2 = f32[64,8]{1,0} fusion(%ragged-dot-none.3), kind=kLoop,'
        ' metadata={op_name="jit(s)/backward/moe_ffn/transpose(jvp(backward/'
        'moe_ffn))/jvp()/checkpoint/experts/mul"}',
        '  %fusion.3 = f32[8]{0} fusion(%p.1), kind=kLoop, metadata='
        '{op_name="jit(s)/update/adam/sub"}',
        '  %ragged-dot-none.4 = f32[4,8,8]{2,1,0} custom-call(%fusion.1, '
        '%fusion.3), custom_call_target="tpu_custom_call", metadata='
        '{op_name="ragged-dot-none"}',
        "}"])
    _module, table = profiler.scopes_of_module(text)
    assert table["fusion.1"] == "forward/moe_ffn/route"
    assert table["fusion.2"] == "backward/moe_ffn/experts"
    assert table["fusion.3"] == "update/adam"
    for made_by_the_compiler in ("ragged-dot-metadata.1", "ragged-dot-none.3",
                                 "ragged-dot-none.4", "copy-done.7"):
        assert table[made_by_the_compiler] == "unscoped"


@pytest.mark.parametrize("where", ["model", "rung"])
def test_rows_of_no_group_never_reach_the_result_or_the_gradients(
        monkeypatch, where):
    """On the chip a grouped product leaves whatever the buffer held (NaN,
    seen by ``chip_smoke.py``) in the rows past the held pairs, forward and
    in the gradient of its left operand. With NaN planted there the loss
    and every gradient are what they are without: in the small model (one
    rung: all the pairs) and in a held share's rungs, whose rows past
    ``RowsHeld`` are such rows too."""
    config = tiny(True)
    batch = batches(config, 17, 1)[0]
    real = jax.lax.ragged_dot

    def dead_rows_nan(out, sizes):
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(sizes), out, jnp.nan)

    @jax.custom_vjp
    def planted(lhs, rhs, sizes):
        return dead_rows_nan(real(lhs, rhs, sizes,
                                  preferred_element_type=jnp.float32), sizes)

    def fwd(lhs, rhs, sizes):
        return planted(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _out, vjp = jax.vjp(lambda a, b: real(
            a, b, sizes, preferred_element_type=jnp.float32), lhs, rhs)
        live = (jnp.arange(g.shape[0]) < jnp.sum(sizes))[:, None]
        d_lhs, d_rhs = vjp(jnp.where(live, g, 0.0))
        return dead_rows_nan(d_lhs, sizes), d_rhs, None

    planted.defvjp(fwd, bwd)
    got, traced = [], []

    def plant_it(a, b, s, **_kw):
        traced.append(a.shape)
        return planted(a, b, s)
    for plant in (False, True):
        # the held part keeps its traced rungs (``_held_part``): what was
        # traced without the plant must not answer for the run with it
        decoder_ops._held_part.cache_clear()
        if plant:
            monkeypatch.setattr(jax.lax, "ragged_dot", plant_it)
        if where == "rung":
            # 0, ~1.5x and ~3x the expected pairs held: the first rung
            # twice and the second, each with dead rows inside it
            layer = held_layer(True, cached=False)
            runs = [layer["run"](b) for b in HELD_BIAS[:3]]
            assert [r[1] for r in runs] == [layer["rungs"][0]] * 2 \
                + [layer["rungs"][1]]
            assert all(r[1] > int(r[0][1].sum()) for r in runs)
            got.append([a for r in runs for a in r[0]])
            names = ["loss", "rows_held", "x"] + layer["names"]
            names = [n + "/%d" % i for i in range(3) for n in names]
            continue
        exe, main, scope, out, names = program(config, 18)
        with pt.scope_guard(scope):
            got.append(exe.run(main, feed=feed_of(batch), fetch_list=[
                out["loss"]] + [n + "@GRAD" for n in names]))
        names = ["loss"] + names
    decoder_ops._held_part.cache_clear()
    assert traced
    for name, a, b in zip(names, *got):
        assert np.isfinite(b).all(), name
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-8, err_msg=name)


# -- the rungs of a held share ------------------------------------------------
# 768 tokens x 4 picks over 32 experts of which the layer holds 4: 3,072
# pairs, 384 of them expected on held experts, rungs 1,024 / 1,536 / 3,072.
H_ROWS, H_SEQ, H_D, H_EXPERTS, H_K, H_HELD, H_SIZE = 2, 384, 64, 32, 4, \
    (6, 4), 32
H_PAIRS = H_ROWS * H_SEQ * H_K
# what the router's bias adds on the held experts: no pick on them, ~1.5x
# and ~3x the expected share, every pick
HELD_BIAS = (-10.0, 0.05, 0.3, 10.0)
H_PARAMS = ("norm", "router", "expert_gate", "expert_up", "expert_down",
            "shared_gate", "shared_up", "shared_down")
_held_layers = {}


def _held_leaves():
    rng = np.random.default_rng(23)
    d, e, m, c = H_D, H_EXPERTS, H_SIZE, H_HELD[1]
    shapes = [(d,), (d, e), (c, d, m), (c, d, m), (c, m, d), (d, 64), (d, 64),
              (64, d)]
    return [(1.0 + 0.1 * rng.standard_normal(sh) if len(sh) == 1
             else 0.4 * rng.standard_normal(sh)).astype(np.float32)
            for sh in shapes]


def _held_x():
    return np.random.default_rng(29).standard_normal(
        (H_ROWS, H_SEQ, H_D)).astype(np.float32)


def held_layer(remat, one_rung=False, cached=True):
    """One ``moe_ffn`` that holds 4 of 32 experts under the loss mean(out^2),
    with SGD at rate 0 (so that every gradient can be fetched): ``run(bias
    on the held experts)`` -> ([loss, rows_held, d loss / d x, every
    parameter's gradient], the rung the step ran at); ``text`` the
    compiled step's text. ``one_rung``: lowered with ``held_rungs``
    answering all the pairs, which is the program this layer had before it
    had rungs."""
    key = (remat, one_rung)
    if cached and key in _held_layers:
        return _held_layers[key]
    from paddle_tpu.core import executor
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        x = L.data("x", shape=[H_SEQ, H_D], dtype="float32")
        x.stop_gradient = False
        out, _load, held = L.moe_ffn(
            x, H_EXPERTS, H_K, H_SIZE, 64, experts_held=H_HELD,
            scaling=2.448, prefix="m")
        loss = L.mean(out * out)
        pt.optimizer.SGDOptimizer(learning_rate=0.0).minimize(loss)
        if remat:
            pt.memory_optimize(main, remat_types=("moe_ffn",))
    exe = pt.Executor(pt.CPUPlace())
    names = ["m." + n for n in H_PARAMS]
    with pt.scope_guard(scope):
        exe.run(startup)
        for name, leaf in zip(names, _held_leaves()):
            scope.set_var(name, leaf)
    rungs = decoder_ops.held_rungs(H_PAIRS, H_HELD[1], H_EXPERTS)
    fetch = [loss, held, "x@GRAD"] + [n + "@GRAD" for n in names]

    def run(bias):
        b = np.zeros(H_EXPERTS, np.float32)
        b[H_HELD[0]:H_HELD[0] + H_HELD[1]] = bias
        real = decoder_ops.held_rungs
        if one_rung:
            decoder_ops.held_rungs = lambda pairs, _c, _n: (pairs,)
        try:
            with pt.scope_guard(scope):
                scope.set_var("m.router_bias", b)
                got = exe.run(main, feed={"x": _held_x()}, fetch_list=fetch)
        finally:
            decoder_ops.held_rungs = real
        return got, L.moe_rows_moved(got[1], H_PAIRS, H_HELD[1], H_EXPERTS)

    layer = {"run": run, "names": names, "rungs": rungs, "exe": exe}
    run(0.0)                    # traced and compiled here, under this key
    step = list(executor.compiled_steps())[-1]
    layer["text"] = step.fn.lower(*step._avals).compile().as_text()
    if cached:
        _held_layers[key] = layer
    return layer


def _branch_computations(text):
    out = []
    for line in text.splitlines():
        if " conditional(" in line:
            out += re.search(r"branch_computations=\{([^}]*)\}",
                             line).group(1).replace("%", "").split(", ")
    return out


def _instructions_of(text, computation):
    """Names of the instructions in ``computation``'s body."""
    names, inside = [], False
    for line in text.splitlines():
        if not inside:
            inside = re.match(r"\s*%?" + re.escape(computation) + r"\s*\(",
                              line) is not None and line.rstrip().endswith("{")
        elif line.strip() == "}":
            break
        else:
            m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s", line)
            if m:
                names.append(m.group(1))
    return names


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "recomputed"])
@pytest.mark.parametrize("bias", HELD_BIAS,
                         ids=["none", "x1.5", "x3", "every_pick"])
def test_every_rung_gives_the_loss_and_gradients_of_the_one_rung_lowering(
        remat, bias):
    """The rung follows the DATA (the router's bias on the held experts),
    and at each the step is the step of the layer lowered with one rung."""
    layer, plain = held_layer(remat), held_layer(remat, one_rung=True)
    assert layer["rungs"] == (1024, 1536, 3072)
    (got, rung), (want, _all) = layer["run"](bias), plain["run"](bias)
    held = int(got[1].sum())
    assert held == int(want[1].sum())
    expected = H_PAIRS * H_HELD[1] / H_EXPERTS
    lo, hi, at = {-10.0: (0, 0, 1024), 0.05: (1.3, 1.8, 1024),
                  0.3: (2.7, 3.6, 1536), 10.0: (8, 8, 3072)}[bias]
    assert lo * expected <= held <= hi * expected, held
    assert rung == at
    for name, a, b in zip(["loss", "rows_held", "x"] + layer["names"], got,
                          want):
        scale = float(np.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6 * scale,
                                   err_msg=name)


def test_at_the_top_rung_no_pick_is_dropped_and_the_layer_is_the_dense_one():
    """Every pick pushed onto the 4 held experts (k = 4): all 3,072 pairs
    are held, the step runs at the last rung, and result and gradients are
    the plain reference's, which loops over the experts and sorts nothing."""
    layer = held_layer(True)
    got, rung = layer["run"](10.0)
    assert int(got[1].sum()) == min(H_K, H_HELD[1]) * H_ROWS * H_SEQ \
        == H_PAIRS == rung == layer["rungs"][-1]
    config = dict(n_routed_experts=H_EXPERTS, experts_held=list(H_HELD),
                  num_experts_per_tok=H_K, routed_scaling_factor=2.448,
                  rms_norm_eps=1e-6)
    bias = jnp.zeros(H_EXPERTS).at[H_HELD[0]:sum(H_HELD)].set(10.0)

    def loss_fn(x, leaves):
        out, _picks = ref.expert_ffn(x.reshape(-1, H_D), leaves, bias,
                                     config, "f32", None)
        return jnp.mean(out * out)
    leaves = [jnp.asarray(l) for l in _held_leaves()]
    loss, (dx, dleaves) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
        jnp.asarray(_held_x()), leaves)
    np.testing.assert_allclose(float(got[0].reshape(())), float(loss),
                               rtol=2e-6)
    for name, a, b in zip(["x"] + layer["names"], got[2:],
                          [dx.reshape(got[2].shape)] + list(dleaves)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, np.asarray(b), rtol=2e-4,
                                   atol=2e-5 * scale, err_msg=name)


@pytest.mark.parametrize("pairs,count,experts,want", [
    (8192 * 6, 16, 128, (12288, 24576, 49152)),         # K's expert layers
    (8192 * 8, 16, 128, (16384, 32768, 65536)),         # T's
    (8192 * 6, 128, 128, (49152,)),                     # every expert held
    (128, 4, 8, (128,)),                                # the small models
    (3072, 16, 32, (3072,)),                            # 2x is all the pairs
    (3072, 8, 32, (1536, 3072)),                        # 4x is
    (3000, 2, 16, (1024, 1536, 3000)),                  # rounded up to 512s
    (3072, 4, 32, (1024, 1536, 3072))])
def test_held_rungs(pairs, count, experts, want):
    rungs = decoder_ops.held_rungs(pairs, count, experts)
    assert rungs == want
    assert rungs[-1] == pairs and list(rungs) == sorted(set(rungs))
    # the host's reading of a fetched RowsHeld: the first rung that holds it
    for held in (0, 1, rungs[0], min(rungs[0] + 1, pairs), pairs):
        moved = L.moe_rows_moved(np.array([held], np.int32), pairs, count,
                                 experts)
        assert moved == min(r for r in rungs if r >= held)


def test_a_layer_that_holds_every_expert_has_no_conditional():
    """``experts_held=None``: one rung, and the compiled step (forward,
    recomputed forward, backward) has no ``conditional`` at all."""
    from paddle_tpu.core import executor
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        x = L.data("x", shape=[H_SEQ, H_D], dtype="float32")
        out, _load, _held = L.moe_ffn(x, 8, 2, H_SIZE, 64, prefix="w")
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(
            L.mean(out * out))
        pt.memory_optimize(main, remat_types=("moe_ffn",))
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": _held_x()}, fetch_list=[out])
    step = list(executor.compiled_steps())[-1]
    text = step.fn.lower(*step._avals).compile().as_text()
    assert "moe_ffn" in text and " conditional(" not in text


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "recomputed"])
def test_no_conditional_of_the_gradient_returns_a_rungs_arrays(remat):
    """Autodiff of a bare ``lax.switch`` would make every branch return all
    branches' residuals (``[rung, .]`` arrays, zero-filled for the rungs not
    taken). The held part is one ``custom_vjp``: one conditional forward,
    one backward (the recomputed forward's is dead), and what they return
    is the layer's result and the operands' gradients."""
    layer = held_layer(remat)
    conds = [l for l in layer["text"].splitlines() if " conditional(" in l]
    assert len(conds) == 2, conds
    for line in conds:
        result = line.split(" conditional(")[0].split("=", 1)[1]
        dims = {int(n) for shape in re.findall(r"\[([\d,]*)\]", result)
                for n in shape.split(",") if n}
        assert dims and not dims & set(layer["rungs"]), line
        assert len(_branch_computations(line)) == len(layer["rungs"])


def test_the_rungs_are_counted_where_the_flash_blocks_are():
    from paddle_tpu import tune
    tune.reset_counters()
    layer = held_layer(True, cached=False)
    name = "%d -> 1024/1536/3072" % H_PAIRS
    counted = tune.counters()["moe_rungs"]
    # forward, and the backward pass's recomputed forward
    assert counted == {name: 2}
    assert layer["exe"].stats["moe_rungs"] == counted
    held_layer(True, one_rung=True, cached=False)
    assert tune.counters()["moe_rungs"] == {name: 2,
                                            "%d -> %d" % (H_PAIRS,
                                                          H_PAIRS): 2}
    tune.reset_counters()
    assert tune.counters()["moe_rungs"] == {}


def _step_of_expert_layers(monkeypatch, n_layers, shared):
    """A recomputed step of ``n_layers`` ``moe_ffn`` of one shape, each
    holding 4 of 32 experts: ({rung: times its body's Python ran},
    {scope: instructions of the compiled step}). ``shared=False``: as it
    was traced before the rungs were kept, every layer and pass its own
    closures (``_held_part`` not kept, no inlined ``jit`` around a rung)."""
    from paddle_tpu import profiler
    from paddle_tpu.core import executor
    body_runs = collections.Counter()
    real_rung, real_jit = decoder_ops._held_rung, jax.jit

    def counted(rows, *args):
        rung = real_rung(rows, *args)

        def body(*operands):
            body_runs[rows] += 1
            return rung(*operands)
        return body
    monkeypatch.setattr(decoder_ops, "_held_rung", counted)
    decoder_ops._held_part.cache_clear()
    if not shared:
        monkeypatch.setattr(decoder_ops, "_held_part",
                            decoder_ops._held_part.__wrapped__)
        monkeypatch.setattr(jax, "jit", lambda f, **kw: f if "inline" in kw
                            else real_jit(f, **kw))
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        x = L.data("x", shape=[H_SEQ, H_D], dtype="float32")
        out = x
        for i in range(n_layers):
            out, _load, _held = L.moe_ffn(
                out, H_EXPERTS, H_K, H_SIZE, 64, experts_held=H_HELD,
                scaling=2.448, prefix="m%d" % i)
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(
            L.mean(out * out))
        pt.memory_optimize(main, remat_types=("moe_ffn",))
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": _held_x()}, fetch_list=[out])
    step = list(executor.compiled_steps())[-1]
    _module, table = profiler.scopes_of_module(
        step.fn.lower(*step._avals).compile().as_text())
    monkeypatch.undo()
    decoder_ops._held_part.cache_clear()
    return dict(body_runs), collections.Counter(table.values())


def test_a_rung_is_traced_once_for_all_layers_and_every_scope_stays(
        monkeypatch):
    """A program of several expert layers of one shape runs the Python of
    each rung's body once in the step's own trace and once under
    ``jax.checkpoint`` (another trace context to jax's tracing cache),
    however many layers it has; traced layer by layer and pass by pass, as
    it was, every layer runs it four times (its forward, its recomputed
    forward and that one's differentiation, the backward's ``jax.vjp``).
    And sharing the trace moves no
    instruction from one scope to another: the compiled step has the same
    instructions under every ``<phase>/moe_ffn/<part>`` either way (a
    shared LOWERING would give every call site the first caller's name)."""
    rungs = decoder_ops.held_rungs(H_PAIRS, H_HELD[1], H_EXPERTS)
    one, _scopes = _step_of_expert_layers(monkeypatch, 1, True)
    two, scopes = _step_of_expert_layers(monkeypatch, 2, True)
    own, own_scopes = _step_of_expert_layers(monkeypatch, 2, False)
    assert one == two == {rows: 2 for rows in rungs}
    assert own == {rows: 4 * 2 for rows in rungs}
    assert scopes == own_scopes
    for phase in ("forward", "backward"):
        for part in ("route", "experts", "shared"):
            assert scopes["%s/moe_ffn/%s" % (phase, part)] > 0

"""``models.prerouted_moe_lm`` (a softmax router that reads the attention's
normed input, placed before attention; grouped-query heads in window and
global layers, positions on window layers only; ReLU-gated experts, no
shared expert) against the plain reference
``chipbench/reference/prerouted_moe_lm.py`` on seeded weights, CPU,
float32, at a small size: rows of three windows; whole and as one chip's
share of an expert-parallel group."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu.models.prerouted_moe_lm import check_config, prerouted_moe_lm

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    path = os.path.join(_ROOT, "chipbench", *parts)
    spec = importlib.util.spec_from_file_location(
        "t_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference", "prerouted_moe_lm.py")

SEQ, ROWS, WINDOW = 48, 2, 16
HALVES = ("grouped_attention", "moe_ffn")
OPT = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}


def tiny(share):
    """d 64, 14 query heads on 2 key/value heads of 16 (groups of 7, as
    published), a global layer then two window layers of 16 keys with
    positions, 8 experts top-3 of 32, vocabulary 256; ``share``: 4 experts
    and 64 rows held, neither range starting at 0."""
    config = dict(
        hidden_size=64, num_attention_heads=14, num_key_value_heads=2,
        head_dim=16, moe_ffn_hidden_size=32, moe_num_primary_experts=8,
        moe_num_active_primary_experts=3, num_hidden_layers=3,
        sliding_window_layout=[0, 1, 1], rope_layout=[0, 1, 1],
        sliding_window_size=WINDOW, rms_norm_eps=1e-6, rope_theta=1.5e6,
        vocab_size=256, moe_primary_router_apply_softmax=True,
        norm_topk_prob=True, rope_scaling=None, tie_word_embeddings=False)
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


def program(config, seed, remat=True):
    """(exe, main, scope, model outputs, names): the model with Adam, the
    reference's seeded leaves in the scope."""
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        tokens = L.data("tokens", shape=[SEQ], dtype="int64")
        labels = L.data("labels", shape=[SEQ], dtype="int64")
        out = prerouted_moe_lm(tokens, config, labels=labels)
        pt.optimizer.AdamOptimizer(
            learning_rate=OPT["learning_rate"], beta1=OPT["beta1"],
            beta2=OPT["beta2"], epsilon=OPT["epsilon"]).minimize(out["loss"])
        if remat:
            pt.memory_optimize(main, remat_types=HALVES)
    exe = pt.Executor(pt.CPUPlace())
    specs = ref.leaf_specs(config)
    with pt.scope_guard(scope):
        exe.run(startup)
        params = [p.name for p in main.all_parameters()]
        assert params == [n for n, _s in specs]      # no bias, no shared
        for (name, shape), leaf in zip(
                specs, ref.init_leaves(ref.key_data(seed), config)):
            assert tuple(scope.find_var(name).shape) == tuple(shape)
            scope.set_var(name, np.asarray(leaf))
    return exe, main, scope, out, [n for n, _s in specs]


def feed_of(batch):
    return {"tokens": batch[0], "labels": batch[1]}


def reference_loss(config, seed, batch, **more):
    return ref.loss_fn(ref.init_leaves(ref.key_data(seed), config),
                       jnp.asarray(batch[0]), jnp.asarray(batch[1]), config,
                       **more)[0]


@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_loss_and_every_gradient_match_the_reference(share):
    config = tiny(share)
    exe, main, scope, out, names = program(config, 11)
    batch = batches(config, 5, 1)[0]
    with pt.scope_guard(scope):
        got = exe.run(main, feed=feed_of(batch),
                      fetch_list=[out["loss"]] + [n + "@GRAD"
                                                  for n in names])
    leaves = ref.init_leaves(ref.key_data(11), config)
    (loss, _picks), grads = jax.value_and_grad(ref.loss_fn, has_aux=True)(
        leaves, jnp.asarray(batch[0]), jnp.asarray(batch[1]), config)
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
    want = ref.follow(12, two, OPT, config, keep_leaves=True)
    start = [np.asarray(l)
             for l in ref.init_leaves(ref.key_data(12), config)]
    deltas = np.array([np.linalg.norm(g - s) for g, s in zip(got, start)])
    np.testing.assert_allclose(deltas, want["delta_norms"], rtol=2e-3)
    for name, g, w, s in zip(names, got, want["leaves"], start):
        # as tests/test_latent_moe_lm.py: Adam's first steps move every
        # element by about the rate, one whose gradient is nought to
        # rounding by its SIGN
        gap = np.abs((g - s) - (w - s))
        assert np.mean(gap > OPT["learning_rate"] * 0.02) < 1e-3, name
        assert gap.max() <= OPT["learning_rate"] * 4.0, name


def test_positions_and_windows_follow_the_two_layouts():
    config = tiny(False)
    _exe, main, _scope, _out, _names = program(config, 1)
    ops = main.global_block().ops
    attn = [op for op in ops if op.type == "grouped_attention"]
    assert [op.attr("rotary") for op in attn] == [False, True, True]
    assert [op.attr("window") for op in attn] == [0, WINDOW, WINDOW]
    assert {(op.attr("qk_norm"), op.attr("gate")) for op in attn} == {
        (False, False)}
    moe = [op for op in ops if op.type == "moe_ffn"]
    assert {(op.attr("score"), op.attr("activation")) for op in moe} == {
        ("softmax", "relu")}


def test_the_router_reads_the_attention_s_input():
    """Each expert layer's router reads the block's input x (the attention
    op's X) under the attention's norm scale, and that scale gets the
    gradient of both readers."""
    config = tiny(True)
    _exe, main, _scope, _out, _names = program(config, 1, remat=False)
    ops = main.global_block().ops
    attn = [op for op in ops if op.type == "grouped_attention"]
    moe = [op for op in ops if op.type == "moe_ffn"]
    for i, (a, m) in enumerate(zip(attn, moe)):
        assert m.input("RouterX") == a.input("X")
        assert m.input("RouterNormScale") == ["L%d.attn.norm" % i]
        assert m.input("X") == a.output("Out")
        assert not m.input("RouterBias") and not m.input("SharedGate")
    writers = [op.type for op in ops
               if any(n.startswith("L0.attn.norm@GRAD")
                      for n in op.output_arg_names)]
    # the attention's gradient op, the expert layer's, and their sum
    assert len(writers) >= 3, writers


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_reference_and_not_the_program(fault):
    config = tiny(True)
    batch = batches(config, 7, 1)[0]
    clean = float(reference_loss(config, 19, batch))
    assert abs(float(reference_loss(config, 19, batch, fault=fault))
               - clean) > 1e-5 * clean
    exe, main, scope, out, _names = program(config, 19)
    with pt.scope_guard(scope):
        got, = exe.run(main, feed=feed_of(batch), fetch_list=[out["loss"]])
    np.testing.assert_allclose(float(np.asarray(got).reshape(())), clean,
                               rtol=2e-6)


def test_the_first_half_of_one_row_is_what_half_a_batch_of_one_means():
    config = tiny(False)
    (tokens, labels), = batches(config, 2, 1)
    one = [(tokens[:1], labels[:1])]
    half = ref.follow(5, one, OPT, config, rows=0)
    want = ref.follow(5, [(tokens[:1, :SEQ // 2], labels[:1, :SEQ // 2])],
                      OPT, config)
    assert half["losses"] == want["losses"]
    assert half["losses"] != ref.follow(5, one, OPT, config)["losses"]


@pytest.mark.parametrize("key,value", [
    ("moe_primary_router_apply_softmax", False), ("norm_topk_prob", False),
    ("tie_word_embeddings", True),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("hidden_act", "silu"),
    ("sliding_window_layout", [0, 2, 1]),
    ("rope_layout", [1, 1, 1])])
def test_a_key_the_block_cannot_honour_raises(key, value):
    config = dict(tiny(False), **{key: value})
    with pt.program_guard(pt.Program(), pt.Program()):
        tokens = L.data("tokens", shape=[SEQ], dtype="int64")
        with pytest.raises(NotImplementedError, match=key):
            prerouted_moe_lm(tokens, config)


def test_a_layout_of_another_length_raises():
    config = dict(tiny(False), num_hidden_layers=2)
    with pt.program_guard(pt.Program(), pt.Program()):
        tokens = L.data("tokens", shape=[SEQ], dtype="int64")
        with pytest.raises(ValueError, match="layout"):
            prerouted_moe_lm(tokens, config)


def test_recomputed_half_layers_give_the_gradients_of_kept_ones():
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


def test_a_compiled_step_has_the_part_scopes():
    from paddle_tpu import profiler
    config = tiny(True)
    exe, main, scope, out, _names = program(config, 14)
    with pt.scope_guard(scope):
        exe.run(main, feed=feed_of(batches(config, 9, 1)[0]),
                fetch_list=[out["loss"]])
    scopes = set()
    for table in profiler.device_scopes().values():
        scopes |= set(table.values())
    for want in ("grouped_attention/attn_window", "grouped_attention/attn_full",
                 "grouped_attention/rope", "moe_ffn/route",
                 "moe_ffn/experts"):
        for phase in ("forward", "backward"):
            assert "%s/%s" % (phase, want) in scopes, (want, sorted(scopes))


def test_the_eight_shares_add_up_to_the_whole_layer():
    """Each of 8 chips holds 1 of 8 experts. With no shared expert and no
    post norm, the eight shares' routed parts plus the stream once are the
    uncut reference's expert half-layer, the router reading the
    attention's input norm on every share."""
    config = tiny(False)
    specs = ref.leaf_specs(config)
    leaves = dict(zip([n for n, _s in specs],
                      ref.init_leaves(ref.key_data(21), config)))
    names = ("norm", "router", "expert_gate", "expert_up", "expert_down")
    ffn = [leaves["L1.ffn." + n] for n in names]
    w_attn = leaves["L1.attn.norm"]
    keys = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(keys[0], (ROWS, SEQ, 64))       # the block's input
    u = jax.random.normal(keys[1], (ROWS, SEQ, 64))       # after attention
    flat_x, flat_u = x.reshape(-1, 64), u.reshape(-1, 64)
    h = ref.rms_norm(flat_x, w_attn, config["rms_norm_eps"])
    whole, _picks = ref.expert_ffn(flat_u, h, ffn, config, "f32", None)
    total, rows = np.asarray(flat_u), 0
    for share in range(8):
        main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
        with pt.program_guard(main, startup):
            xv = L.data("x", shape=[SEQ, 64], dtype="float32")
            uv = L.data("u", shape=[SEQ, 64], dtype="float32")
            scale = L.create_parameter([64], "float32", name="a.norm")
            out, load, n_held = L.moe_ffn(
                uv, 8, 3, 32, 0, experts_held=(share, 1),
                epsilon=config["rms_norm_eps"], score="softmax",
                activation="relu", router_input=(xv, scale), prefix="m")
        exe = pt.Executor(pt.CPUPlace())
        with pt.scope_guard(scope):
            exe.run(startup)
            for name, leaf in zip(names, ffn):
                leaf = np.asarray(leaf)
                if name.startswith("expert_"):
                    leaf = leaf[share:share + 1]
                scope.set_var("m." + name, leaf)
            scope.set_var("a.norm", np.asarray(w_attn))
            got, got_load, got_held = exe.run(
                main, feed={"x": np.asarray(x), "u": np.asarray(u)},
                fetch_list=[out, load, n_held])
        assert int(got_held.sum()) == int(got_load[share])
        rows += int(got_held.sum())
        total = total + (got.reshape(-1, 64) - np.asarray(flat_u))
    assert rows == ROWS * SEQ * 3       # every pair landed on one share
    np.testing.assert_allclose(total, np.asarray(whole), rtol=2e-4,
                               atol=2e-5)


def test_the_cells_configuration_is_the_published_one_cut_as_it_says():
    """``chipbench/configs/smallthinker-21b-a3b-ep8.json`` through its
    network module: the model accepts it, published layers 0-3 (global,
    window, window, window), 370,547,200 trainable parameters and 28.2
    TFLOP a 16,384-token row."""
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "smallthinker-21b-a3b-ep8.json")) as f:
        config = json.load(f)
    network = _load("networks", "prerouted_moe_lm.py")
    model = network.model_config(config)
    check_config(model)
    assert model["sliding_window_layout"] == [0, 1, 1, 1]
    assert model["rope_layout"] == [0, 1, 1, 1]
    assert len(config["sliding_window_layout"]) == 52
    assert model["experts_held"] == [0, 8]
    assert model["vocab_held"] == [0, 18992]
    count = sum(int(np.prod(s)) for _n, s in ref.leaf_specs(model))
    assert count == 370547200          # 5.93 GB of f32 state at 16 B
    assert network.matmul_params_per_token(config) == pytest.approx(
        150.86e6, rel=1e-3)
    assert network.seen_pairs(16384, 4096) == 58722304
    assert network.seen_pairs(16384) == 134225920
    flops = network.attention_flops_per_step(config, 1, 16384)
    assert flops["window"] == 3 * 3 * 58722304 * 28 * 512
    assert flops["full"] == 3 * 134225920 * 28 * 512
    assert network.train_flops_per_row(config, None, 16384) == pytest.approx(
        28.2e12, rel=5e-3)
    assert ref.init_router_biases(None, model) == []
    for key, value in (("n_routed_experts", 128), ("num_experts_per_tok", 8),
                       ("first_k_dense_replace", 1)):
        with pytest.raises(ValueError, match=key):
            network.model_config(dict(config, **{key: value}))

"""``models.hybrid_ssm_moe_lm`` (Mamba-2 mixers, squared-ReLU expert layers
with a shared expert and grouped-query attention without positions, placed
by ``hybrid_override_pattern``) against the plain reference
``chipbench/reference/hybrid_ssm_moe_lm.py`` on seeded weights, CPU,
float32, at a small size: rows of four chunks, 16 q heads on one k/v head;
whole and as one chip's share of an expert-parallel group."""
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu.models.hybrid_ssm_moe_lm import check_config, hybrid_ssm_moe_lm

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    path = os.path.join(_ROOT, "chipbench", *parts)
    spec = importlib.util.spec_from_file_location(
        "t_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference", "hybrid_ssm_moe_lm.py")

SEQ, ROWS, CHUNK = 32, 2, 8
HALVES = ("ssm_mixer", "grouped_attention", "moe_ffn")
OPT = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}


def tiny(share, pattern="MEM*E"):
    """d 64; mixers of 8 heads of 8 on 2 groups of state 8, a 4-tap conv,
    chunks of 8; 16 query heads on 1 key/value head of 8 (groups of 16, as
    published); 16 experts top-3 of width 32 and a shared expert of 48,
    scaling 2.5; vocabulary 256; ``share``: 4 experts and 64 rows held,
    neither range starting at 0."""
    config = dict(
        hidden_size=64, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
        ssm_state_size=8, conv_kernel=4, chunk_size=CHUNK,
        num_attention_heads=16, num_key_value_heads=1, head_dim=8,
        n_routed_experts=16, num_experts_per_tok=3, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=48, n_shared_experts=1,
        routed_scaling_factor=2.5, num_hidden_layers=len(pattern),
        hybrid_override_pattern=pattern, layer_norm_epsilon=1e-5,
        vocab_size=256, time_step_min=0.001, time_step_max=0.1,
        time_step_floor=1e-4, rescale_prenorm_residual=True, n_group=1,
        topk_group=1, norm_topk_prob=True, tie_word_embeddings=False,
        mlp_hidden_act="relu2", mamba_hidden_act="silu",
        sliding_window=None, residual_in_fp32=False, use_conv_bias=True,
        mamba_proj_bias=False, attention_bias=False, mlp_bias=False,
        use_bias=False, rope_theta=10000, partial_rotary_factor=1)
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
    reference's seeded leaves and selection biases in the scope."""
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        tokens = L.data("tokens", shape=[SEQ], dtype="int64")
        labels = L.data("labels", shape=[SEQ], dtype="int64")
        out = hybrid_ssm_moe_lm(tokens, config, labels=labels)
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
        params = [p.name for p in main.all_parameters()
                  if not p.name.endswith("router_bias")]
        assert params == [n for n, _s in specs]
        for (name, shape), leaf in zip(specs, ref.init_leaves(words, config)):
            assert tuple(scope.find_var(name).shape) == tuple(shape)
            scope.set_var(name, np.asarray(leaf))
        for i, b in enumerate(ref.init_router_biases(words, config)):
            scope.set_var("L%d.ffn.router_bias" % i, np.asarray(b))
    return exe, main, scope, out, [n for n, _s in specs]


def feed_of(batch):
    return {"tokens": batch[0], "labels": batch[1]}


def reference_loss(config, seed, batch, **more):
    """(loss, picks per expert layer) of the reference."""
    words = ref.key_data(seed)
    return ref.loss_fn(ref.init_leaves(words, config),
                       ref.init_router_biases(words, config),
                       jnp.asarray(batch[0]), jnp.asarray(batch[1]), config,
                       **more)


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
    (loss, _picks), grads = jax.value_and_grad(ref.loss_fn, has_aux=True)(
        ref.init_leaves(words, config), ref.init_router_biases(words, config),
        jnp.asarray(batch[0]), jnp.asarray(batch[1]), config)
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


def test_the_pattern_places_the_kinds_and_names_the_expert_layers_first():
    """Each letter builds its kind; each kind counts its own layers, so the
    expert layers are L0.ffn, L1.ffn, ... whatever lies between them (the
    driver installs the i-th selection bias as L<i>.ffn.router_bias)."""
    config = tiny(False)
    _exe, main, _scope, _out, names = program(config, 1)
    ops = main.global_block().ops
    kinds = [op.type for op in ops if op.type in HALVES]
    assert kinds == ["ssm_mixer", "moe_ffn", "ssm_mixer",
                     "grouped_attention", "moe_ffn"]
    attn = [op for op in ops if op.type == "grouped_attention"]
    assert [(op.attr("rotary"), op.attr("qk_norm"), op.attr("gate"),
             op.attr("window")) for op in attn] == [(False, False, False, 0)]
    moe = [op for op in ops if op.type == "moe_ffn"]
    assert {(op.attr("score"), op.attr("activation"), op.attr("scaling"))
            for op in moe} == {("sigmoid", "relu2", 2.5)}
    for op in moe:
        assert not op.input("ExpertGate") and not op.input("SharedGate")
        assert op.input("SharedUp") and op.input("RouterBias")
    assert [op.input("RouterBias")[0] for op in moe] == [
        "L0.ffn.router_bias", "L1.ffn.router_bias"]
    assert [n for n in names if n.endswith(".w_in")] == [
        "M0.mixer.w_in", "M1.mixer.w_in"]
    assert "A0.attn.wq" in names


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_reference_and_not_the_program(fault):
    """A fault moves the reference's loss, or (the selection's, under a
    bias of N(0, 0.02^2)) the picks."""
    config = tiny(True)
    batch = batches(config, 7, 1)[0]
    clean, picks = reference_loss(config, 19, batch)
    clean = float(clean)
    moved, moved_picks = reference_loss(config, 19, batch, fault=fault)
    assert abs(float(moved) - clean) > 1e-5 * clean or any(
        (np.asarray(a) != np.asarray(b)).any()
        for a, b in zip(picks, moved_picks))
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
    ("n_group", 2), ("topk_group", 2), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("mlp_hidden_act", "silu"),
    ("mamba_hidden_act", "gelu"), ("sliding_window", 4096),
    ("residual_in_fp32", True), ("use_conv_bias", False),
    ("mamba_proj_bias", True), ("attention_bias", True), ("mlp_bias", True),
    ("time_step_limit", [0.0, 0.1]),
    ("hybrid_override_pattern", "MEM-E")])
def test_a_key_the_block_cannot_honour_raises(key, value):
    config = dict(tiny(False), **{key: value})
    with pt.program_guard(pt.Program(), pt.Program()):
        tokens = L.data("tokens", shape=[SEQ], dtype="int64")
        with pytest.raises(NotImplementedError,
                           match="pattern" if key.startswith("hybrid")
                           else key):
            hybrid_ssm_moe_lm(tokens, config)


def test_the_published_time_step_limit_and_a_pattern_of_another_length():
    check_config(dict(tiny(False), time_step_limit=[0.0, math.inf]))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        check_config(dict(tiny(False), num_hidden_layers=4))


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


def test_a_compiled_step_has_the_part_scopes_and_counts_its_scans():
    from paddle_tpu import profiler, tune
    config = tiny(True)
    tune.reset_counters()
    exe, main, scope, out, _names = program(config, 14)
    with pt.scope_guard(scope):
        exe.run(main, feed=feed_of(batches(config, 9, 1)[0]),
                fetch_list=[out["loss"]])
    scopes = set()
    for table in profiler.device_scopes().values():
        scopes |= set(table.values())
    for want in ("ssm_mixer/proj", "ssm_mixer/conv", "ssm_mixer/scan",
                 "ssm_mixer/norm", "grouped_attention/attn_full",
                 "moe_ffn/route", "moe_ffn/experts", "moe_ffn/shared"):
        for phase in ("forward", "backward"):
            assert "%s/%s" % (phase, want) in scopes, (want, sorted(scopes))
    scans = tune.counters()["ssm_scans"]
    assert set(scans) == {"Q8 x4 h8 g2 n8 p8 pallas"}
    assert exe.stats["ssm_scans"] == scans


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """Each of 16 chips holds 1 of 16 experts. The sixteen shares' routed
    parts, plus the shared expert and the stream counted once, are the
    uncut reference's expert half-layer."""
    config = tiny(False)
    specs = ref.leaf_specs(config)
    words = ref.key_data(21)
    leaves = dict(zip([n for n, _s in specs],
                      ref.init_leaves(words, config)))
    bias = ref.init_router_biases(words, config)[0]
    names = ("norm", "router", "expert_up", "expert_down", "shared_up",
             "shared_down")
    ffn = [leaves["L0.ffn." + n] for n in names]
    x = jax.random.normal(jax.random.PRNGKey(3), (ROWS, SEQ, 64))
    flat = x.reshape(-1, 64)
    whole, _picks = ref.expert_ffn(flat, ffn, bias, config, "f32", None)
    t = ref.rms_norm(flat, ffn[0], config["layer_norm_epsilon"])
    shared = ref.squared_relu(t, ffn[4], ffn[5], "f32", None)
    total, rows = np.asarray(flat + shared), 0
    for share in range(16):
        main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
        with pt.program_guard(main, startup):
            xv = L.data("x", shape=[SEQ, 64], dtype="float32")
            out, load, n_held = L.moe_ffn(
                xv, 16, 3, 32, 48, experts_held=(share, 1), scaling=2.5,
                epsilon=config["layer_norm_epsilon"], activation="relu2",
                prefix="m")
        exe = pt.Executor(pt.CPUPlace())
        with pt.scope_guard(scope):
            exe.run(startup)
            for name, leaf in zip(names, ffn):
                leaf = np.asarray(leaf)
                if name.startswith("expert_"):
                    leaf = leaf[share:share + 1]
                scope.set_var("m." + name, leaf)
            scope.set_var("m.router_bias", np.asarray(bias))
            got, got_load, got_held = exe.run(
                main, feed={"x": np.asarray(x)},
                fetch_list=[out, load, n_held])
        assert int(got_held.sum()) == int(got_load[share])
        rows += int(got_held.sum())
        total = total + (got.reshape(-1, 64) - np.asarray(flat + shared))
    assert rows == ROWS * SEQ * 3       # every pair landed on one share
    np.testing.assert_allclose(total, np.asarray(whole), rtol=2e-4,
                               atol=2e-5)


def test_squared_relu_experts_of_the_published_width_on_the_kernels():
    """An expert layer of the published expert width (1,856, no multiple
    of 128) holding 4 of 8 experts, through the grouped-matmul kernels in
    interpret mode: its result and every gradient against the reference's
    dense expert layer; two products a rung (no gate), whose launches are
    counted."""
    from paddle_tpu import tune
    d, w, sw, E = 128, 1856, 256, 8
    config = dict(tiny(False), hidden_size=d, moe_intermediate_size=w,
                  moe_shared_expert_intermediate_size=sw, n_routed_experts=E,
                  experts_held=[2, 4])
    rng = np.random.RandomState(4)
    mk = lambda *shape, s=0.05: (s * rng.randn(*shape)).astype(np.float32)
    ffn = [1.0 + mk(d, s=0.1), mk(d, E, s=0.3), mk(4, d, w), mk(4, w, d),
           mk(d, sw), mk(sw, d)]
    bias = mk(E, s=0.02)
    x = mk(1, 64, d, s=1.0)
    cot = mk(1, 64, d, s=1.0)
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        xv = L.data("x", shape=[64, d], dtype="float32")
        xv.stop_gradient = False
        out, _load, _held = L.moe_ffn(
            xv, E, 3, w, sw, experts_held=(2, 4), scaling=2.5,
            epsilon=1e-5, activation="relu2", prefix="m")
        loss = L.reduce_sum(L.elementwise_mul(out, L.assign(cot)))
        pt.append_backward(loss)
    names = ("norm", "router", "expert_up", "expert_down", "shared_up",
             "shared_down")
    exe = pt.Executor(pt.CPUPlace())
    tune.reset_counters()
    with pt.scope_guard(scope):
        exe.run(startup)
        for name, leaf in zip(names, ffn):
            scope.set_var("m." + name, leaf)
        scope.set_var("m.router_bias", bias)
        got = exe.run(main, feed={"x": x},
                      fetch_list=[out, "x@GRAD"]
                      + ["m.%s@GRAD" % n for n in names])
    assert set(k.split()[0] for k in tune.counters()["gmm_blocks"]) == {
        "gmm", "gmm_t", "tgmm"}

    def whole(xx, *leaves):
        y, _picks = ref.expert_ffn(xx.reshape(-1, d), list(leaves),
                                   jnp.asarray(bias), config, "f32", None)
        return y
    want, vjp = jax.vjp(whole, jnp.asarray(x), *map(jnp.asarray, ffn))
    grads = vjp(jnp.asarray(cot).reshape(-1, d))
    np.testing.assert_allclose(got[0].reshape(-1, d), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    for name, g, ref_g in zip(("x",) + names, got[1:], grads):
        scale = float(jnp.max(jnp.abs(ref_g)))
        np.testing.assert_allclose(np.asarray(g).reshape(ref_g.shape),
                                   np.asarray(ref_g), rtol=2e-4,
                                   atol=2e-5 * scale, err_msg=name)


def test_the_cells_configuration_is_the_published_one_cut_as_it_says():
    """``chipbench/configs/nemotron-3-nano-30b-a3b-ep16.json`` through its
    network module: the model accepts it, published layers 0-8
    (MEMEM*EME), 666,963,456 trainable parameters, 17.64 TFLOP an
    8,192-token row."""
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "nemotron-3-nano-30b-a3b-ep16.json")) as f:
        config = json.load(f)
    network = _load("networks", "hybrid_ssm_moe_lm.py")
    model = network.model_config(config)
    check_config(model)
    assert model["hybrid_override_pattern"] == "MEMEM*EME"
    assert len(config["hybrid_override_pattern"]) == 52
    assert model["published_layers"] == 52
    assert model["experts_held"] == [0, 8]
    assert model["vocab_held"] == [0, 16384]
    specs = ref.leaf_specs(model)
    # the four selection biases (128 each, no gradient) are no leaf
    assert sum(int(np.prod(s)) for _n, s in specs) + 4 * 128 == 666963456
    assert [n for n, _s in specs if n.endswith("router")] == [
        "L%d.ffn.router" % i for i in range(4)]
    assert network.scan_flops_per_token(config) == 3407872
    assert network.train_flops_per_row(config, None, 8192) == pytest.approx(
        17.64e12, rel=2e-3)
    counts = network.attention_flops_per_step(config, 1, 8192)
    assert counts["window"] == 0
    assert counts["full"] == 3 * 4 * 32 * 128 * (8192 * 8193 // 2)
    assert counts["ssm_scan"] == 3 * 4 * 8192 * 3407872
    # a roofline of ~2.1 ms a step, bound by the bytes
    assert counts["ssm_scan_bytes"] / 819e9 == pytest.approx(2.15e-3,
                                                             rel=1e-2)
    assert network.expert_flops_per_step(config, 1) == 12 * 2688 * 1856
    for key, value in (("first_k_dense_replace", 1), ("layers_kept", [0])):
        with pytest.raises(ValueError, match=key):
            network.model_config(dict(config, **{key: value}))

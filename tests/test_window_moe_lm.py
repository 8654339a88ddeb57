"""``models.window_moe_lm`` (grouped-query attention with QK norms, an
output gate and sliding-window / full layers, sandwich norms, a sigmoid
top-k router with shared experts) against the plain reference
``chipbench/reference/window_moe_lm.py`` on seeded weights, CPU, float32,
at a small size: rows of three windows; whole and as one chip's share of an
expert-parallel group."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu.models.window_moe_lm import window_moe_lm

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    path = os.path.join(_ROOT, "chipbench", *parts)
    spec = importlib.util.spec_from_file_location(
        "t_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference", "window_moe_lm.py")

SEQ, ROWS, WINDOW = 48, 2, 16
HALVES = ("grouped_attention", "gated_ffn", "moe_ffn")
OPT = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}


def tiny(share):
    """d 64, 4 query heads on 2 key/value heads of 16, windows of 16 keys
    on layers 0 and 1 and none on layer 2, 8 experts top-2, 1 dense + 2
    expert layers, vocabulary 256; ``share``: 4 experts and 64 rows held,
    neither range starting at 0."""
    config = dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
        route_scale=2.826, route_norm=True, num_dense_layers=1,
        num_hidden_layers=3, sliding_window=WINDOW,
        layer_types=["sliding_attention", "sliding_attention",
                     "full_attention"],
        rms_norm_eps=1e-5, rope_theta=10000, vocab_size=256,
        mup_enabled=True, n_group=1, topk_group=1, score_func="sigmoid",
        rope_scaling=None, tie_word_embeddings=False, hidden_act="silu")
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
    reference's seeded leaves and router biases in the scope."""
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        tokens = L.data("tokens", shape=[SEQ], dtype="int64")
        labels = L.data("labels", shape=[SEQ], dtype="int64")
        out = window_moe_lm(tokens, config, labels=labels)
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
        dense = config["num_dense_layers"]
        for i, b in enumerate(ref.init_router_biases(words, config)):
            scope.set_var("L%d.ffn.router_bias" % (dense + i), np.asarray(b))
    return exe, main, scope, out, [n for n, _s in specs]


def feed_of(batch):
    return {"tokens": batch[0], "labels": batch[1]}


def reference_loss(config, seed, batch, **more):
    words = ref.key_data(seed)
    return ref.loss_fn(ref.init_leaves(words, config),
                       ref.init_router_biases(words, config),
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


def test_the_reference_in_query_blocks_is_the_reference_in_one(monkeypatch):
    """Its dense attention runs a block of query rows at a time; blocks of
    a third of the row give what the whole row gives."""
    config = tiny(False)
    batch = batches(config, 4, 1)[0]
    whole = reference_loss(config, 3, batch)
    monkeypatch.setattr(ref, "Q_BLOCK", SEQ // 3)
    np.testing.assert_allclose(float(reference_loss(config, 3, batch)),
                               float(whole), rtol=1e-6)


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


def test_positions_and_windows_are_on_the_sliding_layers_only():
    config = tiny(False)
    _exe, main, _scope, _out, _names = program(config, 1)
    attn = [op for op in main.global_block().ops
            if op.type == "grouped_attention"]
    assert [op.attr("rotary") for op in attn] == [True, True, False]
    assert [op.attr("window") for op in attn] == [WINDOW, WINDOW, 0]
    assert {op.attr("kv_heads") for op in attn} == {2}


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_reference_and_not_the_program(fault):
    config = tiny(True)
    batch = batches(config, 7, 1)[0]
    clean = float(reference_loss(config, 19, batch))
    # (a post norm hides most of a pick weight's SIZE from the loss: the
    # weights left unnormalised move it by 3e-5 of itself, 60 roundings)
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
    ("score_func", "softmax"), ("n_group", 8), ("topk_group", 4),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("route_norm", False),
    ("layer_types", ["sliding_attention", "chunked_attention",
                     "full_attention"])])
def test_a_key_the_block_cannot_honour_raises(key, value):
    config = dict(tiny(False), **{key: value})
    with pt.program_guard(pt.Program(), pt.Program()):
        tokens = L.data("tokens", shape=[SEQ], dtype="int64")
        with pytest.raises(NotImplementedError, match=key):
            window_moe_lm(tokens, config)


def test_layer_types_of_another_length_raises():
    config = dict(tiny(False), num_hidden_layers=2)
    with pt.program_guard(pt.Program(), pt.Program()):
        tokens = L.data("tokens", shape=[SEQ], dtype="int64")
        with pytest.raises(ValueError, match="layer_types"):
            window_moe_lm(tokens, config)


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


def test_a_compiled_step_has_the_new_part_scopes():
    from paddle_tpu import profiler
    config = tiny(True)
    exe, main, scope, out, _names = program(config, 14)
    with pt.scope_guard(scope):
        exe.run(main, feed=feed_of(batches(config, 9, 1)[0]),
                fetch_list=[out["loss"]])
    scopes = set()
    for table in profiler.device_scopes().values():
        scopes |= set(table.values())
    for part in ("proj", "rope", "gate", "attn_window", "attn_full",
                 "post_norm"):
        for phase in ("forward", "backward"):
            want = "%s/grouped_attention/%s" % (phase, part)
            assert want in scopes, (want, sorted(scopes))
    for want in ("forward/moe_ffn/post_norm", "backward/moe_ffn/post_norm",
                 "forward/moe_ffn/experts", "forward/gated_ffn/post_norm",
                 "update/adam"):
        assert want in scopes, (want, sorted(scopes))


def _moe_leaves(config, seed):
    specs = ref.leaf_specs(config)
    leaves = dict(zip([n for n, _s in specs],
                      ref.init_leaves(ref.key_data(seed), config)))
    names = ("norm", "router", "expert_gate", "expert_up", "expert_down",
             "shared_gate", "shared_up", "shared_down", "post_norm")
    return names, [leaves["L2.ffn." + n] for n in names]


def test_the_eight_shares_add_up_only_under_one_norm_of_their_sum():
    """Each of 8 chips holds 1 of 8 experts. A post norm of a partial sum
    is no part of the whole: the eight shares' routed parts (post norm
    off) plus the shared expert counted once, THEN the post norm and the
    residual add, are the uncut reference's layer; with the norm taken on
    each share they are not."""
    config = tiny(False)
    names, ffn = _moe_leaves(config, 21)
    bias = ref.init_router_biases(ref.key_data(21), config)[1]
    # a stream as small as the layer's own part, so that taking it off a
    # share's output again costs no digits (the norm divides by that part)
    x = jax.random.normal(jax.random.PRNGKey(3), (ROWS, SEQ, 64)) * 0.02
    flat = x.reshape(-1, 64)
    whole, _picks = ref.expert_ffn(flat, ffn, bias, config, "f32", None)
    t = ref.rms_norm(flat, ffn[0], config["rms_norm_eps"])
    shared = ref.gated(t, ffn[5], ffn[6], ffn[7], "f32")
    routed = {False: 0.0, True: 0.0}        # by "post norm on the share"
    rows = 0
    for share in range(8):
        for normed in (False, True):
            main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
            with pt.program_guard(main, startup):
                xv = L.data("x", shape=[SEQ, 64], dtype="float32")
                out, load, held = L.moe_ffn(
                    xv, 8, 2, 32, 32, experts_held=(share, 1),
                    scaling=2.826, epsilon=config["rms_norm_eps"],
                    post_norm=normed, prefix="m")
            exe = pt.Executor(pt.CPUPlace())
            with pt.scope_guard(scope):
                exe.run(startup)
                for name, leaf in zip(names, ffn):
                    leaf = np.asarray(leaf)
                    if name.startswith("expert_"):
                        leaf = leaf[share:share + 1]
                    if normed or name != "post_norm":
                        scope.set_var("m." + name, leaf)
                scope.set_var("m.router_bias", np.asarray(bias))
                got, n_load, n_held = exe.run(
                    main, feed={"x": np.asarray(x)},
                    fetch_list=[out, load, held])
            routed[normed] = routed[normed] + (
                got.reshape(-1, 64) - np.asarray(flat))
            if not normed:
                assert int(n_held.sum()) == int(n_load[share])
                rows += int(n_held.sum())
                routed[False] = routed[False] - np.asarray(shared)
    assert rows == ROWS * SEQ * 2       # every pair landed on one share
    total = flat + ref.rms_norm(shared + routed[False], ffn[8],
                                config["rms_norm_eps"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-4, atol=2e-5)
    # eight normed shares: each about as large as the whole layer's part
    normed_sum = np.asarray(flat) + routed[True]
    assert np.abs(normed_sum - np.asarray(whole)).max() > 0.5


def test_the_half_split_rotary_layer_matches_the_reference():
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        x = L.data("x", shape=[SEQ, 4, 16], dtype="float32")
        turned = L.rotary_embedding(x, theta=1e4, layout="half")
    exe = pt.Executor(pt.CPUPlace())
    xv = np.random.default_rng(0).standard_normal(
        (ROWS, SEQ, 4, 16)).astype(np.float32)
    with pt.scope_guard(scope):
        exe.run(startup)
        got, = exe.run(main, feed={"x": xv}, fetch_list=[turned])
    for r in range(ROWS):
        np.testing.assert_allclose(got[r], ref.rotary(xv[r], 1e4),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[:, 0], xv[:, 0], rtol=1e-6)
    # HF rotate_half: out = x cos + cat(-x2, x1) sin, cos = cat(f, f)
    i = np.arange(8)
    ang = np.arange(SEQ)[:, None] * 1e4 ** (-2.0 * i / 16)[None, :]
    cos = np.concatenate([np.cos(ang)] * 2, -1)[None, :, None, :]
    sin = np.concatenate([np.sin(ang)] * 2, -1)[None, :, None, :]
    half = np.concatenate([-xv[..., 8:], xv[..., :8]], -1)
    np.testing.assert_allclose(got, xv * cos + half * sin, rtol=1e-4,
                               atol=1e-5)


def test_the_cells_configuration_is_the_published_one_cut_as_it_says():
    """``chipbench/configs/trinity-mini-ep8.json`` through its network
    module: the model accepts it, layers 0 and 4-7 of the published
    pattern, 705.5 M trainable parameters and 18.1 TFLOP a step."""
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           "trinity-mini-ep8.json")) as f:
        config = json.load(f)
    network = _load("networks", "window_moe_lm.py")
    model = network.model_config(config)
    from paddle_tpu.models.window_moe_lm import check_config
    check_config(model)
    assert model["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"]
    assert len(config["layer_types"]) == 32
    assert model["experts_held"] == [0, 16]
    assert model["vocab_held"] == [0, 25024]
    count = sum(int(np.prod(s)) for _n, s in ref.leaf_specs(model))
    assert count == 705473792          # 8.47 GB of f32 state at 12 B
    assert network.matmul_params_per_token(config) == pytest.approx(
        276.7e6, rel=1e-3)
    assert network.seen_pairs(8192, 2048) == 14681088
    assert network.seen_pairs(8192) == 33558528
    flops = network.attention_flops_per_step(config, 1, 8192)
    assert flops["window"] == 3 * 4 * 14681088 * 32 * 512
    assert flops["full"] == 3 * 33558528 * 32 * 512
    assert network.train_flops_per_row(config, None, 8192) == pytest.approx(
        18.1e12, rel=5e-3)
    with pytest.raises(ValueError, match="num_experts"):
        network.model_config(dict(config, n_routed_experts=64))

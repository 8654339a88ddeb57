"""A recomputed op keeps its flash-attention calls' ``o`` and ``lse``:
``memory_optimize``'s ``jax.checkpoint`` (``ops/generic_grad.py``) saves
the two residuals the kernels name (``flash_attention.KEPT``) and
recomputes the rest of the op, so the backward pass runs the dQ and dK/dV
kernels and no second forward kernel. Gradients are those of the
all-recompute form bit for bit; an op that makes no flash call lowers as
it did; ``tune.counters()["flash_kept"]`` counts one per attention layer
with the bytes its shapes give. CPU, interpret mode, tiny sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers as L
from paddle_tpu import tune
from paddle_tpu.core.executor import RngSource, trace_ops
from paddle_tpu.ops import generic_grad

import test_latent_moe_lm as latent
import test_window_moe_lm as window

# every recomputed op's forward again, as it was before the names were kept
RECOMPUTE_ALL = None


def _attention_program(seed):
    """(exe, main, scope, loss, parameter names, feed): the stand-alone
    ``flash_attention`` op (in ``memory_optimize``'s default set) between
    two projections, 2 rows x 40 positions x 4 heads of 16."""
    S, H, D = 40, 4, 16
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        x = L.data("x", shape=[S, H * D], dtype="float32")
        q, k, v = (L.reshape(L.fc(x, H * D, num_flatten_dims=2,
                                  bias_attr=False), [-1, S, H, D])
                   for _ in range(3))
        out = L.fc(L.reshape(L.flash_attention(q, k, v, causal=True),
                             [-1, S, H * D]), H * D, num_flatten_dims=2,
                   bias_attr=False)
        loss = L.mean(out * out)
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        pt.memory_optimize(main)
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
    names = [p.name for p in main.all_parameters()]
    feed = {"x": np.random.RandomState(seed).randn(2, S, H * D).astype(
        np.float32)}
    return exe, main, scope, loss, names, feed


def _decoder(model):
    def build(seed):
        config = model.tiny(False)
        exe, main, scope, out, names = model.program(config, seed)
        feed = model.feed_of(model.batches(config, seed, 1)[0])
        return exe, main, scope, out["loss"], names, feed
    return build


PROGRAMS = {"latent_attention": _decoder(latent),
            "grouped_attention": _decoder(window),
            "flash_attention": _attention_program}


@pytest.mark.parametrize("which", sorted(PROGRAMS))
def test_the_kept_o_and_lse_give_the_all_recompute_gradients_bit_for_bit(
        which, monkeypatch):
    grads = []
    for policy in (generic_grad._KEEP, RECOMPUTE_ALL):
        monkeypatch.setattr(generic_grad, "_KEEP", policy)
        exe, main, scope, _loss, names, feed = PROGRAMS[which](21)
        with pt.scope_guard(scope):
            grads.append(exe.run(main, feed=feed,
                                 fetch_list=[n + "@GRAD" for n in names]))
    assert names
    for name, kept, recomputed in zip(names, *grads):
        assert np.abs(kept).max() > 0, name
        np.testing.assert_array_equal(kept, recomputed, err_msg=name)


def _step(main, scope, feed):
    """(fn(state, feed) -> new state, state, feed): one training step of
    ``main`` as one jax function of its state and feed."""
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        state = {n: jnp.asarray(scope.find_var(n)) for n in exe._state_inputs(
            main, scope, set(feed))}

    def fn(state, feed):
        env = dict(feed, **state)
        trace_ops(main.global_block(), env, RngSource(jax.random.PRNGKey(0)))
        return {n: env[n] for n in state}
    return fn, state, {n: jnp.asarray(v) for n, v in feed.items()}


def _kernels(jaxpr, inside=False, found=None):
    """[(kernel name, inside a recomputed part)] of every Pallas launch of
    ``jaxpr``, in order."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["jaxpr"].debug_info.func_name, inside))
            continue
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernels(sub, inside or eqn.primitive.name in (
                        "checkpoint", "remat2"), found)
    return found


@pytest.mark.parametrize("which", ["latent_attention", "grouped_attention"])
def test_the_recomputed_half_layer_runs_the_backward_kernels_only(
        which, monkeypatch):
    """Traced, a layer's forward kernel appears twice (the forward op's and
    the one its gradient replays, which XLA merges on the chip:
    ``tests/test_chip_compile.py``), and never inside the recomputed part;
    dQ and dK/dV are there. All-recompute, the forward kernel is in it
    too."""
    layers = 3
    for policy, forward_inside in ((generic_grad._KEEP, 0),
                                   (RECOMPUTE_ALL, layers)):
        monkeypatch.setattr(generic_grad, "_KEEP", policy)
        _exe, main, scope, _loss, _names, feed = PROGRAMS[which](22)
        fn, state, feed = _step(main, scope, feed)
        found = _kernels(jax.make_jaxpr(fn)(state, feed).jaxpr)
        inside = [k for k, recomputed in found if recomputed]
        assert inside.count("_fa_kernel") == forward_inside, found
        assert inside.count("_fa_bwd_dq_kernel") == layers, found
        assert inside.count("_fa_bwd_dkv_kernel") == layers, found
        # and outside it the forward op's and the replayed forward's
        assert [k for k, _r in found].count("_fa_kernel") == (
            2 * layers + forward_inside)


def _one_op_program(op):
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        x = L.data("x", shape=[16, 32], dtype="float32")
        if op == "rms_norm":
            out = L.rms_norm(x)
        elif op == "gated_ffn":
            out = L.gated_ffn(x, 48, prefix="f")
        else:
            out, _load, _held = L.moe_ffn(x, 8, 2, 16, 16, prefix="m")
        loss = L.mean(out * out)
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        pt.memory_optimize(main, remat_types=(op,))
    with pt.scope_guard(scope):
        pt.Executor(pt.CPUPlace()).run(startup)
    feed = {"x": np.random.RandomState(1).randn(2, 16, 32).astype(
        np.float32)}
    return main, scope, feed


@pytest.mark.parametrize("op", ["rms_norm", "gated_ffn", "moe_ffn"])
def test_an_op_without_a_flash_call_lowers_as_it_did(op, monkeypatch):
    texts = []
    for policy in (generic_grad._KEEP, RECOMPUTE_ALL):
        monkeypatch.setattr(generic_grad, "_KEEP", policy)
        main, scope, feed = _one_op_program(op)
        assert any(o.type == "generic_grad" and o.attr("__fwd_type__") == op
                   for o in main.global_block().ops)
        fn, state, feed = _step(main, scope, feed)
        # by position: the two builds name their variables apart
        names = sorted(state)

        def by_position(values, feed):
            new = fn(dict(zip(names, values)), feed)
            return [new[n] for n in names]
        texts.append(jax.jit(by_position).lower(
            [state[n] for n in names], feed).as_text())
    assert texts[0] == texts[1]


# [(forward launch, attention layers at it)] of each program, and the
# bytes a layer keeps: o [rows x heads, S, v head] and lse [rows x heads,
# S], f32 (2 rows, 4 heads, v heads of 16)
KEPT = {
    "latent_attention": [("fwd 32x32", 3)],
    "grouped_attention": [("fwd 48x48 g2 w16", 2), ("fwd 48x48 g2", 1)],
    "flash_attention": [("fwd 40x40", 1)],
}
LAYER_BYTES = {"latent_attention": 2 * 4 * 32 * (16 + 1) * 4,
               "grouped_attention": 2 * 4 * 48 * (16 + 1) * 4,
               "flash_attention": 2 * 4 * 40 * (16 + 1) * 4}


@pytest.mark.parametrize("which", sorted(PROGRAMS))
def test_flash_kept_counts_one_per_attention_layer_with_its_bytes(which):
    exe, main, scope, loss, _names, feed = PROGRAMS[which](23)
    tune.reset_counters()
    with pt.scope_guard(scope):
        exe.run(main, feed=feed, fetch_list=[loss])
    counted = tune.counters()
    layers = sum(n for _name, n in KEPT[which])
    assert counted["flash_kept"] == dict(KEPT[which])
    assert counted["flash_kept_bytes"] == layers * LAYER_BYTES[which]
    assert exe.stats["flash_kept"] == counted["flash_kept"]
    assert exe.stats["flash_kept_bytes"] == counted["flash_kept_bytes"]
    tune.reset_counters()
    assert tune.counters()["flash_kept"] == {}
    assert tune.counters()["flash_kept_bytes"] == 0


def test_the_scope_table_header_prints_what_was_kept(capsys):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "tools", "scope_table.py")
    spec = importlib.util.spec_from_file_location("scope_table", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    exe, main, scope, loss, _names, feed = PROGRAMS["flash_attention"](24)
    tune.reset_counters()
    with pt.scope_guard(scope):
        exe.run(main, feed=feed, fetch_list=[loss])
    tool.report("step", 1, {}, tune.counters())
    assert "flash_kept: fwd 40x40 x1; flash_kept_bytes %d" % LAYER_BYTES[
        "flash_attention"] in capsys.readouterr().out
    tune.reset_counters()

"""paddle_tpu.comm: bucketed / hierarchical / quantized gradient
communication, on the forced 8-virtual-device CPU mesh (conftest's
``dp8_mesh`` fixture).

Acceptance anchors (ISSUE 5): the ``none`` policy is BIT-identical to
the bare per-leaf pmean path it replaced; fused + hierarchical match it
within fp32 reduction tolerance; int8 with error feedback trains to
within 2% relative final loss of fp32; a forced ``comm.quantize`` fault
falls back to full precision with a recorded ``comm_degraded`` event
while the step loop survives; bucketing reduces collective dispatches
below the parameter count.
"""
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import comm
from paddle_tpu.comm import (CommPolicy, build_plan, flatten_to_buckets,
                             unflatten_from_buckets, hierarchical_all_reduce,
                             quantized_all_reduce, bytes_on_wire,
                             quantized_reduce_scatter_all_gather)
from paddle_tpu.comm.quant import quantize, dequantize
from paddle_tpu.flags import flags_guard
from paddle_tpu.parallel import data_parallel_step_fn, make_mesh
from paddle_tpu import resilience as R
from paddle_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _clean_faults_events():
    faults.reset()
    R.clear_events()
    yield
    faults.reset()
    R.clear_events()


def _grad_tree(seed=0, n_extra=0):
    rng = np.random.RandomState(seed)
    tree = {
        "w1": jnp.asarray(rng.randn(64, 32).astype(np.float32)),
        "b1": jnp.asarray(rng.randn(32).astype(np.float32)),
        "emb": jnp.asarray(rng.randn(128, 16).astype(np.float32)),
        "step": jnp.asarray(np.int32(7)),
        "w2_bf16": jnp.asarray(rng.randn(16, 8).astype(np.float32)
                               ).astype(jnp.bfloat16),
    }
    for i in range(n_extra):
        tree["x%02d" % i] = jnp.asarray(
            rng.randn(10, 10).astype(np.float32))
    return tree


# ---------------------------------------------------------------------------
# bucket plan + round trip


def test_bucket_roundtrip_exact():
    tree = _grad_tree(n_extra=5)
    plan = build_plan(tree, bucket_bytes=2048, pad_multiple=4)
    flats = flatten_to_buckets(plan, tree)
    for b, f in zip(plan.buckets, flats):
        assert f.ndim == 1 and f.dtype == b.dtype
        assert f.shape[0] == b.numel + b.pad
        assert f.shape[0] % 4 == 0
    back = unflatten_from_buckets(plan, flats)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(tree)
    for a, b_ in zip(jax.tree_util.tree_leaves(tree),
                     jax.tree_util.tree_leaves(back)):
        assert a.dtype == b_.dtype and a.shape == b_.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_bucket_plan_dtype_homogeneous_and_bounded():
    tree = _grad_tree(n_extra=8)
    bound = 1024  # bytes; several leaves exceed it -> own buckets
    plan = build_plan(tree, bucket_bytes=bound)
    for b in plan.buckets:
        assert len({b.dtype}) == 1
        payload = b.numel * np.dtype(b.dtype).itemsize
        # a bucket only exceeds the bound when a single leaf does
        if payload > bound:
            assert len(b.leaf_ids) == 1
    # every leaf lands in exactly one bucket, in order
    seen = [i for b in plan.buckets for i in b.leaf_ids]
    assert sorted(seen) == list(range(plan.n_leaves))


def test_bucketing_reduces_dispatches():
    """The fusion claim: far fewer collectives than parameters."""
    tree = {"p%02d" % i: jnp.ones((8, 8), jnp.float32) for i in range(24)}
    plan = build_plan(tree, bucket_bytes=4 * 1024 * 1024)
    assert plan.num_buckets < len(tree)
    assert plan.num_buckets == 1  # 24 * 256B fits one 4MiB bucket


# ---------------------------------------------------------------------------
# collective kernels


def test_hierarchical_all_reduce_is_mean(dp8_mesh):
    x = np.random.RandomState(3).randn(8, 64).astype(np.float32)

    def body(v):
        return hierarchical_all_reduce(
            jax.lax.squeeze(v, (0,)), "dp", hosts=2)[None]

    out = comm.shard_map(body, dp8_mesh, in_specs=P("dp"),
                         out_specs=P("dp"))(x)
    np.testing.assert_allclose(np.asarray(out),
                               np.tile(x.mean(0), (8, 1)), rtol=2e-6)


def test_hierarchical_rejects_bad_factorisation(dp8_mesh):
    x = np.random.RandomState(3).randn(8, 60).astype(np.float32)

    def body(v):
        return hierarchical_all_reduce(
            jax.lax.squeeze(v, (0,)), "dp", hosts=3)[None]

    with pytest.raises(ValueError, match="not divisible by hosts"):
        comm.shard_map(body, dp8_mesh, in_specs=P("dp"),
                       out_specs=P("dp"))(x)


def test_quantize_roundtrip_error_bound():
    rng = np.random.RandomState(5)
    v = jnp.asarray(rng.randn(1000).astype(np.float32) * 3.0)
    q, scales, n = quantize(v, chunk=128)
    assert q.dtype == jnp.int8 and n == 1000
    back = dequantize(q, scales, n)
    # symmetric quantisation error is at most half a step per chunk
    step = np.asarray(scales).max()
    assert float(jnp.abs(back - v).max()) <= step / 2 + 1e-7
    # zeros quantise exactly
    zq, zs, zn = quantize(jnp.zeros(64), chunk=64)
    np.testing.assert_array_equal(np.asarray(dequantize(zq, zs, zn)), 0.0)


def test_quantized_all_reduce_dynamic_range_fallback(dp8_mesh):
    """A non-finite value anywhere on the axis trips the psum'd vote and
    the exact full-precision branch runs (fell_back=1)."""
    good = np.random.RandomState(1).randn(8, 32).astype(np.float32)
    bad = good.copy()
    bad[3, 7] = np.inf

    def body(v):
        out, res, fell = quantized_all_reduce(
            jax.lax.squeeze(v, (0,)), "dp", chunk=16)
        return out[None], res[None], fell[None]

    f = comm.shard_map(body, dp8_mesh, in_specs=P("dp"),
                       out_specs=(P("dp"), P("dp"), P("dp")))
    out, res, fell = f(good)
    assert int(np.asarray(fell).sum()) == 0
    np.testing.assert_allclose(np.asarray(out)[0], good.mean(0), atol=0.05)
    out2, res2, fell2 = f(bad)
    assert int(np.asarray(fell2).sum()) == 8  # every device took the branch
    # exact branch = plain pmean (inf propagates faithfully, residual 0)
    assert np.isinf(np.asarray(out2)[0, 7])
    np.testing.assert_array_equal(np.asarray(res2), 0.0)


# ---------------------------------------------------------------------------
# policy resolution + bytes model


def test_policy_resolution_from_flags():
    with flags_guard(comm_policy="fused", comm_bucket_mb=1.0,
                     comm_quant="int8", comm_hosts=2):
        p = comm.resolve_policy(axis_size=8)
    assert p.base == "fused" and p.quant == "int8"
    assert p.bucket_bytes == 1024 * 1024 and p.hosts == 2
    # quant over the none base promotes to fused (needs the flat form)
    assert CommPolicy(base="none", quant="int8").base == "fused"
    with pytest.raises(ValueError, match="policy base"):
        CommPolicy(base="bogus")
    with pytest.raises(ValueError, match="quant"):
        CommPolicy(quant="fp4")


def test_bytes_on_wire_model():
    B = 1024 * 1024
    n = 8
    flat = bytes_on_wire(B, CommPolicy(base="fused"), n)
    assert flat == int(2 * 7 / 8 * B)
    assert bytes_on_wire(B, CommPolicy(base="none"), n) == flat
    h = bytes_on_wire(B, CommPolicy(base="hierarchical", hosts=2), n)
    # intra RS+AG over 4 chips + inter ring on the quarter chunk
    assert h == int(2 * 3 / 4 * B) + B // 4
    q = bytes_on_wire(B, CommPolicy(base="fused", quant="int8"), n)
    assert q == 7 * (B // 4 + (B // 4 // 256) * 4)
    # honest model: the gather-based int8 form scales (n-1)*B/4 vs the
    # ring's 2(n-1)/n*B — it wins bytes only BELOW n=8 (ties at 8, the
    # scale overhead tips it over). The scalable int8 shape is the
    # hierarchical policy, whose quantised inter-host chunk beats the
    # fp32 hierarchical form at any host count:
    assert bytes_on_wire(B, CommPolicy(base="fused", quant="int8"), 4) \
        < bytes_on_wire(B, CommPolicy(base="fused"), 4)
    hq = bytes_on_wire(
        B, CommPolicy(base="hierarchical", quant="int8", hosts=2), n)
    assert hq < h
    assert bytes_on_wire(B, CommPolicy(), 1) == 0


def test_accounting_comm_policy_table(dp8_mesh):
    from paddle_tpu import layers
    from paddle_tpu.parallel import accounting
    x = layers.data("x", shape=[16], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=32, act="relu")
    pred = layers.fc(h, size=4, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    pt.SGD(learning_rate=0.1).minimize(loss)
    table = accounting.comm_policy_table(
        pt.default_main_program(), {}, {"dp": 8}, hosts=2)
    assert table["axis_size"] == 8
    assert table["dp_synced_param_bytes"] > 0
    rows = {r["policy"]: r for r in table["policies"]}
    assert set(rows) == {"none", "fused", "hierarchical", "fused+int8",
                         "fused+int8_2shot", "hierarchical+int8",
                         "multipath", "multipath+int8"}
    # fusion: fewer dispatches than parameters; same bytes as none
    assert rows["fused"]["collective_dispatches"] < \
        rows["none"]["collective_dispatches"]
    assert rows["fused"]["bytes_per_chip"] == rows["none"]["bytes_per_chip"]
    # topology: hierarchical puts ~1/chips of the flat stream on the
    # inter-host link
    assert rows["hierarchical"]["inter_host_bytes_per_link"] < \
        rows["none"]["inter_host_bytes_per_link"] / 4
    # quantisation: int8 shrinks inter-host bytes further
    assert rows["hierarchical+int8"]["inter_host_bytes_per_link"] < \
        rows["hierarchical"]["inter_host_bytes_per_link"]
    # 2-shot: the scalable int8 form — beats the gather form at n=8
    assert rows["fused+int8_2shot"]["bytes_per_chip"] < \
        rows["fused+int8"]["bytes_per_chip"]
    # multipath: the per-path columns decompose the per-chip total and
    # carry the configured split ratio
    mp = rows["multipath"]
    assert mp["split_ratio"] is not None
    assert mp["bytes_primary_path"] + mp["bytes_secondary_path"] == \
        mp["bytes_per_chip"]
    # non-multipath rows put everything on the primary path
    assert rows["fused"]["bytes_secondary_path"] == 0
    assert rows["fused"]["split_ratio"] is None


def test_accounting_cli_verb(tmp_path, capsys):
    cfg = tmp_path / "cfg.py"
    cfg.write_text(
        "import paddle_tpu as pt\n"
        "from paddle_tpu import layers\n"
        "def model():\n"
        "    x = layers.data('x', shape=[8], dtype='float32')\n"
        "    y = layers.data('y', shape=[1], dtype='int64')\n"
        "    p = layers.fc(x, size=4, act='softmax')\n"
        "    loss = layers.mean(layers.cross_entropy(p, y))\n"
        "    pt.SGD(learning_rate=0.1).minimize(loss)\n"
        "    return {'cost': loss, 'feed_list': ['x', 'y'],\n"
        "            'reader': None}\n")
    from paddle_tpu import cli
    rc = cli.main(["accounting", str(cfg), "--mesh", "dp=8", "--hosts", "2"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["mesh"] == {"dp": 8}
    assert report["comm"]["dp_synced_param_bytes"] > 0
    assert len(report["comm"]["policies"]) == 8
    assert "dp_grad_allreduce" in report["collectives"]
    assert all("bytes_primary_path" in row
               for row in report["comm"]["policies"])
    # --split-ratio parameterises the multipath rows
    rc2 = cli.main(["accounting", str(cfg), "--mesh", "dp=8", "--hosts",
                    "2", "--split-ratio", "0.5"])
    assert rc2 == 0
    report2 = json.loads(capsys.readouterr().out)
    mp = [r for r in report2["comm"]["policies"]
          if r["policy"] == "multipath"][0]
    assert mp["split_ratio"] == 0.5


# ---------------------------------------------------------------------------
# end-to-end DP training parity (the acceptance matrix)


def _mlp_loss(p, x, y):
    h = jnp.maximum(x @ p["w1"] + p["b1"], 0)
    logits = h @ p["w2"] + p["b2"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))


def _mlp_params(seed=0, feat=16, hidden=32, classes=4):
    rng = np.random.RandomState(seed)
    s = np.sqrt(2.0 / feat)
    return {"w1": jnp.asarray(rng.randn(feat, hidden).astype(np.float32) * s),
            "b1": jnp.zeros((hidden,), jnp.float32),
            "w2": jnp.asarray(
                rng.randn(hidden, classes).astype(np.float32) * 0.1),
            "b2": jnp.zeros((classes,), jnp.float32)}


def _mlp_data(seed=0, n=64, feat=16, classes=4):
    rng = np.random.RandomState(seed)
    w = np.random.RandomState(99).randn(feat, classes)
    x = rng.rand(n, feat).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int64)
    return x, y


def _train(mesh, policy, steps=9, lr=0.1, seed=0):
    """'3-pass' run: 3 batches x 3 passes = 9 steps."""
    step, state0 = data_parallel_step_fn(_mlp_loss, mesh, policy=policy)
    params = _mlp_params(seed)
    state = state0(params)
    batches = [_mlp_data(seed=s) for s in range(3)]
    losses = []
    for i in range(steps):
        x, y = batches[i % 3]
        loss, params, state = step(params, state, x, y, lr)
        losses.append(float(loss))
    return losses, params, state


def _bare_pmean_train(mesh, steps=9, lr=0.1, seed=0):
    """The pre-comm sync path, verbatim: per-leaf lax.pmean."""
    rep = P()
    xspec = P("dp")

    def per_device(p, x, y, lr_):
        loss, grads = jax.value_and_grad(_mlp_loss)(p, x, y)
        loss = jax.lax.pmean(loss, "dp")
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, "dp"), grads)
        return loss, jax.tree_util.tree_map(
            lambda a, g: a - lr_ * g, p, grads)

    params = _mlp_params(seed)
    pspecs = jax.tree_util.tree_map(lambda _: rep, params)
    stepf = jax.jit(comm.shard_map(
        per_device, mesh, in_specs=(pspecs, xspec, xspec, rep),
        out_specs=(rep, pspecs)))
    batches = [_mlp_data(seed=s) for s in range(3)]
    losses = []
    for i in range(steps):
        x, y = batches[i % 3]
        loss, params = stepf(params, x, y, jnp.float32(lr))
        losses.append(float(loss))
    return losses


def test_none_policy_bit_identical_to_bare_psum(dp8_mesh):
    bare = _bare_pmean_train(dp8_mesh)
    ours, _, state = _train(dp8_mesh, CommPolicy(base="none"))
    assert ours == bare  # BIT-identical, not allclose
    assert int(state["comm_quant_fallbacks"]) == 0


def test_fused_and_hierarchical_match_within_tolerance(dp8_mesh):
    ref, _, _ = _train(dp8_mesh, CommPolicy(base="none"))
    fused, _, _ = _train(dp8_mesh, CommPolicy(
        base="fused", bucket_bytes=1024))
    hier, _, _ = _train(dp8_mesh, CommPolicy(
        base="hierarchical", bucket_bytes=1024, hosts=2))
    np.testing.assert_allclose(fused, ref, rtol=1e-5)
    np.testing.assert_allclose(hier, ref, rtol=1e-5)


def test_int8_error_feedback_trains_close_to_fp32(dp8_mesh):
    ref, _, _ = _train(dp8_mesh, CommPolicy(base="none"), steps=18)
    q, _, state = _train(dp8_mesh, CommPolicy(
        base="fused", bucket_bytes=4096, quant="int8"), steps=18)
    # acceptance: within 2% relative final loss, error feedback on
    assert abs(q[-1] - ref[-1]) / ref[-1] < 0.02, (q[-1], ref[-1])
    assert int(state["comm_quant_fallbacks"]) == 0
    # the residuals are live state, not zeros (error feedback is real)
    res_mag = max(float(jnp.abs(r).max())
                  for r in jax.tree_util.tree_leaves(state["residual"]))
    assert res_mag > 0.0


def test_hierarchical_int8_trains_close(dp8_mesh):
    ref, _, _ = _train(dp8_mesh, CommPolicy(base="none"), steps=12)
    q, _, _ = _train(dp8_mesh, CommPolicy(
        base="hierarchical", bucket_bytes=4096, quant="int8", hosts=2),
        steps=12)
    assert abs(q[-1] - ref[-1]) / ref[-1] < 0.02, (q[-1], ref[-1])


def test_int8_without_state_raises(dp8_mesh):
    def make_body(state):
        def body(v):
            g = {"w": jax.lax.squeeze(v, (0,))}
            out, _ = comm.all_reduce_grads(
                g, "dp", CommPolicy(base="fused", quant="int8"),
                state=state)
            return out["w"][None]
        return body

    x = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    with pytest.raises(ValueError, match="error-feedback"):
        comm.shard_map(make_body(None), dp8_mesh, in_specs=P("dp"),
                       out_specs=P("dp"))(x)
    # a residual-less state (built under a non-quant policy / restored
    # from a pre-int8 checkpoint) must raise too, not silently skip EF
    stale = {"comm_quant_fallbacks": jnp.zeros((), jnp.int32)}
    with pytest.raises(ValueError, match="has none"):
        comm.shard_map(make_body(stale), dp8_mesh, in_specs=P("dp"),
                       out_specs=P("dp"))(x)


def test_int8_preserves_non_f32_bucket_dtypes(dp8_mesh):
    """bf16 / int leaves must come back in their own dtype: only fp32
    buckets quantise; the rest ride the full-precision base path."""
    rng = np.random.RandomState(2)

    def body(v):
        g = {"w": jax.lax.squeeze(v, (0,)),
             "h": jax.lax.squeeze(v, (0,)).astype(jnp.bfloat16)}
        state = comm.init_state(g, CommPolicy(base="fused", quant="int8"))
        out, _ = comm.all_reduce_grads(
            g, "dp", CommPolicy(base="fused", quant="int8"), state=state)
        return out["w"][None], out["h"][None]

    x = rng.randn(8, 16).astype(np.float32)
    w, h = comm.shard_map(body, dp8_mesh, in_specs=P("dp"),
                          out_specs=(P("dp"), P("dp")))(x)
    assert np.asarray(w).dtype == np.float32
    assert h.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(w)[0], x.mean(0), atol=0.05)


def test_bucket_wire_bytes_prices_inert_quant_as_fp32():
    """The bytes model charges int8 only where the runtime quantises:
    non-fp32 buckets and hosts=1 hierarchical ride fp32 pricing."""
    from paddle_tpu.comm.policy import bucket_wire_bytes, quant_inert_for
    B, n = 1 << 20, 8
    q = CommPolicy(base="fused", quant="int8")
    f = CommPolicy(base="fused")
    assert bucket_wire_bytes(B, np.float32, q, n) == \
        bytes_on_wire(B, q, n)
    assert bucket_wire_bytes(B, jnp.bfloat16, q, n) == \
        bytes_on_wire(B, f, n)
    hq1 = CommPolicy(base="hierarchical", quant="int8", hosts=1)
    assert quant_inert_for(hq1, np.float32)
    assert bucket_wire_bytes(B, np.float32, hq1, n) == bytes_on_wire(
        B, CommPolicy(base="hierarchical", hosts=1), n)
    # and plan_summary composes it: a mixed f32+bf16 tree under int8
    # prices the bf16 bucket at full precision
    tree = {"a": jnp.zeros((256, 64), jnp.float32),
            "b": jnp.zeros((256, 64), jnp.bfloat16)}
    s = comm.plan_summary(tree, q, axis_size=n)
    f32_b, bf16_b = 256 * 64 * 4, 256 * 64 * 2
    assert s["comm_bytes"] == bytes_on_wire(f32_b, q, n) + \
        bytes_on_wire(bf16_b, f, n)


def test_hierarchical_int8_hosts1_is_inert_no_phantom_fallbacks(dp8_mesh):
    """hosts=1 hierarchical int8: nothing quantises (no inter-host hop),
    so a non-finite gradient must NOT tick the fallback counter."""
    step, state0 = data_parallel_step_fn(
        _mlp_loss, dp8_mesh,
        policy=CommPolicy(base="hierarchical", bucket_bytes=4096,
                          quant="int8", hosts=1))
    params = _mlp_params()
    params = dict(params, w2=params["w2"].at[0, 0].set(jnp.inf))
    state = state0(params)
    x, y = _mlp_data()
    _, _, state = step(params, state, x, y, 0.1)
    assert int(state["comm_quant_fallbacks"]) == 0


def test_hierarchical_int8_overflow_falls_back(dp8_mesh):
    """The hierarchical int8 leg carries the same all-finite vote as the
    fused path: a non-finite gradient runs the exact composition (inf
    propagates faithfully instead of NaN garbage) and counts a
    fallback in the carried state."""
    step, state0 = data_parallel_step_fn(
        _mlp_loss, dp8_mesh,
        policy=CommPolicy(base="hierarchical", bucket_bytes=4096,
                          quant="int8", hosts=2))
    params = _mlp_params()
    params = dict(params, w2=params["w2"].at[0, 0].set(jnp.inf))
    state = state0(params)
    x, y = _mlp_data()
    _, _, state = step(params, state, x, y, 0.1)
    assert int(state["comm_quant_fallbacks"]) > 0


# ---------------------------------------------------------------------------
# degradation paths (fault sites + runtime fallback)


def test_quantize_fault_falls_back_to_full_precision(dp8_mesh):
    """Armed comm.quantize (via the PADDLE_TPU_FAULT_SPEC grammar): the
    int8 build degrades to full precision, records comm_degraded, and
    the step loop SURVIVES with fp32-grade numerics."""
    faults.load_fault_spec("comm.quantize:raise:nth=1,times=*")
    ref, _, _ = _train(dp8_mesh, CommPolicy(base="none"))
    q, _, state = _train(dp8_mesh, CommPolicy(
        base="fused", bucket_bytes=1024, quant="int8"))
    evs = R.events(kind="comm_degraded", site="comm.quantize")
    assert evs, "no comm_degraded event recorded"
    # every bucket degraded -> numerically the plain fused fp32 path
    np.testing.assert_allclose(q, ref, rtol=1e-5)
    assert int(state["comm_quant_fallbacks"]) == 0  # build-time, not runtime


def test_bucket_roundtrip_fault_degrades_to_unbucketed(dp8_mesh):
    faults.load_fault_spec("comm.bucket_roundtrip:raise:nth=1,times=*")
    ref = _bare_pmean_train(dp8_mesh, steps=3)
    got, _, _ = _train(dp8_mesh, CommPolicy(base="fused",
                                            bucket_bytes=1024), steps=3)
    assert got == ref  # the unbucketed fallback IS the bare pmean path
    evs = R.events(kind="comm_degraded", site="comm.bucket_roundtrip")
    assert evs


def test_runtime_overflow_records_event_and_survives(dp8_mesh):
    """Drive a real dynamic-range overflow (inf loss scale -> inf grads)
    through a quantised step: the exact branch runs, the carried
    fallback counter ticks, and record_step_stats records the event."""
    step, state0 = data_parallel_step_fn(
        _mlp_loss, dp8_mesh,
        policy=CommPolicy(base="fused", bucket_bytes=4096, quant="int8"))
    params = _mlp_params()
    # poison one weight -> non-finite grads in every bucket touched
    params = dict(params, w2=params["w2"].at[0, 0].set(jnp.inf))
    state = state0(params)
    x, y = _mlp_data()
    _, _, state = step(params, state, x, y, 0.1)
    n_fallbacks = int(state["comm_quant_fallbacks"])
    assert n_fallbacks > 0
    stats = {"comm_quant_fallbacks": 0}
    last = comm.record_step_stats(state, last_fallbacks=0, stats=stats)
    assert last == n_fallbacks
    assert stats["comm_quant_fallbacks"] == n_fallbacks
    evs = R.events(kind="comm_degraded")
    assert any(e.get("reason") == "dynamic_range_overflow" for e in evs)


# ---------------------------------------------------------------------------
# observability: executor stats, profiler comm section


def test_executor_records_comm_model(dp8_mesh, tmp_path):
    from paddle_tpu import layers, profiler
    from paddle_tpu.parallel import data_parallel
    x = layers.data("x", shape=[16], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=32, act="relu")
    pred = layers.fc(h, size=4, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    pt.SGD(learning_rate=0.1).minimize(loss)

    profiler.reset_profiler()
    ctx = data_parallel(dp8_mesh)
    exe = pt.Executor(pt.CPUPlace(), dist_context=ctx)
    exe.run(pt.default_startup_program())
    xs, ys = _mlp_data()
    feed = {"x": xs, "y": ys[:, None]}
    exe.run(pt.default_main_program(), feed=feed, fetch_list=[loss])
    assert exe.stats["comm_bytes"] > 0
    assert exe.stats["comm_buckets"] >= 1
    counters = profiler.comm_counters()
    assert counters["comm_bytes"] > 0 and counters["comm_buckets"] >= 1
    # the comm section rides the timeline artifact
    path = tmp_path / "timeline.json"
    artifact = profiler.write_timeline(str(path))
    assert artifact["comm"]["comm_bytes"] > 0
    assert json.loads(path.read_text())["comm"] == artifact["comm"]


def test_all_reduce_grads_build_updates_comm_counters(dp8_mesh):
    from paddle_tpu import profiler
    profiler.reset_comm_counters()
    _train(dp8_mesh, CommPolicy(base="fused", bucket_bytes=1024), steps=1)
    c = profiler.comm_counters()
    assert c["comm_builds"] >= 1
    assert c["comm_buckets"] >= 2  # 1KiB buckets split the MLP grads
    assert c["comm_bytes"] > 0


# ---------------------------------------------------------------------------
# pipeline-parallel integration (dp x pp grad sync routes through comm)


def test_pipelined_step_fn_comm_policy_parity(forced_cpu_devices):
    from paddle_tpu.parallel import pipelined_step_fn
    mesh = make_mesh({"dp": 2, "pp": 4}, devices=forced_cpu_devices)
    n_micro, B, D = 4, 16, 8
    rng = np.random.RandomState(0)
    stacked = {"w": jnp.asarray(rng.randn(4, D, D).astype(np.float32) * 0.3)}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    def loss_fn(yp, yt):
        return jnp.mean((yp - yt) ** 2)

    x = rng.randn(B, D).astype(np.float32)
    yt = rng.randn(B, D).astype(np.float32)

    def run(policy):
        step = pipelined_step_fn(stage_fn, loss_fn, mesh, n_micro,
                                 data_axis="dp", comm_policy=policy)
        p = {"w": stacked["w"]}
        ls = []
        for _ in range(3):
            loss, p = step(p, x, yt, 0.05)
            ls.append(float(loss))
        return ls

    ref = run(CommPolicy(base="none"))
    fused = run(CommPolicy(base="fused", bucket_bytes=512))
    assert ref == run(CommPolicy(base="none"))  # deterministic harness
    np.testing.assert_allclose(fused, ref, rtol=1e-5)


def test_pipelined_step_fn_overlap_parity(forced_cpu_devices):
    """dp x pp: the staged overlap sync holds parity through the
    pipelined step builder too (stateless policies only there)."""
    from paddle_tpu.parallel import pipelined_step_fn
    mesh = make_mesh({"dp": 2, "pp": 4}, devices=forced_cpu_devices)
    n_micro, B, D = 4, 16, 8
    rng = np.random.RandomState(0)
    stacked = {"w": jnp.asarray(rng.randn(4, D, D).astype(np.float32) * 0.3)}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    def loss_fn(yp, yt):
        return jnp.mean((yp - yt) ** 2)

    x = rng.randn(B, D).astype(np.float32)
    yt = rng.randn(B, D).astype(np.float32)

    def run(policy, overlap):
        step = pipelined_step_fn(stage_fn, loss_fn, mesh, n_micro,
                                 data_axis="dp", comm_policy=policy,
                                 overlap=overlap)
        p, ls = {"w": stacked["w"]}, []
        for _ in range(3):
            loss, p = step(p, x, yt, 0.05)
            ls.append(float(loss))
        return ls

    ref = run(CommPolicy(base="none"), False)
    assert run(CommPolicy(base="none"), True) == ref  # BIT-identical
    np.testing.assert_allclose(
        run(CommPolicy(base="fused", bucket_bytes=512), True), ref,
        rtol=1e-5)


# ---------------------------------------------------------------------------
# comm/compute overlap: the staged step (ISSUE 7 tentpole)


def test_backward_schedule_orders_buckets():
    """The overlap issue order: the bucket holding the HIGHEST leaf
    positions (last-declared params, first-finalised grads) goes
    first."""
    tree = {"p%02d" % i: jnp.ones((64,), jnp.float32) for i in range(6)}
    plan = build_plan(tree, bucket_bytes=512)  # 2 leaves per bucket
    order = plan.backward_schedule()
    assert sorted(order) == list(range(plan.num_buckets))
    maxima = [max(plan.buckets[i].leaf_ids) for i in order]
    assert maxima == sorted(maxima, reverse=True)
    assert order[0] == plan.num_buckets - 1  # last bucket issues first


def test_overlap_bit_identical_policy_none(dp8_mesh):
    """Acceptance: overlap-on under comm_policy=none is BIT-identical
    to the serialized path over 3 passes — the staged restructure moves
    issue order and update staging, never values."""
    ser, _, _ = _train(dp8_mesh, CommPolicy(base="none"))
    ov, _, state = _train_overlap(dp8_mesh, CommPolicy(base="none"))
    assert ov == ser
    assert int(state["comm_quant_fallbacks"]) == 0


def _train_overlap(mesh, policy, steps=9, lr=0.1, seed=0):
    step, state0 = data_parallel_step_fn(_mlp_loss, mesh, policy=policy,
                                         overlap=True)
    params = _mlp_params(seed)
    state = state0(params)
    batches = [_mlp_data(seed=s) for s in range(3)]
    losses = []
    for i in range(steps):
        x, y = batches[i % 3]
        loss, params, state = step(params, state, x, y, lr)
        losses.append(float(loss))
    return losses, params, state


@pytest.mark.parametrize("policy_kw", [
    dict(base="fused", bucket_bytes=1024),
    dict(base="hierarchical", bucket_bytes=1024, hosts=2),
    dict(base="multipath", bucket_bytes=1024, hosts=2, split_ratio=0.5),
    dict(base="fused", bucket_bytes=4096, quant="int8"),
    dict(base="fused", bucket_bytes=4096, quant="int8_2shot"),
])
def test_overlap_parity_per_policy(dp8_mesh, policy_kw):
    """Every policy x overlap: the staged step runs the SAME per-bucket
    collective (_bucket_collective is shared), so losses match the
    serialized build exactly up to fp tolerance."""
    pol = CommPolicy(**policy_kw)
    ser, _, _ = _train(dp8_mesh, pol, steps=6)
    ov, _, _ = _train_overlap(dp8_mesh, pol, steps=6)
    np.testing.assert_allclose(ov, ser, rtol=1e-6)


def test_overlap_fault_degrades_to_serialized(dp8_mesh):
    """Armed comm.overlap: the staged build degrades to the serialized
    path with a recorded comm_degraded event — losses land exactly on
    the serialized build's."""
    ser, _, _ = _train(dp8_mesh, CommPolicy(base="fused",
                                            bucket_bytes=1024), steps=3)
    faults.load_fault_spec("comm.overlap:raise:nth=1,times=*")
    got, _, _ = _train_overlap(dp8_mesh, CommPolicy(base="fused",
                                                    bucket_bytes=1024),
                               steps=3)
    assert got == ser
    evs = R.events(kind="comm_degraded", site="comm.overlap")
    assert evs


def test_overlap_records_profiler_counters(dp8_mesh):
    from paddle_tpu import profiler
    profiler.reset_comm_counters()
    _train_overlap(dp8_mesh, CommPolicy(base="fused", bucket_bytes=1024),
                   steps=1)
    c = profiler.comm_counters()
    assert c["comm_overlap_builds"] >= 1
    # 1KiB buckets split the MLP grads -> at least one early bucket
    # with estimated hidden bytes
    assert c["comm_overlap_buckets_early"] >= 1
    assert c["comm_overlap_hidden_bytes_est"] > 0


def test_overlap_resolves_from_flag(dp8_mesh):
    """overlap=None defers to FLAGS.comm_overlap at build time."""
    from paddle_tpu import profiler
    with flags_guard(comm_overlap=True):
        profiler.reset_comm_counters()
        step, state0 = data_parallel_step_fn(
            _mlp_loss, dp8_mesh,
            policy=CommPolicy(base="fused", bucket_bytes=1024))
        params = _mlp_params()
        x, y = _mlp_data()
        step(params, state0(params), x, y, 0.1)
        assert profiler.comm_counters()["comm_overlap_builds"] >= 1


# ---------------------------------------------------------------------------
# 2-shot int8: reduce-scatter + all-gather (scales past n=8)


def test_2shot_allreduce_error_bound(dp8_mesh):
    """The 2-shot result is the mean within two quantisation steps
    (shot-1 + shot-2 rounding), and the residual is live error
    feedback."""
    x = np.random.RandomState(7).randn(8, 1000).astype(np.float32)

    def body(v):
        out, res, fell = quantized_reduce_scatter_all_gather(
            jax.lax.squeeze(v, (0,)), "dp", chunk=128)
        return out[None], res[None], fell[None]

    out, res, fell = comm.shard_map(
        body, dp8_mesh, in_specs=P("dp"),
        out_specs=(P("dp"), P("dp"), P("dp")))(x)
    assert int(np.asarray(fell).sum()) == 0
    np.testing.assert_allclose(np.asarray(out)[0], x.mean(0), atol=0.05)
    # every device dequantises the same gathered payload (fp noise only)
    assert np.asarray(out).std(axis=0).max() < 1e-6
    # residuals are real (nonzero) and bounded by the quantisation step
    r = np.asarray(res)
    assert np.abs(r).max() > 0.0
    assert np.abs(r).max() < 0.5


def test_2shot_bytes_beat_gather_and_ring_at_8():
    """The crossover doc/comm.md documents: at n=8 the gather int8 form
    LOSES to the fp32 ring while the 2-shot form beats both — and keeps
    winning as n grows."""
    B = 1 << 20
    for n in (8, 16, 64):
        two = bytes_on_wire(B, CommPolicy(base="fused",
                                          quant="int8_2shot"), n)
        gather = bytes_on_wire(B, CommPolicy(base="fused", quant="int8"), n)
        ring = bytes_on_wire(B, CommPolicy(base="fused"), n)
        assert two < ring, (n, two, ring)
        assert two < gather, (n, two, gather)
    # the gather form's honest failure mode at n=8: >= the fp32 ring
    assert bytes_on_wire(B, CommPolicy(base="fused", quant="int8"), 8) \
        >= bytes_on_wire(B, CommPolicy(base="fused"), 8)


def test_2shot_error_feedback_trains_close(dp8_mesh):
    ref, _, _ = _train(dp8_mesh, CommPolicy(base="none"), steps=18)
    q, _, state = _train(dp8_mesh, CommPolicy(
        base="fused", bucket_bytes=4096, quant="int8_2shot"), steps=18)
    assert abs(q[-1] - ref[-1]) / ref[-1] < 0.02, (q[-1], ref[-1])
    assert int(state["comm_quant_fallbacks"]) == 0
    res_mag = max(float(jnp.abs(r).max())
                  for r in jax.tree_util.tree_leaves(state["residual"]))
    assert res_mag > 0.0  # error feedback is live state


def test_2shot_overflow_falls_back(dp8_mesh):
    step, state0 = data_parallel_step_fn(
        _mlp_loss, dp8_mesh,
        policy=CommPolicy(base="fused", bucket_bytes=4096,
                          quant="int8_2shot"))
    params = _mlp_params()
    params = dict(params, w2=params["w2"].at[0, 0].set(jnp.inf))
    state = state0(params)
    x, y = _mlp_data()
    _, _, state = step(params, state, x, y, 0.1)
    assert int(state["comm_quant_fallbacks"]) > 0


def test_2shot_requires_fused_base():
    """int8_2shot IS a flat-axis collective shape: composing it under
    hierarchical/multipath is refused readably (their inter-host legs
    quantise via plain int8 instead)."""
    with pytest.raises(ValueError, match="fused-base"):
        CommPolicy(base="hierarchical", quant="int8_2shot", hosts=2)
    with pytest.raises(ValueError, match="fused-base"):
        CommPolicy(base="multipath", quant="int8_2shot", hosts=2)
    # none promotes to fused, like plain int8
    assert CommPolicy(base="none", quant="int8_2shot").base == "fused"


# ---------------------------------------------------------------------------
# multipath (FlexLink): primary + secondary path simultaneously


def test_multipath_split_reassembles_bitwise(dp8_mesh):
    """The split/concat machinery moves bytes, never values: with BOTH
    paths running the same reduction (hosts=1 secondary = flat RS+AG =
    psum-equivalent mean), the reassembled vector is bitwise the
    unsplit psum's per element of each slice."""
    from paddle_tpu.comm.multipath import split_flat
    x = np.random.RandomState(3).randn(8, 512).astype(np.float32)
    k = 256

    def split_body(v):
        flat = jax.lax.squeeze(v, (0,))
        a, b = split_flat(flat, k)
        # same collective on both slices: psum — reassembly must be
        # bitwise the unsplit psum (elementwise op, disjoint slices)
        out = jnp.concatenate([jax.lax.psum(a, "dp"),
                               jax.lax.psum(b, "dp")])
        return out[None]

    def whole_body(v):
        return jax.lax.psum(jax.lax.squeeze(v, (0,)), "dp")[None]

    split_out = comm.shard_map(split_body, dp8_mesh, in_specs=P("dp"),
                               out_specs=P("dp"))(x)
    whole_out = comm.shard_map(whole_body, dp8_mesh, in_specs=P("dp"),
                               out_specs=P("dp"))(x)
    np.testing.assert_array_equal(np.asarray(split_out),
                                  np.asarray(whole_out))


def test_multipath_all_reduce_is_mean(dp8_mesh):
    x = np.random.RandomState(5).randn(8, 1024).astype(np.float32)

    def body(v):
        return comm.multipath_all_reduce(
            jax.lax.squeeze(v, (0,)), "dp", hosts=2, k=512)[None]

    out = comm.shard_map(body, dp8_mesh, in_specs=P("dp"),
                         out_specs=P("dp"))(x)
    # secondary slice reassociates (hierarchical): fp32 tolerance
    np.testing.assert_allclose(np.asarray(out),
                               np.tile(x.mean(0), (8, 1)), rtol=1e-5)


def test_multipath_trains_close(dp8_mesh):
    ref, _, _ = _train(dp8_mesh, CommPolicy(base="none"))
    mp, _, _ = _train(dp8_mesh, CommPolicy(
        base="multipath", bucket_bytes=1024, hosts=2, split_ratio=0.5))
    np.testing.assert_allclose(mp, ref, rtol=1e-5)


def test_multipath_split_elems_alignment():
    """The split point honours the ratio, stays chips-aligned (the
    secondary slice feeds a hierarchical reduce-scatter) and leaves
    small buckets whole on the primary path."""
    from paddle_tpu.comm.policy import MULTIPATH_MIN_BYTES
    p = CommPolicy(base="multipath", hosts=2, split_ratio=0.75)
    numel = 100_000  # 400 KB > floor
    k = p.split_elems(numel, numel * 4, chips=4)
    assert k % 4 == 0 and (numel - k) % 4 == 0
    assert abs(k / numel - 0.75) < 0.01
    # below the floor: everything primary
    small = (MULTIPATH_MIN_BYTES // 4) - 4
    assert p.split_elems(small, small * 4, chips=4) == small
    # extremes clamp
    assert CommPolicy(base="multipath", hosts=2, split_ratio=1.0) \
        .split_elems(numel, numel * 4, 4) == numel
    assert CommPolicy(base="multipath", hosts=2, split_ratio=0.0) \
        .split_elems(numel, numel * 4, 4) == 0


def test_measured_split_ratio():
    from paddle_tpu.comm import measured_split_ratio
    # FlexLink's rule: bytes proportional to bandwidth
    assert measured_split_ratio(3.0, 1.0) == 0.75
    assert measured_split_ratio(1.0, 0.0) == 1.0
    with pytest.raises(ValueError):
        measured_split_ratio(0.0, 1.0)


def test_multipath_bytes_model():
    """path_split_bytes decomposes the per-chip total; the primary ring
    slice and the secondary hierarchical slice price like their
    single-path forms."""
    from paddle_tpu.comm import path_split_bytes
    B, n = 1 << 20, 8
    p = CommPolicy(base="multipath", hosts=2, split_ratio=0.5)
    split = path_split_bytes(B, p, n)
    assert split["split_ratio"] == 0.5
    assert split["primary"] + split["secondary"] == bytes_on_wire(B, p, n)
    # each path prices as its own algorithm on its slice (chips=4
    # alignment can shift the split point by < 1 chunk)
    half = B // 2
    assert abs(split["primary"]
               - bytes_on_wire(half, CommPolicy(base="fused"), n)) < 64
    assert abs(split["secondary"] - bytes_on_wire(
        half, CommPolicy(base="hierarchical", hosts=2), n)) < 64
    # the point of the split: the boundary link carries LESS than a
    # flat ring (part of the stream crosses on the secondary path's
    # 1/chips chunk), more than pure hierarchical
    from paddle_tpu.comm.policy import inter_host_bytes_per_link
    flat = inter_host_bytes_per_link(B, CommPolicy(base="fused"), n)
    hier = inter_host_bytes_per_link(
        B, CommPolicy(base="hierarchical", hosts=2), n)
    mp = inter_host_bytes_per_link(B, p, n)
    assert hier < mp < flat


def test_policy_table_multipath_dispatches_honest():
    """The table doubles multipath dispatches only when the split
    actually happens — a sub-floor bucket or ratio 1.0 flies ONE
    collective, matching plan_summary's live decision."""
    from paddle_tpu.comm.policy import policy_table
    small = {r["policy"]: r for r in policy_table(32 * 1024, 8, hosts=2)}
    assert small["multipath"]["collective_dispatches"] == \
        small["fused"]["collective_dispatches"]  # below the 64 KiB floor
    whole = {r["policy"]: r
             for r in policy_table(1 << 20, 8, hosts=2, split_ratio=1.0)}
    assert whole["multipath"]["collective_dispatches"] == \
        whole["fused"]["collective_dispatches"]  # ratio 1.0: one path
    split = {r["policy"]: r
             for r in policy_table(1 << 20, 8, hosts=2, split_ratio=0.5)}
    assert split["multipath"]["collective_dispatches"] == \
        2 * split["fused"]["collective_dispatches"]


# ---------------------------------------------------------------------------
# executor: explicit comm routing on the GSPMD path (tentpole part 4)


def _dp_program():
    from paddle_tpu import layers
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data("x", shape=[16], dtype="float32")
        y = layers.data("y", shape=[1], dtype="int64")
        h = layers.fc(x, size=32, act="relu")
        pred = layers.fc(h, size=4, act="softmax")
        loss = layers.mean(layers.cross_entropy(pred, y))
        pt.SGD(learning_rate=0.1).minimize(loss)
    return prog, startup, loss, pred


def _run_executor(prog, startup, fetches, dp8_mesh, n_steps=3):
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.parallel import data_parallel
    scope = Scope()
    ctx = data_parallel(dp8_mesh)
    exe = pt.Executor(pt.CPUPlace(), dist_context=ctx)
    exe.run(startup, scope=scope)
    xs, ys = _mlp_data()
    losses = []
    out = None
    for _ in range(n_steps):
        out = exe.run(prog, feed={"x": xs, "y": ys[:, None]},
                      fetch_list=fetches, scope=scope)
        losses.append(float(np.asarray(out[0]).reshape(())))
    return losses, exe, out


def test_executor_explicit_comm_path(dp8_mesh):
    """comm_policy != none routes the GSPMD Executor path's grad sync
    through the explicit comm collectives: stats say so, and losses +
    batch fetches match the model-path build."""
    prog, startup, loss, pred = _dp_program()
    ref, exe0, out0 = _run_executor(prog, startup, [loss, pred], dp8_mesh)
    assert exe0.stats["comm_path"] == "model"  # none policy: GSPMD owns
    with flags_guard(comm_policy="fused", comm_hosts=2):
        got, exe, out = _run_executor(prog, startup, [loss, pred],
                                      dp8_mesh)
    assert exe.stats["comm_path"] == "explicit"
    assert exe.stats["comm_bytes"] > 0 and exe.stats["comm_buckets"] >= 1
    assert not R.events(kind="comm_degraded", site="comm.gspmd")
    np.testing.assert_allclose(got, ref, rtol=1e-5)
    # batch-leading fetch reassembles over the data axis
    assert np.asarray(out[1]).shape == np.asarray(out0[1]).shape
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(out0[1]),
                               rtol=1e-4, atol=1e-6)


def test_executor_holds_a_gspmd_dp_step_and_not_an_explicit_comm_one(
        dp8_mesh):
    """``run(hold=True)`` (Trainer's dispatch ahead): the plain GSPMD dp
    step is built without donation, held and committed to the losses of
    the donating one; the explicit-comm step has no such build, so the
    executor says no, runs nothing, and the caller's next plain run is
    the explicit path as ever."""
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.parallel import data_parallel
    prog, startup, loss, _ = _dp_program()
    ref, _, _ = _run_executor(prog, startup, [loss], dp8_mesh)
    xs, ys = _mlp_data()
    feed = {"x": xs, "y": ys[:, None]}

    def held_run(exe, scope):
        exe.run(startup, scope=scope)
        out = exe.run(prog, feed=feed, fetch_list=[loss], scope=scope,
                      hold=True)
        return out and float(np.asarray(out[0]).reshape(()))
    scope = Scope()
    exe = pt.Executor(pt.CPUPlace(), dist_context=data_parallel(dp8_mesh))
    got = [held_run(exe, scope)]
    assert exe.commit() and exe.can_hold(prog)
    for _ in range(2):
        out = exe.run(prog, feed=feed, fetch_list=[loss], scope=scope,
                      hold=True)
        assert exe.commit()
        got.append(float(np.asarray(out[0]).reshape(())))
    assert got == ref                                   # bit for bit
    # startup (which another Executor of this test compiled) + ONE step
    assert exe.stats["compiles"] + exe.stats["compile_cache_hits"] == 2
    with flags_guard(comm_policy="fused", comm_hosts=2):
        scope = Scope()
        exe = pt.Executor(pt.CPUPlace(),
                          dist_context=data_parallel(dp8_mesh))
        assert held_run(exe, scope) is None
        assert not exe.can_hold(prog) and exe._held is None
        assert exe.stats["jit_runs"] == 1               # startup's alone
        out = exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
    assert exe.stats["comm_path"] == "explicit"
    np.testing.assert_allclose(float(np.asarray(out[0]).reshape(())),
                               ref[0], rtol=1e-5)


def test_executor_explicit_comm_overlap_and_policies(dp8_mesh):
    """hierarchical/multipath + comm_overlap ride the executor path
    too (overlap = backward-order bucket issue inside the trace)."""
    prog, startup, loss, _ = _dp_program()
    ref, _, _ = _run_executor(prog, startup, [loss], dp8_mesh)
    for kw in (dict(comm_policy="hierarchical", comm_hosts=2),
               dict(comm_policy="multipath", comm_hosts=2),
               dict(comm_policy="fused", comm_overlap=True)):
        with flags_guard(**kw):
            got, exe, _ = _run_executor(prog, startup, [loss], dp8_mesh)
        assert exe.stats["comm_path"] == "explicit", kw
        np.testing.assert_allclose(got, ref, rtol=1e-4)


def test_executor_explicit_ineligible_falls_back(dp8_mesh):
    """A fetch with no sound per-shard assembly (non-scalar, non-batch)
    degrades to the plain GSPMD jit with a recorded comm_degraded event
    — never a dead job."""
    prog, startup, loss, _ = _dp_program()
    w_name = prog.all_parameters()[0].name
    w_var = prog.global_block().var(w_name)
    ref, _, _ = _run_executor(prog, startup, [loss, w_var], dp8_mesh)
    with flags_guard(comm_policy="fused", comm_hosts=2):
        got, exe, _ = _run_executor(prog, startup, [loss, w_var],
                                    dp8_mesh)
    assert exe.stats["comm_path"] == "model"
    evs = R.events(kind="comm_degraded", site="comm.gspmd")
    assert evs
    np.testing.assert_allclose(got, ref, rtol=1e-5)


def test_executor_comm_path_not_sticky(dp8_mesh):
    """An earlier explicit-path compile must not leave stats claiming
    'explicit' for a LATER ineligible program on the same Executor."""
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.parallel import data_parallel
    prog, startup, loss, _ = _dp_program()
    w_var = prog.global_block().var(prog.all_parameters()[0].name)
    scope = Scope()
    xs, ys = _mlp_data()
    with flags_guard(comm_policy="fused", comm_hosts=2):
        ctx = data_parallel(dp8_mesh)
        exe = pt.Executor(pt.CPUPlace(), dist_context=ctx)
        exe.run(startup, scope=scope)
        exe.run(prog, feed={"x": xs, "y": ys[:, None]},
                fetch_list=[loss], scope=scope)
        assert exe.stats["comm_path"] == "explicit"
        # new fetch set -> new compile; the param fetch is ineligible
        exe.run(prog, feed={"x": xs, "y": ys[:, None]},
                fetch_list=[loss, w_var], scope=scope)
        assert exe.stats["comm_path"] == "model"


def test_executor_gspmd_flag_forces_model_path(dp8_mesh):
    prog, startup, loss, _ = _dp_program()
    with flags_guard(comm_policy="fused", comm_gspmd=False):
        _, exe, _ = _run_executor(prog, startup, [loss], dp8_mesh)
    assert exe.stats["comm_path"] == "model"


def test_executor_explicit_path_comm_verify_clean(dp8_mesh, monkeypatch):
    """PADDLE_TPU_VERIFY=1 on the explicit path runs the PT020-PT023
    collective-consistency pass over the traced grad set: a clean build
    verifies silently (comm_path still 'explicit', parity held)."""
    monkeypatch.setenv("PADDLE_TPU_VERIFY", "1")
    prog, startup, loss, pred = _dp_program()
    with flags_guard(comm_policy="fused", comm_hosts=2):
        got, exe, _ = _run_executor(prog, startup, [loss], dp8_mesh)
    assert exe.stats["comm_path"] == "explicit"
    assert all(np.isfinite(got))


def test_executor_explicit_path_comm_verify_raises_on_bad_plan(
        dp8_mesh, monkeypatch):
    """A seeded inconsistency surfaces as ONE readable
    ProgramVerifyError from the explicit build, not a degrade: verify
    means the operator asked to be told."""
    from paddle_tpu.analysis import ProgramVerifyError
    from paddle_tpu.analysis import comm_rules
    monkeypatch.setenv("PADDLE_TPU_VERIFY", "1")
    orig = comm_rules.check_topology

    def seeded(policy, axis_size):
        from paddle_tpu.comm import CommPolicy
        return orig(CommPolicy(base="hierarchical", hosts=3), 8)

    monkeypatch.setattr(comm_rules, "check_topology", seeded)
    prog, startup, loss, _pred = _dp_program()
    with flags_guard(comm_policy="fused", comm_hosts=2):
        with pytest.raises(ProgramVerifyError, match="PT022"):
            _run_executor(prog, startup, [loss], dp8_mesh)

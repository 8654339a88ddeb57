"""Gradient-communication policy measurement harness.

The ONE implementation shared by tools/comm_smoke.py (CI gate) and any
benchmark comm cell, so the parity checks, the dispatch accounting, and
the loss-closeness criterion cannot drift between the evidence record
and the gate.

Workload: a deliberately many-parameter MLP (several small fc layers, so
bucketing has real fusion to do) trained through
``parallel.data_parallel_step_fn`` on a forced 8-virtual-device CPU
``dp`` mesh — the same explicit-collective path a real multi-chip DP job
takes; only the fabric differs. Each policy trains the same
``passes x batches`` schedule from the same init, and the summary
reports per-policy final losses, dispatch counts (from the bucket plan),
and the modelled bytes-on-wire.
"""
from __future__ import annotations


def build_mesh(n=8):
    import jax
    from paddle_tpu.parallel import make_mesh
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            "comm bench needs %d devices (run under XLA_FLAGS="
            "--xla_force_host_platform_device_count=%d on CPU); got %d"
            % (n, n, len(devs)))
    return make_mesh({"dp": n}, devices=devs[:n])


def bench(passes=3, batches=3, batch=64, feat=32, hidden=48, depth=4,
          classes=8, lr=0.1, hosts=2, bucket_kb=16, seed=0):
    """Train the same model under every comm policy; returns the summary
    dict the smoke gate asserts over."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import comm
    from paddle_tpu.comm import CommPolicy

    mesh = build_mesh()

    rng = np.random.RandomState(seed)

    def init_params():
        p = {}
        d_in = feat
        for i in range(depth):
            d_out = hidden if i < depth - 1 else classes
            s = np.sqrt(2.0 / d_in)
            p["w%d" % i] = jnp.asarray(
                rng.randn(d_in, d_out).astype(np.float32) * s)
            p["b%d" % i] = jnp.zeros((d_out,), jnp.float32)
            d_in = d_out
        return p

    def loss_fn(p, x, y):
        h = x
        for i in range(depth - 1):
            h = jnp.maximum(h @ p["w%d" % i] + p["b%d" % i], 0)
        logits = h @ p["w%d" % (depth - 1)] + p["b%d" % (depth - 1)]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

    rule = np.random.RandomState(99).randn(feat, classes)
    data = []
    for b in range(batches):
        x = np.random.RandomState(100 + b).rand(batch, feat).astype(
            np.float32)
        y = (x @ rule).argmax(1).astype(np.int64)
        data.append((x, y))

    params0 = init_params()
    n_params = len(jax.tree_util.tree_leaves(params0))

    def bare_pmean_losses():
        """The pre-comm per-leaf pmean path — the bit-parity baseline."""
        rep, xs = P(), P("dp")

        def per_device(p, x, y, lr_):
            loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
            loss = jax.lax.pmean(loss, "dp")
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, "dp"), grads)
            return loss, jax.tree_util.tree_map(
                lambda a, g: a - lr_ * g, p, grads)

        pspecs = jax.tree_util.tree_map(lambda _: rep, params0)
        step = jax.jit(comm.shard_map(
            per_device, mesh, in_specs=(pspecs, xs, xs, rep),
            out_specs=(rep, pspecs)))
        p, ls = dict(params0), []
        for ep in range(passes):
            for x, y in data:
                loss, p = step(p, x, y, jnp.float32(lr))
                ls.append(float(loss))
        return ls

    def run_policy(policy, overlap=False):
        from paddle_tpu.parallel import data_parallel_step_fn
        step, state0 = data_parallel_step_fn(loss_fn, mesh, policy=policy,
                                             overlap=overlap)
        p = dict(params0)
        state = state0(p)
        ls = []
        for ep in range(passes):
            for x, y in data:
                loss, p, state = step(p, state, x, y, lr)
                ls.append(float(loss))
        summary = comm.plan_summary(p, policy, axis_size=8)
        summary["losses"] = ls
        summary["final_loss"] = ls[-1]
        summary["comm_quant_fallbacks"] = int(
            state.get("comm_quant_fallbacks", 0))
        return summary

    bucket_bytes = bucket_kb * 1024
    policies = {
        "none": CommPolicy(base="none"),
        "fused": CommPolicy(base="fused", bucket_bytes=bucket_bytes),
        "hierarchical": CommPolicy(base="hierarchical",
                                   bucket_bytes=bucket_bytes, hosts=hosts),
        "int8": CommPolicy(base="fused", bucket_bytes=bucket_bytes,
                           quant="int8"),
        "int8_2shot": CommPolicy(base="fused", bucket_bytes=bucket_bytes,
                                 quant="int8_2shot"),
        # multipath: tiny bucket floor would keep CI-sized buckets
        # whole, so split every bucket here (the parity leg is the
        # point on CPU; the bandwidth win needs a real fabric)
        "multipath": CommPolicy(base="multipath",
                                bucket_bytes=bucket_bytes, hosts=hosts,
                                split_ratio=0.5),
    }
    out = {"n_params": n_params, "bare_losses": bare_pmean_losses(),
           "policies": {}, "overlap": {}}
    for name, pol in policies.items():
        out["policies"][name] = run_policy(pol)
    # overlap legs: every policy x overlap-on, parity against its own
    # overlap-off run above (the smoke gate asserts the whole matrix)
    for name, pol in policies.items():
        r = run_policy(pol, overlap=True)
        out["overlap"][name] = {"losses": r["losses"],
                                "final_loss": r["final_loss"]}
    return out


def bench_overlap(steps=30, warmup=3, trials=5, batch=64, feat=32,
                  hidden=48, depth=4, classes=8, lr=0.1, bucket_kb=16,
                  seed=0):
    """Step-time phase: the SAME fused-policy DP step built serialized
    vs staged-overlap, timed over ``steps`` steps (best of ``trials``),
    plus a bit-parity check under policy ``none``. On CPU the two
    builds run the same collectives on a fabric with nothing to hide
    behind — the gate is parity + no-slower; the banked row is the
    baseline the next real-TPU run compares against. Returns the
    summary dict (also banked as a ``paddle_tpu.bench.v1`` row by
    ``bank_overlap_result``)."""
    import time
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import profiler
    from paddle_tpu.comm import CommPolicy
    from paddle_tpu.parallel import data_parallel_step_fn

    mesh = build_mesh()
    rng = np.random.RandomState(seed)

    def init_params():
        p = {}
        d_in = feat
        for i in range(depth):
            d_out = hidden if i < depth - 1 else classes
            s = np.sqrt(2.0 / d_in)
            p["w%d" % i] = jnp.asarray(
                rng.randn(d_in, d_out).astype(np.float32) * s)
            p["b%d" % i] = jnp.zeros((d_out,), jnp.float32)
            d_in = d_out
        return p

    def loss_fn(p, x, y):
        h = x
        for i in range(depth - 1):
            h = jnp.maximum(h @ p["w%d" % i] + p["b%d" % i], 0)
        logits = h @ p["w%d" % (depth - 1)] + p["b%d" % (depth - 1)]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

    params0 = init_params()
    rule = np.random.RandomState(99).randn(feat, classes)
    x = np.random.RandomState(100).rand(batch, feat).astype(np.float32)
    y = (x @ rule).argmax(1).astype(np.int64)

    def build(policy, overlap):
        step, state0 = data_parallel_step_fn(loss_fn, mesh, policy=policy,
                                             overlap=overlap)
        p, st = dict(params0), state0(params0)
        l = None
        for _ in range(warmup):  # compile + settle
            l, p, st = step(p, st, x, y, lr)
        if l is not None:
            jax.block_until_ready(l)
        return step, state0

    def one_trial(step, state0):
        p2, st2 = dict(params0), state0(params0)
        t0 = time.perf_counter()
        l = None
        for _ in range(steps):
            l, p2, st2 = step(p2, st2, x, y, lr)
        jax.block_until_ready(l)
        return time.perf_counter() - t0, float(l)

    fused = CommPolicy(base="fused", bucket_bytes=bucket_kb * 1024)
    none = CommPolicy(base="none")

    profiler.reset_comm_counters()
    serial = build(fused, overlap=False)
    staged = build(fused, overlap=True)
    counters = profiler.comm_counters()
    # INTERLEAVE the trials: these steps are ~ms-scale on CPU, so load
    # drift between two sequential timing phases swamps the comparison
    # (observed 0.55x-1.14x run to run when phased); alternating pairs
    # puts both builds under the same load window, best-of damps the rest
    serial_best = overlap_best = float("inf")
    serial_final = overlap_final = 0.0
    for _ in range(trials):
        dt, serial_final = one_trial(*serial)
        serial_best = min(serial_best, dt)
        dt, overlap_final = one_trial(*staged)
        overlap_best = min(overlap_best, dt)
    serial_sps = steps / serial_best
    overlap_sps = steps / overlap_best

    # bit-parity leg under policy none: overlap restructures issue
    # order and update staging only — values must be BIT-identical
    def losses_of(overlap):
        step, state0 = data_parallel_step_fn(loss_fn, mesh, policy=none,
                                             overlap=overlap)
        p, st, ls = dict(params0), state0(params0), []
        for _ in range(6):
            l, p, st = step(p, st, x, y, lr)
            ls.append(float(l))
        return ls

    parity = losses_of(False) == losses_of(True)
    return {
        "comm_overlap_steps_s": round(overlap_sps, 3),
        "comm_serial_steps_s": round(serial_sps, 3),
        "comm_overlap_speedup": round(overlap_sps / serial_sps, 4),
        "comm_overlap_parity": bool(parity),
        "comm_overlap_final_rel": abs(overlap_final - serial_final)
        / max(abs(serial_final), 1e-9),
        "comm_overlap_buckets_early": int(
            counters.get("comm_overlap_buckets_early", 0)),
        "comm_overlap_hidden_bytes_est": int(
            counters.get("comm_overlap_hidden_bytes_est", 0)),
        "steps": steps, "batch": batch,
    }


def bank_overlap_result(summary):
    """Persist the overlap phase as a ``paddle_tpu.bench.v1`` record so
    the next real-TPU round compares against a banked CPU baseline."""
    from paddle_tpu.tune.results import bench_record, write_result
    rec = bench_record("comm_overlap", rows=[summary],
                       meta={"harness": "benchmark/comm_bench.py",
                             "policy": "fused",
                             "gate": "parity + no-slower (CPU)"})
    return write_result(rec)

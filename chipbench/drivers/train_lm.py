"""Driver of a language-model training configuration: ``Trainer.train(reader,
handler)`` -> ``DataFeeder`` -> ``Executor.run`` on the Trainer's default
loop, tokens and next-token labels in, Adam, pure AMP. The protocol and the
``ctx`` keys are ``drivers/train.py``'s (one Trainer driven from the seed
through its first steps, compared with the plain reference afterwards, and
handed to the window as it is), so the per-layer readers that are there
serve this driver too.

Beside the two norm gaps of ``compare.train_numbers`` it judges two numbers
of its own, because a fault of THIS model can leave every norm where it was:

- ``grad_dir_gap``: worst leaf of |g - g_ref| / max(|g_ref|, median leaf),
  estimated from random-sign sketches of both first gradients
  (``reference.sketch``): a gradient of the right size that points elsewhere
  (rotary positions left out) reads ~1 here and ~0 in ``grad_norm_gap``;
- ``load_gap``: worst expert layer's share of first-step (token, pick) pairs
  that chose another expert than the reference's (half the L1 distance of
  the two ``Load`` vectors): a selection without its bias moves it. The
  program's ``Load`` is an output of the Trainer's own step (every step
  fetches the expert layers' ``Load`` and ``RowsHeld`` beside its loss: 10
  small integer arrays), so the step that is compared is the step that is
  timed.
"""
from __future__ import annotations

import gc
import json
import sys
import time

import numpy as np

from chipbench import compare, harness, loadgen, trace_reduce

_train = harness.load_module("drivers", "train.py")
COMPARED_STEPS, WARM_STEPS = _train.COMPARED_STEPS, _train.WARM_STEPS


def build_trainer(pt, config, mix, network):
    """The configuration's network + Adam + pure AMP, its half-layers
    recomputed in the backward pass, and the Trainer around it (its step
    fetches every expert layer's ``Load`` and ``RowsHeld`` beside the
    loss), in fresh programs and a fresh scope. Returns (trainer, scope,
    main, outputs)."""
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    scope = pt.Scope()
    opt = config["optimizer"]
    with pt.program_guard(main, startup):
        tokens = layers.data("tokens", shape=[mix["seq"]], dtype="int64")
        labels = layers.data("labels", shape=[mix["seq"]], dtype="int64")
        out = network.build(layers, tokens, labels, config)
        if config["precision"] != "pure_amp_bf16":
            raise ValueError("unknown precision %r" % (config["precision"],))
        pt.amp.enable(main, pure=True)
        trainer = pt.Trainer(
            cost=out["loss"],
            optimizer=pt.optimizer.AdamOptimizer(
                learning_rate=opt["learning_rate"], beta1=opt["beta1"],
                beta2=opt["beta2"], epsilon=opt["epsilon"]),
            feed_list=[tokens, labels],
            fetch_list=out["loads"] + out["rows_held"], place=pt.TPUPlace(0),
            main_program=main, startup_program=startup)
        pt.memory_optimize(main, remat_types=network.RECOMPUTED)
    return trainer, scope, main, out


def first_moments(main):
    """{parameter: its first-moment variable} of the parameters Adam moves,
    in the order the program made them, read from the program's own
    ``adam`` ops (the router's selection bias has none)."""
    return {op.input("Param")[0]: op.input("Moment1")[0]
            for op in main.global_block().ops if op.type == "adam"}


def _device_norms(arrays, minus=None):
    """Per-array L2 norm (of ``a - b`` with ``minus``), computed where the
    arrays live, one array at a time."""
    import jax
    import jax.numpy as jnp
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b))))
    minus = [0.0] * len(arrays) if minus is None else minus
    return np.array([float(norm(a, b)) for a, b in zip(arrays, minus)],
                    np.float64)


def lm_numbers(got, want, tokens_per_step, top_k):
    """(numbers, notes): ``compare.train_numbers``'s two gaps and this
    driver's two (module docstring)."""
    numbers, notes = compare.train_numbers(got, want)
    ref = np.asarray(want["grad_norms"], np.float64)
    err = np.sqrt(np.mean(np.square(
        np.asarray(got["grad_sketch"]) - np.asarray(want["grad_sketch"])),
        axis=1))
    rel = err / np.maximum(ref, float(np.median(ref)))
    numbers["grad_dir_gap"] = float(rel.max())
    notes["grad_dir_leaf"] = int(np.argmax(rel))
    moved = np.abs(np.asarray(got["loads"], np.int64)
                   - np.asarray(want["loads"], np.int64)).sum(axis=1) / 2.0
    numbers["load_gap"] = float(moved.max() / (tokens_per_step * top_k)) \
        if moved.size else 0.0
    return numbers, notes


def run(cell, seed, seconds, trace, devices, t_start, tamper=None):
    import paddle_tpu as pt
    from paddle_tpu import layers

    config, mix = cell["config"], cell["traffic"]
    network = harness.load_module("networks", config["network"] + ".py")
    reference = harness.load_module("reference", config["reference"] + ".py")
    model = network.model_config(config)
    batch, seq, opt = mix["batch"], mix["seq"], config["optimizer"]
    watch = harness.CompileWatch()

    trainer, scope, main, out = build_trainer(pt, config, mix, network)
    with pt.scope_guard(scope):
        trainer._maybe_init()
    moment_of = first_moments(main)
    names = list(moment_of)
    words = reference.key_data(seed)
    leaves = reference.init_leaves(words, model)
    specs = reference.leaf_specs(model)
    if names != [n for n, _s in specs]:
        raise RuntimeError("the program's trainable leaves %r are not the "
                           "reference's %r" % (names, [n for n, _s in specs]))
    for (n, shape), leaf in zip(specs, leaves):
        if tuple(scope.find_var(n).shape) != tuple(shape):
            raise RuntimeError("leaf %s: program %r, reference %r"
                               % (n, scope.find_var(n).shape, shape))
        scope.set_var(n, leaf)
    start_host = [np.asarray(l) for l in leaves]
    del leaves
    dense = config["first_k_dense_replace"]
    for i, b in enumerate(reference.init_router_biases(words, model)):
        scope.set_var("L%d.ffn.router_bias" % (dense + i), b)
    first_id, ids = model["vocab_held"]
    pool, arrays = loadgen.generate(mix, seed, first_id=first_id, ids=ids)
    if tamper is not None:
        tamper(trainer, scope)

    got = {"losses": []}
    n_moe = len(out["loads"])
    routing = {}

    if trace:
        feed, run_ = trainer.feeder.feed, trainer.exe.run

        def traced_feed(data):
            with harness.span("feed"):
                return feed(data)

        def traced_run(*a, **kw):
            with harness.span("run"):
                return run_(*a, **kw)
        trainer.feeder.feed, trainer.exe.run = traced_feed, traced_run

    st = {"t0": None, "ends": [], "compiles_at_t0": None, "trace": {},
          "held_pairs": 0}

    def reader():
        for i in range(WARM_STEPS):
            yield pool[i % len(pool)]
        with harness.traced_window(trace, st["trace"]):
            st["compiles_at_t0"] = watch.total
            st["t0"] = time.monotonic()
            with harness.span("window", trace):
                i = WARM_STEPS
                while time.monotonic() - st["t0"] < seconds:
                    yield pool[i % len(pool)]
                    i += 1

    def handler(e):
        if not isinstance(e, pt.trainer_mod.EndIteration):
            return
        loss = float(e.cost)                 # host read: the step is done
        now = time.monotonic()
        if st["t0"] is not None:
            st["ends"].append(now)
            st["held_pairs"] += sum(
                int(np.asarray(v).sum()) for v in e.metrics["fetches"][n_moe:])
            return
        got["losses"].append(loss)
        if e.batch_id == 0:
            # the first step's picks per expert, on the seed's weights
            fetched = [np.asarray(v) for v in e.metrics["fetches"]]
            got["loads"] = np.stack(fetched[:n_moe])
            routing["rows_held"] = [int(v.sum()) for v in fetched[n_moe:]]
            routing.update(layers.moe_load_stats(fetched[n_moe - 1],
                                                 fetched[-1]))
            # Adam from zero: the first moment after one step IS the first
            # gradient times (1 - beta1)
            moments = [scope.find_var(moment_of[n]) for n in names]
            scale = 1.0 - opt["beta1"]
            got["grad_norms"] = _device_norms(moments) / scale
            got["grad_sketch"] = np.asarray(
                reference.sketch(moments, words), np.float64) / scale
        if e.batch_id == COMPARED_STEPS - 1:
            got["delta_norms"] = _device_norms(
                [scope.find_var(n) for n in names], minus=start_host)

    with pt.scope_guard(scope):
        trainer.train(reader, num_passes=1, event_handler=handler)
    t0, ends = st["t0"], st["ends"]
    compiles_in_window = watch.total - st["compiles_at_t0"]
    setup_s = t0 - t_start
    window_s = ends[-1] - t0
    rows_per_s = len(ends) * batch / window_s
    device = harness.device_report(devices)

    # the program's state goes before the reference comes
    del trainer, pool, start_host
    for n in list(scope.local_var_names()):
        scope.erase(n)
    gc.collect()
    want = reference.follow(seed, arrays[:COMPARED_STEPS], opt, model)
    got["losses"] = got["losses"][:COMPARED_STEPS]
    numbers, notes = lm_numbers(got, want, batch * seq,
                                config["num_experts_per_tok"])
    numbers["compiles_in_window"] = compiles_in_window
    compared = compare.judge(numbers, config["limits"])
    held = config.get("n_routed_experts_held", config["n_routed_experts"])
    notes.update(
        routing, tokens_per_s=rows_per_s * seq,
        rows_per_held_expert=[r / float(held)
                              for r in routing["rows_held"]],
        rows_per_held_expert_expected=(
            batch * seq * config["num_experts_per_tok"]
            / float(config["n_routed_experts"])),
        held_pairs_per_step_in_window=st["held_pairs"] / float(len(ends)))

    print("notes: %s" % json.dumps(notes), file=sys.stderr, flush=True)

    metrics = {"train_images_per_s": rows_per_s, "setup_s": setup_s}
    ctx = None
    if trace:
        planes = trace_reduce.read_planes(st["trace"]["xplane"])
        red = trace_reduce.reduce_planes(planes)
        ctx = {"reduction": red, "window_ns": trace_reduce.window_of(red),
               "window_s": window_s, "step_ends": [t0] + ends,
               "steps": len(ends), "batch": batch,
               "train_flops_per_row": network.train_flops_per_row(
                   config, reference, seq),
               "attention_flops_per_step": network.attention_flops_per_step(
                   config, batch, seq),
               "expert_flops_per_step": network.expert_flops_per_step(
                   config, st["held_pairs"] / float(len(ends))),
               "images_per_s": rows_per_s,
               "peaks": harness.peaks_for(device["kind"]),
               "trace_reduce": trace_reduce}
    return {"correct": all(c["ok"] for c in compared.values()),
            "attempted": len(ends), "failed": 0, "metrics": metrics,
            "device": device, "compared": compared, "notes": notes,
            "ctx": ctx,
            "raw": {"got": {k: np.asarray(v).tolist() for k, v in got.items()},
                    "want": {k: np.asarray(v).tolist()
                             for k, v in want.items()}}}


def control_readings(cell, seed, devices, look=False):
    """On the chip at the cell's own size, each put in the program's place
    and passed through the run's own comparison (``compare.judge`` with the
    configuration's limits), where each has to come out as not correct: the
    control (the reference one precision down: fp8 operands and stream);
    the planted faults "half of the batch left out" and "the step returns
    its state unchanged"; and this model's own faults (``reference.FAULTS``:
    selection without the bias, pick weights not renormalised, rotary
    positions left out). With ``look`` also the reference at the precision
    the configuration states (bf16), which has to come out correct."""
    config, mix = cell["config"], cell["traffic"]
    network = harness.load_module("networks", config["network"] + ".py")
    reference = harness.load_module("reference", config["reference"] + ".py")
    model, opt = network.model_config(config), config["optimizer"]
    first_id, ids = model["vocab_held"]
    _pool, arrays = loadgen.generate(mix, seed, first_id=first_id, ids=ids)
    batches = arrays[:COMPARED_STEPS]

    def follow(**more):
        return reference.follow(seed, batches, opt, model, **more)
    want = follow()
    cases = [("control_fp8", lambda: follow(precision="fp8")),
             ("fault_half_batch", lambda: follow(rows=mix["batch"] // 2)),
             ("fault_state_unchanged",
              lambda: dict(want, delta_norms=np.zeros_like(
                  want["delta_norms"])))]
    cases += [("fault_" + f, (lambda f=f: follow(fault=f)))
              for f in reference.FAULTS]
    if look:
        cases.append(("stated_bf16", lambda: follow(precision="bf16")))
    out = {}
    for name, make in cases:
        got = make()
        numbers, notes = lm_numbers(got, want, mix["batch"] * mix["seq"],
                                    config["num_experts_per_tok"])
        numbers["compiles_in_window"] = 0
        compared = compare.judge(numbers, config["limits"])
        out[name] = {"numbers": numbers, "notes": notes, "compared": compared,
                     "correct": all(c["ok"] for c in compared.values())}
        print("control_readings %s: %s %r" % (
            name, "correct" if out[name]["correct"] else "NOT correct",
            numbers), flush=True)
    return out

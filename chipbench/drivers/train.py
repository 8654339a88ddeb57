"""Driver of a training configuration: ``Trainer.train(reader, handler)`` ->
``DataFeeder`` -> ``Executor.run`` on the Trainer's default synchronous feed
path. One Trainer object is built, driven from the seed through its first
steps (compared with the plain reference afterwards), and handed to the
window as it is: the window is the same ``train()`` call going on.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import compare, harness, loadgen, trace_reduce

# steps 1-3 are followed by the reference; step 4 is the first to use what
# step 3 left, and the window opens after it
COMPARED_STEPS = 3
WARM_STEPS = 4


def build_trainer(pt, config, model=None):
    """The configuration's network (``chipbench/networks/<network>.py``) +
    Momentum + pure AMP and the Trainer around it, in fresh programs and a
    fresh scope (after chip_smoke.py's ``_resnet_trainer``, which proved
    this path on the chip). ``model(layers, img, config)`` replaces the
    network (tests)."""
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    scope = pt.Scope()
    opt = config["optimizer"]
    with pt.program_guard(main, startup):
        img = layers.data("img", shape=[3, config["image"], config["image"]],
                          dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        if model is None:
            model = harness.load_module("networks",
                                        config["network"] + ".py").build
        pred = model(layers, img, config)
        avg = layers.mean(layers.cross_entropy(pred, label))
        if config["precision"] != "pure_amp_bf16":
            raise ValueError("unknown precision %r" % (config["precision"],))
        pt.amp.enable(main, pure=True)
        trainer = pt.Trainer(
            cost=avg,
            optimizer=pt.Momentum(learning_rate=opt["learning_rate"],
                                  momentum=opt["momentum"]),
            feed_list=[img, label], place=pt.TPUPlace(0),
            main_program=main, startup_program=startup)
    return trainer, scope, main


def trainable_names(pt, main, scope):
    """Parameters the optimizer moves, in the order the program made them
    (those with a velocity accumulator; batch norm's running statistics
    have none)."""
    names = [v.name for v in main.list_vars()
             if isinstance(v, pt.core.ir.Parameter)]
    return [n for n in names if scope.has_var(n + "_velocity_0")]


def _host_norms(arrays):
    return np.array([np.sqrt(np.sum(np.square(a.astype(np.float64))))
                     for a in arrays])


def run(cell, seed, seconds, trace, devices, t_start, tamper=None,
        model=None):
    import paddle_tpu as pt

    config, mix = cell["config"], cell["traffic"]
    reference = harness.load_module("reference", config["reference"] + ".py")
    image, classes, batch = config["image"], config["classes"], mix["batch"]
    opt = config["optimizer"]
    watch = harness.CompileWatch()

    trainer, scope, main = build_trainer(pt, config, model)
    with pt.scope_guard(scope):
        trainer._maybe_init()
    names = trainable_names(pt, main, scope)
    leaves = reference.init_leaves(reference.key_data(seed), image=image,
                                   classes=classes)
    if len(names) != len(leaves):
        raise RuntimeError("the program has %d trainable leaves, the "
                           "reference %d" % (len(names), len(leaves)))
    for n, leaf in zip(names, leaves):
        if tuple(scope.find_var(n).shape) != tuple(leaf.shape):
            raise RuntimeError("leaf %s: program %r, reference %r"
                               % (n, scope.find_var(n).shape, leaf.shape))
        scope.set_var(n, leaf)
    start_host = [np.asarray(l) for l in leaves]
    del leaves
    pool, arrays = loadgen.generate(mix, seed, image=image, classes=classes)
    if tamper is not None:
        tamper(trainer, scope)
    if trace:
        feed, run_ = trainer.feeder.feed, trainer.exe.run

        def traced_feed(data):
            with harness.span("feed"):
                return feed(data)

        def traced_run(*a, **kw):
            with harness.span("run"):
                return run_(*a, **kw)
        trainer.feeder.feed, trainer.exe.run = traced_feed, traced_run

    got = {"losses": []}
    st = {"t0": None, "ends": [], "compiles_at_t0": None, "trace": {}}

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
            return
        got["losses"].append(loss)
        if e.batch_id == 0:
            # Momentum from zero: the velocity after one step IS the first
            # gradient as the optimizer got it
            got["grad_norms"] = _host_norms(
                [np.asarray(scope.find_var(n + "_velocity_0"))
                 for n in names])
        if e.batch_id == COMPARED_STEPS - 1:
            got["delta_norms"] = _host_norms(
                [np.asarray(scope.find_var(n)) - s
                 for n, s in zip(names, start_host)])

    with pt.scope_guard(scope):
        trainer.train(reader, num_passes=1, event_handler=handler)
    t0, ends = st["t0"], st["ends"]
    compiles_in_window = watch.total - st["compiles_at_t0"]
    setup_s = t0 - t_start
    window_s = ends[-1] - t0
    images_per_s = len(ends) * batch / window_s
    device = harness.device_report(devices)

    # the program's state goes before the reference comes
    del trainer, pool, start_host
    for n in list(scope.local_var_names()):
        scope.erase(n)
    gc.collect()
    want = reference.follow(seed, arrays[:COMPARED_STEPS],
                            opt["learning_rate"], opt["momentum"],
                            precision="f32", image=image, classes=classes)
    got["losses"] = got["losses"][:COMPARED_STEPS]
    numbers, notes = compare.train_numbers(got, want)
    numbers["compiles_in_window"] = compiles_in_window
    compared = compare.judge(numbers, config["limits"])

    metrics = {"train_images_per_s": images_per_s, "setup_s": setup_s}
    ctx = None
    if trace:
        planes = trace_reduce.read_planes(st["trace"]["xplane"])
        red = trace_reduce.reduce_planes(planes)
        network = harness.load_module("networks", config["network"] + ".py")
        ctx = {"reduction": red, "window_ns": trace_reduce.window_of(red),
               "window_s": window_s, "step_ends": [t0] + ends,
               "steps": len(ends), "batch": batch,
               "train_flops_per_row": network.train_flops_per_row(
                   config, reference),
               "images_per_s": images_per_s,
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
    control (the reference one precision down: fp8 operands and stream),
    the planted faults "half of the batch left out" and "the step returns
    its state unchanged" (no change at all, no run needed). With ``look``
    also the reference at the precision the configuration states (bf16),
    which tells a precision gap from a fault and has to come out correct."""
    config, mix = cell["config"], cell["traffic"]
    reference = harness.load_module("reference", config["reference"] + ".py")
    opt = config["optimizer"]
    _pool, arrays = loadgen.generate(mix, seed, image=config["image"],
                                     classes=config["classes"])
    batches = arrays[:COMPARED_STEPS]

    def follow(**more):
        return reference.follow(seed, batches, opt["learning_rate"],
                                opt["momentum"], image=config["image"],
                                classes=config["classes"], **more)
    want = follow(precision="f32")
    cases = [("control_fp8", lambda: follow(precision="fp8")),
             ("fault_half_batch",
              lambda: follow(precision="f32", rows=mix["batch"] // 2)),
             ("fault_state_unchanged",
              lambda: dict(want, delta_norms=np.zeros_like(
                  want["delta_norms"])))]
    if look:
        cases.append(("stated_bf16", lambda: follow(precision="bf16")))
    out = {}
    for name, make in cases:
        got = make()
        numbers, notes = compare.train_numbers(got, want)
        numbers["compiles_in_window"] = 0
        compared = compare.judge(numbers, config["limits"])
        out[name] = {"numbers": numbers, "notes": notes, "compared": compared,
                     "correct": all(c["ok"] for c in compared.values()),
                     "raw": {k: np.asarray(v).tolist()
                             for k, v in got.items()}}
    out["reference"] = {k: np.asarray(v).tolist() for k, v in want.items()}
    return out

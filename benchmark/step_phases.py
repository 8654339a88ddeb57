"""Decompose the ResNet-50 training step: fwd vs fwd+bwd vs full update.

The r4 HLO audit (benchmark/profile_resnet.py on the TPU backend) shows
the step's HBM traffic is already well-scheduled by XLA (weight and
activation prefetch into VMEM, async convs), yet measured throughput
sits ~2.5x above the bytes-bound floor. This harness attributes the
step time to its three phases by timing three programs on the device:

  fwd       - layers + loss only (forward pass)
  fwd+bwd   - + append_backward; all weight grads kept alive by
              fetching a sum of their means (dead-code elimination
              would otherwise prune the filter-grad branches)
  full      - + Momentum update (the bench headline step)

All runs: bs128, pure AMP, autotuned nhwc+s2d picks, fuse=1 (phase
programs have no state update, so a lax.scan carry chain cannot be
used to fuse steps — and the comparison must hold dispatch overhead
constant across phases anyway).

Usage: python -m benchmark.step_phases [--steps 8]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.headline import HEADLINE_ENV


def build(phase):
    import paddle_tpu as pt
    from paddle_tpu import layers, models
    from paddle_tpu.core.backward import append_backward

    main, startup = pt.Program(), pt.Program()
    pt.switch_main_program(main)
    pt.switch_startup_program(startup)
    img = layers.data("img", shape=[3, 224, 224], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    pred = models.resnet_imagenet(img, class_dim=1000, depth=50)
    avg = layers.mean(layers.cross_entropy(pred, label))
    fetch = avg
    if phase == "fwd+bwd":
        pgs = append_backward(avg)
        acc = None
        for _, g in pgs:
            m = layers.mean(g)
            acc = m if acc is None else layers.elementwise_add(acc, m)
        fetch = acc
    elif phase == "full":
        pt.Momentum(learning_rate=0.1, momentum=0.9).minimize(avg)
    pt.amp.enable(main, pure=True)
    return main, fetch


def measure(phase, batch, steps, windows=3):
    import numpy as np
    import paddle_tpu as pt

    main, fetch = build(phase)
    with pt.scope_guard(pt.Scope()):
        exe = pt.Executor(pt.TPUPlace(0))
        exe.run(pt.default_startup_program())
        rng = np.random.RandomState(0)
        feed = exe.prepare_feed(
            {"img": rng.rand(batch, 3, 224, 224).astype("float32"),
             "label": rng.randint(0, 1000, (batch, 1)).astype("int64")})
        out, = exe.run(main, feed=feed, fetch_list=[fetch],
                       return_numpy=False)
        np.asarray(out)  # sync: compile + first run
        best = float("inf")
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(steps):
                out, = exe.run(main, feed=feed, fetch_list=[fetch],
                               return_numpy=False)
            np.asarray(out)  # the window ends when the last value is read
            best = min(best, (time.perf_counter() - t0) / steps)
    return best


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=128)
    args = ap.parse_args(argv)
    for k, v in HEADLINE_ENV.items():
        os.environ.setdefault(k, v)

    rows = {}
    for phase in ("fwd", "fwd+bwd", "full"):
        ms = measure(phase, args.batch, args.steps) * 1e3
        rows[phase] = round(ms, 2)
        print("[phases] %-8s %7.2f ms/step" % (phase, ms),
              file=sys.stderr, flush=True)
    rows["bwd_ms"] = round(rows["fwd+bwd"] - rows["fwd"], 2)
    rows["update_ms"] = round(rows["full"] - rows["fwd+bwd"], 2)
    print(json.dumps({"batch": args.batch, "ms_per_step": rows}))


if __name__ == "__main__":
    main()

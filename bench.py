"""Headline benchmark: ResNet-50 training throughput + MFU on one TPU chip.

One process, one cell: ResNet-50 (3x224x224, 1000 classes), batch 128,
Momentum, pure AMP (bf16 operands and activation stream, f32
accumulation), ``fuse`` steps per dispatch. The program is warmed (the
compile is reported as set-up, not timed), then ``windows`` windows of
``steps`` steps are timed on the host clock, each ended by a device->host
read of the loss, and the best window is reported.

There is no fallback: a process that finds no TPU exits non-zero and
prints no metric line — a number from XLA:CPU is never written under the
device metric's name. MFU is scored against the peak of the chip's own
``device_kind`` (table below); an unknown kind is an error, not a default.

The LAST stdout line is one JSON object (``metric``, ``value``, ``unit``,
``vs_baseline``, ``batch``, ``steps``, ``fuse``, ``amp``, ``platform``,
``device_kind``, ``device_count``, ``commit``, ``mfu``). Status (the
compile time among it) goes to stderr.

Baseline: the reference's best published single-device ResNet-50 training
number, 84.08 images/sec (reference: benchmark/IntelOptimizedPaddle.md:40-46,
2S Xeon 6148). See BASELINE.md.

Usage: python bench.py [batch [steps]]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchmark.baselines import REF_BASELINES  # noqa: E402

BASELINE_IMG_S = REF_BASELINES["resnet50"]
METRIC = "resnet50_train_images_per_sec_per_chip"

# peak dense bf16 FLOP/s per chip, keyed by a lower-cased substring of
# jax's device_kind (public spec sheets; v5e: Google Cloud "TPU v5e")
_PEAK_FLOPS = {"v5 lite": 197e12, "v5e": 197e12, "v4": 275e12,
               "v5p": 459e12, "v6 lite": 918e12, "v6e": 918e12}
# training step ~= 3x forward; ResNet-50 fwd @224 ~= 3.8 GFLOP/image
_ANALYTIC_FLOPS_PER_IMG = 3 * 3.8e9

_T0 = time.time()


def _log(tag, msg):
    print("[bench %s %6.1fs] %s" % (tag, time.time() - _T0, msg),
          file=sys.stderr, flush=True)


def _git_commit():
    """Producing commit, stamped on the record ("unknown" outside git)."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def _peak_flops(dev):
    """Peak bf16 FLOP/s of ``dev`` from its own device_kind."""
    kind = (getattr(dev, "device_kind", "") or "").lower()
    for name, peak in _PEAK_FLOPS.items():
        if name in kind:
            return peak
    raise ValueError(
        "no peak FLOP/s known for device_kind %r (platform %r); add it to "
        "bench._PEAK_FLOPS with its source" % (kind, dev.platform))


def _build_program(pt, layers, models, amp_on):
    main_p, startup = pt.Program(), pt.Program()
    pt.switch_main_program(main_p)
    pt.switch_startup_program(startup)
    img = layers.data("img", shape=[3, 224, 224], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    pred = models.resnet_imagenet(img, class_dim=1000, depth=50)
    cost = layers.cross_entropy(pred, label)
    avg = layers.mean(cost)
    pt.Momentum(learning_rate=0.1, momentum=0.9).minimize(avg)
    if amp_on:
        # bf16 matmul/conv with f32 accumulation: the MXU's native
        # precision; "pure" additionally keeps the activation stream bf16
        pt.amp.enable(main_p, pure=(amp_on == "pure"))
    return main_p, avg


def _measure(pt, layers, models, tag, batch, steps, fuse, amp_on,
             windows=3):
    """Build + compile + time ``steps`` training steps; returns img/s
    (best of ``windows``)."""
    import numpy as np
    main_p, avg = _build_program(pt, layers, models, amp_on)
    with pt.scope_guard(pt.Scope()):
        exe = pt.Executor(pt.TPUPlace(0))
        exe.run(pt.default_startup_program())
        rng = np.random.RandomState(0)
        feed = exe.prepare_feed(
            {"img": rng.rand(batch, 3, 224, 224).astype("float32"),
             "label": rng.randint(0, 1000, (batch, 1)).astype("int64")})
        _log(tag, "compiling batch=%d fuse=%d amp=%s ..."
             % (batch, fuse, amp_on))
        tc = time.time()
        loss, = exe.run(main_p, feed=feed, fetch_list=[avg],
                        return_numpy=False, repeat=fuse)
        loss = np.asarray(loss)  # sync
        _log(tag, "compile+first run %.1fs, loss=%.4f"
             % (time.time() - tc, float(loss.reshape(-1)[0])))
        iters = max(steps // fuse, 1)
        best_dt = float("inf")
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(iters):
                out, = exe.run(main_p, feed=feed, fetch_list=[avg],
                               return_numpy=False, repeat=fuse)
            np.asarray(out)  # the window ends when the last loss is read
            best_dt = min(best_dt, time.perf_counter() - t0)
    img_s = batch * fuse * iters / best_dt
    _log(tag, "batch=%d fuse=%d amp=%s: %.2f img/s best-of-%d (%.1f ms/step)"
         % (batch, fuse, amp_on, img_s, windows,
            1e3 * best_dt / (fuse * iters)))
    return img_s


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    batch = int(argv[0]) if len(argv) > 0 else 128
    steps = int(argv[1]) if len(argv) > 1 else 16
    fuse = max(steps // 4, 1)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _log("main", "no TPU: jax.devices()[0] is %r (platform %r); this "
             "benchmark measures the chip and has no CPU mode"
             % (dev, dev.platform))
        return 2
    peak = _peak_flops(dev)
    _log("main", "device %s (%s), %d device(s)"
         % (dev, dev.device_kind, len(jax.devices())))

    import paddle_tpu as pt
    from paddle_tpu import layers, models

    img_s = _measure(pt, layers, models, "main", batch, steps, fuse, "pure")
    print(json.dumps({
        "metric": METRIC, "value": round(img_s, 2), "unit": "images/sec",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "batch": batch, "steps": steps, "fuse": fuse, "amp": "pure",
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()), "commit": _git_commit(),
        "mfu": round(img_s * _ANALYTIC_FLOPS_PER_IMG / peak, 4)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

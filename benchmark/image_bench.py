"""Image model training benchmark.

reference harness: benchmark/paddle/image/{alexnet,googlenet,resnet,vgg}.py
+ run.sh (batch-size sweeps, img/s reporting; baselines in BASELINE.md).

Usage: python benchmark/image_bench.py --model resnet50 --batch_size 64
Prints one JSON line: images/sec (and ms/batch like the reference tables).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers, models


MODELS = {
    "alexnet": lambda img: models.alexnet(img, class_dim=1000),
    "vgg16": lambda img: models.vgg16(img, class_dim=1000),
    "googlenet": lambda img: models.googlenet(img, class_dim=1000)[0],
    "resnet50": lambda img: models.resnet_imagenet(img, class_dim=1000,
                                                   depth=50),
}


from benchmark.baselines import REF_BASELINES  # single source


def bench(model="resnet50", batch_size=64, iters=16, warmup=1,
          image_size=224, dtype="float32", amp=True, fuse=4, windows=3):
    """Best-of-windows timing (as repo-root bench.py): device-resident feed via
    prepare_feed, ``fuse`` steps per dispatch (lax.scan), best-of-
    ``windows`` wall-clock samples with a host read-back as the sync."""
    main, startup = pt.Program(), pt.Program()
    pt.switch_main_program(main)
    pt.switch_startup_program(startup)
    img = layers.data("img", shape=[3, image_size, image_size], dtype=dtype)
    label = layers.data("label", shape=[1], dtype="int64")
    pred = MODELS[model](img)
    loss = layers.mean(layers.cross_entropy(pred, label))
    pt.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    if amp:
        pt.amp.enable(main)

    exe = pt.Executor(pt.TPUPlace())
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(0)
    feed = exe.prepare_feed({
        "img": rng.rand(batch_size, 3, image_size,
                        image_size).astype("float32"),
        "label": rng.randint(0, 1000, (batch_size, 1)).astype("int64"),
    })
    for _ in range(max(warmup, 1)):
        out, = exe.run(feed=feed, fetch_list=[loss], return_numpy=False,
                       repeat=fuse)
    np.asarray(out)  # sync: the warm-up has finished on the device
    per = max(iters // fuse, 1)
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(per):
            out, = exe.run(feed=feed, fetch_list=[loss],
                           return_numpy=False, repeat=fuse)
        np.asarray(out)
        best = min(best, (time.perf_counter() - t0) / (per * fuse))
    r = {"model": model, "batch_size": batch_size, "amp": amp,
         "ms_per_batch": round(best * 1e3, 2),
         "images_per_sec": round(batch_size / best, 2)}
    if model in REF_BASELINES:
        r["vs_baseline"] = round(batch_size / best / REF_BASELINES[model],
                                 3)
    return r


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50", choices=sorted(MODELS))
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--image_size", type=int, default=224)
    args = p.parse_args()
    print(json.dumps(bench(args.model, args.batch_size, args.iters,
                           image_size=args.image_size)))

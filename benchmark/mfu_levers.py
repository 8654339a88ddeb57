"""MFU lever table: one lever, one on-device measurement, one row.

Executes the plan in doc/design/mfu_notes.md on real hardware (VERDICT r3
item 5): starting from a base configuration, each lowering/step lever is
toggled INDIVIDUALLY and the end-to-end ResNet-50 training throughput is
measured on the device, so every row attributes a delta to exactly one
change. Rows go to benchmark/results/mfu_levers_<device>.json.

Rows persist in the shared paddle_tpu.bench.v1 schema
(paddle_tpu/tune/results.py), re-written after every row so a budget
kill keeps the table so far.

Levers (see doc/design/mfu_notes.md for the mechanism behind each):
  fuse      - steps per dispatch (lax.scan step fusion; amortizes the
              host->device dispatch round trip)
  amp       - bf16 compute / f32 accumulation (MXU native precision)
  layout    - nchw passthrough vs nhwc-internal conv layout
  impl      - native lax.conv vs KH*KW shifted-einsum (im2col-as-matmul)
  s2d       - space-to-depth stem rewrite (7x7/s2 C=3 -> 4x4/s1 C=12)
  batch     - arithmetic intensity (flops/byte rises with N)

Usage: python -m benchmark.mfu_levers [--steps 16] [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_ENV_KEYS = ("PADDLE_TPU_CONV_IMPL", "PADDLE_TPU_CONV_LAYOUT",
             "PADDLE_TPU_CONV_S2D")

# base config: the r4 bench headline configuration
BASE = {"batch": 128, "fuse": 4, "amp": True,
        "impl": "conv", "layout": "nchw", "s2d": "0"}


def run_config(cfg, steps, tag="levers"):
    from bench import _measure, _ANALYTIC_FLOPS_PER_IMG, _peak_flops
    import jax
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    for k, v in zip(_ENV_KEYS, (cfg["impl"], cfg["layout"], cfg["s2d"])):
        os.environ[k] = v
    t0 = time.time()
    img_s = _measure(pt, layers, models, tag, batch=cfg["batch"],
                     steps=max(steps, cfg["fuse"]), fuse=cfg["fuse"],
                     amp_on=cfg["amp"])
    peak = _peak_flops(jax.devices()[0])
    return {"img_s": round(img_s, 1),
            "mfu": round(img_s * _ANALYTIC_FLOPS_PER_IMG / peak, 4),
            "wall_s": round(time.time() - t0, 1)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--quick", action="store_true",
                    help="base + fuse sweep only")
    ap.add_argument("--only", default=None,
                    help="comma-separated lever names to run (others "
                         "skipped); rows merge into the existing table")
    args = ap.parse_args(argv)

    import jax
    from paddle_tpu.tune.results import bench_record, write_result
    dev = jax.devices()[0]
    dev_key = dev.device_kind

    grid = [("base", dict(BASE))]
    for fuse in (1, 8, 16):
        grid.append(("fuse=%d" % fuse, dict(BASE, fuse=fuse)))
    if not args.quick:
        grid += [
            ("amp=off", dict(BASE, amp=False)),
            ("amp=pure", dict(BASE, amp="pure")),
            ("layout=nhwc", dict(BASE, layout="nhwc")),
            ("impl=matmul", dict(BASE, impl="matmul")),
            ("s2d=on", dict(BASE, s2d="1")),
            ("batch=64", dict(BASE, batch=64)),
            ("batch=256", dict(BASE, batch=256)),
        ]

    out = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results",
        "mfu_levers_%s.json" % dev_key.replace("|", "_")
        .replace("/", "_").replace(" ", "_"))
    rows = []
    if args.only:
        only = {n.strip() for n in args.only.split(",")}
        grid = [(n, c) for n, c in grid if n in only]
        try:  # merge into the prior table instead of clobbering it
            with open(out) as f:
                prior = json.load(f)
            if prior.get("device") == dev_key:
                rows = [r for r in prior["rows"]
                        if r.get("lever") not in only]
        except Exception:
            pass
    for name, cfg in grid:
        print("[levers] %s: %r" % (name, cfg), file=sys.stderr, flush=True)
        try:
            r = run_config(cfg, args.steps)
        except Exception as e:
            r = {"error": repr(e)}
        row = {"lever": name, **cfg, **r}
        rows.append(row)
        print(json.dumps(row), flush=True)
        # persist after every row: a budget kill keeps the table so far
        write_result(bench_record(
            "mfu_levers", rows, device=dev_key,
            meta={"base": BASE, "steps": args.steps}), path=out)
    print("wrote %s" % out)


if __name__ == "__main__":
    main()

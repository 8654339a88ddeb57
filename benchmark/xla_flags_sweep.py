"""XLA:TPU compiler-flag + step-config sweep on the real device.

Round-4 MFU climb, next lever set after doc/design/mfu_notes.md's table:
the *compiler* knobs. XLA_FLAGS must be set before backend init, so the
parent forks one child process per candidate, each timing the full
ResNet-50 training step (bench._measure: best-of-N windows, read-back
sync) on the headline configuration (bs128 / fuse4 / pure AMP /
autotuned nhwc + s2d picks).

Candidates (public XLA:TPU knobs, cf. the flag sets MaxText/flax
examples ship):
  latency-hiding scheduler - overlaps copies/collectives with compute;
      on a single chip mostly affects HBM prefetch scheduling
  scoped VMEM limit        - how much VMEM a fusion may claim; larger
      values let XLA keep bigger operand tiles resident
  step-shape re-checks     - fuse / batch re-sweep on top of pure AMP
      (the published lever table toggled them on *plain* AMP; the
      tradeoff moves when activation bytes halve)

Usage: python -m benchmark.xla_flags_sweep [--steps 16] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.headline import HEADLINE_ENV

_LHS = "--xla_tpu_enable_latency_hiding_scheduler=true"
_VMEM = "--xla_tpu_scoped_vmem_limit_kib=%d"

CONFIGS = [
    # (name, xla_flags, measure-kwarg overrides)
    ("base", "", {}),
    ("lhs", _LHS, {}),
    ("vmem64", _VMEM % 65536, {}),
    ("vmem96", _VMEM % 98304, {}),
    ("lhs+vmem96", _LHS + " " + _VMEM % 98304, {}),
    ("fuse8", "", {"fuse": 8}),
    ("fuse16", "", {"fuse": 16}),
    ("bs192", "", {"batch": 192}),
    ("bs256", "", {"batch": 256}),
]


def child_main(args):
    for k, v in HEADLINE_ENV.items():
        os.environ[k] = v
    from bench import _measure, _ANALYTIC_FLOPS_PER_IMG, _peak_flops
    import jax
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    dev = jax.devices()[0]
    img_s = _measure(pt, layers, models, "sweep", batch=args.batch,
                     steps=max(args.steps, args.fuse), fuse=args.fuse,
                     amp_on="pure")
    print(json.dumps({
        "img_s": round(img_s, 1),
        "mfu": round(img_s * _ANALYTIC_FLOPS_PER_IMG / _peak_flops(dev), 4),
        "device": getattr(dev, "device_kind", "?"),
    }), flush=True)


def parent_main(args):
    from paddle_tpu.tune.results import bench_record, write_result
    rows = []
    device = None

    def persist():
        # write after EVERY row (mfu_levers.py convention): a hung child
        # or budget kill must not lose the already-measured table —
        # shared paddle_tpu.bench.v1 schema
        out_path = args.out or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "results",
            "xla_flags_%s.json" % (device or "unknown").replace(" ", "_"))
        rec = bench_record(
            "xla_flags", rows, device=device or "unknown",
            meta={"note": "XLA flag sweep, ResNet-50 train step, "
                          "bs128/fuse4/pure-AMP base unless overridden",
                  "steps": args.steps})
        return write_result(rec, path=out_path)

    for name, flags, over in CONFIGS:
        env = dict(os.environ)
        prior = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (prior + " " + flags).strip()
        cmd = [sys.executable, "-m", "benchmark.xla_flags_sweep", "--child",
               "--batch", str(over.get("batch", 128)),
               "--fuse", str(over.get("fuse", 4)),
               "--steps", str(args.steps)]
        t0 = time.time()
        print("[sweep] %s: XLA_FLAGS=%r ..." % (name, flags),
              file=sys.stderr, flush=True)
        row = {"name": name, "xla_flags": flags, **over}
        try:
            p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                               timeout=1800, cwd=os.path.dirname(
                                   os.path.dirname(os.path.abspath(__file__))))
            out = [l for l in p.stdout.splitlines() if l.startswith("{")]
            if p.returncode == 0 and out:
                row.update(json.loads(out[-1]))
                device = row.pop("device", device)
            else:
                row["error"] = (p.stderr.strip().splitlines() or ["rc=%d" %
                                p.returncode])[-1][:300]
        except subprocess.TimeoutExpired:
            row["error"] = "child timeout (1800s)"
        row["wall_s"] = round(time.time() - t0, 1)
        print("[sweep] %s -> %s" % (name, row), file=sys.stderr, flush=True)
        rows.append(row)
        out_path = persist()
    print(json.dumps({"out": out_path, "rows": rows}))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--fuse", type=int, default=4)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.child:
        child_main(args)
    else:
        parent_main(args)


if __name__ == "__main__":
    main()

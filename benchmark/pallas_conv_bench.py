"""Per-shape microbench: pallas conv3x3 vs lax.conv on ResNet-50's
3x3 conv census (reference role: conv_cudnn_op.cu.cc per-shape algorithm
search). Writes benchmark/results/pallas_conv_<device>.json in the
shared paddle_tpu.bench.v1 schema (paddle_tpu/tune/results.py).

Run on whatever device is live (`python -m benchmark.pallas_conv_bench`);
on CPU the pallas kernel runs in interpret mode, so the numbers are only
meaningful on TPU — the device kind is recorded with every record.

Timing and parity ride the shared paddle_tpu.tune helpers (time_best's
best-of-trials windows with a 1-element readback sync; parity_report's
dtype-aware tolerance) — the same measurement the autotune loop and
mfu_ladder.py use, so rows are comparable across harnesses.

NOTE (r4 lesson, benchmark/results/mfu_levers_*.json): an isolated 3x3
microbench CANNOT justify adoption — impl=matmul won this exact probe
2.6x and regressed the end-to-end step 3x. Adoption is decided on full
training steps and through the tune winner cache (timed per shape, stock
XLA always in the race). This file exists for the per-shape evidence table.
"""
from __future__ import annotations

import json


# ResNet-50 bottleneck 3x3 convs at the bench's bs128 (NHWC: N, H, W, C->O)
CENSUS = [
    (128, 56, 56, 64, 64),
    (128, 28, 28, 128, 128),
    (128, 14, 14, 256, 256),
    (128, 7, 7, 512, 512),
]


def bench(batch=None, dtype="bfloat16", iters=8):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.conv3x3 import conv3x3_s1_nhwc
    from paddle_tpu.tune.results import bench_record, write_result
    from paddle_tpu.tune.timer import parity_report, time_best

    dt = jnp.dtype(dtype)
    rows = []
    for (n, h, w_, c, o) in CENSUS:
        n = batch or n
        k1, k2 = jax.random.split(jax.random.PRNGKey(len(rows)))
        x = jax.random.normal(k1, (n, h, w_, c), dt)
        w = jax.random.normal(k2, (3, 3, c, o), dt) * 0.05

        @jax.jit
        def lax_conv(x_, w_):
            return jax.lax.conv_general_dilated(
                x_, w_, window_strides=(1, 1), padding=[(1, 1), (1, 1)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32).astype(x_.dtype)

        @jax.jit
        def pallas_conv(x_, w_):
            return conv3x3_s1_nhwc(x_, w_)

        flops = 2 * n * h * w_ * c * o * 9
        t_lax = time_best(lax_conv, x, w, iters=iters)
        t_pal = time_best(pallas_conv, x, w, iters=iters)
        mismatch = parity_report(lax_conv(x, w), pallas_conv(x, w))
        row = {"shape": [n, h, w_, c, o],
               "lax_ms": round(1e3 * t_lax, 3),
               "pallas_ms": round(1e3 * t_pal, 3),
               "lax_tflops": round(flops / t_lax / 1e12, 1),
               "pallas_tflops": round(flops / t_pal / 1e12, 1),
               "speedup": round(t_lax / t_pal, 3),
               "parity": mismatch is None,
               "parity_note": mismatch}
        rows.append(row)
        print(json.dumps(row))
    rec = bench_record(
        "pallas_conv", rows,
        meta={"dtype": dtype,
              "note": "interpret-mode (meaningless) if platform != tpu; "
                      "adoption decided on full training steps + "
                      "the tune winner cache"})
    path = write_result(rec)
    print("wrote", path)
    return rec


if __name__ == "__main__":
    import sys
    bs = int(sys.argv[1]) if len(sys.argv) > 1 else None
    bench(batch=bs)

"""Kernel-only sweep of the flash-attention kernels' blocks (needs the chip).

    python3 tools/flash_sweep.py [--out chiprun_out/flash_sweep.jsonl]
        [--lengths 128,256,...] [--widths 64x64,128x128,192x128]
        [--blocks 128,256,512] [--whole-only]

For every length S, head widths (D, Dv) and causal / not, at batch x heads
= 262,144 / S (the bytes of the benchmark's kanana cell: 64 heads of 4,096
positions), bf16: the device time of each of the three kernels (forward,
dQ, dK/dV) alone at every (block_q, block_k) that divides S, whether or not
``flash_attention.vmem_bytes`` would grant it (a shape Mosaic refuses is
recorded as refused, with what the reckoning said), and of the whole call
as a caller makes it (``whole fwd``: ``flash_attention``; ``whole
fwd+bwd``: its ``jax.grad``, at the blocks ``default_blocks`` picks).
``--whole-only`` times only the latter, so the same file runs against
another checkout's kernels (``PYTHONPATH``).
One JSON line a timing; the table ``PERF.md`` §6 (PR 33) quotes is read off
them, and ``default_blocks``' order of preference with it.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import importlib
import itertools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if not any(os.path.isdir(os.path.join(p, "paddle_tpu")) for p in sys.path
           if p):
    sys.path.insert(0, ROOT)

TOKENS = 64 * 4096
REPEATS = 5


def _operands(jax, jnp, S, D, Dv):
    bh = TOKENS // S
    ks = jax.random.split(jax.random.PRNGKey(S + D), 4)
    mk = lambda k, w: jax.random.normal(k, (bh, S, w), jnp.bfloat16)
    return mk(ks[0], D), mk(ks[1], D), mk(ks[2], Dv), mk(ks[3], Dv)


def _time(jax, compiled, args):
    """ms a call: the best of three batches of REPEATS back-to-back calls
    (nothing else of this process runs meanwhile)."""
    jax.block_until_ready(compiled(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = compiled(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / REPEATS * 1e3)
    return best


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default="chiprun_out/flash_sweep.jsonl")
    ap.add_argument("--lengths",
                    default="128,256,512,1024,2048,4096,8192")
    ap.add_argument("--widths", default="64x64,128x128,192x128")
    ap.add_argument("--blocks", default="128,256,512")
    ap.add_argument("--whole-only", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("flash_sweep: no TPU; a CPU timing says nothing",
              file=sys.stderr)
        return 2
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    blocks = [int(b) for b in args.blocks.split(",")]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")

    def emit(**row):
        row["device"] = jax.devices()[0].device_kind
        out.write(json.dumps(row) + "\n")
        out.flush()
        print(json.dumps(row), flush=True)

    pool = concurrent.futures.ThreadPoolExecutor(10)
    for spec in args.widths.split(","):
        D, Dv = (int(x) for x in spec.split("x"))
        for S in (int(s) for s in args.lengths.split(",")):
            q, k, v, do = _operands(jax, jnp, S, D, Dv)
            scale = D ** -0.5
            for causal in (True, False):
                case = {"S": S, "D": D, "Dv": Dv, "causal": causal,
                        "bh": TOKENS // S}
                q4, k4, v4, do4 = (a[None].transpose(0, 2, 1, 3)
                                   for a in (q, k, v, do))
                whole = {
                    "whole fwd": (lambda q, k, v, do: fa.flash_attention(
                        q, k, v, causal=causal)),
                    "whole fwd+bwd": (lambda q, k, v, do: jax.grad(
                        lambda q, k, v: jnp.sum(
                            fa.flash_attention(q, k, v, causal=causal)
                            .astype(jnp.float32) * do), (0, 1, 2))(q, k, v)),
                }
                for name, fn in whole.items():
                    try:
                        c = jax.jit(fn).lower(q4, k4, v4, do4).compile()
                        emit(kernel=name, ms=_time(jax, c,
                                                   (q4, k4, v4, do4)),
                             **case)
                    except Exception as e:  # noqa: BLE001 - recorded
                        emit(kernel=name, refused=str(e)[-200:], **case)
                if args.whole_only:
                    continue
                o, lse = jax.jit(lambda q, k, v: fa._fa_forward(
                    q, k, v, causal, scale, S, interpret=False))(q, k, v)
                delta = jnp.einsum("bsd,bsd->bs", do.astype(jnp.float32),
                                   o.astype(jnp.float32))
                jobs = []
                # while the explicit blocks are traced the reckoning grants
                # everything: what it would refuse is timed too
                limit, fa.VMEM_LIMIT = fa.VMEM_LIMIT, 1 << 40
                for bq, bk in itertools.product(blocks, blocks):
                    if bq > S or bk > S or S % bq or S % bk:
                        continue
                    cfg = (("block_k", bk), ("block_q", bq))

                    def fwd(q, k, v, cfg=cfg):
                        return fa._fa_forward(q, k, v, causal, scale, S,
                                              interpret=False, config=cfg)

                    def bwd(q, k, v, do, lse, delta, cfg=cfg, which=0):
                        r = fa._fa_backward(q, k, v, do, lse, delta, causal,
                                            scale, S, interpret=False,
                                            config=cfg)
                        return r[0] if which == 0 else r[1:]

                    bargs = (q, k, v, do, lse, delta)
                    for kern, fn, a in (
                            ("fwd", fwd, (q, k, v)),
                            ("dq", bwd, bargs),
                            ("dkv", lambda *a, f=bwd: f(*a, which=1),
                             bargs)):
                        fut = pool.submit(
                            lambda fn=fn, a=a: jax.jit(fn).lower(*a)
                            .compile())
                        jobs.append((kern, bq, bk, fut, a))
                # every compile ends before the first timing starts
                concurrent.futures.wait([j[3] for j in jobs])
                fa.VMEM_LIMIT = limit
                for kern, bq, bk, fut, a in jobs:
                    row = dict(case, kernel=kern, bq=bq, bk=bk)
                    row["reckoned_mib"] = round(fa.vmem_bytes(
                        kern, bq, bk, S, S, D, Dv, 2) / 2 ** 20, 2)
                    try:
                        emit(ms=_time(jax, fut.result(), a), **row)
                    except Exception as e:  # noqa: BLE001 - recorded
                        emit(refused=str(e)[-160:], **row)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Kernel-only timing of the held experts' grouped products (needs the chip).

    python3 tools/gmm_sweep.py [--out gmm_sweep.jsonl]
        [--cells K,T,P] [--tiles 128,256,512] [--megablox K] [--points 0,2]

For one expert layer of each language-model cell (K: d 2,048, f 768, 16
held experts; T: d 2,048, f 1,024, 16; P: d 2,560, f 768, 8), at each of
its rungs with a step's held rows split unevenly over the experts (seeded),
bf16 operands: the device time of each of the three kernels a grouped
product makes (the product, the left operand's gradient, the weight
gradient) for the gate / up product ``[rows, d] x [G, d, f]`` and the down
product ``[rows, f] x [G, f, d]``, made by

- ``ragged``: ``jax.lax.ragged_dot`` (f32 results, its ``jax.vjp``), which
  XLA:TPU makes into ``ragged-dot-*`` kernels;
- ``megablox``: jax's shipped Pallas ``gmm`` / ``tgmm`` (cells of
  ``--megablox``), at its default tiling 128 x 128 x 128 and at 256 rows
  with whole widths;
- ``own``: ``paddle_tpu.kernels.grouped_matmul``'s kernels at each row tile
  of ``--tiles`` (blocks as wide as their operands, as ``default_tiling``
  makes them), handed f32 weights as an expert layer under AMP hands them
  (cast in VMEM; the weight gradient written in f32).

Each ``own`` timing also says whether its result equals the ragged dot's
on the held rows (to bf16's rounding of an f32 sum) and is exactly 0 past
them. One JSON line a timing, with the TFLOP/s of the held rows; PERF.md §6
quotes the table, and ``default_tiling``'s row tiles are read off
it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if not any(os.path.isdir(os.path.join(p, "paddle_tpu")) for p in sys.path
           if p):
    sys.path.insert(0, ROOT)

REPEATS = 10
# cell -> (d, f, held experts, [(rung rows, held rows)])
CELLS = {
    "K": (2048, 768, 16, [(12288, 11000), (24576, 14000), (49152, 14000)]),
    "T": (2048, 1024, 16, [(16384, 14000), (32768, 17000), (65536, 17000)]),
    "P": (2560, 768, 8, [(24576, 2000), (49152, 30000), (98304, 53600)]),
}


def _time(jax, compiled, args):
    """ms a call: the best of three batches of REPEATS back-to-back calls."""
    jax.block_until_ready(compiled(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = compiled(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / REPEATS * 1e3)
    return best


def _sizes(np, held, groups, seed):
    share = np.random.default_rng(seed).dirichlet([8.0] * groups)
    sizes = np.floor(share * held).astype(np.int32)
    sizes[0] += held - int(sizes.sum())
    return sizes


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default="gmm_sweep.jsonl")
    ap.add_argument("--cells", default="K,T,P")
    ap.add_argument("--tiles", default="128,256,512")
    ap.add_argument("--points", default="",
                    help="rung indices to time, e.g. 0,2 (default: all)")
    ap.add_argument("--megablox", default="K")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("gmm_sweep: no TPU; a CPU timing says nothing", file=sys.stderr)
        return 2
    import importlib
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    from paddle_tpu.kernels import grouped_matmul as gm
    f32, bf16 = jnp.float32, jnp.bfloat16
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")
    rows_out = []

    def record(**rec):
        rec["device"] = jax.devices()[0].device_kind
        out.write(json.dumps(rec) + "\n")
        out.flush()
        rows_out.append(rec)

    def rd(a, b, s):
        return jax.lax.ragged_dot(a, b, s, preferred_element_type=f32)

    ragged = {
        "fwd": lambda a, w, s, ct, cb, w32: rd(a, w, s),
        "dlhs": lambda a, w, s, ct, cb, w32: jax.vjp(
            lambda x: rd(x, w, s), a)[1](ct)[0],
        "drhs": lambda a, w, s, ct, cb, w32: jax.vjp(
            lambda y: rd(a, y, s), w)[1](ct)[0],
    }

    def own(kind, tm):
        def fwd(a, _w, s, _ct, ct, w):
            return gm._launch("gmm", a.shape[0], a.shape[1], w.shape[2],
                              w.shape[0], a.dtype, w.dtype, jnp.dtype(f32),
                              (tm, a.shape[1], w.shape[2]), False)(a, w, s)[0]

        def dlhs(a, _w, s, _ct, ct, w):
            return gm._launch("gmm_t", a.shape[0], ct.shape[1], a.shape[1],
                              w.shape[0], a.dtype, w.dtype, jnp.dtype(bf16),
                              (tm, ct.shape[1], a.shape[1]), False)(
                                  ct, w, s)[0]

        def drhs(a, w, s, _ct, ct, _w32):
            return gm._launch("tgmm", a.shape[0], a.shape[1], ct.shape[1],
                              w.shape[0], a.dtype, ct.dtype, jnp.dtype(f32),
                              (tm, a.shape[1], ct.shape[1]), False)(
                                  a, ct, s)[0]
        return {"fwd": fwd, "dlhs": dlhs, "drhs": drhs}[kind]

    def mega(kind, tiling):
        def t(a, w):
            return tiling or (256, a.shape[1], w.shape[2])
        return {
            "fwd": lambda a, w, s, _ct, ct, _w32: megablox.gmm(
                a, w, s, f32, t(a, w)),
            "dlhs": lambda a, w, s, _ct, ct, _w32: megablox.gmm(
                ct, w, s, bf16, tiling or (256, w.shape[2], w.shape[1]),
                transpose_rhs=True),
            "drhs": lambda a, w, s, _ct, ct, _w32: megablox.tgmm(
                a.T, ct, s, bf16, tiling or (256, a.shape[1], ct.shape[1])),
        }[kind]

    tiles = [int(t) for t in args.tiles.split(",")]
    for cell in args.cells.split(","):
        d, f, groups, points = CELLS[cell]
        for seed, (rung, held) in enumerate(points):
            if args.points and str(seed) not in args.points.split(","):
                continue
            sizes = jnp.asarray(_sizes(np, held, groups, seed + 1))
            for prod, (k, n) in (("gate", (d, f)), ("down", (f, d))):
                keys = jax.random.split(jax.random.PRNGKey(rung + k), 3)
                a = jax.random.normal(keys[0], (rung, k), bf16)
                w = (0.05 * jax.random.normal(keys[1], (groups, k, n))
                     ).astype(bf16)
                ct = jax.random.normal(keys[2], (rung, n), f32)
                # the result's gradient: f32 to the ragged dot, as the step
                # hands it; bf16 to the Pallas kernels, whose callers cast
                # it where it is made
                call = (a, w, sizes, ct, ct.astype(bf16), w.astype(f32))
                flops = 2.0 * held * k * n
                want = {}
                for kind in ("fwd", "dlhs", "drhs"):
                    variants = [("ragged", None, ragged[kind])]
                    if cell in args.megablox.split(","):
                        variants += [("megablox", "128x128x128",
                                      mega(kind, (128, 128, 128))),
                                     ("megablox", "256xwhole",
                                      mega(kind, None))]
                    for tm in tiles:
                        variants.append(("own", "tm%d" % tm, own(kind, tm)))
                    for maker, tiling, fn in variants:
                        rec = dict(cell=cell, rung=rung, held=held,
                                   product=prod, kernel=kind, maker=maker,
                                   tiling=tiling)
                        try:
                            compiled = jax.jit(fn).lower(*call).compile()
                            ms = _time(jax, compiled, call)
                            rec.update(ms=round(ms, 4), tflops=round(
                                flops / ms / 1e9, 2))
                            got = compiled(*call)
                            if maker == "ragged":
                                want[kind] = got
                            elif kind in want:
                                rec.update(_check(jnp, kind, got, want[kind],
                                                  held))
                        except Exception as e:     # noqa: BLE001
                            rec["error"] = str(e).splitlines()[0][:300]
                        record(**rec)
    _summary(rows_out)
    return 0


def _check(jnp, kind, got, want, held):
    """Equal to the ragged dot's on the held rows, to bf16's rounding of an
    f32 sum; exactly 0 past them (the ragged dot leaves garbage there)."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    if kind == "drhs":
        live_got, live_want, tail_zero = got, want, True
    else:
        live_got, live_want = got[:held], want[:held]
        tail_zero = bool(jnp.all(got[held:] == 0))
    scale = float(jnp.max(jnp.abs(live_want))) or 1.0
    err = float(jnp.max(jnp.abs(live_got - live_want))) / scale
    return {"rel_err": round(err, 6), "tail_zero": tail_zero,
            "finite": bool(jnp.all(jnp.isfinite(got)))}


def _summary(rows):
    for rec in rows:
        print("%s %6d %6d %-4s %-4s %-8s %-12s %s" % (
            rec["cell"], rec["rung"], rec["held"], rec["product"],
            rec["kernel"], rec["maker"], rec["tiling"],
            rec.get("error") or "%.3f ms %.1f TF/s%s" % (
                rec["ms"], rec["tflops"],
                "" if "rel_err" not in rec else " err %.2g zero %s" % (
                    rec["rel_err"], rec["tail_zero"]))))


if __name__ == "__main__":
    sys.exit(main())

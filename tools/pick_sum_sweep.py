"""Kernel-only timing of an expert layer's held-pick sum (needs the chip).

    python3 tools/pick_sum_sweep.py [--out pick_sum_sweep.jsonl]
        [--cells K,T,P,N] [--shares 0.01,0.12,0.54] [--tiles 128,256]

For one expert layer of each language-model cell (K: 8,192 tokens x 6
picks, width 2,048, 16 held experts of 128; T: 8,192 x 8, 2,048, 16 of
128; P: 16,384 x 6, 2,560, 8 of 64; N: 8,192 x 6, 2,688, 8 of 128), at
each share of the (token, pick) pairs held (each pair held with that
chance, on a held expert drawn from a seeded Dirichlet split), bf16 rows
in the rung's ``a [rows, d]`` (``decoder_ops.held_rungs``: the smallest
rung that holds the pairs): the device time of

- ``jnp``: ``kernels.pick_sum.reference``, the form the kernel replaced
  (every pick's row gathered into [T, k, d], masked, summed in f32);
- ``pallas``: ``kernels.pick_sum.pick_sum`` as an expert layer calls it
  (the runs found from ``inv`` and ``sizes``, then the launch), at each
  token tile of ``--tiles``; ``launch`` the launch alone.

Each ``pallas`` timing also says how far its result is from the jnp
form's (relative to its largest value) and whether it is finite with the
rows past ``held`` set to NaN. One JSON line a timing; PERF.md §6 quotes
the table.
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
# cell -> (tokens, picks, width, held experts, experts)
CELLS = {
    "K": (8192, 6, 2048, 16, 128),
    "T": (8192, 8, 2048, 16, 128),
    "P": (16384, 6, 2560, 8, 64),
    "N": (8192, 6, 2688, 8, 128),
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


def pairs(np, T, k, groups, share, seed):
    """(inv [T, k], sizes [groups]) of a layer whose pairs are held with
    chance ``share``: the stable sort of the pairs by held expert, the
    others last."""
    rng = np.random.default_rng(seed)
    split = rng.dirichlet([8.0] * groups)
    expert = rng.choice(groups, size=(T, k), p=split)
    key = np.where(rng.random((T, k)) < share, expert, groups).reshape(-1)
    order = np.argsort(key, kind="stable")
    inv = np.empty(T * k, np.int32)
    inv[order] = np.arange(T * k, dtype=np.int32)
    sizes = np.bincount(key, minlength=groups + 1)[:groups].astype(np.int32)
    return inv.reshape(T, k), sizes


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default="pick_sum_sweep.jsonl")
    ap.add_argument("--cells", default="K,T,P,N")
    ap.add_argument("--shares", default="0.01,0.12,0.54")
    ap.add_argument("--tiles", default="128,256")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("pick_sum_sweep: no TPU; a CPU timing says nothing",
              file=sys.stderr)
        return 2
    from paddle_tpu.kernels import pick_sum as ps
    from paddle_tpu.ops.decoder_ops import held_rungs
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")
    done = []

    def record(**rec):
        rec["device"] = jax.devices()[0].device_kind
        out.write(json.dumps(rec) + "\n")
        out.flush()
        done.append(rec)

    for cell in args.cells.split(","):
        T, k, d, groups, experts = CELLS[cell]
        rungs = held_rungs(T * k, groups, experts)
        for n, share in enumerate(float(s) for s in args.shares.split(",")):
            inv, sizes = pairs(np, T, k, groups, share, n + 1)
            held = int(sizes.sum())
            rows = next(r for r in rungs if r >= held)
            a = jax.random.normal(jax.random.PRNGKey(n), (rows, d),
                                  jnp.bfloat16)
            a = a.at[held:].set(jnp.nan)
            call = (a, jnp.asarray(inv), jnp.asarray(sizes))
            base = dict(cell=cell, share=share, held=held, rows=rows)
            want = None
            variants = [("jnp", None, lambda a, i, s: ps.reference(
                a, i, jnp.sum(s)))]
            for tile in (int(t) for t in args.tiles.split(",")):
                variants += [("pallas", tile, _pallas(ps, jnp, tile)),
                             ("launch", tile, None)]
            for maker, tile, fn in variants:
                rec = dict(base, maker=maker, tile=tile)
                try:
                    if maker == "launch":
                        table, pos = ps._runs(call[1], call[2], tile, rows)
                        launch = ps._launch(rows, T, k, d, groups,
                                            jnp.dtype(a.dtype),
                                            jnp.dtype(jnp.float32), tile,
                                            False)
                        largs = (a, table, pos)
                        compiled = jax.jit(launch).lower(*largs).compile()
                        rec["ms"] = round(_time(jax, compiled, largs), 4)
                    else:
                        compiled = jax.jit(fn).lower(*call).compile()
                        rec["ms"] = round(_time(jax, compiled, call), 4)
                        got = compiled(*call)
                        if want is None:
                            want = got
                        else:
                            scale = float(jnp.max(jnp.abs(want))) or 1.0
                            rec["rel_err"] = float(
                                jnp.max(jnp.abs(got - want))) / scale
                            rec["finite"] = bool(jnp.all(jnp.isfinite(got)))
                except Exception as e:      # noqa: BLE001
                    rec["error"] = str(e).splitlines()[0][:300]
                record(**rec)
    for rec in done:
        print("%s %5.2f %6d %6d %-7s %-4s %s" % (
            rec["cell"], rec["share"], rec["held"], rec["rows"],
            rec["maker"], rec["tile"] or "",
            rec.get("error") or "%.4f ms%s" % (rec["ms"], "" if
                                               "rel_err" not in rec else
                                               " err %.2g finite %s" % (
                                                   rec["rel_err"],
                                                   rec["finite"]))))
    return 0


def _pallas(ps, jnp, tile):
    """``pick_sum`` at token tile ``tile`` (the rule's own where it picks
    the same)."""
    def fn(a, inv, sizes):
        rows, d = a.shape
        T, k = inv.shape
        table, pos = ps._runs(inv, sizes, tile, rows)
        return ps._launch(rows, T, k, d, sizes.shape[0], jnp.dtype(a.dtype),
                          jnp.dtype(jnp.float32), tile, False)(
                              a, table, pos)[0]
    return fn


if __name__ == "__main__":
    sys.exit(main())

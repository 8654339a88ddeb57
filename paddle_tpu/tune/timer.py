"""Benchmark timers and numeric-parity helpers — the ONE copy.

Role of the cuDNN search's timing harness inside
``conv_cudnn_op.cu.cc`` (the reference times each algorithm with cuda
events before picking): here a *timer* is any callable
``timer(fn, operands, candidate=None, space=None, key=None) -> seconds``.
Two implementations ship:

- :func:`wall_timer` — real wall clock, best-of-``trials`` windows of
  ``iters`` calls ended by a 1-element host readback per window.
  This is the only timer whose numbers mean anything on a
  real device, and it is the same measurement loop
  ``benchmark/pallas_conv_bench.py`` has always used — moved here so the
  autotune loop, the MFU ladder, and every microbench time identically.

- :func:`model_timer` — a deterministic *injectable* stand-in for CI:
  seconds come from a pure function of the candidate config (by default
  the space's VMEM-footprint model, biased so larger-but-valid tiles
  win), never from the clock. The autotune loop is then fully
  deterministic on CPU in pallas interpret mode — the loop, the cache,
  and the dispatch integration are testable in tier-1 without a TPU.
  The winner rows record which timer produced them; doc/tuning.md is
  blunt that model-timed winners are NOT performance claims.

Parity: :func:`parity_ok` / :func:`parity_report` compare a candidate's
output against the stock XLA lowering with dtype-aware tolerances —
numeric agreement is an *eligibility gate* in the autotune loop, never a
soft warning.
"""
from __future__ import annotations

import time

import numpy as np

__all__ = ["wall_timer", "model_timer", "table_timer", "time_best",
           "parity_ok", "parity_report", "default_tolerance"]


def time_best(fn, *args, iters=8, trials=3):
    """Best-of-``trials`` mean seconds over ``iters`` calls of ``fn``,
    each window ended by a 1-element host readback."""
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    first = out[0] if isinstance(out, (tuple, list)) else out
    float(np.asarray(first.reshape(-1)[:1]).astype(np.float32))
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        first = out[0] if isinstance(out, (tuple, list)) else out
        float(np.asarray(first.reshape(-1)[:1]).astype(np.float32))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def wall_timer(iters=8, trials=3):
    """Timer factory: real wall clock via :func:`time_best`."""

    def timer(fn, operands, candidate=None, space=None, key=None):
        return time_best(fn, *operands, iters=iters, trials=trials)

    timer.kind = "wall"
    return timer


def model_timer(scale=1e-9):
    """Deterministic injectable timer: 'seconds' = a pure function of the
    candidate — the space's VMEM footprint model, *inverted* so the
    largest valid working set wins (bigger resident tiles = fewer HBM
    round trips is the right prior, and determinism is the actual point).
    Stock XLA ('use: xla') scores a fixed middle value so kernel configs
    can deterministically beat or lose to it in tests."""

    del scale  # kept for signature stability

    def timer(fn, operands, candidate=None, space=None, key=None):
        if candidate is None or candidate.get("use") == "xla":
            return 0.5  # fixed reference rung
        if space is not None and key is not None:
            from .space import VMEM_BUDGET
            frac = min(float(space.vmem_bytes(candidate, key))
                       / VMEM_BUDGET, 1.0)
            # spread [1.0 .. 0.2] across footprint: configs using more
            # than ~5/8 of the budget deterministically beat the stock
            # rung, tiny tiles deterministically lose to it
            return 1.0 - 0.8 * frac
        # no model available: stable value from the sorted config items
        h = sum((i + 1) * (len(str(k)) + len(str(v))) for i, (k, v)
                in enumerate(sorted(candidate.items())))
        return 1.0 + (h % 997) * 1e-4

    timer.kind = "model"
    return timer


def table_timer(table, default=1.0):
    """Timer factory for tests: seconds looked up from
    ``{frozenset(config.items()): seconds}`` (missing -> ``default``)."""

    def timer(fn, operands, candidate=None, space=None, key=None):
        return table.get(frozenset((candidate or {}).items()), default)

    timer.kind = "table"
    return timer


def default_tolerance(dtype):
    """(rtol, atol) for parity vs the stock lowering, by compute dtype.
    bf16 operands accumulate in f32 in both the kernels and the stock
    lowering, but rounding points differ — hence the wider band."""
    dt = np.dtype(dtype) if not hasattr(dtype, "name") else dtype
    if str(dt) in ("bfloat16", "float16"):
        return 2e-2, 2e-2
    return 2e-4, 1e-5


def parity_report(ref, got, rtol=None, atol=None):
    """None when ``got`` matches ``ref`` within tolerance, else a short
    human-readable mismatch description. Handles tuple outputs (compares
    the first element — the primary output; auxiliary outputs like lse
    are representation-dependent)."""
    if isinstance(ref, (tuple, list)):
        ref = ref[0]
    if isinstance(got, (tuple, list)):
        got = got[0]
    r = np.asarray(ref, dtype=np.float32)
    g = np.asarray(got, dtype=np.float32)
    if r.shape != g.shape:
        return "shape mismatch: ref %s vs got %s" % (r.shape, g.shape)
    if rtol is None or atol is None:
        d_rtol, d_atol = default_tolerance(np.asarray(ref).dtype)
        rtol = d_rtol if rtol is None else rtol
        atol = d_atol if atol is None else atol
    if not np.all(np.isfinite(g)):
        return "non-finite values in candidate output"
    err = np.abs(g - r)
    bound = atol + rtol * np.abs(r)
    bad = err > bound
    if bad.any():
        worst = float((err - bound).max())
        return ("%d/%d elements outside rtol=%g atol=%g (worst excess %g)"
                % (int(bad.sum()), bad.size, rtol, atol, worst))
    return None


def parity_ok(ref, got, rtol=None, atol=None):
    return parity_report(ref, got, rtol=rtol, atol=atol) is None

"""The comparison that decides ``correct``: numbers of the timed path beside
the plain reference's, each with a limit of its own (from the configuration's
file, where PERF.md gives the readings each was set from)."""
from __future__ import annotations

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# is nought to rounding; it is left out of the parameters' change
ZERO_GRAD_SHARE = 1e-3


def worst_leaf_gap(got, want, keep=None):
    """Largest over the leaves of |got - want| of the NORMS, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Returns (gap, index of the leaf)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = float(np.median(want))
    gaps = np.abs(got - want) / np.maximum(want, floor)
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    at = int(np.argmax(gaps))
    return float(gaps[at]), at


def train_numbers(got, want):
    """``got``/``want``: {"losses", "grad_norms", "delta_norms"} of the
    program and of the reference over the same first steps."""
    lg = max(abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                 want["losses"]))
    keep = want["grad_norms"] >= ZERO_GRAD_SHARE * np.median(
        want["grad_norms"])
    gg, g_at = worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    dg, d_at = worst_leaf_gap(got["delta_norms"], want["delta_norms"], keep)
    # the loss gap is printed, not judged: neither the control nor a fault
    # reads above what sound runs do (PERF.md gives the readings)
    return {"grad_norm_gap": gg, "delta_norm_gap": dg}, \
        {"loss_gap": lg, "grad_leaf": g_at, "delta_leaf": d_at,
         "leaves_left_out": int((~keep).sum())}


def judge(numbers, limits):
    """{name: {"value", "limit", "ok"}}; a number with no limit is an
    error, not a pass."""
    out = {}
    for name, value in numbers.items():
        limit = limits[name]
        ok = bool(np.isfinite(value)) and value <= limit
        out[name] = {"value": float(value), "limit": limit, "ok": ok}
    return out

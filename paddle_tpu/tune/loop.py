"""The autotune loop: enumerate, compile, parity-check, time, pick.

Per candidate config of a :class:`~paddle_tpu.tune.space.KernelSpace`:

1. ``fault_point("tune.candidate")`` — the chaos hook; an armed raise
   here is indistinguishable from a real per-candidate failure.
2. build + run (the compile — a Mosaic lowering error surfaces here);
3. numeric parity vs the stock XLA lowering (eligibility gate — a
   mis-computing candidate is recorded and skipped, never timed);
4. time it (wall clock on a real device; the deterministic injectable
   model timer on CPU, so the whole loop runs in CI under pallas
   interpret mode).

Stock XLA itself is always candidate 0 (``{"use": "xla"}``) — exactly
the cuDNN-search convention of keeping the fallback algorithm in the
race. If stock wins, the cached winner SAYS stock, and dispatch keeps
lowering through XLA for that shape.

Failure isolation is the house degrade-and-record convention: any
candidate failure (compile error, parity miss, injected fault) appends
a record and a ``tune_candidate_failed`` event and the loop moves on.
The loop itself only fails when *zero* candidates survive — and even
then it returns a loser-less result instead of raising; callers decide
(the CLI exits 1, dispatch just keeps using stock XLA).
"""
from __future__ import annotations

import time

from ..place import on_tpu
from ..resilience.events import record_event
from ..resilience.faults import fault_point
from . import cache as cache_mod
from . import timer as timer_mod
from .space import get_space, signature

__all__ = ["autotune", "TuneResult", "default_timer", "XLA_CONFIG"]

XLA_CONFIG = {"use": "xla"}


def default_timer():
    """Wall clock on the TPU, the deterministic model timer on the CPU
    backend (interpret-mode wall times are noise)."""
    if on_tpu():
        return timer_mod.wall_timer()
    return timer_mod.model_timer()


class TuneResult(object):
    """Outcome of one autotune() call."""

    __slots__ = ("kernel", "key", "sig", "winner", "winner_seconds",
                 "records", "timer_kind", "cache_key", "wall_s")

    def __init__(self, kernel, key, sig, winner, winner_seconds, records,
                 timer_kind, cache_key, wall_s):
        self.kernel = kernel
        self.key = key
        self.sig = sig
        self.winner = winner            # config dict or None
        self.winner_seconds = winner_seconds
        self.records = records          # [{config, status, seconds, note}]
        self.timer_kind = timer_kind
        self.cache_key = cache_key
        self.wall_s = wall_s

    @property
    def ok(self):
        return self.winner is not None

    def row(self):
        """One shared-schema benchmark row (results.bench_record)."""
        return {"kernel": self.kernel, "sig": self.sig,
                "winner": self.winner, "winner_s": self.winner_seconds,
                "timer": self.timer_kind,
                "candidates": len(self.records),
                "failed": sum(1 for r in self.records
                              if r["status"] not in ("ok",)),
                "wall_s": round(self.wall_s, 3)}


def autotune(kernel, key, timer=None, budget=None, cache=None,
             persist=True, seed=0, rtol=None, atol=None,
             device_kind=None):
    """Search ``kernel``'s space at shape ``key``; persist and return the
    winner. ``budget`` caps candidates (None -> FLAGS.tune_budget; 0 =
    unlimited); ``timer`` is any ``(fn, operands, candidate=, space=,
    key=) -> seconds`` callable (see tune/timer.py)."""
    from ..flags import FLAGS
    from .results import device_kind as _device_kind

    t_start = time.time()
    space = get_space(kernel)
    sig = signature(key)
    if timer is None:
        timer = default_timer()
    if budget is None:
        budget = FLAGS.tune_budget
    dev = device_kind or _device_kind()
    ckey = cache_mod.cache_key(dev, kernel, sig)

    operands = space.make_operands(key, seed=seed)
    ref_fn = space.reference(key)
    ref_out = ref_fn(*operands)

    # total budget counts the always-present stock-XLA rung: budget=1
    # times stock only (0 kernel candidates), budget=None/0 is uncapped
    kernel_cands = space.candidates(key,
                                    budget=(budget - 1) if budget else None)
    records = []
    best_cfg, best_s = None, float("inf")
    for cfg in [dict(XLA_CONFIG)] + kernel_cands:
        rec = {"config": dict(cfg), "status": "ok", "seconds": None,
               "note": None}
        records.append(rec)
        is_xla = cfg.get("use") == "xla"
        try:
            fault_point("tune.candidate")
            fn = ref_fn if is_xla else space.build(cfg, key)
            out = fn(*operands)
            if not is_xla:
                report = timer_mod.parity_report(ref_out, out,
                                                 rtol=rtol, atol=atol)
                if report is not None:
                    rec["status"] = "parity_fail"
                    rec["note"] = report
                    record_event("tune_candidate_failed",
                                 site="tune.candidate", kernel=kernel,
                                 sig=sig, status="parity_fail",
                                 config=dict(cfg), note=report)
                    continue
            secs = float(timer(fn, operands, candidate=cfg, space=space,
                               key=key))
            rec["seconds"] = secs
            if secs < best_s:
                best_cfg, best_s = dict(cfg), secs
        except Exception as e:
            # per-candidate failure isolation: a candidate that fails to
            # compile or run is recorded and skipped — the loop survives
            rec["status"] = "error"
            rec["note"] = "%s: %s" % (type(e).__name__, str(e)[:200])
            record_event("tune_candidate_failed", site="tune.candidate",
                         kernel=kernel, sig=sig, status="error",
                         config=dict(cfg), note=rec["note"])
            continue

    result = TuneResult(kernel, dict(key), sig, best_cfg,
                        None if best_cfg is None else best_s, records,
                        getattr(timer, "kind", "custom"), ckey,
                        time.time() - t_start)
    if persist and result.ok:
        if cache is None:
            cache = cache_mod.WinnerCache()
        cache.put(ckey, best_cfg, time_ms=best_s * 1e3,
                  timer=result.timer_kind,
                  meta={"kernel": kernel, "sig": sig, "device": dev})
    return result

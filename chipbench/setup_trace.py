"""The program's own record of its set-up: ``paddle_tpu.profiler.setup_spans()``,
the set-up spans it opens where the work happens (``import``, ``build``,
``init``, ``compile``) with jax's compile phases (``jax.trace``,
``jax.lower``, ``xla.compile``, ``cache.read``) as their children, on the
clock of ``time.monotonic()``, the drivers' own.

``load(ctx)`` is what the ``setup.*`` readers under ``layer_metrics/`` call.
It reads the record once per run, keeps only what ended before the window
opened (``ctx["step_ends"][0]``, the drivers' ``t0``: so not what
``device_scopes()`` lowers after the window) and keeps the result in
``ctx``. A checkout whose program keeps no such record (from before it was
added) gives None and every reader falls silent.

A span's self time is its length less what its children cover. The seven
named parts (the self times of ``import``, ``build``, ``init`` and of the
four jax phases under program spans) and the self time of ``compile`` are
a partition of ``program_s``, the union of the program's set-up spans.
"""
from __future__ import annotations

from chipbench import trace_reduce

PROGRAM = ("import", "build", "init", "compile")


def covered(intervals):
    """Seconds that (start, end) intervals cover together."""
    return sum(e - s for s, e in trace_reduce.merge(intervals))


def self_times(records, t0):
    """{index: length less what its children cover} of the records that
    ended by ``t0``."""
    kids = {}
    for n, s, e, p, _a in records:
        if p is not None and e is not None and e <= t0:
            kids.setdefault(p, []).append((s, e))
    return {i: (e - s) - covered(kids.get(i, ()))
            for i, (_n, s, e, _p, _a) in enumerate(records)
            if e is not None and e <= t0}


def reduce_setup(records, t0):
    """Pure function of plain data. ``records``: ``setup_spans()``
    (``[(name, start, end, parent, args)]``); ``t0``: the window's start.
    Of the records that ended by ``t0``:

    - ``program_s``: the union of the program's set-up spans;
    - ``self_s``: {name: summed self time} of the program's spans and of
      the jax phases that lie under one of them;
    - ``executables``: the ``compile`` spans, ``compiles``: {(module,
      why): count} of them;
    - ``outside_s``: {name: summed self time} of the jax phases under no
      program span (the drivers' own jits).
    """
    own = self_times(records, t0)
    owned = [None] * len(records)

    def is_owned(i):
        if owned[i] is None:
            name, parent = records[i][0], records[i][3]
            owned[i] = name in PROGRAM or (parent is not None
                                           and is_owned(parent))
        return owned[i]

    self_s, outside_s, compiles = {}, {}, {}
    for i, t in own.items():
        name, args = records[i][0], records[i][4]
        into = self_s if is_owned(i) else outside_s
        into[name] = into.get(name, 0.0) + t
        if name == "compile":
            key = (args.get("module"), args.get("why"))
            compiles[key] = compiles.get(key, 0) + 1
    return {"program_s": covered((records[i][1], records[i][2])
                                 for i in own if records[i][0] in PROGRAM),
            "self_s": self_s, "outside_s": outside_s,
            "executables": sum(compiles.values()), "compiles": compiles}


def load(ctx):
    """The view of this run's set-up (``reduce_setup``), made once and kept
    in ``ctx``; None where there is nothing to read."""
    if "setup_trace" not in ctx:
        ctx["setup_trace"] = _load(ctx)
    return ctx["setup_trace"]


def _load(ctx):
    from paddle_tpu import profiler
    if not hasattr(profiler, "setup_spans") or not ctx.get("step_ends"):
        return None         # a program from before its set-up record
    return reduce_setup(profiler.setup_spans(), ctx["step_ends"][0])


def self_s(ctx, name):
    """Summed self time, s, of the program's set-up spans or jax phases
    called ``name`` that ended before the window."""
    view = load(ctx)
    return None if view is None else view["self_s"].get(name, 0.0)

"""The program's own view of a traced window: the host spans it opens itself
(``paddle_tpu/train_step``, ``feed``, ``run``, ``upload``, ``dispatch``,
``compile``, ``fetch``; ``paddle_tpu/profiler.py``) and the forward / backward
/ update scope of every device operation, joined from the event's instruction
name through ``paddle_tpu.profiler.device_scopes()``.

The spans are events of the training thread's line of ``/host:CPU`` in the
same xplane file as the device's lines, so they are on the device's clock. A device
operation's event (line ``XLA Ops``) carries its instruction's HLO text and no
scope; it lies inside an event of ``XLA Modules`` whose name, up to the
``(``, is the compiled module's, and the program gives per module a table
instruction name -> scope.

``load(ctx)`` is what the readers under ``layer_metrics/`` call. It finds the
trace where ``harness.traced_window`` left it, reduces it once per run and
keeps the result in ``ctx``. A checkout whose program has no such spans (from
before they were added) gives None and every reader falls silent; a program
that has them and left no ``train_step`` span in a window with steps is an
error.
"""
from __future__ import annotations

import bisect
import glob
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, trace_reduce       # noqa: E402

SPAN_PREFIX = "paddle_tpu/"
HOST_PLANE = "/host:CPU"
PHASES = ("forward", "backward", "update")
UNSCOPED = "unscoped"
IDLE_KINDS = ("feed", "upload", "run_other", "unspanned")


def instruction_name(event_name):
    """``fusion.51`` of ``%fusion.51 = bf16[...] fusion(...)`` (or of the
    short form ``trace_reduce.short_name`` makes of it)."""
    return event_name.split(" ", 1)[0].lstrip("%")


def device_lines(planes):
    """(ops, modules) of the first device plane of planes as
    ``trace_reduce.read_planes`` gives them: [(name, start_ns, dur_ns)]."""
    devs = sorted((p for p in planes
                   if p[0].startswith(trace_reduce.DEVICE_PREFIX)),
                  key=lambda p: p[0])
    if not devs:
        return [], []
    lines = dict(devs[0][1])
    return (lines.get(trace_reduce.OPS_LINE, []),
            lines.get(trace_reduce.MODULES_LINE, []))


def read_xplane(path):
    """(spans, ops, modules) of an xplane file as plain data. ``spans``:
    [(name, start_ns, dur_ns, args)] of the program's spans, prefix taken
    off, from the host line of the training thread: the one that holds the
    ``train_step`` spans (its name is the process's, ``python`` or
    ``python3``); where no line holds one, from every host line. ``ops``
    and ``modules``: as ``device_lines``."""
    from jax.profiler import ProfileData
    spans, others, devices = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            lines = [(line.name, [(ev.name, int(ev.start_ns),
                                   int(ev.duration_ns))
                                  for ev in line.events])
                     for line in plane.lines
                     if line.name in (trace_reduce.OPS_LINE,
                                      trace_reduce.MODULES_LINE)]
            devices.append((plane.name, lines))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                mine = [(ev.name[len(SPAN_PREFIX):], int(ev.start_ns),
                         int(ev.duration_ns), dict(ev.stats))
                        for ev in line.events
                        if ev.name.startswith(SPAN_PREFIX)]
                if any(s[0] == "train_step" for s in mine):
                    spans.extend(mine)
                else:
                    others.extend(mine)
    ops, modules = device_lines(devices)
    return spans or others, ops, modules


def subtract(a, b):
    """Sorted disjoint intervals ``a`` with what ``b`` covers cut out."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def overlap(a, b):
    """Nanoseconds that sorted disjoint intervals ``a`` and ``b`` share."""
    total, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def reduce_window(spans, ops, modules, scopes, window_ns, busy):
    """Pure function of plain data (a recorded slice checks it).

    ``scopes``: ``profiler.device_scopes()``; ``window_ns``: (start, end) of
    the benchmark's window; ``busy``: the device's merged busy intervals
    (``reduction["devices"][0]["busy"]``). Returns

    - ``span_ms``: {span: [ms]} of the program's spans inside the window
      (``train_step`` without the ``end_of_pass`` ones);
    - ``entry_self_ms``: per ``train_step`` span, its length less what its
      ``feed`` and ``run`` children cover;
    - ``scope_ns``: {scope: device self time} inside the window, where a
      scope is ``<phase>/<op>`` or ``unscoped`` (the table says so, or the
      table lacks the module or the instruction): a partition of the busy
      time;
    - ``unscoped_ns``: {instruction: self time} of the unscoped ones;
    - ``idle_ns``: the device's idle time inside the window by the
      innermost program span open on the host: ``feed``; ``upload``;
      ``run_other`` (``run`` outside ``upload``); ``unspanned``: a
      partition of the idle time.
    """
    w0, w1 = window_ns
    inside = [(n, s, s + d, a) for n, s, d, a in spans
              if s >= w0 and s + d <= w1]
    span_ms = {}
    for n, s, e, a in inside:
        if n == "train_step" and a.get("end_of_pass"):
            continue
        span_ms.setdefault(n, []).append((e - s) / 1e6)
    children = sorted((s, e) for n, s, e, _a in inside
                      if n in ("feed", "run"))
    entry_self_ms = []
    for n, s, e, a in inside:
        if n == "train_step" and not a.get("end_of_pass"):
            held = sum(ce - cs for cs, ce in children if cs >= s and ce <= e)
            entry_self_ms.append((e - s - held) / 1e6)

    mods = sorted((s, s + d, n.split("(")[0]) for n, s, d in modules)
    starts = [m[0] for m in mods]
    events = []
    for name, s, d in ops:
        cs, ce = max(s, w0), min(s + d, w1)
        if ce <= cs:
            continue
        i = bisect.bisect_right(starts, s) - 1
        module = mods[i][2] if i >= 0 and s < mods[i][1] else None
        instr = instruction_name(name)
        scope = scopes.get(module, {}).get(instr, UNSCOPED)
        if scope.split("/")[0] not in PHASES:
            scope = UNSCOPED
        events.append((cs, ce, (scope, instr if scope == UNSCOPED else None)))
    scope_ns, unscoped_ns = {}, {}
    for (scope, instr), (_c, ns) in trace_reduce.self_times(events).items():
        scope_ns[scope] = scope_ns.get(scope, 0) + ns
        if instr is not None:
            unscoped_ns[instr] = unscoped_ns.get(instr, 0) + ns

    idle = subtract([(w0, w1)], busy)
    of = {k: trace_reduce.merge((s, e) for n, s, e, _a in inside if n == k)
          for k in ("feed", "upload", "run")}
    upload = subtract(of["upload"], of["feed"])
    run_other = subtract(subtract(of["run"], of["feed"]), upload)
    idle_ns = {"feed": overlap(idle, of["feed"]),
               "upload": overlap(idle, upload),
               "run_other": overlap(idle, run_other)}
    idle_ns["unspanned"] = sum(e - s for s, e in idle) - sum(idle_ns.values())
    return {"span_ms": span_ms, "entry_self_ms": entry_self_ms,
            "scope_ns": scope_ns, "unscoped_ns": unscoped_ns,
            "idle_ns": idle_ns}


def find_xplane():
    found = glob.glob(os.path.join(harness.TRACE_DIR, "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return found[0] if found else None


def load(ctx):
    """The view of this run's traced window (``reduce_window``), made once
    and kept in ``ctx``; None where there is nothing to read."""
    if "program_trace" not in ctx:
        ctx["program_trace"] = _load(ctx)
    return ctx["program_trace"]


def _load(ctx):
    from paddle_tpu import profiler
    if not hasattr(profiler, "step_span") or ctx.get("window_ns") is None:
        return None         # a program from before its spans: silence
    path = find_xplane()
    if path is None:
        return None
    spans, ops, modules = read_xplane(path)
    t0 = time.perf_counter()
    scopes = profiler.device_scopes()
    print("program_trace: device_scopes() took %.3f s for %d modules"
          % (time.perf_counter() - t0, len(scopes)), file=sys.stderr)
    devs = ctx["reduction"]["devices"]
    view = reduce_window(spans, ops, modules, scopes, ctx["window_ns"],
                         devs[0]["busy"] if devs else [])
    if ctx.get("steps") and not view["span_ms"].get("train_step"):
        raise RuntimeError(
            "the window finished %d steps and the trace holds no "
            "paddle_tpu/train_step span: the program's tracing is broken"
            % ctx["steps"])
    worst = sorted(view["unscoped_ns"].items(), key=lambda kv: -kv[1])[:10]
    print("program_trace: unscoped device time by instruction (s): %s"
          % [(k, ns / 1e9) for k, ns in worst], file=sys.stderr)
    return view


def median_span_ms(ctx, name):
    """Median length of the program's span ``name`` in the window, ms."""
    view = load(ctx)
    values = view["span_ms"].get(name) if view else None
    return statistics.median(values) if values else None


def phase_ms(ctx, *prefixes):
    """Device self time per step, ms, of the operations whose scope starts
    with one of ``prefixes``; the divisor is ``train.device_step_ms``'s."""
    view = load(ctx)
    if not view or not view["scope_ns"] or not ctx.get("steps"):
        return None
    ns = sum(v for k, v in view["scope_ns"].items()
             if k.startswith(prefixes))
    return ns / ctx["steps"] / 1e6


def idle_pct(ctx, kind):
    """Device idle time of ``kind`` (``IDLE_KINDS``) as a share of the
    window; window and divisor are ``train.device_idle_pct``'s."""
    view = load(ctx)
    if not view or not ctx.get("window_s") or not view["scope_ns"]:
        return None
    return 100.0 * view["idle_ns"][kind] / 1e9 / ctx["window_s"]


if __name__ == "__main__":
    # python3 chipbench/program_trace.py <cell> <seed> <seconds> <out.json> [ms]
    # runs the cell traced, in this process, and writes the first [ms] of
    # its window as plain data (how testdata/train_program_trace.json was
    # recorded): the planes as trace_reduce cuts them, the program's spans,
    # and the scopes of the instructions that ran.
    import json
    from chipbench import run as bench
    from paddle_tpu import profiler
    cell, seed, seconds, dst = sys.argv[1:5]
    ms = float(sys.argv[5]) if len(sys.argv) > 5 else 700.0
    res = bench.run_cell(cell, int(seed), float(seconds), True)
    w0 = res["ctx"]["window_ns"][0]
    w1 = w0 + int(ms * 1e6)
    path = find_xplane()
    planes = trace_reduce.cut(trace_reduce.read_planes(path), w0, w1)
    spans = [s for s in read_xplane(path)[0] if w0 <= s[1] < w1]
    ran = {instruction_name(n) for n, _s, _d in device_lines(planes)[0]}
    scopes = {m: {k: v for k, v in t.items() if k in ran}
              for m, t in profiler.device_scopes().items()}
    with open(dst, "w") as f:
        json.dump({"planes": planes, "spans": spans,
                   "scopes": {m: t for m, t in scopes.items() if t},
                   "metrics": res["metrics"]}, f)

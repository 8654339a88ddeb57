"""One training step's device time, scope by scope and op by op.

    python3 tools/scope_table.py --seed <n> [--workload <cell>] [--seconds 51] [--out <file.json>] [--hlo <file.txt>]
    python3 tools/scope_table.py --recorded chipbench/testdata/train_program_trace.json

Runs a training cell of the benchmark traced (needs the chip; ``--recorded`` reads
a slice ``chipbench/program_trace.py`` wrote instead, anywhere) and joins the
trace's ``XLA Ops`` events with ``profiler.device_scopes()`` on the
instruction's name: per scope the device self time a step and the number of
instructions, per instruction its scope, result type, fusion kind and time.
``--hlo`` also writes the optimised text of the step's module, which says
what a ``fusion.N`` reads and writes. The cell's result (every per-layer
metric of a ``--trace 1`` run) goes into the ``--out`` file beside the table,
with ``tune.counters()`` (the header's line: dispatch gauges and the blocks
each flash-attention launch was traced at).
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

STEP_PREFIX = "jit_paddle_tpu_step_"
# scopes whose instructions are listed by kind: the statistic passes
DETAILED = ("forward/batch_norm", "backward/batch_norm_grad")


def step_table(ops, modules, scopes, window_ns=None):
    """``ops`` / ``modules``: [(name, start_ns, dur_ns)] of the device's
    ``XLA Ops`` / ``XLA Modules`` lines, an op's name in
    ``trace_reduce.short_name`` form; ``scopes``: ``device_scopes()``.
    Of the step module that ran most often inside ``window_ns``: (module,
    steps, {instruction: [scope, short name, self ns]})."""
    from chipbench import program_trace, trace_reduce
    w0, w1 = window_ns or (float("-inf"), float("inf"))
    mods = sorted((s, s + d, n.split("(")[0]) for n, s, d in modules)
    runs = collections.Counter(m for s, e, m in mods
                               if m.startswith(STEP_PREFIX)
                               and s >= w0 and e <= w1)
    if not runs:
        raise SystemExit("no %s* module ran inside the window" % STEP_PREFIX)
    module, steps = runs.most_common(1)[0]
    starts = [m[0] for m in mods]
    events, shown = [], {}
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= mods[i][1] or mods[i][2] != module \
                or mods[i][0] < w0 or mods[i][1] > w1:
            continue
        instr = program_trace.instruction_name(name)
        shown[instr] = name
        events.append((s, s + d, instr))
    table = scopes.get(module, {})
    rows = {instr: [table.get(instr, program_trace.UNSCOPED), shown[instr], ns]
            for instr, (_c, ns) in trace_reduce.self_times(events).items()}
    return module, steps, rows


def by_scope(rows, steps):
    """[(scope, ms a step, instructions)] by time, longest first."""
    ms, n = collections.defaultdict(float), collections.Counter()
    for scope, _shown, ns in rows.values():
        ms[scope] += ns / steps / 1e6
        n[scope] += 1
    return sorted(((s, ms[s], n[s]) for s in ms), key=lambda r: -r[1])


def by_kind(rows, steps, scope):
    """Inside one scope: [(what, instructions, ms a step)], where ``what``
    is the short name less the instruction's number (``fusion f32[256]
    kLoop``)."""
    ms, n = collections.defaultdict(float), collections.Counter()
    for instr, (sc, shown, ns) in rows.items():
        if sc != scope:
            continue
        head, _, rest = shown.partition(" ")
        what = (head.rsplit(".", 1)[0] + " " + rest).strip()
        ms[what] += ns / steps / 1e6
        n[what] += 1
    return sorted(((w, n[w], ms[w]) for w in ms), key=lambda r: -r[2])


def report(module, steps, rows, counters=None):
    """``counters``: ``tune.counters()`` of the process that traced the
    step (the dispatch gauges and the flash kernels' blocks), where the
    table comes from a run and not from a recorded slice."""
    total = sum(ns for _s, _n, ns in rows.values()) / steps / 1e6
    print("%s: %d steps, %d instructions, %.2f ms a step"
          % (module, steps, len(rows), total))
    if counters:
        blocks = counters.get("flash_blocks", {})
        print("tune: %d hits, %d misses, %d fallbacks; flash_blocks: %s"
              % (counters["tune_hits"], counters["tune_misses"],
                 counters["tune_fallbacks"],
                 ", ".join("%s x%d" % kv for kv in sorted(blocks.items()))
                 or "none"))
        for name, t in sorted(counters.get("flash_tiles", {}).items()):
            print("flash_tiles %s: %d visited, %d masked, %d in the square"
                  % (name, t["visited"], t["masked"], t["square"]))
    for scope, ms, n in by_scope(rows, steps):
        print("  %-28s %8.3f ms %5d ops" % (scope, ms, n))
    for scope in DETAILED:
        print("inside %s:" % scope)
        for what, n, ms in by_kind(rows, steps, scope):
            print("  %4d x %-64s %8.3f ms" % (n, what, ms))
    print("the 12 longest:")
    for _instr, (scope, shown, ns) in sorted(rows.items(),
                                             key=lambda kv: -kv[1][2])[:12]:
        print("  %8.3f ms  %-28s %s" % (ns / steps / 1e6, scope, shown))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--recorded")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--workload", default="resnet50-train-trainer")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out")
    ap.add_argument("--hlo")
    args = ap.parse_args(argv)
    from chipbench import program_trace
    result = counters = None
    if args.recorded:
        with open(args.recorded) as f:
            rec = json.load(f)
        ops, modules = program_trace.device_lines(rec["planes"])
        module, steps, rows = step_table(ops, modules, rec["scopes"])
    else:
        if args.seed is None:
            ap.error("--seed or --recorded")
        from chipbench import harness, run as bench, trace_reduce
        from paddle_tpu import profiler, tune
        from paddle_tpu.core import executor
        try:
            res = bench.run_cell(args.workload, args.seed, args.seconds, True)
        except harness.NoChip as e:
            print("scope_table: %s" % (e,), file=sys.stderr)
            return 2
        ops, modules = program_trace.device_lines(
            trace_reduce.read_planes(program_trace.find_xplane()))
        module, steps, rows = step_table(ops, modules,
                                         profiler.device_scopes(),
                                         res["ctx"]["window_ns"])
        result = {k: res[k] for k in ("correct", "attempted", "metrics",
                                      "device", "compared", "breakdown")}
        counters = result["tune"] = tune.counters()
        if args.hlo:
            for step in executor.compiled_steps():
                facts = step.facts()
                if facts is not None and facts["module"] == module:
                    with open(args.hlo, "w") as f:
                        f.write(step.fn.lower(*step._avals).compile()
                                .as_text())
    report(module, steps, rows, counters)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"module": module, "steps": steps, "rows": rows,
                       "result": result}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

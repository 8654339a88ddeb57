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
with ``tune.counters()`` (the header's lines: dispatch gauges, the blocks
each flash-attention launch was traced at, the row counts each expert
layer's held part was compiled at) and, where the cell's program has expert
layers whose ``Load`` / ``RowsHeld`` its Trainer fetches, the rung every
(layer, step) of the window ran at (``layers.moe_rows_moved`` over the
fetched ``RowsHeld``: a listener of this tool's own on ``EndIteration``).
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


def listen_to_expert_layers(heard):
    """From here on every ``Trainer.train`` of this process hands each
    ``EndIteration``'s fetches to ``heard["steps"]`` too, and says in
    ``heard["layers"]`` which of them are an expert layer's: [(index of
    its ``Load``, index of its ``RowsHeld``, experts held, experts)], read
    off the ``moe_ffn`` ops of the Trainer's program. One ``append`` a step
    inside the loop; everything else waits for ``rung_shares``."""
    from paddle_tpu import trainer
    train = trainer.Trainer.train

    def listening(self, *args, event_handler=None, **kw):
        block = self.main_program.global_block()
        at = {v.name: i for i, v in enumerate(self.fetch_list[1:])}
        heard["layers"] = [
            (at[op.output("Load")[0]], at[op.output("RowsHeld")[0]],
             block._find_var_recursive(op.input("ExpertGate")[0]).shape[0],
             block._find_var_recursive(op.input("WRouter")[0]).shape[1])
            for op in block.ops if op.type == "moe_ffn"
            and op.output("Load")[0] in at and op.output("RowsHeld")[0] in at]
        heard["steps"] = steps = []

        def both(e):
            if isinstance(e, trainer.EndIteration):
                steps.append(e.metrics.get("fetches", ()))
            if event_handler is not None:
                event_handler(e)
        return train(self, *args, event_handler=both, **kw)
    trainer.Trainer.train = listening


def rung_shares(heard, steps):
    """Of the last ``steps`` steps heard (the window's): per expert layer
    {"rows_held": [least, mean, most], "rungs": {rows moved: steps}}."""
    from paddle_tpu import layers
    out = []
    for load, held, count, experts in heard.get("layers", ()):
        rows, rungs = [], collections.Counter()
        for fetches in heard["steps"][-steps:]:
            rows.append(int(fetches[held].sum()))
            rungs[layers.moe_rows_moved(
                fetches[held], int(fetches[load].sum()), count, experts)] += 1
        out.append({"rows_held": [min(rows), sum(rows) / float(len(rows)),
                                  max(rows)], "rungs": dict(rungs)})
    return out


def report(module, steps, rows, counters=None, rungs=None):
    """``counters``: ``tune.counters()`` of the process that traced the
    step (the dispatch gauges, the flash kernels' blocks, the expert
    layers' rungs) and ``rungs``: ``rung_shares`` of the window, where the
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
        if counters.get("moe_rungs"):
            print("moe_rungs: %s" % ", ".join(
                "%s x%d" % kv for kv in sorted(counters["moe_rungs"].items())))
    if rungs:
        tally = collections.Counter()
        for i, layer in enumerate(rungs):
            tally.update(layer["rungs"])
            print("expert layer %d: rows_held %d / %.0f / %d (least / mean /"
                  " most a step); steps at rung %s"
                  % ((i,) + tuple(layer["rows_held"]) + (", ".join(
                      "%d: %d" % kv for kv in sorted(layer["rungs"].items())),)))
        print("rung shares of the window's (layer, step)s: %s" % ", ".join(
            "%d: %.1f%%" % (r, 100.0 * n / sum(tally.values()))
            for r, n in sorted(tally.items())))
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
    result = counters = rungs = None
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
        heard = {}
        listen_to_expert_layers(heard)
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
        rungs = result["moe_rungs_in_window"] = rung_shares(
            heard, res["attempted"])
        if args.hlo:
            for step in executor.compiled_steps():
                facts = step.facts()
                if facts is not None and facts["module"] == module:
                    with open(args.hlo, "w") as f:
                        f.write(step.fn.lower(*step._avals).compile()
                                .as_text())
    report(module, steps, rows, counters, rungs)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"module": module, "steps": steps, "rows": rows,
                       "result": result}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one cell of BENCHMARK.json on the machine it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip. Finds the cell's configuration, traffic
mix, driver and per-layer readers by the names in BENCHMARK.json, warms up
every shape as set-up, measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints ONE JSON object as the last
line of standard output. With no TPU, or fewer chips than the cell asks for,
it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()          # set-up is counted from here

import argparse                     # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness       # noqa: E402


def run_cell(workload, seed, seconds, trace, devices=None, tamper=None,
             t_start=None):
    """Drive one run and return the result as a dict (the tests call this
    with ``devices`` given, which skips the look for a chip)."""
    cell = harness.load_cell(workload)
    harness.setup_compile_cache()
    if devices is None:
        devices = harness.require_chips(cell["entry"]["chips"])
    driver = harness.load_module("drivers", cell["config"]["driver"] + ".py")
    res = driver.run(cell, seed, seconds, trace, devices,
                     T_START if t_start is None else t_start, tamper=tamper)
    wanted = cell["end_to_end"]
    metrics = {m["name"]: {"value": float(res["metrics"][m["name"]]),
                           "unit": m["unit"]} for m in wanted}
    breakdown = None
    if trace:
        ctx = res["ctx"]
        metrics = harness.read_layer_metrics(cell, ctx)
        red, tr = ctx["reduction"], ctx["trace_reduce"]
        res["device"]["busy_s"] = tr.busy_seconds(red, ctx["window_ns"])
        res["device"]["window_s"] = ctx["window_s"]
        gaps, _by = tr.idle_gaps(red, ctx["window_ns"])
        breakdown = {"device_ops": tr.top_ops(red), "idle_gaps": gaps}
    res.update(metrics=metrics, breakdown=breakdown)
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except harness.NoChip as e:
        print("chipbench: %s" % (e,), file=sys.stderr)
        return 2
    harness.result_line(res["correct"], res["attempted"], res["failed"],
                        res["metrics"], res["device"], res["compared"],
                        res["breakdown"])
    return 0


if __name__ == "__main__":
    sys.exit(main())

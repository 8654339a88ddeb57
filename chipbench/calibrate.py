"""Readings that the limits of ``correct`` are set from (PERF.md gives them
beside each limit). Run on the chip, at the cell's own size, in one process:

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --out chiprun_out/calibrate.json

For every seed the program's numbers against the plain reference (the lower
readings); for every control seed the control (the reference in the next
precision down, put in the program's place) and each planted fault, each
passed through ``compare.judge`` with the configuration's limits, where it
has to come out as not correct; with ``--sweep r1,r2,...`` one window per
offered rate on one engine, to find the knee of a served cell once. The
benchmark's own runs never run this.

    python3 chipbench/calibrate.py --workload <cell> --rejudge <file.json>

passes the control's and the faults' numbers that an earlier call wrote to
``<file.json>`` through ``compare.judge`` again, under the limits as they
stand now; needs no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, harness  # noqa: E402


def _brief(value):
    """``value`` without the raw per-leaf / per-token arrays (those go to
    the file only)."""
    if isinstance(value, dict):
        return {k: _brief(v) for k, v in value.items()
                if not k.startswith("raw") and k != "reference"}
    return value


def rejudge(path, limits):
    """Every control's and fault's stored numbers beside the limits; exit 1
    if a control or a fault comes out as correct."""
    with open(path) as f:
        stored = json.load(f)
    passed = []
    for seed, cases in sorted(stored["control"].items()):
        for name, case in sorted(cases.items()):
            if not isinstance(case, dict) or "numbers" not in case \
                    or name == "program":
                continue
            numbers = {k: v for k, v in case["numbers"].items()
                       if k in limits}
            compared = compare.judge(numbers, limits)
            correct = all(c["ok"] for c in compared.values())
            print("seed %s %s: correct %s %s" % (
                seed, name, correct,
                {k: (round(c["value"], 4), c["limit"])
                 for k, c in compared.items()}))
            if correct and name.startswith(("control", "fault")):
                passed.append((seed, name))
    print("controls and faults that came out as correct: %s"
          % (passed or "none"))
    return 1 if passed else 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--sweep", default="",
                    help="offered rates per second, to find the knee")
    ap.add_argument("--sweep-seconds", type=float, default=30.0)
    ap.add_argument("--look", action="store_true",
                    help="also read the reference at the stated precision")
    ap.add_argument("--rejudge", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.rejudge:
        return rejudge(args.rejudge, cell["config"]["limits"])
    if not args.out:
        ap.error("--out is required")
    harness.setup_compile_cache()
    devices = harness.require_chips(cell["entry"]["chips"])
    driver = harness.load_module("drivers", cell["config"]["driver"] + ".py")
    out = {"workload": args.workload, "program": {}, "control": {}}

    def save():
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    if args.sweep:
        out["sweep"] = driver.sweep(
            cell, 1, [float(r) for r in args.sweep.split(",")],
            args.sweep_seconds, devices)
        save()
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t = time.monotonic()
        res = driver.run(cell, seed, args.seconds, False, devices, t)
        out["program"][str(seed)] = {
            "numbers": {k: v["value"] for k, v in res["compared"].items()},
            "notes": res["notes"], "metrics": res["metrics"],
            "raw": res.get("raw"),
            "took_s": time.monotonic() - t}
        print("program", seed, json.dumps(_brief(out["program"][str(seed)])),
              flush=True)
        save()
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        t = time.monotonic()
        out["control"][str(seed)] = driver.control_readings(
            cell, seed, devices, **({"look": True} if args.look else {}))
        out["control"][str(seed)]["took_s"] = time.monotonic() - t
        print("control", seed, json.dumps(_brief(out["control"][str(seed)])),
              flush=True)
        save()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""How the bounds in BENCHMARK.json were set: from files that hold one
result line per run (the last line of each run's standard output), print for
every metric the median and the spread (distance between the first and third
quartile as a share of the median) of each set, and the wider of them.

    python3 chipbench/spreads.py set1.jsonl set2.jsonl
"""
from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402


def read_set(path):
    runs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{"):
                runs.append(json.loads(line))
    return runs


def main(paths):
    sets = [read_set(p) for p in paths]
    names = sorted({n for runs in sets for r in runs for n in r["metrics"]})
    for name in names:
        widest, cols = 0.0, []
        for runs in sets:
            values = [r["metrics"][name]["value"] for r in runs
                      if name in r["metrics"]]
            if name == "setup_s":
                values = values[1:]         # the first run compiles
            if len(values) < 2:
                continue
            s = harness.spread(values)
            widest = max(widest, s)
            cols.append("median %.6g spread %.4f (n=%d)"
                        % (statistics.median(values), s, len(values)))
        print("%-28s %s | widest %.4f -> bound %.4f"
              % (name, " | ".join(cols), widest, max(5 * widest, 0.01)))
    bad = [(p, i) for p, runs in zip(paths, sets)
           for i, r in enumerate(runs) if not r["correct"]]
    print("runs: %s; not correct: %s" % ([len(s) for s in sets], bad or "none"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

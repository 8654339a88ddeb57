"""Perf smoke gate (CPU tier-1): the async execution pipeline
(paddle_tpu.pipeline) must (a) produce bit-identical losses to the
synchronous Trainer loop, (b) not be slower, and (c) show real overlap
(feed-wait below step time), on a small run with a realistic per-batch
host feed cost.

The measurement itself lives in benchmark/pipeline_bench.py, so gate
and evidence cannot drift. Companion to tools/lint.sh (static gate); this is
the dynamic one. Exit 0 on pass, 1 on failure; prints a one-line JSON
summary either way.

Invoked by tools/perf_smoke.sh; usable directly:
    JAX_PLATFORMS=cpu python tools/perf_smoke.py
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from benchmark.pipeline_bench import bench
    # small but feed-heavy; timed_passes=2 -> best-of-2 damps CI noise
    summary = bench(steps=24, batch=32, dim=16, hidden=64, read_ms=3.0,
                    timed_passes=2)
    failures = []
    if not summary["pipeline_parity"]:
        failures.append("losses not bit-identical sync vs pipelined")
    if summary["pipeline_speedup"] < 1.0:
        failures.append("pipelined slower than synchronous (x%.3f)"
                        % summary["pipeline_speedup"])
    if not summary["pipeline_overlap"]:
        failures.append("no overlap: feed-wait %.3f ms/step >= step time "
                        "%.3f ms" % (summary["pipeline_feed_wait_ms_per_step"],
                                     summary["pipeline_ms_per_step"]))
    summary["ok"] = not failures
    print(json.dumps(summary))
    if failures:
        for f in failures:
            print("perf_smoke FAIL: %s" % f, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

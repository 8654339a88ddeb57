"""Serving smoke gate (CPU tier-1): the online-serving tier
(paddle_tpu.serving) must (a) return responses bit-identical to direct
``CompiledModel.run()``, (b) coalesce concurrent requests into real
batches (occupancy > 1), and (c) beat a sequential per-request ``run()``
loop on throughput — the whole point of micro-batching is amortizing
dispatches, so if it cannot beat one-at-a-time on the SAME hardware,
the tier is overhead.

Flow: export a tiny model to a temp dir, stand the service up
in-process (no sockets — the HTTP shell has its own tests), flood it
with in-flight ``infer_async`` requests (the realistic overload shape:
full batches form instantly, no formation-timeout stalls), and time the
sequential loop over the same feeds on the same warmed model. Both
measurements run per wave; the best-of-``WAVES`` ratio is gated, to damp
scheduler noise.

Companion to tools/lint.sh (static); invoked by tools/serve_smoke.sh,
which retries once to damp shared-CI scheduler noise. Exit 0 on pass, 1
on failure; prints a one-line JSON summary either way.

    JAX_PLATFORMS=cpu python tools/serve_smoke.py
"""
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REQUESTS = 64
MAX_BATCH = 16
WAVES = 2
DIM = 6
ROWS = 4


def main():
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.serving import InferenceService

    with tempfile.TemporaryDirectory() as tmp:
        art = os.path.join(tmp, "artifact")
        x = pt.layers.data("x", shape=[DIM], dtype="float32")
        h = pt.layers.fc(x, size=16, act="relu")
        pred = pt.layers.fc(h, size=3, act="softmax")
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())
        pt.inference.export_compiled(
            art, ["x"], [pred], exe,
            example_feed={"x": np.zeros((ROWS, DIM), np.float32)})

        model = pt.inference.load_compiled(art)
        rng = np.random.RandomState(7)
        feeds = [rng.rand(ROWS, DIM).astype(np.float32)
                 for _ in range(REQUESTS)]
        # reference outputs double as the run() warm-up
        want = [np.asarray(model.run({"x": f})[0]) for f in feeds]

        svc = InferenceService(max_batch=MAX_BATCH, batch_timeout_ms=2.0,
                               queue_depth=4 * REQUESTS)
        try:
            svc.load_model("m", art)   # warm-up compiles every bucket
            t_service, t_sequential = [], []
            for _ in range(WAVES):
                t0 = time.perf_counter()
                handles = [svc.infer_async("m", {"x": f}) for f in feeds]
                got = [h.wait(timeout=120) for h in handles]
                t_service.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                for f in feeds:
                    np.asarray(model.run({"x": f})[0])
                t_sequential.append(time.perf_counter() - t0)
            st = svc.stats
        finally:
            svc.close()

    bit_exact = all(np.array_equal(g[0], w) for g, w in zip(got, want))
    ratio = max(s / v for s, v in zip(t_sequential, t_service))
    summary = {
        "requests": st["requests"],
        "batches": st["batches"],
        "bit_exact": bit_exact,
        "batch_occupancy": round(st["batch_occupancy"], 3),
        "max_occupancy": st["max_occupancy"],
        "padded_rows": st["padded_rows"],
        "service_s": [round(t, 4) for t in t_service],
        "sequential_s": [round(t, 4) for t in t_sequential],
        "throughput_ratio": round(ratio, 3),
        "latency_ms_p50": round(st["latency_ms_p50"], 3),
        "latency_ms_p99": round(st["latency_ms_p99"], 3),
    }
    failures = []
    if not bit_exact:
        failures.append("batched responses not bit-identical to run()")
    if st["max_occupancy"] <= 1:
        failures.append("no coalescing: every batch served one request")
    if ratio < 1.0:
        failures.append("batched serving slower than the sequential "
                        "per-request loop (x%.3f)" % ratio)
    if st["completed"] != WAVES * REQUESTS or st["failed"] or st["shed"]:
        failures.append("lost requests: %r" % st)
    summary["ok"] = not failures
    print(json.dumps(summary))
    if failures:
        for f in failures:
            print("serve_smoke FAIL: %s" % f, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

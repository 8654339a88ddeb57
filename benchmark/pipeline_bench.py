"""Sync-vs-pipelined Trainer measurement harness.

The ONE implementation behind tools/perf_smoke.py (gate) and any
benchmark cell, so the overlap formula, timed windows, and
parity check cannot drift between the evidence record and the CI gate.

Workload: a small MLP trained through the public Trainer surface over a
reader with a per-batch host feed cost (sample-list conversion through
DataFeeder) plus ``read_ms`` of simulated input latency — the workload
class the feed/fetch overlap exists for. Pass 0 warms the compile
caches; passes 1..timed_passes are timed and the best (least-contended)
window is reported, with the feed-wait counter scoped to that same
window. Runs on CPU (tier-1) and on device.
"""
from __future__ import annotations


def bench(steps=30, batch=64, dim=64, hidden=128, read_ms=3.0,
          timed_passes=1, lr=0.01):
    """Returns the pipeline evidence fields: both
    modes' steps/s, the speedup, bit-exact parity, and the pipeline
    counters proving (or refuting) the overlap."""
    import time

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import layers

    def make_reader():
        def r():
            rng = np.random.RandomState(0)
            for _ in range(steps):
                time.sleep(read_ms / 1e3)  # simulated input I/O per batch
                xs = rng.rand(batch, dim).astype("float32")
                yield [(xs[i], xs[i, :1]) for i in range(batch)]
        return r

    def run_mode(pipelined):
        with pt.scope_guard(pt.Scope()):
            main_p, startup = pt.Program(), pt.Program()
            with pt.program_guard(main_p, startup):
                x = layers.data("px", shape=[dim], dtype="float32")
                y = layers.data("py", shape=[1], dtype="float32")
                h = layers.fc(input=x, size=hidden, act="relu")
                pred = layers.fc(input=h, size=1, act=None)
                cost = layers.mean(
                    layers.square_error_cost(input=pred, label=y))
            trainer = pt.Trainer(
                cost=cost, optimizer=pt.SGD(learning_rate=lr),
                feed_list=[x, y], place=pt.TPUPlace(0),
                main_program=main_p, startup_program=startup)
            es = trainer.exe.stats
            windows = {}  # timed pass_id -> marks/deltas
            events = []

            def handler(e):
                # costs stay untouched here (lazy): they materialise at
                # pass end, inside the window — the pipelined mode's
                # honest per-pass sync point. The per-pass pipeline
                # counters are merged into exe.stats before EndPass
                # fires, so the BeginPass/EndPass deltas scope feed-wait
                # to exactly the timed window.
                if isinstance(e, pt.BeginPass) and e.pass_id >= 1:
                    windows[e.pass_id] = {
                        "t0": time.perf_counter(),
                        "feed0": es["feed_wait_ms"]}
                elif isinstance(e, pt.EndPass) and e.pass_id >= 1:
                    w = windows[e.pass_id]
                    w["dt"] = time.perf_counter() - w["t0"]
                    w["feed_wait_ms"] = es["feed_wait_ms"] - w["feed0"]
                elif isinstance(e, pt.EndIteration) and e.pass_id >= 1:
                    events.append(e)

            trainer.train(make_reader(), num_passes=1 + timed_passes,
                          event_handler=handler, pipeline=pipelined)
            best = min(windows.values(), key=lambda w: w["dt"])
            last = timed_passes  # last pass id
            losses = [e.cost for e in events  # cached post-train access
                      if e.pass_id == last]
            return {"dt": best["dt"],
                    "feed_wait_ms": best["feed_wait_ms"],
                    "losses": losses, "stats": dict(es)}

    sync = run_mode(False)
    pipe = run_mode(True)
    st = pipe["stats"]
    ms_per_step = 1e3 * pipe["dt"] / steps
    feed_wait = pipe["feed_wait_ms"] / steps
    return {
        "pipeline_sync_steps_s": round(steps / sync["dt"], 2),
        "pipeline_steps_s": round(steps / pipe["dt"], 2),
        "pipeline_speedup": round(sync["dt"] / max(pipe["dt"], 1e-9), 3),
        "pipeline_parity": sync["losses"] == pipe["losses"],
        "pipeline_feed_wait_ms_per_step": round(feed_wait, 3),
        "pipeline_ms_per_step": round(ms_per_step, 3),
        # nonzero overlap = the step never stalls a full feed behind it
        "pipeline_overlap": bool(feed_wait < ms_per_step),
        "pipeline_dispatch_depth": st["dispatch_depth"],
        "pipeline_fetch_syncs": st["fetch_sync_count"],
        "pipeline_compile_cache_hits": st["compile_cache_hits"],
    }

"""Async execution pipeline (paddle_tpu.pipeline): overlapped feed
prefetch, lazy fetches, warm compile cache.

Contracts under test: bit-exact loss parity sync vs. pipelined over >=3
passes, bounded ring reuse at depth=2, the declared lazy-fetch
materialization points, the ``pipeline.feed_next`` fault site (feed
thread dies -> clean synchronous fallback with a recorded resilience
event, no batch dropped), and the process-level warm-start compile cache
(second Executor skips the compile).

(The GPipe pipeline-*parallelism* tests live in tests/test_pipeline.py —
different subsystem, prior name.)
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu import resilience
from paddle_tpu.pipeline import (AsyncFetch, FeedPipeline, materialize,
                                 materialize_scalar)

N_BATCHES = 8
BATCH = 4
DIM = 8


def _build():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[DIM], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(input=x, size=16, act="tanh")
        pred = layers.fc(input=h, size=1, act=None)
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
    return main, startup, cost, [x, y]


def _reader(n=N_BATCHES, seed=3):
    def r():
        rng = np.random.RandomState(seed)
        for _ in range(n):
            xs = rng.rand(BATCH, DIM).astype("float32")
            yield [(xs[i], xs[i, :1]) for i in range(BATCH)]
    return r


def _train(pipelined, num_passes=3, depth=2):
    """One full Trainer run in a fresh scope; losses collected lazily
    (the handler never touches .cost during the pass)."""
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, feeds = _build()
        tr = pt.Trainer(cost=cost, optimizer=pt.SGD(learning_rate=0.05),
                        feed_list=feeds, place=pt.CPUPlace(),
                        main_program=main, startup_program=startup)
        events = []
        tr.train(_reader(), num_passes=num_passes,
                 event_handler=events.append,
                 pipeline=pipelined, pipeline_depth=depth)
        losses = [e.cost for e in events
                  if isinstance(e, pt.EndIteration)]
        pass_avgs = [e.metrics["avg_cost"] for e in events
                     if isinstance(e, pt.EndPass)]
        return losses, pass_avgs, tr


# -- parity -------------------------------------------------------------------

def test_bit_exact_parity_sync_vs_pipelined():
    l_sync, p_sync, _ = _train(False)
    l_pipe, p_pipe, tr = _train(True)
    assert len(l_sync) == 3 * N_BATCHES
    assert l_sync == l_pipe          # bit-exact, all 3 passes
    assert p_sync == p_pipe
    st = tr.exe.stats
    assert st["lazy_fetches"] > 0
    assert st["dispatch_depth"] >= 1
    assert st["dispatch_depth"] <= 2


def test_pipeline_flag_default(monkeypatch):
    # FLAGS.pipeline drives the default; explicit arg wins
    with pt.flags_guard(pipeline=True):
        l_pipe, _, tr = _train(None)  # pipeline=None -> FLAGS
    assert tr.exe.stats["dispatch_depth"] >= 1
    l_sync, _, tr2 = _train(False)
    # the default loop fetches lazily too (it reads the loss after it has
    # taken the next batch); what tells the feed thread is its ring
    assert tr2.exe.stats["dispatch_depth"] == 0
    assert tr2.exe.stats["lookahead_steps"] == 3 * (N_BATCHES - 1)
    assert l_pipe == l_sync


def test_check_nan_inf_forces_synchronous():
    with pt.flags_guard(check_nan_inf=True):
        _, _, tr = _train(True, num_passes=1)
    assert tr.exe.stats["dispatch_depth"] == 0  # no feed thread
    assert tr.exe.stats["eager_runs"] == N_BATCHES + 1  # startup too


# -- ring buffer --------------------------------------------------------------

def test_ring_buffer_reuse_depth2():
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, feeds = _build()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        feeder = pt.DataFeeder(feed_list=feeds, program=main)
        pipe = FeedPipeline(_reader(), feeder, exe, depth=2)
        try:
            got = list(pipe)
        finally:
            pipe.close()
        assert len(got) == N_BATCHES
        for feed in got:
            assert set(feed) == set(feeder.feed_names)
        st = pipe.stats
        assert st["depth"] == 2
        assert st["batches"] == N_BATCHES
        # at most `depth` prefetched batches ever in flight...
        assert 1 <= st["max_in_flight"] <= 2
        # ...and the two slots were recycled for every batch past the
        # first fill (8 batches, 2 fresh slots -> 6 reuses)
        assert st["slot_reuse"] == N_BATCHES - 2


def test_depth_one_still_works():
    l_pipe, p_pipe, _ = _train(True, num_passes=1, depth=1)
    l_sync, p_sync, _ = _train(False, num_passes=1)
    assert l_pipe == l_sync and p_pipe == p_sync


# -- lazy fetches -------------------------------------------------------------

def test_lazy_fetch_materialization_points():
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, feeds = _build()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        feeder = pt.DataFeeder(feed_list=feeds, program=main)
        feed = feeder.feed(next(iter(_reader(n=1)())))

        outs = exe.run(main, feed=feed, fetch_list=[cost], sync=False)
        h = outs[0]
        assert isinstance(h, AsyncFetch)
        assert exe.stats["lazy_fetches"] == 1
        assert exe.stats["fetch_sync_count"] == 0

        # block() waits without transferring
        h.block()
        assert h.ready
        assert exe.stats["fetch_sync_count"] == 0

        # first access materialises (and counts) exactly once
        v = float(h)
        assert exe.stats["fetch_sync_count"] == 1
        assert float(h) == v
        assert float(np.asarray(h).reshape(-1)[0]) == v
        assert materialize_scalar(h) == v
        assert exe.stats["fetch_sync_count"] == 1  # cached

        # sync=True path is unchanged and counts nothing
        sync_out = exe.run(main, feed=feed, fetch_list=[cost])
        assert isinstance(sync_out[0], np.ndarray)
        assert float(sync_out[0].reshape(-1)[0]) == v
        assert exe.stats["fetch_sync_count"] == 1


def test_end_iteration_event_is_lazy():
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, feeds = _build()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        feeder = pt.DataFeeder(feed_list=feeds, program=main)
        feed = feeder.feed(next(iter(_reader(n=1)())))
        h, extra = exe.run(main, feed=feed, fetch_list=[cost, cost],
                           sync=False)
        ev = pt.EndIteration(0, 0, h, {"fetches": [extra]})
        assert exe.stats["fetch_sync_count"] == 0
        c = ev.cost  # touching .cost is the materialization point
        assert isinstance(c, float)
        assert exe.stats["fetch_sync_count"] == 1
        f = ev.metrics["fetches"]  # touching .metrics materialises too
        assert float(np.asarray(f[0]).reshape(-1)[0]) == c
        assert exe.stats["fetch_sync_count"] == 2


def test_materialize_passthrough():
    assert materialize(3.5) == 3.5
    assert materialize([1, 2]) == [1, 2]
    assert materialize_scalar(np.float32(2.0)) == 2.0


# -- fault injection / fallback ----------------------------------------------

def test_feed_thread_death_falls_back_synchronous():
    resilience.reset()
    resilience.clear_events()
    resilience.arm("pipeline.feed_next", action="raise", nth=3)
    try:
        l_pipe, p_pipe, tr = _train(True, num_passes=1)
    finally:
        resilience.reset()
    l_sync, p_sync, _ = _train(False, num_passes=1)
    # the batch the feed thread died on was retried synchronously:
    # nothing dropped, losses still bit-identical
    assert l_pipe == l_sync
    assert p_pipe == p_sync
    evs = resilience.events(kind="pipeline_degraded")
    assert evs and evs[0]["site"] == "pipeline.feed_next"


def test_persistent_feed_fault_degrades_cleanly():
    # a fault armed to fire forever kills the feed thread on batch 0;
    # the fallback (which is no longer the instrumented thread site)
    # finishes the whole run synchronously with full parity
    resilience.reset()
    resilience.clear_events()
    resilience.arm("pipeline.feed_next", action="raise", nth=1,
                   times=None, exc=ConnectionError)
    try:
        l_pipe, p_pipe, _ = _train(True, num_passes=2)
    finally:
        resilience.reset()
    l_sync, p_sync, _ = _train(False, num_passes=2)
    assert l_pipe == l_sync and p_pipe == p_sync
    assert len(resilience.events(kind="pipeline_degraded")) == 2  # per pass


def test_reader_exception_propagates_through_pipeline():
    def dying_reader():
        def r():
            for d in _reader(n=2)():
                yield d
            raise ValueError("reader died")
        return r

    with pt.scope_guard(pt.Scope()):
        main, startup, cost, feeds = _build()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        feeder = pt.DataFeeder(feed_list=feeds, program=main)
        pipe = FeedPipeline(dying_reader(), feeder, exe, depth=2)
        try:
            with pytest.raises(ValueError, match="reader died"):
                list(pipe)
        finally:
            pipe.close()


# -- compile cache ------------------------------------------------------------

def test_warm_compile_cache_hit_on_second_executor():
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, feeds = _build()
        feeder = pt.DataFeeder(feed_list=feeds, program=main)
        feed = feeder.feed(next(iter(_reader(n=1)())))

        exe1 = pt.Executor(pt.CPUPlace())
        exe1.run(startup)
        out1 = exe1.run(main, feed=feed, fetch_list=[cost])
        assert exe1.stats["compile_cache_hits"] == 0

        # a second Executor over the same (program uid, version, feed
        # signature) warm-starts from the process-level registry
        exe2 = pt.Executor(pt.CPUPlace())
        out2 = exe2.run(main, feed=feed, fetch_list=[cost])
        assert exe2.stats["jit_runs"] == 1
        assert exe2.stats["compile_cache_hits"] == 1
        np.testing.assert_array_equal(np.asarray(out1[0]),
                                      np.asarray(out2[0]))


def test_compile_cache_flag_and_dir():
    from paddle_tpu import pipeline as pl
    # the lazy hook never overrides an explicitly configured dir and
    # honors the opt-out flag; enable_compile_cache reports its target
    with pt.flags_guard(compile_cache=False):
        saved = dict(pl._compile_cache_state)
        pl._compile_cache_state["configured"] = False
        try:
            pl.maybe_enable_compile_cache()
            assert pl._compile_cache_state["configured"]
        finally:
            pl._compile_cache_state.update(saved)


def test_examples_config_parity():
    """Acceptance: bit-identical losses sync vs pipelined on the book
    config (examples/configs/fit_a_line.py — same contract `paddle_tpu
    train` drives)."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "configs", "fit_a_line.py")
    spec_ = importlib.util.spec_from_file_location("fit_a_line_cfg", path)
    cfg = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(cfg)

    def run(pipelined):
        with pt.scope_guard(pt.Scope()):
            main, startup = pt.Program(), pt.Program()
            with pt.program_guard(main, startup):
                spec = cfg.model()
            tr = pt.Trainer(cost=spec["cost"], optimizer=spec["optimizer"],
                            feed_list=spec["feed_list"],
                            place=pt.CPUPlace(), main_program=main,
                            startup_program=startup)
            events = []
            tr.train(spec["reader"], num_passes=spec["num_passes"],
                     event_handler=events.append, pipeline=pipelined)
            return [e.cost for e in events
                    if isinstance(e, pt.EndIteration)]

    l_sync = run(False)
    l_pipe = run(True)
    assert l_sync and l_sync == l_pipe


def test_eval_pipeline_parity():
    """Trainer.test rides the same async pipeline as training (ROADMAP
    follow-up from PR 3): feed prefetch + lazy fetches, with the whole
    eval pass materializing at its one sync point — the return value.
    Results must match the synchronous eval loop exactly."""
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, feeds = _build()
        tr = pt.Trainer(cost=cost, optimizer=pt.SGD(learning_rate=0.05),
                        feed_list=feeds, place=pt.CPUPlace(),
                        main_program=main, startup_program=startup)
        tr.train(_reader(), num_passes=1, pipeline=False)
        base_lazy = tr.exe.stats["lazy_fetches"]

        sync_metrics = tr.test(_reader(seed=11), pipeline=False)
        assert tr.exe.stats["lazy_fetches"] == base_lazy
        pipe_metrics = tr.test(_reader(seed=11), pipeline=True)
        assert tr.exe.stats["lazy_fetches"] > base_lazy  # eval went lazy
        assert sync_metrics == pipe_metrics              # exact parity
        # FLAGS.pipeline drives the default for eval too
        with pt.flags_guard(pipeline=True):
            flag_metrics = tr.test(_reader(seed=11))
        assert flag_metrics == sync_metrics


def test_profiler_pipeline_counters(tmp_path):
    from paddle_tpu import profiler
    profiler.reset_pipeline_counters()
    _train(True, num_passes=1)
    ctr = profiler.pipeline_counters()
    assert ctr.get("pipeline_batches", 0) >= N_BATCHES
    assert ctr.get("dispatch_depth", 0) >= 1
    # counters land in the timeline artifact
    path = str(tmp_path / "timeline.json")
    art = profiler.write_timeline(path)
    assert art["pipeline"]["pipeline_batches"] >= N_BATCHES

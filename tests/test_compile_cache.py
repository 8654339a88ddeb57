"""The compile cache: the process-level warm-start registry (a second
Executor over the same program skips the compile) and the persistent
cache's lazy hook (``paddle_tpu.core.compile_cache``)."""
import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import compile_cache as cc

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


def _one_feed(main, feeds):
    rng = np.random.RandomState(3)
    xs = rng.rand(BATCH, DIM).astype("float32")
    feeder = pt.DataFeeder(feed_list=feeds, program=main)
    return feeder.feed([(xs[i], xs[i, :1]) for i in range(BATCH)])


def test_warm_compile_cache_hit_on_second_executor():
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, feeds = _build()
        feed = _one_feed(main, feeds)

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
    # the lazy hook never overrides an explicitly configured dir and
    # honors the opt-out flag; enable_compile_cache reports its target
    with pt.flags_guard(compile_cache=False):
        saved = dict(cc._compile_cache_state)
        cc._compile_cache_state["configured"] = False
        try:
            cc.maybe_enable_compile_cache()
            assert cc._compile_cache_state["configured"]
        finally:
            cc._compile_cache_state.update(saved)

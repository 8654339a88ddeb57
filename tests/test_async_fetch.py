"""Lazy fetches: ``Executor.run(..., sync=False)`` hands out
:class:`AsyncFetch` handles that stay on the device until somebody reads
them, once, at a counted sync point."""
import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core.executor import (AsyncFetch, materialize,
                                      materialize_scalar)

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


def test_lazy_fetch_materialization_points():
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, feeds = _build()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        feed = _one_feed(main, feeds)

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


def test_materialize_passthrough():
    assert materialize(3.5) == 3.5
    assert materialize([1, 2]) == [1, 2]
    assert materialize_scalar(np.float32(2.0)) == 2.0

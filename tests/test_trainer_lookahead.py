"""Trainer's default loop looks one batch ahead on the training thread:
step n is dispatched, batch n+1 is taken, stacked and uploaded while the
device computes, then step n's loss is read and ``EndIteration(n)`` fires.
What a user could see of the old strictly serial order stays true: the
losses, the state a handler reads, the order of events, where a reader's
exception lands."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.core import unique_name
from paddle_tpu.trainer import _Lookahead

N, BATCH, DIM = 6, 4, 8


def _batches(n=N, seed=5):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        xs = rng.rand(BATCH, DIM).astype("float32")
        out.append([(xs[i], xs[i, :1]) for i in range(BATCH)])
    return out


def _program():
    main, startup = pt.Program(), pt.Program()
    with unique_name.guard(), pt.program_guard(main, startup):
        x = layers.data("x", shape=[DIM], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(input=x, size=16, act="tanh")
        pred = layers.fc(input=h, size=1, act=None)
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
    return main, startup, cost, pred, [x, y]


def _trainer(**kw):
    main, startup, cost, pred, feeds = _program()
    tr = pt.Trainer(cost=cost, optimizer=pt.Momentum(0.05, momentum=0.9),
                    feed_list=feeds, fetch_list=[pred],
                    place=pt.CPUPlace(), main_program=main,
                    startup_program=startup, **kw)
    return tr, main


def _param_names(main):
    return sorted(v.name for v in main.list_vars()
                  if isinstance(v, pt.core.ir.Parameter))


def _by_hand(batches):
    """Feed, run, read, strictly in turn, with a bare Executor: the losses
    and the parameters after every step."""
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, pred, feeds = _program()
        with pt.program_guard(main, startup):
            pt.Momentum(0.05, momentum=0.9).minimize(cost)
        exe = pt.Executor(pt.CPUPlace())
        feeder = pt.DataFeeder(feeds, place=pt.CPUPlace(), program=main)
        exe.run(startup)
        scope = pt.global_scope()
        losses, states, preds = [], [], []
        for b in batches:
            loss, p = exe.run(main, feed=feeder.feed(b),
                              fetch_list=[cost, pred])
            losses.append(float(np.asarray(loss).reshape(-1)[0]))
            preds.append(np.asarray(p))
            states.append({n: np.array(scope.find_var(n))
                           for n in _param_names(main)})
    return losses, states, preds


class _Recorded(object):
    """A Trainer whose reader, ``exe.run`` and handler write one log: the
    reader when it is asked for batch k (``take``), ``exe.run`` when step
    n is dispatched, the handler at every event."""

    def __init__(self, batches=None, num_passes=1, fail_at=None,
                 on_end=None, **kw):
        self.log = []
        self.events = []
        self.batches = _batches() if batches is None else batches
        self.scope = pt.Scope()
        with pt.scope_guard(self.scope):
            self.trainer, self.main = _trainer(**kw)
            self.trainer._maybe_init()
            run, main, steps = self.trainer.exe.run, self.main, [0]

            def logged_run(program=None, **k):
                if program is main:
                    self.log.append(("dispatch", steps[0]))
                    steps[0] += 1
                return run(program, **k)
            # on the instance, as a tracer would put it there
            self.trainer.exe.run = logged_run

            def reader():
                self.log.append(("reader",))
                for k, b in enumerate(self.batches):
                    self.log.append(("take", k))
                    if k == fail_at:
                        raise IOError("batch %d is unreadable" % k)
                    yield b
                self.log.append(("exhausted",))

            def handler(e):
                self.log.append((type(e).__name__,
                                 getattr(e, "batch_id", e.pass_id)))
                self.events.append(e)
                if on_end is not None and isinstance(e, pt.EndIteration):
                    on_end(self, e)
            self.error = None
            try:
                self.trainer.train(reader, num_passes=num_passes,
                                   event_handler=handler)
            except Exception as e:      # kept for the test to look at
                self.error = e

    def at(self, *entry):
        return self.log.index(entry)

    @property
    def ends(self):
        return [e for e in self.events if isinstance(e, pt.EndIteration)]


@pytest.fixture(scope="module")
def one_pass():
    seen = {}

    def on_end(rec, e):
        seen[e.batch_id] = {n: np.array(rec.scope.find_var(n))
                            for n in _param_names(rec.main)}
    rec = _Recorded(on_end=on_end)
    rec.seen = seen
    return rec


@pytest.fixture(scope="module")
def by_hand():
    return _by_hand(_batches())


@pytest.mark.parametrize("n", range(N - 1))
def test_batch_n_plus_1_is_taken_between_dispatch_n_and_end_n(one_pass, n):
    r = one_pass
    assert r.error is None
    assert (r.at("BeginIteration", n) < r.at("dispatch", n)
            < r.at("take", n + 1) < r.at("EndIteration", n)
            < r.at("dispatch", n + 1))


@pytest.mark.parametrize("n", range(N - 2))
def test_never_two_batches_ahead(one_pass, n):
    assert one_pass.at("EndIteration", n) < one_pass.at("take", n + 2)


def test_the_last_step_finds_the_reader_exhausted_and_finishes(one_pass):
    r = one_pass
    assert (r.at("dispatch", N - 1) < r.at("exhausted")
            < r.at("EndIteration", N - 1) < r.at("EndPass", 0))
    assert r.log.count(("exhausted",)) == 1
    assert [e for e in r.log if e[0] == "dispatch"] == [
        ("dispatch", k) for k in range(N)]


def test_losses_are_those_of_feeding_and_running_in_turn(one_pass, by_hand):
    assert [e.cost for e in one_pass.ends] == by_hand[0]   # bit for bit


def test_final_parameters_are_those_of_feeding_and_running_in_turn(
        one_pass, by_hand):
    for n in _param_names(one_pass.main):
        np.testing.assert_array_equal(
            np.array(one_pass.scope.find_var(n)), by_hand[1][-1][n])


@pytest.mark.parametrize("n", range(N))
def test_the_handler_of_end_n_reads_the_state_after_step_n(
        one_pass, by_hand, n):
    assert sorted(one_pass.seen[n]) == _param_names(one_pass.main)
    for name, value in one_pass.seen[n].items():
        np.testing.assert_array_equal(value, by_hand[1][n][name])


def test_cost_is_a_float_and_fetches_are_host_arrays(one_pass, by_hand):
    for n, e in enumerate(one_pass.ends):
        assert type(e.cost) is float and "cost" in vars(e)
        (pred,) = vars(e)["metrics"]["fetches"]     # plain attributes
        assert type(pred) is np.ndarray
        np.testing.assert_array_equal(e.metrics["fetches"][0],
                                      by_hand[2][n])


def test_two_passes_fire_in_order_and_no_batch_crosses_the_boundary():
    r = _Recorded(batches=_batches(3), num_passes=2)
    assert r.error is None
    names = [e for e in r.log if e[0] in ("BeginPass", "EndPass", "reader",
                                          "exhausted")]
    assert names == [("BeginPass", 0), ("reader",), ("exhausted",),
                     ("EndPass", 0), ("BeginPass", 1), ("reader",),
                     ("exhausted",), ("EndPass", 1)]
    first = r.log[:r.at("EndPass", 0)]
    assert [e for e in first if e[0] == "take"] == [("take", k)
                                                    for k in range(3)]
    assert [e for e in r.log if e[0] == "dispatch"] == [
        ("dispatch", k) for k in range(6)]
    l1, _s, _p = _by_hand(_batches(3) + _batches(3))
    assert [e.cost for e in r.ends] == l1


@pytest.mark.parametrize("k", [1, 3])
def test_a_reader_that_raises_at_batch_k_lets_end_k_minus_1_fire_first(k):
    r = _Recorded(fail_at=k)
    assert isinstance(r.error, IOError) and str(k) in str(r.error)
    assert r.log[-2:] == [("take", k), ("EndIteration", k - 1)]
    assert [e.batch_id for e in r.ends] == list(range(k))
    assert ("dispatch", k) not in r.log


def test_a_reader_that_raises_at_its_first_batch_raises_at_once():
    r = _Recorded(fail_at=0)
    assert isinstance(r.error, IOError)
    assert r.log == [("BeginPass", 0), ("reader",), ("take", 0)]


def test_a_feed_that_raises_on_batch_k_lets_end_k_minus_1_fire_first():
    batches = _batches(4)
    batches[2] = [(np.zeros(DIM, "float32"),)] * BATCH    # a slot short
    r = _Recorded(batches=batches)
    assert r.error is not None and not isinstance(r.error, IOError)
    assert r.log[-2:] == [("take", 2), ("EndIteration", 1)]


def test_check_nan_inf_takes_the_same_order(by_hand):
    with pt.flags_guard(check_nan_inf=True):
        r = _Recorded()
    assert r.error is None
    assert r.trainer.exe.stats["eager_runs"] == N + 1   # startup too
    for n in range(N - 1):
        assert (r.at("dispatch", n) < r.at("take", n + 1)
                < r.at("EndIteration", n) < r.at("dispatch", n + 1))
    np.testing.assert_allclose([e.cost for e in r.ends], by_hand[0],
                               rtol=1e-5)


def test_the_two_counters_count(one_pass):
    st = one_pass.trainer.exe.stats
    assert st["lookahead_steps"] == N - 1       # the last step had no next
    assert 0 <= st["lookahead_loss_ready"] <= st["lookahead_steps"]
    assert type(st["lookahead_loss_ready"]) is int


def test_the_counters_reach_the_profilers_pipeline_section():
    profiler.reset_pipeline_counters()
    r = _Recorded(batches=_batches(3), num_passes=2)
    got = profiler.pipeline_counters()
    assert got["lookahead_steps"] == 4 == \
        r.trainer.exe.stats["lookahead_steps"]
    assert got["lookahead_loss_ready"] == \
        r.trainer.exe.stats["lookahead_loss_ready"]


def test_a_window_that_opens_inside_the_reader_at_batch_4():
    """Shaped like the benchmark's driver: its window opens when the
    reader is asked for batch 4, and its handler takes the compared state
    at ``EndIteration(2)`` only while the window is still shut."""
    st = {"open": False}
    at_end = {}

    class Opening(object):
        def __iter__(self):
            for k, b in enumerate(_batches()):
                if k == 4:
                    st["open"] = True
                yield b

    def on_end(rec, e):
        at_end[e.batch_id] = st["open"]
    r = _Recorded(batches=Opening(), on_end=on_end)
    assert r.error is None
    assert at_end[2] is False                   # the driver's condition
    assert at_end == {0: False, 1: False, 2: False, 3: True, 4: True,
                      5: True}


def test_on_preemption_one_batch_was_taken_and_is_not_trained():
    def on_end(rec, e):
        if e.batch_id == 1:
            rec.trainer.request_preempt()
    r = _Recorded(on_end=on_end)
    assert r.error is None
    assert r.log[-3:] == [("dispatch", 1), ("take", 2), ("EndIteration", 1)]


def test_feed_and_run_are_looked_up_on_the_instances_at_every_step():
    with pt.scope_guard(pt.Scope()):
        tr, _main = _trainer()
        tr._maybe_init()
        calls = {"feed": 0, "run": 0}
        feed, run = tr.feeder.feed, tr.exe.run

        def counted_feed(data):
            calls["feed"] += 1
            return feed(data)

        def counted_run(*a, **k):
            calls["run"] += 1
            return run(*a, **k)
        batches = _batches()

        def reader():
            for k, b in enumerate(batches):
                if k == 2:      # put there while train() is under way
                    tr.feeder.feed, tr.exe.run = counted_feed, counted_run
                yield b
        tr.train(reader)
    assert calls == {"feed": N - 2, "run": N - 2}


def test_lookahead_holds_what_take_raised_until_next():
    class Feeder(object):
        def feed(self, raw):
            if raw == "bad":
                raise ValueError(raw)
            return {"x": raw}

    class Exe(object):
        def prepare_feed(self, feed):
            return dict(feed, here=True)

    class T(object):
        feeder, exe = Feeder(), Exe()
    ahead = _Lookahead(["a", "b", "bad"], T())
    assert next(ahead) == {"x": "a", "here": True}     # takes it itself
    ahead = _Lookahead(["a", "bad"], T())
    assert ahead.take() is True and next(ahead)["x"] == "a"
    assert ahead.take() is False                        # held, not raised
    with pytest.raises(ValueError):
        next(ahead)
    assert ahead.take() is False                        # the reader's end
    with pytest.raises(StopIteration):
        next(ahead)

"""Trainer's default loop looks one batch ahead on the training thread
and dispatches one step ahead: in iteration n the state of step n is
committed to the scope, batch n+1 is taken, stacked and uploaded while the
device computes, step n+1 is dispatched on it with its new state held
back, then step n's loss is read and ``EndIteration(n)`` fires. What a
user could see of the old strictly serial order stays true: the losses,
the state a handler reads, the order of events, where a reader's
exception lands."""
import types

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.core import executor as executor_mod
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


def _poke(scope, main, k):
    """What a handler that writes the scope does: a parameter set anew
    (another object, another value)."""
    name = _param_names(main)[0]
    scope.set_var(name, np.array(scope.find_var(name)) * 0.5 + 0.01 * k)


def _by_hand(batches, poke_after=None):
    """Feed, run, read, strictly in turn, with a bare Executor: the losses
    and the parameters after every step. ``poke_after=k``: the scope is
    written (``_poke``) between step k and step k+1."""
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
            if poke_after == len(losses) - 1:
                _poke(scope, main, poke_after)
    return losses, states, preds


class _Recorded(object):
    """A Trainer whose reader, ``exe.run``, ``exe.commit`` and handler
    write one log: the reader when it is asked for batch k (``take``),
    ``exe.run`` when the k-th step is dispatched (``dispatch``, and with
    which ``hold``), ``exe.commit`` when the k-th held state is written
    to the scope (``commit``) or dropped instead (``stale``), the handler
    at every event. ``on_event(rec, e)`` sees every event,
    ``on_end(rec, e)`` every ``EndIteration``; ``build(main)`` may add to
    the program."""

    def __init__(self, batches=None, num_passes=1, fail_at=None,
                 on_end=None, on_event=None, build=None, **kw):
        self.log = []
        self.events = []
        self.holds = []
        self.batches = _batches() if batches is None else batches
        self.scope = pt.Scope()
        with pt.scope_guard(self.scope):
            self.trainer, self.main = _trainer(**kw)
            if build is not None:
                build(self.main)
            self.trainer._maybe_init()
            exe, main = self.trainer.exe, self.main
            run, commit, steps, commits = exe.run, exe.commit, [0], [0]

            def logged_run(program=None, **k):
                if program is main:
                    self.log.append(("dispatch", steps[0]))
                    self.holds.append(k.get("hold", False))
                    steps[0] += 1
                return run(program, **k)

            def logged_commit():
                held = exe._held is not None
                done = commit()
                if done:
                    self.log.append(("commit", commits[0]))
                    commits[0] += 1
                elif held:
                    self.log.append(("stale",))
                return done
            # on the instance, as a tracer would put them there
            exe.run, exe.commit = logged_run, logged_commit

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
                if on_event is not None:
                    on_event(self, e)
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

    @property
    def stats(self):
        return self.trainer.exe.stats

    def state(self):
        return {n: np.array(self.scope.find_var(n))
                for n in _param_names(self.main)}


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
    """And step n+1 is dispatched on it before ``EndIteration(n)``, after
    step n's state went to the scope: commit n < take n+1 < dispatch n+1
    < EndIteration n."""
    r = one_pass
    assert r.error is None
    assert (r.at("dispatch", n) < r.at("commit", n)
            < r.at("take", n + 1) < r.at("dispatch", n + 1)
            < r.at("EndIteration", n) < r.at("BeginIteration", n + 1)
            < r.at("commit", n + 1))
    assert r.at("BeginIteration", n) < r.at("commit", n)


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
    assert r.stats["ahead_steps"] == 0 and r.holds == [False] * N
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
    assert r.log[-3:] == [("take", 2), ("dispatch", 2), ("EndIteration", 1)]
    # step 2 was dispatched ahead on that batch and is dropped: the scope
    # holds the state after step 1
    assert ("commit", 2) not in r.log and r.stats["ahead_dropped"] == 1
    want = _by_hand(_batches())[1][1]
    for name, value in r.state().items():
        np.testing.assert_array_equal(value, want[name])


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


# -- dispatch ahead, commit late -------------------------------------------

def test_every_step_but_the_first_of_a_pass_is_dispatched_ahead(one_pass):
    r = one_pass
    assert r.stats["ahead_steps"] == N - 1 and r.stats["ahead_dropped"] == 0
    assert r.holds == [True] * N            # one executable for every step
    assert [e for e in r.log if e[0] == "commit"] == [
        ("commit", k) for k in range(N)]
    assert ("stale",) not in r.log and r.trainer.exe._held is None


def test_the_ahead_counters_reach_the_profilers_pipeline_section():
    profiler.reset_pipeline_counters()

    def on_end(rec, e):
        if e.batch_id == 0:
            _poke(rec.scope, rec.main, 0)
    r = _Recorded(batches=_batches(3), num_passes=2, on_end=on_end)
    got = profiler.pipeline_counters()
    assert got["ahead_steps"] == 4 == r.stats["ahead_steps"]
    assert got["ahead_dropped"] == 2 == r.stats["ahead_dropped"]


@pytest.mark.parametrize("event,k,dropped", [
    ("EndIteration", 0, 1), ("EndIteration", 2, 1),
    ("EndIteration", N - 1, 0),             # nothing is pending at the end
    ("BeginIteration", 1, 1), ("BeginIteration", 3, 1)])
def test_a_handler_that_writes_the_scope_gets_the_by_hand_answer(
        event, k, dropped):
    """The step dispatched ahead was computed from a state that is no
    longer the scope's: it is dropped and runs again."""
    seen = {}

    def on_event(rec, e):
        if type(e).__name__ == event and e.batch_id == k:
            _poke(rec.scope, rec.main, k if event == "EndIteration"
                  else k - 1)
        if isinstance(e, pt.EndIteration):
            seen[e.batch_id] = rec.state()
    r = _Recorded(on_event=on_event)
    assert r.error is None
    after = k if event == "EndIteration" else k - 1
    losses, states, _p = _by_hand(_batches(), poke_after=after)
    assert [e.cost for e in r.ends] == losses             # bit for bit
    for name, value in r.state().items():
        want = states[-1][name]
        if after == N - 1 and name == _param_names(r.main)[0]:
            want = want * 0.5 + 0.01 * after              # poked last
        np.testing.assert_array_equal(value, want)
    for n in range(N):
        if n == after and event == "EndIteration":
            continue                        # read after its own write
        for name, value in seen[n].items():
            np.testing.assert_array_equal(value, states[n][name])
    assert r.stats["ahead_dropped"] == dropped
    assert r.log.count(("stale",)) == dropped
    assert r.stats["ahead_steps"] == N - 1
    assert r.stats["compiles"] == 2         # startup, and ONE step


@pytest.mark.parametrize("k", [1, 3])
def test_a_reader_that_raises_at_batch_k_leaves_the_state_of_step_k_minus_1(
        k, by_hand):
    r = _Recorded(fail_at=k)
    assert isinstance(r.error, IOError)
    assert r.stats["ahead_steps"] == k - 1 and r.trainer.exe._held is None
    for name, value in r.state().items():
        np.testing.assert_array_equal(value, by_hand[1][k - 1][name])


def test_a_handler_that_raises_at_end_k_drops_the_step_dispatched_ahead(
        by_hand):
    def on_end(rec, e):
        if e.batch_id == 2:
            raise KeyError("the handler's own")
    r = _Recorded(on_end=on_end)
    assert isinstance(r.error, KeyError)
    assert r.log[-3:] == [("take", 3), ("dispatch", 3), ("EndIteration", 2)]
    assert r.stats["ahead_dropped"] == 1 and r.trainer.exe._held is None
    for name, value in r.state().items():
        np.testing.assert_array_equal(value, by_hand[1][2][name])


def test_no_step_dispatched_ahead_crosses_a_pass():
    r = _Recorded(batches=_batches(3), num_passes=2)
    assert r.stats["ahead_steps"] == 4 and r.stats["ahead_dropped"] == 0
    assert r.stats["compiles"] == 2         # startup, and ONE step
    between = r.log[r.at("EndIteration", 2):r.at("BeginPass", 1)]
    assert not [e for e in between if e[0] in ("dispatch", "commit")]
    # the first step of pass 1 is dispatched in its own iteration
    begin = [i for i, e in enumerate(r.log)
             if e == ("BeginIteration", 0)][1]
    assert r.log[begin + 1:begin + 3] == [("dispatch", 3), ("commit", 3)]


def test_the_step_is_compiled_once(caplog):
    """The memory rule compiles the step to read its ``memory_analysis()``
    before the first call: that call must not compile it again."""
    import jax
    import logging
    with jax.log_compiles(True), caplog.at_level(logging.WARNING):
        r = _Recorded(batches=_batches(3))
    assert r.error is None and r.stats["ahead_steps"] == 2
    done = [m for m in caplog.messages
            if m.startswith("Finished XLA compilation of jit(paddle_tpu_step")]
    names = {m.split("jit(")[1].split(")")[0] for m in done}
    assert len(done) == len(names) == 2, done   # startup's and the step's


class _Mem(object):
    argument_size_in_bytes = 140         # state, feed and the spare set
    output_size_in_bytes = 42            # new state (in the spare), loss
    temp_size_in_bytes = 900
    alias_size_in_bytes = 40


@pytest.mark.parametrize("limit,fits", [
    (None, True), (1084, True), (1083, False), (1, False)])
def test_one_more_copy_of_the_outputs_has_to_fit_beside_the_peak(
        limit, fits):
    assert executor_mod._held_step_fits(_Mem(), limit) is fits
    assert executor_mod._held_step_fits(None, limit) is True


def test_where_two_steps_do_not_fit_the_loop_runs_todays_order_and_donates(
        monkeypatch, by_hand):
    monkeypatch.setattr(executor_mod, "_device_bytes_limit", lambda d: 1)
    r = _Recorded(num_passes=1)
    assert r.error is None
    assert r.stats["ahead_steps"] == 0 and r.stats["ahead_dropped"] == 0
    assert not r.trainer.exe.can_hold(r.main)
    # the executor was asked once, at step 0, and said no with nothing run
    assert r.holds == [True] + [False] * N
    assert r.stats["jit_runs"] == N + 1     # startup too
    d = [i for i, e in enumerate(r.log) if e[0] == "dispatch"]
    assert d[1] == d[0] + 1 and "commit" not in [e[0] for e in r.log]
    for n in range(1, N - 1):
        assert (r.log.index(("dispatch", n + 1)) < r.at("take", n + 1)
                < r.at("EndIteration", n)
                < r.log.index(("dispatch", n + 2)))
    assert [e.cost for e in r.ends] == by_hand[0]         # bit for bit
    for name, value in r.state().items():
        np.testing.assert_array_equal(value, by_hand[1][-1][name])


class _Held(object):
    """A held step whose compile the device's compiler refuses."""

    def __init__(self, error=None):
        self.error, self.compiles = error, 0

    def memory(self, *args):
        self.compiles += 1
        if self.error is not None:
            raise self.error
        return _Mem()


def _args(n):
    state = {"w": np.zeros((n,), np.float32)}
    return (state, {"x": np.zeros((2,), np.float32)}, None, state)


@pytest.mark.parametrize("case,fits,compiles", [
    ("two_sets_exceed_the_limit", False, 0),
    ("two_sets_and_the_new_state_exceed_the_limit", False, 0),
    ("the_compiler_runs_out_of_device_memory", False, 1),
    ("it_fits", True, 1)])
def test_a_held_step_the_device_cannot_hold_is_a_no_not_an_error(
        case, fits, compiles):
    """XLA:TPU raises RESOURCE_EXHAUSTED at COMPILE time for a step that
    exceeds the device's memory (it reports no analysis): the loop then
    dispatches from donated state, it does not die. Two sets of state
    that, with the new state the rule wants beside them, exceed the limit
    are not compiled at all: the rule could only say no (800 B of state:
    two sets 1,600 of 2,000, three 2,400)."""
    import jax
    oom = jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 16.80G of 15.75G hbm.")
    held = _Held(oom if case.startswith("the_compiler") else None)
    n = {"two_sets_exceed_the_limit": 1000,
         "two_sets_and_the_new_state_exceed_the_limit": 200}.get(case, 10)
    assert executor_mod._held_step_compiles_and_fits(
        held, _args(n), 2000) is fits
    assert held.compiles == compiles
    if not compiles:
        # what the compile would have been asked: the rule's own answer for
        # the least such a step can take
        class _Least(object):
            argument_size_in_bytes = 2 * 4 * n
            output_size_in_bytes = 4 * n
            temp_size_in_bytes = alias_size_in_bytes = 0
        assert executor_mod._held_step_fits(_Least(), 2000) is False


def test_another_compile_error_of_the_held_step_is_raised():
    import jax
    held = _Held(jax.errors.JaxRuntimeError("INTERNAL: something else"))
    with pytest.raises(jax.errors.JaxRuntimeError, match="something else"):
        executor_mod._held_step_compiles_and_fits(held, _args(10), 2000)


def test_a_limit_with_room_for_two_steps_dispatches_ahead(monkeypatch):
    monkeypatch.setattr(executor_mod, "_device_bytes_limit",
                        lambda d: 1 << 40)
    r = _Recorded(batches=_batches(3))
    assert r.stats["ahead_steps"] == 2 and r.holds == [True] * 3


def test_a_program_with_a_host_op_runs_todays_order(tmp_path, by_hand):
    def build(main):
        main.global_block().append_op(
            type="save", inputs={"X": [_param_names(main)[0]]}, outputs={},
            attrs={"file_path": str(tmp_path / "w.ckpt")})
    r = _Recorded(build=build)
    assert r.error is None
    assert r.stats["hybrid_runs"] == N and r.stats["ahead_steps"] == 0
    assert r.holds == [False] * N and not r.trainer.exe.can_hold(r.main)
    for n in range(N - 1):
        assert (r.at("dispatch", n) < r.at("take", n + 1)
                < r.at("EndIteration", n) < r.at("dispatch", n + 1))
    np.testing.assert_allclose([e.cost for e in r.ends], by_hand[0],
                               rtol=1e-6)


# -- Executor.run(hold=True), commit, drop ----------------------------------

@pytest.fixture
def bare():
    """A bare Executor after startup, its scope, program and one feed."""
    scope = pt.Scope()
    with pt.scope_guard(scope):
        main, startup, cost, _pred, feeds = _program()
        with pt.program_guard(main, startup):
            pt.Momentum(0.05, momentum=0.9).minimize(cost)
        exe = pt.Executor(pt.CPUPlace())
        feeder = pt.DataFeeder(feeds, place=pt.CPUPlace(), program=main)
        exe.run(startup)
        feed = exe.prepare_feed(feeder.feed(_batches(1)[0]))
        yield types.SimpleNamespace(exe=exe, scope=scope, main=main,
                                    cost=cost, feed=feed)


def test_run_with_defaults_still_donates_and_writes_back(bare):
    b, name = bare, _param_names(bare.main)[0]
    b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost])   # commits state
    before = b.scope.find_var(name)
    b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost])
    assert b.scope.find_var(name) is not before and before.is_deleted()
    assert b.exe._held is None and b.exe.stats["ahead_dropped"] == 0


def test_a_held_step_leaves_the_scope_and_its_buffers_until_commit(bare):
    b, names = bare, _param_names(bare.main)
    b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost])
    before = {n: b.scope.find_var(n) for n in names}
    values = {n: np.array(v) for n, v in before.items()}
    (loss,) = b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost],
                        sync=False, hold=True)
    float(loss)                                         # the step is done
    for n in names:
        assert b.scope.find_var(n) is before[n]
        assert not before[n].is_deleted()
        np.testing.assert_array_equal(np.array(before[n]), values[n])
    assert b.exe.commit() is True and b.exe.commit() is False
    assert all(b.scope.find_var(n) is not before[n] for n in names)
    assert b.exe.stats["ahead_dropped"] == 0


def test_held_then_committed_equals_run_with_defaults_bit_for_bit(bare):
    b = bare
    losses = []
    for _ in range(3):
        (loss,) = b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost],
                            hold=True)
        assert b.exe.commit()
        losses.append(float(np.asarray(loss).reshape(-1)[0]))
    want, states, _p = _by_hand(_batches(1) * 3)
    assert losses == want
    for n in _param_names(b.main):
        np.testing.assert_array_equal(np.array(b.scope.find_var(n)),
                                      states[-1][n])


def test_commit_refuses_a_step_whose_scope_was_written_since(bare):
    b, name = bare, _param_names(bare.main)[0]
    b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost], hold=True)
    poked = np.array(b.scope.find_var(name)) + 1.0
    b.scope.set_var(name, poked)
    assert b.exe.commit() is False and b.exe.stats["ahead_dropped"] == 1
    assert b.scope.find_var(name) is poked
    # a second hold drops the first; drop() with nothing held counts nothing
    b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost], hold=True)
    b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost], hold=True)
    assert b.exe.stats["ahead_dropped"] == 2
    b.exe.drop()
    b.exe.drop()
    assert b.exe.stats["ahead_dropped"] == 3 and b.exe._held is None
    assert b.scope.find_var(name) is poked


def test_hold_is_refused_with_nothing_run_off_the_jit_path(bare):
    b = bare
    runs = dict(b.exe.stats)
    with pt.flags_guard(check_nan_inf=True):
        assert not b.exe.can_hold(b.main)
        assert b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost],
                         hold=True) is None
    assert b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost],
                     use_jit=False, hold=True) is None
    assert {k: b.exe.stats[k] for k in ("jit_runs", "eager_runs",
                                        "hybrid_runs")} == \
        {k: runs[k] for k in ("jit_runs", "eager_runs", "hybrid_runs")}
    assert b.exe.can_hold(b.main)


def test_a_scope_counts_the_writes_that_change_it():
    parent = pt.Scope()
    kid = parent.new_scope()
    parent.set_var("w", 1.0)
    stamp = kid.write_stamp()
    value = parent.find_var("w")
    kid.set_var("w", value)                 # the very object: no change
    assert kid.write_stamp() == stamp
    kid.set_var("w", 2.0)                   # written through to the owner
    assert kid.write_stamp() != stamp and parent.find_var("w") == 2.0
    for write in (lambda: kid.set_var("new", 0), lambda: kid.var("other"),
                  lambda: kid.erase("new"), lambda: parent.erase("w")):
        stamp = kid.write_stamp()
        write()
        assert kid.write_stamp() != stamp
    stamp = kid.write_stamp()
    kid.erase("absent"), kid.var("other"), kid.find_var("other")
    assert kid.write_stamp() == stamp


def test_held_steps_take_turns_on_two_sets_of_buffers(bare, recwarn):
    """A held step writes its new state into the buffers of the state
    before the one it reads (what the last commit replaced in the scope),
    handed over as donated spare arguments: nothing is allocated anew for
    it, which on the TPU is what keeps the dispatch short."""
    b = bare
    # parameters, velocities, learning rate: XLA pairs a spare buffer with
    # any output of its shape and dtype, so the SETS take turns
    names = b.exe._state_inputs(b.main, b.scope, b.feed)

    def held_step():
        b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost], hold=True)
        assert b.exe.commit()
        arrays = [b.scope.find_var(n) for n in names]
        return arrays, {a.unsafe_buffer_pointer() for a in arrays}
    held_step()                         # the startup's buffers go first
    first, at_first = held_step()
    second, at_second = held_step()
    third, at_third = held_step()
    assert len(at_first) == len(names) > len(_param_names(b.main))
    # (the fetched loss, an output too, takes the spare buffer of one
    # float of the state, which is then allocated anew)
    assert len(at_third - at_first) <= 1 and at_first.isdisjoint(at_second)
    assert all(a.is_deleted() for a in first)       # given to the third
    assert not any(a.is_deleted() for a in second + third)
    # a dropped step's buffers are the next one's spare set
    b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost], hold=True)
    b.exe.run(b.main, feed=b.feed, fetch_list=[b.cost], hold=True)
    assert b.exe.commit()
    assert len({b.scope.find_var(n).unsafe_buffer_pointer()
                for n in names} - at_second) <= 2
    b.exe.drop()
    assert b.exe._spare is None
    assert not [w for w in recwarn.list if "donated" in str(w.message)]

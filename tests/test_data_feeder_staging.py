"""``DataFeeder.feed`` stacks a dense field row by row into a staging array
the feeder keeps, and takes that array again only when nothing else can
still read it. What a caller could see of ``np.array(rows, dtype)`` built
fresh every call stays true: the bytes, shapes, dtypes and errors; a fed
batch never changes while the caller, a view, an upload in flight or a
device array that aliases it is alive."""
import sys
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import unique_name
from paddle_tpu.core.lod import LoDTensor


def _feeder(*fields):
    """A feeder over fields (name, shape, dtype[, lod_level])."""
    main = pt.Program()
    with unique_name.guard(), pt.program_guard(main, pt.Program()):
        feeds = [layers.data(f[0], shape=list(f[1]), dtype=f[2],
                             lod_level=f[3] if len(f) > 3 else 0)
                 for f in fields]
    return pt.DataFeeder(feeds, place=pt.CPUPlace(), program=main)


def _parent(rows, shape, dtype):
    """The parent commit's dense ``DataToLoDTensorConverter.done()``."""
    arr = np.array(rows, dtype=dtype)
    shape = tuple(s for s in shape if s != -1)
    if shape and arr.ndim == 1:
        try:
            arr = arr.reshape((-1,) + shape)
        except ValueError:
            pass
    return arr


_RNG = np.random.RandomState(3)
_BIG = _RNG.rand(64, 32768)             # 8 MB as float32


# name -> (shape, dtype, rows)
DENSE = {
    "arrays": ([3, 4], "float32",
               [_RNG.rand(3, 4).astype("float32") for _ in range(5)]),
    "python_floats": ([1], "float32", [0.1, 0.2, 1e-9, 3.0]),
    "python_ints": ([1], "int64", [3, 0, -7, 2 ** 40]),
    "numpy_scalars": ([1], "float32", list(np.float64([1.5, 2.25, 1 / 3]))),
    "nested_lists": ([2, 3], "float32",
                     [[[1, 2, 3], [4.5, 5, 6]], [[0, 0, 0.1], [7, 8, 9]]]),
    "label_lists": ([1], "int64", [[3], [1], [4]]),
    "label_arrays": ([1], "int64",
                     [np.array([i], "int64") for i in range(4)]),
    "float64_to_float32": ([4], "float32",
                           [_RNG.rand(4) * 1e3 for _ in range(6)]),
    "int32_to_int64": ([2], "int64",
                       [np.array([i, -i], "int32") for i in range(5)]),
    "float_to_int_truncates": ([2], "int64",
                               [np.array([1.7, -2.9]), np.array([0.5, 9.99])]),
    "ints_to_float32": ([3], "float32", [[1, 2, 3], [2 ** 24 + 1, 0, -1]]),
    "mixed_lists_and_arrays": ([3], "float32",
                               [[1, 2, 3], np.array([4., 5., 6.]),
                                (7, 8, 9)]),
    "scalars_fold_into_shape": ([2], "float32", [1., 2., 3., 4.]),
    "scalars_that_do_not_fold": ([2], "float32", [1., 2., 3.]),
    "flat_rows_of_a_shaped_field": ([1, 2, 2], "float32",
                                    [_RNG.rand(4).astype("float32")
                                     for _ in range(3)]),
    "rows_of_another_rank": ([4], "float32",
                             [_RNG.rand(2, 2).astype("float32")
                              for _ in range(3)]),
    "strided_views": ([3], "float32",
                      list(_RNG.rand(6, 4, 3).astype("float32")[:, 1])),
    "batch_of_one": ([3, 2], "float32", [_RNG.rand(3, 2)]),
    "batch_of_one_scalar": ([1], "int64", [5]),
    "empty_batch": ([3, 2], "float32", []),
    "empty_batch_of_labels": ([1], "int64", []),
    "zero_sized_rows": ([0], "float32", [np.zeros(0), np.zeros(0)]),
    "big_rows": ([32768], "float32", list(_BIG)),
    "two_big_rows_converted": ([2 ** 20], "float32",
                               [np.arange(2 ** 20, dtype="float64"),
                                np.ones(2 ** 20)]),
}


@pytest.mark.parametrize("as_iterable", [list, iter], ids=["list", "iter"])
@pytest.mark.parametrize("case", sorted(DENSE))
def test_a_dense_batch_has_the_parents_bytes_shape_and_dtype(case,
                                                             as_iterable):
    shape, dtype, rows = DENSE[case]
    feeder = _feeder(("v", shape, dtype), ("w", [1], "int64"))
    want = _parent(rows, shape, dtype)
    for _ in range(3):          # the third call writes into the first's array
        got = feeder.feed(as_iterable([(r, i) for i, r in enumerate(rows)]))
        assert list(got) == ["v", "w"]
        v = got["v"]
        assert type(v) is np.ndarray and v.flags.c_contiguous
        assert (v.shape, v.dtype) == (want.shape, want.dtype)
        assert v.tobytes() == want.tobytes()
        assert got["w"].tobytes() == _parent(range(len(rows)), [1],
                                             "int64").tobytes()
        del got, v


# name -> (shape, dtype, rows): np.array refuses them, and says why
REFUSED = {
    "rows_of_unequal_shape": ([3], "float32",
                              [np.zeros(3), np.zeros(4), np.zeros(3)]),
    "first_row_differs": ([3], "float32", [np.zeros(2), np.zeros(3)]),
    "unequal_at_the_last_row": ([32768], "float32",
                                list(_BIG[:63]) + [np.zeros(5)]),
    "a_row_that_would_broadcast": ([3], "float32",
                                   [np.zeros(3), np.zeros(1)]),
    "a_scalar_among_rows": ([3], "float32", [np.zeros(3), 1.0]),
    "ragged_inside_a_row": ([2], "float32", [[[1, 2], [3]], [[1, 2], [3]]]),
    "not_a_number": ([1], "float32", [1.0, "abc"]),
    "int_out_of_range": ([1], "int64", [1, 2 ** 70]),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_does_not_stack_raises_np_arrays_own_error(case):
    shape, dtype, rows = REFUSED[case]
    with pytest.raises(Exception) as want:
        _parent(rows, shape, dtype)
    feeder = _feeder(("v", shape, dtype))
    with pytest.raises(type(want.value)) as got:
        feeder.feed([(r,) for r in rows])
    assert str(got.value) == str(want.value)
    # and the feeder is as good as new
    ok = feeder.feed([(np.ones(shape, dtype),)] * 2)["v"]
    assert ok.tobytes() == np.ones([2] + shape, dtype).tobytes()


@pytest.mark.parametrize("fields", [1, 3])
def test_a_wrong_field_count_is_refused_as_before(fields):
    feeder = _feeder(("a", [2], "float32"), ("b", [1], "int64"))
    batch = [([1., 2.], 1), tuple([[1., 2.]] * fields)]
    with pytest.raises(ValueError, match="sample has %d fields, feed_list "
                                         "expects 2" % fields):
        feeder.feed(batch)


def test_lod_fields_are_built_as_before_and_never_staged():
    feeder = _feeder(("words", [1], "int64", 1), ("pair", [2], "float32", 2),
                     ("y", [1], "int64"))
    batch = [([1, 2, 3], [[[1., 2.]], [[3., 4.], [5., 6.]]], 0),
             ([4], [[[7., 8.]]], 1)]
    for _ in range(2):
        got = feeder.feed(batch)
        words, pair = got["words"], got["pair"]
        assert isinstance(words, LoDTensor) and isinstance(pair, LoDTensor)
        assert words.lod() == [[0, 3, 4]]
        assert np.asarray(words.numpy()).tobytes() == np.array(
            [[1], [2], [3], [4]], "int64").tobytes()
        assert pair.lod() == [[0, 2, 3], [0, 1, 3, 4]]
        assert np.asarray(pair.numpy()).tobytes() == np.array(
            [[1, 2], [3, 4], [5, 6], [7, 8]], "float32").tobytes()
        assert got["y"].tobytes() == np.array([[0], [1]], "int64").tobytes()
    assert feeder._staged[:2] == [[], []] and feeder._staged[2]


# -- ownership ----------------------------------------------------------------

ROWS, DIM = 6, 40


def _numbered(n):
    """Batch n as sample tuples, and as the arrays they should stack to."""
    x = (np.arange(ROWS * DIM, dtype="float32").reshape(ROWS, DIM)
         + 1000 * n)
    y = np.arange(ROWS, dtype="int64").reshape(ROWS, 1) + n
    return [(x[i].copy(), y[i].copy()) for i in range(ROWS)], {"x": x, "y": y}


def _spy_on_staging(feeder):
    """One entry per staging array taken: was it one handed out before."""
    again, take = [], feeder._staging

    def staging(field, shape, dtype):
        before = {(id(a), a.shape) for a in feeder._staged[field]}
        arr = take(field, shape, dtype)
        again.append((id(arr), arr.shape) in before)
        return arr
    feeder._staging = staging
    return again


@pytest.mark.parametrize("holding", ["dict", "device", "view", "nothing"])
def test_a_fed_batch_is_unchanged_while_anything_can_read_it(holding):
    feeder = _feeder(("x", [DIM], "float32"), ("y", [1], "int64"))
    again = _spy_on_staging(feeder)
    exe = pt.Executor(pt.CPUPlace())
    batch, want = _numbered(0)
    fed = feeder.feed(batch)
    dev = exe.prepare_feed(fed)
    kept = {"dict": fed, "device": dev, "nothing": None,
            "view": {"x": fed["x"][2:4, ::3], "y": memoryview(fed["y"])}
            }[holding]
    # batch 0's arrays, where something but the feeder still refers to them
    held_ids = list(map(id, fed.values())) if holding in ("dict",
                                                          "view") else []
    del fed, dev
    for n in range(1, 5):
        later, want_later = _numbered(n)
        fed_later = feeder.feed(later)
        assert not any(id(a) in held_ids for a in fed_later.values())
        dev_later = exe.prepare_feed(fed_later)
        jax.block_until_ready(dev_later)
        for name in want_later:     # what is uploaded is the batch itself
            assert np.array_equal(np.asarray(dev_later[name]),
                                  want_later[name])
        del fed_later, dev_later
    if holding == "view":
        assert np.array_equal(kept["x"], want["x"][2:4, ::3])
        assert np.array_equal(np.asarray(kept["y"]), want["y"])
    elif holding != "nothing":
        for name in want:
            assert np.array_equal(np.asarray(kept[name]), want[name])
    if holding == "nothing":
        # batch 0's arrays among them; and not one array a batch
        assert any(again[2:]), again
        assert all(len(h) <= 2 for h in feeder._staged)


def test_the_feeder_holds_what_is_in_flight_and_no_more():
    """And one spare of the batch's shape."""
    feeder = _feeder(("x", [DIM], "float32"))
    kept = [feeder.feed([(np.full(DIM, n),)] * ROWS) for n in range(5)]
    assert len(feeder._staged[0]) == 5
    assert len({k["x"].ctypes.data for k in kept}) == 5
    del kept[1:]
    # takes one, keeps one as the spare, drops 2
    one = feeder.feed([(np.full(DIM, 9),)] * ROWS)
    assert len(feeder._staged[0]) == 3
    assert np.array_equal(kept[0]["x"], np.zeros((ROWS, DIM), "float32"))
    assert np.array_equal(one["x"], np.full((ROWS, DIM), 9, "float32"))
    # another batch size is another array; the old size's goes when free
    small = feeder.feed([(np.full(DIM, 7),)] * 2)
    assert small["x"].shape == (2, DIM) and len(feeder._staged[0]) == 3
    del one
    feeder.feed([(np.full(DIM, 7),)] * 2)
    assert sorted(a.shape[0] for a in feeder._staged[0]) == [2, 2, ROWS]


def test_two_arrays_that_take_turns_survive_a_feed_that_finds_both_free():
    """Trainer's loop stacks batch n+1 while jax still refers to batch n's
    array, so two arrays take turns. jax lets go at its next device_put
    or at any pass of Python's collector: now and then a feed finds both
    free. With one of them let go, the feed after the next would have to
    map a fresh array (~150 ms for the benchmark's 154 MB batch)."""
    feeder = _feeder(("x", [DIM], "float32"))
    batch = [(np.zeros(DIM),)] * ROWS
    in_flight = feeder.feed(batch)["x"]         # batch n, "uploading"
    turns = {id(in_flight)}
    for n in range(8):
        fed = feeder.feed(batch)["x"]           # batch n+1 beside it
        turns.add(id(fed))
        assert fed is not in_flight and len(feeder._staged[0]) == 2
        if n == 3:
            # jax let go of batch n's array early, and the loop dropped
            # batch n+1's: the next feed finds both free
            del in_flight, fed
            in_flight = feeder.feed(batch)["x"]
            assert len(feeder._staged[0]) == 2  # one taken, one spare
            continue
        in_flight = fed                         # the older one is let go of
    assert len(turns) == 2                      # never a third array


# -- the training loops over it ---------------------------------------------

STEPS, BATCH = 8, 4


def _net():
    main, startup = pt.Program(), pt.Program()
    with unique_name.guard(), pt.program_guard(main, startup):
        x = layers.data("x", shape=[DIM], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(input=x, size=16, act="tanh")
        pred = layers.fc(input=h, size=1, act=None)
        cost = layers.mean(layers.square_error_cost(input=pred, label=y))
    return main, startup, cost, [x, y]


def _train_batches():
    rng = np.random.RandomState(11)
    xs = rng.rand(STEPS, BATCH, DIM).astype("float32")
    ys = rng.rand(STEPS, BATCH, 1).astype("float32")
    return xs, ys


@pytest.fixture(scope="module")
def by_hand_losses():
    """A bare Executor, strictly in turn, on feeds np.array built."""
    xs, ys = _train_batches()
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, _ = _net()
        with pt.program_guard(main, startup):
            pt.Momentum(0.05, momentum=0.9).minimize(cost)
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        return [float(np.asarray(exe.run(
            main, feed={"x": np.array(list(xs[n]), "float32"),
                        "y": np.array(list(ys[n]), "float32")},
            fetch_list=[cost])[0]).reshape(-1)[0]) for n in range(STEPS)]


@pytest.fixture(scope="module")
def by_hand_eval():
    """The forward ops alone on a bare Executor: the mean loss over the
    batches, parameters as the startup program left them."""
    xs, ys = _train_batches()
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, _ = _net()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        return sum(float(np.asarray(exe.run(
            main, feed={"x": np.array(list(xs[n]), "float32"),
                        "y": np.array(list(ys[n]), "float32")},
            fetch_list=[cost])[0]).reshape(-1)[0])
            for n in range(STEPS)) / STEPS


@pytest.mark.parametrize("loop", ["one_pass", "two_passes", "eval"])
def test_the_loops_train_on_the_bytes_a_bare_executor_is_fed(
        loop, by_hand_losses, by_hand_eval):
    xs, ys = _train_batches()
    passes = 2 if loop == "two_passes" else 1
    per_pass = STEPS // passes
    losses, started = [], []

    def reader():       # the second pass goes on where the first ended
        started.append(len(started))
        for n in range(started[-1] * per_pass,
                       (started[-1] + 1) * per_pass):
            yield [(xs[n, i], ys[n, i]) for i in range(BATCH)]

    def handler(e):
        if isinstance(e, pt.EndIteration):
            losses.append(float(e.cost))
    with pt.scope_guard(pt.Scope()):
        main, startup, cost, feeds = _net()
        trainer = pt.Trainer(cost=cost,
                             optimizer=pt.Momentum(0.05, momentum=0.9),
                             feed_list=feeds, place=pt.CPUPlace(),
                             main_program=main, startup_program=startup)
        again = _spy_on_staging(trainer.feeder)
        if loop == "eval":
            # Trainer.test hands the staging arrays to Executor.run itself
            assert trainer.test(reader) == [by_hand_eval]
        else:
            trainer.train(reader, num_passes=passes, event_handler=handler)
            assert losses == by_hand_losses
    assert len(again) == 2 * STEPS and any(again)
    if loop == "two_passes":
        # the arrays outlive the pass: its first batch takes used ones
        assert all(again[2 * per_pass:2 * per_pass + 2])


# -- two callers at once --------------------------------------------------------

def test_threads_feeding_one_feeder_at_once_get_their_own_batches():
    callers, rounds, rows, dim = 12, 12, 16, 2 ** 16       # x: 4 MB a batch
    feeder = _feeder(("x", [dim], "float32"), ("y", [1], "int64"))
    failures, done = [], []

    def caller(c):
        try:
            held = []
            for r in range(rounds):
                tag = c * 1000 + r
                got = feeder.feed([(np.full(dim, tag + i, "float64"),
                                    [tag + i]) for i in range(rows)])
                held.append((tag, got))
                if len(held) > 2:
                    held.pop(0)
                for t, g in held:       # this one and the two before it
                    want = t + np.arange(rows)
                    if not (np.array_equal(g["x"][:, 0], want)
                            and np.array_equal(g["x"][:, -1], want)
                            and np.array_equal(g["y"][:, 0], want)):
                        failures.append((c, r, t))
            done.append(c)
        except Exception as e:          # read by the assertion below
            failures.append((c, repr(e)))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(callers)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 120
        for t in threads:
            t.join(timeout=max(deadline - time.monotonic(), 0.1))
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not failures, failures[:5]
    assert sorted(done) == list(range(callers))
    # each caller held three batches at most
    assert len(feeder._staged[0]) <= 3 * callers

"""The readers of the program's set-up record (``setup.*``): on a hand-made
record, and through the driver at a tiny size on the CPU."""
import time

import pytest

import tiny
from tiny import harness

from chipbench import setup_trace

METRICS = ["setup.program_s", "setup.import_s", "setup.build_s",
           "setup.init_s", "setup.trace_s", "setup.lower_s",
           "setup.xla_compile_s", "setup.cache_read_s", "setup.executables"]
PARTS = METRICS[1:8]

# (name, start, end, parent, args) as profiler.setup_spans() gives them: a
# nested jax phase is recorded before the phase that adopted it, so its
# parent's index is the larger
RECORD = [
    ("import", 0.0, 2.0, None, {}),                                # 0
    ("jax.trace", 0.5, 0.7, 0, {"fun": "x"}),                      # 1
    ("build", 3.0, 3.5, None, {}),                                 # 2
    ("init", 4.0, 6.0, None, {}),                                  # 3
    ("compile", 4.5, 5.5, 3, {"module": "jit_s", "why": "first_call"}),
    ("jax.trace", 4.65, 4.75, 6, {}),                              # 5
    ("jax.trace", 4.6, 4.9, 4, {}),                                # 6
    ("jax.lower", 4.9, 5.0, 4, {}),                                # 7
    ("cache.read", 5.05, 5.15, 9, {}),                             # 8
    ("xla.compile", 5.0, 5.4, 4, {}),                              # 9
    ("jax.trace", 6.5, 6.6, None, {"fun": "norm"}),                # 10
    ("compile", 7.0, 8.0, None, {"module": "jit_m", "why": "fit_check"}),
    ("xla.compile", 7.1, 7.9, 11, {}),                             # 12
    ("compile", 8.0, 8.5, None, {"module": "jit_m", "why": "first_call"}),
    # after the window opened at 10: device_scopes() lowering again
    ("compile", 11.0, 13.0, None, {"module": "jit_x", "why": "first_call"}),
    ("jax.trace", 11.5, 12.5, 14, {}),
    ("build", 9.5, None, None, {}),                                # open
]
T0 = 10.0
WANT = {"setup.program_s": 2.0 + 0.5 + 2.0 + 1.0 + 0.5,
        "setup.import_s": 1.8, "setup.build_s": 0.5, "setup.init_s": 1.0,
        "setup.trace_s": 0.2 + 0.2 + 0.1, "setup.lower_s": 0.1,
        "setup.xla_compile_s": 0.3 + 0.8, "setup.cache_read_s": 0.1,
        "setup.executables": 3}


def _read(name, ctx):
    return harness.load_module("layer_metrics", name + ".py").read(ctx)


@pytest.fixture
def record(monkeypatch):
    from paddle_tpu import profiler
    monkeypatch.setattr(profiler, "setup_spans", lambda: list(RECORD))


@pytest.mark.parametrize("name", METRICS)
def test_each_reader_on_a_hand_made_record(record, name):
    got = _read(name, {"step_ends": [T0, 11.0, 12.0]})
    assert got == pytest.approx(WANT[name])


def test_the_parts_and_compiles_own_time_partition_the_programs():
    view = setup_trace.reduce_setup(RECORD, T0)
    assert sum(WANT[n] for n in PARTS) + view["self_s"]["compile"] \
        == pytest.approx(view["program_s"])
    assert view["self_s"]["compile"] == pytest.approx(0.2 + 0.2 + 0.5)
    assert view["outside_s"] == pytest.approx({"jax.trace": 0.1})
    assert view["compiles"] == {("jit_s", "first_call"): 1,
                                ("jit_m", "fit_check"): 1,
                                ("jit_m", "first_call"): 1}


def test_what_ends_after_the_window_opened_is_left_out():
    early = setup_trace.reduce_setup(RECORD, 7.5)
    assert early["executables"] == 1
    assert early["program_s"] == pytest.approx(2.0 + 0.5 + 2.0)
    late = setup_trace.reduce_setup(RECORD, 20.0)
    assert late["executables"] == 4
    assert late["self_s"]["jax.trace"] == pytest.approx(0.5 + 1.0)


@pytest.mark.parametrize("name", METRICS)
def test_silent_on_a_program_without_a_setup_record(monkeypatch, name):
    from paddle_tpu import profiler
    monkeypatch.delattr(profiler, "setup_spans")
    assert _read(name, {"step_ends": [T0]}) is None


def test_silent_without_a_window(record):
    assert _read("setup.program_s", {}) is None


@pytest.fixture(scope="module")
def cpu_run():
    """The tiny cell, traced, through the driver on the CPU, with the
    set-up record's length where the window opened and where it closed."""
    import jax
    from paddle_tpu import profiler
    harness.setup_compile_cache()
    driver = harness.load_module("drivers", "train.py")
    peaks = harness.peaks_for
    harness.peaks_for = lambda kind: {"flops_bf16": 1e12}
    try:
        t_start = time.monotonic()
        res = driver.run(tiny.train_cell(), 4343, 1.0, True,
                         jax.devices()[:1], t_start)
    finally:
        harness.peaks_for = peaks
    assert res["correct"]
    return res["ctx"], res["metrics"]["setup_s"], profiler.setup_spans()


def test_on_the_cpu_every_reader_reads_and_the_parts_fit(cpu_run):
    ctx, setup_s, _rec = cpu_run
    got = {n: _read(n, dict(ctx)) for n in METRICS}
    assert all(v is not None for v in got.values()), got
    assert got["setup.executables"] >= 2        # startup and main step
    assert got["setup.trace_s"] > 0 and got["setup.xla_compile_s"] > 0
    assert sum(got[n] for n in PARTS) <= got["setup.program_s"] + 1e-9
    assert got["setup.program_s"] <= setup_s


def test_on_the_cpu_nothing_is_recorded_inside_the_window(cpu_run):
    ctx, _setup_s, rec = cpu_run
    t0, t1 = ctx["step_ends"][0], ctx["step_ends"][-1]
    assert not [r for r in rec if t0 <= r[1] <= t1 or (
        r[2] is not None and t0 <= r[2] <= t1)]

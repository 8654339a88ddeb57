"""The reader of the program's own spans and device scopes: on hand-made
data, on the recorded slice of one chip run (testdata/
train_program_trace.json: the first 700 ms of a traced window of
resnet50-train-trainer on a TPU v5 lite, with the program's spans and the
scopes of the instructions that ran; its window span and its first step's
``train_step`` began before the cut), and through the driver at a tiny size
on the CPU, where only the host's spans exist."""
import contextlib
import copy
import json
import os
import time

import pytest

import tiny
from tiny import harness

from chipbench import program_trace as pt_
from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                    "testdata", "train_program_trace.json")
NEW = ["train.feed_ms", "train.entry_self_ms", "train.upload_ms",
       "train.dispatch_ms", "train.fetch_wait_ms", "train.fwd_ms",
       "train.bwd_ms", "train.update_ms", "train.conv_ms",
       "train.unscoped_device_pct", "train.idle_feed_pct",
       "train.idle_upload_pct", "train.idle_run_other_pct",
       "train.idle_unspanned_pct"]
HOST_ONLY = NEW[:5]


def _read(name, ctx):
    return harness.load_module("layer_metrics", name + ".py").read(ctx)


def test_intervals():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (45, 60)]
    assert pt_.subtract(a, b) == [(0, 5), (25, 30), (40, 45)]
    assert pt_.overlap(a, b) == 5 + 5 + 5
    assert pt_.subtract(a, []) == a and pt_.overlap(a, []) == 0
    assert pt_.instruction_name("%fusion.51 = bf16[2]{0} fusion(...)") \
        == "fusion.51"
    assert pt_.instruction_name("fusion.51 (tuple) kOutput") == "fusion.51"


def _hand_made():
    spans = [("train_step", 0, 1000, {"step_num": 0}),
             ("feed", 100, 300, {"rows": 2}),
             ("run", 450, 500, {"program": 1}),
             ("upload", 460, 100, {"bytes": 8}),
             ("dispatch", 570, 30, {}),
             ("fetch", 610, 330, {}),
             ("train_step", 1000, 5, {"step_num": 1, "end_of_pass": 1}),
             ("feed", 5000, 10, {})]               # outside the window
    ops = [("%fusion.1 = f32[2] fusion()", 600, 100),     # forward/conv2d
           ("%while.2 = () while()", 700, 200),           # backward parent
           ("%fusion.3 = f32[2] fusion()", 720, 50),      # update child
           ("%copy.4 = f32[2] copy()", 900, 20),          # unscoped
           ("%fusion.1 = f32[2] fusion()", 930, 10),      # no module: absent
           ("%fusion.9 = f32[2] fusion()", 1990, 20)]     # cut by the window
    modules = [("jit_step_a(17)", 590, 340), ("jit_step_b(3)", 1980, 50)]
    scopes = {"jit_step_a": {"fusion.1": "forward/conv2d",
                             "while.2": "backward/while_grad",
                             "fusion.3": "update/momentum",
                             "copy.4": "unscoped"},
              "jit_step_b": {"fusion.9": "backward/conv2d_grad"}}
    return spans, ops, modules, scopes


def test_both_splits_are_partitions_on_hand_made_data():
    spans, ops, modules, scopes = _hand_made()
    window = (0, 2000)
    busy = tr.merge((s, s + d) for _n, s, d in ops)
    view = pt_.reduce_window(spans, ops, modules, scopes, window, busy)
    assert view["span_ms"]["train_step"] == [1000 / 1e6]
    assert view["span_ms"]["feed"] == [300 / 1e6]
    assert view["entry_self_ms"] == [(1000 - 300 - 500) / 1e6]
    assert view["scope_ns"] == {
        "forward/conv2d": 100, "backward/while_grad": 150,
        "update/momentum": 50, "backward/conv2d_grad": 10,
        "unscoped": 20 + 10}
    assert view["unscoped_ns"] == {"copy.4": 20, "fusion.1": 10}
    busy_ns = sum(min(e, 2000) - s for s, e in busy)
    assert sum(view["scope_ns"].values()) == busy_ns == 340
    # idle: 0-600 and 920-930, 940-1990; feed 100-400, upload 460-560,
    # run 450-950 less upload
    assert view["idle_ns"] == {"feed": 300, "upload": 100,
                               "run_other": 10 + 40 + 10 + 10,
                               "unspanned": 2000 - 340 - 300 - 100 - 70}
    assert sum(view["idle_ns"].values()) == 2000 - busy_ns


def _slice():
    with open(DATA) as f:
        rec = json.load(f)
    red = tr.reduce_planes(rec["planes"])
    # the slice holds two whole steps and the head of a third: the window
    # closes with the second step's span
    second = min((s, s + d) for n, s, d, _a in rec["spans"]
                 if n == "train_step")
    window = (tr.window_of(red)[0], second[1])
    ctx = {"reduction": red, "window_ns": window,
           "window_s": (window[1] - window[0]) / 1e9, "steps": 2,
           "trace_reduce": tr}
    ops, modules = pt_.device_lines(rec["planes"])
    ctx["program_trace"] = pt_.reduce_window(
        rec["spans"], ops, modules, rec["scopes"], window,
        red["devices"][0]["busy"])
    return rec, ctx


def test_recorded_slice_sums_to_the_accepted_metrics():
    """fwd + bwd + update + unscoped = train.device_step_ms (1%); the four
    idle shares = train.device_idle_pct (0.5 points)."""
    _rec, ctx = _slice()
    got = {n: _read(n, ctx) for n in NEW}
    assert all(v is not None for v in got.values()), got
    step_ms = _read("train.device_step_ms", ctx)
    unscoped_ms = pt_.phase_ms(ctx, "unscoped")
    parts = got["train.fwd_ms"] + got["train.bwd_ms"] \
        + got["train.update_ms"] + unscoped_ms
    assert abs(parts - step_ms) / step_ms < 0.01, (parts, step_ms)
    idle = _read("train.device_idle_pct", ctx)
    four = sum(got["train.idle_%s_pct" % k] for k in pt_.IDLE_KINDS)
    assert abs(four - idle) < 0.5, (four, idle)
    # what the slice shows of this cell: the feed starves the device, the
    # backward pass is the longer one, the optimizer's is short
    assert got["train.idle_feed_pct"] > got["train.idle_upload_pct"] > 0
    assert got["train.bwd_ms"] > got["train.fwd_ms"] > got["train.update_ms"]
    assert 0 < got["train.conv_ms"] < got["train.fwd_ms"] + got["train.bwd_ms"]
    assert got["train.unscoped_device_pct"] == pytest.approx(
        100 * unscoped_ms / parts)
    assert got["train.feed_ms"] > got["train.upload_ms"] > 0


@pytest.mark.parametrize("gone", ["feed", "upload", "run"])
def test_a_span_taken_out_of_the_slice_moves_its_idle_time(gone):
    """The sums hold by construction; what a missing span breaks is the
    attribution, and that shows: its share goes to the span around it."""
    rec, ctx = _slice()
    whole = ctx["program_trace"]["idle_ns"]
    ops, modules = pt_.device_lines(rec["planes"])
    spans = [s for s in rec["spans"] if s[0] != gone]
    cut = pt_.reduce_window(spans, ops, modules, rec["scopes"],
                            ctx["window_ns"],
                            ctx["reduction"]["devices"][0]["busy"])
    assert sum(cut["idle_ns"].values()) == sum(whole.values())
    kind = {"run": "run_other"}.get(gone, gone)
    assert cut["idle_ns"][kind] == 0 < whole[kind]
    heir = {"feed": "unspanned", "upload": "run_other",
            "run": "unspanned"}[gone]
    assert cut["idle_ns"][heir] == whole[heir] + whole[kind]
    assert gone not in cut["span_ms"]


def test_the_sum_checks_fail_loudly_on_a_slice_with_device_ops_taken_out():
    """An instruction missing from the table is unscoped, not dropped; ops
    missing from the trace break the sum against the accepted metric."""
    rec, ctx = _slice()
    ops, modules = pt_.device_lines(rec["planes"])
    busy = ctx["reduction"]["devices"][0]["busy"]
    scopes = {m: {k: v for k, v in t.items() if not k.startswith("fusion")}
              for m, t in rec["scopes"].items()}
    less = pt_.reduce_window(rec["spans"], ops, modules, scopes,
                             ctx["window_ns"], busy)
    whole = ctx["program_trace"]["scope_ns"]
    assert sum(less["scope_ns"].values()) == sum(whole.values())
    assert less["scope_ns"]["unscoped"] > whole["unscoped"]
    fewer = [o for o in ops if not o[0].startswith("fusion")]
    ctx["program_trace"] = pt_.reduce_window(
        rec["spans"], fewer, modules, rec["scopes"], ctx["window_ns"], busy)
    parts = sum(pt_.phase_ms(ctx, p) for p in pt_.PHASES + ("unscoped",))
    step_ms = _read("train.device_step_ms", ctx)
    assert abs(parts - step_ms) / step_ms > 0.01


@pytest.fixture(scope="module")
def cpu_run():
    """The tiny cell, traced, through the driver on the CPU."""
    import jax
    harness.setup_compile_cache()
    driver = harness.load_module("drivers", "train.py")
    peaks = harness.peaks_for
    harness.peaks_for = lambda kind: {"flops_bf16": 1e12}
    try:
        res = driver.run(tiny.train_cell(), 4242, 1.0, True,
                         jax.devices()[:1], time.monotonic())
    finally:
        harness.peaks_for = peaks
    assert res["correct"] and res["attempted"] >= 2
    return res["ctx"]


def _fresh(ctx):
    ctx = copy.copy(ctx)
    ctx.pop("program_trace", None)
    return ctx


def test_on_the_cpu_the_host_spans_read_and_the_device_metrics_are_silent(
        cpu_run):
    ctx = _fresh(cpu_run)
    got = {n: _read(n, ctx) for n in NEW}
    assert {n for n, v in got.items() if v is not None} == set(HOST_ONLY)
    assert all(got[n] > 0 for n in HOST_ONLY)
    view = ctx["program_trace"]
    # the step whose span opened before the session began is not recorded
    assert len(view["span_ms"]["train_step"]) == ctx["steps"] - 1
    assert len(view["span_ms"]["feed"]) == ctx["steps"]
    assert "compile" not in view["span_ms"]
    wall = _read("train.step_wall_ms", ctx)
    assert got["train.feed_ms"] + got["train.upload_ms"] \
        + got["train.dispatch_ms"] + got["train.fetch_wait_ms"] < wall


def test_a_program_from_before_its_spans_is_silent(cpu_run, monkeypatch):
    from paddle_tpu import profiler
    monkeypatch.delattr(profiler, "step_span")
    ctx = _fresh(cpu_run)
    assert [_read(n, ctx) for n in NEW] == [None] * len(NEW)


def test_steps_and_no_train_step_span_is_an_error(cpu_run, monkeypatch):
    monkeypatch.setattr(
        pt_, "read_xplane",
        lambda path, read=pt_.read_xplane: (
            [s for s in read(path)[0] if s[0] != "train_step"], [], []))
    with pytest.raises(RuntimeError, match="no paddle_tpu/train_step span"):
        _read("train.feed_ms", _fresh(cpu_run))
    with contextlib.suppress(KeyError):
        del cpu_run["program_trace"]

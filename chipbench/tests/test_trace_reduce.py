"""The trace reduction: on hand-made planes, and on the recorded trace cut
from one chip run (testdata/train_trace.json: the first steps of
resnet50-train-trainer on a TPU v5 lite)."""
import json
import os

import pytest

from tiny import harness  # noqa: F401

from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                    "testdata", "train_trace.json")


def _planes():
    ops = [("fusion.1", 100, 50),
           ("while.2", 200, 100),                # parent of the next two
           ("fusion.3", 210, 30),
           ("fusion.1", 250, 40),
           ("copy.4", 400, 20)]
    mods = [("jit_step(17)", 100, 200), ("jit_step(17)", 400, 20)]
    host = [("chipbench/window", 0, 500),
            ("chipbench/feed", 300, 90), ("other", 0, 1000)]
    return [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", mods),
                               ("Steps", [("0", 0, 10)])]),
            ("/host:CPU", [("thread-1", host)])]


def test_busy_is_the_union_and_op_time_is_self_time():
    red = tr.reduce_planes(_planes())
    dev = red["devices"][0]
    assert dev["busy"] == [(100, 150), (200, 300), (400, 420)]
    assert tr.busy_seconds(red) == 170e-9
    assert tr.busy_seconds(red, within=(120, 410)) == (30 + 100 + 10) * 1e-9
    assert dev["ops"]["while.2"] == (1, 30)      # 100 - 30 - 40
    assert dev["ops"]["fusion.1"] == (2, 90)
    assert dev["modules"]["jit_step"][:2] == (2, 220)


def test_spans_window_and_idle_gaps():
    red = tr.reduce_planes(_planes())
    assert [s[0] for s in red["spans"]] == ["window", "feed"]
    assert tr.window_of(red) == (0, 500)
    gaps, by_name = tr.idle_gaps(red, (0, 500))
    # 0-100, 150-200, 300-400 (covered by feed 300-390), 420-500
    assert sorted(g[1] for g in gaps) == [50e-9, 80e-9, 100e-9, 100e-9]
    assert by_name["feed"] == 100e-9
    assert abs(by_name["no_span"] - 230e-9) < 1e-15
    assert tr.top_ops(red, top=1) == [["fusion.1", 90e-9]]


def test_recorded_trace_from_the_chip():
    with open(DATA) as f:
        planes = json.load(f)
    red = tr.reduce_planes(planes)
    assert len(red["devices"]) == 1
    dev = red["devices"][0]
    busy = tr.busy_seconds(red)
    lo = min(s for s, _ in dev["busy"])
    hi = max(e for _, e in dev["busy"])
    assert 0 < busy <= (hi - lo) / 1e9
    # self times add up to the busy time (nesting counted once)
    total = sum(ns for _c, ns in dev["ops"].values()) / 1e9
    assert abs(total - busy) / busy < 1e-6
    assert dev["modules"]["jit_one_step"][0] >= 1      # the step program
    # the benchmark's own spans are on the same clock as the device
    names = {s[0] for s in red["spans"]}
    assert {"feed", "run"} <= names


def test_programs_are_told_apart_by_launch_order_or_it_is_an_error():
    mods = [("jit_fn(1)", 0, 10), ("jit_fn(2)", 20, 30), ("jit_fn(1)", 60, 10)]
    red = tr.reduce_planes([("/device:TPU:0", [("XLA Modules", mods)])])
    launches = [("decode", 3, 40), ("prefill", 100, 0), ("decode", 4, 50)]
    got = tr.programs_by_launch(red, launches)
    assert got["decode"] == {"seconds": 20e-9, "count": 2, "size": 7,
                             "live": 90}
    assert got["prefill"]["seconds"] == 30e-9 and got["prefill"]["size"] == 100
    with pytest.raises(ValueError, match="launched 2 programs"):
        tr.programs_by_launch(red, launches[:2])

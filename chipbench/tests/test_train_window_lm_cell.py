"""The window / full language-model training cell at a tiny size on the CPU,
through ``drivers/train_lm.py`` as it is: the plain reference agrees with the
program; the control and the planted faults come out as not correct through
the driver's own comparison, the stated recipe as correct; the four
``train.gqa_*`` readers on a recorded slice."""
import json
import time

import pytest

import tiny_window_lm
from tiny import harness

CELL = "trinity-mini-train-ep8share-s8192"


@pytest.fixture(scope="module")
def driver():
    harness.setup_compile_cache()
    return harness.load_module("drivers", "train_lm.py")


def test_reference_agrees_with_the_program(driver):
    import jax
    res = driver.run(tiny_window_lm.train_window_lm_cell(), 12345678901, 0.5,
                     False, jax.devices()[:1], time.monotonic())
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    # f32 on the CPU: far inside the limits that bf16 on the chip needs
    assert res["compared"]["grad_norm_gap"]["value"] < 5e-3
    assert res["compared"]["grad_dir_gap"]["value"] < 5e-3
    assert res["compared"]["load_gap"]["value"] == 0.0
    assert res["notes"]["loss_gap"] < 1e-4
    assert res["notes"]["rows_per_held_expert_expected"] == 48 * 2 / 8.0
    assert sum(res["notes"]["rows_held"]) > 0


def test_control_and_faults_put_in_the_programs_place_are_not_correct(
        driver):
    """What calibrate.py runs on the chip at the cell's own size. A batch
    of one row: its half is the first half of the row's positions."""
    out = driver.control_readings(tiny_window_lm.train_window_lm_cell(), 77,
                                  None, look=True)
    for name in ("control_fp8", "fault_half_batch", "fault_state_unchanged",
                 "fault_window_ignored", "fault_gate_left_out",
                 "fault_selection_without_bias"):
        assert not out[name]["correct"], (name, out[name]["compared"])
    assert out["fault_state_unchanged"]["numbers"]["delta_norm_gap"] == 1.0
    assert not out["fault_selection_without_bias"]["compared"][
        "load_gap"]["ok"]
    # a gradient that points elsewhere at nearly the same size
    for name in ("fault_rotary_on_full_layers", "fault_window_ignored"):
        n = out[name]["numbers"]
        assert n["grad_dir_gap"] > 3 * n["grad_norm_gap"] > 0, (name, n)
    assert out["stated_bf16"]["correct"], out["stated_bf16"]["compared"]


def test_the_new_readers_on_a_recorded_slice():
    view = {"scope_ns": {"forward/grouped_attention/attn_window": 20e6,
                         "backward/grouped_attention/attn_window": 60e6,
                         "forward/grouped_attention/attn_full": 10e6,
                         "backward/grouped_attention/attn_full": 30e6,
                         "forward/grouped_attention/proj": 30e6,
                         "forward/latent_attention/attn": 7e6,
                         "update/adam": 20e6, "unscoped": 9e6},
            "unscoped_ns": {}}
    ctx = {"program_trace": view, "steps": 2,
           "peaks": {"flops_bf16": 197e12},
           "attention_flops_per_step": {"window": 2.886e12,
                                        "full": 1.649e12}}
    read = lambda name, c=ctx: harness.load_module(
        "layer_metrics", name + ".py").read(c)
    assert read("train.gqa_attn_ms") == pytest.approx(60.0)
    assert read("train.gqa_window_attn_ms") == pytest.approx(40.0)
    assert read("train.gqa_attn_roofline_pct") == pytest.approx(
        100 * 4.535e12 / (0.060 * 197e12))
    assert read("train.gqa_window_attn_roofline_pct") == pytest.approx(
        100 * 2.886e12 / (0.040 * 197e12))
    # a program without these scopes (the parent), or another network's
    # single count under the same key: silence, no error
    silent = dict(ctx, program_trace={"scope_ns": {"forward/conv2d": 1e6}})
    other = dict(ctx, attention_flops_per_step=6.18e12)
    for name in ("train.gqa_attn_ms", "train.gqa_attn_roofline_pct",
                 "train.gqa_window_attn_ms",
                 "train.gqa_window_attn_roofline_pct"):
        assert read(name, silent) is None
        assert read(name, {}) is None
        if name.endswith("_pct"):
            assert read(name, other) is None


def test_the_cell_reports_what_benchmark_json_says():
    cell = harness.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert {"train.gqa_attn_ms", "train.gqa_attn_roofline_pct",
            "train.gqa_window_attn_ms", "train.gqa_window_attn_roofline_pct",
            "train.moe_route_ms", "train.moe_experts_ms",
            "train.moe_experts_roofline_pct", "train.step_mfu_pct",
            "train.update_ms"} <= names
    assert not {"train.conv_ms", "train.attn_ms",
                "train.attn_roofline_pct"} & names
    assert [m["name"] for m in cell["end_to_end"]] == [
        "train_images_per_s", "setup_s"]
    config = cell["config"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = [json.loads(l) for l in f if '"Trinity-Mini"' in l][0]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["num_experts"] == 128 and config["vocab_size"] == 200192
    assert cell["traffic"]["batch"] == 1 and cell["traffic"]["seq"] == 8192
    # the kanana cell's own lists are as they were
    other = {m["name"] for m in harness.load_cell(
        "kanana2-train-ep8share-s4096")["per_layer"]}
    assert not {n for n in other if n.startswith("train.gqa_")}

"""The prerouted language-model training cell at a tiny size on the CPU,
through ``drivers/train_lm.py`` as it is: the plain reference agrees with the
program; the control and the planted faults come out as not correct through
the driver's own comparison, the stated recipe as correct; the two new
``train.gqa_full_*`` readers on a recorded slice."""
import time

import pytest

import tiny_prerouted_lm
from tiny import harness

CELL = "smallthinker-train-ep8share-s16384"


@pytest.fixture(scope="module")
def driver():
    harness.setup_compile_cache()
    return harness.load_module("drivers", "train_lm.py")


def test_reference_agrees_with_the_program(driver):
    import jax
    res = driver.run(tiny_prerouted_lm.train_prerouted_lm_cell(),
                     12345678901, 0.5, False, jax.devices()[:1],
                     time.monotonic())
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    # f32 on the CPU: far inside the limits that bf16 on the chip needs
    assert res["compared"]["grad_norm_gap"]["value"] < 5e-3
    assert res["compared"]["grad_dir_gap"]["value"] < 5e-3
    assert res["compared"]["load_gap"]["value"] == 0.0
    assert res["notes"]["loss_gap"] < 1e-4
    assert res["notes"]["rows_per_held_expert_expected"] == 48 * 3 / 8.0
    assert sum(res["notes"]["rows_held"]) > 0


def test_control_and_faults_put_in_the_programs_place_are_not_correct(
        driver):
    """What calibrate.py runs on the chip at the cell's own size."""
    out = driver.control_readings(
        tiny_prerouted_lm.train_prerouted_lm_cell(), 77, None, look=True)
    for name in ("control_fp8", "fault_half_batch", "fault_state_unchanged",
                 "fault_window_ignored", "fault_rotary_on_global_layers",
                 "fault_router_after_attention", "fault_silu_for_relu",
                 "fault_weights_not_renormalised"):
        assert not out[name]["correct"], (name, out[name]["compared"])
    assert out["fault_state_unchanged"]["numbers"]["delta_norm_gap"] == 1.0
    assert not out["fault_router_after_attention"]["compared"][
        "load_gap"]["ok"]
    assert out["stated_bf16"]["correct"], out["stated_bf16"]["compared"]


def test_the_new_readers_on_a_recorded_slice():
    view = {"scope_ns": {"forward/grouped_attention/attn_window": 20e6,
                         "backward/grouped_attention/attn_window": 60e6,
                         "forward/grouped_attention/attn_full": 10e6,
                         "backward/grouped_attention/attn_full": 30e6,
                         "update/adam": 20e6},
            "unscoped_ns": {}}
    ctx = {"program_trace": view, "steps": 2,
           "peaks": {"flops_bf16": 197e12},
           "attention_flops_per_step": {"window": 7.58e12,
                                        "full": 5.77e12}}
    read = lambda name, c=ctx: harness.load_module(
        "layer_metrics", name + ".py").read(c)
    assert read("train.gqa_full_attn_ms") == pytest.approx(20.0)
    assert read("train.gqa_full_attn_roofline_pct") == pytest.approx(
        100 * 5.77e12 / (0.020 * 197e12))
    silent = dict(ctx, program_trace={"scope_ns": {"forward/conv2d": 1e6}})
    for name in ("train.gqa_full_attn_ms", "train.gqa_full_attn_roofline_pct"):
        assert read(name, silent) is None
        assert read(name, {}) is None


def test_the_cell_reports_what_benchmark_json_says():
    cell = harness.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert {"train.gqa_attn_ms", "train.gqa_attn_roofline_pct",
            "train.gqa_window_attn_ms", "train.gqa_window_attn_roofline_pct",
            "train.gqa_full_attn_ms", "train.gqa_full_attn_roofline_pct",
            "train.moe_route_ms", "train.moe_experts_ms",
            "train.moe_experts_roofline_pct", "train.step_mfu_pct",
            "setup.program_s"} <= names
    assert not {"train.conv_ms", "train.attn_ms",
                "train.attn_roofline_pct"} & names
    assert [m["name"] for m in cell["end_to_end"]] == [
        "train_images_per_s", "setup_s"]
    config = cell["config"]
    assert config["moe_num_primary_experts"] == 64
    assert config["vocab_size"] == 151936
    assert cell["traffic"]["batch"] == 1 and cell["traffic"]["seq"] == 16384
    for other in ("kanana2-train-ep8share-s4096",
                  "trinity-mini-train-ep8share-s8192"):
        assert not {m["name"] for m in harness.load_cell(other)["per_layer"]
                    if m["name"].startswith("train.gqa_full_")}

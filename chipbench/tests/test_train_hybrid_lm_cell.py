"""The hybrid language-model training cell at a tiny size on the CPU,
through ``drivers/train_lm.py`` as it is: the plain reference agrees with
the program; the control and the planted faults come out as not correct
through the driver's own comparison, the stated recipe as correct; the
three ``train.ssm_*`` readers on a recorded slice."""
import time

import pytest

import tiny_hybrid_lm
from tiny import harness

CELL = "nemotron3-nano-train-ep16share-s8192"


@pytest.fixture(scope="module")
def driver():
    harness.setup_compile_cache()
    return harness.load_module("drivers", "train_lm.py")


def test_reference_agrees_with_the_program(driver):
    import jax
    res = driver.run(tiny_hybrid_lm.train_hybrid_lm_cell(), 12345678901,
                     0.5, False, jax.devices()[:1], time.monotonic())
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    # f32 on the CPU: far inside the limits that bf16 on the chip needs
    assert res["compared"]["grad_norm_gap"]["value"] < 5e-3
    assert res["compared"]["grad_dir_gap"]["value"] < 5e-3
    assert res["compared"]["load_gap"]["value"] == 0.0
    assert res["notes"]["loss_gap"] < 1e-4
    assert res["notes"]["rows_per_held_expert_expected"] == 32 * 3 / 16.0
    assert sum(res["notes"]["rows_held"]) > 0


def test_control_and_faults_put_in_the_programs_place_are_not_correct(
        driver):
    """What calibrate.py runs on the chip at the cell's own size."""
    out = driver.control_readings(
        tiny_hybrid_lm.train_hybrid_lm_cell(), 77, None, look=True)
    for name in ("control_fp8", "fault_half_batch", "fault_state_unchanged",
                 "fault_state_not_carried", "fault_norm_before_gate",
                 "fault_norm_over_all_channels", "fault_relu_for_relu2",
                 "fault_no_routed_scaling", "fault_selection_without_bias"):
        assert not out[name]["correct"], (name, out[name]["compared"])
    assert out["fault_state_unchanged"]["numbers"]["delta_norm_gap"] == 1.0
    assert out["stated_bf16"]["correct"], out["stated_bf16"]["compared"]


def test_the_new_readers_on_a_recorded_slice():
    view = {"scope_ns": {"forward/ssm_mixer/proj": 20e6,
                         "forward/ssm_mixer/scan": 4e6,
                         "backward/ssm_mixer/scan": 8e6,
                         "backward/ssm_mixer/norm": 6e6,
                         "forward/moe_ffn/route": 5e6,
                         "update/adam": 20e6},
            "unscoped_ns": {}}
    ctx = {"program_trace": view, "steps": 2,
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "attention_flops_per_step": {"window": 0, "full": 1.6e12,
                                        "ssm_scan": 3.35e11,
                                        "ssm_scan_bytes": 1.757e9}}
    read = lambda name, c=ctx: harness.load_module(
        "layer_metrics", name + ".py").read(c)
    assert read("train.ssm_mixer_ms") == pytest.approx(19.0)
    assert read("train.ssm_scan_ms") == pytest.approx(6.0)
    assert read("train.ssm_scan_roofline_pct") == pytest.approx(
        100 * (1.757e9 / 819e9) / 0.006)
    silent = dict(ctx, program_trace={"scope_ns": {"forward/conv2d": 1e6}})
    for name in ("train.ssm_mixer_ms", "train.ssm_scan_ms",
                 "train.ssm_scan_roofline_pct"):
        assert read(name, silent) is None
        assert read(name, {}) is None
    # a network that counts no scan
    other = dict(ctx, attention_flops_per_step={"window": 1.0, "full": 1.0})
    assert read("train.ssm_scan_roofline_pct", other) is None


def test_the_cell_reports_what_benchmark_json_says():
    cell = harness.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert {"train.ssm_mixer_ms", "train.ssm_scan_ms",
            "train.ssm_scan_roofline_pct", "train.gqa_attn_ms",
            "train.gqa_attn_roofline_pct", "train.moe_route_ms",
            "train.moe_experts_ms", "train.moe_experts_roofline_pct",
            "train.step_mfu_pct", "train.device_step_ms",
            "setup.program_s", "setup.xla_compile_s"} <= names
    assert not {"train.conv_ms", "train.attn_ms", "train.attn_roofline_pct",
                "train.gqa_window_attn_ms", "train.gqa_full_attn_ms"} & names
    assert [m["name"] for m in cell["end_to_end"]] == [
        "train_images_per_s", "setup_s"]
    assert cell["traffic"]["batch"] == 1 and cell["traffic"]["seq"] == 8192
    for other in ("kanana2-train-ep8share-s4096",
                  "trinity-mini-train-ep8share-s8192",
                  "smallthinker-train-ep8share-s16384"):
        assert not {m["name"] for m in harness.load_cell(other)["per_layer"]
                    if m["name"].startswith("train.ssm_")}

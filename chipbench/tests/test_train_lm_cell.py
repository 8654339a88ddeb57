"""The language-model training cell at a tiny size on the CPU, through
``drivers/train_lm.py``: the plain reference agrees with the program; the
control and every planted fault come out as not correct through the driver's
own comparison, the stated recipe as correct; the five new readers on a
recorded slice."""
import json
import os
import time

import numpy as np
import pytest

import tiny_lm
from tiny import harness


@pytest.fixture(scope="module")
def driver():
    harness.setup_compile_cache()
    return harness.load_module("drivers", "train_lm.py")


def _run(driver, tamper=None, seed=12345678901):
    import jax
    return driver.run(tiny_lm.train_lm_cell(), seed, 0.5, False,
                      jax.devices()[:1], time.monotonic(), tamper=tamper)


def test_reference_agrees_with_the_program(driver):
    res = _run(driver)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    # f32 on the CPU: far inside the limits that bf16 on the chip needs
    assert res["compared"]["grad_norm_gap"]["value"] < 5e-3
    assert res["compared"]["grad_dir_gap"]["value"] < 5e-3
    assert res["compared"]["load_gap"]["value"] == 0.0
    assert res["notes"]["loss_gap"] < 1e-4
    assert res["notes"]["rows_per_held_expert_expected"] == 2 * 32 * 2 / 8.0
    assert sum(res["notes"]["rows_held"]) > 0
    assert res["notes"]["moe_rows_held"] == res["notes"]["rows_held"][-1]


def test_half_of_the_batch_left_out_is_not_correct(driver):
    def half(trainer, scope):
        feed = trainer.feeder.feed
        trainer.feeder.feed = lambda data: feed(data[:len(data) // 2])
    res = _run(driver, tamper=half)
    assert not res["correct"]


def test_control_and_faults_put_in_the_programs_place_are_not_correct(
        driver):
    """What calibrate.py runs on the chip at the cell's own size. At this
    size (32 positions, 8 rotary dimensions of 32) leaving the rotary
    positions out moves too little for a limit set on the chip, so that
    fault is held to what it has to show: a gradient that points elsewhere
    at nearly the same size."""
    out = driver.control_readings(tiny_lm.train_lm_cell(), 77, None, look=True)
    for name in ("control_fp8", "fault_half_batch", "fault_state_unchanged",
                 "fault_selection_without_bias",
                 "fault_weights_not_renormalised"):
        assert not out[name]["correct"], (name, out[name]["compared"])
    assert out["fault_state_unchanged"]["numbers"]["delta_norm_gap"] == 1.0
    assert not out["fault_selection_without_bias"]["compared"][
        "load_gap"]["ok"]
    rotary = out["fault_rotary_left_out"]["numbers"]
    assert rotary["grad_dir_gap"] > 10 * rotary["grad_norm_gap"] > 0
    assert out["stated_bf16"]["correct"], out["stated_bf16"]["compared"]


def test_the_new_readers_on_a_recorded_slice():
    """``train.attn_ms`` .. ``train.moe_experts_roofline_pct`` over scope
    times as ``program_trace.reduce_window`` gives them."""
    view = {"scope_ns": {"forward/latent_attention/attn": 40e6,
                         "backward/latent_attention/attn": 110e6,
                         "forward/latent_attention/proj": 30e6,
                         "forward/moe_ffn/route": 4e6,
                         "backward/moe_ffn/route": 6e6,
                         "forward/moe_ffn/experts": 5e6,
                         "backward/moe_ffn/experts": 15e6,
                         "update/adam": 20e6, "unscoped": 9e6},
            # the grouped-matmul kernels carry no scope of the program's
            "unscoped_ns": {"ragged-dot-none.59": 6e6,
                            "ragged-dot-metadata.3": 2e6,
                            "copy-done.7": 1e6}}
    ctx = {"program_trace": view, "steps": 2,
           "peaks": {"flops_bf16": 197e12},
           "attention_flops_per_step": 6.18e12,
           "expert_flops_per_step": 0.87e12}
    read = lambda name: harness.load_module(
        "layer_metrics", name + ".py").read(ctx)
    assert read("train.attn_ms") == pytest.approx(75.0)
    assert read("train.moe_route_ms") == pytest.approx(5.0)
    assert read("train.moe_experts_ms") == pytest.approx(14.0)
    assert read("train.attn_roofline_pct") == pytest.approx(
        100 * 6.18e12 / (0.075 * 197e12))
    assert read("train.moe_experts_roofline_pct") == pytest.approx(
        100 * 0.87e12 / (0.014 * 197e12))
    # a program without these scopes (the parent): silence, no error
    silent = dict(ctx, program_trace={"scope_ns": {"forward/conv2d": 1e6}})
    for name in ("train.attn_ms", "train.attn_roofline_pct",
                 "train.moe_route_ms", "train.moe_experts_ms",
                 "train.moe_experts_roofline_pct"):
        assert harness.load_module(
            "layer_metrics", name + ".py").read(silent) is None
    assert harness.load_module(
        "layer_metrics", "train.attn_ms.py").read({}) is None


def test_the_cell_reports_what_benchmark_json_says():
    cell = harness.load_cell("kanana2-train-ep8share-s4096")
    names = {m["name"] for m in cell["per_layer"]}
    assert {"train.attn_ms", "train.attn_roofline_pct", "train.moe_route_ms",
            "train.moe_experts_ms", "train.moe_experts_roofline_pct",
            "train.step_mfu_pct", "train.update_ms"} <= names
    assert "train.conv_ms" not in names
    assert [m["name"] for m in cell["end_to_end"]] == [
        "train_images_per_s", "setup_s"]
    config = cell["config"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = [json.loads(l) for l in f
               if "kanana-2-30b-a3b-instruct-2601" in l][0]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["n_routed_experts"] == 128 and config["vocab_size"] == 128256

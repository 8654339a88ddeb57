"""The training cell at a tiny size on the CPU: the plain reference agrees
with the program; the control and each planted fault come out as not
correct through the driver's own comparison; run.py refuses to run without
a TPU."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import tiny
from tiny import harness



@pytest.fixture(scope="module")
def driver():
    harness.setup_compile_cache()
    return harness.load_module("drivers", "train.py")


def _run(driver, tamper=None, model=None, seed=12345678901):
    import jax
    return driver.run(tiny.train_cell(), seed, 0.5, False, jax.devices()[:1],
                      time.monotonic(), tamper=tamper, model=model)


def test_reference_agrees_with_the_program(driver):
    res = _run(driver)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    # f32 on the CPU: far inside the limits that bf16 on the chip needs
    assert res["compared"]["grad_norm_gap"]["value"] < 5e-3
    assert res["notes"]["loss_gap"] < 1e-3


def test_state_left_unchanged_is_not_correct(driver):
    def frozen(trainer, scope):
        run = trainer.exe.run

        def run_and_restore(program, *a, **kw):
            saved = {n: np.asarray(scope.find_var(n))
                     for n in scope.local_var_names()
                     if hasattr(scope.find_var(n), "shape")}
            out = run(program, *a, **kw)
            for n, v in saved.items():
                scope.set_var(n, v)
            return out
        trainer.exe.run = run_and_restore
    res = _run(driver, tamper=frozen)
    assert not res["correct"]
    assert res["compared"]["delta_norm_gap"]["value"] > 0.99


def test_half_of_the_batch_left_out_is_not_correct(driver):
    def half(trainer, scope):
        feed = trainer.feeder.feed
        trainer.feeder.feed = lambda data: feed(data[:len(data) // 2])
    res = _run(driver, tamper=half)
    assert not res["correct"]
    assert not res["compared"]["grad_norm_gap"]["ok"]


def test_the_programs_own_resnet_drops_a_relu_and_is_not_correct(driver):
    """models.resnet_imagenet is not the published network (PERF.md, Open
    questions): the comparison sees it."""
    def theirs(layers, img, config):
        from paddle_tpu import models
        return models.resnet_imagenet(img, class_dim=config["classes"],
                                      depth=config["depth"])
    res = _run(driver, model=theirs)
    assert not res["correct"]


def test_control_and_faults_put_in_the_programs_place_are_not_correct(
        driver):
    """What calibrate.py runs on the chip at the cell's own size: each
    passes through compare.judge under the configuration's limits."""
    out = driver.control_readings(tiny.train_cell(), 77, None, look=True)
    for name in ("control_fp8", "fault_half_batch", "fault_state_unchanged"):
        assert not out[name]["correct"], (name, out[name]["compared"])
    assert out["fault_state_unchanged"]["numbers"]["delta_norm_gap"] == 1.0
    assert out["stated_bf16"]["correct"], out["stated_bf16"]["compared"]


def test_run_py_exits_nonzero_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.ROOT, "chipbench", "run.py"),
         "--workload", "resnet50-train-trainer", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""

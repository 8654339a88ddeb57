import os

# 8 virtual CPU devices: the multi-chip sharding tests run on a CPU mesh
# (real multi-chip TPU isn't available in CI; the sharding lowering is
# identical, only the collective fabric differs).
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

# Pin the config too, not only the env var: jax may already have been
# imported (and have snapshotted JAX_PLATFORMS) by the time this runs.
# Executor._device reads this pin to let a TPUPlace resolve to the CPU.
jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

# The ONE definition of the forced multi-device CPU setup (the XLA_FLAGS
# lines above): multi-chip sharding tests ask for the platform through
# these helpers instead of re-reading jax.devices() and hand-rolling
# meshes per test file.
FORCED_CPU_DEVICES = 8


@pytest.fixture(scope="session")
def forced_cpu_devices():
    """The forced virtual CPU devices, or a named skip when the platform
    did not come up with enough (e.g. XLA_FLAGS were overridden)."""
    devs = jax.devices()
    if len(devs) < FORCED_CPU_DEVICES:
        pytest.skip("needs the forced %d-device CPU platform, got %d "
                    "device(s)" % (FORCED_CPU_DEVICES, len(devs)))
    return devs[:FORCED_CPU_DEVICES]


@pytest.fixture
def dp8_mesh(forced_cpu_devices):
    """A {'dp': 8} mesh over the forced CPU devices — the data-parallel
    fixture test_comm.py and the parallel tests share."""
    from paddle_tpu.parallel import make_mesh
    return make_mesh({"dp": FORCED_CPU_DEVICES},
                     devices=forced_cpu_devices)

# The <=3-minute pre-commit tier (VERDICT r3 item 4): broad, fast coverage —
# core IR/executor, the whole per-op contract suite, control flow, sequence,
# models, parallelism meshes, and the registry-vs-reference audit. Measured
# ~2m50s on the CI host. Run: python -m pytest tests/ -q -m smoke
SMOKE_FILES = {
    "test_core.py",
    "test_op_contract.py",
    "test_op_contract_suite.py",
    "test_control_flow.py",
    "test_split_merge_lod.py",
    "test_sequence.py",
    "test_models.py",
    "test_parallel.py",
    "test_registry_audit.py",
    # serialization goldens: seconds to run, and the class of drift they
    # catch (op attrs changing the serialized program form) comes
    # exactly from the op/layer edits smoke is meant to gate
    "test_config_serialization.py",
    "test_detection.py",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (item.fspath.basename in SMOKE_FILES
                and "slow" not in item.keywords):
            item.add_marker(pytest.mark.smoke)


# Threaded-subsystem test modules run under the lock-order race detector
# (paddle_tpu.analysis.locks): every lock those tiers build goes through
# the shared constructor, so tier-1 order-checks the serving stack for
# free — an A->B/B->A inversion or a held-across-join introduced by a
# future edit fails these suites even though CPU CI never wins the race.
LOCK_SANITIZED_FILES = {
    "test_serving.py",
    "test_router.py",
    "test_generation.py",
    "test_autoscale.py",
}


@pytest.fixture(autouse=True)
def _lock_order_detector(request):
    if request.fspath.basename not in LOCK_SANITIZED_FILES:
        yield
        return
    from paddle_tpu.analysis import locks
    locks.reset()
    locks.enable()
    try:
        yield
        rep = locks.report()
    finally:
        locks.disable()
        locks.reset()
    assert rep["cycles"] == [], \
        "lock-order cycle (potential deadlock): %r" % rep
    assert rep["join_hazards"] == [], \
        "held-across-join hazard: %r" % rep


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs, scope, and name counters."""
    import paddle_tpu as pt
    from paddle_tpu.core import unique_name

    main, startup = pt.Program(), pt.Program()
    old_main = pt.switch_main_program(main)
    old_startup = pt.switch_startup_program(startup)
    scope = pt.Scope()
    with unique_name.guard():
        with pt.scope_guard(scope):
            yield
    pt.switch_main_program(old_main)
    pt.switch_startup_program(old_startup)

"""The rules that keep the device visible (PR 21): one compiled-or-
interpreted decision, a TPUPlace that does not silently become the CPU, a
compile cache placed from outside, and a bench that has no CPU mode."""
import os

import jax
import pytest

import paddle_tpu as pt
from paddle_tpu.core import compile_cache as pl
from paddle_tpu import place as place_mod


class _Dev(object):
    def __init__(self, platform, device_kind="x"):
        self.platform = platform
        self.device_kind = device_kind


@pytest.mark.parametrize("platform,want", [("cpu", False), ("tpu", True)])
def test_on_tpu_reads_the_default_device(monkeypatch, platform, want):
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev(platform)])
    assert place_mod.on_tpu() is want


def test_on_tpu_refuses_other_platforms_and_propagates_init_errors(
        monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("gpu")])
    with pytest.raises(RuntimeError, match="'gpu'"):
        place_mod.on_tpu()

    def boom(*a):
        raise RuntimeError("backend init failed")
    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="backend init failed"):
        place_mod.on_tpu()


def test_tpuplace_resolves_to_cpu_only_when_pinned():
    # the suite pins jax_platforms=cpu (conftest): a TPUPlace is the CPU
    assert pt.Executor(pt.TPUPlace(0))._device().platform == "cpu"
    prev = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(RuntimeError, match="TPUPlace"):
            pt.Executor(pt.TPUPlace(0))._device()
    finally:
        jax.config.update("jax_platforms", prev)


def test_compile_cache_dir_is_placed_from_outside(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    saved = dict(pl._compile_cache_state)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert pl.enable_compile_cache() == str(tmp_path)
        # jax reads the variable itself; the program set nothing
        assert jax.config.jax_compilation_cache_dir == "sentinel"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(pt.__file__))), ".jax_cache")
        assert pl.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        pl._compile_cache_state.update(saved)


def test_bench_has_no_cpu_mode(capsys):
    import bench
    assert bench.main([]) == 2          # CPU-pinned suite: no TPU
    assert capsys.readouterr().out == ""  # and no metric line
    assert bench._peak_flops(_Dev("tpu", "TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="device_kind"):
        bench._peak_flops(_Dev("tpu", "TPU v9 imaginary"))


def test_executor_commits_state_so_the_step_compiles_once():
    """Startup outputs are uncommitted, step outputs committed; jit keys
    its executable on that, so without the commit in _run_jit the same
    step program compiled twice (a second whole XLA compile on a TPU)."""
    import jax.monitoring
    import numpy as np
    from paddle_tpu import layers

    compiles = []

    def on_event(name, *_a, **_kw):
        if name.endswith("backend_compile_duration"):
            compiles.append(name)

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup), pt.scope_guard(pt.Scope()):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[1], dtype="int64")
        loss = layers.mean(layers.cross_entropy(
            layers.fc(x, size=3, act="softmax"), y))
        pt.SGD(learning_rate=0.1).minimize(loss)
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        feed = {"x": np.ones((8, 4), "float32"),
                "y": np.zeros((8, 1), "int64")}
        exe.run(main, feed=feed, fetch_list=[loss])
        jax.monitoring.register_event_duration_secs_listener(on_event)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
    assert compiles == []
    assert exe.stats["compiles"] == 2   # startup + one step program

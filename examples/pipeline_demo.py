"""Pipeline-parallel training demo on a dp x pp mesh.

A CPU-mesh dry run: self-provisions 8 virtual CPU devices (same trick as
__graft_entry__.dryrun_multichip), builds a
4-stage residual-MLP pipeline with data parallelism across the other
axis, and trains a regression target with the GPipe microbatch schedule.

Run: python -m examples.pipeline_demo
"""
from __future__ import annotations

import os
import sys


def _provision(n=8):
    """Run on an n-device virtual CPU mesh: in place when this process is
    already pinned to the CPU with enough devices, else by re-executing
    self with ``--cpu-mesh`` (a backend, once initialised, cannot be
    re-provisioned in place)."""
    if "--cpu-mesh" in sys.argv:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % n)
        import jax
        jax.config.update("jax_platforms", "cpu")
        return jax
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo_root)
    import jax
    if jax.config.jax_platforms == "cpu" and len(jax.devices()) >= n:
        return jax
    import subprocess
    from paddle_tpu.parallel.env import cpu_mesh_env
    env = cpu_mesh_env(n)
    # scripts put their own dir on sys.path, not the repo root
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--cpu-mesh"],
        env=env, cwd=repo_root, timeout=540)
    raise SystemExit(proc.returncode)


def main():
    jax = _provision(8)
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.parallel import (make_mesh, pipelined_step_fn,
                                     stack_stage_params)

    feat, pp, n_micro, steps = 32, 4, 8, 60
    mesh = make_mesh({"dp": 2, "pp": pp})
    rng = np.random.RandomState(0)
    stages = [{"w": jnp.asarray(rng.randn(feat, feat) * 0.15, jnp.float32),
               "b": jnp.zeros((feat,), jnp.float32)} for _ in range(pp)]

    def stage_fn(p, x):
        return x + jnp.tanh(x @ p["w"] + p["b"])

    def loss_fn(yp, yt):
        return jnp.mean((yp - yt) ** 2)

    step = pipelined_step_fn(stage_fn, loss_fn, mesh, n_micro,
                             axis_name="pp", data_axis="dp")
    params = stack_stage_params(stages)
    x = jnp.asarray(rng.randn(64, feat), jnp.float32)
    target = jnp.tanh(x @ jnp.asarray(rng.randn(feat, feat) * 0.3,
                                      jnp.float32))
    import time
    t0 = time.time()
    for i in range(steps):
        loss, params = step(params, x, target, 0.05)
        if i % 10 == 0 or i == steps - 1:
            print("step %3d: loss=%.5f" % (i, float(loss)))
    bubble = (pp - 1) / (n_micro + pp - 1)
    print("mesh=%s microbatches=%d bubble=%.0f%% wall=%.1fs"
          % (dict(mesh.shape), n_micro, 100 * bubble, time.time() - t0))


if __name__ == "__main__":
    main()

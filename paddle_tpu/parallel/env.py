"""Multi-host process environment.

Replaces etcd discovery + the Go master/pserver bootstrap
(reference: go/pserver/etcd_client.go:70-204, go/master/service.go) with the
JAX distributed coordination service: one coordinator address, every host
calls ``init_distributed`` and the runtime wires global device ids.
"""
from __future__ import annotations

import collections
import os
from typing import Optional

import jax

# The resolved process world, shared by init_distributed, the elastic
# supervisor (paddle_tpu.elastic) and tests. Unset fields are None (the
# TPU-pod auto-detect path); ``generation`` counts elastic relaunches.
World = collections.namedtuple(
    "World", ["coordinator", "num_processes", "process_id", "elastic",
              "generation"])


def _int_env(env, key):
    raw = env.get(key)
    if raw is None:
        return None
    try:
        return int(raw)
    except (TypeError, ValueError):
        raise ValueError(
            "%s=%r is not an integer; the launcher exports it as a "
            "decimal rank/count (see paddle_tpu.launch)" % (key, raw))


def validate_world(num_processes, process_id):
    """Readable range checks for an explicit (count, rank) pair — the
    checks ``jax.distributed.initialize`` would otherwise fail opaquely
    on (a hung barrier or a cryptic RPC error instead of a message)."""
    if num_processes is not None and num_processes <= 0:
        raise ValueError(
            "PADDLE_TPU_NUM_PROCESSES must be > 0, got %d — a world "
            "needs at least one process" % num_processes)
    if process_id is not None:
        if process_id < 0:
            raise ValueError(
                "PADDLE_TPU_PROCESS_ID must be >= 0, got %d" % process_id)
        if num_processes is not None and process_id >= num_processes:
            raise ValueError(
                "PADDLE_TPU_PROCESS_ID=%d is out of range for "
                "PADDLE_TPU_NUM_PROCESSES=%d (ranks are 0-based: valid "
                "ranks are 0..%d)"
                % (process_id, num_processes, num_processes - 1))
    if (num_processes is None) != (process_id is None):
        raise ValueError(
            "PADDLE_TPU_NUM_PROCESSES and PADDLE_TPU_PROCESS_ID must be "
            "set together (got count=%r, rank=%r): setting only one "
            "would make jax.distributed guess the other and hang the "
            "coordination barrier" % (num_processes, process_id))


def world(env=None) -> World:
    """Resolve and VALIDATE the process world from the launcher env vars.
    Unset values stay None (jax auto-detects process count/rank on TPU
    pods); malformed or out-of-range values raise a readable ValueError
    instead of letting ``jax.distributed`` fail opaquely."""
    env = os.environ if env is None else env
    num = _int_env(env, "PADDLE_TPU_NUM_PROCESSES")
    pid = _int_env(env, "PADDLE_TPU_PROCESS_ID")
    validate_world(num, pid)
    gen = _int_env(env, "PADDLE_TPU_ELASTIC_GENERATION") or 0
    return World(coordinator=env.get("PADDLE_TPU_COORDINATOR"),
                 num_processes=num, process_id=pid,
                 elastic=env.get("PADDLE_TPU_ELASTIC", "") not in
                 ("", "0", "false"),
                 generation=gen)


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None):
    """Initialise multi-host JAX. No-op when single-process (the common
    dev/test path). Env fallbacks mirror the reference's flags
    (trainer_id/num_gradient_servers, reference: paddle/utils/Flags.cpp:44-65).
    """
    coordinator_address = coordinator_address or os.environ.get(
        "PADDLE_TPU_COORDINATOR")
    if coordinator_address is None:
        return False
    # leave unset values as None: jax.distributed auto-detects process
    # count/rank on TPU pods; forcing 1/0 would make every host rank 0.
    # Env vars are read lazily, only for fields the caller left None —
    # explicit arguments shield the call from stale/malformed env —
    # then the MERGED values get the readable validation.
    if num_processes is None:
        num_processes = _int_env(os.environ, "PADDLE_TPU_NUM_PROCESSES")
    if process_id is None:
        process_id = _int_env(os.environ, "PADDLE_TPU_PROCESS_ID")
    validate_world(num_processes, process_id)
    jax.distributed.initialize(coordinator_address, num_processes, process_id)
    return True


def get_world_size() -> int:
    return jax.process_count()


def get_rank() -> int:
    return jax.process_index()


def cpu_mesh_env(n, base_env=None):
    """Environment dict for re-exec'ing a child onto an n-device virtual
    CPU mesh (JAX_PLATFORMS + xla_force_host_platform_device_count)."""
    env = dict(base_env if base_env is not None else os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=%d"
                        % int(n))
    return env

"""Elastic multi-host training: survive worker loss, re-plan, resume.

The fusion point of the ``resilience`` (retry/fault/event) and ``comm``
(topology-parameterised collectives) subsystems, after the reference's
Go runtime (PAPER.md §Go runtime: etcd task queue, master snapshots,
pserver re-registration). Five parts:

- :mod:`.supervisor` — ``ElasticSupervisor``: the coordinator behind
  ``paddle_tpu.launch --elastic``; classifies worker death
  (transient -> bounded RetryPolicy-backoff restart at full world,
  permanent -> shrink to the survivors), owns the cross-generation
  task master, and records every move as a resilience event.
- :mod:`.replan` — ``replan(world_size)``: the (host, chip)
  factorisation + ``CommPolicy`` + hierarchical ``axis_index_groups``
  recomputed for the survivor set; ``apply_flags()`` re-keys the
  Executor's jit cache so a shrunk world cannot hit a stale compile.
- :mod:`.resume` — the checkpoint <-> task-master-snapshot PAIRING that
  makes a resumed world consistent with itself: model state and the
  dataset pass restart from the same point, so no task is double-
  processed or lost across a resize.
- :mod:`.worker` — ``ElasticWorker``: the WORKER half of the protocol
  as a first-class role, so ``Trainer.train(elastic=True)`` — the real
  training loop, comm_overlap included — leases batches
  through the supervisor-owned task master, pairs its checkpoints with
  master snapshots, and resumes cross-world like the chaos harness
  always did by hand.
- the chaos harness that proves it: ``benchmark/chaos_run.py`` +
  ``tools/elastic_smoke.sh`` (CPU CI), the same recipe as the real
  TPU-pod chaos run (cluster/README.md).

Fault sites: ``elastic.heartbeat``, ``elastic.replan``,
``elastic.resume`` (see paddle_tpu.resilience.faults). Observability:
``profiler.elastic_counters()`` + the ``elastic`` timeline section +
``elastic.record_stats(exe.stats)``.
"""
from __future__ import annotations

from .replan import ElasticPlan  # noqa: F401
from .replan import replan as plan_for  # noqa: F401
from .resume import (  # noqa: F401
    ResumePoint, resume_point, snapshot_path, pair_snapshot,
    record_stats, SNAP_IN_DIR,
)
from .resume import resume as resume_latest  # noqa: F401
from .supervisor import (  # noqa: F401
    ElasticSupervisor, TaskMasterHost, Gang, free_port,
)
from .fingerprints import (  # noqa: F401
    check_replica_schedule, publish_fingerprint, gather_fingerprints,
)
from .worker import ElasticWorker  # noqa: F401
# the submodules stay addressable as attributes (elastic.replan.replan,
# elastic.resume.resume): the verb aliases above exist because the
# module names and their primary verbs collide
from . import fingerprints, replan, resume, supervisor, worker  # noqa: F401

__all__ = [
    "ElasticPlan", "plan_for",
    "ResumePoint", "resume_point", "resume_latest", "snapshot_path",
    "pair_snapshot", "record_stats", "SNAP_IN_DIR",
    "ElasticSupervisor", "TaskMasterHost", "Gang", "free_port",
    "check_replica_schedule", "publish_fingerprint",
    "gather_fingerprints", "ElasticWorker",
    "fingerprints", "replan", "resume", "supervisor", "worker",
]

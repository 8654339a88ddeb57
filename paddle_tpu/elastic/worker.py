"""The elastic WORKER role: ``Trainer.train`` speaking the PR-8
protocol.

The supervisor half of elasticity (:mod:`.supervisor`) has been real
since PR 8 — classify death, re-queue leases, re-plan, relaunch — but
its only in-tree client was the raw-Executor loop in
``benchmark/chaos_run.py``. This module is the worker half as a
first-class role, so the REAL training loop (``Trainer.train`` with
the PR-7 ``comm_overlap`` step builds and the PR-13 fingerprint
exchange) runs as an elastic worker with no bespoke glue:

- **world** — resolve + validate the launcher env
  (``parallel.env.world()``), ``replan(world).apply_flags()`` the
  (host, chip)/comm factorisation for THIS generation (plan summary
  written to ``<state>/plan-gen<G>.json`` for the audit tooling), and
  transpile the trainer's program onto the plan's mesh — a relaunched
  survivor can never hit a stale compile (``plan.cache_signature()``).
- **leases** — batches come from the supervisor-owned task master
  (``v2.master.client``, heartbeating worker registration): the worker
  leases a task, maps it to a batch through the caller's
  ``task_reader(payload)``, and commits the lease only AFTER the step
  ran (``task_finished``; a lapsed lease is recorded and NOT counted —
  a survivor owns that task now). A ``task_reader`` raise follows the
  PR-1 poison-task contract: ``task_failed`` re-queues it up to the
  master's ``failure_max``, then the master drops it with a recorded
  ``task_dropped`` event — the pass continues either way.
- **pairing** — every ``FLAGS.elastic_ckpt_period`` committed tasks:
  master snapshot FIRST, ``save_checkpoint(step=, keep_last=)``
  second, snapshot moved in-dir third (:mod:`.resume` explains why
  every kill window then lands on a consistent (model, data-pass)
  point); startup resumes from ``resume()``'s newest consistent pair
  onto the CURRENT mesh.
- **fingerprints** — published for free: the env-gated PR-13 exchange
  fires inside the step builders the transpiled program routes
  through; the worker's job is only to have set the flags/mesh up
  before the first trace (which ``replan`` did).

A worker WITHOUT a task master (no ``PADDLE_TPU_MASTER_ADDR``) still
gets the full role minus leasing — world/replan/transpile/resume plus
unpaired retention checkpoints — which is how every NON-lease-owning
rank of a CPU chaos job runs the same ``Trainer.train`` code path the
lease owner does (doc/elasticity.md spells out the honest CPU-vs-pod
difference: on a pod the batch shards over the mesh inside ONE SPMD
program; on CPU each process is its own island, so only one rank can
own the audited lease stream).
"""
from __future__ import annotations

import collections
import json
import os
import time

from ..resilience import record_durable_event
from .replan import replan
from . import resume as resume_mod

__all__ = ["ElasticWorker"]


class ElasticWorker(object):
    """One ``Trainer.train`` pass's elastic-protocol state machine.

    Built by ``Trainer.train(elastic=True)``; drives world resolution,
    re-plan + transpile, paired resume, the lease reader, and the
    commit/checkpoint pairing. ``task_reader(payload) -> batch-data``
    turns one leased task payload into one minibatch (the shape
    ``DataFeeder.feed`` accepts); ``on_commit(step, task_id, payload,
    cost)`` fires after a successful lease commit and BEFORE the paired
    checkpoint (where the chaos harness writes its audit row).
    """

    def __init__(self, trainer, task_reader=None, root=None,
                 ckpt_period=None, keep_last=4, data_axis="dp",
                 on_commit=None, on_skip=None, env=None):
        from ..flags import FLAGS
        from ..parallel import env as _env

        self.trainer = trainer
        self.task_reader = task_reader
        self.root = root or trainer.checkpoint_dir
        self.keep_last = int(keep_last)
        self.data_axis = data_axis
        self.on_commit = on_commit
        self.on_skip = on_skip
        self.ckpt_period = int(ckpt_period if ckpt_period is not None
                               else FLAGS.elastic_ckpt_period)
        if self.ckpt_period < 1:
            raise ValueError("elastic_ckpt_period must be >= 1, got %d"
                             % self.ckpt_period)

        environ = os.environ if env is None else env
        w = _env.world(environ)          # validated launcher env
        self.world_size = w.num_processes or 1
        self.rank = w.process_id or 0
        self.generation = w.generation
        self.state_dir = environ.get("PADDLE_TPU_ELASTIC_STATE")
        self.master_addr = environ.get("PADDLE_TPU_MASTER_ADDR")
        self.master_timeout = float(
            environ.get("PADDLE_TPU_MASTER_TIMEOUT", "60"))
        if self.task_reader is not None and not self.master_addr:
            raise ValueError(
                "Trainer.train(elastic=True) with a task_reader needs a "
                "supervisor-owned task master (PADDLE_TPU_MASTER_ADDR "
                "unset — launch through `paddle_tpu launch --elastic "
                "--master-tasks-file ...`)")

        self.plan = None
        self.dist_context = None
        self.client = None
        self.watchdog = None            # set by Trainer.train when armed
        self.step = 0                   # committed good steps (resumed)
        self._last_pair_step = None
        self._leases = collections.deque()  # (task_id, payload) in batch order
        self.commits = 0
        self.lease_losses = 0
        self.task_failures = 0
        # per-step wall-time record for the supervisor's gray-failure
        # sweep (resilience.grayfail): EWMA + a short window, published
        # per iteration into <state>/heartbeat-rank<r>.json
        self._hb_window = collections.deque(maxlen=8)
        self._hb_ewma = None

    # -- generation setup ----------------------------------------------------
    def setup(self):
        """Re-plan for THIS world, transpile the trainer's program onto
        the plan's mesh, connect the master, resume from the newest
        consistent pair. Called by ``Trainer.train`` before the startup
        program runs (the dist context must exist first)."""
        from ..parallel import DistributeTranspiler, ShardingStrategy

        self.plan = replan(self.world_size).apply_flags()
        if self.state_dir and self._owns_audit():
            try:
                path = os.path.join(self.state_dir,
                                    "plan-gen%d.json" % self.generation)
                with open(path + ".tmp", "w") as f:
                    json.dump(self.plan.summary(), f, indent=1)
                os.replace(path + ".tmp", path)
            except OSError:
                pass  # audit artifact only — never fail setup on it
        import jax
        devices = None
        local = jax.devices()
        if len(local) != self.plan.dp:
            # the plan is a sub-mesh of the local device set (a shrunk
            # world on a forced CPU mesh, or a devbox with more chips
            # than the job) — never silently idle chips IMPLICITLY, but
            # the plan's dp is explicit intent
            if len(local) < self.plan.dp:
                raise ValueError(
                    "elastic plan wants dp=%d but only %d local devices "
                    "exist — the launcher must force the mesh before "
                    "jax initialises (benchmark/chaos_run.py shows how)"
                    % (self.plan.dp, len(local)))
            devices = local[:self.plan.dp]
        mesh = self.plan.make_mesh(self.data_axis, devices=devices)
        self.dist_context = DistributeTranspiler().transpile(
            program=self.trainer.main_program, mesh=mesh,
            strategy=ShardingStrategy(data_axis=self.data_axis))
        self.trainer.exe.dist_context = self.dist_context
        if self.master_addr:
            from ..v2 import master as v2_master
            self.client = v2_master.client(
                self.master_addr, timeout_sec=self.master_timeout,
                worker_name="rank%d" % self.rank)
        return self

    def _owns_audit(self):
        """Exactly one rank writes the shared per-generation audit
        artifacts: the lease owner when there is one, rank 0 otherwise."""
        return self.task_reader is not None or self.rank == 0

    def resume(self):
        """Restore the newest consistent (checkpoint, snapshot) pair
        onto the CURRENT mesh; returns the resumed step (0 = fresh)."""
        if not self.root:
            return 0
        rp = resume_mod.resume(self.root, self.trainer.main_program,
                               dist_context=self.dist_context)
        if rp is not None and rp.step is not None:
            self.step = rp.step
            self._last_pair_step = rp.step
        return self.step

    # -- the lease reader ----------------------------------------------------
    def reader(self):
        """Reader factory for the Trainer loop: leases tasks, maps them
        through ``task_reader``, tracks the lease ledger in batch order
        (the loop trains in reader order, so commits pop the ledger
        head). A poisoned task (task_reader raise) is failed
        back to the master — the PR-1 reader.next contract — and the
        stream continues with the next lease."""
        from .. import profiler as _prof

        def _gen():
            while True:
                tid, payload = self.client.get_task(
                    should_stop=self._lease_wait_tick)
                if tid is None:
                    return            # pass complete
                if tid == "wait":
                    return            # stopping (preemption drain)
                try:
                    batch = self.task_reader(payload)
                except Exception as e:
                    self.task_failures += 1
                    _prof.update_trainer_counters(elastic_task_failures=1)
                    dropped = self.client.task_failed(tid)
                    record_durable_event(
                        "elastic_task_read_failed", site="trainer.elastic",
                        task_id=tid, error=repr(e), dropped=dropped,
                        rank=self.rank, generation=self.generation)
                    continue
                self._leases.append((tid, payload))
                yield batch
        return _gen

    def _lease_wait_tick(self):
        """``should_stop`` hook for the blocking lease wait: waiting for
        a peer-held lease is IDLE, not HUNG — re-arm a live step
        deadline each poll so a straggler peer cannot make every
        healthy waiting worker fire its watchdog. ``tick`` (not
        ``ping``): a deliberately suspended deadline — the commit-path
        checkpoint save — must stay suspended even while the feed
        thread waits here concurrently. Only when the lease LEDGER is
        empty: an uncommitted lease means the main thread still owes a
        step for it — if THAT step is the wedged one, the feed thread's
        idle polling must not keep re-arming the deadline over it."""
        if self.watchdog is not None and not self._leases:
            self.watchdog.tick("lease-wait")
        return self.trainer.preempted

    # -- commit + pairing ----------------------------------------------------
    def commit(self, cost=None, skipped=False):
        """Commit the lease at the ledger head after its step ran.
        Returns True when the commit counted (lease still ours): the
        step advances and, on the checkpoint cadence (skipped batches
        excluded — a within-budget guardrail skip must not pair a
        poisoned model), the (snapshot, checkpoint) pair lands.
        Returns False on a lapsed lease — a survivor owns the task."""
        from .. import profiler as _prof

        tid = payload = None
        if self.client is not None and self.task_reader is not None:
            tid, payload = self._leases.popleft()
            if not self.client.task_finished(tid):
                self.lease_losses += 1
                record_durable_event(
                    "elastic_lease_lost", site="trainer.elastic",
                    task_id=tid, rank=self.rank,
                    generation=self.generation)
                return False
            self.commits += 1
            _prof.update_trainer_counters(elastic_tasks_committed=1)
        if skipped:
            # the task is consumed (committed, if leased) but its model
            # contribution was discarded by the guardrail: no step
            # advance, no checkpoint of a possibly-poisoned model
            if self.on_skip is not None:
                self.on_skip(tid, payload)
            return True
        self.step += 1
        if self.on_commit is not None:
            self.on_commit(self.step, tid, payload, cost)
        if self.root and self.step % self.ckpt_period == 0:
            self.pair_checkpoint()
        return True

    def pair_checkpoint(self):
        """The PR-8 pairing protocol at the current step: snapshot
        FIRST, checkpoint second, snapshot moved in-dir third. Without
        a master the checkpoint lands unpaired (resumes model alone)."""
        from .. import checkpoint as _ckpt

        if not self.root or self.step < 1 \
                or self._last_pair_step == self.step:
            return None
        t0 = time.perf_counter()
        os.makedirs(self.root, exist_ok=True)
        snap = None
        if self.client is not None and self.task_reader is not None:
            # the snapshot pairs ONLY with the lease owner's step
            # counter: a lease-free worker snapshotting the shared
            # master at its own unrelated step would hand the
            # supervisor a restore point that re-queues tasks the
            # owner already committed — double-processing on resume
            snap = resume_mod.snapshot_path(self.root, self.step)
            self.client.snapshot(snap + ".tmp")
            os.replace(snap + ".tmp", snap)
        ckpt_dir = _ckpt.save_checkpoint(
            self.root, self.trainer.main_program, step=self.step,
            keep_last=self.keep_last)
        if snap is not None:
            os.replace(snap, os.path.join(ckpt_dir,
                                          resume_mod.SNAP_IN_DIR))
        self._last_pair_step = self.step
        self.trainer._last_ckpt_secs = time.perf_counter() - t0
        return ckpt_dir

    def rewind(self):
        """Numeric-guardrail rewind target: restore the newest
        consistent pair (the model the last pairing wrote). The master
        is NOT rolled back — tasks committed during the skip streak
        stay committed; their contribution is what the skip policy
        discarded. The step counter rolls back WITH the model (at
        ``ckpt_period`` > 1 the pair can be older than the last good
        commit — a counter that kept running would label the restored
        lineage with steps the model no longer contains, and the next
        pair would disagree with what a resume finds in it). Returns
        True when a restore happened."""
        if not self.root:
            return False
        before = self.step
        rp = resume_mod.resume(self.root, self.trainer.main_program,
                               dist_context=self.dist_context)
        if rp is None:
            return False
        if rp.step is not None:
            self.step = rp.step
            self._last_pair_step = rp.step
            if before > rp.step:
                # ckpt_period > 1: the pair is older than the last good
                # commit, so up to period-1 ACCEPTED batches roll back
                # with the model while their tasks stay finished in the
                # live master (a kill would have re-run them via the
                # paired snapshot restore; a guardrail rewind cannot —
                # it has no authority over the shared master). The loss
                # is bounded and RECORDED; run period=1 when every
                # contribution must survive a rewind
                record_durable_event(
                    "guard_rewind_dropped_commits",
                    site="trainer.elastic", from_step=before,
                    to_step=rp.step, dropped=before - rp.step,
                    rank=self.rank, generation=self.generation)
        return True

    def publish_heartbeat(self, step_ms):
        """Publish this rank's per-step wall time into the elastic
        state dir (``heartbeat-rank<r>.json``, atomic replace) — the
        metric the supervisor's gray-failure sweep judges against the
        peer ranks. ``step_ms`` is the iteration wall delta (dispatch
        + reader wait + any injected delay — an asynchronous dispatch
        makes a device-timer-only number blind to exactly the stalls gray
        detection exists for) with the commit/checkpoint span excluded
        by the caller (legitimate per-role overhead: only the lease
        owner pays it, and it must not make that rank a false
        outlier).
        No state dir -> no-op (a non-elastic run has no supervisor to
        read it)."""
        if not self.state_dir:
            return None
        step_ms = float(step_ms)
        self._hb_window.append(step_ms)
        alpha = 0.3
        self._hb_ewma = (step_ms if self._hb_ewma is None
                         else alpha * step_ms
                         + (1.0 - alpha) * self._hb_ewma)
        payload = {
            "rank": self.rank,
            "generation": self.generation,
            "step": self.step,
            "step_ms": round(step_ms, 3),
            "step_ms_ewma": round(self._hb_ewma, 3),
            "step_ms_window": [round(v, 3) for v in self._hb_window],
            "time": time.time(),
        }
        path = os.path.join(self.state_dir,
                            "heartbeat-rank%d.json" % self.rank)
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
        except OSError:
            return None   # observability only — never fail the step
        return path

    def close(self):
        if self.client is not None:
            self.client.close()
            self.client = None

    def record_stats(self, stats):
        """Fold the worker's lease accounting + the process elastic
        counters into an ``Executor.stats`` dict."""
        resume_mod.record_stats(stats)
        stats["elastic_tasks_committed"] = self.commits
        stats["elastic_lease_losses"] = self.lease_losses
        stats["elastic_task_failures"] = self.task_failures
        return stats

"""Event-driven trainer: the v2 ``SGD.train`` loop + events, fluid-style.

reference: python/paddle/v2/trainer.py:63,137-215 (SGD class: per-batch
feeder -> forwardBackward -> update, events Begin/EndIteration,
Begin/EndPass fired into a user handler) and the per-pass checkpointing of
paddle/trainer/ParamUtil.cpp.
"""
from __future__ import annotations

import os
import signal
import threading
import time

import numpy as np

from . import io as _io
from .core import ir
from .core.executor import Executor, materialize, materialize_scalar
from .core.scope import global_scope
from .data_feeder import DataFeeder
from .resilience import (NumericGuard, StepWatchdog, fault_point,
                         record_durable_event)


class BeginPass(object):
    def __init__(self, pass_id):
        self.pass_id = pass_id


class EndPass(object):
    def __init__(self, pass_id, metrics=None):
        self.pass_id = pass_id
        self.metrics = metrics or {}


class BeginIteration(object):
    def __init__(self, pass_id, batch_id):
        self.pass_id = pass_id
        self.batch_id = batch_id


class EndIteration(object):
    """``cost`` is a plain float and ``metrics["fetches"]`` host arrays:
    the loop has read both off the device before the event fires."""

    def __init__(self, pass_id, batch_id, cost, metrics=None):
        self.pass_id = pass_id
        self.batch_id = batch_id
        self.cost = cost
        self.metrics = metrics or {}


def _step_spans(batches, pass_id, first_step):
    """``enumerate(batches)`` with every iteration of the caller's loop
    inside its own ``paddle_tpu/train_step`` span: from taking the batch
    off ``batches`` to the end of the loop's body (after ``EndIteration``'s
    handler). Over a :class:`_Lookahead` the batch of step n is in hand
    already (step 0's is taken here), so ``train_step(n)`` covers the
    commit of step n's state, the ``feed`` and ``upload`` of batch n+1,
    the ``run`` that dispatches step n+1 ahead, the ``fetch`` of step n's
    loss and the handler (and the ``run`` of step n itself where it was
    not dispatched ahead: step 0 of a pass). ``step_num`` counts on
    from ``first_step`` (batches since ``train()`` began). A span cannot
    be taken back, so the call that finds the reader exhausted leaves one
    too, marked ``end_of_pass=1`` and with no batch in it."""
    from . import profiler as _prof
    batches = iter(batches)
    batch_id = 0
    while True:
        with _prof.step_span(first_step + batch_id, pass_id=pass_id,
                             batch_id=batch_id) as span:
            try:
                data = next(batches)
            except StopIteration:
                span.set_metadata(end_of_pass=1)
                return
            yield batch_id, data
        batch_id += 1


class _Lookahead(object):
    """The default loop's batches: those of ``batches`` as device-resident
    feed dicts (``feeder.feed`` + ``Executor.prepare_feed``), at most one
    ahead of the step that runs. ``take()`` prepares the next one now —
    the loop calls it before it reads step n's loss, so batch n+1 is
    stacked and uploaded while the device computes — and holds it (where
    the loop dispatches step n+1 ahead, ``held`` is its feed), or what
    taking it raised (the reader's end too), for the ``next()`` that
    follows ``EndIteration(n)``. ``next()`` with nothing
    held takes the batch itself. ``feed`` and ``prepare_feed`` are looked
    up at every batch: a tracer may have replaced them on the
    instances."""

    def __init__(self, batches, trainer):
        self._batches = iter(batches)
        self._trainer = trainer
        self._held = None

    def take(self):
        """Prepare the next batch; True when one is now in hand."""
        t = self._trainer
        try:
            raw = next(self._batches)
            self._held = (t.exe.prepare_feed(t.feeder.feed(raw)), None)
        except Exception as e:
            self._held = (None, e)
        return self._held[1] is None

    @property
    def held(self):
        """The feed dict ``take()`` has in hand, or None."""
        return self._held[0] if self._held is not None else None

    def __iter__(self):
        return self

    def __next__(self):
        if self._held is None:
            self.take()
        (feed, error), self._held = self._held, None
        if error is not None:
            raise error
        return feed


class Trainer(object):
    """Drive a built program over a reader with events.

    Usage:
        trainer = Trainer(cost=avg_cost, optimizer=fluid.SGD(0.01),
                          feed_list=[x, y], place=fluid.TPUPlace())
        trainer.train(reader, num_passes=2, event_handler=handler)
    """

    def __init__(self, cost, optimizer, feed_list, place=None,
                 fetch_list=None, main_program=None, startup_program=None,
                 checkpoint_dir=None, dist_context=None):
        from . import profiler as _prof
        self.cost = cost
        self.main_program = main_program or ir.default_main_program()
        self.startup_program = startup_program or \
            ir.default_startup_program()
        self.optimizer = optimizer
        with _prof.setup_span("build"):
            with ir.program_guard(self.main_program, self.startup_program):
                optimizer.minimize(cost)
            self.exe = Executor(place, dist_context=dist_context)
            self.feeder = DataFeeder(feed_list, place=place,
                                     program=self.main_program)
        self.fetch_list = [cost] + list(fetch_list or [])
        self.checkpoint_dir = checkpoint_dir
        self._initialized = False
        # set by the SIGTERM preemption hook; train() drains the current
        # batch, writes a final synchronous checkpoint, and returns
        self.preempted = False
        self._preempt_at = None      # monotonic stamp of the SIGTERM
        self._grace_sec = None       # launcher-exported drain window
        self._last_ckpt_secs = None  # duration of the last save (est.)

    def _maybe_init(self, load=True):
        """Run startup once; ``load=False`` skips the checkpoint-restore
        walk (the elastic worker resumes through the PAIRED
        ``elastic.resume`` protocol instead of the flat newest-wins
        one)."""
        if self._initialized:
            return
        from . import profiler as _prof
        with _prof.setup_span("init"):
            self.exe.run(self.startup_program)
            if load:
                self._load_checkpoint_state()
        self._initialized = True

    def _load_checkpoint_state(self):
        """Restore from ``checkpoint_dir`` (manifest layout, retention
        root, or flat persistables — newest wins). Returns True when
        anything was loaded; also the numeric guardrail's non-elastic
        rewind target."""
        if self.checkpoint_dir and os.path.isdir(self.checkpoint_dir) and \
                os.listdir(self.checkpoint_dir):
            from . import checkpoint as _ckpt
            if _ckpt._is_complete(self.checkpoint_dir):
                # manifest/shard layout written by save_checkpoint(
                # sharded=True or async_=True)
                _ckpt.load_checkpoint(
                    self.checkpoint_dir, self.main_program,
                    dist_context=self.exe.dist_context)
                return True
            else:
                newest = _ckpt.latest_checkpoint(self.checkpoint_dir)
                files = [os.path.join(self.checkpoint_dir, f)
                         for f in os.listdir(self.checkpoint_dir)
                         if os.path.isfile(os.path.join(
                             self.checkpoint_dir, f))]
                if newest is not None and (
                        not files or os.path.getmtime(newest)
                        >= max(os.path.getmtime(f) for f in files)):
                    # retention root (save_checkpoint(keep_last=)):
                    # newest complete checkpoint, falling back past
                    # corrupt ones. Newest-wins vs the persistables
                    # files this trainer itself writes (per-pass +
                    # preemption saves land in the root as flat files):
                    # a preemption checkpoint must not lose to an older
                    # retained dir on resume
                    _ckpt.load_latest(self.checkpoint_dir,
                                      self.main_program,
                                      dist_context=self.exe.dist_context)
                else:
                    # resume = load persistables (optimizer accumulators
                    # included; reference: io.py save_persistables
                    # semantics)
                    _io.load_persistables(self.exe, self.checkpoint_dir,
                                          main_program=self.main_program)
            return True
        return False

    def _install_preemption_hook(self):
        """SIGTERM -> preempted flag; the training loop turns it into a
        final synchronous checkpoint (the k8s/TPU-maintenance preemption
        contract: the grace window is for draining one batch and writing
        state, reference role: the pserver's crash-safe checkpoint +
        re-register dance). Only the main thread may own signal
        handlers; elsewhere the hook is a no-op (``request_preempt()``
        is the off-main-thread equivalent). Returns (installed,
        previous_handler)."""
        # the supervisor/launcher exports its SIGTERM->SIGKILL window so
        # the drain can be budgeted against the REAL deadline
        grace = os.environ.get("PADDLE_TPU_GRACE_SEC")
        if grace:
            try:
                self._grace_sec = float(grace)
            except ValueError:
                self._grace_sec = None
        if threading.current_thread() is not threading.main_thread():
            return False, None

        def on_sigterm(signum, frame):
            self.preempted = True
            self._preempt_at = time.monotonic()

        try:
            return True, signal.signal(signal.SIGTERM, on_sigterm)
        except ValueError:          # embedded interpreters
            return False, None

    def request_preempt(self):
        """Programmatic preemption: same drain-then-checkpoint path as
        the SIGTERM hook, for callers that own ``train()`` on a
        non-main thread (where ``signal.signal`` is unavailable)."""
        self.preempted = True
        self._preempt_at = time.monotonic()

    def _preempt_checkpoint(self, pass_id, batch_id, save_fn=None):
        """Final drain checkpoint, budgeted against the launcher's
        ``--grace-sec``: when the remaining window cannot plausibly fit
        the save (judged by the last measured save duration), a durable
        ``preempt_truncated`` event lands FIRST — before SIGKILL can —
        and the save is still attempted (checkpoints are atomic: a
        SIGKILL mid-write leaves the previous one intact). A save that
        finishes but overran the window records the same event
        post-hoc."""
        from . import profiler as _prof
        from .resilience import record_event
        t0 = time.monotonic()
        remaining = None
        if self._grace_sec is not None and self._preempt_at is not None:
            remaining = self._grace_sec - (t0 - self._preempt_at)
        est = self._last_ckpt_secs
        truncated = remaining is not None and (
            remaining <= 0
            or (est is not None and est * 1.2 > remaining))
        if truncated:
            _prof.update_trainer_counters(preempts_truncated=1)
            record_durable_event(
                "preempt_truncated", site="trainer.train",
                phase="pre", remaining_sec=round(remaining, 3),
                last_save_sec=est, pass_id=pass_id, batch_id=batch_id)
        (save_fn or self.save_checkpoint)()
        took = time.monotonic() - t0
        if not truncated and remaining is not None and took > remaining:
            _prof.update_trainer_counters(preempts_truncated=1)
            record_durable_event(
                "preempt_truncated", site="trainer.train",
                phase="post", overran_sec=round(took - remaining, 3),
                pass_id=pass_id, batch_id=batch_id)
        record_event("preempt_checkpoint", site="trainer.train",
                     dirname=self.checkpoint_dir, pass_id=pass_id,
                     batch_id=batch_id)

    def _guard_rewind(self):
        """Non-elastic numeric-guardrail rewind: reload the newest state
        from ``checkpoint_dir``. Returns True when a restore happened."""
        if not self.checkpoint_dir:
            return False
        return self._load_checkpoint_state()

    def train(self, reader=None, num_passes=1, event_handler=None,
              elastic=None, task_reader=None, elastic_root=None,
              on_commit=None, on_skip=None, on_resume=None):
        """The default loop looks one batch ahead and dispatches one step
        ahead, on this thread. Iteration n: ``BeginIteration(n)``; the
        new state of step n, which the executor has held back since the
        step was dispatched in iteration n-1, is committed to the scope
        (``Executor.commit``; step 0 of a pass is dispatched here and
        committed at once); batch n+1 is taken off the reader, stacked
        (``DataFeeder.feed``) and uploaded (``Executor.prepare_feed``)
        while the device computes; step n+1 is dispatched on it from the
        state the scope holds (``Executor.run(sync=False, hold=True)``
        returns once it is enqueued, consumes none of the state it reads
        and writes nothing);
        then the loss and the other fetches of step n are read to the
        host (a ``float``, NumPy arrays), and guard, commit, log line
        and ``EndIteration(n)`` follow as ever. When the device ends
        step n, step n+1 is in its queue.

        What a caller can see is the serial loop's. The reader is never
        asked for batch n+2 before ``EndIteration(n)``. The scope a
        handler of ``EndIteration(n)`` reads holds the state after step
        n exactly: step n+1 reads those buffers and does not consume
        them. Losses, fetches and state are those of feeding and running
        strictly in turn, bit for bit. Whatever writes the scope between
        the dispatch of step n+1 and its commit (a handler's
        ``set_var`` at ``EndIteration(n)`` or ``BeginIteration(n+1)``,
        the numeric guard's rewind, a checkpoint load, another
        ``Executor.run``) makes the executor drop that step
        (``Scope.write_stamp``), and it runs again from what the scope
        holds: one wasted device step, the serial loop's answer.
        Preemption and an exception out of a handler drop it and never
        commit it: the scope holds the state after step n; one batch may
        have been taken off the reader and not trained. Nothing is
        dispatched ahead across a pass. What taking batch n+1 raises is
        raised after ``EndIteration(n)``.

        The loop dispatches step n+1 after ``EndIteration(n)`` instead,
        from donated state as before PR 31, where the executor cannot
        hold a step back: a program off the jit path (host ops,
        ``check_nan_inf``), a dp step on the explicit comm collectives,
        or a step whose two sets of state buffers (held steps write the
        new state into the buffers of the state before the one they
        read) do not fit the device's memory (decided by
        ``Executor.run(hold=True)`` once per compiled step from its
        ``memory_analysis()`` and the device's ``bytes_limit``; no flag
        and no argument). ``elastic`` with ``task_reader`` takes
        batch n+1 only after step n's lease is committed (the master
        hands out no lease past a pending last one), so it has no step
        to dispatch ahead and keeps that order too.
        ``Executor.stats`` / ``profiler.pipeline_counters()``:
        ``ahead_steps`` (steps dispatched before the loss of the step
        before them was read; steps - 1 a pass) and ``ahead_dropped``.
        The host arrays of a batch are the feeder's staging arrays
        (``DataFeeder``): the loop drops them once they are uploaded, and
        the feeder writes a later batch into them when the upload has let
        go of them too — a reader sees none of this, its samples are only
        read.

        ``elastic=True`` runs the loop as an ELASTIC WORKER
        (paddle_tpu.elastic.worker, doc/elasticity.md): the launcher
        env is resolved and validated, the (host, chip)/comm plan is
        re-computed for this generation's world and the program
        transpiled onto its mesh, checkpoints pair with task-master
        snapshots, and — when ``task_reader`` is given (``payload ->
        one minibatch``) — batches lease through the supervisor-owned
        task master with exactly-once commit accounting. Without
        ``task_reader`` the plain ``reader`` drives a lease-free worker
        (same role minus the master). Composes with the
        ``comm_overlap``/``comm_policy`` flags in one job.

        Two loop-level failure policies, both off by default:
        ``FLAGS.step_timeout_s`` arms the step-hang watchdog (a wedged
        step exits 75 for a transient supervisor restart) and
        ``FLAGS.loss_skip_budget`` arms the numeric guardrails
        (non-finite/spiking losses skip the batch, budget exhaustion
        rewinds to the last checkpoint once per window)."""
        from . import profiler as _prof
        from .flags import FLAGS
        use_elastic = FLAGS.elastic if elastic is None else bool(elastic)
        if reader is None and not (use_elastic and task_reader is not None):
            raise ValueError("train() needs a reader (or elastic=True "
                             "with task_reader=)")
        worker = None
        if use_elastic:
            from .elastic.worker import ElasticWorker
            if task_reader is not None and reader is not None:
                raise ValueError(
                    "train(elastic=True) takes EITHER a plain reader "
                    "(lease-free worker) OR task_reader= (master-leased "
                    "batches), not both")
            worker = ElasticWorker(
                self, task_reader=task_reader,
                root=elastic_root or self.checkpoint_dir,
                on_commit=on_commit, on_skip=on_skip)
            try:
                worker.setup()
                # startup first, PAIRED resume second (the flat
                # newest-wins restore of _maybe_init would ignore the
                # snapshot pairing)
                self._maybe_init(load=False)
                worker.resume()
                self._elastic_worker = worker
                if on_resume is not None:
                    # the restored-state hook (the chaos harness writes
                    # its probe-continuity anchor here)
                    on_resume(worker)
                if task_reader is not None:
                    reader = worker.reader()
            except BaseException:
                # setup() may already have REGISTERED a heartbeating
                # master client; a failure before the loop's own
                # finally owns the worker must not leak that phantom
                # membership until process exit
                worker.close()
                raise
        self._maybe_init()
        user_handler = event_handler or (lambda e: None)

        def handler(event):
            with _prof.span("handler"):
                user_handler(event)
        log_period = FLAGS.log_period
        # the master answers "wait" while any lease is pending, and the
        # lease of batch n is pending until commit(n): asked for batch n+1
        # before that, this thread would wait on its own commit until the
        # lease lapsed. The lease path takes batch n+1 after commit(n)
        leased = worker is not None and task_reader is not None
        watchdog = None
        if FLAGS.step_timeout_s > 0:
            watchdog = StepWatchdog(FLAGS.step_timeout_s)
            if worker is not None:
                # the lease wait ticks a live deadline (idle != hung)
                worker.watchdog = watchdog
        guard = None
        if FLAGS.loss_skip_budget > 0:
            base_rewind = (worker.rewind if worker is not None
                           else self._guard_rewind)

            def rewind_fn():
                # a checkpoint restore is recovery, not a step: the
                # step deadline pauses around it like it does around
                # the symmetric checkpoint save
                if watchdog is not None:
                    watchdog.disarm()
                try:
                    return base_rewind()
                finally:
                    if watchdog is not None:
                        watchdog.arm("guard-rewind")

            guard = NumericGuard(
                FLAGS.loss_skip_budget,
                spike_factor=FLAGS.loss_spike_factor,
                rewind_fn=rewind_fn)
        # a fresh train() gets a fresh preemption state: the flag from a
        # previous preempted run must not end this one after one batch
        self.preempted = False
        self._preempt_at = None
        old_sigterm = None
        hook_installed = False
        if self.checkpoint_dir or (worker is not None and worker.root):
            hook_installed, old_sigterm = self._install_preemption_hook()
        steps_done = 0
        try:
            for pass_id in range(num_passes):
                handler(BeginPass(pass_id))
                costs = []
                batch_id = -1
                if watchdog is not None:
                    # the deadline covers the first batch's feed+compile
                    # too — a reader wedged before its first yield is
                    # still a hang
                    watchdog.arm("pass%d/start" % pass_id)
                batches = _Lookahead(reader(), self)
                # the handles of step n+1 while it is dispatched ahead
                # (None: the step is dispatched in its own iteration), and
                # whether steps are run held back: never on the lease
                # path, which has no batch n+1 to dispatch ahead on
                ahead = None
                holds = not leased and self.exe.can_hold(self.main_program)
                last_iter_t = None
                commit_ms_last = 0.0
                for batch_id, data in _step_spans(batches, pass_id,
                                                  steps_done):
                    # the gray-failure heartbeat: the wall delta
                    # between iteration starts (reader wait + dispatch
                    # + any injected stall — a device-timer-only
                    # number is blind to these) MINUS the
                    # commit/checkpoint span: that is legitimate
                    # per-role overhead (only the lease owner pays
                    # it), not gray slowness — the step watchdog
                    # pauses around it for the same reason
                    now_t = time.monotonic()
                    if worker is not None and \
                            last_iter_t is not None:
                        worker.publish_heartbeat(
                            max((now_t - last_iter_t) * 1e3
                                - commit_ms_last, 0.0))
                    last_iter_t = now_t
                    commit_ms_last = 0.0
                    handler(BeginIteration(pass_id, batch_id))
                    if watchdog is not None:
                        watchdog.ping("pass%d/batch%d"
                                      % (pass_id, batch_id))
                    # chaos lever: delay = a wedged step (the
                    # watchdog's quarry), raise = a step failure
                    # that propagates (the supervisor's
                    # transient-restart path)
                    fault_point("trainer.step")
                    # this step was dispatched ahead, in the last
                    # iteration: its state goes to the scope now. Where a
                    # handler, a rewind or a checkpoint load wrote the
                    # scope since, the executor drops it instead and the
                    # step runs again, from what the scope holds
                    outs, ahead = ahead, None
                    if outs is None or not self.exe.commit():
                        # step 0 of a pass, a step that was dropped,
                        # and every step of a program the executor cannot
                        # hold back: dispatched now (held steps committed
                        # at once: one executable for every step). data
                        # is a device-resident feed dict (from the
                        # lookahead); the call returns once the step is
                        # enqueued
                        outs = self._dispatch(data, hold=True) \
                            if holds else None
                        holds = outs is not None and self.exe.commit()
                        if not holds:
                            outs = self._dispatch(data)
                    cost = outs[0]  # lazy AsyncFetch
                    # the device computes this step while the host
                    # stacks and uploads the next batch
                    if not leased and batches.take():
                        ready = int(cost.ready)
                        es = self.exe.stats
                        es["lookahead_steps"] += 1
                        es["lookahead_loss_ready"] += ready
                        _prof.update_pipeline_counters(
                            lookahead_steps=1,
                            lookahead_loss_ready=ready)
                        if holds:
                            # step n+1 from the state the scope holds, in
                            # the device's queue before step n's loss is
                            # waited for; its new state is held back so
                            # that EndIteration(n) reads state n
                            ahead = self._dispatch(batches.held, hold=True)
                            holds = ahead is not None
                            if holds:
                                es["ahead_steps"] += 1
                                _prof.update_pipeline_counters(
                                    ahead_steps=1)
                    # the step's sync point: a wedged device surfaces
                    # HERE, inside the armed deadline
                    cost = materialize_scalar(cost)
                    outs = materialize(outs)
                    skipped = False
                    if guard is not None:
                        skipped = guard.check(
                            cost, pass_id=pass_id,
                            batch_id=batch_id) != "ok"
                        if watchdog is not None:
                            watchdog.ping(
                                "pass%d/batch%d/guarded"
                                % (pass_id, batch_id))
                    counted = True
                    if worker is not None:
                        # lease commit + (on the cadence) the
                        # paired checkpoint — not a step, so the
                        # step deadline pauses around it
                        if watchdog is not None:
                            watchdog.disarm()
                        commit_t0 = time.monotonic()
                        counted = worker.commit(cost=cost,
                                                skipped=skipped)
                        commit_ms_last = (time.monotonic()
                                          - commit_t0) * 1e3
                        if watchdog is not None:
                            watchdog.arm("pass%d/batch%d/next"
                                         % (pass_id, batch_id))
                    if not skipped and counted:
                        # a lapsed lease (counted=False) is a
                        # batch the audited timeline disowns —
                        # a survivor re-runs it; pass metrics
                        # must agree with the lease accounting
                        costs.append(cost)
                    if log_period and \
                            (batch_id + 1) % log_period == 0:
                        # the reference's per-log_period batch line
                        # (reference: TrainerInternal.cpp:159-171)
                        window = costs[-log_period:]
                        if window:
                            print("pass %d batch %d: cost=%.6f "
                                  "(avg %.6f)"
                                  % (pass_id, batch_id, window[-1],
                                     float(np.mean(window))))
                        if watchdog is not None:
                            watchdog.ping("pass%d/batch%d/log"
                                          % (pass_id, batch_id))
                    handler(EndIteration(pass_id, batch_id, cost,
                                         {"fetches": outs[1:]}))
                    if self.preempted:
                        break
                steps_done += batch_id + 1
                if watchdog is not None:
                    watchdog.disarm()
                # a guardrail-skipped batch's update may still sit in
                # the params (non-finite case) until a rewind or an
                # accepted batch clears it: persisting that state would
                # make the poison the newest resume point
                tainted = guard is not None and guard.tainted
                if tainted and (worker is not None and worker.root
                                or self.checkpoint_dir):
                    record_durable_event(
                        "checkpoint_skipped_tainted",
                        site="trainer.guard", pass_id=pass_id,
                        batch_id=batch_id, preempted=self.preempted)
                if self.preempted:
                    if tainted:
                        return
                    if worker is not None and worker.root:
                        self._preempt_checkpoint(
                            pass_id, batch_id,
                            save_fn=worker.pair_checkpoint)
                    elif self.checkpoint_dir:
                        self._preempt_checkpoint(pass_id, batch_id)
                    return
                if tainted:
                    pass                      # keep the last clean save
                elif worker is not None:
                    worker.pair_checkpoint()  # pass-end pair (no-op when
                    #                           the cadence already did)
                elif self.checkpoint_dir:
                    self.save_checkpoint()
                handler(EndPass(pass_id,
                                {"avg_cost": float(np.mean(costs))
                                 if costs else float("nan")}))
        finally:
            # preemption, or an exception out of a handler: the step that
            # was dispatched ahead is never committed
            self.exe.drop()
            if watchdog is not None:
                watchdog.close()
            if worker is not None:
                worker.record_stats(self.exe.stats)
                worker.close()
            if hook_installed:
                signal.signal(signal.SIGTERM, old_sigterm)

    def _dispatch(self, feed, hold=False):
        """Enqueue one training step on ``feed``; lazy handles of the
        fetches. ``hold=True``: with its new state held back
        (``Executor.run(hold=True)``), or None where the executor cannot.
        ``run`` is looked up on the instance at every step: a tracer may
        have replaced it."""
        return self.exe.run(self.main_program, feed=feed,
                            fetch_list=self.fetch_list, sync=False,
                            **({"hold": True} if hold else {}))

    def _test_program(self, fetches):
        """Pruned for-test clone: drops backward + optimizer ops so
        evaluation never updates parameters or accumulators (reference:
        the separate test program of Program.clone(for_test=True))."""
        names = tuple(f.name if isinstance(f, ir.Variable) else f
                      for f in fetches)
        cached = getattr(self, "_test_cache", None)
        if cached is None or cached[0] != names:
            pruned = self.main_program.prune(
                feeds=list(self.feeder.feed_names), fetches=names)
            self._test_cache = (names, pruned)
        return self._test_cache[1]

    def test(self, reader, fetch_list=None, program=None):
        """Average fetched metrics over a reader (reference:
        v2/trainer.py test / fluid book tests' test loops). Feeds and
        runs ``program`` (default: the pruned for-test clone, which
        writes no parameter or accumulator) one batch after the other on
        this thread."""
        self._maybe_init()
        fetches = fetch_list or self.fetch_list
        program = program or self._test_program(fetches)
        acc, n = None, 0
        for data in reader():
            # accumulation is O(1) in pass length — a 50k-batch eval
            # must not buffer 50k fetch tensors host- or device-side
            vals = [materialize_scalar(o) for o in self.exe.run(
                program, feed=self.feeder.feed(data), fetch_list=fetches)]
            acc = vals if acc is None else [a + v for a, v in zip(acc, vals)]
            n += 1
        return [a / max(n, 1) for a in (acc or [])]

    def save_checkpoint(self, dirname=None, sharded=False, async_=False,
                        step=None):
        """Default: save/load-op persistables (reference io.py semantics).
        ``sharded``/``async_`` route through paddle_tpu.checkpoint —
        per-shard files under a mesh, background write, atomic + marker
        (the Go pserver checkpoint role)."""
        dirname = dirname or self.checkpoint_dir
        from . import checkpoint as _ckpt
        t0 = time.monotonic()
        try:
            if sharded or async_:
                return _ckpt.save_checkpoint(dirname, self.main_program,
                                             step=step, async_=async_)
            os.makedirs(dirname, exist_ok=True)
            # a stale manifest in the same dir would shadow this newer
            # persistables save on resume (_maybe_init prefers the
            # manifest layout); retire it
            for fn in (_ckpt._COMPLETE, _ckpt._MANIFEST):
                p = os.path.join(dirname, fn)
                if os.path.exists(p):
                    os.remove(p)
            _io.save_persistables(self.exe, dirname,
                                  main_program=self.main_program)
        finally:
            # the preemption drain budgets its final save against this
            # (an async_ save measures only the device->host snapshot —
            # still the synchronous part a drain would wait on)
            self._last_ckpt_secs = time.monotonic() - t0

    def save_inference_model(self, dirname, feeded_var_names, target_vars):
        _io.save_inference_model(dirname, feeded_var_names, target_vars,
                                 self.exe, main_program=self.main_program)

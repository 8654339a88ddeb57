"""Step-hang watchdog: a wedged step becomes a restart, never a wedged
gang.

The one failure class the PR-8 elastic supervisor cannot see from the
outside is a worker that stops MAKING PROGRESS without dying: a
collective wedged on a dead peer, a reader stalled on a hung
filesystem, a device that stopped answering. Process exit is the
supervisor's only liveness signal (heartbeats deliberately never kill,
doc/elasticity.md), so a hung step holds the whole gang hostage until
an operator notices.

:class:`StepWatchdog` closes that gap from the INSIDE. The training
loop arms a deadline per step (``FLAGS.step_timeout_s``; default off)
and pings it at every progress point — each batch, and each declared
materialization sync point, since a step is dispatched asynchronously
and that is where a wedged device actually surfaces. A monitor thread (daemon, one
comparison per poll) fires when the deadline lapses:

1. records a durable ``step_hung`` event (``record_durable_event`` —
   the in-memory log dies with the process, the appended
   ``events.jsonl`` line in the elastic state dir does not);
2. dumps the profiler timeline artifact beside it (the post-mortem:
   which phase the loop died in, every subsystem's counters);
3. ``os._exit(STEP_HUNG_EXIT)`` — a NON-ZERO, non-signal exit, so the
   elastic supervisor classifies the death as TRANSIENT and relaunches
   the worker from the paired checkpoint on the restart budget
   (paddle_tpu.elastic.supervisor). ``os._exit`` is deliberate: the
   main thread is by definition stuck, so normal interpreter teardown
   (atexit, thread joins) could itself hang.

The kill action is injectable (``on_hang=``) so tests observe the
firing without losing the process. Fault site ``trainer.step`` with a
``delay`` action is the seeded-hang chaos lever
(``PADDLE_TPU_FAULT_SPEC="trainer.step:delay:nth=3,delay=3600"``).
"""
from __future__ import annotations

import os
import sys
import threading
import time

from .events import record_durable_event

__all__ = ["StepWatchdog", "STEP_HUNG_EXIT"]

# EX_TEMPFAIL: distinctive, non-zero, not 128+N — the elastic
# supervisor reads any rc > 0 as a transient (restartable) death
STEP_HUNG_EXIT = 75


def _default_on_hang(info):
    """Record durably, dump the post-mortem timeline, exit non-zero.
    Never raises: the watchdog thread is the process's last honest
    reporter and must reach ``os._exit`` no matter what."""
    from .. import profiler as _prof
    try:
        _prof.update_trainer_counters(steps_hung=1)
    except Exception:
        pass
    state_dir = os.environ.get("PADDLE_TPU_ELASTIC_STATE")
    timeline = None
    try:
        import tempfile
        out_dir = state_dir if state_dir and os.path.isdir(state_dir) \
            else tempfile.gettempdir()
        timeline = os.path.join(
            out_dir, "step-hung-rank%s-pid%d-timeline.json"
            % (os.environ.get("PADDLE_TPU_PROCESS_ID", "x"), os.getpid()))
        _prof.write_timeline(timeline)
    except Exception:
        timeline = None
    try:
        record_durable_event("step_hung", site="trainer.watchdog",
                             timeline=timeline, **info)
    except Exception:
        pass
    try:
        sys.stderr.write(
            "paddle_tpu step watchdog: no progress for %.1fs at %r — "
            "exiting %d for a supervisor restart (timeline: %s)\n"
            % (info.get("timeout_s", 0.0), info.get("label"),
               STEP_HUNG_EXIT, timeline))
        sys.stderr.flush()
    except Exception:
        pass
    os._exit(STEP_HUNG_EXIT)


class StepWatchdog(object):
    """Per-step progress deadline on a monitor thread.

    ``arm(label)`` starts (or re-starts) the deadline; ``ping(label)``
    re-arms it at every progress point; ``disarm()`` suspends it across
    stretches with no step deadline (checkpoint saves, pass
    boundaries); ``close()`` stops the thread. A lapse calls
    ``on_hang(info)`` exactly once — the default handler never returns.
    """

    def __init__(self, timeout_s, on_hang=None, poll_s=None):
        self.timeout_s = float(timeout_s)
        if self.timeout_s <= 0:
            raise ValueError("step watchdog needs timeout_s > 0, got %r"
                             % timeout_s)
        self._on_hang = on_hang or _default_on_hang
        self._poll_s = (float(poll_s) if poll_s is not None
                        else max(min(self.timeout_s / 4.0, 1.0), 0.02))
        self._lock = threading.Lock()
        self._deadline = None        # None = disarmed
        self._label = None
        self._fired = False
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._watch, name="paddle_tpu-step-watchdog",
            daemon=True)
        self._thread.start()

    # -- loop-side API -------------------------------------------------------
    def arm(self, label="step"):
        with self._lock:
            self._deadline = time.monotonic() + self.timeout_s
            self._label = label

    ping = arm  # every progress point re-arms the same deadline

    def tick(self, label="wait"):
        """Progress signal that re-arms ONLY an already-armed deadline.
        For waits that are progress-like but must not resurrect a
        deliberately suspended deadline — the elastic lease wait ticks
        from the feed thread while peers hold the remaining tasks (an
        idle worker is not a hung worker), and a concurrent ``disarm``
        window (checkpoint save) must stay suspended."""
        with self._lock:
            if self._deadline is not None:
                self._deadline = time.monotonic() + self.timeout_s
                self._label = label

    def disarm(self):
        with self._lock:
            self._deadline = None
            self._label = None

    @property
    def fired(self):
        return self._fired

    def close(self):
        self.disarm()
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- monitor thread ------------------------------------------------------
    def _watch(self):
        while not self._stop.wait(self._poll_s):
            with self._lock:
                deadline, label = self._deadline, self._label
                lapsed = (deadline is not None
                          and time.monotonic() > deadline)
                if lapsed:
                    # fire once; suspend so a test-injected on_hang that
                    # RETURNS does not re-fire every poll
                    self._deadline = None
                    self._fired = True
            if lapsed:
                self._on_hang({
                    "label": label, "timeout_s": self.timeout_s,
                    "rank": os.environ.get("PADDLE_TPU_PROCESS_ID"),
                    "generation": os.environ.get(
                        "PADDLE_TPU_ELASTIC_GENERATION"),
                })

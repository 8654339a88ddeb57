"""Framework-wide fault tolerance: retry budgets, fault injection,
degraded-mode records.

The reference's distributed story is fault tolerance end to end — the Go
master leases RecordIO chunks with timeouts/failure caps and snapshots to
etcd, the pserver checkpoints and re-registers, trainers redial — and
this package is that posture rebuilt as one subsystem (HiCCL, arxiv
2408.05962, argues the same: coordination layers deserve explicit
failure semantics, not scattered try/excepts):

- :mod:`.retry` — ``RetryPolicy``: the declared budget every
  cross-host/cross-process edge spends (dataset cache lookups,
  pserver RPC).
- :mod:`.faults` — deterministic injection registry; tests and the
  ``PADDLE_TPU_FAULT_SPEC`` env var arm named sites to raise, delay, or
  corrupt at the Nth hit.
- :mod:`.events` — the process-local record of every degradation, so
  "it kept going" is auditable.
- :mod:`.supervise` — the ONE slot-lifecycle idiom (restart budget +
  crash-loop window + generation bump + SIGTERM->SIGKILL escalation)
  both the elastic trainer supervisor and the serving replica pool
  consume, so their judgement cannot drift.
- :mod:`.grayfail` — ``SkewDetector``: the ONE robust latency-skew
  judgement (median+MAD baseline, breach streaks, hysteresis) the
  elastic supervisor and the serving router both consume to notice
  members that are alive but consistently slower than their peers —
  the gray failures binary health checks cannot see.
- :mod:`.watchdog` — ``StepWatchdog``: the per-step progress deadline
  that turns a wedged training step (hung collective, stalled reader)
  into a recorded ``step_hung`` + non-zero exit the elastic supervisor
  restarts transiently — a hang becomes a restart, never a wedged gang.
- :mod:`.guardrails` — ``NumericGuard``: non-finite/spiking losses
  skip the batch under a consecutive-skip budget, exhaustion rewinds
  to the last checkpoint once per window before giving up.

Consumers elsewhere in the package: checkpoint.py (CRC + fallback to the
previous complete checkpoint), trainer.py (SIGTERM preemption
checkpoint), parallel/async_sgd.py (bounded reconnect, then recorded
degraded continuation), paddle_tpu.native.Reader (reader.next site),
and dataset/common.py.
"""
from .events import (  # noqa: F401
    record_event, record_durable_event, events, clear_events,
)
from .retry import (  # noqa: F401
    RetryPolicy, RetryError, AttemptTimeout, retry,
)
from .faults import (  # noqa: F401
    FaultError, SITE_TABLE, arm, disarm, reset, hits, armed,
    fault_point, parse_fault_spec, load_fault_spec,
)
from .supervise import (  # noqa: F401
    SlotDecision, SlotSupervision, escalate_stop, signal_quietly,
)
from .grayfail import GrayVerdict, SkewDetector  # noqa: F401
from .watchdog import StepWatchdog, STEP_HUNG_EXIT  # noqa: F401
from .guardrails import NumericGuard  # noqa: F401

__all__ = [
    "record_event", "record_durable_event", "events", "clear_events",
    "RetryPolicy", "RetryError", "AttemptTimeout", "retry",
    "FaultError", "SITE_TABLE", "arm", "disarm", "reset", "hits",
    "armed", "fault_point", "parse_fault_spec", "load_fault_spec",
    "SlotDecision", "SlotSupervision", "escalate_stop",
    "signal_quietly", "GrayVerdict", "SkewDetector",
    "StepWatchdog", "STEP_HUNG_EXIT", "NumericGuard",
]

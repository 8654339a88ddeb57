"""Deterministic fault injection, keyed by site name.

The reference proves its fault tolerance by killing things: the Go
master's tests drop workers mid-lease and watch the chunk requeue
(go/master/service_internal_test.go role), and paddle_tpu already does
that ad hoc for the native task master. This module makes the technique
a first-class, *declarative* surface: production code calls
``fault_point("site.name", payload)`` at its failure-relevant edges, and
tests — or an operator chaos-testing a cluster via the
``PADDLE_TPU_FAULT_SPEC`` env var — arm a site to raise, delay, or
corrupt at the Nth hit. Disarmed sites cost one dict lookup.

Instrumented sites (grow this list with the codebase):

========================  ====================================================
site                      where
========================  ====================================================
``checkpoint.write``      every shard/manifest byte-blob before it hits disk
                          (corrupt-able: models bit-rot AFTER the CRC was
                          computed)
``checkpoint.load``       each shard read back (raise/delay)
``async_sgd.push_grads``  trainer->pserver gradient push, per RPC attempt
``async_sgd.pull_params`` pserver->trainer parameter pull, per RPC attempt
``reader.next``           each record out of the native recordio reader
``dataset.download``      each dataset cache-lookup attempt
``serving.dispatch``      the micro-batcher's device dispatch, per batch,
                          before run/run_many (a raise fails that batch's
                          requests with a recorded batch_failed event —
                          the dispatch loop survives; a delay models a
                          slow device and backs the queue up into
                          admission control)
``serving.reload``        model-registry warm-up, per (re)load, before
                          the jit pre-trigger (a raise on a hot reload
                          rolls back to the serving version with a
                          recorded reload_rollback event)
``serving.generate``      the generation engine's device edges, hit
                          once per prefill and once per fused decode
                          step: a raise at prefill fails THAT request
                          (generate_failed event, slot and pages
                          recycled); a raise at the decode step fails
                          the running sequences (their cache rows are
                          suspect) and the engine loop keeps admitting
                          and serving — the serving.dispatch contract,
                          generation-shaped; a delay models a slow
                          device and stretches inter-token latency
                          into the deadline shed path
``serving.sample``        the generation engine's fused-face build
                          (device-side sampling jits, once per engine
                          construction with serve_device_sample on): a
                          raise degrades THAT engine to host-side
                          sampling for its lifetime with a recorded
                          device_sample_degraded event — same tokens
                          under greedy, the loop keeps serving; never
                          a crash
``serving.speculate``     the speculative-decoding draft side
                          (paddle_tpu.serving.speculative), hit at
                          draft-engine build, per draft prefill, and
                          per propose round: a raise ANYWHERE degrades
                          that engine to plain fused decode for its
                          lifetime with a recorded
                          ``speculation_degraded`` event — a perf
                          regression (no drafted tokens), never an
                          outage; running sequences are unharmed
                          because only the draft's own pool is at
                          stake, and greedy output is token-identical
                          either way
``serving.route``         the router's proxy edge
                          (paddle_tpu.serving.router), hit once per
                          proxied replica attempt, before the upstream
                          POST: a raise is indistinguishable from a
                          dead replica — that attempt fails over to
                          the next-best replica with a recorded
                          ``route_failover`` event and the router
                          keeps serving (never a crash); a delay
                          models a slow fabric and stretches proxied
                          latency into the client's deadline
``serving.autoscale``     the closed-loop autoscaler's control tick
                          (paddle_tpu.serving.autoscale), hit once per
                          tick before any decision: a raise — armed or
                          real — records ``autoscale_degraded`` and
                          freezes the fleet at its current size (no
                          more grows/shrinks); the router keeps
                          serving — a dead controller is a sizing
                          regression, never an outage; a delay models
                          a slow control plane and stretches the
                          reaction time, not correctness
``serving.prefix``        copy-on-write prefix sharing
                          (paddle_tpu.serving.prefix), hit at cache
                          build and per prefix match: a raise degrades
                          that engine to plain no-sharing private
                          pages for its lifetime with a recorded
                          ``prefix_degraded`` event — a memory-
                          economics regression (every request pays
                          full-price pages again), never an outage;
                          running sequences and greedy outputs are
                          bit-identical with sharing on or off
``serving.ship``          the disaggregated prefill->decode handoff
                          hop (paddle_tpu.serving.disagg), hit once
                          per shipped artifact before the decode-tier
                          install: a raise loses the HOP, never the
                          request — the original prompt is re-
                          submitted to the decode engine, which re-
                          prefills locally (slower, bit-identical
                          output) with a recorded ``handoff_failed``
                          event; overload/pool-exhaustion answers are
                          honest backpressure and propagate unchanged
``comm.quantize``         paddle_tpu.comm, per bucket at the quantised
                          all-reduce BUILD (trace time — the traced
                          collectives never re-enter the host): a raise
                          degrades that bucket to full precision for
                          the step function's lifetime, with a recorded
                          ``comm_degraded`` event; the step build
                          survives (runtime dynamic-range overflows
                          take the in-jit full-precision branch and are
                          surfaced by comm.record_step_stats instead)
``comm.bucket_roundtrip`` paddle_tpu.comm bucket-plan build, per
                          all_reduce_grads trace: a raise degrades the
                          whole sync to the unbucketed per-leaf path
                          (policy ``none`` shape) with a recorded
                          ``comm_degraded`` event
``comm.overlap``          paddle_tpu.comm.overlap staged-step build,
                          per step-function trace (comm_overlap=1): a
                          raise degrades that build to the serialized
                          sync-then-update path with a recorded
                          ``comm_degraded`` event — overlap is an
                          optimisation, never a correctness dependency
``comm.gspmd``            not a fault_point: the SITE recorded on the
                          ``comm_degraded`` event when the Executor's
                          explicit-comm build (FLAGS.comm_gspmd) finds
                          a program it cannot hold the contract for
                          and falls back to the plain GSPMD jit
``tune.candidate``        paddle_tpu.tune autotune loop, per candidate
                          config, before build/compile: a raise is
                          indistinguishable from a real candidate
                          failure — recorded as a failed candidate +
                          ``tune_candidate_failed`` event, skipped, the
                          loop survives and still picks a winner from
                          the rest (stock XLA is always in the race)
``tune.cache``            paddle_tpu.tune winner-cache write, per
                          persist, between entry-CRC computation and
                          disk (corrupt-able, the checkpoint.write
                          convention): the next load DETECTS the rot,
                          drops the file/entry with a recorded
                          ``tune_cache_corrupt`` event, and dispatch
                          falls back to default-config/stock-XLA until
                          a re-tune repopulates
``elastic.heartbeat``     the elastic supervisor's health sweep, per
                          sweep: a raise models a flapping
                          heartbeat/registry probe — counted and
                          recorded (``elastic_heartbeat_failed``
                          event), the sweep continues; worker LIVENESS
                          decisions stay on process exit, so a flaky
                          probe can never kill a healthy job
``elastic.replan``        paddle_tpu.elastic.replan, per mesh/comm
                          re-plan for a (survivor) world: a raise
                          degrades the plan to the flat hosts=1
                          factorisation (topology-blind but always
                          correct) with a recorded
                          ``elastic_degraded`` event — training
                          continues on the survivors either way
``elastic.resume``        paddle_tpu.elastic.resume resume-point
                          resolution, per resolution: a raise marks
                          the newest checkpoint+snapshot pair
                          unusable — the walk falls through to the
                          next-older complete pair with a recorded
                          ``elastic_degraded`` event
``trainer.step``          the Trainer.train loop, once per training
                          step before the Executor dispatch: a delay
                          models a WEDGED step (a hung collective, a
                          stalled device) — with ``FLAGS.
                          step_timeout_s`` set, the step watchdog
                          trips, records a durable ``step_hung``
                          event, dumps the profiler timeline and
                          exits 75 so an elastic supervisor restarts
                          the worker transiently; a raise models a
                          step failure and propagates out of
                          ``train()`` (non-zero exit -> the same
                          transient-restart path)
========================  ====================================================

Spec grammar (env var or ``load_fault_spec`` string)::

    site:action[:key=value[,key=value...]][;site:action[...]]...

    action  = raise | delay | corrupt
    nth     = 1-based hit that triggers (default 1); '*' = every hit
    times   = how many consecutive hits fire (default 1); '*' = unbounded
    delay   = seconds (delay action)
    exc     = exception class name from builtins (raise action;
              default FaultError)
    message = exception text (raise action; '_' stands for space)
    seed    = corruption determinism seed (corrupt action)

e.g. ``PADDLE_TPU_FAULT_SPEC="checkpoint.write:corrupt:nth=2,seed=7;``
``async_sgd.push_grads:raise:nth=1,times=2,exc=ConnectionError"``.

Hit counting starts when a site is armed (disarmed sites are not
counted — the fast path must stay a lookup). All mutation is
lock-protected; ``fault_point`` itself is thread-safe.
"""
from __future__ import annotations

import builtins
import random
import threading
import time

from .events import record_event

__all__ = ["FaultError", "arm", "disarm", "reset", "hits", "armed",
           "fault_point", "parse_fault_spec", "load_fault_spec",
           "SITE_TABLE"]

_ENV_VAR = "PADDLE_TPU_FAULT_SPEC"
_ACTIONS = ("raise", "delay", "corrupt")

# The machine-readable face of the docstring table above: site ->
# (defining module under paddle_tpu/, armable, delay_documented).
# ``armable=False`` marks names that are only EVENT sites (recorded on
# degradation events but never a ``fault_point`` call).
# ``delay_documented=True`` marks the sites whose docstring row
# documents DELAY semantics — the slow-device/slow-rank model the
# gray-failure chaos legs (benchmark/chaos_run.py CHAOS_SLOW_RANK,
# benchmark/load_bench.py gray_leg) arm to fake a gray member.
# tests/test_trainer_resilience.py walks this registry and asserts
# code, this table, the docstring table and cluster/README.md agree —
# drift between them is a test failure, not a doc rot.
SITE_TABLE = {
    "checkpoint.write": ("checkpoint.py", True, False),
    "checkpoint.load": ("checkpoint.py", True, False),
    "async_sgd.push_grads": ("parallel/async_sgd.py", True, False),
    "async_sgd.pull_params": ("parallel/async_sgd.py", True, False),
    "reader.next": ("native/__init__.py", True, False),
    "dataset.download": ("dataset/common.py", True, False),
    "serving.dispatch": ("serving/batcher.py", True, True),
    "serving.reload": ("serving/registry.py", True, False),
    "serving.generate": ("serving/generator.py", True, True),
    "serving.sample": ("serving/generator.py", True, False),
    "serving.speculate": ("serving/speculative.py", True, False),
    "serving.route": ("serving/router.py", True, True),
    "serving.autoscale": ("serving/autoscale.py", True, True),
    "serving.prefix": ("serving/prefix.py", True, False),
    "serving.ship": ("serving/disagg.py", True, False),
    "comm.quantize": ("comm/allreduce.py", True, False),
    "comm.bucket_roundtrip": ("comm/bucket.py", True, False),
    "comm.overlap": ("comm/overlap.py", True, False),
    "comm.gspmd": ("core/executor.py", False, False),
    "tune.candidate": ("tune/loop.py", True, False),
    "tune.cache": ("tune/cache.py", True, False),
    "elastic.heartbeat": ("elastic/supervisor.py", True, False),
    "elastic.replan": ("elastic/replan.py", True, False),
    "elastic.resume": ("elastic/resume.py", True, False),
    "trainer.step": ("trainer.py", True, True),
}


class FaultError(RuntimeError):
    """Default exception an armed 'raise' site throws."""


class _Fault(object):
    __slots__ = ("site", "action", "nth", "times", "delay", "message",
                 "exc", "seed", "hits", "fired")

    def __init__(self, site, action, nth, times, delay, message, exc, seed):
        self.site = site
        self.action = action
        self.nth = nth          # 1-based first firing hit
        self.times = times      # None = unbounded window
        self.delay = delay
        self.message = message
        self.exc = exc
        self.seed = seed
        self.hits = 0           # counted from arming time
        self.fired = 0

    def should_fire(self):
        if self.hits < self.nth:
            return False
        return self.times is None or self.hits < self.nth + self.times


_lock = threading.Lock()
_faults = {}          # site -> _Fault
_env_loaded = False


def arm(site, action="raise", nth=1, times=1, delay=0.0, message=None,
        exc=None, seed=0):
    """Arm ``site``. The fault fires on hits ``nth .. nth+times-1``
    (1-based, counted from now); ``times=None`` keeps firing forever."""
    if action not in _ACTIONS:
        raise ValueError("action must be one of %r" % (_ACTIONS,))
    if nth < 1:
        raise ValueError("nth is 1-based")
    if exc is not None and not (isinstance(exc, type)
                                and issubclass(exc, BaseException)):
        raise ValueError("exc must be an exception class")
    f = _Fault(site, action, int(nth),
               None if times is None else int(times),
               float(delay), message, exc or FaultError, int(seed))
    with _lock:
        _faults[site] = f
    return f


def disarm(site):
    with _lock:
        return _faults.pop(site, None) is not None


def reset():
    """Disarm everything and forget counters (test teardown)."""
    with _lock:
        _faults.clear()


def hits(site):
    """Hits at ``site`` since arming (0 if not armed)."""
    with _lock:
        f = _faults.get(site)
        return f.hits if f else 0


def armed():
    """Snapshot {site: action} of armed faults."""
    with _lock:
        return {s: f.action for s, f in _faults.items()}


def _corrupt_bytes(data, rng):
    """Flip a deterministic handful of bytes — enough to break any CRC,
    few enough to keep sizes identical (a torn-size fault is the
    _COMPLETE marker's job, not this one's)."""
    buf = bytearray(data)
    if not buf:
        return bytes(buf)
    for _ in range(min(8, len(buf))):
        buf[rng.randrange(len(buf))] ^= 0xFF
    return bytes(buf)


def fault_point(site, payload=None):
    """Declare a failure-relevant edge. Returns ``payload`` (possibly
    corrupted); raises/delays when the site is armed and the hit count is
    inside the firing window. Disarmed cost: one LOCK-FREE dict lookup —
    this sits on hot loops (reader.next, trainer.step), where taking the
    registry lock per call would serialise a prefetch thread against
    arm/disarm and every other instrumented site."""
    _load_env_once()
    if site not in _faults:
        # read-mostly fast path: membership reads on a dict are atomic
        # under CPython, and arming is a rare, test-time event. A racing
        # arm() is picked up on the next hit — counting starts "when a
        # site is armed" only up to that one-call window.
        return payload
    with _lock:
        f = _faults.get(site)
        if f is None:  # disarmed between the lock-free check and here
            return payload
        f.hits += 1
        if not f.should_fire():
            return payload
        f.fired += 1
        # capture EVERYTHING this firing needs while still under the
        # lock: concurrent hits at the same armed site (overlapping
        # async checkpoint saves) would otherwise read each other's
        # f.hits/f.fired and derive the same corruption seed / wrong
        # hit numbers
        action, hits, fired = f.action, f.hits, f.fired
        exc, message, delay, seed = f.exc, f.message, f.delay, f.seed
    record_event("fault_injected", site=site, action=action, hit=fired)
    if action == "raise":
        raise exc(message or
                  "injected fault at %r (hit %d)" % (site, hits))
    if action == "delay":
        time.sleep(delay)
        return payload
    # corrupt: only byte-like payloads carry data to damage; a site that
    # passes nothing just counts the hit
    if payload is None:
        return payload
    # int seed: seeding random.Random with a non-int hashable is
    # deprecated (3.9+) and an error on newer CPythons; hash() of an
    # int tuple is deterministic across processes (PYTHONHASHSEED only
    # perturbs str/bytes hashing)
    rng = random.Random(hash((seed, fired)))
    if isinstance(payload, (bytes, bytearray)):
        return _corrupt_bytes(payload, rng)
    try:
        import numpy as np
        if isinstance(payload, np.ndarray):
            flat = np.frombuffer(_corrupt_bytes(payload.tobytes(), rng),
                                 dtype=payload.dtype)
            return flat.reshape(payload.shape)
    except ImportError:                                 # pragma: no cover
        pass
    raise TypeError("cannot corrupt payload of type %s at %r"
                    % (type(payload).__name__, site))


# -- spec parsing -------------------------------------------------------------

def parse_fault_spec(spec):
    """Parse the grammar into a list of ``arm()`` kwarg dicts (pure
    function; raises ValueError with the offending entry on bad input)."""
    out = []
    for entry in spec.split(";"):
        entry = entry.strip()
        if not entry:
            continue
        parts = entry.split(":", 2)
        if len(parts) < 2:
            raise ValueError("bad fault entry %r (want site:action[:kv])"
                             % entry)
        site, action = parts[0].strip(), parts[1].strip()
        if action not in _ACTIONS:
            raise ValueError("bad action %r in %r" % (action, entry))
        kw = {"site": site, "action": action}
        if len(parts) == 3 and parts[2].strip():
            for pair in parts[2].split(","):
                if "=" not in pair:
                    raise ValueError("bad key=value %r in %r"
                                     % (pair, entry))
                k, v = (s.strip() for s in pair.split("=", 1))
                if k == "nth":
                    if v == "*":
                        kw["nth"], kw["times"] = 1, None
                    else:
                        kw["nth"] = int(v)
                elif k == "times":
                    kw["times"] = None if v == "*" else int(v)
                elif k == "delay":
                    kw["delay"] = float(v)
                elif k == "seed":
                    kw["seed"] = int(v)
                elif k == "message":
                    kw["message"] = v.replace("_", " ")
                elif k == "exc":
                    e = getattr(builtins, v, None)
                    if not (isinstance(e, type)
                            and issubclass(e, BaseException)):
                        raise ValueError("exc %r is not a builtin "
                                         "exception (in %r)" % (v, entry))
                    kw["exc"] = e
                else:
                    raise ValueError("unknown key %r in %r" % (k, entry))
        out.append(kw)
    return out


def load_fault_spec(spec=None):
    """Arm every entry of ``spec`` (default: the ``PADDLE_TPU_FAULT_SPEC``
    env var). Returns the number of sites armed."""
    import os
    if spec is None:
        spec = os.environ.get(_ENV_VAR, "")
    entries = parse_fault_spec(spec)
    for kw in entries:
        arm(**kw)
    return len(entries)


def _load_env_once():
    """First fault_point arms the env spec, so chaos runs need no code
    change — exactly how the reference reads gflags at process start."""
    global _env_loaded
    if _env_loaded:
        return
    with _lock:
        if _env_loaded:
            return
        _env_loaded = True
    try:
        load_fault_spec()
    except ValueError as e:                              # pragma: no cover
        import warnings
        warnings.warn("ignoring malformed %s: %s" % (_ENV_VAR, e))

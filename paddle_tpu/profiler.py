"""Profiling: per-run host timers + XLA/xplane trace capture.

reference: python/paddle/fluid/profiler.py:20-125 (profiler / cuda_profiler
context managers over the C++ RecordEvent profiler,
paddle/fluid/platform/profiler.h:60-151) and platform/device_tracer.h (CUPTI
timeline). On TPU the per-op host loop doesn't exist — one jitted program is
one device launch — so the host profiler records per-run wall/compile times
per program, and the device timeline comes from jax.profiler's xplane trace
(TensorBoard-compatible), which is the CUPTI-tracer equivalent.

The program's own spans (``span`` / ``step_span``) are events of that same
trace, on its clock: they are always in the code and record only while a
jax.profiler session is open (``xla_trace``). Set-up spans (``setup_span``:
import, build, init, compile) are such spans that are also always kept in
memory, with jax's own compile phases as their children (``setup_spans``).
``device_scopes`` gives the table that names each device op of a compiled
step by its forward / backward / update scope (doc/diagnostics.md).
"""
from __future__ import annotations

import contextlib
import re
import threading as _threading
import time
from collections import defaultdict

import jax
import jax.monitoring

__all__ = ["timer", "stat_summary", "print_stats", "reset_stats",
           "BarrierStat",
           "span", "step_span", "device_scopes",
           "start_profiler", "stop_profiler", "reset_profiler", "profiler",
           "cuda_profiler", "xla_trace", "profiler_enabled", "record_run",
           "record_op_event", "write_timeline",
           "setup_span", "record_setup_span", "setup_spans",
           "reset_setup_spans",
           "update_pipeline_counters", "pipeline_counters", "part_scope",
           "reset_pipeline_counters",
           "update_serving_counters", "serving_counters",
           "reset_serving_counters",
           "update_comm_counters", "comm_counters", "reset_comm_counters",
           "update_tune_counters", "tune_counters", "reset_tune_counters",
           "update_elastic_counters", "elastic_counters",
           "reset_elastic_counters",
           "update_generation_counters", "generation_counters",
           "reset_generation_counters", "speculation_counters",
           "prefix_counters",
           "update_router_counters", "router_counters",
           "reset_router_counters",
           "update_autoscale_counters", "autoscale_counters",
           "reset_autoscale_counters",
           "update_memory_counters", "memory_counters",
           "reset_memory_counters",
           "update_trainer_counters", "trainer_counters",
           "reset_trainer_counters",
           "update_grayfail_counters", "grayfail_counters",
           "reset_grayfail_counters"]

_enabled = False
_records = defaultdict(list)  # label -> [seconds]
_op_events = []               # chrome-trace X events (eager per-op spans)
_program_analyses = {}        # label -> {flops, bytes, collectives, ...}
_profiled_steps = {}          # label -> compiled step run while profiling
_pipeline_counters = defaultdict(float)  # fetch syncs, cache hits, lookahead
_serving_counters = defaultdict(float)   # online-serving observability
_comm_counters = defaultdict(float)      # gradient-communication observability
_tune_counters = defaultdict(float)      # kernel-autotuning observability
_elastic_counters = defaultdict(float)   # elasticity observability
_generation_counters = defaultdict(float)  # autoregressive-serving observability
_router_counters = defaultdict(float)     # multi-replica-router observability
_autoscale_counters = defaultdict(float)  # closed-loop-autoscaler observability
_memory_counters = defaultdict(float)     # static-memory-planner observability
_trainer_counters = defaultdict(float)    # trainer-loop failure-policy observability
_grayfail_counters = defaultdict(float)   # gray-failure-detection observability
_T0 = time.perf_counter()


def profiler_enabled():
    return _enabled


_phase = "eager"


def set_phase(phase):
    """'eager' = per-op spans are real run time; 'trace' = spans measure
    trace/lowering cost (the jit path runs as one fused program)."""
    global _phase
    _phase = phase


def record_run(label, seconds):
    """Called by Executor.run while profiling is on."""
    if _enabled:
        _records[label].append(seconds)
        t_end = time.perf_counter()
        _op_events.append({
            "name": label, "cat": "program", "ph": "X",
            "ts": (t_end - seconds - _T0) * 1e6, "dur": seconds * 1e6,
            "pid": 0, "tid": 1, "args": {}})


def start_profiler(state="All"):
    """reference: profiler.py start_profiler (state CPU/GPU/All — moot on
    TPU: the device timeline needs xla_trace instead)."""
    global _enabled
    _enabled = True


def reset_profiler():
    _records.clear()
    del _op_events[:]
    _program_analyses.clear()
    _profiled_steps.clear()
    _pipeline_counters.clear()
    _serving_counters.clear()
    _comm_counters.clear()
    _tune_counters.clear()
    _elastic_counters.clear()
    _generation_counters.clear()
    _router_counters.clear()
    _autoscale_counters.clear()
    _memory_counters.clear()
    _trainer_counters.clear()
    _grayfail_counters.clear()


def update_pipeline_counters(**counters):
    """Accumulate the step-feeding observability counters (always on — a
    few dict adds per step/materialisation, not per op). Keys in use:
    ``fetch_sync_count``, ``compile_cache_hits``; of Trainer's default
    loop, ``lookahead_steps``, ``lookahead_loss_ready``, ``ahead_steps``
    and ``ahead_dropped``."""
    for k, v in counters.items():
        _pipeline_counters[k] += float(v)


def pipeline_counters():
    """Snapshot {counter: value} of :func:`update_pipeline_counters`."""
    return dict(_pipeline_counters)


def reset_pipeline_counters():
    _pipeline_counters.clear()


def update_serving_counters(**counters):
    """Accumulate online-serving observability counters (always on — a
    few dict adds per BATCH, not per request-row). Keys in use:
    ``requests``, ``batches``, ``padded_rows``, ``queue_wait_ms``,
    ``shed_overload``, ``shed_deadline``, ``failed``;
    ``max_occupancy`` is kept as a max, not a sum."""
    for k, v in counters.items():
        if k == "max_occupancy":
            _serving_counters[k] = max(_serving_counters[k], float(v))
        else:
            _serving_counters[k] += float(v)


def serving_counters():
    """Snapshot {counter: value} of the online-serving counters."""
    return dict(_serving_counters)


def reset_serving_counters():
    _serving_counters.clear()


def update_comm_counters(**counters):
    """Accumulate gradient-communication observability counters
    (paddle_tpu.comm; a few dict adds per step-BUILD or per recorded
    step, never per collective). Keys in use: ``comm_bytes`` (modelled
    per-chip wire bytes per step), ``comm_payload_bytes``,
    ``comm_buckets``, ``comm_dispatches``, ``comm_builds``; the overlap
    step (comm.overlap) adds ``comm_overlap_builds``,
    ``comm_overlap_buckets_early`` (buckets issued before the final
    one — each data-independent of the remaining backward chain) and
    ``comm_overlap_hidden_bytes_est`` (wire bytes of those early
    buckets — the estimate of what the latency-hiding scheduler can
    hide; an estimate, CPU CI cannot time a real fabric);
    ``comm_quant_fallbacks`` is a cumulative gauge kept as a max, not a
    sum (the comm state already accumulates it across steps)."""
    for k, v in counters.items():
        if k == "comm_quant_fallbacks":
            _comm_counters[k] = max(_comm_counters[k], float(v))
        else:
            _comm_counters[k] += float(v)


def comm_counters():
    """Snapshot {counter: value} of the gradient-communication counters."""
    return dict(_comm_counters)


def reset_comm_counters():
    _comm_counters.clear()


def update_tune_counters(**counters):
    """Accumulate kernel-autotuning observability counters
    (paddle_tpu.tune; a few dict adds per kernel DISPATCH, which happens
    at trace time — once per compile, never per step). Keys in use:
    ``tune_hits`` (cached winner applied), ``tune_misses`` (kernel ran
    its default config), ``tune_fallbacks`` (stock XLA lowering),
    ``tune_loops`` / ``tune_candidates`` (autotune-loop activity from
    the CLI / smoke gate)."""
    for k, v in counters.items():
        _tune_counters[k] += float(v)


def tune_counters():
    """Snapshot {counter: value} of the kernel-autotuning counters."""
    return dict(_tune_counters)


def reset_tune_counters():
    _tune_counters.clear()


def update_elastic_counters(**counters):
    """Accumulate elasticity observability counters (paddle_tpu.elastic;
    a few dict adds per RESIZE/RESUME — rare, operator-visible events,
    never per step). Keys in use: ``elastic_resizes`` (world shrinks),
    ``elastic_lost_ranks``, ``elastic_restarts`` (transient full-world
    relaunches), ``elastic_requeued_tasks`` (the dead worker's leased
    dataset tasks re-queued through the task master),
    ``elastic_resumes`` and ``elastic_resume_ms`` (cross-world
    checkpoint-restore latency), ``elastic_heartbeat_failures``."""
    for k, v in counters.items():
        _elastic_counters[k] += float(v)


def elastic_counters():
    """Snapshot {counter: value} of the elasticity counters."""
    return dict(_elastic_counters)


def reset_elastic_counters():
    _elastic_counters.clear()


def update_trainer_counters(**counters):
    """Accumulate trainer-loop failure-policy observability counters
    (the elastic-worker/watchdog/guardrail machinery; a few dict adds
    per SKIP/REWIND/HANG — operator-visible events, never per step).
    Keys in use: ``batches_skipped`` (numeric-guardrail skips),
    ``guard_rewinds`` (budget-exhaustion checkpoint rewinds),
    ``steps_hung`` (watchdog firings — normally the last counter the
    process ever bumps), ``elastic_tasks_committed`` and
    ``elastic_task_failures`` (lease accounting of the elastic Trainer
    worker), ``preempts_truncated`` (SIGTERM drains that could not fit
    a final checkpoint inside the grace window)."""
    for k, v in counters.items():
        _trainer_counters[k] += float(v)


def trainer_counters():
    """Snapshot {counter: value} of the trainer-loop counters."""
    return dict(_trainer_counters)


def reset_trainer_counters():
    _trainer_counters.clear()


_GEN_MAX_KEYS = frozenset(("gen_max_running", "gen_page_util_max"))


def update_generation_counters(**counters):
    """Accumulate autoregressive-serving observability counters
    (paddle_tpu.serving.generator; a few dict adds per engine STEP or
    per retired request, never per token-row). Keys in use:
    ``gen_requests``, ``gen_completed``, ``gen_prefills``,
    ``gen_decode_steps``, ``gen_tokens`` (generated, prompt excluded),
    ``gen_shed_overload`` / ``gen_shed_deadline`` / ``gen_shed_pool``,
    ``gen_preemptions``, ``gen_failed``;
    ``gen_device_sample_steps`` (decode steps whose sampling ran inside
    the jit), ``gen_host_logit_syncs`` (device edges that materialized
    a full logits row/batch on the host to sample — 0 on the fused
    path), ``gen_kernel_hits`` (decode steps routed through the Pallas
    paged-attention kernel); ``gen_max_running`` and
    ``gen_page_util_max`` are kept as maxima, not sums.

    Speculative decoding adds ``gen_spec_steps`` (decode steps that ran
    as draft-propose / fused-verify rounds), ``gen_draft_tokens``
    (tokens the draft proposed), ``gen_accepted_tokens`` (proposals the
    target's verify accepted — acceptance rate is their ratio, surfaced
    by :func:`speculation_counters`), and ``gen_spec_degraded``
    (speculation dropped to plain decode; fault site
    ``serving.speculate``).

    Prefix sharing and disaggregation add ``gen_prefix_hits`` (prefill
    pages satisfied from the shared cache instead of recomputed),
    ``gen_prefix_published`` (pages a prefill published for reuse),
    ``gen_cow_copies`` (copy-on-write page splits on first divergent
    write), ``gen_prefix_degraded`` (sharing dropped to private pages;
    fault site ``serving.prefix``), ``gen_handoff_installs`` (prefill
    artifacts installed on a decode replica), and ``gen_handoff_failed``
    (handoffs that fell back to re-prefill; fault site
    ``serving.ship``) — surfaced by :func:`prefix_counters`."""
    for k, v in counters.items():
        if k in _GEN_MAX_KEYS:
            _generation_counters[k] = max(_generation_counters[k], float(v))
        else:
            _generation_counters[k] += float(v)


def generation_counters():
    """Snapshot {counter: value} of the autoregressive-serving counters."""
    return dict(_generation_counters)


def speculation_counters():
    """The speculative-decoding slice of the generation counters, plus
    the derived ``acceptance_rate`` (accepted / drafted; 0.0 before any
    speculative round). This is the timeline artifact's ``speculation``
    section — all zeros on a non-speculative engine."""
    g = _generation_counters
    drafted = g.get("gen_draft_tokens", 0.0)
    return {
        "spec_steps": g.get("gen_spec_steps", 0.0),
        "draft_tokens": drafted,
        "accepted_tokens": g.get("gen_accepted_tokens", 0.0),
        "acceptance_rate": (g.get("gen_accepted_tokens", 0.0) / drafted
                            if drafted else 0.0),
        "spec_degraded": g.get("gen_spec_degraded", 0.0),
    }


def prefix_counters():
    """The prefix-sharing / disaggregation slice of the generation
    counters, plus the derived ``hit_rate`` (cache-hit pages over pages
    published + hit; 0.0 before any shared prefill). This is the
    timeline artifact's ``prefix`` section — all zeros on an engine
    without sharing or handoffs."""
    g = _generation_counters
    hits = g.get("gen_prefix_hits", 0.0)
    published = g.get("gen_prefix_published", 0.0)
    return {
        "prefix_hits": hits,
        "prefix_published": published,
        "hit_rate": (hits / (hits + published) if hits + published
                     else 0.0),
        "cow_copies": g.get("gen_cow_copies", 0.0),
        "prefix_degraded": g.get("gen_prefix_degraded", 0.0),
        "handoff_installs": g.get("gen_handoff_installs", 0.0),
        "handoff_failed": g.get("gen_handoff_failed", 0.0),
    }


def reset_generation_counters():
    _generation_counters.clear()


_MEM_MAX_KEYS = frozenset(("mem_predicted_peak_bytes",
                           "mem_measured_live_bytes"))


def update_memory_counters(**counters):
    """Accumulate static-memory-planner observability counters
    (paddle_tpu.analysis.memory; a few dict adds per PREFLIGHT/plan
    build — once per fresh compile, never per step). Keys in use:
    ``mem_preflights`` (executor pre-compile checks run),
    ``mem_plans`` (lint/accounting/elastic plan builds),
    ``mem_predicted_peak_bytes`` and ``mem_measured_live_bytes``
    (``jax.live_arrays`` evidence) — both kept as maxima, so the
    timeline's ``memory`` section reads as the run's high-water
    predicted-vs-actual pair."""
    for k, v in counters.items():
        if k in _MEM_MAX_KEYS:
            _memory_counters[k] = max(_memory_counters[k], float(v))
        else:
            _memory_counters[k] += float(v)


def memory_counters():
    """Snapshot {counter: value} of the static-memory-planner counters."""
    return dict(_memory_counters)


def reset_memory_counters():
    _memory_counters.clear()


_ROUTER_MAX_KEYS = frozenset(("router_peak_load", "router_replicas"))


def update_router_counters(**counters):
    """Accumulate multi-replica-router observability counters
    (paddle_tpu.serving.router/pool; a few dict adds per routed request
    or per supervision event, recorded in the ROUTER process — each
    replica keeps its own serving/generation counters). Keys in use:
    ``router_requests`` (proxied attempts), ``router_failovers``,
    ``router_no_replica`` (503s: no healthy replica),
    ``router_proxy_failed`` (503s: replicas were routable but both
    failover attempts died on transport), ``router_ejects``
    / ``router_readmits`` (health state transitions),
    ``router_reloads`` / ``router_reload_rollbacks`` (rolling hot
    reload outcomes), ``router_replica_restarts`` /
    ``router_replica_lost`` (pool supervision), ``router_gray_ejects``
    / ``router_gray_readmits`` (latency-skew ejections — replica
    answered /healthz 200 but the SkewDetector condemned its proxied
    latency EWMA), ``router_hedges`` / ``router_hedge_wins`` (hedged
    ``:predict`` attempts fired past the p99 deadline, and how many
    answered before the primary); ``router_peak_load``
    (largest per-replica load score observed by the poller) and
    ``router_replicas`` (configured pool size) are kept as maxima."""
    for k, v in counters.items():
        if k in _ROUTER_MAX_KEYS:
            _router_counters[k] = max(_router_counters[k], float(v))
        else:
            _router_counters[k] += float(v)


def router_counters():
    """Snapshot {counter: value} of the multi-replica-router counters."""
    return dict(_router_counters)


def reset_router_counters():
    _router_counters.clear()


_AUTOSCALE_MAX_KEYS = frozenset(("autoscale_replicas",
                                 "autoscale_pressure_max"))


def update_autoscale_counters(**counters):
    """Accumulate closed-loop-autoscaler observability counters
    (paddle_tpu.serving.autoscale; a few dict adds per control tick
    and per decision). Keys in use: ``autoscale_ticks`` (control-loop
    iterations), ``autoscale_ups`` / ``autoscale_downs`` (fleet
    resizes), ``autoscale_breaker_opens`` /
    ``autoscale_breaker_half_opens`` / ``autoscale_breaker_closes``
    (crash-loop circuit-breaker transitions),
    ``autoscale_breaker_refused`` (scale-ups the open breaker vetoed),
    ``autoscale_degraded`` (controller failures degraded to a fixed
    fleet); ``autoscale_replicas`` (largest fleet size reached) and
    ``autoscale_pressure_max`` (largest smoothed pressure observed)
    are kept as maxima."""
    for k, v in counters.items():
        if k in _AUTOSCALE_MAX_KEYS:
            _autoscale_counters[k] = max(_autoscale_counters[k],
                                         float(v))
        else:
            _autoscale_counters[k] += float(v)


def autoscale_counters():
    """Snapshot {counter: value} of the autoscaler counters."""
    return dict(_autoscale_counters)


def reset_autoscale_counters():
    _autoscale_counters.clear()


def update_grayfail_counters(**counters):
    """Accumulate gray-failure-detection observability counters
    (paddle_tpu.resilience.grayfail consumers — the elastic supervisor
    and the serving router; a few dict adds per detector verdict
    change or hedged request). Keys in use: ``gray_suspected`` (verdict
    escalations recorded at either tier), ``gray_mitigated_restarts``
    / ``gray_mitigated_resizes`` (the supervisor's budgeted
    mitigations of a condemned rank), ``gray_ejects`` /
    ``gray_readmits`` (the router's latency-only replica ejections and
    their probation returns), ``router_hedges`` (hedged :predict
    attempts fired past the p99 deadline) and ``router_hedge_wins``
    (hedges whose answer beat the primary)."""
    for k, v in counters.items():
        _grayfail_counters[k] += float(v)


def grayfail_counters():
    """Snapshot {counter: value} of the gray-failure counters."""
    return dict(_grayfail_counters)


def reset_grayfail_counters():
    _grayfail_counters.clear()


def record_op_event(op_type, name, t_start, t_end):
    """Per-op span from the eager interpreter path (on the jit path the
    per-op loop does not exist at run time — op granularity comes from the
    program analysis + xla_trace instead)."""
    _op_events.append({
        "name": "%s:%s" % (op_type, name), "cat": "op", "ph": "X",
        "ts": (t_start - _T0) * 1e6, "dur": (t_end - t_start) * 1e6,
        "pid": 0, "tid": 0,
        "args": {"op_type": op_type, "phase": _phase}})


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def analyse_compiled(compiled, mesh_devices=1):
    """The ``programs`` entry of one compiled program: XLA's compiled cost
    analysis + a census of the collectives GSPMD inserted (the mesh
    'barrier stat': every collective is a cross-device sync point;
    reference: platform/device_tracer.h timeline + profiler.proto role)."""
    entry = {"mesh_devices": int(mesh_devices)}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        entry["flops"] = float(ca.get("flops", 0.0))
        entry["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception:
        pass
    try:
        text = compiled.as_text()
        coll = {}
        for kind in _COLLECTIVES:
            # "<kind>(" appears only at instruction call sites (operand
            # references are "%<kind>.N" — no open paren); async pairs
            # count once via -start
            n = text.count(" %s(" % kind) + text.count(" %s-start(" % kind)
            if n:
                coll[kind] = n
        entry["collectives"] = coll
        entry["barrier_points"] = sum(coll.values())
    except Exception:
        entry.setdefault("collectives", {})
        entry.setdefault("barrier_points", 0)
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            entry["peak_device_memory_bytes"] = int(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0))
    except Exception:
        pass
    return entry


def note_profiled_step(label, step):
    """Called by Executor._run_jit while profiling is on: ``step`` (the
    executor's compiled-step wrapper) ran under ``label``. Its analysis
    is taken on demand (``write_timeline`` / ``get_program_analysis``)
    from the step's kept abstract values, so the executable that runs is
    the one that runs with profiling off."""
    _profiled_steps[label] = step


def _resolve_profiled_steps():
    for label, step in list(_profiled_steps.items()):
        facts = step.facts()
        if facts is not None:
            _program_analyses[label] = facts["analysis"]
        del _profiled_steps[label]


def get_program_analysis(label):
    _resolve_profiled_steps()
    return _program_analyses.get(label)


_SCOPE_RE = re.compile(r"(?:^|/)((?:forward|backward|update)/[^/\"]+)(.*)")
_INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
UNSCOPED = "unscoped"
_PARTS = set()      # names ``part_scope`` has opened in this process


def part_scope(name):
    """A named part INSIDE one op's lowering (``attn`` of
    ``latent_attention``): ``device_scopes()`` keeps it as a second level,
    ``forward/latent_attention/attn``. For ops that span several kernels;
    the name is a plain word, the same in forward and backward."""
    _PARTS.add(name)
    return jax.named_scope(name)


def _part_of(rest):
    """The first ``part_scope`` name in what follows ``<phase>/<op>`` in an
    ``op_name``; autodiff wraps the components (``jvp(attn)``,
    ``transpose(jvp(attn))``, ``checkpoint``), so each is searched for a
    whole word."""
    for comp in rest.split("/"):
        for word in re.findall(r"[A-Za-z_][\w.\-]*", comp):
            if word in _PARTS:
                return word
    return None


def scopes_of_module(text):
    """(module name, {instruction name: scope}) from a compiled module's
    text. The scope is the outermost ``<phase>/<op>`` that
    ``executor.trace_ops`` opened around the op's lowering, with the
    ``part_scope`` the lowering itself opened inside it where there is one
    (``<phase>/<op>/<part>``), read from the
    instruction's own ``op_name`` (a fusion takes the fusion
    instruction's); an instruction the compiler made with no such
    ``op_name`` (copies, async starts; the ``ragged-dot-*`` kernels XLA:TPU
    makes from ``jax.lax.ragged_dot``) is ``unscoped``, never guessed.
    Every computation of the module is read: a ``while`` body's
    instructions run as device ops of their own."""
    module, table = None, {}
    for line in text.splitlines():
        if module is None and line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _INSTRUCTION_RE.match(line)
        if m is None:
            continue
        op_name = _OP_NAME_RE.search(line)
        scope = _SCOPE_RE.search(op_name.group(1)) if op_name else None
        if scope is None:
            table[m.group(1)] = UNSCOPED
            continue
        part = _part_of(scope.group(2)) if _PARTS else None
        table[m.group(1)] = (scope.group(1) + "/" + part if part
                             else scope.group(1))
    return module, table


def device_scopes():
    """``{module name: {instruction name: scope}}`` for every step this
    process compiled and still keeps (the executor's warm cache keeps
    them after the Trainer is gone). A device op's event in the trace
    (line ``XLA Ops``) carries its instruction's name and no scope; this
    table is the join. Each step is lowered again from the abstract values
    of its first call (jax's own caches answer: nothing is compiled a
    second time) and its optimised module's text is read, once per step:
    call it outside any timed window."""
    from .core import executor as _executor
    out = {}
    for step in _executor.compiled_steps():
        facts = step.facts()
        if facts is not None:
            out[facts["module"]] = facts["scopes"]
    return out


def write_timeline(path):
    """Write the structured timeline artifact (JSON):

    - ``trace_events``: chrome-trace (catapult) spans — per-op eager spans
      and per-program run spans; loadable in chrome://tracing / Perfetto —
      the device_tracer.proto analog
      (reference: paddle/fluid/platform/device_tracer.h:30-60).
    - ``host_events``: aggregated wall-time table (profiler.h role).
    - ``programs``: per-compiled-program XLA cost analysis, collective
      census ('barrier stat' for mesh runs) and memory analysis.
    - ``pipeline``: fetch syncs, compile-cache hits and the default
      loop's lookahead counters (doc/feeding.md).
    - ``serving``: online-serving counters (requests, batches, padded
      rows, queue-wait ms, shed counts, max batch occupancy) — the
      coalescing evidence for paddle_tpu.serving.
    - ``comm``: gradient-communication counters (modelled wire bytes,
      bucket/dispatch counts, cumulative quant fallbacks) — the
      fusion/topology evidence for paddle_tpu.comm.
    - ``tune``: kernel-autotuning counters (winner-cache hits/misses/
      stock-XLA fallbacks at dispatch, autotune-loop activity) — the
      adoption evidence for paddle_tpu.tune.
    - ``elastic``: elasticity counters (resizes, lost ranks, requeued
      tasks, resume latency) — the survive-and-resize evidence for
      paddle_tpu.elastic.
    - ``generation``: autoregressive-serving counters (prefills, fused
      decode steps, generated tokens, running-batch/page-utilization
      maxima, sheds/preemptions) — the continuous-batching evidence for
      paddle_tpu.serving.generator.
    - ``router``: multi-replica-router counters (proxied requests,
      failovers, health ejects/readmits, rolling-reload outcomes,
      replica restarts, peak load score) — the fleet evidence for
      paddle_tpu.serving.router.
    - ``autoscale``: closed-loop-autoscaler counters (control ticks,
      scale-ups/downs, breaker transitions, degraded falls, max fleet
      size and max smoothed pressure) — the sizing evidence for
      paddle_tpu.serving.autoscale.
    - ``memory``: static-memory-planner counters (preflights/plans run,
      predicted peak vs ``jax.live_arrays`` measured high-water — the
      predicted-vs-actual evidence for paddle_tpu.analysis.memory).
    - ``trainer``: trainer-loop failure-policy counters (guardrail
      batch skips and rewinds, watchdog step_hung firings, elastic
      lease commits, truncated preemptions — the survival evidence
      for the elastic Trainer worker).
    - ``setup``: the set-up record (``setup_spans()``: import, build,
      init, each step's compile and jax's trace / lower / compile /
      cache-read inside them), one entry per span.
    """
    import json
    _resolve_profiled_steps()
    rows = []
    for label, times in _records.items():
        n = len(times)
        total = sum(times)
        rows.append({"name": label, "calls": n, "total_ms": total * 1e3,
                     "avg_ms": total / n * 1e3,
                     "min_ms": min(times) * 1e3,
                     "max_ms": max(times) * 1e3})
    artifact = {
        "schema": "paddle_tpu.timeline.v1",
        "trace_events": list(_op_events),
        "host_events": rows,
        "programs": dict(_program_analyses),
        "pipeline": dict(_pipeline_counters),
        "serving": dict(_serving_counters),
        "comm": dict(_comm_counters),
        "tune": dict(_tune_counters),
        "elastic": dict(_elastic_counters),
        "generation": dict(_generation_counters),
        "speculation": speculation_counters(),
        "prefix": prefix_counters(),
        "router": dict(_router_counters),
        "autoscale": dict(_autoscale_counters),
        "memory": dict(_memory_counters),
        "trainer": dict(_trainer_counters),
        "grayfail": dict(_grayfail_counters),
        "setup": [{"name": n, "start_s": s, "end_s": e, "parent": p,
                   "args": a} for n, s, e, p, a in setup_spans()],
    }
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
    return artifact


def stop_profiler(sorted_key=None, profile_path=None):
    """Print the aggregated per-program table
    (reference: platform/profiler.h:138-151 PrintProfiler)."""
    global _enabled
    _enabled = False
    rows = []
    for label, times in _records.items():
        n = len(times)
        total = sum(times)
        rows.append((label, n, total, total / n, min(times), max(times)))
    key = {None: lambda r: 0, "default": lambda r: 0,
           "calls": lambda r: -r[1], "total": lambda r: -r[2],
           "ave": lambda r: -r[3], "min": lambda r: -r[4],
           "max": lambda r: -r[5]}.get(sorted_key, lambda r: 0)
    rows.sort(key=key)
    lines = ["%-40s %8s %12s %12s %12s %12s" %
             ("Event", "Calls", "Total(ms)", "Avg(ms)", "Min(ms)", "Max(ms)")]
    for label, n, total, avg, mn, mx in rows:
        lines.append("%-40s %8d %12.3f %12.3f %12.3f %12.3f" %
                     (label, n, total * 1e3, avg * 1e3, mn * 1e3, mx * 1e3))
    report = "\n".join(lines)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(report + "\n")
    print(report)
    return rows


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None,
             timeline_path=None):
    """reference: profiler.py:125 profiler context manager. Pass
    ``timeline_path`` to also write the structured JSON timeline artifact
    (see write_timeline)."""
    start_profiler(state)
    reset_profiler()
    try:
        yield
    finally:
        try:
            if timeline_path:
                write_timeline(timeline_path)
        finally:
            stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """Device-timeline capture. The reference wraps nvprof
    (profiler.py:20-60); the TPU analog is an xplane trace directory
    loadable in TensorBoard/XProf."""
    if output_file:
        with xla_trace(output_file):
            yield
    else:
        yield


def span(name, **args):
    """A host span ``paddle_tpu/<name>`` in the jax profiler's trace, on
    the clock of the device lines. Recorded only while a jax.profiler
    session is open (``xla_trace``); otherwise it costs one activity
    check. It starts where it is made: make it in the ``with``."""
    return jax.profiler.TraceAnnotation("paddle_tpu/" + name, **args)


def step_span(n, **args):
    """The step span ``paddle_tpu/train_step`` with ``step_num=n``: the
    parent of one training step's spans (see ``span``)."""
    return jax.profiler.StepTraceAnnotation("paddle_tpu/train_step",
                                            step_num=n, **args)


# ---------------------------------------------------------------------------
# Set-up spans: ``span`` with a memory. Set-up (import, build, init, the
# first call of each compiled step) happens before anyone opens a profiler
# session, so these are also kept in a list, always, on the clock of
# ``time.monotonic()``; jax's own compile phases become their children.
# They open a handful of times per process, never per step.

_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "xla.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache.read",
}
_setup = []         # [name, start, end (None while open), parent record, args]
_setup_lock = _threading.Lock()
_setup_open = _threading.local()    # .stack: this thread's open records


def _open_stack():
    stack = getattr(_setup_open, "stack", None)
    if stack is None:
        stack = _setup_open.stack = []
    return stack


@contextlib.contextmanager
def setup_span(name, **args):
    """A set-up phase: the ``span`` ``paddle_tpu/<name>`` (in a session's
    trace, on the device lines' clock) that is also always recorded in
    ``setup_spans()``. For work done once (import, build, init, a step's
    compile), never per step."""
    with span(name, **args):
        stack = _open_stack()
        rec = [name, time.monotonic(), None, stack[-1] if stack else None,
               args]
        with _setup_lock:
            _setup.append(rec)
        stack.append(rec)
        try:
            yield
        finally:
            rec[2] = time.monotonic()
            stack.remove(rec)


def record_setup_span(name, start, end=None, **args):
    """Record a set-up span that is over: ``[start, end]`` (``end``: now)
    under the innermost set-up span open on this thread. The spans of that
    parent that lie inside the interval become its children, so spans
    recorded after the fact nest as if they had been open (jax's phases:
    ``cache.read`` inside ``xla.compile``; ``import``). A child of the same
    name (the trace of a jit nested in the one being traced) is merged into
    it: the record keeps one span per outermost phase."""
    end = time.monotonic() if end is None else end
    stack = _open_stack()
    parent = stack[-1] if stack else None
    if parent is not None:
        start = max(start, parent[1])
    new = [name, start, end, parent, args]
    with _setup_lock:
        merged = set()
        j = len(_setup) - 1
        while j >= 0 and _setup[j] is not parent:
            rec = _setup[j]
            if rec[3] is parent:
                # the parent's children run one after another: the first
                # that ended before ``start`` ends the search
                if rec[2] is None or rec[2] <= start or rec[1] >= end:
                    break
                rec[1], rec[2], rec[3] = max(rec[1], start), min(rec[2],
                                                                 end), new
                if rec[0] == name:
                    merged.add(id(rec))
                    del _setup[j]
            elif id(rec[3]) in merged:
                rec[3] = new
            j -= 1
        _setup.append(new)


def _on_jax_event(event, duration, **kw):
    """jax.monitoring listener: a compile phase of jax's, ending now."""
    name = _JAX_PHASES.get(event)
    if name is not None:
        end = time.monotonic()
        args = {"fun": kw["fun_name"]} if "fun_name" in kw else {}
        record_setup_span(name, end - duration, end, **args)


jax.monitoring.register_event_duration_secs_listener(_on_jax_event)


def setup_spans():
    """The set-up record: ``[(name, start, end, parent, args)]`` in the
    order the spans opened (jax's phases: in the order they ended), times
    in ``time.monotonic()`` seconds, ``parent`` the index of the enclosing
    record or None; ``end`` is None while a span is open. Names:
    ``import``, ``build``, ``init``, ``compile`` (``setup_span``) and
    jax's ``jax.trace``, ``jax.lower``, ``xla.compile``, ``cache.read``;
    a jax phase with no set-up span open around it has no parent."""
    with _setup_lock:
        at = {id(rec): i for i, rec in enumerate(_setup)}
        return [(n, s, e, None if p is None else at.get(id(p)), a)
                for n, s, e, p, a in _setup]


def reset_setup_spans():
    with _setup_lock:
        del _setup[:]


@contextlib.contextmanager
def xla_trace(logdir):
    """jax.profiler trace — kernel timeline, HBM usage, per-fusion costs
    (device_tracer equivalent; reference: platform/device_tracer.h:30-60)."""
    jax.profiler.start_trace(str(logdir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def record_event(name):
    """Host-side RAII timer (reference: platform/profiler.h RecordEvent)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        record_run(name, time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Hierarchical stats: the REGISTER_TIMER role (reference: paddle/utils/Stat.h
# — per-name accumulated timers printed as a tree every log period, plus
# BarrierStat for straggler analysis across trainers). Here: nested `timer`
# scopes accumulate (count/total/max) per dotted path; `print_stats` renders
# the tree; `BarrierStat.observe` records per-member arrival times of a
# collective/barrier and reports the straggler gap.

_stat_state = _threading.local()


class _StatNode(object):
    __slots__ = ("count", "total", "max")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.max = 0.0

    def add(self, dt):
        self.count += 1
        self.total += dt
        self.max = max(self.max, dt)


_stats = {}
_stats_lock = _threading.Lock()


@contextlib.contextmanager
def timer(name):
    """Accumulating hierarchical timer: nesting builds dotted paths.

    >>> with profiler.timer("forward"):
    ...     with profiler.timer("conv"):   # recorded as "forward.conv"
    ...         ...
    """
    stack = getattr(_stat_state, "stack", None)
    if stack is None:
        stack = _stat_state.stack = []
    stack.append(name)
    path = ".".join(stack)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        stack.pop()
        with _stats_lock:
            _stats.setdefault(path, _StatNode()).add(dt)


def stat_summary():
    """{path: (count, total_s, avg_s, max_s)} snapshot."""
    with _stats_lock:
        return {p: (n.count, n.total, n.total / n.count, n.max)
                for p, n in _stats.items() if n.count}


def print_stats(file=None):
    """Render the timer tree (REGISTER_TIMER print analog)."""
    import sys as _sys
    out = file or _sys.stdout
    snap = stat_summary()
    if not snap:
        print("(no stats recorded)", file=out)
        return
    print("%-40s %8s %12s %12s %12s" %
          ("timer", "count", "total_ms", "avg_ms", "max_ms"), file=out)
    for path in sorted(snap):
        cnt, tot, avg, mx = snap[path]
        depth = path.count(".")
        label = "  " * depth + path.rsplit(".", 1)[-1]
        print("%-40s %8d %12.3f %12.3f %12.3f" %
              (label, cnt, 1e3 * tot, 1e3 * avg, 1e3 * mx), file=out)


def reset_stats():
    with _stats_lock:
        _stats.clear()


class BarrierStat(object):
    """Straggler analysis for an N-member barrier (reference:
    paddle/pserver/ParameterServer2 BarrierStat / utils/Stat.h): feed each
    member's arrival timestamp per round; report the slowest-minus-fastest
    gap and which member lags most often."""

    def __init__(self, n_members, name="barrier"):
        self.n = n_members
        self.name = name
        self._round = {}
        self._gaps = []
        self._slowest = {}  # member id (any hashable) -> lag-round count
        self._lock = _threading.Lock()

    def observe(self, member, t=None):
        t = time.perf_counter() if t is None else t
        with self._lock:
            self._round[member] = t
            if len(self._round) == self.n:
                ts = self._round
                fastest = min(ts, key=ts.get)
                slowest = max(ts, key=ts.get)
                self._gaps.append(ts[slowest] - ts[fastest])
                self._slowest[slowest] = self._slowest.get(slowest, 0) + 1
                self._round = {}

    def summary(self):
        with self._lock:
            if not self._gaps:
                return {"rounds": 0}
            worst = max(self._slowest, key=self._slowest.get)
            return {
                "rounds": len(self._gaps),
                "mean_gap_s": sum(self._gaps) / len(self._gaps),
                "max_gap_s": max(self._gaps),
                "worst_member": worst,
                "worst_member_lag_rounds": self._slowest[worst],
            }

"""Process-level flag registry: the gflags role.

The reference configures its runtime through three generations of gflags
(legacy set: reference paddle/utils/Flags.cpp:18-95 — use_gpu,
trainer_count, port, trainer_id…; fluid's own: FLAGS_benchmark,
FLAGS_check_nan_inf in framework/executor.cc:29-32, dynload dirs in
platform/dynload/dynamic_loader.cc:25-44) re-exported to Python via
``core.init_gflags`` (pybind.cc). This module is the TPU-native analog:
a typed, declared-with-default registry, overridable three ways —

- environment: ``PADDLE_TPU_FLAGS="check_nan_inf=true,conv_impl=matmul"``
  or per-flag ``PADDLE_TPU_FLAG_CHECK_NAN_INF=true`` (read at first use);
- code: ``flags.FLAGS.check_nan_inf = True`` or ``flags.set_flags({...})``;
- CLI: ``init_from_args(argv)`` consumes ``--name=value`` pairs and returns
  the rest (the InitGflags role, reference: framework/init.cc:25).

Declaring is ``DEFINE_bool/int32/float/string(name, default, help)``;
reading is attribute access on ``FLAGS``. Unknown names raise — the same
contract as gflags' compile-time check.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict, List

__all__ = ["FLAGS", "DEFINE_bool", "DEFINE_int32", "DEFINE_float",
           "DEFINE_string", "set_flags", "get_flags", "init_from_args",
           "flags_guard"]

_TRUE = frozenset(("1", "true", "yes", "on"))
_FALSE = frozenset(("0", "false", "no", "off", ""))


def _parse_bool(s):
    if isinstance(s, bool):
        return s
    t = str(s).strip().lower()
    if t in _TRUE:
        return True
    if t in _FALSE:
        return False
    raise ValueError("not a boolean: %r" % (s,))


class _FlagDef(object):
    __slots__ = ("name", "default", "help", "parse")

    def __init__(self, name, default, help_, parse):
        self.name = name
        self.default = default
        self.help = help_
        self.parse = parse


class _Flags(object):
    """Attribute-style access over the registry; thread-safe writes."""

    def __init__(self):
        object.__setattr__(self, "_defs", {})
        object.__setattr__(self, "_values", {})
        object.__setattr__(self, "_lock", threading.Lock())
        object.__setattr__(self, "_env_loaded", False)

    # -- registry ----------------------------------------------------------
    def _define(self, name, default, help_, parse):
        with self._lock:
            if name in self._defs:
                raise ValueError("flag %r already defined" % name)
            self._defs[name] = _FlagDef(name, default, help_, parse)

    def _load_env_once(self):
        if self._env_loaded:
            return
        with self._lock:
            if self._env_loaded:
                return
            blob = os.environ.get("PADDLE_TPU_FLAGS", "")
            for pair in blob.split(","):
                pair = pair.strip()
                if not pair:
                    continue
                k, _, v = pair.partition("=")
                d = self._defs.get(k.strip())
                if d is not None:
                    self._values[d.name] = d.parse(v.strip())
            for name, d in self._defs.items():
                env_key = "PADDLE_TPU_FLAG_" + name.upper()
                if env_key in os.environ:
                    self._values[name] = d.parse(os.environ[env_key])
            object.__setattr__(self, "_env_loaded", True)

    # -- access ------------------------------------------------------------
    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        self._load_env_once()
        if name not in self._defs:
            raise AttributeError("undeclared flag %r" % name)
        return self._values.get(name, self._defs[name].default)

    def __setattr__(self, name, value):
        self._load_env_once()
        if name not in self._defs:
            raise AttributeError("undeclared flag %r" % name)
        with self._lock:
            self._values[name] = self._defs[name].parse(value)

    def _snapshot(self) -> Dict[str, Any]:
        self._load_env_once()
        return {n: self._values.get(n, d.default)
                for n, d in self._defs.items()}


FLAGS = _Flags()


def DEFINE_bool(name, default, help=""):
    FLAGS._define(name, default, help, _parse_bool)


def DEFINE_int32(name, default, help=""):
    FLAGS._define(name, default, help, int)


def DEFINE_float(name, default, help=""):
    FLAGS._define(name, default, help, float)


def DEFINE_string(name, default, help=""):
    FLAGS._define(name, default, help, str)


def set_flags(d: Dict[str, Any]):
    for k, v in d.items():
        setattr(FLAGS, k, v)


def get_flags(names=None) -> Dict[str, Any]:
    snap = FLAGS._snapshot()
    if names is None:
        return snap
    return {n: snap[n] for n in names}


def init_from_args(argv: List[str]) -> List[str]:
    """Consume ``--flag=value`` / ``--flag value`` pairs for declared flags;
    returns the remaining argv (unknown args pass through untouched)."""
    rest, i = [], 0
    FLAGS._load_env_once()
    while i < len(argv):
        a = argv[i]
        if a.startswith("--"):
            k, eq, v = a[2:].partition("=")
            if k in FLAGS._defs:
                if not eq:
                    if i + 1 >= len(argv):
                        raise ValueError("flag --%s needs a value" % k)
                    v, i = argv[i + 1], i + 1
                setattr(FLAGS, k, v)
                i += 1
                continue
        rest.append(a)
        i += 1
    return rest


class flags_guard(object):
    """Scoped overrides: ``with flags_guard(check_nan_inf=True): ...``."""

    def __init__(self, **over):
        self._over = over
        self._saved = {}

    def __enter__(self):
        for k, v in self._over.items():
            self._saved[k] = getattr(FLAGS, k)
            setattr(FLAGS, k, v)
        return FLAGS

    def __exit__(self, *exc):
        for k, v in self._saved.items():
            setattr(FLAGS, k, v)
        return False


# ---------------------------------------------------------------------------
# Core flag set (the FLAGS_* the rest of the framework consults; the legacy
# trainer flags live on their consumers' call signatures instead)

DEFINE_bool("check_nan_inf", False,
            "scan every op output for NaN/Inf on the per-op path "
            "(reference: FLAGS_check_nan_inf, executor.cc:30)")
DEFINE_bool("benchmark", False,
            "synchronise and time every Executor.run "
            "(reference: FLAGS_benchmark, executor.cc:29)")
DEFINE_string("conv_impl", "conv",
              "dense conv2d lowering: 'conv' (lax.conv) or 'matmul' "
              "(shifted einsums)")
DEFINE_string("conv_layout", "nchw",
              "internal conv execution layout: 'nchw' (the API contract "
              "layout, passed through) or 'nhwc' (transpose to NHWC/HWIO "
              "around the conv — TPU vector lanes ride the channel dim; "
              "XLA cancels the transpose pairs between adjacent convs)")
DEFINE_bool("conv_first_s2d", False,
            "rewrite the ImageNet stem conv (7x7/s2/p3, C_in<=4) as "
            "space-to-depth + 4x4/s1 conv: 4x better MXU lane utilization "
            "on the 3-channel input (the public MLPerf ResNet trick); "
            "numerically exact")
DEFINE_bool("debug_shapes", False,
            "raise (instead of recording) on shape-inference failures")
DEFINE_bool("verify", False,
            "run the paddle_tpu.analysis static verifier on every program "
            "before its first trace (also enabled by PADDLE_TPU_VERIFY=1); "
            "malformed programs raise ProgramVerifyError with the full "
            "PT-code diagnostic list instead of a cryptic trace error. "
            "When the Executor takes the explicit-collective path this "
            "also runs the PT020-PT023 collective-consistency pass over "
            "the traced grad set, and every fresh compile runs the "
            "static memory preflight (analysis.memory, PT030): a "
            "program whose predicted peak HBM exceeds the budget "
            "raises with the residency table BEFORE the XLA compile "
            "instead of dying in an unreadable device OOM. Programs "
            "carrying declared PartitionSpecs (program._shardings) also "
            "run the static sharding preflight (analysis.sharding, "
            "PT040-PT045): invalid or conflicting specs raise with the "
            "sharding plan table before the jit compile")
DEFINE_float("memory_budget_gb", 0.0,
             "per-device HBM budget (GiB) the static memory planner "
             "checks predicted peaks against (lint --memory, the "
             "executor preflight under PADDLE_TPU_VERIFY, the elastic "
             "post-resize audit, PT034 KV-pool sizing). 0 = autodetect "
             "from device.memory_stats()['bytes_limit'] (present on "
             "TPU; usually absent on CPU, where the checks then stay "
             "silent). The CLI --budget-gb overrides per run. The "
             "estimate is static — it ignores XLA fusion/remat and "
             "allocator fragmentation, so a predicted fit is a lower "
             "bound, not a guarantee (doc/diagnostics.md)")
DEFINE_string("sanitize", "",
              "runtime sanitizer modes, comma-separated (union with the "
              "PADDLE_TPU_SANITIZE env var): 'alias' arms the "
              "donation-aliasing checks at the device-transfer choke "
              "points (executor state ingestion, checkpoint restore, "
              "serving KV-pool install) — a numpy-backed buffer headed "
              "for a donated argument position raises SanitizeError "
              "naming the var and entry point; 'locks' swaps the shared "
              "lock constructor (analysis.locks) for instrumented locks "
              "that record the acquisition-order graph and report "
              "cycles (potential deadlocks) and held-across-join "
              "hazards at process exit. Both cost nothing when off; "
              "honest limit: CPU CI can only observe the ORDER "
              "inversion, never the deadlock itself (doc/diagnostics.md)")
DEFINE_string("data_home", "~/.cache/paddle_tpu/dataset",
              "dataset cache directory (reference: v2/dataset common)")
DEFINE_int32("log_period", 100,
             "steps between trainer progress lines "
             "(reference: utils/Flags.cpp log_period)")
DEFINE_string("lstm_impl", "scan",
              "whole-sequence LSTM lowering: 'scan' (lax.scan) or "
              "'pallas' (fused VMEM-resident kernel, standard gate set)")
DEFINE_bool("compile_cache", True,
            "persist XLA compilations via jax's on-disk compilation "
            "cache so repeat runs skip the cold compile; set to 0 to "
            "opt out")
DEFINE_string("compile_cache_dir",
              os.path.join(os.path.dirname(os.path.dirname(
                  os.path.abspath(__file__))), ".jax_cache"),
              "directory for the persistent XLA compilation cache when "
              "JAX_COMPILATION_CACHE_DIR is unset (default "
              "<checkout>/.jax_cache, resolved from the package's own "
              "location: the path is part of the cache key, so it must "
              "not move between runs). Where the environment variable is "
              "set, jax reads it itself and this flag is ignored")
DEFINE_int32("serve_max_batch", 8,
             "online serving (paddle_tpu.serving): most requests the "
             "micro-batcher coalesces into one run_many device dispatch. "
             "Also sets the padding buckets (powers of two capped here) "
             "the model registry pre-compiles at warm-up, so raising it "
             "on a live service only takes effect for models (re)loaded "
             "afterwards")
DEFINE_float("serve_batch_timeout_ms", 2.0,
             "online serving: how long the dispatch loop holds the "
             "OLDEST queued request open for same-model arrivals before "
             "dispatching a partial batch — the latency/throughput "
             "knob: 0 dispatches immediately (lowest latency, occupancy "
             "only from true concurrency); larger values trade p50 "
             "latency for fuller batches")
DEFINE_string("comm_policy", "none",
              "gradient-communication policy for the DP sync path "
              "(paddle_tpu.comm): 'none' = per-parameter pmean, "
              "bit-identical to the pre-comm psum path; 'fused' = "
              "bucketed (comm_bucket_mb) single all-reduce per bucket — "
              "N-param dispatches become N-bucket dispatches; "
              "'hierarchical' = bucketed + topology-routed: intra-host "
              "reduce-scatter -> inter-host ring on 1/chips of the "
              "bytes -> intra-host all-gather (the slow inter-host wire "
              "carries 1/chips of the flat-ring traffic). Policy matrix "
              "and when each wins: doc/comm.md")
DEFINE_float("comm_bucket_mb", 4.0,
             "bucket size bound in MiB for the fused/hierarchical/int8 "
             "comm policies: grad leaves are concatenated, in "
             "declaration order and per dtype, into flat buckets of at "
             "most this many payload bytes (a larger leaf gets its own "
             "bucket). Bigger buckets amortise dispatch latency; "
             "smaller ones overlap earlier with the backward pass")
DEFINE_string("comm_quant", "none",
              "wire precision for the comm policies: 'none' (fp32) or "
              "'int8' (symmetric per-chunk quantisation with fp32 "
              "scales + error-feedback residuals carried in comm state, "
              "EQuARX-style). With comm_policy=hierarchical only the "
              "inter-host leg quantises (stateless); otherwise the "
              "policy promotes to fused buckets. Dynamic-range overflow "
              "falls back to full precision for that step with a "
              "recorded comm_degraded event")
DEFINE_int32("comm_hosts", 0,
             "host count of the (host, chip) factorisation the "
             "hierarchical comm policy routes along; 0 = auto "
             "(jax.process_count() when it divides the data axis, else "
             "flat). Set explicitly to simulate a multi-host topology "
             "on a forced CPU mesh (tools/comm_smoke.py uses 2x4)")
DEFINE_bool("comm_overlap", False,
            "overlap gradient communication with the tail of backward "
            "(paddle_tpu.comm.overlap): the DP step builders issue each "
            "comm bucket's all-reduce in backward-finalisation order, as "
            "its own data-independent collective, and apply that "
            "bucket's parameter update immediately — no bucket waits on "
            "another's collective, so XLA's latency-hiding scheduler "
            "can hide the early buckets behind the remaining backward "
            "chain. 0 (default) keeps the serialized sync-then-update "
            "step, bit-identical to the pre-overlap build. A raise at "
            "fault site comm.overlap degrades to the serialized path "
            "with a recorded comm_degraded event")
DEFINE_float("comm_split_ratio", 0.75,
             "fraction of each large bucket the multipath comm policy "
             "(comm_policy=multipath, FlexLink-style) routes over the "
             "PRIMARY path (flat ring over ICI); the remainder rides "
             "the SECONDARY path (hierarchical inter-host hop over the "
             "comm_hosts factorisation) at the same time, so both "
             "fabrics carry bytes simultaneously. Configure from "
             "measured per-path bandwidths via "
             "comm.measured_split_ratio(primary_gbps, secondary_gbps); "
             "buckets below 64 KiB ride the primary path whole "
             "(splitting them buys nothing and costs a dispatch)")
DEFINE_bool("comm_gspmd", True,
            "route the GSPMD Executor path's data-parallel gradient "
            "sync through the explicit paddle_tpu.comm collectives "
            "(bucketed/hierarchical/quantized per comm_policy) instead "
            "of only modelling the bytes: eligible pure-DP programs "
            "trace under shard_map with comm.all_reduce_grads at the "
            "backward/optimizer boundary, and Executor.stats reports "
            "comm_path='explicit' with stats measured from the traced "
            "plan. Only engages when comm_policy != 'none' (the none "
            "policy keeps the pre-PR GSPMD build bit-identical); "
            "ineligible programs (tensor/ZeRO sharding, batch-coupled "
            "or random ops, non-batch fetches) fall back to the "
            "modelled path with a recorded comm_degraded event. 0 "
            "forces model-only")
DEFINE_bool("tune", True,
            "consult the paddle_tpu.tune winner cache at kernel dispatch "
            "sites: a cached per-(device, shape) winner activates the "
            "Pallas kernel with the winning config (tune_hits); a miss "
            "keeps legacy behavior — the kernel's default config where a "
            "kernel is already flag-enabled (tune_misses), stock XLA "
            "lowering otherwise (tune_fallbacks). 0 disables cache "
            "consultation entirely: dispatch is exactly the pre-tune "
            "build, with fallbacks still counted so the stats say why "
            "nothing was tuned")
DEFINE_string("tune_cache_dir", "~/.cache/paddle_tpu/tune",
              "directory of the persistent kernel-winner cache "
              "(winners.json keyed device_kind|kernel|shape-signature, "
              "entry-CRC checked; written by `paddle_tpu tune` and "
              "tune.autotune) — deliberately beside compile_cache_dir: "
              "both are per-device derived state, safe to wipe")
DEFINE_int32("tune_budget", 0,
             "cap on candidates the autotune loop compiles+times per "
             "(kernel, shape), stock-XLA rung included; 0 = the full "
             "valid space. The CLI's --budget overrides per run")
DEFINE_bool("elastic", False,
            "default supervision mode for paddle_tpu.launch: True turns "
            "the launcher's fail-fast job abort into survive-and-resize "
            "(paddle_tpu.elastic) — on worker death the supervisor "
            "classifies the loss (signal death = permanent, crash exit = "
            "transient while the restart budget lasts), re-queues the "
            "dead worker's leased dataset tasks through the task master, "
            "re-plans the (host, chip) comm factorisation for the "
            "survivor set, and relaunches the job on the survivors from "
            "load_latest + the paired task-master snapshot, recording an "
            "elastic_resize event — the job only dies when the quorum "
            "(elastic_min_workers) is gone. CLI --elastic overrides")
DEFINE_int32("elastic_min_workers", 1,
             "elastic quorum: the smallest world size the supervisor "
             "will resize down to; one more permanent worker loss below "
             "this aborts the job with the real exit code (CLI "
             "--elastic-min-workers overrides)")
DEFINE_int32("elastic_restart_budget", 2,
             "how many transient worker failures (non-zero exit, not "
             "signal death) the elastic supervisor restarts at FULL "
             "world size before treating the next one as permanent; "
             "restarts back off on the resilience RetryPolicy schedule "
             "(CLI --elastic-restart-budget overrides)")
DEFINE_float("step_timeout_s", 0.0,
             "per-step deadline for the Trainer loop's hang watchdog "
             "(paddle_tpu.resilience.watchdog). 0 (default) = off. When "
             "set, a monitor thread checks that the training loop makes "
             "progress (every batch and every declared materialization "
             "point re-arms the deadline); a step that exceeds it — a "
             "wedged collective, a stalled reader, a hung device — "
             "records a durable step_hung event, dumps the profiler "
             "timeline artifact next to the elastic state dir, and "
             "exits the worker with code 75 (EX_TEMPFAIL) so an elastic "
             "supervisor classifies the death as TRANSIENT and "
             "restarts it from the paired checkpoint: a hang becomes a "
             "restart, never a wedged gang. Size it to several times "
             "the slowest legitimate step (cold compiles re-arm the "
             "deadline only when they finish)")
DEFINE_float("loss_spike_factor", 0.0,
             "numeric guardrail (paddle_tpu.resilience.guardrails): a "
             "batch whose loss exceeds this factor times the running "
             "median of recent accepted losses is treated like a "
             "non-finite loss — the batch is SKIPPED (not counted into "
             "pass metrics, recorded as a batch_skipped event) under "
             "the loss_skip_budget. 0 (default) = spike detection off "
             "(non-finite detection is governed by loss_skip_budget "
             "alone). The comparison starts after 3 accepted batches; "
             "values below ~2 will false-positive on normal early-"
             "training noise")
DEFINE_int32("loss_skip_budget", 0,
             "numeric guardrail: how many CONSECUTIVE batches the "
             "Trainer loop may skip (non-finite loss, or a spike past "
             "loss_spike_factor) before escalating. 0 (default) = "
             "guardrails off — a non-finite loss flows through exactly "
             "as before (check_nan_inf keeps its per-op raise "
             "semantics). On budget exhaustion the loop REWINDS model "
             "+ optimizer state to the last checkpoint (the PAIRED "
             "checkpoint in elastic mode) once per budget window and "
             "keeps training; a second consecutive exhaustion with no "
             "accepted batch in between gives up with "
             "FloatingPointError. The check reads the loss the loop has "
             "already brought to the host")
DEFINE_int32("elastic_ckpt_period", 1,
             "elastic Trainer worker (Trainer.train(elastic=True)): "
             "lease-committed batches between paired checkpoint+"
             "task-master-snapshot saves. 1 (default) pairs every "
             "committed batch — the chaos-gate setting; larger values "
             "amortise checkpoint cost, and a kill then replays up to "
             "period-1 committed tasks from the paired snapshot "
             "(still exactly-once in the resumed timeline: the model "
             "rolls back to the same point the task master does). A "
             "numeric-guardrail REWIND, by contrast, cannot roll the "
             "live master back, so at period>1 it discards up to "
             "period-1 accepted batches' contributions with a recorded "
             "guard_rewind_dropped_commits event — run period=1 when "
             "every contribution must survive a rewind")
DEFINE_int32("serve_queue_depth", 64,
             "online serving: bound on requests queued for dispatch "
             "across all models; request queue_depth+1 is shed "
             "immediately with OverloadError (HTTP 429) and a recorded "
             "request_shed degradation event instead of queuing into "
             "certain lateness")
DEFINE_int32("serve_max_running", 8,
             "generation engine (paddle_tpu.serving.generator): most "
             "sequences decoded concurrently by the fused iteration-"
             "level decode step. Fixes the decode program's batch "
             "shape, so it is compiled ONCE per engine — raising it on "
             "a live engine has no effect; set it before the engine is "
             "built. Idle rows cost one masked lane each, so size it "
             "to the sustained concurrency, not the peak queue")
DEFINE_int32("serve_kv_pages", 64,
             "generation engine: usable pages preallocated in the "
             "per-model paged KV pool (one extra trash page is added "
             "internally). Pool token capacity = serve_kv_pages x "
             "serve_page_tokens; admission reserves ceil((prompt + "
             "max_new_tokens) / serve_page_tokens) pages per sequence, "
             "and a request that could NEVER fit is shed at submit "
             "with a recorded kv_pool_exhausted event")
DEFINE_int32("serve_page_tokens", 16,
             "generation engine: K/V positions per page. Smaller pages "
             "waste less tail capacity per sequence but grow the block "
             "tables (max_blocks = ceil(max_seq / page_tokens) gather "
             "indices per row in the fused decode step)")
DEFINE_bool("serve_device_sample", True,
            "generation engine: sample the next token INSIDE the jitted "
            "decode/prefill step (seeded jax.random.categorical keyed "
            "by fold_in(PRNGKey(seed), token_offset); temperature<=0 is "
            "argmax) so each step returns [R] tokens + logprobs instead "
            "of [R, V] logits and the host loop is pure bookkeeping. "
            "Greedy output is token-identical to host sampling; "
            "temperature output is a DIFFERENT (but seeded, "
            "reproducible) stream than the host RandomState path. 0 "
            "restores host-side sampling bit-identically; a fused build "
            "failure degrades to the same host path with a recorded "
            "device_sample_degraded event (fault site serving.sample). "
            "Resolved once at engine construction — flipping it needs "
            "a new engine (hot reload)")
DEFINE_string("serve_draft_dir", "",
              "generation engine: directory of an exported generative "
              "artifact to load as the DRAFT model for speculative "
              "decoding (same vocabulary as the target; typically much "
              "smaller). Empty disables speculation unless the serving "
              "artifact itself is a paired speculative export "
              "(inference.export_speculative), which carries its own "
              "draft and wins. The draft gets its own KV page pool "
              "sized by serve_kv_pages x serve_page_tokens, priced into "
              "the PT034 memory check alongside the target's")
DEFINE_int32("serve_spec_k", 4,
             "generation engine: speculation depth — how many tokens "
             "the draft model proposes per round before ONE fused "
             "target step verifies them all. Per-request spec_k can "
             "only lower it. Greedy output is token-identical to "
             "non-speculative decode at any k; higher k wins only "
             "while the draft's acceptance rate holds up (watch "
             "acceptance_rate in /statz). 0 disables speculation even "
             "when a draft is available")
DEFINE_bool("serve_prefix_sharing", False,
            "generation engine: content-hash prefill pages (rolling "
            "blake2b chain over serve_page_tokens-sized token chunks) "
            "and let N concurrent requests PIN one physical copy of a "
            "shared prompt prefix instead of each paying full-price KV "
            "pages. The pool refcounts pages; the first divergent "
            "write copy-on-writes just that page; admission discounts "
            "its reservation by the cached full pages it will pin; an "
            "LRU keeps unreferenced prefix pages warm until allocation "
            "pressure reclaims them. Greedy output is bit-identical "
            "with sharing on or off. A failure in the sharing layer "
            "degrades that engine to plain private pages with a "
            "recorded prefix_degraded event (fault site "
            "serving.prefix), never an outage")
DEFINE_string("serve_tier", "",
              "serving tier class for the disaggregated fleet "
              "(serving/disagg.py): empty = a normal do-everything "
              "replica; 'prefill' advertises the replica as prefill-"
              "class (router sends it fresh prompts, ships the "
              "finished KV pages + request state to a decode replica); "
              "'decode' advertises decode-class (receives handoff "
              "artifacts, runs the steady-state token loop). The tier "
              "is advertised through /statz; the Router never "
              "dispatches a tier to work outside its class")
DEFINE_float("route_prefill_up_queue", 4.0,
             "tiered autoscale: a prefill-class tier scales UP when "
             "its per-replica mean queue depth (queued + running "
             "prefills — the compute-bound signal) exceeds this; see "
             "route_scale_down_pressure's decode analogue "
             "route_decode_up_frac for the decode tier")
DEFINE_float("route_decode_up_frac", 0.8,
             "tiered autoscale: a decode-class tier scales UP when its "
             "mean KV page-pool PHYSICAL occupancy fraction exceeds "
             "this (memory-bound signal — decode replicas run out of "
             "pages long before they run out of FLOPs)")
DEFINE_int32("route_replicas", 3,
             "serving router (paddle_tpu.serving.router): how many "
             "`serve` worker processes the replica pool spawns and "
             "supervises behind one `paddle_tpu route` front tier "
             "(CLI --replicas overrides)")
DEFINE_int32("route_poll_ms", 100,
             "serving router: background poll interval for each "
             "replica's /statz (load score) and /healthz (liveness). "
             "Between polls the score is freshened by the router's own "
             "in-flight request count, so a shorter interval mainly "
             "tightens eject/readmit latency, not balance")
DEFINE_int32("route_eject_after", 3,
             "serving router: consecutive /healthz failures before a "
             "replica is ejected from routing (it keeps being polled; "
             "see route_readmit_after for the probation readmit)")
DEFINE_int32("route_readmit_after", 2,
             "serving router: consecutive /healthz successes an "
             "ejected replica must bank (probation) before it is "
             "readmitted to routing — one lucky poll must not put a "
             "flapping replica back in rotation")
DEFINE_int32("route_restart_budget", 2,
             "serving router: how many times the replica pool restarts "
             "one dead `serve` worker (on the resilience RetryPolicy "
             "backoff schedule, each restart a recorded "
             "router_replica_restart event) before declaring it lost "
             "(router_replica_lost; the remaining replicas keep "
             "serving). The budget bounds crash LOOPS: a respawn that "
             "stays up 60s (ReplicaPool budget_reset_s) resets the "
             "slot's record")
DEFINE_float("route_proxy_timeout_s", 300.0,
             "serving router: socket timeout for one proxied replica "
             "request (predict/generate/reload). A request carrying "
             "deadline_ms uses min(deadline, this). Proxy failures "
             "inside the window fail over once to the next-best "
             "replica")
DEFINE_float("route_pressure_alpha", 0.4,
             "serving router: EWMA smoothing factor for the per-model "
             "autoscale pressure signal (smoothed = alpha*raw + "
             "(1-alpha)*previous, seeded with the first raw sample). "
             "/statz exposes both 'pressure' (raw, one poll window) "
             "and 'pressure_smoothed'; the autoscaler acts ONLY on the "
             "smoothed one, so a single poll spike can neither trigger "
             "a scale-up nor mask a sustained overload. Must be in "
             "(0, 1]; 1.0 disables smoothing")
DEFINE_float("route_scale_up_pressure", 1.0,
             "autoscaler (paddle_tpu.serving.autoscale): smoothed "
             "pressure at or above this for k_up consecutive control "
             "ticks grows the fleet by one replica (pressure = "
             "backlog/capacity + shed_rate, so 1.0 means the backlog "
             "equals the healthy fleet's capacity). Must exceed "
             "route_scale_down_pressure — the dead band between them "
             "is the hysteresis that stops oscillating load from "
             "thrashing the fleet")
DEFINE_float("route_scale_down_pressure", 0.2,
             "autoscaler: smoothed pressure at or below this for the "
             "(longer) quiet window shrinks the fleet by one replica, "
             "drain-first: the victim is marked draining in the "
             "router, in-flight requests run out (bounded by the drain "
             "deadline), then the worker is retired on the shared "
             "SIGTERM->SIGKILL escalation — no request is lost to a "
             "policy decision")
DEFINE_float("route_cooldown_s", 30.0,
             "autoscaler: minimum seconds between scale-UPs (the "
             "scale-down cooldown defaults to 2x this, and a "
             "scale-down additionally waits it out since the last "
             "scale-up). Cooldowns are the second flap guard after "
             "the threshold hysteresis")
DEFINE_float("gray_step_ratio", 0.0,
             "gray-failure detection for the elastic TRAINING gang "
             "(paddle_tpu.resilience.grayfail consumed by the elastic "
             "supervisor): a rank whose per-step wall time — published "
             "in its heartbeat-rank<N>.json under --state-dir — stays "
             "above ratio x the cross-rank median (median+MAD robust "
             "baseline, consecutive sweeps, hysteresis) is condemned "
             "as a GRAY failure: alive and heartbeating but "
             "consistently slower than its peers, dragging every "
             "collective to its pace. 0.0 (default) = detection off; "
             "enable with a ratio comfortably above legitimate skew "
             "(3.0 is the chaos-gate setting). Mitigation is budgeted "
             "by gray_mitigation_budget and recorded as durable "
             "gray_suspected / gray_mitigated events; it never drops "
             "the gang below --min-workers and runs at most one "
             "mitigation per generation. CPU caveat: the CI legs "
             "inject slowness via delay faults on trainer.step — real "
             "cross-host skew (thermal throttle, a bad NIC) needs the "
             "pod trip")
DEFINE_int32("gray_mitigation_budget", 1,
             "gray-failure mitigation budget for the elastic "
             "supervisor: how many condemned-rank mitigations are "
             "spent as TRANSIENT restarts (full-world relaunch from "
             "the paired checkpoint — maybe the host just had a bad "
             "hour) before a recurrence is demoted to PERMANENT: the "
             "condemned rank is dropped and the gang resizes via the "
             "normal clean-resize machinery. Spent per job, not per "
             "generation, so a persistently slow host cannot buy "
             "itself a restart loop")
DEFINE_float("route_gray_ratio", 0.0,
             "gray-failure detection for the SERVING fleet "
             "(paddle_tpu.resilience.grayfail consumed by the "
             "router's poller): a replica whose proxied-latency EWMA "
             "stays above ratio x the cross-replica median (same "
             "robust baseline + streak + hysteresis detector as the "
             "training tier) is drained and ejected into the normal "
             "probation/readmit cycle EVEN THOUGH its /healthz still "
             "answers 200 — latency-only ejection, recorded as "
             "durable gray_suspected / gray_mitigated events and "
             "counted in /statz. 0.0 (default) = detection off; 3.0 "
             "is the load_bench slow-replica-leg setting. Needs at "
             "least 3 replicas with traffic to pick an outlier (the "
             "median of a pair splits it)")
DEFINE_float("route_gray_hold_s", 10.0,
             "serving router: how long a latency-ejected (gray) "
             "replica is held out of rotation before its detector "
             "record is forgotten and the normal /healthz probation "
             "(route_readmit_after) may readmit it. An ejected "
             "replica receives no traffic, so its latency signal "
             "cannot clear itself — the hold is the readmit path, and "
             "a replica that is still slow after readmission is "
             "simply condemned again")
DEFINE_float("route_hedge_budget", 0.0,
             "serving router: request hedging for IDEMPOTENT "
             ":predict proxies only (:generate consumes KV budget and "
             "decode slots — it is NEVER hedged). A predict still "
             "unanswered past the hedge deadline — the router's "
             "observed p99 proxied latency, floored at "
             "route_hedge_min_ms — fires ONE hedged attempt at the "
             "next-best replica; the first answer wins and the loser "
             "is discarded on arrival. This value caps hedges as a "
             "fraction of proxied traffic (0.05 = at most 5% extra "
             "load) so tail-chasing can never melt an overloaded "
             "fleet. 0.0 (default) = hedging off. Hedges and hedge "
             "wins are counted in /statz and the grayfail profiler "
             "family")
DEFINE_float("route_hedge_min_ms", 20.0,
             "serving router: floor for the p99-derived hedge "
             "deadline, and the deadline used while fewer than 20 "
             "latency samples exist. Keeps a fast fleet (p99 of a "
             "few ms) from hedging on scheduler noise — a hedge "
             "should chase a genuinely late request, not jitter")

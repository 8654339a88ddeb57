"""InferenceService: the in-process serving front end.

Ties the registry, micro-batcher, and admission controller into one
object with a blocking ``infer()`` / non-blocking ``infer_async()`` API
and a metrics surface (``.stats``) on the same pattern as
``Executor.stats`` and the training loop's profiler counters: request
and shed counts, batch occupancy, queue wait, and p50/p99 end-to-end
latency, mirrored into ``profiler.serving_counters()`` and the
``serving`` section of the timeline artifact.

Two request families share the front end:

- **one-shot inference** (``infer`` / ``infer_async``) over compiled
  artifacts through the micro-batcher — the PR-4 path;
- **autoregressive generation** (``generate`` / ``generate_async``)
  over generative artifacts through a per-model
  :class:`~paddle_tpu.serving.generator.GenerationEngine` (continuous
  batching + paged KV-cache). ``load_model`` auto-detects which kind a
  directory holds; eligibility is decided per artifact, and the
  micro-batcher keeps serving the non-autoregressive models.

The HTTP endpoint (:mod:`~paddle_tpu.serving.httpd`) and the
``paddle_tpu serve`` CLI verb are thin shells over this class — tests
and embedders use it directly.
"""
from __future__ import annotations

import collections
import threading

import numpy as np

from .admission import (AdmissionController, ModelUnavailableError,
                        OverloadError, ServingError)
from .batcher import MicroBatcher, Request
# the shared lock constructor: plain threading primitives normally, the
# lock-order race detector's instrumented ones under PADDLE_TPU_SANITIZE=locks
from ..analysis import locks as _locks

__all__ = ["InferenceService", "GenEntry"]

# bounded latency reservoirs: long-lived servers must not grow a list
# per request; percentiles over the most recent window are the ones an
# operator acts on anyway
_WINDOW = 4096


def _percentile(values, q):
    if not values:
        return 0.0
    s = sorted(values)
    return s[min(int(q * len(s)), len(s) - 1)]


class GenEntry(object):
    """One published generative (name, version): the registry
    ModelEntry's shape, generation-flavored. ``engine_kwargs`` records
    the deployment's engine knobs so a later reload without explicit
    kwargs (the HTTP ``:reload`` path) rebuilds the SAME geometry
    instead of silently falling back to the flag defaults."""

    __slots__ = ("name", "version", "dirname", "engine", "engine_kwargs",
                 "loaded_at")

    def __init__(self, name, version, dirname, engine, engine_kwargs=None):
        import time as _time
        self.name = name
        self.version = version
        self.dirname = dirname
        self.engine = engine
        self.engine_kwargs = dict(engine_kwargs or {})
        self.loaded_at = _time.time()

    @property
    def warmup_ms(self):
        return self.engine.warmup_ms

    def describe(self):
        eng = self.engine
        return {"version": self.version, "dirname": self.dirname,
                "loaded_at": self.loaded_at, "kind": "generative",
                "warmup_ms": round(eng.warmup_ms, 3),
                "max_running": eng.max_running,
                "kv_pages": eng.pool.num_pages,
                "page_tokens": eng.pool.page_tokens,
                "max_context": eng.max_context}


class InferenceService(object):
    """Online inference over registered compiled artifacts.

    Usage::

        svc = InferenceService()                       # knobs from FLAGS
        svc.load_model("resnet", "./artifact_dir")     # warm-up included
        outs = svc.infer("resnet", {"x": batch})       # list per fetch
        svc.reload_model("resnet", "./artifact_v2")    # atomic hot swap
        svc.stats                                      # metrics snapshot
        svc.close()

    Knob defaults come from ``FLAGS.serve_max_batch`` /
    ``serve_batch_timeout_ms`` / ``serve_queue_depth``.
    """

    def __init__(self, registry=None, max_batch=None, batch_timeout_ms=None,
                 queue_depth=None, tier=None):
        from ..flags import FLAGS
        # serving tier class for the disaggregated fleet (FLAGS.
        # serve_tier): "" = do-everything replica, "prefill"/"decode"
        # advertise the class through /statz and /healthz so the router
        # never dispatches a tier to work outside its class. The tier
        # is a ROUTING contract, not a capability fence — a prefill
        # replica can still decode (the re-prefill fallback depends on
        # decode replicas being whole engines).
        self.tier = str(tier if tier is not None else FLAGS.serve_tier)
        if self.tier not in ("", "prefill", "decode"):
            raise ValueError("tier must be '', 'prefill' or 'decode', "
                             "got %r" % self.tier)
        self.max_batch = int(max_batch if max_batch is not None
                             else FLAGS.serve_max_batch)
        self.batch_timeout_ms = float(
            batch_timeout_ms if batch_timeout_ms is not None
            else FLAGS.serve_batch_timeout_ms)
        depth = int(queue_depth if queue_depth is not None
                    else FLAGS.serve_queue_depth)
        from .batcher import padding_buckets
        from .registry import ModelRegistry
        self.registry = registry or ModelRegistry(
            warm_buckets=padding_buckets(self.max_batch))
        self.admission = AdmissionController(depth)
        self._lock = _locks.make_lock("serving.service.state")
        self._counts = collections.Counter()
        self._occupancy_sum = 0
        self._max_occupancy = 0
        self._padded_rows = 0
        self._queue_wait_ms = collections.deque(maxlen=_WINDOW)
        self._latency_ms = collections.deque(maxlen=_WINDOW)
        self._batcher = MicroBatcher(
            self.registry, self.max_batch, self.batch_timeout_ms,
            self.admission, on_shed=self._on_shed,
            on_batch=self._on_batch, on_fail=self._on_fail)
        self._generators = {}       # name -> GenEntry
        self._gen_versions = {}     # name -> last assigned version int
        # name -> (gen version, disagg.PrefillEngine): the prefill-tier
        # face over the SAME model a generative entry serves, built
        # lazily on the first ``:prefill`` and retired with its entry —
        # version-keyed so a hot reload never exports KV computed by
        # the previous weights
        self._prefill_engines = {}
        # serializes generative load/reload/drop per SERVICE: two racing
        # :reload threads would otherwise both build engines and both
        # retire only the older one — the loser's engine thread and
        # device-resident pool would leak for the process lifetime
        self._gen_reload_lock = _locks.make_lock("serving.service.gen_reload")
        self._closed = False

    # -- model management ----------------------------------------------------
    def load_model(self, name, dirname, warm=True, **gen_kwargs):
        """Load (or hot-reload) ``dirname`` as ``name``. The artifact
        kind decides the path: an ``export_generative`` directory builds
        a generation engine (``gen_kwargs`` — max_running/kv_pages/...
        — apply there); anything else goes through the compiled-model
        registry (``gen_kwargs`` are rejected: a compiled artifact has
        no engine to configure)."""
        from ..inference import is_generative_artifact
        if is_generative_artifact(dirname):
            return self.load_generative(name, dirname, warm=warm,
                                        **gen_kwargs)
        if gen_kwargs:
            raise TypeError(
                "%r is a compiled artifact; generation engine knobs %s "
                "do not apply" % (dirname, sorted(gen_kwargs)))
        entry = self.registry.load(name, dirname, warm=warm)
        # a compiled artifact replacing a generative name: retire the
        # stale engine, or it would keep answering :generate with the
        # previous model forever
        self._drop_generative(name)
        return entry

    def reload_model(self, name, dirname, warm=True, **gen_kwargs):
        """Atomic hot reload; on failure the previous version keeps
        serving (rollback) and the error propagates to this caller."""
        return self.load_model(name, dirname, warm=warm, **gen_kwargs)

    # cap on how long a hot reload waits for the previous engine's
    # in-flight generations before closing it anyway
    _DRAIN_TIMEOUT_S = 60.0

    def load_generative(self, name, dirname, warm=True, **engine_kwargs):
        """Load a generative artifact and stand its engine up. The new
        engine is fully built (and warmed) BEFORE the publish swap; the
        previous engine drains its in-flight sequences (new submits go
        to the replacement) and closes after the swap — the registry's
        hot-reload discipline. A reload without explicit
        ``engine_kwargs`` reuses the previous deployment's knobs (the
        HTTP ``:reload`` path must not silently reset the pool
        geometry to flag defaults). On failure the previous version
        keeps serving with a recorded ``reload_rollback`` event.

        A speculative pairing (``inference.export_speculative``) is
        auto-detected: the draft model and the pairing's k ride into
        the engine kwargs, and the ARTIFACT is the source of truth —
        it overrides a stale draft reused from the previous
        deployment's kwargs, and reloading a plain artifact over a
        speculative one drops the old draft rather than resurrecting
        it."""
        from ..inference import (is_speculative_artifact,
                                 load_generative, load_speculative)
        from ..resilience import record_event
        from .generator import GenerationEngine
        with self._gen_reload_lock:
            self._check_open()
            prev = self._generators.get(name)
            explicit_draft = "draft_model" in engine_kwargs
            if not engine_kwargs and prev is not None:
                engine_kwargs = dict(prev.engine_kwargs)
            engine_kwargs.setdefault("queue_depth",
                                     self.admission.queue_depth)
            try:
                if is_speculative_artifact(dirname):
                    model, draft, spec_k = load_speculative(dirname)
                    if not explicit_draft:
                        engine_kwargs["draft_model"] = draft
                        # an explicitly-passed spec_k (CLI --spec_k)
                        # still wins over the pairing's qualified k
                        engine_kwargs.setdefault("spec_k", spec_k)
                elif not explicit_draft:
                    # plain artifact: never inherit a previous
                    # deployment's draft across the reload
                    model = load_generative(dirname)
                    engine_kwargs.pop("draft_model", None)
                    engine_kwargs.pop("spec_k", None)
                else:
                    model = load_generative(dirname)
                engine = GenerationEngine(model, name=name, warm=warm,
                                          **engine_kwargs)
            except BaseException as e:
                if prev is not None:
                    record_event("reload_rollback", site="serving.reload",
                                 model=name, kept_version=prev.version,
                                 dirname=dirname, error=repr(e))
                raise
            with self._lock:
                version = self._gen_versions.get(name, 0) + 1
                self._gen_versions[name] = version
                entry = GenEntry(name, version, dirname, engine,
                                 engine_kwargs)
                self._generators[name] = entry
            record_event("model_loaded", site="serving.reload", model=name,
                         version=version, dirname=dirname,
                         artifact="generative",
                         warmup_ms=round(engine.warmup_ms, 3))
            if prev is not None:
                prev.engine.drain(timeout=self._DRAIN_TIMEOUT_S)
                prev.engine.close()
            self._drop_prefill(name, keep_version=version)
            # a generative artifact replacing a compiled name: retire the
            # stale compiled entry, or it would keep answering :predict
            # with the previous model forever
            self.registry.unload(name)
            return entry

    def register_generative(self, name, model, **engine_kwargs):
        """In-process entry point (tests/benchmarks/embedders): stand an
        engine up over an already-built
        :class:`~paddle_tpu.models.transformer.TransformerLM`."""
        from .generator import GenerationEngine
        with self._gen_reload_lock:
            self._check_open()
            prev = self._generators.get(name)
            engine_kwargs.setdefault("queue_depth",
                                     self.admission.queue_depth)
            engine = GenerationEngine(model, name=name, **engine_kwargs)
            with self._lock:
                version = self._gen_versions.get(name, 0) + 1
                self._gen_versions[name] = version
                entry = GenEntry(name, version, "<in-process>", engine,
                                 engine_kwargs)
                self._generators[name] = entry
            if prev is not None:
                prev.engine.drain(timeout=self._DRAIN_TIMEOUT_S)
                prev.engine.close()
            self._drop_prefill(name, keep_version=version)
            self.registry.unload(name)
            return entry

    def _check_open(self):
        """Called under ``_gen_reload_lock``: a generative load racing
        :meth:`close` must lose — an engine published after the close
        sweep would leak its thread and device-resident page pool for
        the process lifetime."""
        if self._closed:
            raise RuntimeError("InferenceService is closed")

    def _drop_generative(self, name):
        """Retire ``name``'s generation engine (cross-kind replacement),
        draining in-flight work first."""
        with self._gen_reload_lock:
            with self._lock:
                entry = self._generators.pop(name, None)
            if entry is not None:
                entry.engine.drain(timeout=self._DRAIN_TIMEOUT_S)
                entry.engine.close()
            self._drop_prefill(name)

    def _drop_prefill(self, name, keep_version=None):
        """Retire ``name``'s cached prefill engine unless it already
        matches ``keep_version`` — called on reload/drop so a stale
        prefill face never outlives the weights it was traced over."""
        with self._lock:
            cached = self._prefill_engines.get(name)
            if cached is None or cached[0] == keep_version:
                return
            del self._prefill_engines[name]
        cached[1].close()

    def _gen_entry(self, name):
        with self._lock:
            entry = self._generators.get(name)
            known = sorted(self._generators) if entry is None else None
        if entry is None:
            raise ModelUnavailableError(
                "no generative model registered under %r (registered: "
                "%s)" % (name, known or "none"))
        return entry

    def model_info(self):
        """Registry listing covering both families (httpd /v1/models)."""
        info = self.registry.info()
        with self._lock:
            gens = dict(self._generators)
        info.update({n: e.describe() for n, e in gens.items()})
        return info

    def readiness(self):
        """Per-model readiness detail for ``/healthz``: what a router
        needs to weight and drain on — kind, version, queue depth, and
        (generative) KV page utilization + draining state. Presence of
        a model key means "loaded"; ``draining`` True means the engine
        is handing over to a replacement and new work should go
        elsewhere."""
        out = {}
        for name in self.registry.names():
            try:
                entry = self.registry.get(name)
            except ModelUnavailableError:
                continue
            out[name] = {"kind": "compiled", "version": entry.version,
                         "queued": self._batcher.pending_for(name),
                         "draining": False}
        with self._lock:
            gens = dict(self._generators)
        for name, e in gens.items():
            st = e.engine.stats
            out[name] = {"kind": "generative", "version": e.version,
                         "queued": st["queued"], "running": st["running"],
                         "page_utilization": round(
                             st["page_utilization"]["frac"], 4),
                         "draining": e.engine.draining}
        return out

    def retry_after_ms(self, model=None):
        """Back-off hint for 429/503 answers, derived from the queue-wait
        the service is CURRENTLY delivering: a client that retries after
        roughly one p99 queue-wait arrives behind a drained backlog
        instead of re-feeding the convoy. Floor: one batch-formation
        window. For a generative ``model``, the inter-token p50 times
        the queued depth estimates the engine's drain time and takes
        the max. A pool-exhausted shed takes a further max against the
        OBSERVED page-release rate: queued-depth-many sequences each
        need pages, and pages come back at ``pool.release_rate()``
        pages/s, so waiting ``(queued+1)/rate`` seconds is when capacity
        plausibly exists — the batch window would tell an exhausted-pool
        client to hammer a server that cannot admit anyone. Clamped to
        [1 ms, 30 s]."""
        with self._lock:
            qw = list(self._queue_wait_ms)
            gen = self._generators.get(model) if model else None
        est = max(self.batch_timeout_ms, _percentile(qw, 0.99))
        if gen is not None:
            st = gen.engine.stats
            est = max(est,
                      st["intertoken_ms_p50"] * (st["queued"] + 1))
            rate = st.get("page_release_rate", 0.0)
            if rate > 0.0:
                est = max(est, 1000.0 * (st["queued"] + 1) / rate)
        return min(max(est, 1.0), 30000.0)

    # -- request path --------------------------------------------------------
    def infer_async(self, name, feed, deadline_ms=None):
        """Enqueue one request; returns its :class:`Request` handle
        (``.wait()`` for the rows). Raises :class:`OverloadError`
        immediately when the queue is full. ``feed`` maps each of the
        model's feed names to one request's arrays (the exported
        per-request shape, no extra batch axis)."""
        entry = self.registry.get(name)   # fail fast on unknown models
        feed = self._checked_feed(name, entry.model, feed)
        req = Request(name, feed,
                      self.admission.deadline_from(deadline_ms))
        with self._lock:
            self._counts["requests"] += 1
        try:
            self._batcher.submit(req)
        except OverloadError:
            with self._lock:
                self._counts["shed_overload"] += 1
            from .. import profiler as _prof
            _prof.update_serving_counters(shed_overload=1)
            raise
        return req

    @staticmethod
    def _checked_feed(name, model, feed):
        """Validate one request against the artifact signature BEFORE it
        queues: a malformed feed must fail its own submit, not poison
        every co-batched request at np.stack time. Array-likes are
        checked by attribute only (never np.asarray on a possibly
        device-resident value — that forces a device->host transfer);
        plain lists/scalars are converted to the exported dtype here."""
        spec = model.feed_spec
        out = {}
        for fn, (shape, dtype) in spec.items():
            if fn not in feed:
                raise ValueError(
                    "feed for model %r is missing %r (wants %s)"
                    % (name, fn, sorted(spec)))
            v = feed[fn]
            if not hasattr(v, "shape"):
                v = np.asarray(v, dtype=dtype)
            if tuple(v.shape) != tuple(shape):
                raise ValueError(
                    "feed %r for model %r has shape %s; the artifact was "
                    "exported for %s (one request = one exported feed, "
                    "no extra batch axis)"
                    % (fn, name, tuple(v.shape), tuple(shape)))
            if str(getattr(v, "dtype", dtype)) != dtype:
                raise ValueError(
                    "feed %r for model %r has dtype %s; the artifact was "
                    "exported for %s" % (fn, name, v.dtype, dtype))
            out[fn] = v
        return out

    def infer(self, name, feed, deadline_ms=None, timeout=None):
        """Blocking inference: list of per-fetch arrays, bit-identical
        to ``CompiledModel.run(feed)`` on the served version."""
        return self.infer_async(name, feed, deadline_ms).wait(timeout)

    # -- generation path -----------------------------------------------------
    def generate_async(self, name, tokens, max_new_tokens=16,
                       temperature=0.0, seed=0, deadline_ms=None,
                       spec_k=None):
        """Enqueue one autoregressive generation on ``name``'s engine;
        returns its :class:`~paddle_tpu.serving.generator.GenRequest`
        handle (``.wait()`` for the
        :class:`~paddle_tpu.serving.generator.GenResult`). Sheds raise
        immediately (OverloadError / PoolExhausted), the engine's
        submit contract. The handle's ``model_version`` is stamped from
        the entry that took the submit, so responses attribute tokens
        to the version that produced them even across a hot reload."""
        entry = self._gen_entry(name)
        try:
            req = entry.engine.submit(
                tokens, max_new_tokens=max_new_tokens,
                temperature=temperature, seed=seed,
                deadline_ms=deadline_ms, spec_k=spec_k)
        except ServingError:
            # lost the race with a hot reload: the entry fetched above
            # drained/closed before this submit landed. Retry ONCE
            # against the current registry state — the replacement
            # engine owns new traffic; a second loss means the model is
            # genuinely going away and the error is real
            entry = self._gen_entry(name)
            req = entry.engine.submit(
                tokens, max_new_tokens=max_new_tokens,
                temperature=temperature, seed=seed,
                deadline_ms=deadline_ms, spec_k=spec_k)
        req.model_version = entry.version
        return req

    # -- disaggregated tier path ---------------------------------------------
    def _prefill_for(self, entry):
        """The cached prefill engine for ``entry``, built on first use
        over the entry's OWN model object (same weights, same page
        geometry as the decode pools it will hand off to)."""
        with self._lock:
            cached = self._prefill_engines.get(entry.name)
            if cached is not None and cached[0] == entry.version:
                return cached[1]
        from .disagg import PrefillEngine
        eng = PrefillEngine(entry.engine.model,
                            page_tokens=entry.engine.pool.page_tokens,
                            name=entry.name, eos_id=entry.engine.eos_id)
        with self._lock:
            cached = self._prefill_engines.get(entry.name)
            if cached is not None and cached[0] == entry.version:
                stale = eng          # lost a build race: keep the winner
                eng = cached[1]
            else:
                stale = cached[1] if cached is not None else None
                self._prefill_engines[entry.name] = (entry.version, eng)
        if stale is not None:
            stale.close()
        return eng

    def prefill(self, name, tokens, max_new_tokens=16, temperature=0.0,
                seed=0):
        """Prefill-tier entry point (httpd ``:prefill``): run ONLY the
        prompt pass on ``name``'s weights and return the
        :class:`~paddle_tpu.serving.disagg.HandoffArtifact` — finished
        KV pages + enough request state for any decode-class replica to
        continue bit-exactly."""
        entry = self._gen_entry(name)
        return self._prefill_for(entry).prefill(
            tokens, max_new_tokens=max_new_tokens,
            temperature=temperature, seed=seed)

    def decode_handoff_async(self, name, payload, deadline_ms=None):
        """Decode-tier entry point (httpd ``:decode``): install a
        shipped artifact (wire payload or HandoffArtifact) into
        ``name``'s engine via :func:`~paddle_tpu.serving.disagg.ship`
        and return the request handle. The ship fallback applies — a
        bad artifact re-prefills HERE rather than failing the request —
        while overload/pool-exhaustion propagate as backpressure."""
        from .disagg import HandoffArtifact, ship
        artifact = (payload if isinstance(payload, HandoffArtifact)
                    else HandoffArtifact.from_payload(payload))
        entry = self._gen_entry(name)
        try:
            req = ship(artifact, entry.engine, deadline_ms=deadline_ms)
        except ServingError:
            # same reload race as generate_async: retry once against
            # the current entry
            entry = self._gen_entry(name)
            req = ship(artifact, entry.engine, deadline_ms=deadline_ms)
        req.model_version = entry.version
        return req

    def decode_handoff(self, name, payload, deadline_ms=None, timeout=None):
        """Blocking :meth:`decode_handoff_async` -> GenResult."""
        return self.decode_handoff_async(name, payload,
                                         deadline_ms=deadline_ms).wait(timeout)

    def generate(self, name, tokens, max_new_tokens=16, temperature=0.0,
                 seed=0, deadline_ms=None, timeout=None, spec_k=None):
        """Blocking generation -> GenResult (greedy outputs are
        token-identical to sequential full-sequence decode of the same
        prompt — the continuous-batching parity contract)."""
        return self.generate_async(name, tokens, max_new_tokens,
                                   temperature, seed, deadline_ms,
                                   spec_k=spec_k).wait(timeout)

    # -- observer hooks (dispatch thread) ------------------------------------
    def _on_batch(self, requests, bucket):
        n = len(requests)
        with self._lock:
            self._counts["completed"] += n
            self._counts["batches"] += 1
            self._occupancy_sum += n
            self._max_occupancy = max(self._max_occupancy, n)
            self._padded_rows += bucket - n
            for r in requests:
                self._queue_wait_ms.append(r.queue_wait_ms)
                self._latency_ms.append(r.latency_ms)
        from .. import profiler as _prof
        _prof.update_serving_counters(
            requests=n, batches=1, padded_rows=bucket - n,
            max_occupancy=n,
            queue_wait_ms=sum(r.queue_wait_ms for r in requests))

    def _on_shed(self, request, reason):
        with self._lock:
            self._counts["shed_" + reason] += 1
        from .. import profiler as _prof
        _prof.update_serving_counters(**{"shed_" + reason: 1})

    def _on_fail(self, requests, exc):
        with self._lock:
            self._counts["failed"] += len(requests)
        from .. import profiler as _prof
        _prof.update_serving_counters(failed=len(requests))

    # -- metrics -------------------------------------------------------------
    @property
    def stats(self):
        """Snapshot: counts, occupancy, queue wait, p50/p99 latency."""
        with self._lock:
            c = dict(self._counts)
            batches = c.get("batches", 0)
            qw = list(self._queue_wait_ms)
            lat = list(self._latency_ms)
            snap = {
                "requests": c.get("requests", 0),
                "completed": c.get("completed", 0),
                "failed": c.get("failed", 0),
                "shed_overload": c.get("shed_overload", 0),
                "shed_deadline": c.get("shed_deadline", 0),
                "pending": self._batcher.pending(),
                "max_batch": self.max_batch,
                "batches": batches,
                "batch_occupancy": (self._occupancy_sum / batches
                                    if batches else 0.0),
                "max_occupancy": self._max_occupancy,
                "padded_rows": self._padded_rows,
                "queue_wait_ms_p50": _percentile(qw, 0.50),
                "queue_wait_ms_p99": _percentile(qw, 0.99),
                "latency_ms_p50": _percentile(lat, 0.50),
                "latency_ms_p99": _percentile(lat, 0.99),
                "models": self.registry.versions(),
                "tier": self.tier,
            }
            gens = dict(self._generators)
            pre = {n: v[1] for n, v in self._prefill_engines.items()}
        snap["shed"] = snap["shed_overload"] + snap["shed_deadline"]
        if gens:
            snap["generation"] = {n: e.engine.stats
                                  for n, e in sorted(gens.items())}
            snap["models"].update({n: e.version
                                   for n, e in gens.items()})
        if pre:
            snap["prefill"] = {n: e.stats for n, e in sorted(pre.items())}
        return snap

    # -- lifecycle -----------------------------------------------------------
    def close(self):
        # _closed flips under _gen_reload_lock so an in-flight
        # load_generative either publishes BEFORE the sweep below
        # (its engine is collected here) or observes _closed and
        # refuses — no engine can be published into a closed service
        with self._gen_reload_lock:
            if self._closed:
                return
            self._closed = True
            with self._lock:
                gens = list(self._generators.values())
                self._generators.clear()
                pre = [v[1] for v in self._prefill_engines.values()]
                self._prefill_engines.clear()
        for p in pre:
            p.close()
        self._batcher.close()
        # same contract as hot reload: in-flight generations finish
        # (bounded) before the engine is torn down, so a SIGTERM
        # drain-and-exit never 500s a request mid-stream
        for e in gens:
            e.engine.drain(timeout=self._DRAIN_TIMEOUT_S)
            e.engine.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # convenience for embedders comparing against the offline path
    @staticmethod
    def as_numpy(rows):
        return [np.asarray(r) for r in rows]

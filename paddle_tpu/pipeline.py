"""Asynchronous execution pipeline: overlapped feed prefetch, lazy
fetches, and the persistent compile cache.

A training step uses three resources that can run concurrently: the host
builds batch k (``DataFeeder.feed`` + ``device_put``), the device
computes step k, and the host reads the fetches back. Trainer's default
loop already overlaps the first two by ONE batch, on the training thread
and with no thread of its own: it dispatches step k, prepares batch k+1
while the device computes, and only then reads step k's loss
(``Trainer.train``). This module adds what that cannot give: a feed
thread that runs up to ``depth`` batches ahead, so a feed that outlasts
the device step is hidden too as long as the thread keeps up on average,
and fetches that stay on the device until somebody reads them — the same
overlap-hiding principle the reference's C++ double-buffer data provider
applied to disk reads (reference: gserver/dataproviders
DoubleBufferedDataProvider) and HiCCL (arxiv 2408.05962) applies to
collectives: keep every resource busy by separating producer from
consumer.

Three stages:

- :class:`FeedPipeline` — a background thread runs
  ``feeder.feed(batch k+1)`` + ``Executor.prepare_feed`` (device_put)
  while the device computes batch k, handing device-resident feed dicts
  through a bounded ring of ``depth`` buffers (double-buffered by
  default). If the feed thread dies, the pipeline records a resilience
  event and falls back to clean synchronous feeding — no batch is
  dropped, so losses stay bit-identical to the synchronous mode.
- :class:`AsyncFetch` (defined in core.executor, re-exported here) —
  ``Executor.run(..., sync=False)`` returns these instead of blocking on
  a device->host transfer per step; materialisation happens only at real
  sync points (the event handler touching ``.cost``/``.metrics``, the
  log-period progress line, pass end, before checkpoints).
- the persistent compile cache — jax's on-disk XLA compilation cache
  (``JAX_COMPILATION_CACHE_DIR`` when set, else
  ``FLAGS.compile_cache_dir`` = ``<checkout>/.jax_cache``; opt-out
  ``FLAGS.compile_cache=0``) plus the in-process warm-start
  registry in core.executor keyed by (program uid, version, feed
  signature), so repeat runs skip the cold compile.

Observability: :attr:`FeedPipeline.stats`, the pipeline counters on
``Executor.stats`` (dispatch depth, feed-wait ms, fetch-sync count,
compile-cache hits), and ``profiler.pipeline_counters()`` / the
``pipeline`` section of the timeline artifact.
"""
from __future__ import annotations

import os
import queue
import threading
import time

import numpy as np

from .core.executor import AsyncFetch, clear_warm_cache  # noqa: F401
from .resilience import fault_point, record_event

__all__ = ["AsyncFetch", "FeedPipeline", "materialize",
           "materialize_scalar", "compile_cache_dir",
           "enable_compile_cache",
           "maybe_enable_compile_cache", "clear_warm_cache"]


# -- lazy-fetch helpers -------------------------------------------------------

def materialize(value):
    """Force an AsyncFetch (or a list/tuple of them) to its host value;
    anything already concrete passes through unchanged."""
    if isinstance(value, AsyncFetch):
        return value.value()
    if isinstance(value, (list, tuple)):
        return type(value)(materialize(v) for v in value)
    return value


def materialize_scalar(value):
    """Python float of a fetched scalar, materialising lazily if needed."""
    if isinstance(value, float):
        return value
    return float(np.asarray(materialize(value)).reshape(-1)[0])


# -- persistent compile cache -------------------------------------------------

_compile_cache_state = {"configured": False}


def compile_cache_dir():
    """Where the persistent compile cache lives:
    ``JAX_COMPILATION_CACHE_DIR`` places it from outside; unset, it is
    ``FLAGS.compile_cache_dir`` (``<checkout>/.jax_cache``)."""
    from .flags import FLAGS
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or FLAGS.compile_cache_dir)


def enable_compile_cache():
    """Turn on jax's persistent XLA compilation cache and return its
    directory (:func:`compile_cache_dir`). Where the environment variable
    is set jax reads it itself and no directory is set in code."""
    import jax

    dirname = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(dirname, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", dirname)
    # the default threshold (1 s) would skip the long tail of small
    # programs a repeat run compiles again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    _compile_cache_state["configured"] = True
    return dirname


def maybe_enable_compile_cache():
    """Idempotent lazy hook the Executor calls before its first compile:
    honors ``FLAGS.compile_cache`` (opt-out)."""
    if _compile_cache_state["configured"]:
        return
    _compile_cache_state["configured"] = True
    from .flags import FLAGS
    if FLAGS.compile_cache:
        enable_compile_cache()


# -- background feed stage ----------------------------------------------------

_END = object()


class _Degraded(object):
    """Sentinel the dying feed thread hands over: carries the raw batch it
    failed on so the synchronous fallback can retry it — parity with the
    synchronous mode means no batch may be dropped."""

    __slots__ = ("item", "error")

    def __init__(self, item, error):
        self.item = item
        self.error = error


class _ReaderError(object):
    """The READER itself raised on the feed thread: re-raised in the
    consumer, exactly as the synchronous loop would see it — a dying
    reader must not silently truncate the pass."""

    __slots__ = ("error",)

    def __init__(self, error):
        self.error = error


class FeedPipeline(object):
    """Background feed stage: ``feeder.feed`` + ``device_put`` for batch
    k+1 run on a feed thread while batch k computes on the device.

    Iterating yields device-resident feed dicts, in reader order, from a
    bounded ring of ``depth`` positions (``depth=2`` = classic double
    buffering: one batch computing, one staging). The ring is the bounded
    queue itself: at most ``depth`` prefetched batches are alive
    device-side, and position ``k % depth`` is recycled as soon as the
    consumer frees it (``stats["slot_reuse"]``). jax arrays are
    immutable, so the reuse is of the ring position / allocation bound,
    not an in-place buffer mutation — true donation-based reuse is a
    ROADMAP follow-up.

    ``host_buffer=N`` additionally wraps the reader in
    ``reader.buffered(r, N)`` so raw-sample production (disk, decode —
    or the native recordio prefetch loader upstream of it) overlaps the
    feed conversion itself.

    Failure contract: an exception on the feed thread (instrumented as
    fault site ``pipeline.feed_next``) records a ``pipeline_degraded``
    resilience event and flips the pipeline to clean synchronous feeding
    on the consumer thread, retrying the batch that failed. Training
    continues; only the overlap is lost.
    """

    def __init__(self, reader, feeder, executor, depth=2, host_buffer=None):
        self.depth = max(int(depth), 1)
        self._feeder = feeder
        self._exe = executor
        if host_buffer:
            from . import reader as _reader_mod
            reader = _reader_mod.buffered(reader, host_buffer)
        self._it = iter(reader())
        self._q = queue.Queue(maxsize=self.depth)  # the ring: depth slots
        self._stop = False
        self._sync_mode = False
        self.stats = {"depth": self.depth, "batches": 0,
                      "feed_wait_ms": 0.0, "produce_wait_ms": 0.0,
                      "max_in_flight": 0, "slot_reuse": 0,
                      "fallback_sync": False}
        self._thread = threading.Thread(target=self._produce,
                                        name="paddle_tpu-feed", daemon=True)
        self._thread.start()

    # -- producer (feed thread) ----------------------------------------------
    def _prepare(self, raw):
        return self._exe.prepare_feed(self._feeder.feed(raw))

    def _produce(self):
        k = 0
        try:
            while not self._stop:
                try:
                    raw = next(self._it)
                except StopIteration:
                    break
                except BaseException as e:
                    self._put(_ReaderError(e))
                    return
                try:
                    fault_point("pipeline.feed_next")
                    dev = self._prepare(raw)
                except BaseException as e:
                    record_event("pipeline_degraded",
                                 site="pipeline.feed_next",
                                 error=repr(e), batch=k)
                    self._put(_Degraded(raw, e))
                    return
                slot = k % self.depth
                if k >= self.depth:
                    self.stats["slot_reuse"] += 1
                k += 1
                self._put((slot, dev))
                n = self._q.qsize()
                if n > self.stats["max_in_flight"]:
                    self.stats["max_in_flight"] = n
        finally:
            self._put(_END)

    def _put(self, item):
        t0 = time.perf_counter()
        while not self._stop:
            try:
                self._q.put(item, timeout=0.1)
                break
            except queue.Full:
                continue  # re-check _stop so close() can't deadlock us
        self.stats["produce_wait_ms"] += (time.perf_counter() - t0) * 1e3

    # -- consumer --------------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._sync_mode:
            return self._next_sync()
        t0 = time.perf_counter()
        e = self._q.get()
        self.stats["feed_wait_ms"] += (time.perf_counter() - t0) * 1e3
        if e is _END:
            raise StopIteration
        if isinstance(e, _ReaderError):
            raise e.error
        if isinstance(e, _Degraded):
            # feed thread died: finish the pass synchronously, starting
            # with the very batch it failed on (the fault may have been
            # transient; a persistent one raises here, exactly like the
            # synchronous mode would)
            self._sync_mode = True
            self.stats["fallback_sync"] = True
            self.stats["batches"] += 1
            return self._prepare(e.item)
        slot, dev = e
        self.stats["batches"] += 1
        return dev

    def _next_sync(self):
        raw = next(self._it)  # StopIteration ends the pass
        self.stats["batches"] += 1
        return self._prepare(raw)

    def close(self):
        """Stop the feed thread and release the ring (safe to call twice;
        called by Trainer even on early exit/preemption)."""
        self._stop = True
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

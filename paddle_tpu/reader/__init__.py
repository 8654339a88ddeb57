"""Composable data readers: a reader is a no-arg callable returning an
iterable of samples; decorators wrap readers.

reference: python/paddle/v2/reader/decorator.py (map_readers, buffered,
compose, chain, shuffle, firstn, xmap_readers), python/paddle/v2/minibatch.py
(batch), python/paddle/fluid/framework's reader ops
(CreateShuffleReaderOp/CreateBatchReaderOp, operators/create_reader_op.cc)
— here the decorator stack IS the reader framework; the C++ prefetch path
is paddle_tpu.reader.prefetch backed by the native runtime loader.

TPU addition: ``bucket`` groups variable-length samples into a small set of
length buckets so the executor's (total_tokens, num_seqs) compile cache stays
bounded — the shape-static answer to LoD's fully-dynamic batching.
"""
from __future__ import annotations

import heapq
import itertools
import random as _random
import threading
import queue as _queue

__all__ = [
    "map_readers", "buffered", "compose", "chain", "shuffle", "firstn",
    "xmap_readers", "batch", "bucket", "cache", "multiprocess_guard",
    "recordio", "recordio_prefetch",
]


def recordio(paths, deserializer=None):
    """Reader over native recordio files (one record per sample).
    reference: python/paddle/v2/reader/creator.py:60 (creator.recordio)."""
    if isinstance(paths, str):
        paths = [paths]

    def reader():
        from .. import native
        for p in paths:
            with native.Reader(p) as r:
                for rec in r:
                    yield deserializer(rec) if deserializer else rec

    return reader


def recordio_prefetch(paths, deserializer=None, num_threads=2,
                      queue_cap=256):
    """Reader over recordio files via the native threaded prefetch loader
    (the C++ double-buffer data path; reference role:
    gserver/dataproviders DoubleBufferedDataProvider)."""
    if isinstance(paths, str):
        paths = [paths]

    def reader():
        from .. import native
        loader = native.PrefetchLoader(paths, num_threads=num_threads,
                                       queue_cap=queue_cap)
        try:
            for rec in loader:
                yield deserializer(rec) if deserializer else rec
        finally:
            loader.close()

    return reader


def map_readers(func, *readers):
    """reader of func(*samples) zipped over readers.
    reference: v2/reader/decorator.py map_readers."""

    def reader():
        rs = [r() for r in readers]
        for vals in zip(*rs):
            yield func(*vals)

    return reader


def shuffle(reader, buf_size, seed=None):
    """reference: v2/reader/decorator.py shuffle — buffered shuffle.

    Each invocation (i.e. each training pass) advances the permutation so
    successive epochs see different orders; pass ``seed`` for a
    deterministic-but-per-pass-varying stream."""
    epoch = [0]

    def data_reader():
        epoch[0] += 1
        rng = (_random.Random(seed * 1000003 + epoch[0])
               if seed is not None else _random.Random())
        buf = []
        for e in reader():
            buf.append(e)
            if len(buf) >= buf_size:
                rng.shuffle(buf)
                for b in buf:
                    yield b
                buf = []
        if buf:
            rng.shuffle(buf)
            for b in buf:
                yield b

    return data_reader


def chain(*readers):
    """Concatenate readers. reference: v2/reader/decorator.py chain."""

    def reader():
        for r in readers:
            for e in r():
                yield e

    return reader


def compose(*readers, **kwargs):
    """Zip readers into flat tuples.
    reference: v2/reader/decorator.py compose (check_alignment)."""
    check_alignment = kwargs.pop("check_alignment", True)

    def make_tuple(x):
        return x if isinstance(x, tuple) else (x,)

    def reader():
        rs = [r() for r in readers]
        if check_alignment:
            for outputs in zip(*rs):
                yield sum([make_tuple(o) for o in outputs], ())
        else:
            for outputs in itertools.zip_longest(*rs):
                if any(o is None for o in outputs):
                    raise RuntimeError("readers not aligned")
                yield sum([make_tuple(o) for o in outputs], ())

    return reader


def buffered(reader, size):
    """Background-thread prefetch buffer.
    reference: v2/reader/decorator.py buffered (and the double-buffer thread
    in gserver/dataproviders/DataProvider.h DoubleBufferedDataProvider).

    A producer-thread exception is re-raised in the consumer instead of
    silently truncating the stream: the consumer can tell "reader done"
    from "reader died"."""

    class _End(object):
        pass

    class _Err(object):
        def __init__(self, error):
            self.error = error

    def data_reader():
        r = reader()
        q = _queue.Queue(maxsize=size)

        def feed():
            try:
                for d in r:
                    q.put(d)
                q.put(_End())
            except BaseException as e:
                q.put(_Err(e))

        t = threading.Thread(target=feed, daemon=True)
        t.start()
        while True:
            e = q.get()
            if isinstance(e, _End):
                break
            if isinstance(e, _Err):
                raise e.error
            yield e

    return data_reader


def firstn(reader, n):
    """reference: v2/reader/decorator.py firstn."""

    def data_reader():
        for i, item in enumerate(reader()):
            if i == n:
                break
            yield item

    return data_reader


def xmap_readers(mapper, reader, process_num, buffer_size, order=False):
    """Parallel map over a reader using worker threads.
    reference: v2/reader/decorator.py xmap_readers."""

    class _End(object):
        pass

    def data_reader():
        in_q = _queue.Queue(buffer_size)
        out_q = _queue.Queue(buffer_size)

        def read_worker():
            for i, d in enumerate(reader()):
                in_q.put((i, d))
            for _ in range(process_num):
                in_q.put(_End())

        def map_worker():
            while True:
                e = in_q.get()
                if isinstance(e, _End):
                    out_q.put(_End())
                    break
                i, d = e
                out_q.put((i, mapper(d)))

        threading.Thread(target=read_worker, daemon=True).start()
        workers = [threading.Thread(target=map_worker, daemon=True)
                   for _ in range(process_num)]
        for w in workers:
            w.start()

        finished = 0
        if order:
            pending = []
            next_i = 0
            while finished < process_num:
                e = out_q.get()
                if isinstance(e, _End):
                    finished += 1
                    continue
                heapq.heappush(pending, e)
                while pending and pending[0][0] == next_i:
                    yield heapq.heappop(pending)[1]
                    next_i += 1
            while pending:
                yield heapq.heappop(pending)[1]
        else:
            while finished < process_num:
                e = out_q.get()
                if isinstance(e, _End):
                    finished += 1
                    continue
                yield e[1]

    return data_reader


def batch(reader, batch_size, drop_last=False):
    """Group samples into lists of batch_size.
    reference: python/paddle/v2/minibatch.py batch."""

    def batch_reader():
        b = []
        for instance in reader():
            b.append(instance)
            if len(b) == batch_size:
                yield b
                b = []
        if b and not drop_last:
            yield b

    return batch_reader


def bucket(reader, batch_size, key=None, buckets=(16, 32, 64, 128, 256),
           buffer_batches=32, drop_last=False):
    """Length-bucketed batching: samples whose key (default: len of field 0)
    falls in the same bucket batch together, bounding the number of distinct
    padded shapes the jit cache sees. TPU-native replacement for free-form
    LoD batching (no reference equivalent — the reference pays per-shape
    nothing, XLA would pay a recompile)."""
    key = key or (lambda sample: len(sample[0]))

    def bucket_of(n):
        for b in buckets:
            if n <= b:
                return b
        return buckets[-1]

    def data_reader():
        pools = {}
        pending = 0
        for sample in reader():
            b = bucket_of(key(sample))
            pools.setdefault(b, []).append(sample)
            pending += 1
            if len(pools[b]) == batch_size:
                yield pools.pop(b)
                pending -= batch_size
            elif pending >= buffer_batches * batch_size:
                # flush the fullest pool to bound memory
                fullest = max(pools, key=lambda k: len(pools[k]))
                out = pools.pop(fullest)
                pending -= len(out)
                yield out
        for b in sorted(pools):
            if pools[b] and not drop_last:
                yield pools[b]

    return data_reader


def cache(reader):
    """Materialise a reader once, replay from memory afterwards."""
    memo = []
    done = [False]

    def data_reader():
        if done[0]:
            for e in memo:
                yield e
            return
        for e in reader():
            memo.append(e)
            yield e
        done[0] = True

    return data_reader


class multiprocess_guard(object):
    """API-parity shim for readers used under multiprocessing in the
    reference; threads suffice here."""

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

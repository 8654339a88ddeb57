"""DataFeeder: convert reader minibatches into the Executor feed dict.

reference: python/paddle/fluid/data_feeder.py:118 (DataFeeder /
DataToLoDTensorConverter) — rows of python/numpy values become dense arrays,
lod_level>0 fields become LoDTensors with offsets built from nested lists.

A dense field is stacked once, row by row, into a staging array the feeder
keeps and takes again when nothing else refers to it any more (see
``DataFeeder``); ragged and LoD fields go through the converter.
"""
from __future__ import annotations

import sys
import threading

import numpy as np

from . import profiler as _prof
from .core.ir import Variable
from .core.lod import LoDTensor, lengths_to_offsets
from .core.types import convert_dtype


def _refs(held, i):
    return sys.getrefcount(held[i])


# what _refs reads for an array that only ``held`` refers to
_SOLE_REF = _refs([np.empty(0)], 0)


class DataToLoDTensorConverter(object):
    """Collects one field's samples in a Python list and builds the batch
    with ``np.array(list, dtype)`` in ``done()``: a fresh array each time.
    ``DataFeeder`` uses it for LoD fields (``lod_level > 0``, nested lists
    flattened and their lengths kept as offsets) and for the dense batches
    it cannot stack in place (an empty batch, rows of unequal shape: the
    errors are ``np.array``'s)."""

    def __init__(self, lod_level, shape, dtype):
        self.lod_level = lod_level
        self.shape = tuple(s for s in shape if s != -1) if shape else ()
        self.dtype = dtype
        self.data = []
        self.lod = [[] for _ in range(lod_level)]

    def feed(self, data):
        self._feed_impl_(data, self.lod, self.lod_level)

    def _feed_impl_(self, data, lod, lod_level):
        if lod_level == 0:
            self.data.append(data)
        else:
            lod[0].append(len(data))
            for each_data in data:
                self._feed_impl_(each_data, lod[1:], lod_level - 1)

    def done(self):
        if self.lod_level == 0:
            arr = np.array(self.data, dtype=self.dtype)
            if self.shape and arr.ndim == 1 and len(self.shape) > 0:
                try:
                    arr = arr.reshape((-1,) + self.shape)
                except ValueError:
                    pass
            return arr
        flat = np.array(self.data, dtype=self.dtype)
        if self.shape:
            try:
                flat = flat.reshape((-1,) + self.shape)
            except ValueError:
                pass
        if flat.ndim == 1:
            flat = flat.reshape(-1, 1)
        t = LoDTensor(flat, [lengths_to_offsets(l) for l in self.lod])
        return t


def _row(value, dtype):
    """A sample's value as an array: an ndarray as it is (its dtype is
    converted by the copy into the batch), anything else as
    ``np.array(list, dtype)`` would read it."""
    return value if type(value) is np.ndarray else np.asarray(value, dtype)


def _copy_rows(rows, samples, field):
    """Each sample's value of ``field`` into its row of ``rows``; False at
    the first that is no row of ``rows``' shape (nothing broadcasts) or
    that numpy cannot convert."""
    shape, dtype = rows.shape[1:], rows.dtype
    try:
        for i, each_sample in enumerate(samples):
            value = _row(each_sample[field], dtype)
            if value.shape != shape:
                return False
            rows[i] = value
    except (ValueError, TypeError, OverflowError):
        return False        # np.array says which, in its own words
    return True


class DataFeeder(object):
    """reference: python/paddle/fluid/data_feeder.py DataFeeder.

    **Who owns a fed array.** A dense field (``lod_level == 0``) comes
    back as a *staging array* that the feeder keeps a reference to. The
    array is the caller's for as long as the caller — or anything that
    got it from the caller — refers to it: the feed dict, a view or
    slice, a ``memoryview``, an ``Executor.prepare_feed`` /
    ``jax.device_put`` still copying from it, a device array that aliases
    it (XLA:CPU may keep the numpy buffer as the device buffer). Once the
    feeder's own reference is the only one left, a later ``feed`` of the
    same batch shape writes the next batch into it, so a training loop
    maps its batch memory once instead of once a step. The test is the
    array's reference count; nothing has to be handed back and no call
    marks a batch as done. Keep the array (or a view) and it is never
    written again; keep only a raw address (``arr.ctypes.data``) and it
    may be. LoD fields, an empty batch and rows of unequal shape are
    built fresh by ``DataToLoDTensorConverter``.
    """

    def __init__(self, feed_list, place=None, program=None):
        self.feed_dtypes = []
        self.feed_names = []
        self.feed_shapes = []
        self.feed_lod_level = []
        for each_var in feed_list:
            if isinstance(each_var, str):
                from .core.ir import default_main_program
                each_var = (program or default_main_program()) \
                    .global_block().var(each_var)
            if not isinstance(each_var, Variable):
                raise TypeError("feed_list entries must be Variables/names")
            self.feed_names.append(each_var.name)
            self.feed_lod_level.append(each_var.lod_level)
            self.feed_shapes.append(each_var.shape)
            self.feed_dtypes.append(convert_dtype(each_var.dtype))
        self.place = place
        # per-field (lod_level, shape less its batch wildcards, dtype),
        # resolved once
        self._converter_specs = [
            (lod, tuple(d for d in shape or () if d != -1), dtype)
            for lod, shape, dtype in zip(self.feed_lod_level,
                                         self.feed_shapes, self.feed_dtypes)]
        # the staging arrays handed out, per field, under _lock: the only
        # state that feed() calls share
        self._staged = [[] for _ in self.feed_names]
        self._lock = threading.Lock()

    def feed(self, iterable):
        """Minibatch (iterable of per-sample field tuples) -> feed dict.

        Dense fields are stacked into staging arrays (class docstring)
        on the calling thread, inside the ``feed`` span (args ``rows``,
        ``bytes``).

        Safe to call from several threads at once: a staging array is taken
        under the feeder's lock and, from then on, referred to by the
        call that took it, so no other call can take it; everything else
        is local to the call."""
        with _prof.span("feed") as span:
            samples = (iterable if isinstance(iterable, (list, tuple))
                       else list(iterable))
            fields = len(self._converter_specs)
            for each_sample in samples:
                if len(each_sample) != fields:
                    raise ValueError(
                        "sample has %d fields, feed_list expects %d"
                        % (len(each_sample), fields))
            out = {name: self._stack(samples, field)
                   for field, name in enumerate(self.feed_names)}
            span.set_metadata(rows=len(samples),
                              bytes=sum(np.asarray(batch).nbytes
                                        for batch in out.values()))
            return out

    def _stack(self, samples, field):
        """The batch of one field: stacked in place where it is dense and
        its rows have one shape, else as the converter builds it."""
        lod_level, shape, dtype = self._converter_specs[field]
        batch = None
        if lod_level == 0 and samples:
            batch = self._stack_dense(samples, field, shape, dtype)
        if batch is None:
            conv = DataToLoDTensorConverter(lod_level, shape, dtype)
            for each_sample in samples:
                conv.feed(each_sample[field])
            batch = conv.done()
        return batch

    def _stack_dense(self, samples, field, shape, dtype):
        """samples' rows of ``field`` in a staging array, in the bytes,
        shape and dtype of ``np.array(rows, dtype)`` (+ the converter's
        reshape of a batch of scalars); None where they do not stack
        (the array taken stays with the feeder for a later batch)."""
        n = len(samples)
        try:
            row_shape = _row(samples[0][field], dtype).shape
        except (ValueError, TypeError, OverflowError):
            return None
        batch_shape = (n,) + row_shape
        if not row_shape and shape:
            per = int(np.prod(shape))
            if per and n % per == 0:
                batch_shape = (n // per,) + shape
        batch = self._staging(field, batch_shape, dtype)
        rows = batch.reshape((n,) + row_shape)          # a view
        return batch if _copy_rows(rows, samples, field) else None

    def _staging(self, field, shape, dtype):
        """An array of ``shape`` that nothing but this call can read or
        write: one handed out earlier that only the feeder still refers
        to, else a new one. Of the other arrays found unreferenced one of
        this shape stays as a spare and the rest are let go: the feeder
        holds what is in flight and one batch more. The spare is for a
        loop whose uploads take turns on two arrays (batch n+1 is stacked
        while jax still refers to batch n's array): jax lets go of an
        array at its next ``device_put`` or at any pass of Python's
        collector, so now and then both are found free, and with one let
        go the next batch would map 154 MB of fresh pages (~150 ms on the
        chip's host) two steps later."""
        with self._lock:
            held = self._staged[field]
            free = [held.pop(i) for i in reversed(range(len(held)))
                    if _refs(held, i) == _SOLE_REF]
            fit = [a for a in free if a.shape == shape][:2]
            taken = fit.pop(0) if fit else np.empty(shape, dtype)
            held.extend(fit)            # the spare, if there was one
            held.append(taken)
            return taken

"""DataFeeder: convert reader minibatches into the Executor feed dict.

reference: python/paddle/fluid/data_feeder.py:118 (DataFeeder /
DataToLoDTensorConverter) — rows of python/numpy values become dense arrays,
lod_level>0 fields become LoDTensors with offsets built from nested lists.
"""
from __future__ import annotations

import numpy as np

from . import profiler as _prof
from .core.ir import Variable
from .core.lod import LoDTensor, lengths_to_offsets
from .core.types import convert_dtype


class DataToLoDTensorConverter(object):
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


class DataFeeder(object):
    """reference: python/paddle/fluid/data_feeder.py DataFeeder."""

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
        # per-field converter specs, resolved once: feed() builds fresh
        # converters from these each call, so it carries no mutable state
        # between calls — safe to run on the async pipeline's feed thread
        # concurrently with Executor.run on the main thread
        self._converter_specs = list(zip(self.feed_lod_level,
                                         self.feed_shapes,
                                         self.feed_dtypes))

    def feed(self, iterable):
        """Minibatch (iterable of per-sample field tuples) -> feed dict.
        Stateless per call (thread-safe; see _converter_specs)."""
        with _prof.span("feed") as span:
            converters = [
                DataToLoDTensorConverter(lod_level=lod, shape=shape or (),
                                         dtype=dtype)
                for lod, shape, dtype in self._converter_specs]
            rows = 0
            for each_sample in iterable:
                if len(each_sample) != len(converters):
                    raise ValueError(
                        "sample has %d fields, feed_list expects %d"
                        % (len(each_sample), len(converters)))
                for value, conv in zip(each_sample, converters):
                    conv.feed(value)
                rows += 1
            span.set_metadata(rows=rows)
            return {name: conv.done()
                    for name, conv in zip(self.feed_names, converters)}

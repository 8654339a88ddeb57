"""ctypes bindings to the native runtime (native/paddle_tpu_native.cc):
recordio storage, threaded prefetch loader, fault-tolerant task master.

Built on demand with make/g++ (no pybind11 in this environment; the C ABI +
ctypes is the binding layer, playing the role of the reference's pybind
`core`, paddle/fluid/pybind/pybind.cc:60, for these host-runtime pieces).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

from ..resilience import fault_point

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libpaddle_tpu_native.so")
_lock = threading.Lock()
_lib = None
_build_error = None


def _build():
    subprocess.run(["make", "-s", "-C", _NATIVE_DIR], check=True,
                   capture_output=True)


def load():
    """Build (if needed) and load the native library; raises RuntimeError
    with the build log when no toolchain is available."""
    global _lib, _build_error
    with _lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            raise RuntimeError(_build_error)
        try:
            # rebuild keyed on source content hash, not mtimes (git
            # checkouts don't preserve mtime ordering)
            src = os.path.join(_NATIVE_DIR, "paddle_tpu_native.cc")
            with open(src, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()
            stamp = _LIB_PATH + ".srchash"
            stale = True
            if os.path.exists(_LIB_PATH) and os.path.exists(stamp):
                with open(stamp) as f:
                    stale = f.read().strip() != digest
            if stale:
                _build()
                with open(stamp, "w") as f:
                    f.write(digest)
            lib = ctypes.CDLL(_LIB_PATH)
        except Exception as e:  # toolchain absent / build broke
            _build_error = "native runtime unavailable: %s" % e
            raise RuntimeError(_build_error)
        _configure(lib)
        _lib = lib
        return lib


def available() -> bool:
    try:
        load()
        return True
    except RuntimeError:
        return False


def _configure(lib):
    c = ctypes
    u8p = c.POINTER(c.c_uint8)
    lib.rio_writer_open.restype = c.c_void_p
    lib.rio_writer_open.argtypes = [c.c_char_p]
    lib.rio_writer_write.restype = c.c_int
    lib.rio_writer_write.argtypes = [c.c_void_p, u8p, c.c_uint32]
    lib.rio_writer_count.restype = c.c_uint64
    lib.rio_writer_count.argtypes = [c.c_void_p]
    lib.rio_writer_close.restype = c.c_int
    lib.rio_writer_close.argtypes = [c.c_void_p]
    lib.rio_reader_open.restype = c.c_void_p
    lib.rio_reader_open.argtypes = [c.c_char_p]
    lib.rio_reader_next.restype = c.c_int64
    lib.rio_reader_next.argtypes = [c.c_void_p, c.POINTER(u8p)]
    lib.rio_reader_seek_record.restype = c.c_int
    lib.rio_reader_seek_record.argtypes = [c.c_void_p, c.c_uint64]
    lib.rio_reader_close.restype = c.c_int
    lib.rio_reader_close.argtypes = [c.c_void_p]
    lib.loader_create.restype = c.c_void_p
    lib.loader_create.argtypes = [c.POINTER(c.c_char_p), c.c_int, c.c_int,
                                  c.c_int]
    lib.loader_next.restype = c.c_int64
    lib.loader_next.argtypes = [c.c_void_p, c.POINTER(u8p)]
    lib.loader_destroy.restype = None
    lib.loader_destroy.argtypes = [c.c_void_p]
    lib.master_create.restype = c.c_void_p
    lib.master_create.argtypes = [c.c_int, c.c_double]
    lib.master_add_task.restype = c.c_int64
    lib.master_add_task.argtypes = [c.c_void_p, u8p, c.c_uint32]
    lib.master_get_task.restype = c.c_int64
    lib.master_get_task.argtypes = [c.c_void_p, c.POINTER(u8p),
                                    c.POINTER(c.c_int64)]
    lib.master_task_finished.restype = c.c_int
    lib.master_task_finished.argtypes = [c.c_void_p, c.c_int64]
    lib.master_task_failed.restype = c.c_int
    lib.master_task_failed.argtypes = [c.c_void_p, c.c_int64]
    lib.master_counts.restype = c.c_int64
    lib.master_counts.argtypes = [c.c_void_p] + [c.POINTER(c.c_int64)] * 4
    lib.master_new_pass.restype = c.c_int
    lib.master_new_pass.argtypes = [c.c_void_p]
    lib.master_destroy.restype = None
    lib.master_destroy.argtypes = [c.c_void_p]
    lib.master_snapshot.restype = c.c_int
    lib.master_snapshot.argtypes = [c.c_void_p, c.c_char_p]
    lib.master_restore.restype = c.c_int64
    lib.master_restore.argtypes = [c.c_void_p, c.c_char_p]
    lib.master_register_worker.restype = c.c_int64
    lib.master_register_worker.argtypes = [c.c_void_p, u8p, c.c_uint32]
    lib.master_heartbeat.restype = c.c_int
    lib.master_heartbeat.argtypes = [c.c_void_p, c.c_int64]
    lib.master_worker_count.restype = c.c_int64
    lib.master_worker_count.argtypes = [c.c_void_p]
    lib.master_serve.restype = c.c_void_p
    lib.master_serve.argtypes = [c.c_void_p, c.c_int]
    lib.master_serve_port.restype = c.c_int
    lib.master_serve_port.argtypes = [c.c_void_p]
    lib.master_serve_stop.restype = None
    lib.master_serve_stop.argtypes = [c.c_void_p]


def _as_u8p(data: bytes):
    return ctypes.cast(ctypes.create_string_buffer(data, len(data)),
                       ctypes.POINTER(ctypes.c_uint8))


# -- python-facing wrappers ---------------------------------------------------

class Writer(object):
    """recordio writer. reference role: recordio format the Go master
    shards by (go/master/service.go partition)."""

    def __init__(self, path):
        self._lib = load()
        self._h = self._lib.rio_writer_open(path.encode())
        if not self._h:
            raise IOError("cannot open %s for writing" % path)

    def write(self, record: bytes):
        if self._lib.rio_writer_write(self._h, _as_u8p(record),
                                      len(record)) != 0:
            raise IOError("recordio write failed")

    @property
    def count(self):
        return self._lib.rio_writer_count(self._h)

    def close(self):
        if self._h:
            self._lib.rio_writer_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class Reader(object):
    def __init__(self, path, skip_records=0):
        self._lib = load()
        self._h = self._lib.rio_reader_open(path.encode())
        if not self._h:
            raise IOError("cannot open recordio file %s" % path)
        if skip_records:
            if self._lib.rio_reader_seek_record(self._h, skip_records) != 0:
                raise IOError("seek past end of %s" % path)

    def __iter__(self):
        return self

    def __next__(self):
        # resilience fault site: chaos tests drop/delay/corrupt records
        # here without touching the native layer (disarmed: one dict get)
        fault_point("reader.next")
        p = ctypes.POINTER(ctypes.c_uint8)()
        n = self._lib.rio_reader_next(self._h, ctypes.byref(p))
        if n == -1:
            raise StopIteration
        if n == -2:
            raise IOError("recordio corruption detected (crc mismatch)")
        return ctypes.string_at(p, n)

    def close(self):
        if self._h:
            self._lib.rio_reader_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class PrefetchLoader(object):
    """Threaded record loader over recordio files (native double-buffer
    path; reference role: DataProvider double-buffering)."""

    def __init__(self, paths, num_threads=2, queue_cap=256):
        self._lib = load()
        arr = (ctypes.c_char_p * len(paths))(*[p.encode() for p in paths])
        self._h = self._lib.loader_create(arr, len(paths), num_threads,
                                          queue_cap)

    def __iter__(self):
        return self

    def __next__(self):
        p = ctypes.POINTER(ctypes.c_uint8)()
        n = self._lib.loader_next(self._h, ctypes.byref(p))
        if n < 0:
            raise StopIteration
        return ctypes.string_at(p, n)

    def close(self):
        if self._h:
            self._lib.loader_destroy(self._h)
            self._h = None


class TaskMaster(object):
    """Fault-tolerant task queue (lease/timeout/failure-cap/pass semantics
    of the reference Go master, in-process; multi-host deployments front it
    with jax.distributed's coordination service)."""

    PASS_FINISHED = 0

    def __init__(self, failure_max=3, timeout_sec=60.0):
        self._lib = load()
        self._h = self._lib.master_create(failure_max, timeout_sec)

    def add_task(self, payload: bytes) -> int:
        return self._lib.master_add_task(self._h, _as_u8p(payload),
                                         len(payload))

    def get_task(self):
        """-> (task_id, payload) | ("wait", None) | (None, None) when the
        pass is finished."""
        p = ctypes.POINTER(ctypes.c_uint8)()
        n = ctypes.c_int64()
        tid = self._lib.master_get_task(self._h, ctypes.byref(p),
                                        ctypes.byref(n))
        if tid == 0:
            return None, None
        if tid == -1:
            return "wait", None
        return tid, ctypes.string_at(p, n.value)

    def task_finished(self, task_id):
        self._lib.master_task_finished(self._h, task_id)

    def task_failed(self, task_id):
        """1 = failure_max exhausted, task dropped; 0 = re-queued;
        -1 = unknown/expired lease."""
        return self._lib.master_task_failed(self._h, task_id)

    def counts(self):
        vals = [ctypes.c_int64() for _ in range(4)]
        self._lib.master_counts(self._h, *[ctypes.byref(v) for v in vals])
        return {"todo": vals[0].value, "pending": vals[1].value,
                "done": vals[2].value, "failed": vals[3].value}

    def new_pass(self):
        self._lib.master_new_pass(self._h)

    # -- elastic worker registry (reference: go/pserver/etcd_client.go
    # lease registration; timeout_sec doubles as the worker lease TTL) ----
    def register_worker(self, name="worker") -> int:
        b = name.encode("utf-8")
        return self._lib.master_register_worker(self._h, _as_u8p(b),
                                                len(b))

    def heartbeat(self, worker_id) -> bool:
        """False when the lease lapsed — re-register for a new id."""
        return self._lib.master_heartbeat(self._h, worker_id) == 0

    def worker_count(self) -> int:
        return self._lib.master_worker_count(self._h)

    def close(self):
        if self._serve_h:
            self._lib.master_serve_stop(self._serve_h)
            self._serve_h = None
        if self._h:
            self._lib.master_destroy(self._h)
            self._h = None

    # -- cross-process service (reference: go/master/service.go RPC) -------
    _serve_h = None

    def serve(self, port=0) -> int:
        """Expose the queue over TCP so worker *processes* lease tasks
        (length-prefixed binary protocol; see MasterClient). Returns the
        bound port."""
        h = self._lib.master_serve(self._h, port)
        if not h:
            raise RuntimeError("master_serve failed (port %d)" % port)
        self._serve_h = h
        return self._lib.master_serve_port(h)

    def snapshot(self, path) -> None:
        """Atomic snapshot of todo+pending payloads — leased tasks are
        persisted re-runnable, the Go master's etcd recovery semantics
        (go/master/service.go:313-366)."""
        rc = self._lib.master_snapshot(self._h, path.encode())
        if rc != 0:
            raise IOError("master_snapshot(%r) rc=%d" % (path, rc))

    def restore(self, path) -> int:
        """Re-queue tasks from a snapshot; returns how many were added."""
        n = self._lib.master_restore(self._h, path.encode())
        if n < 0:
            raise IOError("master_restore(%r) failed" % path)
        return n


class MasterClient(object):
    """Socket client for TaskMaster.serve — what a worker process runs
    (reference: go/master/client.go). Frames:
    request [u8 op][u32 len][payload], response [i64 a][u32 len][payload].
    """

    (GET, ADD, FIN, FAIL, COUNTS, NEW_PASS, SNAPSHOT, PING,
     REGISTER, HEARTBEAT, WORKER_COUNT) = range(1, 12)

    def __init__(self, host, port, timeout=30.0):
        import socket
        self._sock = socket.create_connection((host, port), timeout=timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._call_lock = threading.Lock()

    def _call(self, op, payload=b""):
        # one request/response pair at a time: a thread that leases
        # (GET) while another commits (FIN) on the SAME connection
        # would — unserialized — cross responses, so a commit could
        # consume a lease reply (a spurious "lease lost" for a task the
        # master counted done — a row silently missing from the
        # exactly-once audit trail)
        import struct
        with self._call_lock:
            self._sock.sendall(struct.pack("<BI", op, len(payload))
                               + payload)
            hdr = self._recv(12)
            a, n = struct.unpack("<qI", hdr)
            data = self._recv(n) if n else b""
        return a, data

    def _recv(self, n):
        buf = b""
        while len(buf) < n:
            chunk = self._sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("master connection closed")
            buf += chunk
        return buf

    def get_task(self):
        """-> (task_id, payload) | ("wait", None) while other workers hold
        leases | (None, None) when the pass is finished — the same contract
        as TaskMaster.get_task."""
        tid, data = self._call(self.GET)
        if tid == 0:
            return None, None
        if tid < 0:
            return "wait", None
        return tid, data

    def add_task(self, payload: bytes) -> int:
        tid, _ = self._call(self.ADD, payload)
        return tid

    def task_finished(self, task_id) -> bool:
        """False when the lease had already expired and the task was
        reclaimed — the caller's work may run twice; don't double-commit."""
        import struct
        rc, _ = self._call(self.FIN, struct.pack("<q", task_id))
        return rc == 0

    def task_failed(self, task_id) -> int:
        """Same tri-state as TaskMaster.task_failed (1 dropped, 0
        re-queued, -1 unknown lease) — decided atomically server-side."""
        import struct
        rc, _ = self._call(self.FAIL, struct.pack("<q", task_id))
        return rc

    def counts(self):
        import struct
        _, data = self._call(self.COUNTS)
        todo, pending, done, failed = struct.unpack("<4q", data)
        return {"todo": todo, "pending": pending, "done": done,
                "failed": failed}

    def new_pass(self):
        self._call(self.NEW_PASS)

    def snapshot(self, path):
        rc, _ = self._call(self.SNAPSHOT, path.encode())
        if rc != 0:
            raise IOError("snapshot rc=%d" % rc)

    def ping(self) -> bool:
        try:
            a, _ = self._call(self.PING)
            return a == 42
        except Exception:
            return False

    # -- elastic worker registry -----------------------------------------
    def register_worker(self, name="worker") -> int:
        wid, _ = self._call(self.REGISTER, name.encode("utf-8"))
        return wid

    def heartbeat(self, worker_id) -> bool:
        import struct
        rc, _ = self._call(self.HEARTBEAT, struct.pack("<q", worker_id))
        return rc == 0

    def worker_count(self) -> int:
        n, _ = self._call(self.WORKER_COUNT)
        return n

    def close(self):
        self._sock.close()

"""What every cell shares: finding a cell's files by name, the device check,
the compile cache, the traced window, the per-layer readers and the result
line. Nothing here knows a model or a traffic mix."""
from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


_MODULES = {}


def load_module(*parts):
    """Import a file under chipbench/ by path (metric names hold dots).
    One module object per file and process: a second copy of a reference
    would compile, and keep, every program a second time."""
    path = os.path.join(HERE, *parts)
    if path not in _MODULES:
        name = "chipbench_" + "_".join(parts).replace(".", "_").replace(
            "-", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def load_cell(name):
    """The cell's entry of BENCHMARK.json with its configuration, its
    traffic mix, its own file and the metrics it reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit("unknown workload %r; BENCHMARK.json has %s"
                         % (name, sorted(cells)))
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[entry["config"]]["file"])) as f:
        config = json.load(f)

    def mine(metrics, reported=None):
        out = []
        for m in metrics:
            cells_of = m.get("workloads")
            if cells_of is not None and name not in cells_of:
                continue
            if cells_of is None and reported is not None \
                    and m["moves"] not in reported:
                continue
            out.append(m)
        return out

    end_to_end = mine(bench["end_to_end"])
    per_layer = mine(bench["per_layer"], {m["name"] for m in end_to_end})
    return {
        "name": name, "entry": entry, "config": config,
        "traffic": load_json("traffic", entry["traffic"] + ".json"),
        "cell": load_json("workloads", name + ".json"),
        "end_to_end": end_to_end, "per_layer": per_layer,
    }


def setup_compile_cache():
    """JAX's persistent compile cache: where JAX_COMPILATION_CACHE_DIR
    says, else at the fixed path <checkout>/.jax_cache (the program's own
    default too, so both write one directory)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        dirname = os.path.join(ROOT, ".jax_cache")
        os.makedirs(dirname, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", dirname)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)


def require_chips(chips):
    """The devices of this machine, which has to hold ``chips`` TPU chips."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip("jax found no accelerator: %s" % (e,))
    if devices[0].platform != "tpu":
        raise NoChip("jax reports platform %r, not tpu"
                     % (devices[0].platform,))
    if len(devices) < chips:
        raise NoChip("the cell asks for %d chips, jax reports %d"
                     % (chips, len(devices)))
    return devices[:chips]


def peaks_for(device_kind):
    table = load_json("peaks.json")
    if device_kind not in table:
        raise KeyError("device kind %r is not in chipbench/peaks.json"
                       % (device_kind,))
    return table[device_kind]


def device_report(devices):
    """The device as JAX reports it. The peak of the fullest chip is its
    peak of live buffers plus its peak of bytes reserved for the loaded
    programs' temporaries: on this runtime ``peak_bytes_in_use`` leaves the
    temporaries out and ``peak_bytes_reserved`` is exactly them (PERF.md)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def quantile(values, q):
    """The q-quantile (0..1) as the value at rank ceil(q (n - 1)): no
    interpolation, so a value standing for "never" stays what it is."""
    s = sorted(values)
    if not s:
        return None
    return s[min(int(math.ceil(q * (len(s) - 1))), len(s) - 1)]


def spread(values):
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``): how bounds are set."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


class CompileWatch(object):
    """Counts jax's own compile events (a compile or a read of the
    persistent cache), so a program compiled inside the window is seen
    whatever layer asked for it."""

    def __init__(self):
        self.compiles = 0
        self.cache_reads = 0
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_a, **_kw):
        if name.endswith("backend_compile_duration"):
            self.compiles += 1
        elif name.endswith("cache_retrieval_time_sec"):
            self.cache_reads += 1

    @property
    def total(self):
        return self.compiles + self.cache_reads


TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


@contextlib.contextmanager
def traced_window(enabled, out):
    """Record the profiler's trace of the enclosed window (``--trace 1``).
    ``out`` gains ``xplane``, the path of the trace file, and
    ``window_s``, the host-clock length of the traced window."""
    if not enabled:
        yield
        return
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(TRACE_DIR)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the spans are ours; no call tracing
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        out["window_s"] = time.perf_counter() - t0
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        out["xplane"] = found[0] if found else None


def span(name, enabled=True):
    """A host span on the profiler's own clock (benchmark-side only)."""
    if not enabled:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation("chipbench/" + name)


def read_layer_metrics(cell, ctx):
    """Run the cell's per-layer readers over ``ctx``; one that finds
    nothing to read returns None and is left out of the line."""
    out = {}
    for m in cell["per_layer"]:
        reader = load_module("layer_metrics", m["name"] + ".py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(correct, attempted, failed, metrics, device, compared,
                breakdown=None):
    """The one JSON object that ends standard output; ``compared`` (each
    number beside its limit) comes last and is echoed on standard error."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    for name, c in compared.items():
        print("compared %s: %r limit %r %s"
              % (name, c["value"], c["limit"],
                 "ok" if c["ok"] else "OVER"), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()

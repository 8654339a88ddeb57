"""From the profiler's trace (``*.xplane.pb``) to what the per-layer readers
use: per device the busy intervals, the time of every operation and of every
compiled program, the idle gaps, and the benchmark's own host spans.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A device
plane is one whose name starts with ``/device:TPU:``; its line ``XLA Ops``
holds one event per executed HLO operation, its line ``XLA Modules`` one per
executed program. Operations can nest (a ``while`` holds its body), so busy
time is the union of the intervals and an operation's time is its self time.
"""
from __future__ import annotations

import collections

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench/"


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events):
    """``events``: (start, end, key) possibly nested. Returns
    {key: [count, self_ns]} where a parent's time leaves out its children."""
    total = collections.defaultdict(lambda: [0, 0])
    stack = []      # [end, key, child_ns, start]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, key, child, start = stack.pop()
            total[key][0] += 1
            total[key][1] += (end - start) - child
            if stack:
                stack[-1][2] += end - start

    for s, e, key in sorted(events, key=lambda t: (t[0], -t[1])):
        close(s)
        stack.append([e, key, 0, s])
    close(float("inf"))
    return {k: (c, ns) for k, (c, ns) in total.items()}


def short_name(name, limit=96):
    """An operation's name as the trace gives it is its whole HLO text;
    keep the instruction's name, its result type and its opcode."""
    if " = " not in name:
        return name[:limit]
    left, right = name.split(" = ", 1)
    head = right.split("(", 1)[0] if not right.startswith("(") else "(tuple)"
    kind = ""
    if "kind=" in right:
        kind = " " + right.split("kind=", 1)[1].split(",", 1)[0]
    return ("%s %s%s" % (left.lstrip("%"), head.strip(), kind))[:limit]


def reduce_planes(planes):
    """``planes``: [(plane_name, [(line_name, [(name, start_ns,
    dur_ns)])])]. Pure function of plain data, so a recorded trace kept as
    JSON checks it."""
    devices, spans = [], []
    for pname, lines in planes:
        if pname.startswith(DEVICE_PREFIX):
            dev = {"plane": pname, "ops": {}, "modules": {}, "busy": []}
            for lname, events in lines:
                if lname == OPS_LINE:
                    dev["busy"] = merge((s, s + d) for _n, s, d in events)
                    dev["ops"] = self_times(
                        (s, s + d, n) for n, s, d in events)
                elif lname == MODULES_LINE:
                    mods = collections.defaultdict(lambda: [0, 0, []])
                    for n, s, d in events:
                        m = mods[n.split("(")[0]]
                        m[0] += 1
                        m[1] += d
                        m[2].append((s, s + d))
                    dev["modules"] = {k: tuple(v) for k, v in mods.items()}
            devices.append(dev)
        else:
            for _lname, events in lines:
                for n, s, d in events:
                    if n.startswith(SPAN_PREFIX):
                        spans.append((n[len(SPAN_PREFIX):], s, s + d))
    devices.sort(key=lambda d: d["plane"])
    return {"devices": devices, "spans": sorted(spans, key=lambda t: t[1])}


def read_planes(path):
    """The planes of an xplane file as the plain data ``reduce_planes``
    takes. Only device planes and benchmark spans are kept."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                name = ev.name
                if not is_dev and not name.startswith(SPAN_PREFIX):
                    continue
                if is_dev and line.name == OPS_LINE:
                    name = short_name(name)
                events.append((name, int(ev.start_ns), int(ev.duration_ns)))
            if events:
                lines.append((line.name, events))
        if lines:
            planes.append((plane.name, lines))
    return planes


def busy_seconds(reduction, within=None):
    """Seconds in which an operation ran, averaged over the device planes;
    ``within`` = (start_ns, end_ns) clips to a window."""
    devs = reduction["devices"]
    if not devs:
        return 0.0
    total = 0
    for dev in devs:
        for s, e in dev["busy"]:
            if within is not None:
                s, e = max(s, within[0]), min(e, within[1])
            if e > s:
                total += e - s
    return total / len(devs) / 1e9


def window_of(reduction, span_name="window"):
    """(start_ns, end_ns) of the benchmark's window span, else of all device
    work."""
    for n, s, e in reduction["spans"]:
        if n == span_name:
            return (s, e)
    edges = [(d["busy"][0][0], d["busy"][-1][1])
             for d in reduction["devices"] if d["busy"]]
    if not edges:
        return None
    return (min(s for s, _ in edges), max(e for _, e in edges))


def idle_gaps(reduction, within, top=10):
    """The longest idle gaps of device 0 inside ``within``, each named by
    the innermost benchmark span that covers its middle (``no_span`` when
    none does), and the total idle time by name: ([name, seconds] * top,
    {name: seconds})."""
    devs = reduction["devices"]
    if not devs or within is None:
        return [], {}
    gaps, at = [], within[0]
    for s, e in devs[0]["busy"]:
        if e <= within[0] or s >= within[1]:
            continue
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if within[1] > at:
        gaps.append((at, within[1]))
    spans = [t for t in reduction["spans"] if t[0] != "window"]
    by_name = collections.defaultdict(float)
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        name, width = "no_span", None
        for n, ss, se in spans:
            if ss <= mid <= se and (width is None or se - ss < width):
                name, width = n, se - ss
        by_name[name] += (e - s) / 1e9
        named.append([name, (e - s) / 1e9])
    named.sort(key=lambda t: -t[1])
    return named[:top], dict(by_name)


def top_ops(reduction, top=10):
    """[name, seconds] of the operations with most self time on device 0."""
    devs = reduction["devices"]
    if not devs:
        return []
    ops = sorted(devs[0]["ops"].items(), key=lambda kv: -kv[1][1])
    return [[k, ns / 1e9] for k, (_c, ns) in ops[:top]]


def programs_by_launch(reduction, launches):
    """Device time per kind of program. ``launches``: the order in which the
    host launched its programs as (kind, size, live) tuples. The device runs
    programs in launch order, so the i-th event of ``XLA Modules`` is the
    i-th launch whatever its name. Returns {kind: {"seconds", "count",
    "size", "live"}}. Counts that disagree are an error, never a guess:
    the readers would otherwise fall silent and nobody would see why."""
    devs = reduction["devices"]
    events = sorted((s, e) for dev in devs[:1]
                    for _c, _ns, ivals in dev["modules"].values()
                    for s, e in ivals)
    if len(events) != len(launches):
        raise ValueError("the host launched %d programs in the window, the "
                         "trace's %r line holds %d" % (
                             len(launches), MODULES_LINE, len(events)))
    out = {}
    for (s, e), (kind, size, live) in zip(events, launches):
        k = out.setdefault(kind, {"seconds": 0.0, "count": 0, "size": 0,
                                  "live": 0})
        k["seconds"] += (e - s) / 1e9
        k["count"] += 1
        k["size"] += size
        k["live"] += live
    return out


def describe(path, out=None):
    """For the look by hand: every plane and line of a trace with its event
    count, first names and the stat keys of its first event."""
    import sys
    from jax.profiler import ProfileData
    out = out or sys.stdout
    for plane in ProfileData.from_file(path).planes:
        print("plane", repr(plane.name), file=out)
        for line in plane.lines:
            events = list(line.events)
            names = []
            for ev in events:
                if ev.name not in names:
                    names.append(ev.name)
                if len(names) >= 12:
                    break
            keys = [k for k, _v in events[0].stats] if events else []
            print("  line %r: %d events; names %r; stat keys %r"
                  % (line.name, len(events), names, keys), file=out)


def cut(planes, start_ns, end_ns):
    """The events of ``planes`` that start inside [start_ns, end_ns)."""
    out = []
    for pname, lines in planes:
        kept = [(ln, [e for e in evs if start_ns <= e[1] < end_ns])
                for ln, evs in lines]
        kept = [(ln, evs) for ln, evs in kept if evs]
        if kept:
            out.append((pname, kept))
    return out


if __name__ == "__main__":
    # python3 chipbench/trace_reduce.py <trace.xplane.pb> <out.json> [ms]
    # writes the description to <out.json>.txt and, cut to the first [ms]
    # of device work, the planes as JSON (how testdata/ was recorded)
    import json
    import sys
    src, dst = sys.argv[1], sys.argv[2]
    ms = float(sys.argv[3]) if len(sys.argv) > 3 else 500.0
    with open(dst + ".txt", "w") as f:
        describe(src, f)
    planes = read_planes(src)
    red = reduce_planes(planes)
    t0 = min(d["busy"][0][0] for d in red["devices"] if d["busy"])
    with open(dst, "w") as f:
        json.dump(cut(planes, t0, t0 + int(ms * 1e6)), f)

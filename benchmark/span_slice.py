"""From the profiler trace of a traced run to the program's own
phases.  The program (``flink_tpu/runtime/tracing.py``:
``Tracer.phase``) enters a ``jax.profiler.TraceAnnotation`` named
``flink/<phase>`` around every batch-level step, so the slice the
harness traces holds the phases on the clock of the device ops.  This
module reads them back; checked by ``tests/test_span_slice.py`` on a
small trace kept beside it.

    phase       a host event named ``flink/<name>``; jax's own
                ``backend_compile*`` events are the pseudo-phase
                ``jax.compile``
    nesting     by containment, per thread line
    self        a phase's intervals outside its children, so a compile
                is carved out of the phase that needed the program
    idle        the part of those self intervals in which the first
                device ran no op (``xplane.merge`` / ``covered``)

The harness hands a reader no path: the trace is the newest
``.xplane.pb`` under ``benchmark_out/trace/*/plugins/profile/``.  A
program without phases (the parent of the PR that added them) gives a
table without them, and every reader over it returns ``None``.
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np

import xplane

PHASE_PREFIX = "flink/"
COMPILE_EVENT_PREFIX = "backend_compile"
COMPILE_PHASE = "jax.compile"
#: the window operators' two entries; every other phase is a leaf
TOP = ("window.ingest", "window.watermark")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_tables = {}


def newest_trace(root=ROOT):
    found = glob.glob(os.path.join(root, "benchmark_out", "trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def phase_name(event_name):
    if event_name.startswith(PHASE_PREFIX):
        return event_name[len(PHASE_PREFIX):]
    if event_name.startswith(COMPILE_EVENT_PREFIX):
        return COMPILE_PHASE
    return None


def nest(events):
    """``events``: (start, end, name) of one thread line.  Yields
    (name, under, start, end, self intervals) with ``under`` the name
    of the outermost enclosing event (its own, at top level).  A
    child that outlasts its parent is cut to it."""
    open_ = []  # [name, under, start, end, cursor, self intervals]

    def close():
        name, under, start, end, cursor, pieces = open_.pop()
        if end > cursor:
            pieces.append((cursor, end))
        return name, under, start, end, pieces

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while open_ and open_[-1][3] <= start:
            yield close()
        under = name
        if open_:
            parent = open_[-1]
            end = min(end, parent[3])
            under = parent[1]
            if start > parent[4]:
                parent[5].append((parent[4], start))
            parent[4] = max(parent[4], end)
        open_.append([name, under, start, end, start, []])
    while open_:
        yield close()


def reduce_phases(profile):
    """Per phase name ``count``, ``total_s``, ``self_s`` and ``idle_s``
    (``None`` without a device plane), plus what the two ratio metrics
    divide: ``top_total_s`` and ``under_top_self_s`` (self time of
    everything nested in a ``TOP`` phase), ``idle_s`` (all device-idle
    time from the trace's first event to its last) and
    ``idle_in_leaves_s`` (the part inside some leaf's self time)."""
    busy, first, last = None, np.inf, -np.inf
    lines = []
    for plane in profile.planes:
        device = plane.name.startswith(xplane.DEVICE_PREFIX)
        if device and busy is None:
            ops = xplane._ops_line(plane)
            if ops is not None:
                busy = xplane.merge([(e.start_ns, e.start_ns + e.duration_ns)
                                     for e in ops.events])
        for line in plane.lines:
            named = []
            for e in line.events:
                first = min(first, e.start_ns)
                last = max(last, e.start_ns + e.duration_ns)
                name = None if device else phase_name(e.name)
                if name is not None:
                    named.append((e.start_ns, e.start_ns + e.duration_ns,
                                  name))
            if named:
                lines.append(named)

    def idle_ns(intervals):
        """Device-idle time inside disjoint ``intervals``."""
        a = np.asarray(intervals, np.float64).reshape(-1, 2)
        a = np.clip(a, first, last)
        return float(((a[:, 1] - a[:, 0]) - (xplane.covered(busy, a[:, 1])
                      - xplane.covered(busy, a[:, 0]))).sum())

    phases, leaves, under_top = {}, [], 0.0
    for events in lines:
        for name, under, start, end, pieces in nest(events):
            row = phases.setdefault(name, {"count": 0, "total_s": 0.0,
                                           "self_s": 0.0, "pieces": []})
            self_ns = sum(b - a for a, b in pieces)
            row["count"] += 1
            row["total_s"] += (end - start) * 1e-9
            row["self_s"] += self_ns * 1e-9
            row["pieces"].extend(pieces)
            if name not in TOP:
                leaves.extend(pieces)
                if under in TOP:
                    under_top += self_ns * 1e-9
    for row in phases.values():
        pieces = row.pop("pieces")
        row["idle_s"] = None if busy is None else idle_ns(pieces) * 1e-9
    out = {"phases": phases,
           "top_total_s": sum(phases[t]["total_s"] for t in TOP
                              if t in phases),
           "under_top_self_s": under_top,
           "idle_s": None, "idle_in_leaves_s": None}
    if busy is not None and last > first:
        out["idle_s"] = idle_ns([(first, last)]) * 1e-9
        # leaves of two threads may overlap: count shared time once
        out["idle_in_leaves_s"] = idle_ns(xplane.merge(leaves)) * 1e-9
    return out


def table(run):
    """The phase table of the trace this run just wrote, read once per
    process; the first read prints it as a ``[phases]`` line and writes
    ``phases.json`` beside the trace.  ``None`` without a trace."""
    path = newest_trace() if run.get("slice_s") else None
    if path is None:
        return None
    if path not in _tables:
        from jax.profiler import ProfileData
        out = reduce_phases(ProfileData.from_file(path))
        out["slice_s"] = run["slice_s"]
        _tables[path] = out
        # <trace directory>/plugins/profile/<time>/<host>.xplane.pb
        directory = os.path.normpath(os.path.join(path, *[os.pardir] * 4))
        with open(os.path.join(directory, "phases.json"), "w",
                  encoding="utf-8") as f:
            json.dump(out, f, indent=1)
        print(f"[phases] {json.dumps(out)}", flush=True)
    return _tables[path]


def has_phases(t):
    return t is not None and any(name != COMPILE_PHASE
                                 for name in t["phases"])


def share(run, names):
    """Σ self time of the named phases as % of the profiler slice;
    ``None`` where none of them occurred."""
    t = table(run)
    if t is None:
        return None
    rows = [t["phases"][n] for n in names if n in t["phases"]]
    if not rows:
        return None
    return 100.0 * sum(r["self_s"] for r in rows) / run["slice_s"]

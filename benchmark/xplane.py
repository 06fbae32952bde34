"""From a profiler trace (``.xplane.pb``) to numbers.  The one place
where device time is computed; checked by ``tests/test_xplane.py`` on
a small trace kept beside it.

    busy        union of the intervals in which an XLA op ran on a
                device plane, averaged over the device planes
    idle share  1 - busy / slice        (the slice is the caller's)
    device_ops  the ops that took most device time, by XLA's names
    idle_gaps   device-idle seconds by what the host was in: the
                ``bench.*`` annotations the harness's wrappers write
                into the same trace, the rest under OUTSIDE

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.
"""

from __future__ import annotations

import collections

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
#: the device line whose events are single XLA ops; the modules line
#: covers whole programs and stands in only where there is no ops line
OPS_LINES = ("XLA Ops", "XLA Modules")
HOST_LABEL_PREFIX = "bench."
OUTSIDE = "outside the wrappers (executor loop, exchange)"


def merge(intervals):
    """Sorted, disjoint (start, end) pairs covering the same points."""
    a = np.asarray(intervals, np.float64).reshape(-1, 2)
    if not len(a):
        return a
    a = a[np.argsort(a[:, 0], kind="stable")]
    # an interval opens a new run when it starts after every end so far
    reach = np.maximum.accumulate(a[:, 1])
    opens = np.concatenate([[True], a[1:, 0] > reach[:-1]])
    starts = a[opens, 0]
    ends = np.maximum.reduceat(a[:, 1], np.flatnonzero(opens))
    return np.stack([starts, ends], axis=1)


def covered(merged, t):
    """Length of ``merged`` (disjoint, sorted) that lies before each
    time in ``t``."""
    t = np.asarray(t, np.float64)
    if not len(merged):
        return np.zeros_like(t)
    starts, ends = merged[:, 0], merged[:, 1]
    before = np.concatenate([[0.0], np.cumsum(ends - starts)])
    i = np.searchsorted(starts, t, side="right") - 1
    inside = np.clip(np.minimum(t, ends[np.maximum(i, 0)])
                     - starts[np.maximum(i, 0)], 0.0, None)
    return np.where(i >= 0, before[np.maximum(i, 0)] + inside, 0.0)


def _ops_line(plane):
    lines = {line.name: line for line in plane.lines}
    for name in OPS_LINES:
        if name in lines:
            return lines[name]
    return None


def summary(profile):
    """Planes, lines, event counts and a few names: what to look at
    before trusting a reduction on a trace from a new device."""
    out = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({"line": line.name, "events": len(events),
                          "first": [e.name[:80] for e in events[:3]]})
        out.append({"plane": plane.name, "lines": lines})
    return out


def reduce_trace(profile, top=10):
    """``None`` when no device plane has an ops line (nothing to
    read); otherwise a dict with ``devices``, ``busy_s`` (mean over
    the device planes), ``device_ops`` and ``idle_gaps``."""
    busy, op_seconds, first_busy = [], collections.Counter(), None
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        line = _ops_line(plane)
        if line is None:
            continue
        spans = []
        for e in line.events:
            spans.append((e.start_ns, e.start_ns + e.duration_ns))
            op_seconds[e.name] += e.duration_ns * 1e-9
        merged = merge(spans)
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9)
        if first_busy is None:
            first_busy = merged
    if not busy:
        return None
    return {"devices": len(busy),
            "busy_s": float(np.mean(busy)),
            "device_ops": [[name, secs] for name, secs
                           in op_seconds.most_common(top)],
            "idle_gaps": idle_by_host_label(profile, first_busy, top)}


def idle_by_host_label(profile, busy, top=10):
    """The time in which one device ran no op, from the first event of
    the trace to the last, attributed to the ``bench.*`` host
    annotations that overlap it."""
    labelled = collections.defaultdict(list)
    first, last = np.inf, -np.inf
    for plane in profile.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            for e in line.events:
                first = min(first, e.start_ns)
                last = max(last, e.start_ns + e.duration_ns)
                if not device and e.name.startswith(HOST_LABEL_PREFIX):
                    labelled[e.name].append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    # the trace runs from its first event to its last, on any plane:
    # the device is idle before its first op and after its last, too
    edges = np.concatenate([[first], busy.ravel(), [last]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    if not len(gaps):
        return []
    idle = {}
    for label, spans in labelled.items():
        m = merge(spans)
        idle[label] = float((covered(m, gaps[:, 1])
                             - covered(m, gaps[:, 0])).sum()) * 1e-9
    total = float((gaps[:, 1] - gaps[:, 0]).sum()) * 1e-9
    idle[OUTSIDE] = max(total - sum(idle.values()), 0.0)
    ranked = sorted(idle.items(), key=lambda kv: -kv[1])
    return [[label, secs] for label, secs in ranked[:top] if secs > 0]

"""End-to-end tracing & kernel profiling (ref: the reference runtime's
LatencyStats / CheckpointStatsTracker observability story, extended
down to the device tiers).

Three cooperating pieces live here because they share one registry
surface:

* **Span tracing** — :class:`Tracer` with a ``span(name, **attrs)``
  context manager, a thread-local span stack (parent/child + self-time
  attribution), a bounded buffer of finished spans, and Chrome
  trace-event JSON export (loadable in Perfetto / ``chrome://tracing``).
  When disabled, ``span()`` returns a shared no-op object — one
  attribute check and a dict-free return, so instrumented hot paths pay
  near zero.  ``phase(name, **attrs)`` is the always-on form for work
  at batch or fire granularity: it feeds the same per-name stats with
  the tracer off, and enters a ``jax.profiler.TraceAnnotation`` named
  ``flink/<name>`` so a profiler trace shows the phase on the clock of
  the device ops.  A cyclic collection of CPython's is booked like a
  backend compile: on the innermost phase it interrupted, out of that
  phase's self time, as ``flink/py.gc`` in a trace
  (:func:`gc_totals`).  Every watermark that fired a window cuts a
  *period* out of these books (:meth:`Tracer.periods`).

* **Kernel profiling** — ``record_kernel(name, t0_ns, t1_ns)`` called
  by the wrappers in :mod:`flink_tpu.native` around every
  ``host_runtime`` entry point: per-kernel dispatch counters +
  wall-time reservoirs, surfaced as gauges and (when the tracer is
  enabled) as ``native.<kernel>`` spans in the Chrome trace.

* **JAX compile tracking** — :func:`traced_jit` wraps ``jax.jit`` and
  detects recompiles via the jitted callable's ``_cache_size()``
  (grows across a call ⇒ that call compiled; otherwise a cache hit).
  One ``jax.monitoring`` listener books every backend compile of the
  process, ``traced_jit`` or not, on the innermost open span or phase
  and on :func:`backend_compile_totals`.
  Non-JAX compilation events (the CEP predicate bytecode compiler)
  report through :func:`record_compile_event` into the same store.

All three feed the existing :class:`MetricRegistry` through
:func:`register_runtime_profile_gauges` — names that appear *after*
registration (engines are tier-selected on first flush) back-fill into
every registered registry, so ``registry.dump()`` always reflects the
full picture.
"""
from __future__ import annotations

import functools
import gc
import json
import os
import threading
import time
import uuid
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "Tracer",
    "get_tracer",
    "set_tracer",
    "make_trace_context",
    "estimate_clock_offset",
    "build_cluster_trace",
    "traced_jit",
    "record_kernel",
    "record_compile_event",
    "kernel_stats",
    "jit_stats",
    "backend_compile_totals",
    "gc_totals",
    "phase_annotation",
    "reset_jit_stats",
    "register_runtime_profile_gauges",
]

_perf_ns = time.perf_counter_ns

#: every phase's profiler annotation is named PHASE_PREFIX + its name
PHASE_PREFIX = "flink/"
#: the collector's pseudo-phase, as ``jax.compile`` is the compiler's
GC_PHASE = "py.gc"
#: the window operators' watermark entry: one that fired cuts a period
PERIOD_PHASE = "window.watermark"
#: periods the ring keeps
MAX_PERIODS = 512
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# one lock guards the aggregate stores (kernel + jit + span stats and
# the registered-registry list); all updates are batch-level, not
# per-record, so contention is negligible
_LOCK = threading.Lock()


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


class _Reservoir:
    """Bounded sliding reservoir of recent durations (milliseconds)."""

    __slots__ = ("values",)

    def __init__(self, size: int = 512):
        self.values: deque = deque(maxlen=size)

    def update(self, v: float) -> None:
        self.values.append(v)

    def quantile(self, q: float) -> float:
        return _percentile(sorted(self.values), q)


# ---------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------

class _NullSpan:
    """Shared no-op context manager returned while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attr(self, key: str, value: Any) -> None:
        pass

    def add_count(self, key: str, n: int) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("tracer", "name", "attrs", "counts", "start_ns",
                 "child_ns", "parent", "compile_ns", "compiles", "gc_ns",
                 "gcs", "gc_under_ns", "native_ns")

    def __init__(self, tracer: "Tracer", name: str, attrs: Optional[dict]):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        #: the attributes that are counts the books sum (`add_count`)
        self.counts: Optional[dict] = None
        self.child_ns = 0
        self.parent: Optional[_Span] = None
        #: backend compiles that ran while this span was the innermost
        #: open one (booked by the jax.monitoring listener)
        self.compile_ns = 0
        self.compiles = 0
        #: cyclic collections that interrupted this span while it was
        #: the innermost open one (booked by the gc.callbacks hook),
        #: and the time of those that ran anywhere under it
        self.gc_ns = 0
        self.gcs = 0
        self.gc_under_ns = 0
        #: time in native kernels called while this span was the
        #: innermost open one (``record_kernel``): part of its self
        #: time, and named
        self.native_ns = 0

    def set_attr(self, key: str, value: Any) -> None:
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    def add_count(self, key: str, n: int) -> None:
        """An attribute that counts the span's work (rows, keys): on
        the event like any other, and summed per span name in the
        books (``stats()[name]["counts"]``, and its growth in every
        fire period)."""
        self.set_attr(key, n)
        if self.counts is None:
            self.counts = {}
        self.counts[key] = n

    def __enter__(self):
        stack = self.tracer._stack()
        self.parent = stack[-1] if stack else None
        stack.append(self)
        self.start_ns = _perf_ns()
        return self

    def __exit__(self, *exc):
        end_ns = _perf_ns()
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        dur_ns = end_ns - self.start_ns
        if self.parent is not None:
            self.parent.child_ns += dur_ns
            self.parent.gc_under_ns += self.gc_under_ns
        self.tracer._finish(self, dur_ns)
        return False


class _Phase(_Span):
    """A span that is always on and is also a profiler annotation."""

    __slots__ = ("annotation",)

    def __enter__(self):
        self.annotation = phase_annotation(self.name, **(self.attrs or {}))
        self.annotation.__enter__()
        return _Span.__enter__(self)

    def __exit__(self, *exc):
        _Span.__exit__(self)
        self.annotation.__exit__(*exc)
        if self.name == PERIOD_PHASE and self.attrs \
                and self.attrs.get("fired"):
            self.tracer._cut_period(self.attrs)
        return False

    def set_attr(self, key: str, value: Any) -> None:
        """An attribute known only once the phase runs (a count its
        loop arrives at), on the profiler's event as in the ring."""
        _Span.set_attr(self, key, value)
        self.annotation.set_metadata(**{key: value})


class _SpanStat:
    __slots__ = ("count", "total_ms", "self_ms", "compile_ms", "compiles",
                 "gc_ms", "gcs", "gc_under_ms", "native_ms", "counts",
                 "reservoir")
    #: what stats() gives of a name beside the percentiles, and what a
    #: period holds of it: the growth of these
    CUT = ("count", "total_ms", "self_ms", "gc_ms", "gcs", "gc_under_ms",
           "compiles", "compile_ms", "native_ms")

    def __init__(self):
        self.count = 0
        self.total_ms = 0.0
        self.self_ms = 0.0
        self.compile_ms = 0.0
        self.compiles = 0
        self.gc_ms = 0.0
        self.gcs = 0
        self.gc_under_ms = 0.0
        self.native_ms = 0.0
        #: Σ of what the spans counted (`_Span.add_count`), by key
        self.counts: Dict[str, int] = {}
        self.reservoir = _Reservoir()

    def cut(self) -> dict:
        """What stats() gives of a name beside the percentiles, and
        what a period holds the growth of."""
        out = {f: getattr(self, f) for f in self.CUT}
        if self.counts:
            out["counts"] = dict(self.counts)
        return out


class _GcBooks:
    """Cyclic collections since the tracer was made or reset: all of
    them, by generation, and those that found no span open."""

    __slots__ = ("collections", "ns", "by_generation", "unphased")

    def __init__(self):
        self.collections = 0
        self.ns = 0
        #: generation -> [collections, ns]
        self.by_generation = [[0, 0], [0, 0], [0, 0]]
        self.unphased = [0, 0]

    def totals(self) -> dict:
        return {"collections": self.collections, "gc_ms": self.ns / 1e6,
                "by_generation": {
                    g: {"collections": n, "gc_ms": ns / 1e6}
                    for g, (n, ns) in enumerate(self.by_generation)},
                "unphased": {"collections": self.unphased[0],
                             "gc_ms": self.unphased[1] / 1e6}}


def _growth(now: dict, base: dict) -> dict:
    """``now - base``, key by key, through nested dicts; what did not
    grow is left out."""
    out = {}
    for key, value in now.items():
        before = base.get(key)
        if isinstance(value, dict):
            grown = _growth(value, before or {})
        else:
            grown = value - (before or 0)
        if grown:
            out[key] = grown
    return out


class Tracer:
    """Span recorder with Chrome trace-event export and per-name
    aggregate stats.  One tracer is process-global (``get_tracer()``);
    instrumentation points check ``tracer.enabled`` and skip all work
    when off."""

    def __init__(self, max_events: int = 100_000):
        self.enabled = False
        self.max_events = max_events
        self._events: deque = deque(maxlen=max_events)
        self._stats: Dict[str, _SpanStat] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._pid = os.getpid()
        #: spans evicted by the bounded ring (deque maxlen drops the
        #: oldest silently; this makes truncation self-describing)
        self.dropped = 0
        self._seq = 0
        # metric groups (weakrefs) that want per-span-name gauges
        self._metric_groups: List[weakref.ref] = []
        self._new_books()

    def _new_books(self) -> None:
        self._gc = _GcBooks()
        #: the last MAX_PERIODS fire periods, and how many left the ring
        self._periods: deque = deque(maxlen=MAX_PERIODS)
        self.dropped_periods = 0
        self._period_seq = 0
        #: the books at the last cut, and its host time
        self._cut_base: dict = {}
        self._cut_s = time.perf_counter()

    # ---- recording --------------------------------------------------
    def span(self, name: str, **attrs):
        """Context manager timing one unit of work.  Near-free when
        the tracer is disabled (returns a shared no-op)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs or None)

    def phase(self, name: str, **attrs):
        """Context manager for work at BATCH or FIRE granularity,
        always on: never per record, key or timer.  With the tracer
        off it still feeds :meth:`stats` (count, total, self time,
        compiles) and enters a ``jax.profiler.TraceAnnotation`` named
        ``flink/<name>`` — inert without a profiler session; with one,
        the phase lies in the trace on the clock of the device ops.
        Only while ``enabled`` does it also land in the event ring, as
        a span does."""
        return _Phase(self, name, attrs or None)

    def span_linked(self, name: str, ctx: Optional[dict], **attrs):
        """Like :meth:`span`, but causally linked to a propagated
        trace context (``make_trace_context()`` dict stamped on a
        barrier's options or a netchannel frame): the consumer-side
        span carries the producer's ``trace_id`` and points at its
        ``span_id``, so cross-host viewers can stitch the tree."""
        if not self.enabled:
            return _NULL_SPAN
        if ctx:
            attrs["trace_id"] = ctx.get("trace_id")
            attrs["parent_span_id"] = ctx.get("span_id")
        return _Span(self, name, attrs or None)

    # ---- logical lanes ----------------------------------------------
    # All task-manager runners in the single-process executors share
    # THIS tracer; a thread-local lane label partitions their events so
    # the merged cluster trace can render one process lane per worker.
    def set_lane(self, label: Optional[str]) -> None:
        """Tag every event recorded by the CURRENT thread with a
        worker-lane label (e.g. ``tm-0``)."""
        self._tls.lane = label

    def current_lane(self) -> Optional[str]:
        return getattr(self._tls, "lane", None)

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = []
            self._tls.stack = stack
        return stack

    def _append_locked(self, event: dict) -> None:
        # caller holds self._lock; the ring is full exactly when the
        # next append will evict its oldest event
        if len(self._events) == self.max_events:
            self.dropped += 1
        self._seq += 1
        event["seq"] = self._seq
        self._events.append(event)

    def _finish(self, span: _Span, dur_ns: int) -> None:
        event = None
        # a phase reaches the ring only while the tracer is on; a span
        # exists only because it was
        if self.enabled or span.__class__ is _Span:
            event = {
                "name": span.name,
                "ph": "X",
                "ts": span.start_ns / 1000.0,
                "dur": dur_ns / 1000.0,
                "pid": self._pid,
                "tid": threading.get_ident(),
            }
            lane = getattr(self._tls, "lane", None)
            if lane is not None:
                event["lane"] = lane
            if span.parent is not None:
                event["parent"] = span.parent.name
            if span.attrs:
                event["args"] = span.attrs
        total_ms = dur_ns / 1e6
        # neither a compile nor a collection is the span's own work
        self_ms = (dur_ns - span.child_ns - span.compile_ns
                   - span.gc_ns) / 1e6
        with self._lock:
            if event is not None:
                self._append_locked(event)
            stat = self._stats.get(span.name)
            if stat is None:
                stat = self._stats[span.name] = _SpanStat()
                self._register_span_gauges(span.name, stat)
            stat.count += 1
            stat.total_ms += total_ms
            stat.self_ms += self_ms
            if span.compiles:
                stat.compiles += span.compiles
                stat.compile_ms += span.compile_ns / 1e6
            if span.gc_under_ns:
                stat.gcs += span.gcs
                stat.gc_ms += span.gc_ns / 1e6
                stat.gc_under_ms += span.gc_under_ns / 1e6
            if span.native_ns:
                stat.native_ms += span.native_ns / 1e6
            if span.counts:
                for key, n in span.counts.items():
                    stat.counts[key] = stat.counts.get(key, 0) + n
            stat.reservoir.update(total_ms)

    # ---- fire periods -------------------------------------------------
    def note_fire(self, operator: str, windows: int, keys: int,
                  newest_window_end: int) -> None:
        """Called by a window operator under its ``window.watermark``
        phase once it knows what the watermark fired: the phase's exit
        then cuts a period (:meth:`periods`).  Several calls under one
        watermark add up."""
        if not windows:
            return
        for span in reversed(self._stack()):
            if span.name == PERIOD_PHASE:
                attrs = span.attrs or {}
                if attrs.get("fired"):
                    windows += attrs["fired"]
                    keys += attrs["fired_keys"]
                    newest_window_end = max(newest_window_end,
                                            attrs["newest_window_end"])
                for key, value in (("operator", operator),
                                   ("fired", windows), ("fired_keys", keys),
                                   ("newest_window_end", newest_window_end)):
                    span.set_attr(key, value)
                return

    def _cut_period(self, attrs: dict) -> None:
        """One walk over the books (tens of names), once per fire."""
        now_s = time.perf_counter()
        with _LOCK:
            kernels = {name: st.total_ms
                       for name, st in _kernel_stats.items()}
        with self._lock:  # two operators' threads may cut at once
            books = {"phases": {name: st.cut()
                                for name, st in self._stats.items()},
                     "gc": self._gc.totals(), "kernels": kernels}
            period = {"seq": self._period_seq, "start_s": self._cut_s,
                      "end_s": now_s, "operator": attrs.get("operator"),
                      "watermark": attrs.get("watermark"),
                      "windows": attrs["fired"],
                      "keys": attrs["fired_keys"],
                      "newest_window_end": attrs["newest_window_end"],
                      **{k: _growth(v, self._cut_base.get(k, {}))
                         for k, v in books.items()}}
            if len(self._periods) == MAX_PERIODS:
                self.dropped_periods += 1
            self._periods.append(period)
            self._period_seq += 1
            self._cut_base, self._cut_s = books, now_s

    def periods(self) -> List[dict]:
        """The last ``MAX_PERIODS`` fire periods, oldest first.  A
        period ends where a ``window.watermark`` phase that fired at
        least one window ends, and holds the growth of the process's
        books since the period before it (since the tracer was made or
        reset, for the first): per phase ``count``, ``total_ms``,
        ``self_ms``, ``gc_ms``, ``gcs``, ``gc_under_ms``, ``compiles``,
        ``compile_ms``, ``native_ms``, and ``counts`` (what its spans
        counted with ``add_count``, by key); ``gc`` as :func:`gc_totals` gives it;
        ``kernels`` as ``kernel_stats()``' ``total_ms``; names that did
        not grow are left out.  Beside them the host times of the two
        cuts (``time.perf_counter``), the operator that fired, its
        watermark, the windows and keys it fired and the end timestamp
        of the newest of those windows.  ``dropped_periods`` counts
        what left the ring."""
        with self._lock:
            return list(self._periods)

    def gc_totals(self) -> dict:
        with self._lock:
            return self._gc.totals()

    def record_instant(self, name: str, **attrs) -> None:
        """Record a zero-duration marker event (checkpoint triggers,
        compile events...)."""
        if not self.enabled:
            return
        event = {
            "name": name,
            "ph": "i",
            "ts": _perf_ns() / 1000.0,
            "pid": self._pid,
            "tid": threading.get_ident(),
            "s": "t",
        }
        lane = getattr(self._tls, "lane", None)
        if lane is not None:
            event["lane"] = lane
        if attrs:
            event["args"] = attrs
        with self._lock:
            self._append_locked(event)

    # ---- export -----------------------------------------------------
    def recent(self, limit: int = 200) -> List[dict]:
        """Most recent finished spans, oldest first."""
        with self._lock:
            events = list(self._events)
        return events[-limit:]

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object (``traceEvents`` uses
        complete events: ``ph``/``ts``/``dur``/``pid``/``tid``/
        ``name``; timestamps are microseconds).  When the bounded ring
        has evicted events, the export says so in ``metadata`` instead
        of silently presenting a truncated timeline as complete."""
        with self._lock:
            events = list(self._events)
            dropped = self.dropped
        trace = {"traceEvents": events, "displayTimeUnit": "ms"}
        if dropped:
            trace["metadata"] = {
                "dropped_events": dropped,
                "warning": (f"trace truncated: {dropped} oldest events "
                            f"dropped at the {self.max_events}-event "
                            f"ring limit"),
            }
        return trace

    def export_since(self, seq: int, lane: Optional[str] = None) -> dict:
        """Incremental buffer export for cross-process shipping: every
        event appended after sequence number ``seq`` (optionally only
        one lane's), plus a clock anchor pairing this process's
        ``perf_counter`` epoch with its wall clock — the receiver
        converts span timestamps to wall time, then applies the
        RPC-estimated inter-host offset."""
        with self._lock:
            events = [e for e in self._events if e.get("seq", 0) > seq]
            max_seq = self._seq
        if lane is not None:
            events = [e for e in events if e.get("lane") == lane]
        return {"events": events, "anchor": clock_anchor(),
                "seq": max_seq, "pid": self._pid}

    def lane_buffers(self, default_lane: str = "main") -> Dict[str, dict]:
        """The full event buffer partitioned by worker lane, each with
        the (shared, same-process) clock anchor — the single-process
        executors' input to :func:`build_cluster_trace`."""
        anchor = clock_anchor()
        with self._lock:
            events = list(self._events)
        buffers: Dict[str, dict] = {}
        for ev in events:
            lane = ev.get("lane", default_lane)
            buf = buffers.get(lane)
            if buf is None:
                buf = buffers[lane] = {"events": [], "anchor": anchor}
            buf["events"].append(ev)
        return buffers

    def write_chrome_trace(self, path: str) -> int:
        """Write the trace file; returns the number of events."""
        trace = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(trace, f)
        return len(trace["traceEvents"])

    def stats(self) -> Dict[str, dict]:
        """Aggregated per-span-name stats.  ``self_ms`` is the span's
        own work: the time in its children, in backend compiles
        (``compile_ms``) and in cyclic collections (``gc_ms``, ``gcs``
        of them) that ran while it was the innermost open span is left
        out.  ``gc_under_ms`` is the collector's time anywhere under
        the span, its children's included; ``native_ms`` the part of
        ``self_ms`` spent in native kernels called straight from the
        span (``kernel_stats()`` names them)."""
        out = {}
        with self._lock:
            for name, st in self._stats.items():
                vals = sorted(st.reservoir.values)
                out[name] = {
                    **st.cut(),
                    "p50_ms": _percentile(vals, 0.50),
                    "p99_ms": _percentile(vals, 0.99),
                }
        return out

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._stats.clear()
            self.dropped = 0
            self._new_books()

    # ---- metric registry feed --------------------------------------
    def install_metrics(self, group) -> None:
        """Register per-span-name aggregate gauges under ``group``
        (a ``MetricGroup``); names that appear later back-fill."""
        with self._lock:
            self._metric_groups.append(weakref.ref(group))
            group.gauge("dropped", lambda: self.dropped)
            for name, stat in self._stats.items():
                self._add_gauges(group, name, stat)

    def _register_span_gauges(self, name: str, stat: _SpanStat) -> None:
        # caller holds self._lock
        alive = []
        for ref in self._metric_groups:
            group = ref()
            if group is None:
                continue
            alive.append(ref)
            self._add_gauges(group, name, stat)
        self._metric_groups[:] = alive

    @staticmethod
    def _add_gauges(group, name: str, stat: _SpanStat) -> None:
        g = group.add_group(name)
        g.gauge("count", lambda s=stat: s.count)
        g.gauge("totalMs", lambda s=stat: s.total_ms)
        g.gauge("selfMs", lambda s=stat: s.self_ms)
        g.gauge("compiles", lambda s=stat: s.compiles)
        g.gauge("compileMs", lambda s=stat: s.compile_ms)
        g.gauge("gcs", lambda s=stat: s.gcs)
        g.gauge("gcMs", lambda s=stat: s.gc_ms)
        g.gauge("p50Ms", lambda s=stat: s.reservoir.quantile(0.50))
        g.gauge("p99Ms", lambda s=stat: s.reservoir.quantile(0.99))


_tracer = Tracer()

#: jax.profiler.TraceAnnotation, once the first phase() or traced_jit()
#: has hooked jax (this module imports jax nowhere at import time)
_TraceAnnotation = None
#: [count, ns] of every backend compile since the hook
_backend_compiles = [0, 0]


#: (annotation, start ns) of the collection that is running: CPython
#: runs one at a time, on the thread whose allocation triggered it
_gc_open = None


def _hook_jax() -> None:
    """Once per process: the annotation class phases enter, the one
    listener that books backend compiles where they happen, and the
    one ``gc.callbacks`` hook that books cyclic collections there."""
    global _TraceAnnotation
    import jax
    with _LOCK:
        if _TraceAnnotation is None:
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            _TraceAnnotation = jax.profiler.TraceAnnotation
            gc.callbacks.append(_on_gc)


def _on_gc(phase: str, info: dict) -> None:
    # a collection runs on the thread whose allocation set it off,
    # inside whatever span is open there, and is not that span's work
    global _gc_open
    if phase == "start":
        annotation = _TraceAnnotation(PHASE_PREFIX + GC_PHASE,
                                      generation=info["generation"])
        annotation.__enter__()
        _gc_open = (annotation, _perf_ns())
        return
    if _gc_open is None:  # hooked in the middle of a collection
        return
    end_ns = _perf_ns()
    annotation, start_ns = _gc_open
    _gc_open = None
    annotation.__exit__(None, None, None)
    ns = end_ns - start_ns
    tracer = _tracer
    books = tracer._gc
    books.collections += 1
    books.ns += ns
    generation = books.by_generation[info["generation"]]
    generation[0] += 1
    generation[1] += ns
    stack = getattr(tracer._tls, "stack", None)
    if stack:
        span = stack[-1]
        span.gcs += 1
        span.gc_ns += ns
        span.gc_under_ns += ns
    else:
        books.unphased[0] += 1
        books.unphased[1] += ns


def _on_jax_duration(event: str, secs: float, **_kw) -> None:
    # jax compiles on the calling thread, so the innermost open span of
    # this thread is the work that needed the program
    if event != _BACKEND_COMPILE_EVENT:
        return
    ns = int(secs * 1e9)
    with _LOCK:
        _backend_compiles[0] += 1
        _backend_compiles[1] += ns
    stack = getattr(_tracer._tls, "stack", None)
    if stack:
        stack[-1].compiles += 1
        stack[-1].compile_ns += ns


def phase_annotation(name: str, **attrs):
    """The profiler half of a phase alone (``flink/<name>`` in a
    trace), for a call site that keeps its own books: the native
    kernel wrappers, whose times ``kernel_stats()`` holds."""
    if _TraceAnnotation is None:
        _hook_jax()
    return _TraceAnnotation(PHASE_PREFIX + name, **attrs)


def backend_compile_totals() -> Dict[str, float]:
    """Every backend compile (or read from the compile cache) of the
    process since the first phase or ``traced_jit``, whoever jitted
    the program: ``jit_stats()`` names the ones behind a label."""
    with _LOCK:
        return {"compiles": _backend_compiles[0],
                "compile_ms": _backend_compiles[1] / 1e6}


def gc_totals() -> Dict[str, Any]:
    """Every cyclic collection of CPython's since the process's tracer
    was made or reset (the hook goes in with the first phase or
    ``traced_jit``): ``collections`` and ``gc_ms``, the same
    ``by_generation``, and ``unphased``, the ones that found no span
    open on their thread.  Those that found one are in
    ``stats()[name]["gcs"]`` / ``["gc_ms"]``."""
    return _tracer.gc_totals()


def get_tracer() -> Tracer:
    return _tracer


def set_tracer(tracer: Tracer) -> Tracer:
    global _tracer
    _tracer = tracer
    return tracer


# ---------------------------------------------------------------------
# cluster-causal tracing: context propagation + clock alignment
# ---------------------------------------------------------------------

def make_trace_context() -> dict:
    """A Dapper-style propagation context (Sigelman et al., 2010):
    stamped onto checkpoint-barrier options and netchannel frames so
    consumer-side spans on other hosts link back to the producer."""
    return {"trace_id": uuid.uuid4().hex[:16],
            "span_id": uuid.uuid4().hex[:16]}


def clock_anchor() -> dict:
    """One (perf_counter, wall clock) pair sampled together: converts
    this process's span timestamps (perf-epoch µs) to wall-clock µs."""
    return {"perf_us": _perf_ns() / 1000.0,
            "wall_us": time.time() * 1e6}


def estimate_clock_offset(probe: Callable[[], float],
                          samples: int = 8) -> dict:
    """Min-RTT-midpoint clock-offset estimate (the NTP idea, one
    peer): ``probe()`` round-trips to the remote and returns its wall
    clock in µs; the sample with the smallest RTT bounds the offset
    tightest, and the midpoint assumption splits that RTT evenly.
    Returns ``{"offset_us": remote − local, "rtt_us": best}``."""
    best_rtt: Optional[float] = None
    best_off = 0.0
    for _ in range(max(1, samples)):
        t0 = time.time()
        remote_us = probe()
        t1 = time.time()
        rtt_us = (t1 - t0) * 1e6
        offset_us = remote_us - (t0 * 1e6 + rtt_us / 2.0)
        if best_rtt is None or rtt_us < best_rtt:
            best_rtt = rtt_us
            best_off = offset_us
    return {"offset_us": best_off, "rtt_us": best_rtt or 0.0}


def build_cluster_trace(buffers: Dict[str, dict],
                        offsets: Optional[Dict[str, float]] = None
                        ) -> dict:
    """Merge per-worker tracer buffers into ONE Chrome trace with one
    process lane per worker and clock-aligned timestamps.

    ``buffers`` maps a lane label to ``{"events": [...], "anchor":
    {"perf_us", "wall_us"}}`` (the :meth:`Tracer.export_since` /
    :meth:`Tracer.lane_buffers` shape); ``offsets`` maps a lane to its
    host's wall-clock offset in µs relative to the assembler
    (``estimate_clock_offset`` — subtracted to align).  Timestamps are
    normalized to the earliest aligned event so the merged view starts
    at t=0."""
    offsets = offsets or {}
    merged: List[dict] = []
    lanes_meta: Dict[str, dict] = {}
    lane_order = sorted(buffers)
    for idx, lane in enumerate(lane_order, start=1):
        buf = buffers[lane] or {}
        anchor = buf.get("anchor") or {}
        shift = (anchor.get("wall_us", 0.0) - anchor.get("perf_us", 0.0)
                 - float(offsets.get(lane, 0.0)))
        events = buf.get("events") or []
        lanes_meta[lane] = {"pid": idx,
                            "offset_us": float(offsets.get(lane, 0.0)),
                            "events": len(events)}
        for ev in events:
            e = dict(ev)
            e["ts"] = float(ev.get("ts", 0.0)) + shift
            e["pid"] = idx
            e.pop("seq", None)
            merged.append(e)
    if merged:
        t0 = min(e["ts"] for e in merged)
        for e in merged:
            e["ts"] -= t0
    merged.sort(key=lambda e: e["ts"])
    events: List[dict] = [
        {"name": "process_name", "ph": "M", "pid": idx, "tid": 0,
         "args": {"name": lane}}
        for idx, lane in enumerate(lane_order, start=1)]
    events.extend(merged)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": {"lanes": lanes_meta}}


# ---------------------------------------------------------------------
# native kernel profiling (fed by flink_tpu.native wrappers)
# ---------------------------------------------------------------------

class _KernelStat:
    __slots__ = ("dispatches", "total_ms", "reservoir")

    def __init__(self):
        self.dispatches = 0
        self.total_ms = 0.0
        self.reservoir = _Reservoir()


_kernel_stats: Dict[str, _KernelStat] = {}


def record_kernel(name: str, t0_ns: int, t1_ns: int) -> None:
    """Account one native-kernel dispatch (called by the wrappers in
    ``flink_tpu/native/__init__.py``)."""
    ms = (t1_ns - t0_ns) / 1e6
    with _LOCK:
        stat = _kernel_stats.get(name)
        if stat is None:
            stat = _kernel_stats[name] = _KernelStat()
            _backfill_kernel_gauges(name, stat)
        stat.dispatches += 1
        stat.total_ms += ms
        stat.reservoir.update(ms)
    tracer = _tracer
    stack = getattr(tracer._tls, "stack", None)
    if stack:
        stack[-1].native_ns += t1_ns - t0_ns
    if tracer.enabled:
        event = {
            "name": "native." + name,
            "ph": "X",
            "ts": t0_ns / 1000.0,
            "dur": (t1_ns - t0_ns) / 1000.0,
            "pid": tracer._pid,
            "tid": threading.get_ident(),
        }
        lane = tracer.current_lane()
        if lane is not None:
            event["lane"] = lane
        with tracer._lock:
            tracer._append_locked(event)


def kernel_stats() -> Dict[str, dict]:
    """Per-kernel dispatch counters + wall-time summaries."""
    out = {}
    with _LOCK:
        for name, st in _kernel_stats.items():
            vals = sorted(st.reservoir.values)
            out[name] = {
                "dispatches": st.dispatches,
                "total_ms": st.total_ms,
                "p50_ms": _percentile(vals, 0.50),
                "p99_ms": _percentile(vals, 0.99),
            }
    return out


# ---------------------------------------------------------------------
# JAX jit compile tracking
# ---------------------------------------------------------------------

class _JitStat:
    __slots__ = ("recompiles", "compile_time_ms", "cache_hits",
                 "last_shape_sig", "shape_sigs")

    def __init__(self):
        self.recompiles = 0
        self.compile_time_ms = 0.0
        self.cache_hits = 0
        #: arg-shape signature of the most recent recompile + the set
        #: of distinct signatures seen — shape-churn retraces become
        #: diagnosable instead of just counted
        self.last_shape_sig = ""
        self.shape_sigs: set = set()


_jit_stats: Dict[str, _JitStat] = {}


def _jit_entry(name: str) -> _JitStat:
    with _LOCK:
        stat = _jit_stats.get(name)
        if stat is None:
            stat = _jit_stats[name] = _JitStat()
            _backfill_jit_gauges(name, stat)
        return stat


def _shape_signature(args, kwargs) -> str:
    """Compact per-leaf ``dtype[shape]`` signature of a call's
    arguments — the thing that changed when a jit retraced."""
    def leaf_sig(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return f"{dtype}[{','.join(map(str, shape))}]"
        return type(x).__name__

    try:
        import jax
        leaves = jax.tree_util.tree_leaves((args, kwargs))
    except Exception:  # noqa: BLE001
        leaves = list(args)
    return "(" + ", ".join(leaf_sig(x) for x in leaves) + ")"


def traced_jit(fn, name: Optional[str] = None, **jit_kwargs):
    """``jax.jit`` with compile-event accounting.  Each call compares
    the jitted callable's ``_cache_size()`` before/after: growth means
    the call traced+compiled (count it, with wall time — compilation
    dominates the call so attributing the whole call is a fine
    estimate, plus the triggering arg-shape signature); no growth is a
    cache hit.  When the device telemetry plane is enabled every
    dispatch additionally accumulates wall time and bytes in/out per
    kernel name (``runtime/device_stats.py``).  The program carries
    the label: a profiler trace's "XLA Modules" line reads
    ``jit_state_result`` for ``name="state.result"``, and its ops lie
    under a ``named_scope`` of the label."""
    import jax

    from flink_tpu.runtime.device_stats import TELEMETRY, tree_nbytes

    if _TraceAnnotation is None:
        _hook_jax()
    label = name or getattr(fn, "__name__", None) or "jit_fn"

    @functools.wraps(fn)
    def program(*args, **kwargs):
        with jax.named_scope(label):
            return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = label.replace(".", "_")
    jitted = jax.jit(program, **jit_kwargs)
    stat = _jit_entry(label)
    cache_size = jitted._cache_size

    def wrapper(*args, **kwargs):
        before = cache_size()
        t0 = _perf_ns()
        out = jitted(*args, **kwargs)
        if cache_size() > before:
            ms = (_perf_ns() - t0) / 1e6
            sig = _shape_signature(args, kwargs)
            with _LOCK:
                stat.recompiles += 1
                stat.compile_time_ms += ms
                stat.last_shape_sig = sig
                stat.shape_sigs.add(sig)
            tracer = _tracer
            if tracer.enabled:
                tracer.record_instant("jit.compile." + label,
                                      compile_ms=round(ms, 3),
                                      arg_shapes=sig)
        else:
            stat.cache_hits += 1
        if TELEMETRY.enabled:
            TELEMETRY.record_kernel_dispatch(
                label, (_perf_ns() - t0) / 1e6,
                tree_nbytes((args, kwargs)), tree_nbytes(out))
        return out

    wrapper.__name__ = "traced_" + label.replace(".", "_")
    wrapper._jitted = jitted  # escape hatch (.lower(), cache control)
    wrapper._jit_label = label
    return wrapper


def record_compile_event(name: str, seconds: float) -> None:
    """Account a non-JAX compilation (e.g. the CEP predicate bytecode
    compiler) in the same store ``traced_jit`` feeds."""
    stat = _jit_entry(name)
    ms = seconds * 1000.0
    with _LOCK:
        stat.recompiles += 1
        stat.compile_time_ms += ms
    tracer = _tracer
    if tracer.enabled:
        tracer.record_instant("compile." + name, compile_ms=round(ms, 3))


def jit_stats() -> Dict[str, dict]:
    out = {}
    with _LOCK:
        for name, st in _jit_stats.items():
            out[name] = {
                "recompiles": st.recompiles,
                "compile_time_ms": st.compile_time_ms,
                "cache_hits": st.cache_hits,
                "shape_variants": len(st.shape_sigs),
                "last_shape_sig": st.last_shape_sig,
            }
    return out


def reset_jit_stats() -> None:
    """Zero every label's counts in place: a live ``traced_jit``
    wrapper holds its stat object, and shows here again with its next
    call."""
    with _LOCK:
        for stat in _jit_stats.values():
            stat.__init__()


# ---------------------------------------------------------------------
# registry wiring
# ---------------------------------------------------------------------

# (weakref-to-root-group, kind) pairs; kernel/jit names discovered
# after registration back-fill into every live registered group
_profile_groups: List[weakref.ref] = []
_registered_registry_ids: "weakref.WeakSet" = weakref.WeakSet()


def _backfill_kernel_gauges(name: str, stat: _KernelStat) -> None:
    # caller holds _LOCK
    for ref in list(_profile_groups):
        root = ref()
        if root is None:
            _profile_groups.remove(ref)
            continue
        _add_kernel_gauges(root.add_group("native"), name, stat)


def _backfill_jit_gauges(name: str, stat: _JitStat) -> None:
    # caller holds _LOCK
    for ref in list(_profile_groups):
        root = ref()
        if root is None:
            _profile_groups.remove(ref)
            continue
        _add_jit_gauges(root.add_group("jit"), name, stat)


def _add_kernel_gauges(group, name: str, stat: _KernelStat) -> None:
    g = group.add_group(name)
    g.gauge("dispatches", lambda s=stat: s.dispatches)
    g.gauge("totalMs", lambda s=stat: s.total_ms)
    g.gauge("p50Ms", lambda s=stat: s.reservoir.quantile(0.50))
    g.gauge("p99Ms", lambda s=stat: s.reservoir.quantile(0.99))


def _add_jit_gauges(group, name: str, stat: _JitStat) -> None:
    g = group.add_group(name)
    g.gauge("recompiles", lambda s=stat: s.recompiles)
    g.gauge("compileTimeMs", lambda s=stat: s.compile_time_ms)
    g.gauge("cacheHits", lambda s=stat: s.cache_hits)
    g.gauge("shapeVariants", lambda s=stat: len(s.shape_sigs))
    g.gauge("lastArgShapes", lambda s=stat: s.last_shape_sig)


def register_runtime_profile_gauges(registry) -> None:
    """Publish native-kernel dispatch stats, jit compile stats, and
    span aggregates into ``registry`` (a :class:`MetricRegistry`).
    Idempotent per registry; kernel/jit/span names that first appear
    after registration (engines tier-select on first flush) back-fill
    automatically."""
    if registry in _registered_registry_ids:
        return
    _registered_registry_ids.add(registry)
    root = registry.root
    with _LOCK:
        _profile_groups.append(weakref.ref(root))
        native_group = root.add_group("native")
        for name, stat in _kernel_stats.items():
            _add_kernel_gauges(native_group, name, stat)
        jit_group = root.add_group("jit")
        jit_group.gauge("backendCompiles", lambda: _backend_compiles[0])
        jit_group.gauge("backendCompileMs",
                        lambda: _backend_compiles[1] / 1e6)
        for name, stat in _jit_stats.items():
            _add_jit_gauges(jit_group, name, stat)
    _tracer.install_metrics(root.add_group("tracing"))

"""What the harness reads besides its own clocks: compile events, the
operators a job really built, timers around the window operator's two
entries, the program's exact counters, and the profiler slice.
"""

from __future__ import annotations

import collections
import glob
import os
import shutil
import time

import jax

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """Every backend compile of the process, ``traced_jit`` or not
    (``chip_smoke.py``'s meter).  jax reports a program read from the
    persistent cache under the same event, so ``backend_compiles``
    counts every program the process had to get, and ``cache_hits``
    says how many of them it did not have to build."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.count = collections.Counter()
        self.secs = collections.defaultdict(float)
        #: (host time at which it ended, seconds) of each backend compile
        self.compiled_at = []
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.count.update([name]))
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, name, secs, **kw):
        self.count[name] += 1
        self.secs[name] += secs
        if name == _BACKEND_COMPILE:
            self.compiled_at.append((self.clock(), secs))

    def between(self, start, end):
        """Backend compiles that ended in (start, end]."""
        secs = [s for t, s in self.compiled_at if start < t <= end]
        return {"count": len(secs), "seconds": sum(secs)}

    def report(self):
        return {"backend_compiles": self.count[_BACKEND_COMPILE],
                "backend_compile_s": round(self.secs[_BACKEND_COMPILE], 2),
                "cache_hits": self.count["/jax/compilation_cache/cache_hits"],
                "cache_misses":
                    self.count["/jax/compilation_cache/cache_misses"]}


def capture_operators(env, on_new=None):
    """Every operator instance the executor builds for this job
    (``chip_smoke.py``'s), so a run can name the operator and engine
    that really ran.  ``on_new(op)`` sees each one as it is made."""
    made = []
    for node in env.get_stream_graph().nodes.values():
        def factory(inner=node.operator_factory):
            op = inner()
            made.append(op)
            if on_new is not None:
                on_new(op)
            return op
        node.operator_factory = factory
    return made


def working_operator(ops, class_name, engine_attr):
    """The one instance of the named class that did work (the
    pre-flight linter dry-constructs operators too)."""
    found = [op for op in ops if type(op).__name__ == class_name
             and (getattr(op, engine_attr, None) is not None
                  or op.columnar_rows or op.boxed_rows)]
    if len(found) != 1:
        raise AssertionError(
            f"expected one working {class_name}, the job built "
            f"{[type(op).__name__ for op in ops]}")
    return found[0]


class OperatorTimers:
    """Host seconds inside the window operator's batch entry
    (``ingest``) and watermark entry (``fire``: fire, emit and
    whatever is chained after it), since :meth:`reset`.  Each entry
    also carries a ``bench.<part>`` annotation, so a profiler slice
    can say what the host was in while the device sat idle."""

    def __init__(self, class_name, ingest_entry, clock=time.perf_counter):
        self.class_name = class_name
        self.entries = {ingest_entry: "ingest", "process_watermark": "fire"}
        self.clock = clock
        self.seconds = {"ingest": 0.0, "fire": 0.0}
        self.calls = {"ingest": 0, "fire": 0}

    def total(self):
        return self.seconds["ingest"] + self.seconds["fire"]

    def reset(self):
        for part in self.seconds:
            self.seconds[part] = 0.0
            self.calls[part] = 0

    def wrap(self, op):
        if type(op).__name__ != self.class_name:
            return
        for method, part in self.entries.items():
            setattr(op, method, self._timed(getattr(op, method), part))

    def _timed(self, inner, part):
        clock, seconds, calls = self.clock, self.seconds, self.calls
        label = f"bench.{part}"

        def timed(*args, **kwargs):
            t = clock()
            with jax.profiler.TraceAnnotation(label):
                out = inner(*args, **kwargs)
            seconds[part] += clock() - t
            calls[part] += 1
            return out
        return timed


def program_counters():
    """The program's own exact counts, as plain numbers."""
    from flink_tpu.runtime import tracing
    from flink_tpu.state.stats import STATE_STATS
    native = tracing.kernel_stats()
    return {"flush_rows": STATE_STATS.flush_rows,
            "flush_batches": STATE_STATS.flush_batches,
            "state_batch_rows": STATE_STATS.batch_rows,
            "state_row_fallback_rows": STATE_STATS.row_fallback_rows,
            "native_ms": sum(k["total_ms"] for k in native.values()),
            "native_dispatches": sum(k["dispatches"]
                                     for k in native.values())}


class SliceProfiler:
    """``jax.profiler`` around one slice of the run, Python tracing
    off (a per-call tracer would swamp a host-bound job).  The trace
    lands in ``directory``, emptied first."""

    def __init__(self, directory):
        self.directory = directory
        self.running = False

    def start(self):
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.running = True

    def stop(self):
        if self.running:
            self.running = False
            jax.profiler.stop_trace()

    def trace_file(self):
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None

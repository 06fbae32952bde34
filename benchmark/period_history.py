"""From the program's own history of fire periods to whole-interval
per-layer numbers.

The program (``flink_tpu/runtime/tracing.py``: ``Tracer.periods()``)
cuts its always-on books at every watermark that fired a window: per
period the growth of every phase's count, total, self and collector
time, of the collector's totals and of the native kernels' time, with
the host times of the two cuts and the end timestamp of the newest
window fired.  A reader that runs after ``env.execute()`` in the same
process picks the MEASURED periods out of that history: the harness's
measured window ``w`` (``timeline.Timeline``) is the period that ends
with the fire of the window whose end is ``(w + 1) * window_ms``, so

    index       newest_window_end // window_ms - 1
    measured    the n = events // events_per_window periods whose
                indexes are warm-up .. warm-up + n - 1: the warm-up
                periods before them and the closing one-batch window
                after them drop out
    share       Σ over the measured periods ÷ Σ of their lengths

where the span slice has one traced period, this has all of them, on
the host clock, collector time apart.  ``None`` from everything where
the measured periods cannot be told: a program without ``periods()``
(the parent of the PR that added it), a count that is not n, indexes
that do not follow each other, or lengths that, less the profiler's
stalls (:func:`stalls_s`), do not sum to the harness's ``window_s``
within 2%.
"""

from __future__ import annotations

import json
import os
import statistics

import loader
import span_slice

TOP = span_slice.TOP
WATERMARK = "window.watermark"
#: Σ period lengths, less the stalls, may differ from the harness's
#: interval by this much (the cuts fall at the watermark phase's exit,
#: the harness's marks at the last result row's arrival)
LENGTH_TOLERANCE = 0.02

#: (run, table) of the last run read: once per process, as span_slice
_last = None


def warmup_windows(config, traffic):
    """Windows the cell's source emits before ``t0``, where its module
    says so; ``None`` where it does not."""
    source = loader.load_module("sources", traffic["source"])
    if hasattr(source, "WARMUP_WINDOWS"):
        return source.WARMUP_WINDOWS
    if hasattr(source, "WARMUP_PERIODS_BEYOND_A_WINDOW"):
        return (config["window_size_ms"] // config["slide_ms"]
                + source.WARMUP_PERIODS_BEYOND_A_WINDOW)
    return None


def measured(periods, window_ms, n, warmup=None):
    """The ``n`` measured periods of a history, or ``None``.  With the
    warm-up count: those from the first period of window ``warmup``
    on; without: the ``n`` before the closing one.  Either way their
    window indexes follow each other and a closing period comes after
    them."""
    index = [p["newest_window_end"] // window_ms - 1 for p in periods]
    if warmup is None:
        start = len(periods) - 1 - n
    else:
        start = index.index(warmup) if warmup in index else -1
    if n <= 0 or start < 0 or start + n >= len(periods):
        return None
    if index[start:start + n] != list(range(index[start],
                                            index[start] + n)):
        return None
    return periods[start:start + n]


def stalls_s(chosen):
    """Time the periods spent in no top-level phase beyond what the
    median period does: the harness's ``window_s`` leaves out the
    profiler's start and stop (0.05 s and 0.3 to 0.7 s, in two periods
    of a traced run), which run between two batches, in no phase."""
    idle = [p["end_s"] - p["start_s"]
            - sum(p["phases"].get(t, {}).get("total_ms", 0.0)
                  for t in TOP) * 1e-3 for p in chosen]
    usual = statistics.median(idle)
    return sum(max(0.0, s - usual) for s in idle)


def _quantile(sorted_values, q):
    return sorted_values[min(len(sorted_values) - 1,
                             int(round(q * (len(sorted_values) - 1))))]


def reduce_periods(chosen):
    """The table of the measured periods: ``interval_s`` (Σ lengths),
    the lengths' p50 / p90 / max, per phase count, self, collector
    (booked on it) and total seconds per period as mean and median
    plus the sums the shares divide, and the collector's seconds and
    collections per period, by generation and outside every phase."""
    n = len(chosen)
    lengths = sorted(p["end_s"] - p["start_s"] for p in chosen)
    names = sorted({name for p in chosen for name in p["phases"]})
    phases = {}
    for name in names:
        rows = [p["phases"].get(name, {}) for p in chosen]
        row = {"count_mean": sum(r.get("count", 0) for r in rows) / n,
               "gcs_mean": sum(r.get("gcs", 0) for r in rows) / n}
        for field in ("self", "gc", "gc_under", "native", "total"):
            ms = [r.get(field + "_ms", 0.0) for r in rows]
            row[field + "_s_sum"] = sum(ms) * 1e-3
            if field in ("self", "gc"):
                row[field + "_s_mean"] = sum(ms) * 1e-3 / n
                row[field + "_s_median"] = statistics.median(ms) * 1e-3
        phases[name] = row
    gcs = [p.get("gc", {}) for p in chosen]
    generations = sorted({g for c in gcs for g in c.get("by_generation", {})})
    return {
        "periods": n,
        "interval_s": sum(lengths),
        "length_s": {"p50": _quantile(lengths, 0.5),
                     "p90": _quantile(lengths, 0.9), "max": lengths[-1]},
        "phases": phases,
        "gc": {
            "gc_s_sum": sum(c.get("gc_ms", 0.0) for c in gcs) * 1e-3,
            "gc_s_mean": sum(c.get("gc_ms", 0.0) for c in gcs) * 1e-3 / n,
            "collections_mean": sum(c.get("collections", 0)
                                    for c in gcs) / n,
            "unphased_s_mean": sum(c.get("unphased", {}).get("gc_ms", 0.0)
                                   for c in gcs) * 1e-3 / n,
            "collections_mean_by_generation": {
                str(g): sum(c.get("by_generation", {}).get(g, {})
                            .get("collections", 0) for c in gcs) / n
                for g in generations},
            "gc_s_mean_by_generation": {
                str(g): sum(c.get("by_generation", {}).get(g, {})
                            .get("gc_ms", 0.0) for c in gcs) * 1e-3 / n
                for g in generations}},
        "kernels_s_mean": {
            name: sum(p.get("kernels", {}).get(name, 0.0)
                      for p in chosen) * 1e-3 / n
            for name in sorted({k for p in chosen
                                for k in p.get("kernels", {})})},
    }


def read_history(run, tracer=None):
    """The table of this run's measured periods, or ``None`` (the
    module's docstring says when)."""
    if tracer is None:
        from flink_tpu.runtime.tracing import get_tracer
        tracer = get_tracer()
    if not hasattr(tracer, "periods"):
        return None
    config = run["config"]
    n = run["events"] // config["events_per_window"]
    chosen = measured(tracer.periods(), config["window_ms"], n,
                      warmup_windows(config, run["traffic"]))
    if chosen is None:
        return None
    out = reduce_periods(chosen)
    out["window_s"] = run["window_s"]
    out["stalls_s"] = stalls_s(chosen)
    if abs(out["interval_s"] - out["stalls_s"] - run["window_s"]) \
            > LENGTH_TOLERANCE * run["window_s"]:
        return None
    out["dropped_periods"] = tracer.dropped_periods
    out["first_window"] = chosen[0]["newest_window_end"] \
        // config["window_ms"] - 1
    out["measured"] = chosen
    return out


def table(run, tracer=None):
    """:func:`read_history`, once per process and run; the first read
    prints the table as a ``[periods]`` line and writes it, with the
    measured periods as the program cut them, to ``periods.json``
    beside ``phases.json``."""
    global _last
    if _last is None or _last[0] is not run:
        out = read_history(run, tracer)
        _last = (run, out)
        if out is not None:
            line = {k: v for k, v in out.items() if k != "measured"}
            print(f"[periods] {json.dumps(line)}", flush=True)
            path = span_slice.newest_trace() if run.get("slice_s") else None
            if path is not None:
                # <trace directory>/plugins/profile/<time>/<host>.xplane.pb
                directory = os.path.normpath(
                    os.path.join(path, *[os.pardir] * 4))
                with open(os.path.join(directory, "periods.json"), "w",
                          encoding="utf-8") as f:
                    json.dump(out, f, indent=1)
    return _last[1]


def share(run, names, field="self"):
    """Σ ``field`` seconds of the named phases over the measured
    periods as % of the interval; ``None`` without a history, or where
    none of the phases occurred."""
    t = table(run)
    if t is None:
        return None
    rows = [t["phases"][n] for n in names if n in t["phases"]]
    if not rows:
        return None
    return 100.0 * sum(r[field + "_s_sum"] for r in rows) / t["interval_s"]

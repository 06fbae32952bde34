#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--rehearse-cpu]

One process, no children: the process that runs the job holds the
chip.  The cell's configuration, traffic mix, job builder, source,
key distribution, reference and per-layer metric readers are found by
name (``loader.py``); this file wires the gates and the clocks and has
no table of cells, aggregates or sources.  The job goes through
``StreamExecutionEnvironment.execute()`` on the LocalExecutor; the
benchmark owns the source, the sink, the clocks, the reference and the
comparison that decides ``correct``.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device``: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics (plus ``breakdown``).  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
``--rehearse-cpu`` runs the same code at the tiny sizes the
configuration file gives under ``rehearsal`` on whatever device jax
has; it says so, and every metric it prints is named ``rehearsal_*``.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH_DIR)

import loader  # noqa: E402


def say(tag, fact):
    """An earlier line: facts for the reader, never the result."""
    fact = {"at_s": round(time.perf_counter() - _PROCESS_START, 3), **fact}
    print(f"[{tag}] {json.dumps(fact, default=str)}", flush=True)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny sizes on whatever device jax has; prints "
                        "rehearsal_* metrics only")
    return p.parse_args(argv)


def route_problems(cell, op, events):
    """The run counts only if the route the configuration names is the
    one that ran, all of it columnar."""
    from flink_tpu.streaming import chain_fusion
    expect = cell.config["expect"]
    problems = []
    engine = getattr(op, expect["engine_attr"], None)
    if type(engine).__name__ != expect["engine"]:
        problems.append(f"engine {type(engine).__name__}, the "
                        f"configuration names {expect['engine']}")
    if op.boxed_fallbacks:
        problems.append(f"{op.boxed_fallbacks} boxed fallbacks "
                        f"({op.columnar_fallback_reason})")
    if expect["columnar_rows_equal_events"] and op.columnar_rows != events:
        problems.append(f"columnar_rows {op.columnar_rows} of {events} "
                        f"events")
    if chain_fusion.FUSION_STATS.demotions:
        problems.append(f"{chain_fusion.FUSION_STATS.demotions} fused "
                        f"chains demoted: "
                        f"{chain_fusion.FUSION_STATS.last_demotion}")
    return problems


def main(argv=None):
    args = parse_args(argv)
    try:
        cell = loader.load_cell(args.workload, rehearsal=args.rehearse_cpu)
    except loader.CellError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    try:
        import jax

        import flink_tpu  # noqa: F401 — sets the compile cache's place
        import flink_tpu.native as nat
        from flink_tpu.ops import link_probe
        from flink_tpu.runtime.device_stats import get_telemetry
        from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    except ImportError as e:
        print(f"benchmark: cannot import the system beside "
              f"{BENCH_DIR}: {e}", file=sys.stderr)
        return 2
    import meters
    import peaks
    import timeline as clocks
    import xplane

    # every program goes to the persistent cache, not only those that
    # took a second to compile: a cell's later runs read them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compile_meter = meters.CompileMeter()

    # ---- the device gate --------------------------------------------
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if args.rehearse_cpu:
        print(f"benchmark: REHEARSAL at tiny sizes on {device}: no "
              f"number below is a measurement", flush=True)
    else:
        if device["platform"] != "tpu" or device["count"] < cell.chips:
            print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); "
                  f"jax found {device}. --rehearse-cpu rehearses at a tiny "
                  f"size", file=sys.stderr)
            return 3
        peaks.for_device(device["kind"])
    if not nat.available():
        print(f"benchmark: native runtime: {nat.load_error()}",
              file=sys.stderr)
        return 3
    if get_telemetry().enabled:
        print("benchmark: device telemetry is on; it serialises dispatch",
              file=sys.stderr)
        return 3
    say("device", {**device, "jax": jax.__version__,
                   "native": nat.library_path(),
                   "compile_cache": jax.config.jax_compilation_cache_dir,
                   "link": link_probe.measure(),
                   "finish_tier": link_probe.recommended_finish_tier()})

    # ---- data, job ---------------------------------------------------
    config, traffic = cell.config, cell.traffic
    source = loader.load_module("sources", traffic["source"]).make(
        config, traffic, args.seed, args.seconds)
    timeline = source.timeline
    say("data", {"source": traffic["source"],
                 "events_per_window": config["events_per_window"],
                 "warmup_windows": timeline.warmup_windows})
    sink = clocks.ArrivalSink(timeline, config["window_ms"],
                              config["result_columns"].index("window_start"))
    env = StreamExecutionEnvironment()
    job = loader.load_module("jobs", config["job"])
    job.build(env, source, sink, config)

    expect = config["expect"]
    timers = meters.OperatorTimers(expect["operator"], expect["ingest_entry"])
    profiler = None
    if args.trace:
        profiler = meters.SliceProfiler(
            os.path.join(ROOT, "benchmark_out", "trace", cell.name))
        timeline.profiler = profiler
        timeline.nested_s = timers.total
    ops = meters.capture_operators(
        env, on_new=timers.wrap if args.trace else None)

    marks = {}

    def at_t0():
        timers.reset()
        marks["t0"] = meters.program_counters()
        say("setup", compile_meter.report())
        # a program compiled from here on has a shape that follows the
        # data (set-up warmed every other): it stays out of the
        # persistent cache, so that a run does the same work whether
        # or not an earlier run drew the same seed
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          1e9)

    def at_end():
        marks["end"] = {**meters.program_counters(),
                        "ingest_s": timers.seconds["ingest"],
                        "fire_s": timers.seconds["fire"],
                        "ingest_calls": timers.calls["ingest"],
                        "fire_calls": timers.calls["fire"],
                        "source_s": timeline.source_s}

    timeline.on_t0.append(at_t0)
    timeline.on_end.append(at_end)

    # ---- the run ------------------------------------------------------
    try:
        env.execute(f"benchmark-{cell.name}")
    finally:
        if profiler is not None:
            timeline.stop_slice()
    sink.finish()
    if timeline.t0 is None or timeline.last_measured is None \
            or "end" not in marks:
        print("benchmark: the job ended before the measured window did",
              file=sys.stderr)
        return 4

    measured = timeline.measured_windows()
    events = len(measured) * config["events_per_window"]
    window_s = timeline.window_s()
    fires = timeline.fire_latencies_s()
    periods = timeline.periods_s()
    setup_s = timeline.t0 - _PROCESS_START
    op = meters.working_operator(ops, expect["operator"],
                                 expect["engine_attr"])
    problems = route_problems(cell, op, source.events_emitted)
    if problems:
        print(f"benchmark: not the route {cell.config['name']} names: "
              f"{problems}", file=sys.stderr)
        return 5
    say("route", {"operator": type(op).__name__,
                  **(job.describe(op) if hasattr(job, "describe") else {})})
    emitted = source.emitted()
    seen, first_seen, replayed = set(), [], []
    for e in emitted:
        if e.window in measured:
            (replayed if e.data_id in seen else first_seen).append(
                timeline.arrivals[e.window] - timeline.closes[e.window])
        seen.add(e.data_id)
    in_window = compile_meter.between(timeline.t0,
                                      timeline.arrivals[measured[-1]])
    say("window", {"measured_windows": len(measured), "events": events,
                   "window_s": window_s, "fires": len(fires),
                   "events_per_window_over_median_period":
                       config["events_per_window"]
                       / statistics.median(periods),
                   "fire_ms": [round(f * 1e3, 1) for f in fires[:96]],
                   "period_ms": [round(p * 1e3, 1) for p in periods[:96]],
                   "fire_p50_ms_first_seen_rows":
                       first_seen and statistics.median(first_seen) * 1e3,
                   "fire_p50_ms_replayed_rows":
                       replayed and statistics.median(replayed) * 1e3,
                   "windows_first_seen_replayed":
                       [len(first_seen), len(replayed)],
                   "compiles_in_window": in_window,
                   "profiler_stall_s": timeline.excluded_s,
                   **compile_meter.report()})

    stats = devices[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:max(cell.chips, 1)])
    device["memory_peak_bytes"] = peak
    say("memory", {k: stats.get(k) for k in
                   ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")})

    # ---- correctness, after the timed window --------------------------
    t_check = time.perf_counter()
    reference = loader.load_module("references", config["reference"])
    verdict = reference.check(config, emitted, sink.by_window())
    say("check", {**verdict["facts"], "problems": verdict["problems"],
                  "seconds": time.perf_counter() - t_check})
    result = {"correct": not verdict["failed"] and not verdict["problems"],
              "attempted": verdict["attempted"], "failed": verdict["failed"]}

    # ---- metrics --------------------------------------------------------
    end_to_end = {
        "events_per_s": events / window_s,
        "fire_p50_ms": statistics.median(fires) * 1e3,
        "setup_s": setup_s,
    }
    if args.trace:
        trace = None
        path = profiler.trace_file()
        if path is not None:
            profile = jax.profiler.ProfileData.from_file(path)
            with open(os.path.join(profiler.directory, "summary.json"),
                      "w", encoding="utf-8") as f:
                json.dump(xplane.summary(profile), f, indent=1)
            trace = xplane.reduce_trace(profile)
        slice_s = (timeline.slice_stop - timeline.slice_start
                   if timeline.slice_stop is not None else None)
        run = {"config": config, "traffic": traffic,
               "window_s": window_s, "events": events,
               "t0": marks["t0"], "end": marks["end"],
               "compiles_in_window": in_window["count"],
               "compile_s_in_window": in_window["seconds"],
               "trace": trace, "slice_s": slice_s,
               "memory_peak_bytes": peak, "end_to_end": end_to_end}
        values = {}
        for metric in cell.per_layer:
            reader = loader.load_module("layer_metrics", metric["name"])
            value = reader.read(run)
            if value is not None:
                values[metric["name"]] = (float(value), metric["unit"])
        if trace is not None and slice_s:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = slice_s
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
        say("traced", {"end_to_end_with_tracing_on": end_to_end,
                       "slice_s": slice_s,
                       "devices_in_trace": trace and trace["devices"]})
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = {name: (value, units[name])
                  for name, value in end_to_end.items() if name in units}

    prefix = "rehearsal_" if args.rehearse_cpu else ""
    result["metrics"] = {prefix + name: {"value": value, "unit": unit}
                         for name, (value, unit) in values.items()}
    result["device"] = device
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Command-line front end (ref: flink-clients CliFrontend.java + the
bin/flink script).

    python -m flink_tpu run <script.py> [args...]   execute a job script
                                   [-s PATH]         ... from a savepoint or
                                                     a retained checkpoint
    python -m flink_tpu lint <script.py|dir> [args...] pre-flight checks
                                   [--strict]        without executing:
                                   [--json]          graph linter + UDF
                                   [--check-imports] liftability analysis
    python -m flink_tpu profile <script.py> [args...] run with the tracer
                                   [--trace-out F]   attached; write a
                                                     Chrome trace-event
                                                     file + span summary
    python -m flink_tpu top <rest-url>               live per-vertex view of
                                   [--job NAME]      a running job (records/s,
                                   [--interval S]    backpressure, watermark
                                   [--once]          lag, checkpoints,
                                                     bottleneck)
    python -m flink_tpu state inspect <dir>          offline checkpoint
                                   [--checkpoint N]  inspector: per-state
                                   [--top K]         per-key-group rows/bytes,
                                   [--parallelism P] dtypes, heaviest keys,
                                   [--json]          rescale preview
    python -m flink_tpu list --master H:P            list cluster jobs
    python -m flink_tpu cancel --master H:P <job>    cancel a running job
                                   [-s DIR]          ... with a savepoint
    python -m flink_tpu savepoint --master H:P <job> <dir>
                                                     trigger a savepoint
    python -m flink_tpu stop --master H:P <job> --savepoint-dir DIR
                                                     savepoint then stop
    python -m flink_tpu info                         version + devices
    python -m flink_tpu bench --workload <cell> ...   benchmark/run.py with
                                                     these arguments
    python -m flink_tpu jobmanager [--port P]        start a cluster master
                                                     (Dispatcher + RM + blob)
    python -m flink_tpu taskmanager --master H:P     start a worker process
                                   [--slots N]
    python -m flink_tpu config-docs                  render the config-option
                                                     reference (flink-docs)
    python -m flink_tpu shell [--master H:P]         interactive REPL with a
                                                     preloaded environment
"""

from __future__ import annotations

import runpy
import sys


def _info() -> int:
    import flink_tpu
    print(f"flink_tpu {flink_tpu.__version__}")
    try:
        import jax
        print(f"jax {jax.__version__}, devices: {jax.devices()}")
    except Exception as e:  # noqa: BLE001
        print(f"jax unavailable: {e}")
    try:
        import flink_tpu.native as nat
        print(f"native host runtime: "
              f"{'available' if nat.available() else nat.load_error()}")
    except Exception as e:  # noqa: BLE001
        print(f"native host runtime: {e}")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help", "help"):
        print(__doc__)
        return 0
    verb, rest = argv[0], argv[1:]
    if verb == "info":
        return _info()
    if verb == "run":
        if len(rest) >= 2 and rest[0] in ("-s", "--from-savepoint"):
            # (ref: `flink run -s <path>`) a savepoint file, or a
            # retained checkpoint directory / chk-N file
            from flink_tpu.streaming import datastream
            datastream.DEFAULT_RESTORE_PATH = rest[1]
            rest = rest[2:]
        if not rest:
            print("usage: flink_tpu run [-s <savepoint or retained "
                  "checkpoint>] <script.py> [args...]", file=sys.stderr)
            return 2
        sys.argv = rest
        runpy.run_path(rest[0], run_name="__main__")
        return 0
    if verb == "lint":
        return _lint(rest)
    if verb == "profile":
        return _profile(rest)
    if verb == "bench":
        # benchmark/run.py is the one program that gives a number; it
        # runs here, in this process (the process that runs the job
        # holds the chip), and exits with its own code
        import os
        run_py = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "run.py")
        sys.argv = [run_py] + rest
        runpy.run_path(run_py, run_name="__main__")
        return 0
    if verb == "shell":
        return _shell(rest)
    if verb == "config-docs":
        from flink_tpu.core.config_docs import main as docs_main
        return docs_main()
    if verb == "jobmanager":
        return _jobmanager(rest)
    if verb == "taskmanager":
        return _taskmanager(rest)
    if verb == "top":
        return _top(rest)
    if verb == "state":
        return _state(rest)
    if verb == "list":
        return _list(rest)
    if verb == "cancel":
        return _cancel(rest)
    if verb == "savepoint":
        return _savepoint(rest)
    if verb == "stop":
        return _stop(rest)
    print(f"unknown command {verb!r}; "
          f"try: run | lint | profile | top | state | list | cancel "
          f"| savepoint | stop | info | bench | jobmanager | taskmanager",
          file=sys.stderr)
    return 2


def _lint(rest) -> int:
    """Pre-flight static analysis of job scripts: capture the
    topologies a script builds (execute() is neutered), run the graph
    linter + liftability analyzer, and report FTxxx diagnostics.
    Exit code 0 = no errors, 1 = errors found, 2 = usage."""
    import json as _json
    import os

    strict = json_out = check_imports = types = False
    args = []
    for a in rest:
        if a == "--strict":
            strict = True
        elif a == "--json":
            json_out = True
        elif a == "--check-imports":
            check_imports = True
        elif a == "--types":
            types = True
        else:
            args.append(a)
    if not args:
        print("usage: flink_tpu lint [--strict] [--json] [--types] "
              "[--check-imports] <script.py|dir> [script args...]",
              file=sys.stderr)
        return 2
    target, script_args = args[0], args[1:]

    if os.path.isdir(target):
        scripts = sorted(
            os.path.join(target, f) for f in os.listdir(target)
            if f.endswith(".py") and not f.startswith("_"))
        if script_args:
            print("script args only apply to a single script",
                  file=sys.stderr)
            return 2
    else:
        scripts = [target]

    import contextlib

    from flink_tpu.analysis.script_lint import lint_script
    total_errors = total_warnings = 0
    payload = []
    for script in scripts:
        if json_out:
            # the linted script's own prints must not corrupt the
            # machine-readable payload on stdout
            with contextlib.redirect_stdout(sys.stderr):
                res = lint_script(script, script_args, types=types)
        else:
            res = lint_script(script, script_args, types=types)
        c = res.counts()
        total_errors += c["error"]
        total_warnings += c["warning"]
        if json_out:
            jobs = []
            for _, report in res.reports:
                j = report.to_dict()
                tf = getattr(report, "typeflow", None)
                if tf is not None:
                    j["typeflow"] = tf.to_dict()
                jobs.append(j)
            payload.append({
                "script": script,
                "script_error": (repr(res.script_error)
                                 if res.script_error else None),
                "jobs": jobs,
            })
            continue
        print(f"== {script}")
        if res.script_error is not None:
            print(f"   script raised during graph construction: "
                  f"{res.script_error!r}")
        if not res.reports:
            print("   (no topology captured)")
        for _, report in res.reports:
            print("   " + report.render().replace("\n", "\n   "))
            tf = getattr(report, "typeflow", None)
            if tf is not None:
                s = tf.summary()
                print(f"   typeflow: {s['edges_conclusive']}/"
                      f"{s['edges_total']} edges conclusive, "
                      f"{s['kernels_proven']}/{s['kernels_total']} "
                      f"kernels proven probe-free, "
                      f"{s['pickle_edges']} pickle-tier exchange "
                      f"edge(s), predicted state "
                      f"{s['predicted_state_bytes']} B")

    imports_rc = 0
    if check_imports:
        from flink_tpu.analysis.imports_check import check_file, check_tree
        findings = []
        for t in args:
            findings.extend(check_tree(t) if os.path.isdir(t)
                            else check_file(t))
        if json_out:
            payload.append({"unused_imports": [
                f.__dict__ for f in findings]})
        else:
            for f in findings:
                print(f.render())
        imports_rc = 1 if findings else 0

    if json_out:
        print(_json.dumps(payload, indent=2))
    if total_errors or (strict and (total_warnings or imports_rc)):
        return 1
    return imports_rc if strict else 0


def _profile(rest) -> int:
    """Run a job script with the tracer attached; on exit write the
    Chrome trace-event file (load in Perfetto / chrome://tracing) and
    print the per-span and per-kernel summaries to stderr.  With
    --flame the sampling profiler rides along and the folded
    collapsed-stack profile (flamegraph.pl / speedscope input) is
    written too."""
    out = "trace.json"
    if "--trace-out" in rest:
        i = rest.index("--trace-out")
        if i + 1 >= len(rest):
            print("--trace-out needs a path", file=sys.stderr)
            return 2
        out = rest[i + 1]
        rest = rest[:i] + rest[i + 2:]
    flame = "--flame" in rest
    if flame:
        rest = [a for a in rest if a != "--flame"]
    flame_out = "profile.folded"
    if "--flame-out" in rest:
        i = rest.index("--flame-out")
        if i + 1 >= len(rest):
            print("--flame-out needs a path", file=sys.stderr)
            return 2
        flame_out = rest[i + 1]
        rest = rest[:i] + rest[i + 2:]
        flame = True
    flame_hz = 50.0
    if "--flame-hz" in rest:
        i = rest.index("--flame-hz")
        if i + 1 >= len(rest):
            print("--flame-hz needs a number", file=sys.stderr)
            return 2
        try:
            flame_hz = float(rest[i + 1])
        except ValueError:
            print(f"--flame-hz wants a number, got {rest[i + 1]!r}",
                  file=sys.stderr)
            return 2
        rest = rest[:i] + rest[i + 2:]
        flame = True
    if not rest:
        print("usage: flink_tpu profile <script.py> [args...] "
              "[--trace-out trace.json] [--flame] "
              "[--flame-out profile.folded] [--flame-hz 50]",
              file=sys.stderr)
        return 2

    from flink_tpu.runtime import tracing
    tracer = tracing.get_tracer()
    tracer.enabled = True
    profiler = None
    if flame:
        from flink_tpu.runtime.profiler import get_profiler
        profiler = get_profiler()
        profiler.enable(hz=flame_hz)
    sys.argv = rest
    try:
        runpy.run_path(rest[0], run_name="__main__")
    finally:
        if profiler is not None:
            profiler.disable()
            from flink_tpu.runtime.profiler import collapsed_lines
            folded = collapsed_lines(profiler.export())
            with open(flame_out, "w") as f:
                f.write("\n".join(folded) + ("\n" if folded else ""))
            print(f"-- flame: {sum(profiler.samples)} samples, "
                  f"{len(folded)} stacks -> {flame_out}",
                  file=sys.stderr)
        n = tracer.write_chrome_trace(out)
        print(f"-- trace: {n} events -> {out}", file=sys.stderr)
        stats = sorted(tracer.stats().items(),
                       key=lambda kv: -kv[1]["total_ms"])
        for name, s in stats[:20]:
            print(f"{name:<40} n={s['count']:<8} "
                  f"total={s['total_ms']:.1f}ms self={s['self_ms']:.1f}ms "
                  f"p99={s['p99_ms']:.3f}ms", file=sys.stderr)
        kernels = sorted(tracing.kernel_stats().items(),
                         key=lambda kv: -kv[1]["total_ms"])
        for name, s in kernels[:20]:
            print(f"native.{name:<33} n={s['dispatches']:<8} "
                  f"total={s['total_ms']:.1f}ms p99={s['p99_ms']:.3f}ms",
                  file=sys.stderr)
    return 0


def _top_fetch(base, path):
    import json as _json
    import urllib.request
    with urllib.request.urlopen(base + path, timeout=5.0) as resp:
        return _json.loads(resp.read().decode())


def _top_hot_frames(flame) -> dict:
    """vertex id -> hottest frame label from a `/flamegraph` payload
    (max self-samples anywhere in that vertex's subtree); {} when the
    profiler is off or the server predates the route."""
    out = {}
    tree = (flame or {}).get("tree") or {}
    for child in tree.get("children") or []:
        try:
            vid = int(str(child.get("name", "")).split("_", 1)[0])
        except ValueError:
            continue
        from flink_tpu.runtime.profiler import hottest_frame
        best = hottest_frame(child)
        if best is not None:
            out[vid] = best[0]
    return out


def _top_latency_footer(job, metrics) -> str:
    """One-line end-to-end latency picture from the job's `latency.*`
    histograms (p50/p95/p99 ms per source→operator pair, worst
    subtask), or "" when no latency markers flow."""
    prefix = f"{job}.latency.source_"
    pairs = {}
    for k, v in metrics.items():
        if not k.startswith(prefix) or not isinstance(v, dict):
            continue
        if not v.get("count"):
            continue
        src, sep, op = k[len(prefix):].partition(".operator_")
        if not sep:
            continue
        src_op = src.rsplit("_", 1)[0]  # strip the subtask index
        worst = pairs.setdefault((src_op, op), [0.0, 0.0, 0.0])
        for i, q in enumerate(("p50", "p95", "p99")):
            val = v.get(q)
            if isinstance(val, (int, float)):
                worst[i] = max(worst[i], float(val))
    if not pairs:
        return ""
    parts = [f"{src}→{op} {w[0]:.1f}/{w[1]:.1f}/{w[2]:.1f}"
             for (src, op), w in sorted(pairs.items())]
    return "latency ms (p50/p95/p99): " + "; ".join(parts)


def _top_rows(job, detail, metrics, prev, dt_s, hot=None):
    """One table row per vertex: records/s (Δ numRecordsOut across the
    vertex's subtasks between refreshes), worst backpressure, max
    watermarkLag, hottest sampled frame."""
    rows = []
    for v in detail.get("vertices") or []:
        prefix = f"{job}.{v['id']}_"
        out_now = sum(val for k, val in metrics.items()
                      if k.startswith(prefix) and k.endswith(".numRecordsOut")
                      and isinstance(val, (int, float)))
        out_prev = sum(val for k, val in prev.items()
                       if k.startswith(prefix) and k.endswith(".numRecordsOut")
                       and isinstance(val, (int, float))) if prev else None
        rate = ((out_now - out_prev) / dt_s
                if out_prev is not None and dt_s > 0 else None)
        lags = [val for k, val in metrics.items()
                if k.startswith(prefix) and k.endswith(".watermarkLag")
                and isinstance(val, (int, float))]
        # columnar pipeline health: worst per-subtask batch-row ratio
        # (None until a batch is seen) and total boxed fallbacks
        col_ratios = [val for k, val in metrics.items()
                      if k.startswith(prefix)
                      and k.endswith(".columnar.ratio")
                      and isinstance(val, (int, float))]
        col_boxed = sum(val for k, val in metrics.items()
                        if k.startswith(prefix)
                        and k.endswith(".columnar.boxed_fallbacks")
                        and isinstance(val, (int, float)))
        # chain-fusion share: worst per-subtask fraction of rows that
        # rode a fused chain program (None until a batch is seen)
        fused_ratios = [val for k, val in metrics.items()
                        if k.startswith(prefix)
                        and k.endswith(".columnar.fused_ratio")
                        and isinstance(val, (int, float))]
        bp = (detail.get("backpressure") or {}).get(str(v["id"])) or {}
        rows.append({
            "id": v["id"], "name": v["name"],
            "parallelism": v.get("parallelism"),
            "records_per_s": rate,
            "bp_ratio": bp.get("max_ratio"), "bp_level": bp.get("level"),
            "watermark_lag_ms": max(lags) if lags else None,
            "columnar_ratio": min(col_ratios) if col_ratios else None,
            "fused_ratio": min(fused_ratios) if fused_ratios else None,
            "columnar_boxed": col_boxed,
            "hot": (hot or {}).get(v["id"]),
        })
    return rows


def _top_state_footer(metrics, state=None) -> str:
    """One-line keyed-state picture from the process-wide `state.*`
    gauges plus, when the introspection plane is on, the skew and
    hot-key cells from the `/jobs/<n>/state` payload.  "" when the
    server predates the gauges; the skew cells degrade away when
    introspection is disabled or the server predates the route."""
    if not any(k.startswith("state.") for k in metrics):
        return ""

    def g(key, default=0):
        v = metrics.get("state." + key)
        return v if isinstance(v, (int, float)) else default

    line = (f"state: batch rows {g('batchRows'):,.0f}, "
            f"row-fallback {g('rowFallbackRows'):,.0f}")
    if g("flushBatches"):
        line += (f"; flush mean {g('flushSizeMean'):,.0f} "
                 f"max {g('flushSizeMax'):,.0f}")
    if g("device.states"):
        line += (f"; device slots {g('device.slotsInUse'):,.0f}"
                 f"/{g('device.capacity'):,.0f}, "
                 f"spilled {g('device.spilledEntries'):,.0f}, "
                 f"evictions {g('device.evictions'):,.0f}, "
                 f"promotions {g('device.promotions'):,.0f}, "
                 f"pending {g('device.pendingDepth'):,.0f}")
    if isinstance(state, dict) and state.get("enabled"):
        sk = state.get("skew") or {}
        cell = f"; skew {sk.get('ratio', 0.0):,.2f}x"
        verdict = sk.get("verdict")
        if verdict and verdict not in ("idle",):
            cell += f" ({verdict})"
        hot_kg = sk.get("hot_key_group")
        if isinstance(hot_kg, int) and hot_kg >= 0:
            cell += f" kg {hot_kg}"
        line += cell
        hot = state.get("hot_keys") or []
        if hot:
            h = hot[0]
            line += (f"; hot-key {h.get('key')} "
                     f"{float(h.get('share', 0.0)) * 100:,.0f}%"
                     f" of {h.get('state')}")
    return line


def _fmt_bytes(n) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return (f"{n:,.0f} {unit}" if unit == "B"
                    else f"{n:,.1f} {unit}")
        n /= 1024
    return f"{n:,.1f} GiB"


def _top_device_footer(metrics, prev=None, dt=0.0) -> str:
    """One-line device-telemetry picture from the process-wide
    `device.*` gauges: HBM used/capacity, transfer B/s, flushes/s and
    the fire-flush ratio.  "" when the telemetry plane is disabled or
    the server predates it."""
    if not metrics.get("device.enabled"):
        return ""

    def g(key, default=0):
        v = metrics.get("device." + key)
        return v if isinstance(v, (int, float)) else default

    def rate(key):
        if not prev or not dt:
            return None
        pv = (prev or {}).get("device." + key)
        if not isinstance(pv, (int, float)):
            return None
        return max(0.0, (g(key) - pv) / dt)

    line = f"device: HBM {_fmt_bytes(g('hbm.bytesInUse'))}"
    if g("hbm.bytesLimit"):
        line += f"/{_fmt_bytes(g('hbm.bytesLimit'))}"
    h2d, d2h = rate("h2d.bytes"), rate("d2h.bytes")
    line += ("; h2d " + (f"{_fmt_bytes(h2d)}/s" if h2d is not None
                         else _fmt_bytes(g("h2d.bytes")) + " total"))
    line += ("; d2h " + (f"{_fmt_bytes(d2h)}/s" if d2h is not None
                         else _fmt_bytes(g("d2h.bytes")) + " total"))
    fl = rate("flushes")
    line += ("; flushes " + (f"{fl:,.1f}/s" if fl is not None
                             else f"{g('flushes'):,.0f}"))
    # prefer the sample-delta rate (same horizon as the other /s
    # figures); fall back to the telemetry plane's own ring gauge
    wf = rate("windowsFired")
    if wf is None:
        wf = g("windowsFiredRate")
    line += f"; fired {wf:,.1f}/s"
    line += f"; fire/flush {g('fireFlushRatio'):,.2f}"
    return line


def _top_typeflow_footer(job, metrics) -> str:
    """One-line type-flow picture: the AOT `typeflow.*` summary
    gauges plus the live probe-free story from the per-operator
    `columnar.decided_by` / `columnar.probes` gauges.  "" when the
    prover never ran and no kernel has decided yet."""
    def g(key):
        v = metrics.get(f"{job}.typeflow.{key}")
        return v if isinstance(v, (int, float)) else None

    static = probed = fused = 0
    probes = 0.0
    for k, v in metrics.items():
        if not k.startswith(f"{job}."):
            continue
        if k.endswith(".columnar.decided_by"):
            if v == "static":
                static += 1
            elif v == "probe":
                probed += 1
            elif v == "fused":
                fused += 1
        elif k.endswith(".columnar.probes") \
                and isinstance(v, (int, float)):
            probes += v
    if g("edges_total") is None and not (static or probed or fused
                                         or probes):
        return ""
    parts = []
    if g("edges_total") is not None:
        parts.append(f"{g('edges_conclusive') or 0:,.0f}/"
                     f"{g('edges_total'):,.0f} edges conclusive")
        parts.append(f"{g('kernels_proven') or 0:,.0f}/"
                     f"{g('kernels_total') or 0:,.0f} kernels proven")
        if g("pickle_edges"):
            parts.append(f"{g('pickle_edges'):,.0f} pickle edge(s)")
    parts.append(f"kernels decided static {static} / probe {probed} "
                 f"/ fused {fused}, probes run {probes:,.0f}")
    return "typeflow: " + ", ".join(parts)


def _top_render(job, status, rows, checkpoints, alerts,
                bottleneck=None, state_line="", device_line="",
                latency_line="", typeflow_line="") -> str:
    def fmt(v, spec="{:.0f}", dash="-"):
        return dash if v is None else spec.format(v)

    bn = (bottleneck or {}).get("bottleneck") or {}
    bn_vid = bn.get("vertex_id")
    lines = [f"job: {job}  [{status}]",
             f"{'id':>4}  {'vertex':<36} {'par':>3}  {'rec/s':>10}  "
             f"{'backpressure':<18} {'wmLag ms':>10} {'col%':>6} "
             f"{'fused%':>6} {'boxed':>6} {'BOTTLENECK':<10} {'HOT':<28}"]
    for r in rows:
        bp = "-"
        if r["bp_ratio"] is not None:
            bp = f"{r['bp_ratio'] * 100:5.1f}%"
            if r["bp_level"]:
                bp += f" ({r['bp_level']})"
        col = ("-" if r.get("columnar_ratio") is None
               else f"{r['columnar_ratio'] * 100:.0f}%")
        fus = ("-" if r.get("fused_ratio") is None
               else f"{r['fused_ratio'] * 100:.0f}%")
        marker = "<<<" if r["id"] == bn_vid else ""
        lines.append(
            f"{r['id']:>4}  {r['name'][:36]:<36} "
            f"{fmt(r['parallelism'], '{:d}'):>3}  "
            f"{fmt(r['records_per_s'], '{:,.0f}'):>10}  {bp:<18} "
            f"{fmt(r['watermark_lag_ms'], '{:,.0f}'):>10} {col:>6} "
            f"{fus:>6} "
            f"{fmt(r.get('columnar_boxed'), '{:,.0f}'):>6} {marker:<10} "
            f"{(r.get('hot') or '-')[:28]:<28}")
    counts = checkpoints.get("counts") or {}
    last = None
    for c in checkpoints.get("history") or []:
        if c.get("status") == "completed":
            last = c
    cp = (f"checkpoints: {counts.get('completed', 0)} completed, "
          f"{counts.get('failed', 0)} failed")
    if last is not None:
        cp += (f"; last #{last['id']} "
               f"{fmt(last.get('duration_ms'), '{:.0f}')} ms, "
               f"{last.get('state_bytes', 0)} B")
    lines.append(cp)
    firing = alerts.get("rules_firing") or []
    lines.append(f"alerts: {alerts.get('total', 0)} total"
                 + (f"; FIRING: {', '.join(firing)}" if firing else ""))
    if state_line:
        lines.append(state_line)
    if device_line:
        lines.append(device_line)
    if latency_line:
        lines.append(latency_line)
    if typeflow_line:
        lines.append(typeflow_line)
    if bn_vid is not None:
        ups = ", ".join(f"{u.get('name')} ({u.get('ratio', 0) * 100:.0f}%)"
                        for u in bn.get("backpressured_upstreams") or [])
        lines.append(
            f"BOTTLENECK: {bn.get('name')} (vertex {bn_vid}) busy "
            f"{fmt(bn.get('busyMsPerSecond'), '{:.0f}')} ms/s"
            + (f"; backpressured upstreams: {ups}" if ups else ""))
    else:
        lines.append("BOTTLENECK: none")
    return "\n".join(lines)


def _top(rest) -> int:
    """Live per-vertex job view over the WebMonitor/HistoryServer REST
    API — the `flink list -r` + web dashboard combination as a
    terminal table (think `top` for one job)."""
    import argparse
    import time
    import urllib.parse

    ap = argparse.ArgumentParser(prog="flink_tpu top")
    ap.add_argument("url", help="WebMonitor base url, e.g. "
                                "http://127.0.0.1:8081")
    ap.add_argument("--job", default=None,
                    help="job name (default: first tracked job)")
    ap.add_argument("--interval", type=float, default=1.0)
    ap.add_argument("--once", action="store_true",
                    help="print one snapshot and exit")
    args = ap.parse_args(rest)
    base = args.url.rstrip("/")
    if "://" not in base:
        base = "http://" + base

    prev_metrics: dict = {}
    prev_full: dict = {}
    prev_t = None
    try:
        while True:
            jobs = _top_fetch(base, "/jobs")
            job = args.job or (sorted(jobs) or [None])[0]
            if job is None:
                print("(no tracked jobs)")
                return 0
            q = urllib.parse.quote(job, safe="")
            detail = _top_fetch(base, f"/jobs/{q}/detail")
            metrics = _top_fetch(base, f"/jobs/{q}/metrics")
            # state.* gauges are process-wide, not job-scoped: the
            # footer reads them off the full registry dump
            try:
                full_dump = _top_fetch(base, "/metrics")
            except OSError:
                full_dump = metrics
            checkpoints = _top_fetch(base, f"/jobs/{q}/checkpoints")
            alerts = _top_fetch(base, f"/jobs/{q}/alerts")
            try:
                bottleneck = _top_fetch(base, f"/jobs/{q}/bottleneck")
            except OSError:  # pre-bottleneck server: footer reads "none"
                bottleneck = None
            try:
                flame = _top_fetch(base, f"/jobs/{q}/flamegraph")
            except OSError:  # pre-profiler server: HOT column reads "-"
                flame = None
            try:
                kstate = _top_fetch(base, f"/jobs/{q}/state")
            except OSError:  # pre-introspection server: no skew cells
                kstate = None
            now = time.monotonic()
            if args.once and prev_t is None:
                # rates need two samples: take a quick second one
                prev_metrics, prev_full, prev_t = metrics, full_dump, now
                time.sleep(min(args.interval, 0.5))
                continue
            dt = (now - prev_t) if prev_t is not None else 0.0
            rows = _top_rows(job, detail, metrics, prev_metrics, dt,
                             hot=_top_hot_frames(flame))
            out = _top_render(job, detail.get("status"), rows,
                              checkpoints, alerts, bottleneck,
                              state_line=_top_state_footer(full_dump,
                                                           kstate),
                              device_line=_top_device_footer(
                                  full_dump, prev_full, dt),
                              latency_line=_top_latency_footer(
                                  job, metrics),
                              typeflow_line=_top_typeflow_footer(
                                  job, metrics))
            if args.once:
                print(out)
                return 0
            # full-redraw refresh (clear + home), like watch(1)
            print("\x1b[2J\x1b[H" + out, flush=True)
            prev_metrics, prev_full, prev_t = metrics, full_dump, now
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0
    except OSError as e:
        print(f"cannot reach {base}: {e}", file=sys.stderr)
        return 1


def _state(rest) -> int:
    """Offline keyed-state tools (ref: flink-state-processor-api's
    read-only SavepointReader, as a terminal inspector).  `state
    inspect <dir>` reads a completed checkpoint's v2 columnar snapshot
    chunks straight off the filesystem — no running job — and prints
    per-state per-key-group rows/bytes, the component dtype breakdown,
    the heaviest keys, and (with --parallelism) a rescale preview."""
    import argparse
    import json as _json

    ap = argparse.ArgumentParser(prog="flink_tpu state")
    sub = ap.add_subparsers(dest="cmd")
    ins = sub.add_parser("inspect",
                         help="inspect a checkpoint directory offline")
    ins.add_argument("directory", help="checkpoint directory (the one "
                                       "holding chk-N subdirs/files)")
    ins.add_argument("--checkpoint", type=int, default=None,
                     help="checkpoint id (default: latest completed)")
    ins.add_argument("--top", type=int, default=10,
                     help="how many heaviest keys to list (default 10)")
    ins.add_argument("--parallelism", type=int, default=None,
                     help="preview per-subtask key-group load at this "
                          "parallelism")
    ins.add_argument("--json", action="store_true", dest="json_out",
                     help="emit the raw report as JSON")
    args = ap.parse_args(rest)
    if args.cmd != "inspect":
        ap.print_help(sys.stderr)
        return 2

    from flink_tpu.state.introspect import inspect_checkpoint
    try:
        report = inspect_checkpoint(args.directory,
                                    checkpoint_id=args.checkpoint,
                                    top=args.top,
                                    parallelism=args.parallelism)
    except (FileNotFoundError, ValueError) as e:
        print(f"state inspect: {e}", file=sys.stderr)
        return 1
    if args.json_out:
        print(_json.dumps(report, indent=2, default=str))
        return 0

    print(f"checkpoint chk-{report['checkpoint_id']} "
          f"({report['directory']})")
    backends = ", ".join(report.get("backends") or []) or "?"
    print(f"backends: {backends}; "
          f"max parallelism: {report.get('max_parallelism')}")
    states = report.get("states") or {}
    if not states:
        print("(no keyed state in this checkpoint)")
        return 0
    for name, st in states.items():
        kgs = st["key_groups"]
        print(f"\nstate {name!r}: {st['rows']:,} rows, "
              f"{_fmt_bytes(st['bytes'])} across {len(kgs)} key group(s)")
        dt = ", ".join(f"{d} {_fmt_bytes(b)}"
                       for d, b in st["dtypes"].items())
        if dt:
            print(f"  dtypes: {dt}")
        print(f"  {'kg':>5}  {'rows':>10}  {'bytes':>12}  {'ns':>4}")
        for kg, e in st["key_groups"].items():
            print(f"  {kg:>5}  {e['rows']:>10,}  "
                  f"{_fmt_bytes(e['bytes']):>12}  {e['namespaces']:>4}")
    if report.get("top_keys"):
        print(f"\nheaviest keys (top {args.top}):")
        for k in report["top_keys"]:
            print(f"  {k['state']:<24} {k['key']:<24} "
                  f"{k['rows']:>8,} rows  {_fmt_bytes(k['bytes'])}")
    rescale = report.get("rescale")
    if rescale:
        print(f"\nrescale preview at parallelism "
              f"{rescale['parallelism']} "
              f"(max {rescale['max_parallelism']}, "
              f"imbalance {rescale['imbalance']:.2f}x):")
        for s in rescale["subtasks"]:
            lo, hi = s["key_group_range"]
            print(f"  subtask {s['subtask']:>3}  kg [{lo:>4}, {hi:>4}]  "
                  f"{s['rows']:>10,} rows  {_fmt_bytes(s['bytes'])}")
    return 0


def _client(master, secret=None, tls_dir=None):
    from flink_tpu.runtime.cluster import RemoteExecutor
    tls = None
    if tls_dir:
        from flink_tpu.runtime.tls import TlsConfig
        tls = TlsConfig.from_dir(tls_dir, create=False)
    return RemoteExecutor(master, secret=secret, tls=tls)


def _ops_parser(prog, job_arg=True):
    import argparse
    ap = argparse.ArgumentParser(prog=f"flink_tpu {prog}")
    ap.add_argument("--master", required=True,
                    help="jobmanager host:port")
    ap.add_argument("--secret", default=None)
    ap.add_argument("--tls-dir", default=None,
                    help="directory with tls.crt/tls.key (mutual TLS "
                         "to a --tls-dir cluster)")
    if job_arg:
        ap.add_argument("job_id")
    return ap


def _list(rest) -> int:
    """(ref: CliFrontend list / `flink list`)"""
    ap = _ops_parser("list", job_arg=False)
    ap.add_argument("--all", action="store_true",
                    help="include finished jobs")
    args = ap.parse_args(rest)
    client = _client(args.master, args.secret, args.tls_dir)
    try:
        jobs = client.list_jobs()
    finally:
        client.stop()
    shown = 0
    for j in jobs:
        if not args.all and j.get("state") not in ("RUNNING", "CREATED",
                                                   "RESTARTING"):
            continue
        line = (f"{j['job_id']}  {j.get('state'):<10}  "
                f"restarts={j.get('restarts', 0)}  "
                f"checkpoints={j.get('checkpoints_completed', 0)}  "
                f"{j.get('job_name', '')}")
        if j.get("last_failure"):
            line += f"\n    last failure: {j['last_failure']}"
        print(line)
        shown += 1
    if shown == 0:
        print("(no jobs)" if args.all else
              "(no running jobs; --all includes finished)")
    return 0


def _cancel(rest) -> int:
    """(ref: CliFrontend cancel [-s])"""
    ap = _ops_parser("cancel")
    ap.add_argument("-s", "--with-savepoint", metavar="DIR", default=None,
                    help="take a savepoint before cancelling")
    args = ap.parse_args(rest)
    client = _client(args.master, args.secret, args.tls_dir)
    try:
        if args.with_savepoint:
            path = client.stop_with_savepoint(args.job_id,
                                              args.with_savepoint)
            print(f"savepoint written to {path}")
        else:
            client.cancel(args.job_id)
        print(f"cancelled {args.job_id}")
    finally:
        client.stop()
    return 0


def _savepoint(rest) -> int:
    """(ref: CliFrontend savepoint <job> <dir>)"""
    ap = _ops_parser("savepoint")
    ap.add_argument("directory")
    args = ap.parse_args(rest)
    client = _client(args.master, args.secret, args.tls_dir)
    try:
        path = client.trigger_savepoint(args.job_id, args.directory)
    finally:
        client.stop()
    print(f"savepoint written to {path}")
    return 0


def _stop(rest) -> int:
    """(ref: CliFrontend stop — savepoint then stop; this runtime's
    stop is cancel-with-savepoint, i.e. no drain phase)"""
    ap = _ops_parser("stop")
    ap.add_argument("--savepoint-dir", required=True)
    args = ap.parse_args(rest)
    client = _client(args.master, args.secret, args.tls_dir)
    try:
        path = client.stop_with_savepoint(args.job_id,
                                          args.savepoint_dir)
    finally:
        client.stop()
    print(f"stopped {args.job_id}; savepoint at {path}")
    return 0


def _shell(rest) -> int:
    """Interactive REPL with a preloaded environment (ref:
    flink-scala-shell/.../FlinkShell.scala — a shell wired to a local
    or remote cluster)."""
    import argparse
    import code

    ap = argparse.ArgumentParser(prog="flink_tpu shell")
    ap.add_argument("--master", default=None,
                    help="attach to a running cluster (host:port); "
                         "default: local executor")
    args = ap.parse_args(rest)

    from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    env = StreamExecutionEnvironment()
    if args.master:
        env.use_remote_cluster(args.master)
    import flink_tpu
    namespace = {"env": env, "flink_tpu": flink_tpu}
    banner = (f"flink_tpu {flink_tpu.__version__} shell — "
              f"`env` is a StreamExecutionEnvironment"
              + (f" attached to {args.master}" if args.master
                 else " (local executor)")
              + "\nExample: env.from_collection([1,2,3])"
                ".map(lambda x: x*2).print_(); env.execute()")
    code.interact(banner=banner, local=namespace)
    return 0


def _jobmanager(rest) -> int:
    """Cluster entry point (ref: StandaloneSessionClusterEntrypoint)."""
    import argparse
    import time

    from flink_tpu.runtime.cluster import JobManagerProcess

    ap = argparse.ArgumentParser(prog="flink_tpu jobmanager")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=6123)
    ap.add_argument("--archive-dir", default=None,
                    help="archive finished jobs here (history server)")
    ap.add_argument("--secret", default=None,
                    help="shared cluster secret (rejects unauthenticated "
                         "RPC frames)")
    ap.add_argument("--ha-dir", default=None,
                    help="shared HA directory: leader election + "
                         "submitted-job recovery (standbys campaign)")
    ap.add_argument("--tls-dir", default=None,
                    help="enable mutual TLS on RPC + data planes; "
                         "tls.crt/tls.key in this directory "
                         "(generated self-signed on first use)")
    args = ap.parse_args(rest)
    tls = None
    if args.tls_dir:
        from flink_tpu.runtime.tls import TlsConfig
        tls = TlsConfig.from_dir(args.tls_dir)
    jm = JobManagerProcess(args.host, args.port,
                           archive_dir=args.archive_dir,
                           secret=args.secret, ha_dir=args.ha_dir,
                           tls=tls)
    print(f"jobmanager listening at {jm.address}", flush=True)
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        jm.stop()
    return 0


def _taskmanager(rest) -> int:
    """Worker entry point (ref: TaskManagerRunner main)."""
    import argparse
    import time

    from flink_tpu.runtime.cluster import TaskManagerProcess

    ap = argparse.ArgumentParser(prog="flink_tpu taskmanager")
    ap.add_argument("--master", default=None, help="jobmanager host:port")
    ap.add_argument("--ha-dir", default=None,
                    help="discover (and follow) the leader via the "
                         "shared HA directory instead of --master")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--tm-id", default=None)
    ap.add_argument("--secret", default=None)
    ap.add_argument("--tls-dir", default=None,
                    help="enable mutual TLS (same tls.crt/tls.key as "
                         "the jobmanager)")
    args = ap.parse_args(rest)
    if (args.master is None) == (args.ha_dir is None):
        print("pass exactly one of --master / --ha-dir", file=sys.stderr)
        return 2
    tls = None
    if args.tls_dir:
        from flink_tpu.runtime.tls import TlsConfig
        tls = TlsConfig.from_dir(args.tls_dir, create=False)
    tm = TaskManagerProcess(args.master, args.slots, args.host, args.tm_id,
                            secret=args.secret, ha_dir=args.ha_dir,
                            tls=tls)
    print(f"taskmanager {tm.tm_id} registered with {tm.jm_address} "
          f"(rpc {tm.rpc.address}, data {tm.data_server.address})",
          flush=True)
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        tm.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

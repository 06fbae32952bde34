"""REST monitoring endpoint (ref: flink-runtime rest/RestServerEndpoint
.java + the web monitor handlers — SURVEY.md §2.2 REST row).

A small threaded HTTP server over the live MetricRegistry and job
clients: `/jobs` (status per tracked job), `/jobs/<name>/metrics`
(scoped dump), `/jobs/<name>/metrics/history` (time-series journal
query: `?metric=<glob>&since=<wall ms>&buckets=<n>` with min/max/avg/
p95 rollups), `/jobs/<name>/checkpoints` (full stats history +
summary percentiles), `/jobs/<name>/alerts` (health events),
`/jobs/<name>/device` (device telemetry ledger: transfers, HBM,
per-kernel attribution — runtime/device_stats.py),
`/metrics` (full dump), `/metrics/prometheus` (text exposition via
PrometheusTextReporter).  JSON out, stdlib only.  Errors are JSON
bodies: unknown routes/jobs are 404, malformed query params 400.
"""

from __future__ import annotations

import json
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional

from flink_tpu.runtime.metrics import MetricRegistry, PrometheusTextReporter


class BadRequest(Exception):
    """Malformed query parameters — surfaces as HTTP 400."""


def parse_history_params(query: Dict[str, list]) -> tuple:
    """Validate `/metrics/history` query params into
    (metric_glob, since_ms, buckets); raises BadRequest on garbage.
    Shared by the live WebMonitor and the HistoryServer so the two
    routes cannot diverge."""
    metric = query.get("metric", ["*"])[0]
    if not metric:
        raise BadRequest("empty 'metric' glob")
    since = None
    if "since" in query:
        try:
            since = float(query["since"][0])
        except (ValueError, TypeError):
            raise BadRequest(
                f"malformed 'since' (want wall-clock ms): "
                f"{query['since'][0]!r}") from None
    buckets = None
    if "buckets" in query:
        try:
            buckets = int(query["buckets"][0])
        except (ValueError, TypeError):
            raise BadRequest(
                f"malformed 'buckets' (want int): "
                f"{query['buckets'][0]!r}") from None
        if buckets <= 0:
            raise BadRequest(f"'buckets' must be positive: {buckets}")
    return metric, since, buckets


def parse_bottleneck_params(query: Dict[str, list]) -> tuple:
    """Validate `/bottleneck` query params into (busy_threshold_ms_per_s,
    ratio_threshold); raises BadRequest on garbage.  Shared by the live
    WebMonitor and the HistoryServer so the two routes cannot
    diverge."""
    from flink_tpu.runtime.backpressure import (
        BUSY_SATURATION_MS_PER_S,
        LOW_THRESHOLD,
    )
    busy = BUSY_SATURATION_MS_PER_S
    ratio = LOW_THRESHOLD
    if "busy_threshold" in query:
        try:
            busy = float(query["busy_threshold"][0])
        except (ValueError, TypeError):
            raise BadRequest(
                f"malformed 'busy_threshold' (want ms/s): "
                f"{query['busy_threshold'][0]!r}") from None
    if "ratio_threshold" in query:
        try:
            ratio = float(query["ratio_threshold"][0])
        except (ValueError, TypeError):
            raise BadRequest(
                f"malformed 'ratio_threshold' (want 0..1): "
                f"{query['ratio_threshold'][0]!r}") from None
    return busy, ratio


def parse_flamegraph_params(query: Dict[str, list]) -> tuple:
    """Validate `/flamegraph` query params into (vertex, mode); raises
    BadRequest on garbage.  Shared by the live WebMonitor and the
    HistoryServer so the two routes cannot diverge."""
    from flink_tpu.runtime.profiler import MODES
    vertex = None
    if "vertex" in query:
        vertex = query["vertex"][0]
        if not vertex:
            raise BadRequest("empty 'vertex' filter")
    mode = query.get("mode", ["full"])[0]
    if mode not in MODES:
        raise BadRequest(
            f"unknown 'mode' (want one of {'|'.join(MODES)}): {mode!r}")
    return vertex, mode


def parse_state_params(query: Dict[str, list]) -> Optional[int]:
    """Validate `/jobs/<n>/state` query params into the hot-key list
    cap `top`; raises BadRequest on garbage.  Shared by the live
    WebMonitor and the HistoryServer so the two routes cannot
    diverge."""
    top = None
    if "top" in query:
        try:
            top = int(query["top"][0])
        except (ValueError, TypeError):
            raise BadRequest(
                f"malformed 'top' (want int): "
                f"{query['top'][0]!r}") from None
        if top <= 0:
            raise BadRequest(f"'top' must be positive: {top}")
    return top

#: the dashboard (ref: flink-runtime-web/web-dashboard — scaled to one
#: dependency-free page over the JSON routes below).  Status colors
#: always pair with a glyph + label (never color alone); all text
#: wears ink tokens; the backpressure meter is a single-hue fill with
#: the numeric value printed beside it.
_DASHBOARD_HTML = """<!doctype html>
<html lang="en"><head><meta charset="utf-8">
<title>flink_tpu dashboard</title>
<style>
 :root { --ink:#1a1a19; --ink2:#555550; --muted:#8a8a84;
         --surface:#ffffff; --panel:#f6f6f4; --line:#e3e3df;
         --good:#0ca30c; --warning:#fab219; --serious:#ec835a;
         --critical:#d03b3b; --meter:#4a79c4; }
 @media (prefers-color-scheme: dark) {
   :root { --ink:#f0f0ee; --ink2:#b5b5af; --muted:#80807a;
           --surface:#1a1a19; --panel:#242422; --line:#3a3a37; } }
 body { margin:0; padding:24px; background:var(--surface);
        color:var(--ink);
        font:14px/1.5 system-ui,-apple-system,sans-serif; }
 h1 { font-size:18px; margin:0 0 16px; }
 h2 { font-size:14px; margin:20px 0 8px; color:var(--ink2); }
 .tiles { display:flex; gap:12px; flex-wrap:wrap; }
 .tile { background:var(--panel); border:1px solid var(--line);
         border-radius:8px; padding:12px 18px; min-width:120px; }
 .tile .num { font-size:26px; font-weight:600; }
 .tile .lbl { color:var(--muted); font-size:12px; }
 table { border-collapse:collapse; width:100%; max-width:860px; }
 th { text-align:left; color:var(--muted); font-weight:500;
      font-size:12px; padding:4px 10px 4px 0;
      border-bottom:1px solid var(--line); }
 td { padding:5px 10px 5px 0; border-bottom:1px solid var(--line); }
 .status { font-weight:600; }
 .meter { display:inline-block; width:120px; height:8px;
          background:var(--line); border-radius:4px;
          vertical-align:middle; margin-right:8px; }
 .meter > i { display:block; height:100%; background:var(--meter);
              border-radius:4px; }
 .mono { font-variant-numeric:tabular-nums; }
 footer { margin-top:24px; color:var(--muted); font-size:12px; }
</style></head><body>
<h1>flink_tpu</h1>
<div class="tiles" id="tiles"></div>
<h2>Jobs</h2>
<div id="jobs"></div>
<footer>auto-refreshes every 2 s &middot; JSON at /jobs, /metrics,
/jobs/&lt;name&gt;/detail</footer>
<script>
const STATUS = {
  RUNNING:  {glyph:'\\u25B6', color:'var(--good)'},
  FINISHED: {glyph:'\\u2713', color:'var(--ink2)'},
  FAILED:   {glyph:'\\u2715', color:'var(--critical)'},
  CANCELED: {glyph:'\\u25A0', color:'var(--serious)'},
};
const esc = s => String(s).replace(/[&<>]/g,
  c => ({'&':'&amp;','<':'&lt;','>':'&gt;'}[c]));
function badge(st) {
  const s = STATUS[st] || {glyph:'?', color:'var(--muted)'};
  return `<span class="status" style="color:${s.color}">` +
         `${s.glyph} ${esc(st)}</span>`;
}
async function j(path) { return (await fetch(path)).json(); }
async function refresh() {
  try {
    const jobs = await j('/jobs');
    const names = Object.keys(jobs);
    const metrics = await j('/metrics');
    const detailList = await Promise.all(names.map(n =>
      j('/jobs/' + encodeURIComponent(n) + '/detail')
        .catch(() => jobs[n])));
    const details = Object.fromEntries(
      names.map((n, i) => [n, detailList[i]]));
    const running = names.filter(n => jobs[n].status === 'RUNNING');
    const cps = names.reduce((a, n) =>
      a + ((details[n].checkpoints || {}).completed || 0), 0);
    document.getElementById('tiles').innerHTML = [
      [names.length, 'jobs'], [running.length, 'running'],
      [cps, 'checkpoints'], [Object.keys(metrics).length, 'metrics'],
    ].map(([n, l]) =>
      `<div class="tile"><div class="num mono">${n}</div>` +
      `<div class="lbl">${l}</div></div>`).join('');
    document.getElementById('jobs').innerHTML = names.map(n => {
      const d = details[n];
      const verts = (d.vertices || []).map(v => {
        const bp = (d.backpressure || {})[String(v.id)] || {};
        const r = bp.max_ratio ?? null;
        const meter = r === null ? '' :
          `<span class="meter"><i style="width:${Math.round(r*100)}%">` +
          `</i></span><span class="mono">${(r*100).toFixed(0)}%` +
          `${bp.level ? ' (' + esc(bp.level) + ')' : ''}</span>`;
        return `<tr><td class="mono">${v.id}</td>` +
               `<td>${esc(v.name)}</td>` +
               `<td class="mono">${v.parallelism}</td>` +
               `<td>${meter}</td></tr>`;
      }).join('');
      const recent = ((d.checkpoints || {}).recent || []).slice(-5)
        .map(c => `#${c.id} ${c.duration_ms ?? '?'} ms ` +
                  `${(c.bytes / 1024).toFixed(0)} KiB`)
        .join(' &middot; ');
      return `<h2>${esc(n)} ${badge(d.status)}</h2>` +
        `<table><tr><th>id</th><th>vertex</th><th>par</th>` +
        `<th>backpressure</th></tr>${verts}</table>` +
        `<p class="mono" style="color:var(--ink2)">checkpoints: ` +
        `${(d.checkpoints || {}).completed ?? 0}` +
        `${recent ? ' &middot; recent: ' + recent : ''}</p>`;
    }).join('') || '<p style="color:var(--muted)">no tracked jobs</p>';
  } catch (e) { /* monitor restarting; retry next tick */ }
}
refresh();
setInterval(refresh, 2000);
</script></body></html>
"""


class WebMonitor:
    def __init__(self, registry: MetricRegistry, port: int = 0):
        self.registry = registry
        self.prometheus = PrometheusTextReporter()
        self.prometheus.open(registry)  # HELP texts from descriptions
        #: job name -> JobClient
        self.jobs: Dict[str, object] = {}
        monitor = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def do_GET(self):
                status = 200
                try:
                    body, ctype = monitor._route(self.path)
                except KeyError as e:
                    status = 404
                    body = {"error": f"not found: {e.args[0] if e.args else self.path}"}
                    ctype = "application/json"
                except BadRequest as e:
                    status = 400
                    body = {"error": str(e)}
                    ctype = "application/json"
                payload = (body if isinstance(body, (bytes, str))
                           else json.dumps(body, default=str))
                if isinstance(payload, str):
                    payload = payload.encode()
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

        self._server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._server.server_address[1]
        self._thread: Optional[threading.Thread] = None

    # ---- lifecycle ---------------------------------------------------
    def start(self) -> "WebMonitor":
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True, name="web-monitor")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()

    def track_job(self, name: str, client) -> None:
        self.jobs[name] = client

    # ---- routing -----------------------------------------------------
    def _route(self, raw_path: str):
        # split the query string off BEFORE dispatch — the suffix
        # matches below must see the bare path
        split = urllib.parse.urlsplit(raw_path)
        path = split.path
        # keep blanks: `?metric=` must surface as an empty glob (400),
        # not silently fall back to the `*` default
        query = urllib.parse.parse_qs(split.query, keep_blank_values=True)
        if path == "/web":
            return _DASHBOARD_HTML, "text/html; charset=utf-8"
        if path in ("/", "/overview"):
            return {"jobs": len(self.jobs),
                    "metrics": len(self.registry.dump())}, "application/json"
        if path == "/jobs":
            return {name: self._job_status(c)
                    for name, c in self.jobs.items()}, "application/json"
        if path == "/metrics":
            return self.registry.dump(), "application/json"
        if path == "/metrics/prometheus":
            self.prometheus.report(self.registry.dump())
            return self.prometheus.render(), "text/plain; version=0.0.4"
        if path.startswith("/jobs/") and path.endswith("/backpressure"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/backpressure")])
            if job not in self.jobs:
                raise KeyError(path)
            # served from the registry's time-aware sticky-window
            # gauges: reading them never blocks the handler (the
            # active 20-sample sampler stays CLI-only)
            from flink_tpu.runtime.backpressure import (
                read_backpressure_gauges,
            )
            stats = read_backpressure_gauges(self.registry.dump(), job)
            return ({str(vid): s for vid, s in stats.items()},
                    "application/json")
        if path.startswith("/jobs/") and path.endswith("/detail"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/detail")])
            if job not in self.jobs:
                raise KeyError(path)
            return self._job_detail(job), "application/json"
        if path.startswith("/jobs/") and path.endswith("/traces"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/traces")])
            if job not in self.jobs:
                raise KeyError(path)
            from flink_tpu.runtime.tracing import (
                build_cluster_trace,
                get_tracer,
            )
            tracer = get_tracer()
            scope = query.get("scope", ["process"])[0]
            if scope == "cluster":
                # one process lane per worker, clock offsets applied
                # (zero for in-process workers sharing this tracer)
                state = (getattr(self.jobs[job], "executor_state", None)
                         or {})
                offsets = state.get("clock_offsets") or {}
                return ({"enabled": tracer.enabled, "scope": "cluster",
                         "trace": build_cluster_trace(
                             tracer.lane_buffers(), offsets)},
                        "application/json")
            if scope != "process":
                raise BadRequest(
                    f"unknown 'scope' (want process|cluster): {scope!r}")
            # the tracer is process-global: spans are not partitioned
            # per job, so this surfaces the recent window + aggregates
            # while the named job is tracked
            return ({"enabled": tracer.enabled,
                     "spans": tracer.recent(200),
                     "stats": tracer.stats(),
                     "periods": tracer.periods(),
                     "dropped_periods": tracer.dropped_periods},
                    "application/json")
        if path.startswith("/jobs/") and path.endswith("/bottleneck"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/bottleneck")])
            if job not in self.jobs:
                raise KeyError(path)
            return self._job_bottleneck(job, query), "application/json"
        if path.startswith("/jobs/") and path.endswith("/metrics/history"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/metrics/history")])
            if job not in self.jobs:
                raise KeyError(path)
            metric, since, buckets = parse_history_params(query)
            journal = (getattr(self.jobs[job], "executor_state", None)
                       or {}).get("journal")
            if journal is None:
                return {"metric": metric, "since": since,
                        "sample_interval_ms": None,
                        "sampling_disabled": True,
                        "series": {}}, "application/json"
            return journal.query(metric, since, buckets), "application/json"
        if path.startswith("/jobs/") and path.endswith("/checkpoints"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/checkpoints")])
            if job not in self.jobs:
                raise KeyError(path)
            return self._job_checkpoints(self.jobs[job]), "application/json"
        if path.startswith("/jobs/") and path.endswith("/alerts"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/alerts")])
            if job not in self.jobs:
                raise KeyError(path)
            return self._job_alerts(self.jobs[job]), "application/json"
        if path.startswith("/jobs/") and path.endswith("/device"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/device")])
            if job not in self.jobs:
                raise KeyError(path)
            # the ledger is process-global (like the tracer): one
            # device plane per host, surfaced while the job is tracked
            from flink_tpu.runtime.device_stats import get_telemetry
            return get_telemetry().payload(), "application/json"
        if path.startswith("/jobs/") and path.endswith("/state"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/state")])
            if job not in self.jobs:
                raise KeyError(path)
            top = parse_state_params(query)
            # the introspection plane is process-global (like the
            # device ledger): per-state per-key-group accounting, hot
            # keys and the skew verdict, surfaced while the job is
            # tracked; {"enabled": false, ...} while disabled
            from flink_tpu.state.introspect import get_introspection
            return get_introspection().payload(top=top), "application/json"
        if path.startswith("/jobs/") and path.endswith("/flamegraph"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/flamegraph")])
            if job not in self.jobs:
                raise KeyError(path)
            vertex, mode = parse_flamegraph_params(query)
            # the profiler is process-global (like the tracer); the
            # d3 tree is built by the same function the HistoryServer
            # twin uses, from the same export shape that archives
            from flink_tpu.runtime.profiler import (
                flamegraph_payload,
                get_profiler,
            )
            return (flamegraph_payload(get_profiler().export(job=job),
                                       job, vertex=vertex, mode=mode),
                    "application/json")
        if path.startswith("/jobs/") and path.endswith("/metrics"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/metrics")])
            dump = {k: v for k, v in self.registry.dump().items()
                    if k.startswith(job + ".")}
            if not dump and job not in self.jobs:
                raise KeyError(path)
            return dump, "application/json"
        if path.startswith("/jobs/") and path.endswith("/exceptions"):
            job = urllib.parse.unquote(
                path[len("/jobs/"):-len("/exceptions")])
            if job not in self.jobs:
                raise KeyError(path)
            return self._job_exceptions(self.jobs[job]), "application/json"
        if path.startswith("/jobs/"):
            job = urllib.parse.unquote(path[len("/jobs/"):])
            if job not in self.jobs:
                raise KeyError(path)
            return self._job_status(self.jobs[job]), "application/json"
        raise KeyError(path)

    @staticmethod
    def _job_checkpoints(client) -> dict:
        """Full retained checkpoint history + percentile summary (ref:
        CheckpointingStatistics behind /jobs/:jobid/checkpoints)."""
        from flink_tpu.runtime.checkpoints import checkpoint_stats_payload
        state = getattr(client, "executor_state", None) or {}
        coordinator = state.get("coordinator")
        base = state.get("checkpoints_base", 0)
        if coordinator is None:
            return {"counts": {"completed": base, "failed": 0,
                               "aborted": 0, "timeout_aborts": 0,
                               "in_progress": 0},
                    "latest_completed_id": None,
                    "summary": {"count": 0},
                    "history": []}
        return checkpoint_stats_payload(coordinator, base)

    @staticmethod
    def _job_alerts(client) -> dict:
        """Structured health alerts (the ROADMAP-3 autoscaler's
        trigger feed)."""
        state = getattr(client, "executor_state", None) or {}
        evaluator = state.get("health")
        if evaluator is None:
            return {"alerts": [], "total": 0, "rules_firing": []}
        return {"alerts": evaluator.snapshot_alerts(),
                "total": evaluator.alerts_total,
                "rules_firing": evaluator.active_rules}

    @staticmethod
    def _job_exceptions(client) -> dict:
        """Last failure cause plus the per-attempt failure history (ref:
        JobExceptionsHandler behind /jobs/:jobid/exceptions)."""
        history = list(getattr(client, "exception_history", None) or [])
        result = getattr(client, "_result", None)
        restarts = getattr(result, "restarts", None)
        if restarts is None and history:
            restarts = history[-1]["attempt"]
        payload: dict = {"restarts": restarts or 0, "history": history}
        if history:
            payload["last_failure"] = history[-1]["exception"]
        err = getattr(client, "_error", None)
        if err is not None:
            payload["root_exception"] = f"{type(err).__name__}: {err}"
        return payload

    def _job_detail(self, name: str) -> dict:
        """Vertices, checkpoint stats, and backpressure for one job —
        the data the dashboard page renders (ref: the job-detail
        handlers behind flink-runtime-web)."""
        client = self.jobs[name]
        detail = dict(self._job_status(client))
        state = getattr(client, "executor_state", None) or {}
        subtasks = state.get("subtasks") or {}
        vertices = []
        for vid, sts in sorted(subtasks.items()):
            v = getattr(sts[0], "vertex", None) if sts else None
            chain = getattr(v, "chain", None)
            vertices.append({
                "id": vid,
                "name": " -> ".join(n.name for n in chain)
                if chain else f"vertex-{vid}",
                "parallelism": len(sts),
            })
        detail["vertices"] = vertices
        coordinator = state.get("coordinator")
        cps = {"completed": state.get("checkpoints_base", 0),
               "recent": []}
        if coordinator is not None:
            cps["completed"] += getattr(coordinator, "completed_count", 0)
            stats = getattr(coordinator, "stats", {}) or {}
            for cid in sorted(stats)[-10:]:
                st = stats[cid]
                cps["recent"].append({
                    "id": st.checkpoint_id,
                    "duration_ms": (
                        round(st.complete_ms - st.trigger_ms, 1)
                        if st.complete_ms is not None else None),
                    "bytes": st.state_bytes,
                })
        detail["checkpoints"] = cps
        try:
            from flink_tpu.runtime.backpressure import (
                read_backpressure_gauges,
            )
            detail["backpressure"] = {
                str(vid): s for vid, s in read_backpressure_gauges(
                    self.registry.dump(), name).items()}
        except Exception:  # noqa: BLE001 — job may be terminal
            detail["backpressure"] = {}
        return detail

    def _job_bottleneck(self, name: str, query: Dict[str, list]) -> dict:
        """Downstream-first bottleneck localization over the live
        registry: the most-downstream busy-saturated vertex whose
        upstreams are backpressured.  Thresholds are overridable via
        `?busy_threshold=<ms/s>&ratio_threshold=<0..1>`."""
        from flink_tpu.runtime.backpressure import (
            locate_bottleneck,
            read_vertex_stats,
        )
        busy, ratio = parse_bottleneck_params(query)
        client = self.jobs[name]
        state = getattr(client, "executor_state", None) or {}
        located = locate_bottleneck(
            state.get("upstreams") or {},
            read_vertex_stats(self.registry.dump(), name),
            busy_threshold=busy, ratio_threshold=ratio)
        return {"bottleneck": located,
                "busy_threshold_ms_per_s": busy,
                "ratio_threshold": ratio}

    @staticmethod
    def _job_status(client) -> dict:
        done = getattr(client, "done", None)
        status = "RUNNING"
        if done:
            status = "FINISHED"
            if getattr(client, "_error", None) is not None:
                status = "FAILED"
            elif getattr(client, "_result", None) is not None and \
                    getattr(client._result, "cancelled", False):
                status = "CANCELED"
        return {"status": status}

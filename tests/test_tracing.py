"""Tracing & kernel profiling subsystem (runtime/tracing.py): span
semantics, Chrome trace-event export, near-zero disabled overhead, and
the end-to-end MiniCluster acceptance path (operator/native/checkpoint
spans + Prometheus watermark-lag/kernel metrics + jit recompile
counts in the registry dump)."""

import gc
import json
import time
import urllib.request

import numpy as np
import pytest

from flink_tpu.runtime import tracing
from flink_tpu.runtime.tracing import Tracer, get_tracer
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.sources import CollectSink
from flink_tpu.streaming.windowing import Time, TumblingEventTimeWindows


@pytest.fixture(autouse=True)
def _tracer_off_after():
    """Tests toggle the process-global tracer; always restore."""
    yield
    tr = get_tracer()
    tr.enabled = False
    tr.reset()


from flink_tpu.ops.device_agg import AvgAggregate, SumAggregate  # noqa: E402
from flink_tpu.ops.sketches import HyperLogLogAggregate  # noqa: E402


class TupleSum(SumAggregate):
    def __init__(self):
        super().__init__(np.float32)

    def extract_value(self, value):
        return value[1]


class TupleAvg(AvgAggregate):
    def extract_value(self, value):
        return value[1]


def _run_window_job(env, n=4000, agg=None, name="trace-job",
                    until_checkpoint=False):
    """``until_checkpoint``: the source holds its tail back until a
    checkpoint has COMPLETED (runtime/chaos.py's gate), so a test that
    reads checkpoint spans does not depend on the 4,000 records
    outlasting the checkpoint timer."""
    sink = CollectSink()
    recs = [((i % 7, 1.0), i * 10) for i in range(n)]
    if until_checkpoint:
        from flink_tpu.runtime.chaos import CheckpointGatedSource
        CheckpointGatedSource.completed = False
        stream = env.add_source(
            CheckpointGatedSource(recs, timestamped=True))
    else:
        stream = env.from_collection(recs, timestamped=True)
    (stream
        .key_by(lambda t: t[0])
        .window(TumblingEventTimeWindows.of(Time.seconds(1)))
        .aggregate(agg or TupleSum(),
                   window_function=lambda k, w, els: [(k, float(els[0]))])
        .add_sink(sink))
    env.execute(name)
    return sink


# ---------------------------------------------------------------------
# span semantics
# ---------------------------------------------------------------------

def test_nested_spans_parent_child_and_self_time():
    tr = Tracer()
    tr.enabled = True
    with tr.span("outer", job="j"):
        time.sleep(0.02)
        with tr.span("inner"):
            time.sleep(0.01)
    events = tr.recent()
    by_name = {e["name"]: e for e in events}
    assert by_name["inner"]["parent"] == "outer"
    assert "parent" not in by_name["outer"]
    assert by_name["outer"]["args"] == {"job": "j"}
    # inner nests fully inside outer on the time axis
    assert by_name["outer"]["ts"] <= by_name["inner"]["ts"]
    assert (by_name["inner"]["ts"] + by_name["inner"]["dur"]
            <= by_name["outer"]["ts"] + by_name["outer"]["dur"] + 1)

    stats = tr.stats()
    assert stats["outer"]["count"] == 1
    assert stats["inner"]["count"] == 1
    # self time excludes the child: outer slept ~20ms itself of ~30ms
    assert stats["outer"]["self_ms"] < stats["outer"]["total_ms"]
    assert stats["outer"]["self_ms"] == pytest.approx(
        stats["outer"]["total_ms"] - stats["inner"]["total_ms"], abs=1.0)
    assert stats["inner"]["self_ms"] == pytest.approx(
        stats["inner"]["total_ms"], abs=0.5)
    assert stats["outer"]["p99_ms"] >= stats["outer"]["p50_ms"] > 0


def test_disabled_tracer_records_nothing():
    tr = Tracer()
    with tr.span("ghost", attr=1):
        pass
    assert tr.recent() == []
    assert tr.stats() == {}


def test_chrome_trace_schema(tmp_path):
    """Every exported event carries the trace-event required keys."""
    env = StreamExecutionEnvironment()
    env.enable_tracing()
    _run_window_job(env, n=2000, name="chrome-schema")
    path = tmp_path / "trace.json"
    n = env.get_tracer().write_chrome_trace(str(path))
    assert n > 0
    with open(path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    assert len(events) == n
    for e in events:
        for key in ("ph", "ts", "pid", "tid", "name"):
            assert key in e, f"missing {key} in {e}"
        if e["ph"] == "X":
            assert "dur" in e and e["dur"] >= 0


def test_disabled_tracer_overhead_under_5_percent():
    """100k disabled span() calls (one per record is the hot-path
    instrumentation rate) must cost < 5% of the 100k-record window
    job they'd piggyback on.  min-of-3 damps scheduler noise."""
    n = 100_000
    env = StreamExecutionEnvironment()
    t0 = time.perf_counter()
    _run_window_job(env, n=n, name="overhead-baseline")
    job_s = time.perf_counter() - t0

    tr = Tracer()
    assert not tr.enabled
    overhead_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("x"):
                pass
        overhead_s = min(overhead_s, time.perf_counter() - t0)
    assert overhead_s < 0.05 * job_s, (
        f"disabled tracer: {overhead_s * 1e3:.1f}ms for {n} spans vs "
        f"{job_s * 1e3:.0f}ms job ({overhead_s / job_s:.1%})")


# ---------------------------------------------------------------------
# jit / kernel / compile accounting
# ---------------------------------------------------------------------

def test_traced_jit_counts_compiles_and_hits():
    import jax.numpy as jnp
    tracing.reset_jit_stats()
    f = tracing.traced_jit(lambda x: x + 1, name="test.add_one")
    f(jnp.ones(4, jnp.float32))
    f(jnp.ones(4, jnp.float32))
    f(jnp.ones(8, jnp.float32))  # new shape -> recompile
    stats = tracing.jit_stats()["test.add_one"]
    assert stats["recompiles"] == 2
    assert stats["cache_hits"] == 1
    assert stats["compile_time_ms"] > 0
    # a reset zeroes the books in place: the wrapper made before it
    # holds the same stat object and shows again with its next call
    tracing.reset_jit_stats()
    assert tracing.jit_stats()["test.add_one"] == {
        "recompiles": 0, "compile_time_ms": 0.0, "cache_hits": 0,
        "shape_variants": 0, "last_shape_sig": ""}
    f(jnp.ones(8, jnp.float32))
    f(jnp.ones(16, jnp.float32))
    stats = tracing.jit_stats()["test.add_one"]
    assert (stats["cache_hits"], stats["recompiles"]) == (1, 1)
    assert stats["last_shape_sig"] == "(float32[16])"


def test_record_compile_event_and_kernel_stats_reach_registry():
    from flink_tpu.runtime.metrics import MetricRegistry
    tracing.record_compile_event("test.compiler", 0.004)
    tracing.record_kernel("test_kernel", 0, 2_000_000)  # 2ms
    registry = MetricRegistry()
    tracing.register_runtime_profile_gauges(registry)
    dump = registry.dump()
    assert dump["jit.test.compiler.recompiles"] >= 1
    assert dump["native.test_kernel.dispatches"] >= 1
    assert dump["native.test_kernel.totalMs"] >= 2.0
    # names first seen AFTER registration back-fill into the registry
    tracing.record_kernel("late_kernel", 0, 1_000_000)
    assert registry.dump()["native.late_kernel.dispatches"] >= 1


def test_scatter_tier_jit_recompiles_in_registry_dump():
    """The acceptance hook: a windowed-aggregate job on the jitted
    scatter tier leaves recompile counts in registry.dump()."""
    env = StreamExecutionEnvironment()
    sink = _run_window_job(env, n=3000, agg=TupleAvg(), name="jit-dump")
    assert sink.values
    dump = env.get_metric_registry().dump()
    assert dump["jit.window.masked_update.recompiles"] >= 1
    assert dump["jit.window.masked_update.compileTimeMs"] > 0


# ---------------------------------------------------------------------
# acceptance: MiniCluster + Chrome trace + Prometheus + REST
# ---------------------------------------------------------------------

def _http_get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.read().decode(), r.headers.get("Content-Type", "")


def test_minicluster_trace_prometheus_and_rest(tmp_path):
    import flink_tpu.native as nat
    from flink_tpu.runtime.rest import WebMonitor

    env = StreamExecutionEnvironment()
    env.use_mini_cluster(2)
    env.enable_checkpointing(20)
    env.enable_tracing()
    sink = _run_window_job(env, n=4000, name="accept-trace",
                           until_checkpoint=True)
    assert sink.values

    # ---- Chrome trace: operator + checkpoint (+ native) spans ------
    tracer = env.get_tracer()
    path = tmp_path / "accept_trace.json"
    assert tracer.write_chrome_trace(str(path)) > 0
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    names = {e["name"] for e in events}
    assert any(n.startswith("op.") for n in names), names
    assert "checkpoint.barrier" in names
    if nat.available():
        assert any(n.startswith("native.") for n in names), names
    for e in events:
        assert {"ph", "ts", "pid", "tid", "name"} <= set(e)

    # ---- Prometheus: watermark lag + per-kernel dispatches ---------
    registry = env.get_metric_registry()
    monitor = WebMonitor(registry).start()
    try:
        monitor.track_job("accept-trace", type("C", (), {
            "executor_state": None, "wait": lambda *a, **k: None})())
        text, ctype = _http_get(monitor.port, "/metrics/prometheus")
        assert "text/plain" in ctype
        assert "# TYPE" in text
        assert "watermarkLag" in text
        lag_values = [float(line.split()[-1])
                      for line in text.splitlines()
                      if not line.startswith("#") and "watermarkLag" in line]
        assert lag_values and all(v >= 0.0 for v in lag_values)
        if nat.available():
            assert "flink_tpu_native_" in text and "_dispatches" in text
        # backpressure classification published as gauges
        dump = registry.dump()
        bp = {k: v for k, v in dump.items() if ".backpressure." in k}
        assert bp and any(k.endswith(".level") for k in bp)
        assert all(v in ("ok", "low", "high") for k, v in bp.items()
                   if k.endswith(".level"))

        # ---- REST /jobs/<name>/traces ------------------------------
        body, ctype = _http_get(monitor.port, "/jobs/accept-trace/traces")
        assert "json" in ctype
        payload = json.loads(body)
        assert payload["enabled"] is True
        assert payload["spans"] and payload["stats"]
        assert any(s["name"].startswith("op.") for s in payload["spans"])
        # the history of fire periods rides along
        assert payload["periods"] and payload["dropped_periods"] == 0
        assert {"seq", "start_s", "end_s", "operator", "watermark",
                "windows", "keys", "newest_window_end", "phases", "gc",
                "kernels"} <= set(payload["periods"][0])
    finally:
        monitor.stop()


# ---------------------------------------------------------------------
# cluster-causal tracing: ring drops, clock alignment, merged lanes,
# barrier trace-context propagation
# ---------------------------------------------------------------------

def test_ring_overflow_counts_drops_and_annotates_export():
    tr = Tracer(max_events=8)
    tr.enabled = True
    for _ in range(20):
        with tr.span("s"):
            pass
    assert tr.dropped == 12
    trace = tr.chrome_trace()
    assert len(trace["traceEvents"]) == 8
    meta = trace["metadata"]
    assert meta["dropped_events"] == 12
    assert "12 oldest events" in meta["warning"]
    assert "8-event ring limit" in meta["warning"]
    tr.reset()
    assert tr.dropped == 0
    assert "metadata" not in tr.chrome_trace()


def test_dropped_counter_reaches_registry_gauge():
    from flink_tpu.runtime.metrics import MetricRegistry
    old = get_tracer()
    tr = tracing.set_tracer(Tracer(max_events=4))
    try:
        tr.enabled = True
        registry = MetricRegistry()
        tracing.register_runtime_profile_gauges(registry)
        assert registry.dump()["tracing.dropped"] == 0
        for _ in range(10):
            with tr.span("x"):
                pass
        assert registry.dump()["tracing.dropped"] == 6
    finally:
        tracing.set_tracer(old)


def test_clock_offset_min_rtt_midpoint():
    # a remote whose wall clock runs 5 s ahead: the estimate recovers
    # the skew to well within the local probe's round-trip time
    est = tracing.estimate_clock_offset(
        lambda: (time.time() + 5.0) * 1e6, samples=4)
    assert est["offset_us"] == pytest.approx(5_000_000.0, abs=100_000)
    assert est["rtt_us"] >= 0.0


def test_export_since_incremental_cursor_and_lane_filter():
    tr = Tracer()
    tr.enabled = True
    tr.set_lane("tm-0")
    with tr.span("first"):
        pass
    out1 = tr.export_since(0, lane="tm-0")
    assert [e["name"] for e in out1["events"]] == ["first"]
    assert {"perf_us", "wall_us"} <= set(out1["anchor"])
    with tr.span("second"):
        pass
    out2 = tr.export_since(out1["seq"], lane="tm-0")
    assert [e["name"] for e in out2["events"]] == ["second"]
    # other lanes' events never ship under this lane's cursor
    tr.set_lane("tm-1")
    with tr.span("third"):
        pass
    assert tr.export_since(out2["seq"], lane="tm-0")["events"] == []


def test_build_cluster_trace_aligns_lanes_and_rewrites_pids():
    anchor = {"perf_us": 0.0, "wall_us": 1_000_000.0}
    buffers = {
        "tm-0": {"anchor": anchor, "events": [
            {"name": "a", "ph": "X", "ts": 100.0, "dur": 5.0,
             "pid": 999, "tid": 1, "seq": 3}]},
        "tm-1": {"anchor": anchor, "events": [
            {"name": "b", "ph": "X", "ts": 100.0, "dur": 5.0,
             "pid": 999, "tid": 2, "seq": 4}]},
    }
    # tm-1's host clock runs 40 µs ahead: subtracting its offset puts
    # its identically-stamped event 40 µs BEFORE tm-0's
    merged = tracing.build_cluster_trace(buffers, offsets={"tm-1": 40.0})
    lanes = merged["metadata"]["lanes"]
    assert lanes["tm-0"]["pid"] == 1 and lanes["tm-1"]["pid"] == 2
    assert lanes["tm-1"]["offset_us"] == 40.0
    names = [e["args"]["name"] for e in merged["traceEvents"]
             if e["ph"] == "M"]
    assert names == ["tm-0", "tm-1"]          # one process lane each
    spans = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in spans] == ["b", "a"]
    assert spans[0]["ts"] == 0.0              # normalized to t=0
    assert spans[1]["ts"] == pytest.approx(40.0)
    assert spans[0]["pid"] == 2 and spans[1]["pid"] == 1
    assert all("seq" not in e for e in spans)


def test_barrier_trace_context_causal_tree_across_lanes():
    """One barrier's life — coordinator trigger → per-subtask barrier
    spans → acks → complete — shares one trace_id, every child points
    at the trigger's span_id, and the barrier spans land in BOTH
    worker lanes (subtask i of every vertex runs on TM i mod N)."""
    env = StreamExecutionEnvironment()
    env.set_parallelism(2)
    env.use_mini_cluster(2)
    env.enable_checkpointing(20)
    env.enable_tracing()
    _run_window_job(env, n=4000, name="causal-trace")

    tracer = env.get_tracer()
    events = tracer.recent(limit=tracer.max_events)

    def args(e):
        return e.get("args") or {}

    triggers = {args(e)["trace_id"]: args(e)["span_id"]
                for e in events if e["name"] == "checkpoint.trigger"}
    assert triggers, "no checkpoint.trigger instants recorded"
    for tid, sid in triggers.items():
        linked = {}
        for e in events:
            a = args(e)
            if a.get("trace_id") == tid and a.get("parent_span_id") == sid:
                linked.setdefault(e["name"], []).append(e)
        if {"checkpoint.barrier", "checkpoint.ack",
                "checkpoint.complete"} <= set(linked):
            lanes = {e.get("lane") for e in linked["checkpoint.barrier"]}
            assert len(lanes) >= 2, lanes
            break
    else:
        raise AssertionError(
            "no barrier with trigger->barrier->ack->complete links")


def test_minicluster_cluster_scope_merged_trace_rest():
    """`/jobs/<n>/traces?scope=cluster` serves ONE merged Chrome trace
    with a process lane per worker, timestamps aligned, normalized to
    t=0, and sorted; the default process scope keeps its shape."""
    import urllib.error

    from flink_tpu.runtime.rest import WebMonitor

    env = StreamExecutionEnvironment()
    env.set_parallelism(2)
    env.use_mini_cluster(2)
    env.enable_tracing()
    sink = _run_window_job(env, n=4000, name="cluster-scope")
    assert sink.values

    monitor = WebMonitor(env.get_metric_registry()).start()
    try:
        monitor.track_job("cluster-scope", type("C", (), {
            "executor_state": None, "wait": lambda *a, **k: None})())
        body, _ = _http_get(monitor.port,
                            "/jobs/cluster-scope/traces?scope=cluster")
        payload = json.loads(body)
        assert payload["enabled"] is True
        assert payload["scope"] == "cluster"
        trace = payload["trace"]
        lanes = trace["metadata"]["lanes"]
        assert sum(1 for l in lanes if l.startswith("tm-")) >= 2, lanes
        meta_events = [e for e in trace["traceEvents"] if e["ph"] == "M"]
        assert {e["args"]["name"] for e in meta_events} == set(lanes)
        spans = [e for e in trace["traceEvents"] if e["ph"] != "M"]
        assert spans
        ts = [e["ts"] for e in spans]
        assert ts == sorted(ts) and ts[0] == 0.0
        worker_pids = {lanes[l]["pid"] for l in lanes
                       if l.startswith("tm-")}
        assert worker_pids <= {e["pid"] for e in spans}
        # the default process scope is unchanged
        body, _ = _http_get(monitor.port, "/jobs/cluster-scope/traces")
        assert {"enabled", "spans", "stats"} <= set(json.loads(body))
        # unknown scope is a 400, not a silent default
        try:
            _http_get(monitor.port,
                      "/jobs/cluster-scope/traces?scope=bogus")
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
    finally:
        monitor.stop()


def test_minicluster_latency_markers_smoke():
    """LatencyMarker flow populates latency.* histograms under the
    MiniCluster executor too (cached histogram path: key_by breaks the
    chain so markers cross a subtask edge)."""
    env = StreamExecutionEnvironment()
    env.use_mini_cluster(2)
    env.set_latency_tracking_interval(0)  # every executor loop pass
    sink = _run_window_job(env, n=4000, name="latency-smoke-mini")
    assert sink.values
    dump = env.get_metric_registry().dump()
    lat = {k: v for k, v in dump.items() if ".latency." in k}
    assert lat, f"no latency histograms in {list(dump)[:20]}"
    h = next(iter(lat.values()))
    assert h["count"] >= 1
    assert h["p99"] >= 0


# ---------------------------------------------------------------------
# phases: always-on batch-level spans, also profiler annotations
# ---------------------------------------------------------------------

def test_phase_feeds_stats_with_the_tracer_off_and_the_ring_only_when_on():
    tr = Tracer()
    assert not tr.enabled
    with tr.phase("batch.step", rows=7):
        pass
    assert tr.recent() == []
    assert tr.stats()["batch.step"]["count"] == 1
    tr.enabled = True
    with tr.phase("batch.step", rows=9):
        pass
    assert tr.stats()["batch.step"]["count"] == 2
    [event] = tr.recent()
    assert event["name"] == "batch.step" and event["ph"] == "X"
    assert event["args"] == {"rows": 9}


def test_nested_phases_give_parent_and_self_time():
    tr = Tracer()
    with tr.phase("fire"):
        time.sleep(0.02)
        with tr.phase("fire.emit"):
            time.sleep(0.01)
    stats = tr.stats()
    assert stats["fire"]["self_ms"] == pytest.approx(
        stats["fire"]["total_ms"] - stats["fire.emit"]["total_ms"], abs=1.0)
    assert stats["fire.emit"]["self_ms"] == pytest.approx(
        stats["fire.emit"]["total_ms"], abs=0.5)
    assert stats["fire"]["total_ms"] >= 28
    # a gated span nests under a phase like under any span
    tr.enabled = True
    with tr.phase("fire"):
        with tr.span("record"):
            pass
    assert [e.get("parent") for e in tr.recent()] == ["fire", None]


def test_phase_is_a_profiler_annotation_under_the_one_prefix(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with Tracer().phase("window.ingest", rows=3):
            with tracing.phase_annotation("native.some_kernel"):
                pass
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = [e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(tracing.PHASE_PREFIX)]
    by_name = {e.name: e for e in events}
    assert set(by_name) == {"flink/window.ingest",
                            "flink/native.some_kernel"}
    assert dict(by_name["flink/window.ingest"].stats)["rows"] == 3


def test_an_attribute_set_inside_a_phase_is_on_the_profiler_event_too(
        tmp_path):
    """A count the phase's own loop arrives at (the fire's
    ``fire_rows_direct``): ``set_attr`` inside the ``with`` puts it
    beside the attributes given at entry, in the ring and the trace."""
    import glob

    import jax
    from jax.profiler import ProfileData
    tr = Tracer()
    tr.enabled = True
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tr.phase("window.fire.batch", keys=7) as phase:
            phase.set_attr("fire_rows_direct", 21)
    finally:
        jax.profiler.stop_trace()
    assert tr.recent()[-1]["args"] == {"keys": 7, "fire_rows_direct": 21}
    [path] = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    [event] = [e for plane in ProfileData.from_file(path).planes
               for line in plane.lines for e in line.events
               if e.name == "flink/window.fire.batch"]
    stats = dict(event.stats)
    assert (stats["keys"], stats["fire_rows_direct"]) == (7, 21)


def test_inert_phase_costs_under_5_microseconds():
    tr = Tracer()
    n = 100_000
    best = float("inf")
    for _ in range(5):  # the best of five damps a busy host
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.phase("x"):
                pass
        best = min(best, time.perf_counter() - t0)
    assert best / n < 5e-6, f"{best / n * 1e6:.2f} us per inert phase"


def test_traced_jit_names_the_program_after_its_label():
    import jax.numpy as jnp
    f = tracing.traced_jit(lambda st, idx: st[idx], name="test.gather")
    lowered = f._jitted.lower(jnp.ones(8), jnp.arange(3))
    text = lowered.as_text(debug_info=True)
    assert "jit_test_gather" in text
    assert "test.gather" in text  # the named_scope around the body


class UserHll(HyperLogLogAggregate):
    """COUNT DISTINCT over field 1 of a (key, user) row."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def _phase_data(rows, batches=8, per_window=4):
    """``batches`` batches of ``rows`` rows; batch b lies wholly in
    the 1 s window b // per_window, so the batches, the windows a batch
    touches and the fires are the same for every ``rows``."""
    rng = np.random.default_rng(5)
    n = rows * batches
    keys = rng.integers(0, rows * 2, n)
    users = rng.integers(0, 1 << 30, n)
    ts = (np.arange(n) // rows // per_window) * 1000 + np.sort(
        rng.integers(0, 1000, (batches, rows)), axis=1).reshape(-1)
    return keys, users, ts


def _state_backend_job(rows, env=None, pinned=True):
    """Config #2's job over a vectorized source.  ``pinned``: the
    scalar WindowOperator over the ``tpu`` state backend; else nothing
    is pinned and aggregate() builds the DeviceWindowOperator, whose
    batch door takes every batch."""
    from flink_tpu.streaming.columnar import VectorizedCollectionSource
    keys, users, ts = _phase_data(rows)
    values = [((int(k), int(u)), int(t))
              for k, u, t in zip(keys, users, ts)]
    env = env or StreamExecutionEnvironment()
    sink = CollectSink()
    windowed = (env.add_source(VectorizedCollectionSource(
        values, timestamped=True, chunk=rows))
        .key_by(0).window(TumblingEventTimeWindows.of(Time.seconds(1))))
    if pinned:
        env.set_state_backend("tpu")
        windowed.disable_device_operator()
    windowed.aggregate(
        UserHll(8), window_function=lambda k, w, v:
        [(k, w.start, float(v[0]))]).add_sink(sink)
    env.execute("phases-state" if pinned else "phases-default-door")
    return len(sink.values)


def _default_door_job(rows):
    return _state_backend_job(rows, pinned=False)


def _sql_tumble_job(rows):
    from flink_tpu.streaming.columnar import ColumnarCollectSink
    from flink_tpu.table import StreamTableEnvironment
    keys, users, ts = _phase_data(rows)
    env = StreamExecutionEnvironment()
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table("ev", t_env.from_columns(
        {"k": keys, "u": users.astype(np.uint64), "ts": ts},
        rowtime="ts", chunk=rows))
    out = t_env.sql_query(
        "SELECT k, APPROX_COUNT_DISTINCT(u) AS d FROM ev "
        "GROUP BY TUMBLE(ts, INTERVAL '1' SECOND), k")
    sink = ColumnarCollectSink()
    out.to_append_stream(batched=True).add_sink(sink)
    env.execute("phases-sql")


STATE_ROUTE_PHASES = {
    "window.ingest": 8, "window.ingest.box": 8, "window.ingest.assign": 8,
    "window.ingest.group": 8, "state.add.slots": 8, "state.add.hash": 8, "timers.register": 8,
    "window.watermark": 1, "timers.sweep": 1, "state.get.lookup": 1,
    "state.flush": 1, "state.get.device": 1, "window.fire.batch": 1,
    "window.fire.columnarize": 1, "window.fire.downstream": 1,
    "window.fire.release": 1,
    "state.clear.slots": 1, "state.clear.device": 1}
SQL_ROUTE_PHASES = {
    "window.ingest": 8, "columnar.ingest.hash": 8, "log.append": 8,
    "window.watermark": 10, "log.concat": 2, "log.finish.pad": 2,
    "log.finish.device": 2, "window.fire.batch": 2,
    "window.fire.downstream": 2}
#: one watermark, the stream's last, fires both windows
DEFAULT_DOOR_PHASES = {
    "window.ingest": 8, "device_window.columns": 8,
    "columnar.ingest.hash": 8, "log.append": 8,
    "window.watermark": 1, "device_window.fire": 1, "log.concat": 2,
    "log.finish.pad": 2, "log.finish.device": 2, "window.fire.batch": 2,
    "window.fire.columnarize": 2, "window.fire.downstream": 2,
    "window.fire.release": 2}
#: per route: (operator uid, windows, newest window end) of every period.
#: A period is cut where a watermark that fired ends: one per firing
#: watermark, so two on the SQL route and one where the stream's last
#: watermark fires both windows
STATE_ROUTE_PERIODS = [("op-2-window_aggregate", 2, 2000)]
SQL_ROUTE_PERIODS = [
    ("columnar-window-agg:0:k:APPROX_COUNT_DISTINCT:u", 1, 1000),
    ("columnar-window-agg:0:k:APPROX_COUNT_DISTINCT:u", 1, 2000)]
DEFAULT_DOOR_PERIODS = [("op-2-window_aggregate", 2, 2000)]

@pytest.mark.parametrize("job, expected, expected_periods", [
    (_state_backend_job, STATE_ROUTE_PHASES, STATE_ROUTE_PERIODS),
    (_sql_tumble_job, SQL_ROUTE_PHASES, SQL_ROUTE_PERIODS),
    (_default_door_job, DEFAULT_DOOR_PHASES, DEFAULT_DOOR_PERIODS)],
    ids=["state_backend", "sql", "default_door"])
def test_phase_counts_follow_batches_and_fires_never_rows(
        job, expected, expected_periods, monkeypatch):
    """The guard against a span per record, key or timer: exactly the
    documented phase names, and the same number of each when every
    batch carries four times the rows and every fire four times the
    keys; `window.fire.release` once a flushed fire; and as many
    periods cut as watermarks fired, whatever the rows and keys.  (The
    finish tier is the device's, as on the chip; here the link probe
    would pick the host.)"""
    import flink_tpu.native as nat
    from flink_tpu.ops import link_probe
    if not nat.available():
        pytest.skip("native runtime unavailable")
    monkeypatch.setattr(link_probe, "recommended_finish_tier",
                        lambda override=None: "device")
    tr = get_tracer()
    assert not tr.enabled
    for rows in (64, 256):
        tr.reset()
        job(rows)
        counts = {name: s["count"] for name, s in tr.stats().items()}
        assert counts == expected, rows
        periods = tr.periods()
        assert [(p["operator"], p["windows"],
                 p["newest_window_end"]) for p in periods] \
            == expected_periods, rows
        assert sum(p["keys"] for p in periods) > rows // 2
        assert tr.dropped_periods == 0
        # the periods' counts add up to the books, but for a watermark
        # after the last cut that fired nothing (the SQL route's last)
        for name, count in expected.items():
            cut = sum(p["phases"].get(name, {}).get("count", 0)
                      for p in periods)
            assert cut == count or (name, cut) == (
                "window.watermark", count - 1), name


@pytest.mark.parametrize("n", [9_000, 15_000])
def test_record_door_phases_follow_buffer_flushes_and_fires(n, monkeypatch):
    """The default door fed one record at a time: the operator's
    8,192-row buffer is its batch.  Both sizes fill it once (a
    ``window.ingest`` of its own) and leave a rest that the stream's
    last watermark flushes before it fires the three windows."""
    import flink_tpu.native as nat
    from flink_tpu.ops import link_probe
    if not nat.available():
        pytest.skip("native runtime unavailable")
    monkeypatch.setattr(link_probe, "recommended_finish_tier",
                        lambda override=None: "device")
    rng = np.random.default_rng(5)
    values = [((int(k), int(u)), int(t)) for k, u, t in zip(
        rng.integers(0, 50, n), rng.integers(0, 1 << 30, n),
        np.sort(rng.integers(0, 3000, n)))]
    tr = get_tracer()
    tr.reset()
    env = StreamExecutionEnvironment()
    sink = CollectSink()
    (env.from_collection(values, timestamped=True)
        .key_by(0).window(TumblingEventTimeWindows.of(Time.seconds(1)))
        .aggregate(UserHll(8), window_function=lambda k, w, v:
                   [(k, w.start, float(v[0]))]).add_sink(sink))
    env.execute("phases-record-door")
    assert len(sink.values) == 150
    counts = {name: s["count"] for name, s in tr.stats().items()}
    assert counts == {
        "window.ingest": 1, "device_window.flush": 2,
        "columnar.ingest.hash": 2, "log.append": 2,
        "window.watermark": 1, "device_window.fire": 1, "log.concat": 3,
        "log.finish.pad": 3, "log.finish.device": 3, "window.fire.batch": 3,
        "window.fire.columnarize": 3, "window.fire.downstream": 3,
        "window.fire.release": 3}
    [period] = tr.periods()
    assert (period["windows"], period["keys"]) == (3, 150)


def test_a_compile_is_booked_on_the_phase_and_the_label_that_needed_it():
    tracing.reset_jit_stats()
    tr = get_tracer()
    tr.reset()
    before = tracing.backend_compile_totals()
    fired_keys = _state_backend_job(96)
    stats = tr.stats()["state.get.device"]
    assert stats["compiles"] == 1 and stats["compile_ms"] > 0
    assert stats["self_ms"] <= stats["total_ms"] - stats["compile_ms"] + 1e-6
    result = tracing.jit_stats()["state.result"]
    assert result["recompiles"] == 1
    # the program is shaped by the fire's bucket, not by its key count
    bucket = 1 << (fired_keys - 1).bit_length()
    assert bucket != fired_keys
    assert result["last_shape_sig"].endswith(f"int32[{bucket}])")
    assert tr.stats()["state.flush"]["compiles"] == 1
    assert tracing.jit_stats()["state.update"]["recompiles"] == 1
    # the process-wide count holds every compile some phase saw
    after = tracing.backend_compile_totals()
    seen = sum(s["compiles"] for s in tr.stats().values())
    assert after["compiles"] - before["compiles"] >= seen >= 3


def test_dispatch_spans_a_watermark_when_the_tracer_is_on():
    env = StreamExecutionEnvironment()
    env.enable_tracing()
    assert _state_backend_job(64, env)
    watermarks = [e for e in env.get_tracer().recent(10_000)
                  if e["name"] == "window.watermark"]
    assert watermarks
    for e in watermarks:
        assert e["parent"].startswith("op.")
        assert e["parent"].endswith(".process")


# ---------------------------------------------------------------------
# the cyclic collector on the books
# ---------------------------------------------------------------------

@pytest.fixture
def forced_collections_only():
    """Only the collections a test forces: an automatic one would land
    in some phase by chance, which is the thing under test."""
    gc.collect()
    gc.disable()
    tr = get_tracer()
    with tr.phase("hook"):  # the hook goes in with the first phase
        pass
    tr.reset()
    try:
        yield tr
    finally:
        gc.enable()


def test_a_collection_is_booked_on_the_phase_it_interrupted(
        forced_collections_only):
    tr = forced_collections_only
    before = sum(g["collections"] for g in gc.get_stats())
    with tr.phase("fire"):
        time.sleep(0.005)
        with tr.phase("fire.emit"):
            junk = [[i] for i in range(20_000)]  # something to walk
            gc.collect()
    stats = tr.stats()
    emit, fire = stats["fire.emit"], stats["fire"]
    assert emit["gcs"] == 1 and emit["gc_ms"] > 0
    assert emit["gc_under_ms"] == emit["gc_ms"]
    # out of the phase's own time, and not into its parent's
    assert emit["self_ms"] == pytest.approx(
        emit["total_ms"] - emit["gc_ms"], abs=1e-6)
    assert (fire["gcs"], fire["gc_ms"]) == (0, 0.0)
    assert fire["gc_under_ms"] == emit["gc_ms"]
    assert fire["self_ms"] == pytest.approx(
        fire["total_ms"] - emit["total_ms"], abs=1e-6)
    totals = tracing.gc_totals()
    assert totals["collections"] == 1
    assert totals["gc_ms"] == pytest.approx(emit["gc_ms"])
    assert totals["by_generation"][2] == {"collections": 1,
                                          "gc_ms": totals["gc_ms"]}
    assert totals["unphased"] == {"collections": 0, "gc_ms": 0.0}
    # one with no phase open counts in the totals alone
    del junk
    gc.collect(0)
    totals = tracing.gc_totals()
    assert totals["collections"] == 2
    assert totals["unphased"]["collections"] == 1
    assert totals["by_generation"][0]["collections"] == 1
    assert tr.stats()["fire.emit"]["gcs"] == 1
    # the books close: every collection is in a phase or unphased, and
    # the interpreter counted as many
    stats = tr.stats()
    assert sum(s["gcs"] for s in stats.values()) \
        + totals["unphased"]["collections"] == totals["collections"]
    assert sum(s["gc_ms"] for s in stats.values()) \
        + totals["unphased"]["gc_ms"] == pytest.approx(totals["gc_ms"])
    assert sum(g["collections"] for g in gc.get_stats()) - before \
        == totals["collections"]


def test_a_collection_is_a_profiler_event_inside_the_phase(
        forced_collections_only, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    tr = forced_collections_only
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tr.phase("window.fire.batch"):
            gc.collect(1)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = {e.name: e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(tracing.PHASE_PREFIX)}
    assert set(events) == {"flink/window.fire.batch", "flink/py.gc"}
    collection, phase = events["flink/py.gc"], events["flink/window.fire.batch"]
    assert dict(collection.stats)["generation"] == 1
    assert phase.start_ns <= collection.start_ns
    assert collection.start_ns + collection.duration_ns \
        <= phase.start_ns + phase.duration_ns


def test_the_hook_is_installed_once_however_many_tracers_are_set():
    old = get_tracer()
    try:
        for _ in range(3):
            tr = tracing.set_tracer(Tracer())
            with tr.phase("x"):
                pass
            tr.reset()
        assert gc.callbacks.count(tracing._on_gc) == 1
        # and books on the tracer that is set when a collection ends
        with tr.phase("x"):
            gc.collect()
        assert tr.stats()["x"]["gcs"] >= 1
        assert tr.gc_totals()["collections"] >= 1
        assert old.gc_totals()["collections"] == 0 or old is tr
    finally:
        tracing.set_tracer(old)


def test_an_inert_collection_callback_pair_costs_under_5_microseconds():
    """What the hook adds to a collection while no profiler session
    runs, beside the 5 us bound of an inert phase."""
    with get_tracer().phase("hook"):
        pass
    info = {"generation": 0, "collected": 0, "uncollectable": 0}
    on_gc = tracing._on_gc
    n = 100_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            on_gc("start", info)
            on_gc("stop", info)
        best = min(best, time.perf_counter() - t0)
    assert best / n < 5e-6, f"{best / n * 1e6:.2f} us per callback pair"


# ---------------------------------------------------------------------
# the history of fire periods
# ---------------------------------------------------------------------

def _watermark(tr, fired, end=1000, work=("state.flush",)):
    with tr.phase("window.ingest", rows=8):
        for name in work:
            with tr.phase(name):
                pass
    with tr.phase("window.watermark", watermark=end - 1):
        with tr.phase("timers.sweep"):
            pass
        if fired:
            tr.note_fire("WindowOperator", fired, 10 * fired, end)
            with tr.phase("window.fire.batch"):
                pass


def test_period_deltas_sum_to_the_stats_and_only_a_fire_cuts(
        forced_collections_only):
    tr = forced_collections_only
    tracing.record_kernel("test.period_kernel", 0, 3_000_000)
    _watermark(tr, 0)
    _watermark(tr, 0)
    assert tr.periods() == []  # a watermark that fires nothing cuts nothing
    _watermark(tr, 1, end=1000)
    tracing.record_kernel("test.period_kernel", 0, 2_000_000)
    with tr.phase("window.ingest"):
        gc.collect()
        # a native kernel called straight from a phase is named time
        # inside that phase's own
        tracing.record_kernel("test.period_kernel", 0, 1_000_000)
    _watermark(tr, 2, end=3000, work=("state.flush", "state.add.hash"))
    first, second = tr.periods()
    assert (first["seq"], first["windows"], first["keys"],
            first["newest_window_end"], first["watermark"]) \
        == (0, 1, 10, 1000, 999)
    assert (second["seq"], second["windows"], second["keys"],
            second["newest_window_end"], second["operator"]) \
        == (1, 2, 20, 3000, "WindowOperator")
    assert first["end_s"] == second["start_s"] < second["end_s"]
    # the first holds the two idle watermarks before it
    assert first["phases"]["window.watermark"]["count"] == 3
    assert first["phases"]["window.ingest"]["count"] == 3
    assert second["phases"]["window.ingest"]["count"] == 2
    assert "state.add.hash" not in first["phases"]  # did not grow
    assert second["phases"]["window.ingest"]["gcs"] == 1
    assert second["gc"]["collections"] == 1 and "gc" in first
    assert first["gc"] == {}
    assert first["kernels"]["test.period_kernel"] == pytest.approx(3.0)
    assert second["kernels"]["test.period_kernel"] == pytest.approx(3.0)
    assert second["phases"]["window.ingest"]["native_ms"] \
        == pytest.approx(1.0)
    assert "native_ms" not in first["phases"]["window.ingest"]
    stats = tr.stats()
    for name, stat in stats.items():
        for field in ("count", "total_ms", "self_ms", "gc_ms", "gcs",
                      "compiles", "compile_ms", "native_ms"):
            assert sum(p["phases"].get(name, {}).get(field, 0)
                       for p in (first, second)) \
                == pytest.approx(stat[field]), (name, field)
    assert second["gc"]["gc_ms"] == pytest.approx(
        tracing.gc_totals()["gc_ms"])
    # several calls under one watermark add up
    with tr.phase("window.watermark", watermark=5):
        tr.note_fire("a", 1, 5, 4000)
        tr.note_fire("a", 2, 7, 3000)
    assert [(p["windows"], p["keys"], p["newest_window_end"])
            for p in tr.periods()][-1] == (3, 12, 4000)
    # a reset empties the ring, the base of the next cut and the books
    tr.reset()
    assert tr.periods() == [] and tr.gc_totals()["collections"] == 0
    _watermark(tr, 1)
    [only] = tr.periods()
    assert only["seq"] == 0
    assert only["phases"]["window.watermark"]["count"] == 1


def test_counts_of_a_phase_are_summed_in_the_books_and_cut_by_period(
        forced_collections_only):
    """`add_count` is an attribute of the event and a sum per name:
    `stats()[name]["counts"]`, and each period holds its growth."""
    tr = forced_collections_only

    def batch(rows, column):
        with tr.phase("window.ingest"):
            with tr.phase("state.add.slots") as phase:
                phase.add_count("rows", rows)
                phase.add_count("int_table", column)
            with tr.phase("state.flush", rows=rows):  # a label: no sum
                pass

    batch(8, 8)
    batch(4, 0)
    _watermark(tr, 1, end=1000, work=())
    batch(16, 16)
    _watermark(tr, 1, end=2000, work=())
    stats = tr.stats()
    assert stats["state.add.slots"]["counts"] == {"rows": 28, "int_table": 24}
    assert "counts" not in stats["state.flush"]
    first, second = tr.periods()
    assert first["phases"]["state.add.slots"]["counts"] \
        == {"rows": 12, "int_table": 8}
    assert second["phases"]["state.add.slots"]["counts"] \
        == {"rows": 16, "int_table": 16}
    assert "counts" not in second["phases"]["state.flush"]
    # a count that did not grow is left out like any other field
    with tr.phase("state.add.slots") as phase:
        phase.add_count("rows", 2)
        phase.add_count("int_table", 0)
    _watermark(tr, 1, end=3000, work=())
    assert tr.periods()[-1]["phases"]["state.add.slots"]["counts"] \
        == {"rows": 2}
    # on the event too, when the ring is on
    tr.enabled = True
    try:
        with tr.phase("state.add.slots") as phase:
            phase.add_count("int_table", 5)
        [event] = [e for e in tr.recent(5) if e["name"] == "state.add.slots"]
        assert event["args"] == {"int_table": 5}
    finally:
        tr.enabled = False


def test_the_period_ring_stops_at_512_and_counts_what_it_dropped():
    tr = Tracer()
    for i in range(tracing.MAX_PERIODS + 8):
        with tr.phase("window.watermark", watermark=i):
            tr.note_fire("op", 1, 1, i)
    periods = tr.periods()
    assert len(periods) == tracing.MAX_PERIODS == 512
    assert tr.dropped_periods == 8
    assert [periods[0]["seq"], periods[-1]["seq"]] == [8, 519]

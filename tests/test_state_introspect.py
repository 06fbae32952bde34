"""Keyed-state introspection plane: per-key-group accounting, hot-key
skew detection, the `key-skew-sustained` health rule, the
`/jobs/<n>/state` route on the live monitor and the HistoryServer, and
the offline snapshot inspector (ref: state/introspect.py)."""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from flink_tpu.core.keygroups import KeyGroupRange, assign_to_key_group
from flink_tpu.core.state import (
    AggregatingStateDescriptor,
    FoldingStateDescriptor,
    ValueStateDescriptor,
)
from flink_tpu.ops.device_agg import SumAggregate
from flink_tpu.runtime.history import FsJobArchivist, HistoryServer
from flink_tpu.runtime.metrics import (
    MetricRegistry,
    register_state_gauges,
    register_state_introspection_gauges,
)
from flink_tpu.runtime.rest import WebMonitor
from flink_tpu.runtime.timeseries import HealthEvaluator, MetricsJournal
from flink_tpu.state.introspect import (
    INTROSPECTION,
    StateIntrospection,
    get_introspection,
    inspect_checkpoint,
    pickled_len,
)
from flink_tpu.state.loader import load_state_backend
from flink_tpu.state.stats import STATE_STATS


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return json.loads(r.read().decode())


def _get_error(port, path):
    try:
        _get(port, path)
    except urllib.error.HTTPError as e:
        return e.code
    raise AssertionError(f"expected HTTP error for {path}")


@pytest.fixture(autouse=True)
def _clean_introspection():
    """The plane is a process-global singleton — every test starts and
    leaves it disabled + empty so suites can run in any order."""
    t = get_introspection()
    t.disable()
    t.reset()
    yield
    t.disable()
    t.reset()


class _KVSum(SumAggregate):
    def __init__(self):
        super().__init__(np.float32)

    def extract_value(self, value):
        return value[1] if isinstance(value, tuple) else value


# ---------------------------------------------------------------------
# disabled path: nothing recorded, near-zero guard cost
# ---------------------------------------------------------------------

def test_disabled_payload_shape():
    t = get_introspection()
    assert not t.enabled
    p = t.payload()
    assert p == {"enabled": False, "accounting": {}, "ingest": {},
                 "skew": {"ratio": 0.0, "hot_key_group": None,
                          "occupied_key_groups": 0,
                          "verdict": "disabled", "per_state": {}},
                 "hot_keys": []}


def test_disabled_path_records_nothing():
    backend = load_state_backend("heap", KeyGroupRange(0, 127), 128)
    state = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("v", SumAggregate(np.float32)))
    keys = np.arange(64, dtype=np.int64)
    backend.add_batch(state, keys, None, keys.astype(np.float64))
    assert get_introspection().payload()["ingest"] == {}
    assert get_introspection().skew_summary()["ratio"] == 0.0


def test_disabled_guard_is_near_free():
    """Same bound discipline as the device-telemetry plane: the
    disabled hot path is ONE attribute check, bounded sub-microsecond
    per call (orders of magnitude below the 3% enabled-overhead
    acceptance bar on real ingest batches)."""
    t = get_introspection()
    t.disable()
    n = 200_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            if t.enabled:
                raise AssertionError("unreachable")
        best = min(best, time.perf_counter() - t0)
    assert best / n < 1e-6, f"disabled guard {best / n * 1e9:.0f} ns/call"


# ---------------------------------------------------------------------
# accounting: exact rows/bytes per (state, key group), both backends
# ---------------------------------------------------------------------

def _expected_heap_value_accounting(keys, values, mp=128):
    per_kg = {}
    for k, v in zip(keys, values):
        kg = assign_to_key_group(k, mp)
        e = per_kg.setdefault(kg, {"rows": 0, "bytes": 0})
        e["rows"] += 1
        e["bytes"] += pickled_len(v)
    return per_kg


def test_heap_accounting_breakdown_exact():
    backend = load_state_backend("heap", KeyGroupRange(0, 127), 128)
    state = backend.create_value_state(ValueStateDescriptor("names", str))
    keys = [f"user-{i}" for i in range(40)]
    values = [f"payload-{i}" * (1 + i % 3) for i in range(40)]
    for k, v in zip(keys, values):
        backend.set_current_key(k)
        state.update(v)
    bd = backend.accounting_breakdown()
    assert set(bd) == {"names"}
    expected = _expected_heap_value_accounting(keys, values)
    got_rows = {kg: e["rows"] for kg, e in bd["names"].items()}
    got_bytes = {kg: e["bytes"] for kg, e in bd["names"].items()}
    assert got_rows == {kg: e["rows"] for kg, e in expected.items()}
    assert got_bytes == {kg: e["bytes"] for kg, e in expected.items()}
    assert all(e["namespaces"] == 1 for e in bd["names"].values())


def test_tpu_accounting_breakdown_exact():
    backend = load_state_backend("tpu", KeyGroupRange(0, 127), 128)
    state = backend.create_aggregating_state(
        AggregatingStateDescriptor("sums", _KVSum()))
    keys = np.arange(50, dtype=np.int64)
    values = [(int(k), 1.0) for k in keys]
    backend.add_batch(state, keys, None, values)
    bd = backend.accounting_breakdown()
    assert set(bd) == {"sums"}
    total_rows = sum(e["rows"] for e in bd["sums"].values())
    total_bytes = sum(e["bytes"] for e in bd["sums"].values())
    assert total_rows == 50
    # one float32 accumulator per key — the row-bytes definition is
    # sum(prod(shape) * itemsize) over the aggregate's state specs
    assert total_bytes == 50 * 4
    per_kg = {}
    for k in keys.tolist():
        kg = assign_to_key_group(k, 128)
        per_kg[kg] = per_kg.get(kg, 0) + 1
    assert {kg: e["rows"] for kg, e in bd["sums"].items()} == per_kg


def test_dispose_freezes_accounting_for_payload():
    import gc
    gc.collect()  # drop earlier tests' backends from the WeakSet
    t = get_introspection()
    t.enable()
    backend = load_state_backend("heap", KeyGroupRange(0, 127), 128)
    state = backend.create_value_state(
        ValueStateDescriptor("frozen-v", int))
    for k in range(20):
        backend.set_current_key(k)
        state.update(k * 10)
    live = t.payload()["accounting"]["frozen-v"]
    backend.dispose()
    frozen = t.payload()["accounting"]["frozen-v"]
    assert frozen == live
    assert frozen["rows"] == 20


# ---------------------------------------------------------------------
# skew detection: sketch estimates, verdicts, scalar/vector parity
# ---------------------------------------------------------------------

def test_skew_detection_vectorized_and_scalar_agree():
    rng = np.random.default_rng(7)
    hot = np.zeros(500, dtype=np.int64)
    cold = rng.integers(1, 40, 500).astype(np.int64)
    keys = np.concatenate([hot, cold])

    vec = StateIntrospection()
    vec.enable()
    vec.note_ingest("s", keys, 128)
    scal = StateIntrospection()
    scal.enable()
    for k in keys.tolist():
        scal.note_row("s", k, 128)

    for t in (vec, scal):
        s = t.skew_summary()
        assert s["ratio"] > 3.0
        p = t.payload()
        assert p["skew"]["verdict"] == "skewed"
        top = p["hot_keys"][0]
        assert top["count"] == 500 and top["share"] == 0.5
    assert (vec._trackers["s"].kg_counts
            == scal._trackers["s"].kg_counts)
    assert np.array_equal(vec._trackers["s"].table,
                          scal._trackers["s"].table)


def test_uniform_keys_stay_balanced():
    t = get_introspection()
    t.enable()
    t.note_ingest("s", np.arange(1000, dtype=np.int64), 128)
    p = t.payload()
    assert p["skew"]["verdict"] == "balanced"
    assert p["skew"]["ratio"] < 3.0
    assert all(e["share"] < 0.05 for e in p["hot_keys"])


def test_ingest_counts_per_state():
    t = get_introspection()
    t.enable()
    t.note_ingest("a", np.arange(30, dtype=np.int64), 128)
    t.note_ingest("b", np.arange(70, dtype=np.int64), 128)
    p = t.payload()
    assert p["ingest"] == {"a": 30, "b": 70}
    assert p["skew"]["per_state"]["a"]["rows"] == 30
    assert p["skew"]["per_state"]["b"]["rows"] == 70


# ---------------------------------------------------------------------
# STATE_STATS: per-state batch/fallback split, aggregate names pinned
# ---------------------------------------------------------------------

def test_state_stats_per_state_split():
    STATE_STATS.reset()
    backend = load_state_backend("heap", KeyGroupRange(0, 127), 128)
    sums = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("sums", SumAggregate(np.float32)))
    folds = backend.get_or_create_keyed_state(
        FoldingStateDescriptor("folds", "", lambda acc, v: acc + v))
    keys = np.arange(16, dtype=np.int64)
    # typed aggregate: native batch path
    assert backend.add_batch(sums, keys, None,
                             keys.astype(np.float64)) == "batch"
    # folding state has no native add_batch: exact per-row fallback
    assert backend.add_batch(folds, list("abcdefghijklmnop"), ("n",),
                             ["x"] * 16) == "rows"
    assert STATE_STATS.per_state_batch_rows.get("sums") == 16
    assert STATE_STATS.per_state_batch_calls.get("sums") == 1
    assert STATE_STATS.per_state_fallback_rows.get("folds") == 16
    assert STATE_STATS.per_state_fallback_calls.get("folds") == 1
    # the aggregates keep counting exactly as before the split
    assert STATE_STATS.batch_rows == 16
    assert STATE_STATS.row_fallback_rows == 16
    STATE_STATS.reset()
    assert STATE_STATS.per_state_batch_rows == {}


def test_state_gauge_names_are_backward_compatible():
    """The pre-split `state.*` dump keys are pinned API: dashboards
    read them by name.  The per-state drill-down and the introspection
    gauges ride alongside, never replace."""
    registry = MetricRegistry()
    register_state_gauges(registry)
    register_state_introspection_gauges(registry)
    dump = registry.dump()
    pinned = [
        "state.batchRows", "state.rowFallbackRows",
        "state.batchCalls", "state.rowFallbackCalls",
        "state.flushBatches", "state.flushRows",
        "state.flushSizeMean", "state.flushSizeMax",
        "state.snapshotColumns", "state.snapshotRows",
        "state.device.states", "state.device.slotsInUse",
        "state.device.capacity", "state.device.spilledEntries",
        "state.device.evictions", "state.device.promotions",
        "state.device.pendingDepth",
    ]
    for key in pinned:
        assert key in dump, f"pinned gauge {key} missing from dump"
    for key in ("state.perState.batchRows", "state.perState.batchCalls",
                "state.perState.rowFallbackRows",
                "state.perState.rowFallbackCalls"):
        assert key in dump
    assert dump["state.introspectionEnabled"] == 0
    assert dump["state.keyGroupSkew"] == 0.0
    assert dump["state.hotKeyGroup"] == -1
    assert dump["state.occupiedKeyGroups"] == 0
    assert dump["state.hotKeyShare"] == 0.0
    assert dump["state.hotKeys"] == 0


# ---------------------------------------------------------------------
# key-skew-sustained health rule: once per episode, re-arms after clear
# ---------------------------------------------------------------------

class _FakeClock:
    def __init__(self, start=0.0):
        self.t = start

    def __call__(self):
        return self.t


def test_key_skew_alert_fires_once_per_episode():
    clock, wall = _FakeClock(), _FakeClock(1_000.0)
    j = MetricsJournal(interval_ms=10, clock=clock, wall_clock=wall)
    ev = HealthEvaluator(j, key_skew_threshold=3.0,
                         key_skew_consecutive=3, wall_clock=wall)

    def feed(ratio, n, hot_kg=46):
        for _ in range(n):
            j.ingest(wall.t, {"state.keyGroupSkew": ratio,
                              "state.hotKeyGroup": hot_kg})
            ev.evaluate()
            clock.t += 10
            wall.t += 10

    feed(1.5, 6)                       # balanced: quiet
    assert ev.alerts_total == 0
    feed(12.0, 10)                     # sustained skew: ONE alert
    skew = [a for a in ev.snapshot_alerts()
            if a["rule"] == "key-skew-sustained"]
    assert len(skew) == 1
    assert skew[0]["metric"] == "state.keyGroupSkew"
    assert skew[0]["value"] == pytest.approx(12.0)
    assert "hot key group 46" in skew[0]["message"]
    assert "key-skew-sustained" in ev.active_rules
    feed(1.2, 4)                       # clears -> re-arms
    assert "key-skew-sustained" not in ev.active_rules
    feed(12.0, 5)                      # second episode
    skew = [a for a in ev.snapshot_alerts()
            if a["rule"] == "key-skew-sustained"]
    assert len(skew) == 2


def test_key_skew_rule_needs_consecutive_samples():
    clock, wall = _FakeClock(), _FakeClock(1_000.0)
    j = MetricsJournal(interval_ms=10, clock=clock, wall_clock=wall)
    ev = HealthEvaluator(j, key_skew_threshold=3.0,
                         key_skew_consecutive=3, wall_clock=wall)
    for ratio in (12.0, 1.0, 12.0, 1.0, 12.0, 1.0, 12.0, 12.0):
        j.ingest(wall.t, {"state.keyGroupSkew": ratio})
        ev.evaluate()
        clock.t += 10
        wall.t += 10
    assert ev.alerts_total == 0       # never 3 in a row


# ---------------------------------------------------------------------
# REST: live /state route, 404/400 discipline, HistoryServer twin
# ---------------------------------------------------------------------

def test_live_state_route_serves_disabled_shape_and_404s():
    monitor = WebMonitor(MetricRegistry()).start()

    class _Client:
        executor_state = {"journal": None, "health": None,
                          "coordinator": None}
        done = False

    try:
        monitor.track_job("real-job", _Client())
        assert _get_error(monitor.port, "/jobs/nope/state") == 404
        assert _get_error(monitor.port,
                          "/jobs/real-job/state?top=abc") == 400
        assert _get_error(monitor.port,
                          "/jobs/real-job/state?top=0") == 400
        body = _get(monitor.port, "/jobs/real-job/state")
        assert body["enabled"] is False
        assert body["skew"]["verdict"] == "disabled"
        assert body["accounting"] == {} and body["hot_keys"] == []
    finally:
        monitor.stop()


def test_live_state_route_top_param_limits_hot_keys():
    t = get_introspection()
    t.enable()
    t.note_ingest("s", np.arange(40, dtype=np.int64), 128)
    monitor = WebMonitor(MetricRegistry()).start()

    class _Client:
        executor_state = {}
        done = False

    try:
        monitor.track_job("j", _Client())
        full = _get(monitor.port, "/jobs/j/state")
        top2 = _get(monitor.port, "/jobs/j/state?top=2")
        assert len(full["hot_keys"]) > 2
        assert len(top2["hot_keys"]) == 2
        assert top2["hot_keys"] == full["hot_keys"][:2]
    finally:
        monitor.stop()


def test_live_and_history_state_payload_parity(tmp_path, monkeypatch):
    """The acceptance invariant: a finished job's archived `/state`
    payload is byte-identical to what the live route served at archive
    time (trackers process-global).

    No executor disposes a keyed backend, so nothing freezes a finished
    job's accounting: both payloads walk the backends that are still
    alive, and the archive is written by the executor's thread after
    `wait()` has returned.  A collection between the two reads took the
    job's backend (or one an earlier test left behind) out of one
    payload only.  So: what earlier tests left is collected first, and
    this job's backends are held until the archive has been read."""
    import gc

    from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    from flink_tpu.streaming.sources import CollectSink
    from flink_tpu.streaming.windowing import TumblingEventTimeWindows

    archive = str(tmp_path / "archive")
    t = get_introspection()
    gc.collect()
    held = []
    register = t.register_backend
    monkeypatch.setattr(
        t, "register_backend", lambda b: (held.append(b), register(b)))
    t.enable()
    env = StreamExecutionEnvironment()
    env.use_mini_cluster(2)
    env.set_state_backend("tpu")
    env.config.set("history.archive.dir", archive)
    records = [((i % 8, 1.0), i * 5) for i in range(2000)]
    sink = CollectSink()
    (env.from_collection(records, timestamped=True)
        .key_by(lambda e: e[0])
        .window(TumblingEventTimeWindows.of(1000))
        .disable_device_operator()
        .aggregate(_KVSum(), window_function=(
            lambda key, w, vals: [(key, w.start, float(vals[0]))]))
        .add_sink(sink))
    client = env.execute_async("state-job")
    monitor = WebMonitor(env.get_metric_registry()).start()
    try:
        monitor.track_job("state-job", client)
        client.wait(timeout=120)
        live = _get(monitor.port, "/jobs/state-job/state")
    finally:
        monitor.stop()
    assert live["enabled"] is True
    assert live["ingest"] and live["accounting"]
    assert sum(live["ingest"].values()) == 2000

    deadline = time.monotonic() + 15
    import os
    while time.monotonic() < deadline:
        if os.path.isdir(archive) and any(
                not f.endswith(".part") for f in os.listdir(archive)):
            break
        time.sleep(0.05)
    hs = HistoryServer([archive]).start()
    try:
        arch = _get(hs.port, "/jobs/state-job/state")
        assert (json.dumps(arch, sort_keys=True)
                == json.dumps(live, sort_keys=True))
        assert _get_error(hs.port, "/jobs/nope/state") == 404
        assert _get_error(hs.port, "/jobs/state-job/state?top=abc") == 400
        top1 = _get(hs.port, "/jobs/state-job/state?top=1")
        assert top1["hot_keys"] == arch["hot_keys"][:1]
    finally:
        hs.stop()


def test_history_state_route_disabled_shape_without_archive_field(
        tmp_path):
    FsJobArchivist.archive(str(tmp_path), "job-1", {
        "job_name": "old-job", "state": "FINISHED"})
    hs = HistoryServer([str(tmp_path)]).start()
    try:
        body = _get(hs.port, "/jobs/old-job/state")
        assert body["enabled"] is False
        assert body["skew"]["verdict"] == "disabled"
    finally:
        hs.stop()


# ---------------------------------------------------------------------
# offline inspector: checkpoint on disk == live accounting, exactly
# ---------------------------------------------------------------------

def _drive_window_job(backend_name):
    from flink_tpu.streaming.elements import RecordBatch
    from flink_tpu.streaming.harness import (
        OneInputStreamOperatorTestHarness)
    from flink_tpu.streaming.window_operator import WindowOperator
    from flink_tpu.streaming.windowing import TumblingEventTimeWindows

    op = WindowOperator(
        TumblingEventTimeWindows.of(10_000),
        AggregatingStateDescriptor("w-sum", _KVSum()),
        window_function=lambda k, w, vs: [(k, w.start, float(v))
                                          for v in vs])
    h = OneInputStreamOperatorTestHarness(
        op, key_selector=lambda x: x[0], state_backend=backend_name)
    h.open()
    rng = np.random.default_rng(17)
    keys = rng.integers(0, 23, 400)
    vals = rng.integers(0, 9, 400).astype(np.float64)
    ts = np.arange(400, dtype=np.int64)
    h.process_batch(RecordBatch({"f0": keys, "f1": vals}, ts=ts))
    return h


@pytest.mark.parametrize("backend_name", ["heap", "tpu"])
def test_inspector_matches_live_accounting(tmp_path, backend_name):
    from flink_tpu.runtime.checkpoints import FsCheckpointStorage

    h = _drive_window_job(backend_name)
    live = h.operator.keyed_backend.accounting_breakdown()
    snap = h.snapshot()
    storage = FsCheckpointStorage(str(tmp_path))
    storage.persist(3, {"timestamp": 123}, {(0, 0): snap})

    report = inspect_checkpoint(str(tmp_path))
    assert report["checkpoint_id"] == 3
    assert set(report["states"]) == set(live)
    for name, per_kg in live.items():
        st = report["states"][name]
        assert ({kg: (e["rows"], e["bytes"]) for kg, e in per_kg.items()}
                == {kg: (e["rows"], e["bytes"])
                    for kg, e in st["key_groups"].items()})
        assert st["rows"] == sum(e["rows"] for e in per_kg.values())
        assert st["bytes"] == sum(e["bytes"] for e in per_kg.values())
    assert report["max_parallelism"] == 128
    assert report["top_keys"]
    assert report["top_keys"] == sorted(
        report["top_keys"], key=lambda e: -e["bytes"])


def test_inspector_checkpoint_selection_and_errors(tmp_path):
    from flink_tpu.runtime.checkpoints import FsCheckpointStorage

    with pytest.raises(FileNotFoundError):
        inspect_checkpoint(str(tmp_path))
    h = _drive_window_job("heap")
    snap = h.snapshot()
    storage = FsCheckpointStorage(str(tmp_path), retain=2)
    storage.persist(1, {"timestamp": 1}, {(0, 0): snap})
    storage.persist(2, {"timestamp": 2}, {(0, 0): snap})
    assert inspect_checkpoint(str(tmp_path))["checkpoint_id"] == 2
    assert inspect_checkpoint(
        str(tmp_path), checkpoint_id=1)["checkpoint_id"] == 1
    with pytest.raises(FileNotFoundError):
        inspect_checkpoint(str(tmp_path), checkpoint_id=9)


def test_rescale_preview_partitions_all_rows(tmp_path):
    from flink_tpu.runtime.checkpoints import FsCheckpointStorage

    h = _drive_window_job("tpu")
    snap = h.snapshot()
    FsCheckpointStorage(str(tmp_path)).persist(1, {}, {(0, 0): snap})
    report = inspect_checkpoint(str(tmp_path), parallelism=4)
    total = sum(st["rows"] for st in report["states"].values())
    r = report["rescale"]
    assert r["parallelism"] == 4 and r["max_parallelism"] == 128
    assert sum(s["rows"] for s in r["subtasks"]) == total
    assert len(r["subtasks"]) == 4
    # ranges tile [0, 128) with no gap or overlap
    edges = [tuple(s["key_group_range"]) for s in r["subtasks"]]
    assert edges[0][0] == 0 and edges[-1][1] == 127
    for (lo1, hi1), (lo2, _hi2) in zip(edges, edges[1:]):
        assert lo2 == hi1 + 1
    with pytest.raises(ValueError):
        inspect_checkpoint(str(tmp_path), parallelism=500)


def test_state_inspect_cli_renders_report(tmp_path, capsys):
    from flink_tpu.cli import main as cli_main
    from flink_tpu.runtime.checkpoints import FsCheckpointStorage

    h = _drive_window_job("heap")
    snap = h.snapshot()
    FsCheckpointStorage(str(tmp_path)).persist(5, {}, {(0, 0): snap})
    rc = cli_main(["state", "inspect", str(tmp_path),
                   "--top", "3", "--parallelism", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "chk-5" in out and "w-sum" in out
    assert "heaviest keys" in out and "rescale preview" in out

    rc = cli_main(["state", "inspect", str(tmp_path), "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    assert json.loads(out)["checkpoint_id"] == 5

    rc = cli_main(["state", "inspect", str(tmp_path / "nope")])
    assert rc == 1

"""MiniCluster multi-worker execution + mesh-sharded window path.

The multi-worker tier of the test pyramid (ref:
flink-runtime/.../minicluster/MiniCluster.java and the ITCase bases in
flink-test-utils-parent — SURVEY.md §4.4): real worker threads, real
cross-worker channel traffic, checkpointing and failure recovery, plus
the mesh-sharded device window engine driven from a JobGraph over the
8-device virtual CPU mesh.
"""

import collections

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from flink_tpu.core.functions import AggregateFunction, MapFunction
from flink_tpu.ops.device_agg import CountAggregate, SumAggregate
from flink_tpu.parallel.mesh_windows import (
    MeshTumblingWindows,
    MeshWindowOverflowError,
)
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.sources import (
    BoundedOutOfOrdernessTimestampExtractor,
    CollectSink,
    FromCollectionSource,
)
from flink_tpu.streaming.windowing import Time, TumblingEventTimeWindows


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest must force 8 virtual devices"
    return Mesh(np.array(devs[:8]), ("kg",))


# ---------------------------------------------------------------------
# MeshTumblingWindows engine semantics
# ---------------------------------------------------------------------

def test_mesh_engine_multi_window_counts(mesh):
    eng = MeshTumblingWindows(CountAggregate(), 1000, mesh,
                              capacity_per_window_shard=256, step_batch=64)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 50, 500)
    ts = rng.integers(0, 3000, 500)
    eng.process_batch(keys, ts)
    eng.advance_watermark(999)
    eng.advance_watermark(2999)
    expect = collections.Counter()
    for k, t in zip(keys.tolist(), ts.tolist()):
        expect[(k, t - t % 1000)] += 1
    got = {(k, s): v for (k, v, s, e) in eng.emitted}
    assert got == dict(expect)
    # window ends are start + size
    assert all(e == s + 1000 for (_, _, s, e) in eng.emitted)


def test_mesh_engine_sums_match_host(mesh):
    eng = MeshTumblingWindows(SumAggregate(), 500, mesh,
                              capacity_per_window_shard=256, step_batch=64)
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 30, 400)
    ts = rng.integers(0, 2000, 400)
    vals = rng.random(400).astype(np.float32)
    eng.process_batch(keys, ts, vals)
    eng.advance_watermark(1999)
    expect = collections.defaultdict(float)
    for k, t, v in zip(keys.tolist(), ts.tolist(), vals.tolist()):
        expect[(k, t - t % 500)] += v
    got = {(k, s): v for (k, v, s, e) in eng.emitted}
    assert set(got) == set(expect)
    for ks in expect:
        assert abs(got[ks] - expect[ks]) < 1e-3


def test_mesh_engine_drops_late_records(mesh):
    eng = MeshTumblingWindows(CountAggregate(), 1000, mesh,
                              capacity_per_window_shard=64, step_batch=64)
    eng.process_batch(np.array([1, 2]), np.array([100, 1100]))
    eng.advance_watermark(999)       # fires window 0
    eng.process_batch(np.array([3]), np.array([500]))  # late for window 0
    assert eng.num_late_dropped == 1
    eng.advance_watermark(1999)
    got = {(k, s) for (k, v, s, e) in eng.emitted}
    assert got == {(1, 0), (2, 1000)}


def test_mesh_engine_far_future_parks_and_ingests(mesh):
    # ring=2: a record 2+ windows ahead of a live one parks host-side
    eng = MeshTumblingWindows(CountAggregate(), 1000, mesh, ring=2,
                              capacity_per_window_shard=64, step_batch=64)
    eng.process_batch(np.array([1]), np.array([100]))     # window 0 (ring 0)
    eng.process_batch(np.array([2]), np.array([2100]))    # window 2000 → ring 0 busy
    assert eng.pending, "far-future record should park"
    eng.advance_watermark(999)   # window 0 fires, ring 0 frees, pending ingests
    eng.advance_watermark(2999)
    got = {(k, s) for (k, v, s, e) in eng.emitted}
    assert got == {(1, 0), (2, 2000)}


def test_mesh_engine_parked_window_fires_on_big_watermark_jump(mesh):
    """A parked window whose due-time passes while parked must still
    fire (one watermark jump past everything — the end-of-input
    MAX_WATERMARK shape), not be counted late: its records arrived on
    time."""
    eng = MeshTumblingWindows(CountAggregate(), 1000, mesh, ring=2,
                              capacity_per_window_shard=64, step_batch=64)
    eng.process_batch(np.array([1]), np.array([100]))   # window 0, ring 0
    eng.process_batch(np.array([2]), np.array([2100]))  # window 2000 parks
    assert eng.pending
    eng.advance_watermark(2 ** 62)  # everything due at once
    got = {(k, s) for (k, v, s, e) in eng.emitted}
    assert got == {(1, 0), (2, 2000)}
    assert eng.num_late_dropped == 0
    assert not eng.pending and not eng.live
    # per-window key directories are cleaned up after fires
    assert not eng.key_directory


def test_mesh_engine_overflow_raises(mesh):
    eng = MeshTumblingWindows(CountAggregate(), 1000, mesh,
                              capacity_per_window_shard=2, step_batch=64,
                              max_probes=2)
    keys = np.arange(1000)
    ts = np.full(1000, 10)
    with pytest.raises(MeshWindowOverflowError):
        eng.process_batch(keys, ts)
        eng.flush()


def test_mesh_engine_snapshot_restore_midwindow(mesh):
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 40, 300)
    ts = rng.integers(0, 2000, 300)

    eng = MeshTumblingWindows(CountAggregate(), 1000, mesh,
                              capacity_per_window_shard=256, step_batch=64)
    eng.process_batch(keys[:150], ts[:150])
    snap = eng.snapshot()

    eng2 = MeshTumblingWindows(CountAggregate(), 1000, mesh,
                               capacity_per_window_shard=256, step_batch=64)
    eng2.restore(snap)
    eng2.process_batch(keys[150:], ts[150:])
    eng2.advance_watermark(1999)

    expect = collections.Counter()
    for k, t in zip(keys.tolist(), ts.tolist()):
        expect[(k, t - t % 1000)] += 1
    got = {(k, s): v for (k, v, s, e) in eng2.emitted}
    assert got == dict(expect)


# ---------------------------------------------------------------------
# MiniCluster execution
# ---------------------------------------------------------------------

class SumAgg(AggregateFunction):
    def create_accumulator(self):
        return 0

    def add(self, value, acc):
        return acc + value[1]

    def get_result(self, acc):
        return acc

    def merge(self, a, b):
        return a + b


def _records(n_keys=8, per_key=100):
    records = []
    for i in range(per_key):
        for k in range(n_keys):
            records.append(((f"k{k}", 1), i * 10))
    return records


@pytest.mark.parametrize("n_tms", [1, 3])
def test_minicluster_windowed_sum(n_tms):
    records = _records()
    sink = CollectSink()
    env = StreamExecutionEnvironment()
    env.use_mini_cluster(n_tms)
    env.set_parallelism(2)
    (env.from_collection(records, timestamped=True)
        .key_by(lambda v: v[0])
        .time_window(Time.milliseconds_of(500))
        .aggregate(SumAgg())
        .add_sink(sink))
    env.execute("mini-windowed-sum")
    assert sum(sink.values) == len(records)


def test_minicluster_map_parallelism_spread():
    """Subtasks of a parallel map land on different workers and all
    records arrive exactly once."""
    env = StreamExecutionEnvironment()
    env.use_mini_cluster(2)
    sink = CollectSink()
    (env.from_collection(list(range(1000)))
        .rebalance()
        .map(lambda v: v * 2, name="double")
        .add_sink(sink))
    env.execute("mini-map")
    assert sorted(sink.values) == [v * 2 for v in range(1000)]


class FailOnceAfterCheckpoint(MapFunction):
    def __init__(self):
        self.checkpoint_completed = False
        self.failed = False

    def notify_checkpoint_complete(self, checkpoint_id):
        self.checkpoint_completed = True

    def map(self, value):
        if self.checkpoint_completed and not self.failed:
            self.failed = True
            raise RuntimeError("induced worker failure")
        return value


class GatedCollectionSource(FromCollectionSource):
    """Deterministic fault-tolerance source (the
    StreamFaultToleranceTestBase pattern, SURVEY.md §4.4): once most
    records are out, trickle the tail one record per step until the
    induced failure has happened, so the checkpoint trigger → barrier →
    ack → notify round trip always completes while records still flow
    through the failing mapper.  The gate rides on a CLASS attribute
    because the source factory deep-copies the function per subtask —
    instance references would be cloned away from the shared failer."""

    gate = None  # shared FailOnceAfterCheckpoint, set by the test
    HOLD = 600   # tail records reserved for the trickle phase

    def emit_step(self, ctx, max_records):
        gate = type(self).gate
        free_until = len(self.items) - self.HOLD
        if (gate is not None and not gate.failed
                and self.offset >= free_until):
            if self.offset >= len(self.items):
                return False  # runway exhausted — finish, let asserts fail
            import time as _t
            _t.sleep(0.001)
            return super().emit_step(ctx, 1)
        return super().emit_step(ctx, max_records)


def test_minicluster_exactly_once_recovery():
    """Worker fails mid-stream after a checkpoint; the master restarts
    the job from the latest snapshot (the multi-worker
    EventTimeWindowCheckpointingITCase shape)."""
    records = _records(n_keys=6, per_key=300)
    sink = CollectSink()
    failer = FailOnceAfterCheckpoint()
    GatedCollectionSource.gate = failer
    env = StreamExecutionEnvironment()
    env.use_mini_cluster(2)
    env.enable_checkpointing(10)
    env.set_restart_strategy("fixed_delay", restart_attempts=3, delay_ms=0)
    (env.add_source(GatedCollectionSource(records, timestamped=True),
                    name="gated_source")
        .map(failer, name="failer")
        .key_by(lambda v: v[0])
        .time_window(Time.milliseconds_of(1000))
        .aggregate(SumAgg())
        .add_sink(sink))
    result = env.execute("mini-exactly-once")
    assert failer.failed
    assert result.restarts == 1
    assert result.checkpoints_completed >= 1
    assert sum(sink.values) == 6 * 300


def test_minicluster_checkpoint_gauges_and_latency():
    """Metric surface parity with LocalExecutor: checkpoint gauges and
    latency histograms exist on the mini-cluster path too."""
    records = _records(n_keys=4, per_key=2000)
    env = StreamExecutionEnvironment()
    env.use_mini_cluster(2)
    env.enable_checkpointing(1)
    env.set_latency_tracking_interval(5)
    sink = CollectSink()
    # the source holds its tail until a checkpoint has completed: on a
    # loaded host the 8,000 records were through before the first one
    from flink_tpu.runtime.chaos import CheckpointGatedSource
    CheckpointGatedSource.completed = False
    (env.add_source(CheckpointGatedSource(records, timestamped=True))
        .key_by(lambda v: v[0])
        .time_window(Time.milliseconds_of(500))
        .aggregate(SumAgg())
        .add_sink(sink))
    result = env.execute("mini-metrics")
    assert result.checkpoints_completed >= 1
    dump = env.get_metric_registry().dump()
    assert dump["mini-metrics.checkpointing.numberOfCompletedCheckpoints"] >= 1
    assert dump["mini-metrics.checkpointing.lastCompletedCheckpointId"] >= 1
    assert any(".latency." in k for k in dump), "no latency histograms"
    # numRecordsIn reflects this attempt's records, once each
    ins = [v for k, v in dump.items() if k.endswith("numRecordsIn")]
    assert sum(ins) > 0


def test_minicluster_cancellation():
    import itertools

    from flink_tpu.streaming.sources import SourceFunction

    class Infinite(SourceFunction):
        def __init__(self):
            self._running = True

        def run(self, ctx):
            for i in itertools.count():
                if not self._running:
                    return
                ctx.collect(i)

        def cancel(self):
            self._running = False

    env = StreamExecutionEnvironment()
    env.use_mini_cluster(2)
    sink = CollectSink()
    env.add_source(Infinite()).map(lambda v: v).add_sink(sink)
    client = env.execute_async("mini-cancel")
    import time as _t
    _t.sleep(0.2)
    client.cancel()
    result = client.wait(timeout=10)
    assert result.cancelled


# ---------------------------------------------------------------------
# Mesh engine driven from the JobGraph (the full framework path)
# ---------------------------------------------------------------------

def _mesh_job(env, events, agg, size_ms=1000):
    sink = CollectSink()
    stream = env.from_collection(events)
    stream = stream.assign_timestamps_and_watermarks(
        BoundedOutOfOrdernessTimestampExtractor(0, lambda e: e[1]))
    (stream.key_by(lambda e: e[0])
        .window(TumblingEventTimeWindows.of(size_ms))
        .aggregate(agg, window_function=(
            lambda key, w, vals: [(key, w.start, vals[0])]))
        .add_sink(sink))
    return sink


def _sorted_events(n=400, n_keys=40, horizon=4000, seed=1):
    rng = np.random.default_rng(seed)
    return sorted(((int(k), int(t)) for k, t in
                   zip(rng.integers(0, n_keys, n),
                       rng.integers(0, horizon, n))), key=lambda e: e[1])


def test_mesh_window_job_on_minicluster(mesh):
    """keyBy().window().aggregate(device_agg) over the 8-device mesh,
    executed by the multi-worker MiniCluster from a JobGraph — the
    VERDICT r1 'connect the mesh path to the framework' milestone."""
    events = _sorted_events()
    env = StreamExecutionEnvironment()
    env.set_mesh(mesh).use_mini_cluster(2)
    env.set_parallelism(2)
    sink = _mesh_job(env, events, CountAggregate())
    env.execute("mesh-window-job")
    expect = collections.Counter()
    for k, t in events:
        expect[(k, t - t % 1000)] += 1
    got = {(k, s): int(v) for (k, s, v) in sink.values}
    assert got == dict(expect)


def test_mesh_window_job_differential_vs_scalar(mesh):
    """Mesh path vs scalar WindowOperator on identical input — the
    differential-testing spine applied to the sharded engine."""
    events = _sorted_events(n=600, n_keys=25, horizon=3000, seed=9)

    env1 = StreamExecutionEnvironment()
    env1.set_mesh(mesh)
    sink1 = _mesh_job(env1, events, CountAggregate())
    env1.execute("mesh")

    env2 = StreamExecutionEnvironment()
    sink2 = CollectSink()
    stream = env2.from_collection(events)
    stream = stream.assign_timestamps_and_watermarks(
        BoundedOutOfOrdernessTimestampExtractor(0, lambda e: e[1]))
    (stream.key_by(lambda e: e[0])
        .window(TumblingEventTimeWindows.of(1000))
        .disable_device_operator()
        .aggregate(CountAggregate(), window_function=(
            lambda key, w, vals: [(key, w.start, vals[0])]))
        .add_sink(sink2))
    env2.execute("scalar")

    got1 = {(k, s): int(v) for (k, s, v) in sink1.values}
    got2 = {(k, s): int(v) for (k, s, v) in sink2.values}
    assert got1 == got2


def test_mesh_window_job_checkpoint_recovery(mesh):
    """Failure + restart with the mesh engine state snapshot/restored
    through the barrier checkpoint path."""
    events = _sorted_events(n=900, n_keys=12, horizon=3000, seed=4)
    failer = FailOnceAfterCheckpoint()
    env = StreamExecutionEnvironment()
    env.set_mesh(mesh)
    env.enable_checkpointing(10)
    env.set_restart_strategy("fixed_delay", restart_attempts=3, delay_ms=0)
    sink = CollectSink()
    stream = env.from_collection(events)
    stream = stream.map(failer, name="failer")
    stream = stream.assign_timestamps_and_watermarks(
        BoundedOutOfOrdernessTimestampExtractor(0, lambda e: e[1]))
    (stream.key_by(lambda e: e[0])
        .window(TumblingEventTimeWindows.of(1000))
        .aggregate(CountAggregate(), window_function=(
            lambda key, w, vals: [(key, w.start, vals[0])]))
        .add_sink(sink))
    result = env.execute("mesh-recovery")
    assert failer.failed
    assert result.restarts == 1
    expect = collections.Counter()
    for k, t in events:
        expect[(k, t - t % 1000)] += 1
    got = {(k, s): int(v) for (k, s, v) in sink.values}
    assert got == dict(expect)


# ---------------------------------------------------------------------
# MeshSlidingWindows: pane-composed sliding on the mesh
# ---------------------------------------------------------------------

def test_mesh_sliding_counts_match_reference(mesh):
    from flink_tpu.parallel.mesh_windows import MeshSlidingWindows
    eng = MeshSlidingWindows(CountAggregate(), 3000, 1000, mesh,
                             capacity_per_window_shard=256, step_batch=64)
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 40, 600)
    ts = np.sort(rng.integers(0, 6000, 600))
    eng.process_batch(keys, ts)
    eng.advance_watermark(20_000)
    expect = collections.Counter()
    for k, t in zip(keys.tolist(), ts.tolist()):
        pane = t - t % 1000
        for w in range(pane - 2000, pane + 1000, 1000):
            expect[(k, w, w + 3000)] += 1
    got = {(k, s, e): v for (k, v, s, e) in eng.emitted}
    assert got == dict(expect)


def test_mesh_sliding_incremental_watermarks_match_vectorized(mesh):
    from flink_tpu.parallel.mesh_windows import MeshSlidingWindows
    from flink_tpu.streaming.vectorized import VectorizedSlidingWindows
    rng = np.random.default_rng(5)
    n = 800
    keys = rng.integers(0, 30, n).astype(np.uint64)
    ts = np.sort(rng.integers(0, 8000, n))
    vals = rng.random(n).astype(np.float32)

    ref = VectorizedSlidingWindows(SumAggregate(), 2000, 1000,
                                   initial_capacity=512)
    ref.process_batch(keys, ts, vals, key_hashes=None)
    ref.advance_watermark(30_000)

    eng = MeshSlidingWindows(SumAggregate(), 2000, 1000, mesh,
                             capacity_per_window_shard=128, step_batch=64)
    CH = 200
    for i in range(0, n, CH):
        sl = slice(i, i + CH)
        eng.process_batch(keys[sl], ts[sl], vals[sl])
        eng.advance_watermark(int(ts[sl][-1]) - 1)
    eng.advance_watermark(30_000)

    want = {(int(k), s, e): round(float(r), 3)
            for k, r, s, e in ref.emitted}
    got = {(int(k), s, e): round(float(r), 3)
           for k, r, s, e in eng.emitted}
    assert got == want


def test_mesh_sliding_snapshot_restore(mesh):
    from flink_tpu.parallel.mesh_windows import MeshSlidingWindows
    rng = np.random.default_rng(7)
    n = 400
    keys = rng.integers(0, 20, n)
    ts = np.sort(rng.integers(0, 5000, n))

    ref = MeshSlidingWindows(CountAggregate(), 2000, 1000, mesh,
                             capacity_per_window_shard=128, step_batch=64)
    ref.process_batch(keys, ts)
    ref.advance_watermark(20_000)

    a = MeshSlidingWindows(CountAggregate(), 2000, 1000, mesh,
                           capacity_per_window_shard=128, step_batch=64)
    a.process_batch(keys[:200], ts[:200])
    a.advance_watermark(int(ts[199]) - 1)
    snap = a.snapshot()
    b = MeshSlidingWindows(CountAggregate(), 2000, 1000, mesh,
                           capacity_per_window_shard=128, step_batch=64)
    b.restore(snap)
    b.process_batch(keys[200:], ts[200:])
    b.advance_watermark(20_000)
    combined = {(int(k), s, e): v for k, v, s, e in a.emitted}
    for k, v, s, e in b.emitted:
        combined[(int(k), s, e)] = v
    want = {(int(k), s, e): v for k, v, s, e in ref.emitted}
    assert combined == want


def test_mesh_sliding_parked_pane_not_lost(mesh):
    """Data spanning more panes than usable ring slots, then one big
    watermark: windows must not fire while one of their panes is
    parked (code-review regression — pane 6000's records were lost)."""
    from flink_tpu.parallel.mesh_windows import MeshSlidingWindows
    eng = MeshSlidingWindows(CountAggregate(), 2000, 1000, mesh,
                             capacity_per_window_shard=64, step_batch=32,
                             extra_ring=4)  # usable ring = 6 panes
    rng = np.random.default_rng(11)
    n = 300
    keys = rng.integers(0, 10, n)
    ts = rng.integers(0, 10_000, n)  # 10 panes > 6 usable slots
    eng.process_batch(keys, ts)
    eng.advance_watermark(50_000)
    expect = collections.Counter()
    for k, t in zip(keys.tolist(), ts.tolist()):
        pane = t - t % 1000
        for w in range(pane - 1000, pane + 1000, 1000):
            expect[(k, w, w + 2000)] += 1
    got = {(k, s, e): v for (k, v, s, e) in eng.emitted}
    assert got == dict(expect)


def test_mesh_sliding_blocked_window_fires_on_later_call(mesh):
    """A window due at watermark W but blocked on a parked pane must
    fire on a LATER advance_watermark call once the pane unparks —
    not vanish behind the fired horizon (round-2 advisor finding:
    _fired_horizon advanced past skipped windows)."""
    from flink_tpu.parallel.mesh_windows import MeshSlidingWindows

    def build():
        return MeshSlidingWindows(CountAggregate(), 2000, 1000, mesh,
                                  capacity_per_window_shard=64,
                                  step_batch=32, extra_ring=4)

    eng = build()
    # pane 6000 claims ring slot (6000//1000) % 6 == 0 first...
    eng.process_batch(np.array([1, 1, 1]), np.array([6500, 6600, 6700]))
    # ...then pane 0 (same slot 0) arrives out of order and parks
    eng.process_batch(np.array([2, 2]), np.array([500, 600]))
    # windows [-1000,1000) and [0,2000) are due but blocked on the
    # parked pane — nothing may fire yet
    assert eng.advance_watermark(1999) == 0
    assert eng.emitted == []
    # blocked windows survive a checkpoint cycle too
    restored = build()
    restored.restore(eng.snapshot())
    for e in (eng, restored):
        # pane 6000's windows fire and prune, slot 0 frees, pane 0
        # unparks, and the two previously-blocked windows fire
        e.advance_watermark(7999)
        got = {(k, s, e_): v for (k, v, s, e_) in e.emitted}
        assert got == {(2, -1000, 1000): 2, (2, 0, 2000): 2,
                       (1, 5000, 7000): 3, (1, 6000, 8000): 3}


def test_mesh_sliding_window_job_on_minicluster(mesh):
    """keyBy().window(Sliding...).aggregate(device_agg) over the mesh,
    executed from a JobGraph — the sliding twin of the tumbling mesh
    job."""
    from flink_tpu.streaming.windowing import SlidingEventTimeWindows
    events = _sorted_events(n=500, n_keys=30, horizon=5000, seed=13)
    env = StreamExecutionEnvironment()
    env.set_mesh(mesh).use_mini_cluster(2)
    env.set_parallelism(2)
    sink = CollectSink()
    stream = env.from_collection(events)
    stream = stream.assign_timestamps_and_watermarks(
        BoundedOutOfOrdernessTimestampExtractor(0, lambda e: e[1]))
    (stream.key_by(lambda e: e[0])
        .window(SlidingEventTimeWindows.of(2000, 1000))
        .aggregate(CountAggregate(), window_function=(
            lambda key, w, vals: [(key, w.start, w.end, vals[0])]))
        .add_sink(sink))
    env.execute("mesh-sliding-window-job")
    expect = collections.Counter()
    for k, t in events:
        pane = t - t % 1000
        for w in range(pane - 1000, pane + 1000, 1000):
            expect[(k, w, w + 2000)] += 1
    got = {(k, s, e): int(v) for (k, s, e, v) in sink.values}
    assert got == dict(expect)

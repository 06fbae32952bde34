"""Mesh-sharded log tier: the all_to_all keyBy exchange feeding
per-shard log-structured engines (parallel/mesh_log.py).

Every test cross-checks the mesh engine against the single-host log
engine on the same input — key groups partition keys disjointly, so
the results must be identical (the mesh moves the exchange, not the
math)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from flink_tpu.ops.device_agg import SumAggregate
from flink_tpu.ops.sketches import (
    CountMinSketchAggregate,
    HyperLogLogAggregate,
    QuantileSketchAggregate,
)
import flink_tpu.native as nat

pytestmark = pytest.mark.skipif(not nat.available(),
                                reason="native runtime required")


def _mesh(n=8):
    devs = np.array(jax.devices()[:n])
    if len(devs) < n:
        pytest.skip(f"need {n} devices")
    return Mesh(devs, ("kg",))


def _hll_inputs(n=5000, keys=37, seed=0):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, keys, n).astype(np.int64)
    ts = np.sort(rng.integers(0, 3000, n)).astype(np.int64)
    users = rng.integers(0, 500, n)
    return k, ts, users


def test_mesh_hll_tumbling_matches_single_host():
    from flink_tpu.parallel.mesh_log import MeshLogTumblingWindows
    from flink_tpu.streaming.log_windows import (
        LogStructuredTumblingWindows,
    )
    from flink_tpu.streaming.vectorized import hash_keys_np

    mesh = _mesh()
    agg = HyperLogLogAggregate(precision=10)
    k, ts, users = _hll_inputs()
    vh = hash_keys_np(users)

    eng = MeshLogTumblingWindows(agg, 1000, mesh, step_batch=512,
                                 finish_tier="host")
    ref = LogStructuredTumblingWindows(agg, 1000, finish_tier="host")
    for e in (eng, ref):
        e.process_batch(k, ts, None, value_hashes=vh)
        e.advance_watermark(10_000)
    got = {(int(kk), int(s)): float(v) for kk, v, s, _ in eng.emitted}
    want = {(int(kk), int(s)): float(v) for kk, v, s, _ in ref.emitted}
    assert got == want
    assert len(got) == len({(int(kk), int(tt) - int(tt) % 1000)
                            for kk, tt in zip(k, ts)})


def test_mesh_sum_sliding_matches_single_host():
    from flink_tpu.parallel.mesh_log import MeshLogSlidingWindows
    from flink_tpu.streaming.log_windows import (
        LogStructuredSlidingWindows,
    )

    mesh = _mesh()
    agg = SumAggregate(np.float64)
    rng = np.random.default_rng(1)
    n = 4000
    k = rng.integers(0, 23, n).astype(np.int64)
    ts = np.sort(rng.integers(0, 2500, n)).astype(np.int64)
    v = rng.integers(1, 100, n).astype(np.float64)

    eng = MeshLogSlidingWindows(agg, 1000, 500, mesh, step_batch=512)
    ref = LogStructuredSlidingWindows(agg, 1000, 500)
    for e in (eng, ref):
        e.process_batch(k, ts, v)
        e.advance_watermark(10_000)
    got = {(int(kk), int(s), int(e2)): float(vv)
           for kk, vv, s, e2 in eng.emitted}
    want = {(int(kk), int(s), int(e2)): float(vv)
            for kk, vv, s, e2 in ref.emitted}
    assert got == want


def test_mesh_quantile_matches_single_host():
    from flink_tpu.parallel.mesh_log import MeshLogTumblingWindows
    from flink_tpu.streaming.log_windows import (
        LogStructuredTumblingWindows,
    )

    mesh = _mesh()
    agg = QuantileSketchAggregate(quantiles=(0.5, 0.99))
    rng = np.random.default_rng(2)
    n = 3000
    k = rng.integers(0, 11, n).astype(np.int64)
    ts = np.sort(rng.integers(0, 2000, n)).astype(np.int64)
    v = rng.gamma(2.0, 10.0, n)

    eng = MeshLogTumblingWindows(agg, 1000, mesh, step_batch=512)
    ref = LogStructuredTumblingWindows(agg, 1000)
    for e in (eng, ref):
        e.process_batch(k, ts, v)
        e.advance_watermark(10_000)
    got = {(int(kk), int(s)): tuple(np.round(vv, 9))
           for kk, vv, s, _ in eng.emitted}
    want = {(int(kk), int(s)): tuple(np.round(vv, 9))
            for kk, vv, s, _ in ref.emitted}
    assert got == want


def test_mesh_sessions_match_single_host():
    from flink_tpu.parallel.mesh_log import MeshLogSessionWindows
    from flink_tpu.streaming.log_windows import (
        LogStructuredSessionWindows,
    )
    from flink_tpu.streaming.vectorized import hash_keys_np

    mesh = _mesh()
    agg = CountMinSketchAggregate(depth=4, width=256)
    rng = np.random.default_rng(3)
    n = 3000
    k = rng.integers(0, 29, n).astype(np.int64)
    ts = np.sort(rng.integers(0, 50_000, n)).astype(np.int64)
    items = rng.integers(0, 64, n)
    vh = hash_keys_np(items)
    ones = np.ones(n, np.float64)

    eng = MeshLogSessionWindows(agg, 100, mesh, step_batch=512)
    ref = LogStructuredSessionWindows(agg, 100)
    for e in (eng, ref):
        # two batches + an intermediate watermark: exercises retained
        # open sessions crossing a fire
        e.process_batch(k[:n // 2], ts[:n // 2], ones[:n // 2],
                        value_hashes=vh[:n // 2])
        e.advance_watermark(int(ts[n // 2 - 1]) - 200)
        e.process_batch(k[n // 2:], ts[n // 2:], ones[n // 2:],
                        value_hashes=vh[n // 2:])
        e.advance_watermark(100_000)
    got = {(int(kk), int(s), int(e2)): int(t)
           for kk, t, s, e2 in eng.emitted}
    want = {(int(kk), int(s), int(e2)): int(t)
            for kk, t, s, e2 in ref.emitted}
    assert got == want


def test_mesh_watermark_mid_stream_and_late_drops():
    from flink_tpu.parallel.mesh_log import MeshLogTumblingWindows
    from flink_tpu.streaming.log_windows import (
        LogStructuredTumblingWindows,
    )

    mesh = _mesh()
    agg = SumAggregate(np.float64)
    eng = MeshLogTumblingWindows(agg, 1000, mesh, step_batch=64)
    ref = LogStructuredTumblingWindows(agg, 1000)
    k1 = np.arange(40, dtype=np.int64) % 7
    ts1 = np.linspace(0, 1999, 40).astype(np.int64)
    v1 = np.ones(40)
    for e in (eng, ref):
        e.process_batch(k1, ts1, v1)
        e.advance_watermark(999)          # fires window [0, 1000)
        # late: window [0,1000) already fired
        e.process_batch(np.array([1], np.int64), np.array([10], np.int64),
                        np.array([5.0]))
        e.advance_watermark(5000)
    assert eng.num_late_dropped == ref.num_late_dropped == 1
    got = {(int(kk), int(s)): float(vv) for kk, vv, s, _ in eng.emitted}
    want = {(int(kk), int(s)): float(vv) for kk, vv, s, _ in ref.emitted}
    assert got == want


def test_mesh_snapshot_restore_roundtrip():
    from flink_tpu.parallel.mesh_log import MeshLogTumblingWindows
    from flink_tpu.streaming.vectorized import hash_keys_np

    mesh = _mesh()
    agg = HyperLogLogAggregate(precision=10)
    k, ts, users = _hll_inputs(seed=4)
    vh = hash_keys_np(users)
    half = len(k) // 2

    eng = MeshLogTumblingWindows(agg, 1000, mesh, step_batch=512,
                                 finish_tier="host")
    eng.process_batch(k[:half], ts[:half], None, value_hashes=vh[:half])
    snap = eng.snapshot()

    eng2 = MeshLogTumblingWindows(agg, 1000, mesh, step_batch=512,
                                  finish_tier="host")
    eng2.restore(snap)
    for e in (eng, eng2):
        e.process_batch(k[half:], ts[half:], None, value_hashes=vh[half:])
        e.advance_watermark(10_000)
    got = {(int(kk), int(s)): float(v) for kk, v, s, _ in eng2.emitted}
    want = {(int(kk), int(s)): float(v) for kk, v, s, _ in eng.emitted}
    assert got == want


def test_mesh_shard_count_mismatch_rejected():
    from flink_tpu.parallel.mesh_log import MeshLogTumblingWindows

    mesh8 = _mesh(8)
    devs = np.array(jax.devices()[:4])
    mesh4 = Mesh(devs, ("kg",))
    agg = SumAggregate(np.float64)
    e8 = MeshLogTumblingWindows(agg, 1000, mesh8)
    e4 = MeshLogTumblingWindows(agg, 1000, mesh4)
    e8.process_batch(np.arange(16, dtype=np.int64),
                     np.zeros(16, np.int64), np.ones(16))
    with pytest.raises(ValueError, match="8 shards"):
        e4.restore(e8.snapshot())


def test_mesh_log_engine_factory_scope():
    from flink_tpu.parallel.mesh_log import mesh_log_engine_for_assigner
    from flink_tpu.parallel.mesh_log import (
        MeshLogSessionWindows,
        MeshLogSlidingWindows,
        MeshLogTumblingWindows,
    )
    from flink_tpu.ops.device_agg import MinAggregate
    from flink_tpu.streaming.windowing import (
        EventTimeSessionWindows,
        SlidingEventTimeWindows,
        TumblingEventTimeWindows,
    )

    mesh = _mesh()
    hll = HyperLogLogAggregate(precision=10)
    assert isinstance(
        mesh_log_engine_for_assigner(
            TumblingEventTimeWindows.of(1000), hll, mesh),
        MeshLogTumblingWindows)
    assert isinstance(
        mesh_log_engine_for_assigner(
            SlidingEventTimeWindows.of(1000, 500), hll, mesh),
        MeshLogSlidingWindows)
    assert isinstance(
        mesh_log_engine_for_assigner(
            EventTimeSessionWindows.with_gap(100),
            CountMinSketchAggregate(), mesh),
        MeshLogSessionWindows)
    # Min has no cell decomposition: no log tier on the mesh either
    assert mesh_log_engine_for_assigner(
        TumblingEventTimeWindows.of(1000),
        MinAggregate(np.float64), mesh) is None


# ---------------------------------------------------------------------
# framework-level: SQL + DataStream jobs riding the mesh log tier
# ---------------------------------------------------------------------

def _synth(n=6000, n_keys=40, horizon=3000, seed=7):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, n).astype(np.int64)
    ts = np.sort(rng.integers(0, horizon, n)).astype(np.int64)
    users = rng.integers(0, 400, n).astype(np.int64)
    return keys, ts, users


def _run_sql(keys, ts, users, mesh):
    from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    from flink_tpu.streaming.columnar import ColumnarCollectSink
    from flink_tpu.table import StreamTableEnvironment

    env = StreamExecutionEnvironment()
    if mesh is not None:
        env.set_mesh(mesh)
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table("ev", t_env.from_columns(
        {"k": keys, "u": users, "ts": ts}, rowtime="ts", chunk=2048))
    out = t_env.sql_query(
        "SELECT k, APPROX_COUNT_DISTINCT(u) AS d, TUMBLE_START(ts) AS ws "
        "FROM ev GROUP BY TUMBLE(ts, INTERVAL '1' SECOND), k")
    sink = ColumnarCollectSink()
    out.to_append_stream(batched=True).add_sink(sink)
    env.execute("sql-mesh" if mesh is not None else "sql-host")
    return {(int(k), int(ws)): round(float(d), 6)
            for k, d, ws in sink.rows()}


def test_sql_tumble_rides_mesh_and_matches_host():
    """A SQL TUMBLE APPROX_COUNT_DISTINCT query with env.set_mesh runs
    the columnar plan on the mesh log tier (all_to_all keyBy) and
    produces exactly the single-host columnar results."""
    mesh = _mesh()
    keys, ts, users = _synth()
    got = _run_sql(keys, ts, users, mesh)
    want = _run_sql(keys, ts, users, None)
    assert got == want and len(got) > 0


def test_columnar_operator_selects_mesh_tier():
    from flink_tpu.parallel.mesh_log import _MeshShardedLogEngine
    from flink_tpu.streaming.columnar import ColumnarWindowOperator
    from flink_tpu.streaming.windowing import TumblingEventTimeWindows

    mesh = _mesh()
    op = ColumnarWindowOperator(
        TumblingEventTimeWindows.of(1000), HyperLogLogAggregate(10),
        "k", "u", [("k", "key"), ("d", "agg")], mesh=mesh)
    from flink_tpu.streaming.window_engines import select_engine
    eng, tier = select_engine(op, np.dtype(np.int64))
    assert isinstance(eng, _MeshShardedLogEngine) and tier == "mesh_log"


def test_datastream_session_job_on_mesh():
    """keyBy().window(EventTimeSessionWindows).aggregate(CountMin) on a
    mesh-enabled environment: sessions ride the mesh log session
    engine; results equal the meshless run."""
    from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    from flink_tpu.streaming.sources import (
        BoundedOutOfOrdernessTimestampExtractor,
        CollectSink,
    )
    from flink_tpu.streaming.windowing import EventTimeSessionWindows

    rng = np.random.default_rng(11)
    n = 3000
    events = sorted(
        ((int(k), int(u), int(t)) for k, u, t in zip(
            rng.integers(0, 24, n), rng.integers(0, 64, n),
            rng.integers(0, 60_000, n))),
        key=lambda e: e[2])

    def run(mesh):
        env = StreamExecutionEnvironment()
        if mesh is not None:
            env.set_mesh(mesh)
        agg = CountMinSketchAggregate(depth=4, width=256)
        agg.extract_value = lambda rec: rec[1]
        sink = CollectSink()
        stream = env.from_collection(events)
        stream = stream.assign_timestamps_and_watermarks(
            BoundedOutOfOrdernessTimestampExtractor(0, lambda e: e[2]))
        (stream.key_by(lambda e: e[0])
            .window(EventTimeSessionWindows.with_gap(500))
            .aggregate(agg, window_function=(
                lambda key, w, vals: [(key, w.start, w.end,
                                       int(vals[0]))]))
            .add_sink(sink))
        env.execute("session-mesh" if mesh is not None else "session-host")
        return {(k, s, e): t for (k, s, e, t) in sink.values}

    got = run(_mesh())
    want = run(None)
    assert got == want and len(got) > 0


def test_sql_mesh_factory_at_parallelism_2():
    """Pod-topology SQL: a mesh FACTORY with parallelism 2 keeps the
    mesh tier per subtask (each builds its own 4-device mesh) and
    results equal the meshless run."""
    import jax
    from jax.sharding import Mesh

    def factory():
        devices = jax.devices()
        return Mesh(np.array(devices[:4]), ("kg",))

    rng = np.random.default_rng(19)
    n = 6000
    cols = {
        "k": rng.integers(0, 24, n).astype(np.int64),
        "u": rng.integers(0, 64, n).astype(np.int64),
        "ts": np.sort(rng.integers(0, 4000, n).astype(np.int64)),
    }

    def run(mesh):
        from flink_tpu.streaming.datastream import (
            StreamExecutionEnvironment,
        )
        from flink_tpu.streaming.sources import CollectSink
        from flink_tpu.table import StreamTableEnvironment
        env = StreamExecutionEnvironment()
        if mesh is not None:
            env.set_mesh(mesh)
            env.set_parallelism(2)
        t_env = StreamTableEnvironment.create(env)
        t_env.register_table("ev", t_env.from_columns(
            dict(cols), rowtime="ts"))
        out = t_env.sql_query(
            "SELECT k, APPROX_COUNT_DISTINCT(u) AS d FROM ev "
            "GROUP BY TUMBLE(ts, INTERVAL '1' SECOND), k")
        sink = CollectSink()
        out.to_append_stream().add_sink(sink)
        env.execute("sql-mesh-factory")
        return sorted(sink.values)

    got = run(factory)
    want = run(None)
    assert got == want and len(got) > 0

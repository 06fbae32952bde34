"""Columnar (RecordBatch) execution tier: the SQL planner's vectorized
physical plan must agree with the row-at-a-time lowering, and plans
outside its shape must fall back to the row path."""

import numpy as np
import pytest

from flink_tpu.streaming.columnar import (
    ColumnarCollectSink,
    ColumnarSource,
    ColumnarWindowOperator,
    RecordBatch,
)
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.sources import (
    BoundedOutOfOrdernessTimestampExtractor,
    CollectSink,
)
from flink_tpu.table import StreamTableEnvironment


def synth(n, n_keys, t_span, seed):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, n).astype(np.uint64)
    ts = np.sort(rng.integers(0, t_span, n).astype(np.int64))
    users = rng.integers(0, 2 ** 40, n).astype(np.uint64)
    return keys, ts, users


SQL = ("SELECT k, APPROX_COUNT_DISTINCT(u) AS d "
       "FROM ev GROUP BY TUMBLE(ts, INTERVAL '1' SECOND), k")


def run_columnar(keys, ts, users, sql=SQL):
    env = StreamExecutionEnvironment()
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table("ev", t_env.from_columns(
        {"k": keys, "u": users, "ts": ts}, rowtime="ts", chunk=4096))
    out = t_env.sql_query(sql)
    sink = ColumnarCollectSink()
    out.to_append_stream(batched=True).add_sink(sink)
    env.execute("columnar")
    return sink


def run_rowpath(keys, ts, users, sql=SQL):
    env = StreamExecutionEnvironment()
    events = list(zip(keys.tolist(), users.tolist(), ts.tolist()))
    stream = env.from_collection(events).assign_timestamps_and_watermarks(
        BoundedOutOfOrdernessTimestampExtractor(0, lambda e: e[2]))
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table("ev", t_env.from_data_stream(
        stream, ["k", "u", "ts"], rowtime="ts"))
    out = t_env.sql_query(sql)
    sink = CollectSink()
    out.to_append_stream().add_sink(sink)
    env.execute("rowpath")
    return sink


def test_columnar_plan_is_chosen():
    keys, ts, users = synth(2000, 50, 3000, seed=1)
    env = StreamExecutionEnvironment()
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table("ev", t_env.from_columns(
        {"k": keys, "u": users, "ts": ts}, rowtime="ts"))
    out = t_env.sql_query(SQL)
    assert getattr(out, "columnar", False)
    assert out.stream.node.name == "columnar_window_agg"


def test_columnar_matches_row_path():
    keys, ts, users = synth(6000, 80, 3000, seed=2)
    col = run_columnar(keys, ts, users)
    row = run_rowpath(keys, ts, users)
    got = {}
    for k, d in col.rows():
        got[int(k)] = got.get(int(k), 0) + round(float(d))
    want = {}
    for k, d in row.values:
        want[int(k)] = want.get(int(k), 0) + round(float(d))
    assert got == want


def test_columnar_window_props_and_order():
    keys, ts, users = synth(3000, 40, 2500, seed=3)
    sql = ("SELECT TUMBLE_END(ts, INTERVAL '1' SECOND) AS we, "
           "APPROX_COUNT_DISTINCT(u) AS d, k "
           "FROM ev GROUP BY TUMBLE(ts, INTERVAL '1' SECOND), k")
    col = run_columnar(keys, ts, users, sql)
    row = run_rowpath(keys, ts, users, sql)
    got = sorted((int(we), int(k), round(float(d))) for we, d, k in col.rows())
    want = sorted((int(we), int(k), round(float(d))) for we, d, k in row.values)
    assert got == want


def test_non_eligible_plan_falls_back_to_rows():
    """Two aggregates -> outside the columnar shape; the plan must
    explode batches to rows and still produce correct results."""
    keys, ts, users = synth(1000, 20, 2000, seed=4)
    sql = ("SELECT k, COUNT(*) AS c, SUM(u) AS s "
           "FROM ev GROUP BY TUMBLE(ts, INTERVAL '1' SECOND), k")
    env = StreamExecutionEnvironment()
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table("ev", t_env.from_columns(
        {"k": keys, "u": users, "ts": ts}, rowtime="ts", chunk=256))
    out = t_env.sql_query(sql)
    assert not getattr(out, "columnar", False)
    sink = CollectSink()
    out.to_append_stream().add_sink(sink)
    env.execute("fallback")
    row = run_rowpath(keys, ts, users, sql)
    assert sorted(sink.values) == sorted(row.values)


def test_columnar_source_rows_roundtrip():
    b = RecordBatch({"a": np.array([1, 2]), "b": np.array([3.0, 4.0])},
                    np.array([10, 20]))
    assert len(b) == 2
    assert list(b.rows()) == [(1, 3.0), (2, 4.0)]


def test_columnar_session_sql_with_hll_falls_back_cleanly():
    """SESSION window + HLL over a columnar table: the log session
    engine only takes Count-Min, so the operator falls back to the
    row-delivering VectorizedSessionWindows — and must still work
    (code-review regression: the fallback used to crash on flush)."""
    rng = np.random.default_rng(6)
    n = 3000
    keys = rng.integers(0, 30, n).astype(np.uint64)
    ts = np.sort(rng.integers(0, 5000, n).astype(np.int64))
    users = rng.integers(0, 2 ** 40, n).astype(np.uint64)
    sql = ("SELECT k, APPROX_COUNT_DISTINCT(u) AS d "
           "FROM ev GROUP BY SESSION(ts, INTERVAL '1' SECOND), k")
    env = StreamExecutionEnvironment()
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table("ev", t_env.from_columns(
        {"k": keys, "u": users, "ts": ts}, rowtime="ts", chunk=512))
    out = t_env.sql_query(sql)
    assert getattr(out, "columnar", False)
    sink = ColumnarCollectSink()
    out.to_append_stream(batched=True).add_sink(sink)
    env.execute("columnar-session")
    row = run_rowpath(keys, ts, users, sql)
    got = sorted((int(k), round(float(d))) for k, d in sink.rows())
    want = sorted((int(k), round(float(d))) for k, d in row.values)
    assert got == want


def test_columnar_exactly_once_recovery():
    """Columnar SQL pipeline through barrier checkpointing: induced
    failure after a completed checkpoint, fixed-delay restart, source
    resumes from the checkpointed batch offset, per-(key, window)
    counts are exactly-once (EventTimeWindowCheckpointingITCase shape
    for the RecordBatch tier)."""
    from flink_tpu.core.functions import MapFunction
    from flink_tpu.ops.device_agg import SumAggregate

    rng = np.random.default_rng(8)
    n, n_keys = 40_000, 50
    keys = rng.integers(0, n_keys, n).astype(np.uint64)
    ts = np.sort(rng.integers(0, 4000, n).astype(np.int64))

    class FailOnceAfterCheckpoint(MapFunction):
        def __init__(self):
            self.checkpoint_completed = False
            self.failed = False

        def notify_checkpoint_complete(self, checkpoint_id):
            self.checkpoint_completed = True

        def map(self, value):
            if self.checkpoint_completed and not self.failed:
                self.failed = True
                raise RuntimeError("induced failure after checkpoint")
            return value

    failer = FailOnceAfterCheckpoint()
    env = StreamExecutionEnvironment()
    env.enable_checkpointing(5)
    env.set_restart_strategy("fixed_delay", restart_attempts=3, delay_ms=0)
    t_env = StreamTableEnvironment.create(env)
    table = t_env.from_columns({"k": keys, "c": np.ones(n, np.float64),
                                "ts": ts}, rowtime="ts", chunk=1024)
    # the failing map rides between source and window op (one element
    # per RecordBatch)
    table.stream = table.stream.map(failer, name="failer")
    t_env.register_table("ev", table)
    out = t_env.sql_query(
        "SELECT k, SUM(c) AS c FROM ev "
        "GROUP BY TUMBLE(ts, INTERVAL '1' SECOND), k")
    assert getattr(out, "columnar", False)
    sink = ColumnarCollectSink()
    out.to_append_stream(batched=True).add_sink(sink)
    result = env.execute("columnar-exactly-once")

    assert failer.failed, "the induced failure never fired"
    assert result.restarts == 1
    assert result.checkpoints_completed >= 1
    total = sum(float(c) for _, c in sink.rows())
    assert total == n  # exactly-once: every record counted once


def test_columnar_string_key_wordcount_matches_rowpath():
    """String key column over the columnar tier: the planner's TUMBLE
    SUM plan lands on the fused intern+sum engine and matches the
    row path exactly (round-2 verdict: real wordcount-over-strings
    must ride a fast tier)."""
    rng = np.random.default_rng(8)
    n = 3000
    vocab = np.asarray([f"w{i}" for i in range(40)])
    words = vocab[rng.integers(0, 40, n)]
    ts = np.sort(rng.integers(0, 3000, n).astype(np.int64))
    ones = np.ones(n, np.float64)
    sql = ("SELECT k, SUM(u) AS c "
           "FROM ev GROUP BY TUMBLE(ts, INTERVAL '1' SECOND), k")
    env = StreamExecutionEnvironment()
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table("ev", t_env.from_columns(
        {"k": words, "u": ones, "ts": ts}, rowtime="ts", chunk=512))
    out = t_env.sql_query(sql)
    assert getattr(out, "columnar", False)
    sink = ColumnarCollectSink()
    out.to_append_stream(batched=True).add_sink(sink)
    env.execute("str-wordcount-columnar")
    row = run_rowpath(words, ts, ones.astype(np.int64), sql)
    got = sorted((str(k), float(v)) for k, v in sink.rows())
    want = sorted((str(k), float(v)) for k, v in row.values)
    assert got == want
    # the fused tier must actually be what this plan's operator
    # selects for a string key column — not a silent fallback
    from flink_tpu.streaming.columnar import ColumnarWindowOperator
    from flink_tpu.streaming.log_windows import StringSumTumblingWindows
    from flink_tpu.streaming.windowing import TumblingEventTimeWindows
    from flink_tpu.ops.device_agg import SumAggregate
    op = ColumnarWindowOperator(
        TumblingEventTimeWindows.of(1000), SumAggregate(np.float64),
        "k", "u", [("k", "key"), ("c", "agg")])
    from flink_tpu.streaming.window_engines import select_engine
    engine, tier = select_engine(op, words.dtype)
    assert isinstance(engine, StringSumTumblingWindows)
    assert tier == "string_sum"


def test_columnar_interval_join_matches_rowpath():
    """SQL interval join over two columnar tables rides the vectorized
    hash-join operator and matches the row-level interval join."""
    from flink_tpu.streaming.sources import (
        BoundedOutOfOrdernessTimestampExtractor)
    rng = np.random.default_rng(12)
    nl = nr = 600
    lk = rng.integers(0, 15, nl).astype(np.int64)
    lts = np.sort(rng.integers(0, 4000, nl).astype(np.int64))
    lid = np.arange(nl)
    rk = rng.integers(0, 15, nr).astype(np.int64)
    rts = np.sort(rng.integers(0, 4000, nr).astype(np.int64))
    rid = np.arange(1000, 1000 + nr)
    SQL = ("SELECT a.lid, b.rid FROM l AS a JOIN r AS b ON a.k = b.rk "
           "AND a.ts BETWEEN b.rts - INTERVAL '300' MILLISECOND "
           "AND b.rts + INTERVAL '500' MILLISECOND")

    env = StreamExecutionEnvironment()
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table("l", t_env.from_columns(
        {"lid": lid, "k": lk, "ts": lts}, rowtime="ts", chunk=256))
    t_env.register_table("r", t_env.from_columns(
        {"rid": rid, "rk": rk, "rts": rts}, rowtime="rts", chunk=256))
    out = t_env.sql_query(SQL)
    assert getattr(out, "columnar", False), "must stay columnar"
    sink = ColumnarCollectSink()
    out.to_append_stream(batched=True).add_sink(sink)
    env.execute("cj")

    # row path reference
    env2 = StreamExecutionEnvironment()
    t2 = StreamTableEnvironment.create(env2)
    ls = env2.from_collection(
        list(zip(lid.tolist(), lk.tolist(), lts.tolist()))
    ).assign_timestamps_and_watermarks(
        BoundedOutOfOrdernessTimestampExtractor(0, lambda e: e[2]))
    rs = env2.from_collection(
        list(zip(rid.tolist(), rk.tolist(), rts.tolist()))
    ).assign_timestamps_and_watermarks(
        BoundedOutOfOrdernessTimestampExtractor(0, lambda e: e[2]))
    t2.register_table("l", t2.from_data_stream(
        ls, ["lid", "k", "ts"], rowtime="ts"))
    t2.register_table("r", t2.from_data_stream(
        rs, ["rid", "rk", "rts"], rowtime="rts"))
    out2 = t2.sql_query(SQL)
    sink2 = CollectSink()
    out2.to_append_stream().add_sink(sink2)
    env2.execute("cj-row")

    got = sorted((int(a), int(b)) for a, b in sink.rows())
    want = sorted((int(a), int(b)) for a, b in sink2.values)
    assert got == want and len(got) > 0


def test_columnar_parallelism_2_matches_parallelism_1():
    """The columnar plan at parallelism 2: batches split per
    key-group-derived subtask through the tag-routed exchange, and
    results are identical to the single-parallelism plan (round-2
    verdict item 7 — the tier used to be parallelism-1-only)."""
    keys, ts, users = synth(8000, 60, 3000, seed=9)

    def run(par):
        env = StreamExecutionEnvironment()
        env.set_parallelism(par)
        t_env = StreamTableEnvironment.create(env)
        t_env.register_table("ev", t_env.from_columns(
            {"k": keys, "u": users, "ts": ts}, rowtime="ts", chunk=512))
        out = t_env.sql_query(SQL)
        assert getattr(out, "columnar", False), \
            f"plan fell off the columnar tier at parallelism {par}"
        sink = ColumnarCollectSink()
        out.to_append_stream(batched=True).add_sink(sink)
        env.execute(f"columnar-p{par}")
        return sorted((int(k), round(float(d))) for k, d in sink.rows())

    assert run(2) == run(1)


def test_columnar_parallelism_2_on_minicluster():
    """Same plan on the 2-worker MiniCluster (real subtask wiring)."""
    keys, ts, users = synth(5000, 40, 2500, seed=10)
    env = StreamExecutionEnvironment()
    env.use_mini_cluster(2)
    env.set_parallelism(2)
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table("ev", t_env.from_columns(
        {"k": keys, "u": users, "ts": ts}, rowtime="ts", chunk=512))
    out = t_env.sql_query(SQL)
    assert getattr(out, "columnar", False)
    sink = ColumnarCollectSink()
    out.to_append_stream(batched=True).add_sink(sink)
    env.execute("columnar-minicluster")
    got = sorted((int(k), round(float(d))) for k, d in sink.rows())
    row = run_rowpath(keys, ts, users)
    want = sorted((int(k), round(float(d))) for k, d in row.values)
    assert got == want


# ---------------------------------------------------------------------
# rescale: checkpoint the columnar SQL plan at par 2, restore at par 4
# (round-3 verdict item 5 — the state used to be warned away)
# ---------------------------------------------------------------------

class GatedColumnarSource(ColumnarSource):
    """Emits the first FREE_ROWS, then idles until released — keeps
    the job alive while the test takes a savepoint mid-stream (the
    PausingSource pattern, batch-columnar edition)."""

    released = False
    FREE_ROWS = 0

    @classmethod
    def reset(cls, free_rows):
        cls.released = False
        cls.FREE_ROWS = free_rows

    def emit_step(self, ctx, max_records):
        if not type(self).released and self.offset >= type(self).FREE_ROWS:
            import time as _t
            _t.sleep(0.001)
            return True
        return super().emit_step(ctx, max_records)


def _sql_rescale_build(par, keys, ts, users, savepoint=None):
    from flink_tpu.table.api import Schema, Table

    env = StreamExecutionEnvironment()
    env.set_parallelism(par)
    env.enable_checkpointing(10)
    if savepoint is not None:
        env.set_savepoint_restore(savepoint)
    t_env = StreamTableEnvironment.create(env)
    cols = {"k": keys, "u": users, "ts": ts}
    stream = env.add_source(
        GatedColumnarSource(cols, "ts", chunk=1024),
        name="columnar_source")
    t = Table(t_env, stream, Schema(list(cols)))
    t.rowtime = "ts"
    t.columnar = True
    t.col_dtypes = {k: np.asarray(v).dtype for k, v in cols.items()}
    t_env.register_table("ev", t)
    out = t_env.sql_query(
        "SELECT k, SUM(u) AS s, TUMBLE_START(ts) AS ws "
        "FROM ev GROUP BY TUMBLE(ts, INTERVAL '1' SECOND), k")
    assert getattr(out, "columnar", False)
    sink = ColumnarCollectSink()
    out.to_append_stream(batched=True).add_sink(sink)
    return env, sink


def test_columnar_sql_rescale_par2_to_par4(tmp_path):
    """Checkpoint a columnar SQL job at parallelism 2, restore the
    savepoint at parallelism 4: engine state re-splits by key group
    (restore_many + keep_fn) and the totals are exact — no warning,
    no dropped state (ref: StateAssignmentOperation + the stable-uid
    contract)."""
    keys, ts, users = synth(20_000, 60, 5000, seed=31)
    users = users.astype(np.float64)
    truth = {}
    for k, u, t in zip(keys.tolist(), users.tolist(), ts.tolist()):
        kk = (int(k), t - t % 1000)
        truth[kk] = truth.get(kk, 0.0) + u

    # gate after ONE chunk: the watermark stays inside the first
    # window, so nothing fires before the savepoint and run 2 alone
    # must reproduce every window (the PausingSource construction —
    # the source keeps emitting between barrier and stop, so anything
    # fired pre-stop would double-count against the savepoint state)
    GatedColumnarSource.reset(free_rows=1024)
    env, _ = _sql_rescale_build(2, keys, ts, users)
    client = env.execute_async("sql-rescale-origin")
    path = client.stop_with_savepoint(str(tmp_path / "sp"))

    GatedColumnarSource.released = True
    env2, sink2 = _sql_rescale_build(4, keys, ts, users, savepoint=path)
    env2.execute("sql-rescale-par4")
    got = {}
    for k, s, ws in sink2.rows():
        got[(int(k), int(ws))] = got.get((int(k), int(ws)), 0.0) + float(s)
    assert got == {k: pytest.approx(v) for k, v in truth.items()}


def test_columnar_sql_rescale_down_par2_to_par1(tmp_path):
    """Scale DOWN across the topology-shape change (par 2 has the
    split exchange node, par 1 does not): vertex matching by operator
    uid carries the window state over; the two old engines merge."""
    keys, ts, users = synth(12_000, 40, 4000, seed=32)
    users = users.astype(np.float64)
    truth = {}
    for k, u, t in zip(keys.tolist(), users.tolist(), ts.tolist()):
        kk = (int(k), t - t % 1000)
        truth[kk] = truth.get(kk, 0.0) + u

    GatedColumnarSource.reset(free_rows=1024)
    env, _ = _sql_rescale_build(2, keys, ts, users)
    client = env.execute_async("sql-rescale-origin-down")
    path = client.stop_with_savepoint(str(tmp_path / "spd"))

    GatedColumnarSource.released = True
    env2, sink2 = _sql_rescale_build(1, keys, ts, users, savepoint=path)
    env2.execute("sql-rescale-par1")
    got = {}
    for k, s, ws in sink2.rows():
        got[(int(k), int(ws))] = got.get((int(k), int(ws)), 0.0) \
            + float(s)
    assert got == {k: pytest.approx(v) for k, v in truth.items()}

"""Config #4's job at a small size on the CPU, through env.execute():
event-time session windows of unit-weight Count-Min sketches over a
4-partition ColumnarPartitionedLog with `state.backend=tpu`, against
the plain reference (tests/session_countmin_reference.py): the same
sessions, their exact totals, both of Count-Min's bounds.  And the
aggregate's two keywords: the defaults are today's aggregate bit for
bit, a merged slot answers with the sum of its parts."""

import math

import jax.numpy as jnp
import numpy as np
import pytest
import session_countmin_reference as reference

from flink_tpu.connectors.log_connector import ReplayableLogSource
from flink_tpu.connectors.partitioned_log import (
    ColumnarPartitionedLog,
    InMemoryPartitionedLog,
)
from flink_tpu.core.config import Configuration
from flink_tpu.core.keygroups import hash_int_column_np, stable_hash64
from flink_tpu.ops.hashing import split_hash64_np
from flink_tpu.ops.sketches import CountMinSketchAggregate
from flink_tpu.state.stats import STATE_STATS
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.sources import CollectSink
from flink_tpu.streaming.window_operator import WindowOperator
from flink_tpu.streaming.windowing import EventTimeSessionWindows

PARTS, PERIODS, PER = 4, 12, 256
GAP_MS, PERIOD_MS = 3000, 1000
WATCH = (3, 1, 4, 15)


class ItemCounts(CountMinSketchAggregate):
    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def stream(seed):
    rng = np.random.default_rng(seed)
    n = PERIODS * PER
    keys = rng.zipf(1.3, n).astype(np.int64) % 400
    items = rng.zipf(1.5, n).astype(np.int64) % 40
    ts = (np.arange(n) // PER) * PERIOD_MS \
        + 1 + ((np.arange(n) % PER) * (PERIOD_MS - 1)) // PER
    return keys, items, ts.astype(np.int64)


def run_job(seed, backend="tpu", width=2048, columns=True, depth=4,
            stated=None):
    """`columns` False is the per-row reference: a log of records, so
    the connector collects them one at a time and the operator takes
    them through process_element, with its per-timer drain.  `stated`
    is the (depth, width) the configuration states, where the job
    runs another."""
    keys, items, ts = stream(seed)
    log = ColumnarPartitionedLog(PARTS) if columns \
        else InMemoryPartitionedLog(PARTS)
    for p in range(PERIODS):
        for part in range(PARTS):
            rows = slice(p * PER + part, (p + 1) * PER, PARTS)
            if columns:
                log.append_columns(
                    part, {"f0": keys[rows], "f1": items[rows]}, ts[rows])
                continue
            for row in zip(keys[rows].tolist(), items[rows].tolist(),
                           ts[rows].tolist()):
                log.append(part, row[:2], row[2])
    env = StreamExecutionEnvironment(Configuration().set(
        "state.backend.tpu.max-device-slots", 4096))
    env.set_state_backend(backend)
    sink = CollectSink()

    def emit_row(key, window, vals):
        return [(key, (window.end - 1) // PERIOD_MS * PERIOD_MS,
                 window.start, window.end, *np.asarray(vals[0]).tolist())]

    windowed = (env.add_source(
        ReplayableLogSource(log, bounded=True, watermark_lag_ms=PERIOD_MS,
                            batch_per_partition=PER // PARTS))
        .key_by(0)
        .window(EventTimeSessionWindows.with_gap(GAP_MS)))
    windowed.disable_device_operator()
    windowed.aggregate(ItemCounts(depth, width, unit_weights=True,
                                  queries=WATCH),
                       window_function=emit_row).add_sink(sink)
    ops = []
    for node in env.get_stream_graph().nodes.values():
        def factory(inner=node.operator_factory):
            ops.append(inner())
            return ops[-1]
        node.operator_factory = factory
    old = WindowOperator.batch_fires
    WindowOperator.batch_fires = columns
    STATE_STATS.reset()
    try:
        env.execute("session-count-min")
    finally:
        WindowOperator.batch_fires = old
    op = [o for o in ops if isinstance(o, WindowOperator)][-1]
    arrival = np.concatenate([
        np.arange(p * PER + part, (p + 1) * PER, PARTS)
        for p in range(PERIODS) for part in range(PARTS)])
    emitted = [(0, None, lambda: (keys[arrival], items[arrival],
                                  ts[arrival], WATCH))]
    cols = tuple(np.asarray(c) for c in zip(*sink.values))
    stated = stated or (depth, width)
    config = {"gap_ms": GAP_MS, "window_ms": PERIOD_MS, "depth": stated[0],
              "width": stated[1]}
    return reference.check(config, emitted, {0: cols}), op, log, sink.values


@pytest.mark.parametrize("seed", [1, 2])
def test_the_job_is_correct_against_the_reference_on_the_batched_path(seed):
    verdict, op, log, rows = run_job(seed)
    assert verdict["failed"] == 0 and verdict["problems"] == []
    assert verdict["attempted"] == verdict["facts"]["sessions"] * 5 > 1000
    assert verdict["facts"]["events"] == PERIODS * PER
    # every row a batch, under state windows; the per-key door of the
    # slot index sees a merge's target and nothing else
    assert op.columnar_rows == PERIODS * PER and op.boxed_fallbacks == 0
    assert STATE_STATS.per_key_probe_rows <= STATE_STATS.merged_rows
    assert STATE_STATS.hash_per_value_rows == 0
    assert op.num_late_records_dropped == 0
    assert op.sessions_opened + op.sessions_extended == PERIODS * PER
    assert op.sessions_opened >= verdict["facts"]["sessions"]
    # sessions grew at the front (partitions interleave in time)
    assert any(start % PERIOD_MS > end % PERIOD_MS or end - start > GAP_MS
               for _, _, start, end, *_ in rows)
    assert log.committed_offsets == {p: PERIODS * PER // PARTS
                                     for p in range(PARTS)}


def test_the_per_row_path_gives_the_same_rows():
    batched = run_job(3)
    rows = run_job(3, columns=False)
    assert batched[1].columnar_rows == PERIODS * PER
    assert batched[1].timers_swept == len(batched[3])
    assert rows[1].columnar_rows == 0 and rows[1].timers_swept == 0
    assert rows[0]["failed"] == 0
    assert sorted(batched[3]) == sorted(rows[3])


def test_the_heap_backend_runs_the_same_job():
    verdict, op, _, rows = run_job(4, backend="heap")
    assert verdict["failed"] == 0 and op.boxed_fallbacks == 0
    assert sorted(rows) == sorted(run_job(4)[3])


def test_a_narrow_table_fails_the_upper_bound():
    """Two columns a row where the configuration states 2,048: the
    estimates of a session of more than one item overshoot, and the
    reference says so."""
    verdict = run_job(1, width=2, stated=(4, 2048))[0]
    assert verdict["failed"] > 0
    assert verdict["facts"]["over_bound_share"] > math.exp(-4)
    assert "beyond exact" in " ".join(verdict["problems"])


def test_a_table_of_one_row_fails_the_large_sessions_share():
    """One row where the configuration states four: over all pairs
    the overshoots stay a share under e^-4 (most sessions hold an item
    or two, exact in any table), among the sessions whose bound is at
    least 1 they do not.  The stated table passes both."""
    facts = run_job(1, width=64)[0]
    assert facts["failed"] == 0
    assert facts["facts"]["large_pairs_compared"] > math.exp(4)
    verdict = run_job(1, width=64, depth=1, stated=(4, 64))[0]
    facts = verdict["facts"]
    assert facts["over_bound_share"] < math.exp(-4) \
        < facts["large_over_bound_share"]
    assert verdict["failed"] == facts["large_estimates_over_bound"] > 0
    assert "sessions whose bound is at least 1" in verdict["problems"][0]


# ---- the aggregate's two keywords ------------------------------------

def _update(agg, state, slots, values):
    values = np.asarray(values)
    hi, lo = split_hash64_np(hash_int_column_np(values.astype(np.int64)))
    return agg.update(state, jnp.asarray(slots, jnp.int32),
                      jnp.asarray(values, jnp.float32), jnp.asarray(hi),
                      jnp.asarray(lo), jnp.ones(len(values), bool))


def test_defaults_are_todays_aggregate_bit_for_bit():
    agg = CountMinSketchAggregate()
    assert (agg.depth, agg.width) == (4, 2048)
    assert agg.needs_value and agg.needs_value_hash
    assert not agg.unit_weights and agg.queries is None
    values = [5, 5, 7, 2, 5, 9]
    state = _update(agg, agg.init_state(4), [0, 1, 0, 2, 0, 1], values)
    # the weight is the value itself, and result is the total alone
    result = np.asarray(agg.result(state, jnp.arange(3, dtype=jnp.int32)))
    assert result.shape == (3,) and result.tolist() == [17, 14, 2]
    table = np.asarray(state["table"])
    assert table.sum() == 4 * sum(values)
    hi, lo = split_hash64_np(np.array([stable_hash64(5)], np.uint64))
    est = agg.point_query(state, jnp.zeros(1, jnp.int32), jnp.asarray(hi),
                          jnp.asarray(lo))
    assert int(est[0]) == 10
    # the scalar twin (heap backend)
    acc = agg.create_accumulator()
    for v in (5, 5, 7):
        acc = agg.add(v, acc)
    assert agg.get_result(acc) == 17


def test_unit_weights_count_events_and_queries_read_the_table():
    agg = CountMinSketchAggregate(unit_weights=True, queries=(5, 7, 11))
    assert not agg.needs_value and agg.needs_value_hash
    state = _update(agg, agg.init_state(4), [0, 1, 0, 2, 0, 1],
                    [5, 5, 7, 2, 5, 9])
    result = np.asarray(agg.result(state, jnp.arange(4, dtype=jnp.int32)))
    assert result.dtype == np.int32 and result.tolist() == [
        [3, 2, 1, 0], [2, 1, 0, 0], [1, 0, 0, 0], [0, 0, 0, 0]]
    # the scalar twin answers alike, hashing one value at a time
    acc = agg.create_accumulator()
    for v in (5, 7, 5):
        acc = agg.add(v, acc)
    assert np.asarray(agg.get_result(acc)).tolist() == [3, 2, 1, 0]
    # a padding row (mask False) adds nothing
    padded = agg.update(
        agg.init_state(2), jnp.zeros(2, jnp.int32), jnp.zeros(2),
        jnp.zeros(2, jnp.uint32), jnp.zeros(2, jnp.uint32),
        jnp.asarray([True, False]))
    assert int(padded["total"][0]) == 1


@pytest.mark.parametrize("merge", ["merge_slots", "merge_rows"])
def test_point_query_of_a_merged_slot_is_the_sum_of_its_parts(merge):
    agg = CountMinSketchAggregate(unit_weights=True, queries=(5, 7, 11))
    state = _update(agg, agg.init_state(4), [0, 1, 0, 2, 0, 1, 2],
                    [5, 5, 7, 2, 5, 11, 11])
    before = np.asarray(agg.result(state, jnp.arange(4, dtype=jnp.int32)))
    merged = getattr(agg, merge)(state, jnp.asarray([0], jnp.int32),
                                 jnp.asarray([1], jnp.int32))
    after = np.asarray(agg.result(merged, jnp.arange(4, dtype=jnp.int32)))
    assert after[0].tolist() == (before[0] + before[1]).tolist() \
        == [5, 3, 1, 1]
    assert after[2].tolist() == before[2].tolist()
    # a pad pair of merge_rows (dst past the table) writes nowhere
    if merge == "merge_rows":
        padded = agg.merge_rows(state, jnp.asarray([0, 4], jnp.int32),
                                jnp.asarray([1, 0], jnp.int32))
        assert np.array_equal(np.asarray(padded["table"]),
                              np.asarray(merged["table"]))

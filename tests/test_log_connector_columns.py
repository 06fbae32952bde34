"""The log connector's columnar read: a log that holds columns hands a
partition's chunk over as ONE RecordBatch, and everything a consumer
can observe — the elements and their timestamps, the offsets, the
watermarks, snapshot and restore, the commit — is what the record read
gives."""

import numpy as np
import pytest

from flink_tpu.connectors.log_connector import ReplayableLogSource
from flink_tpu.connectors.partitioned_log import (
    ColumnarPartitionedLog,
    FilePartitionedLog,
    InMemoryPartitionedLog,
)
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.elements import RecordBatch
from flink_tpu.streaming.sources import CollectSink, SourceContext

PARTS = 4
ROWS = 96  # per partition, appended in chunks of 32


def rows_of(partition):
    rng = np.random.default_rng(partition)
    keys = rng.integers(0, 9, ROWS).astype(np.int64)
    items = rng.integers(0, 50, ROWS).astype(np.int64)
    ts = np.sort(rng.integers(0, 5000, ROWS)).astype(np.int64)
    return keys, items, ts


def filled(kind):
    """The same records in a log of columns or a log of records."""
    log = ColumnarPartitionedLog(PARTS) if kind == "columns" \
        else InMemoryPartitionedLog(PARTS)
    for p in range(PARTS):
        keys, items, ts = rows_of(p)
        for lo in range(0, ROWS, 32):
            sl = slice(lo, lo + 32)
            if kind == "columns":
                assert log.append_columns(
                    p, {"f0": keys[sl], "f1": items[sl]}, ts[sl]) == lo
            else:
                for k, i, t in zip(keys[sl].tolist(), items[sl].tolist(),
                                   ts[sl].tolist()):
                    log.append(p, (k, i), t)
    return log


class Seen(SourceContext):
    """Everything a source hands over, boxed, in order."""

    def __init__(self):
        self.elements = []
        self.batches = 0

    def collect(self, value):
        self.elements.append(("record", value, None))

    def collect_with_timestamp(self, value, timestamp):
        self.elements.append(("record", value, timestamp))

    def collect_batch(self, batch):
        assert isinstance(batch, RecordBatch)
        self.batches += 1
        super().collect_batch(batch)

    def emit_watermark(self, watermark):
        self.elements.append(("watermark", watermark.timestamp, None))


def opened(log, **kwargs):
    source = ReplayableLogSource(log, **kwargs)
    source._my_partitions = list(range(log.num_partitions))
    source.offsets = {p: 0 for p in source._my_partitions}
    return source


def drain(source, ctx, max_records, steps=None):
    n = 0
    while source.emit_step(ctx, max_records) and (steps is None or n < steps):
        n += 1
    return ctx


def test_columns_and_records_give_the_same_elements():
    seen = {}
    for kind in ("columns", "records"):
        source = opened(filled(kind), bounded=True, watermark_lag_ms=100)
        seen[kind] = drain(source, Seen(), 16 * PARTS)
        assert source.offsets == {p: ROWS for p in range(PARTS)}
    assert seen["columns"].elements == seen["records"].elements
    assert seen["columns"].batches == PARTS * ROWS // 16
    assert seen["records"].batches == 0
    marks = [e[1] for e in seen["columns"].elements if e[0] == "watermark"]
    assert marks == sorted(set(marks)) and len(marks) > 1
    newest = max(int(rows_of(p)[2].max()) for p in range(PARTS))
    assert marks[-1] == newest - 100


def test_a_columnar_read_stops_at_the_end_of_its_chunk():
    """40 records a partition a step: the record log gives 40, the
    columnar log the 32 and then the 8 that are left of a chunk; the
    same records either way."""
    by_kind = {}
    for kind in ("columns", "records"):
        source = opened(filled(kind), bounded=True)
        ctx = drain(source, Seen(), 40 * PARTS)
        by_kind[kind] = sorted(e[1:] for e in ctx.elements)
        assert source.offsets == {p: ROWS for p in range(PARTS)}
    assert by_kind["columns"] == by_kind["records"]


def test_read_columns_answers_with_slices_of_the_appended_chunks():
    log = filled("columns")
    keys, items, ts = rows_of(2)
    first, got_ts, cols = log.read_columns(2, 40, 1000)
    assert first == 40 and len(got_ts) == 24   # to the end of its chunk
    assert np.shares_memory(got_ts, got_ts.base) and got_ts.base is not None
    np.testing.assert_array_equal(got_ts, ts[40:64])
    np.testing.assert_array_equal(cols["f0"], keys[40:64])
    np.testing.assert_array_equal(cols["f1"], items[40:64])
    assert log.read(2, 40, 2) == [
        (40, int(ts[40]), (int(keys[40]), int(items[40]))),
        (41, int(ts[41]), (int(keys[41]), int(items[41])))]
    # the head of the log: nothing, and no error
    first, got_ts, cols = log.read_columns(2, ROWS, 10)
    assert first == ROWS and len(got_ts) == 0 and list(cols) == ["f0", "f1"]
    assert log.read(2, ROWS, 10) == []
    assert log.end_offset(2) == ROWS
    assert len(log.all_values(2)) == ROWS and len(log.all_values()) \
        == PARTS * ROWS
    with pytest.raises(ValueError):
        log.append_columns(0, {"f0": keys[:2]}, ts[:2])
    with pytest.raises(ValueError):
        log.append_columns(0, {"f0": keys[:2], "f1": items[:3]}, ts[:2])


def test_logs_of_records_hold_no_columns(tmp_path):
    for log in (InMemoryPartitionedLog(2),
                FilePartitionedLog(str(tmp_path), 2)):
        log.append(0, ("k", 1), 5)
        assert log.read_columns(0, 0, 10) is None
        assert log.read(0, 0, 10) == [(0, 5, ("k", 1))]


def test_a_scalar_column_is_a_log_of_scalars():
    log = ColumnarPartitionedLog(1)
    log.append(0, 7, 10)
    log.append_columns(0, {"v": np.array([8, 9])}, [11, 12])
    assert log.read(0, 0, 10) == [(0, 10, 7)] and log.read(0, 1, 10) == [
        (1, 11, 8), (2, 12, 9)]
    ctx = drain(opened(log, bounded=True), Seen(), 10)
    assert [e[1:] for e in ctx.elements] == [(7, 10), (8, 11), (9, 12)]


@pytest.mark.parametrize("kind", ["columns", "records"])
def test_snapshot_and_restore_rewind_the_read(kind):
    log = filled(kind)
    whole = drain(opened(log, bounded=True, watermark_lag_ms=100),
                  Seen(), 16 * PARTS).elements
    source = opened(log, bounded=True, watermark_lag_ms=100)
    ctx = Seen()
    for _ in range(3):
        source.emit_step(ctx, 16 * PARTS)
    snap = source.snapshot_function_state(7)
    assert snap["offsets"] == {p: 48 for p in range(PARTS)}
    before = len(ctx.elements)
    drain(source, ctx, 16 * PARTS)
    assert ctx.elements == whole
    # a restored consumer reads the rest again, record for record
    again = opened(log, bounded=True, watermark_lag_ms=100)
    again.restore_function_state(snap)
    rest = drain(again, Seen(), 16 * PARTS).elements
    assert [e for e in rest if e[0] == "record"] \
        == [e for e in whole[before:] if e[0] == "record"]
    source.notify_checkpoint_complete(7)
    assert log.committed_offsets == {p: 48 for p in range(PARTS)}
    source.finish()
    assert log.committed_offsets == {p: ROWS for p in range(PARTS)}


@pytest.mark.parametrize("kind", ["columns", "records"])
def test_run_reads_batch_per_partition_from_every_partition(kind):
    """`run` hands `emit_step` batch_per_partition x partitions: a
    source built with batch_per_partition=24 over 4 partitions reads
    24 a partition a step, not 6."""
    log = filled(kind)
    source = opened(log, bounded=True, batch_per_partition=24)
    steps = []
    inner = source.emit_step

    def counting(ctx, max_records):
        before = dict(source.offsets)
        more = inner(ctx, max_records)
        steps.append((max_records,
                      [source.offsets[p] - before[p] for p in range(PARTS)]))
        return more
    source.emit_step = counting
    source.run(Seen())
    assert steps[0] == (24 * PARTS, [24] * PARTS)
    assert source.offsets == {p: ROWS for p in range(PARTS)}


def test_a_job_over_a_columnar_log_takes_batches_and_commits():
    """Through env.execute(): the batches reach a keyed operator as
    batches, the phase is booked, the offsets are committed at the
    end."""
    log = filled("columns")
    sink = CollectSink()
    env = StreamExecutionEnvironment()
    get_tracer().reset()
    (env.add_source(ReplayableLogSource(log, bounded=True,
                                        watermark_lag_ms=100,
                                        batch_per_partition=32))
        .key_by(0)
        .map(lambda v: (v[0], v[1] + 1))
        .add_sink(sink))
    env.execute("columns-through-a-job")
    want = sorted((int(k), int(i) + 1) for p in range(PARTS)
                  for k, i in zip(*rows_of(p)[:2]))
    assert sorted(sink.values) == want
    assert log.committed_offsets == {p: ROWS for p in range(PARTS)}
    read = get_tracer().stats()["source.log.read"]
    assert read["count"] >= 3

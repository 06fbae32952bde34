"""DeviceWindowOperator's batch door and its batched fire: a
RecordBatch goes to the engine as columns (no boxed fallback), a fire
leaves as one RecordBatch per window, and both agree with the record
door and with the scalar WindowOperator on the heap backend — the
repo's plain reference — on the same seeded events."""

import numpy as np
import pytest
from fire_tail_reference import (
    CountedRecord,
    FireSpy,
    assert_fire_left_as,
    reference_batch,
)

import flink_tpu.native as nat
from flink_tpu.ops.device_agg import SumAggregate
from flink_tpu.ops.sketches import HyperLogLogAggregate
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu.streaming.columnar import (
    VectorizedCollectionSource,
    batch_from_records,
)
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.device_window_operator import DeviceWindowOperator
from flink_tpu.streaming.elements import RecordBatch
from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
from flink_tpu.streaming.log_windows import (
    LogStructuredSlidingWindows,
    LogStructuredTumblingWindows,
    StringSumTumblingWindows,
)
from flink_tpu.streaming.sources import CollectSink, SinkFunction
from flink_tpu.streaming.vectorized_sessions import VectorizedSessionWindows
from flink_tpu.streaming.windowing import (
    EventTimeSessionWindows,
    SlidingEventTimeWindows,
    Time,
    TimeWindow,
    TumblingEventTimeWindows,
)

pytestmark = pytest.mark.skipif(not nat.available(),
                                reason="native runtime unavailable")


class UserHll(HyperLogLogAggregate):
    """COUNT DISTINCT over field 1 of a (key, user) row."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


class FieldSum(SumAggregate):
    """Sum over field 1; no extract_column: values box per row."""

    def extract_value(self, value):
        return value[1]


def emit_row(key, window, vals):
    return [(key, window.start, float(vals[0]))]


class BatchSink(SinkFunction):
    """Keeps what arrives, as it arrives: batches and single rows."""

    def __init__(self):
        self.batches = []
        self.singles = []

    def invoke(self, value, context=None):
        self.singles.append(value)

    def invoke_batch(self, batch):
        self.batches.append(batch)

    def rows(self):
        out = list(self.singles)
        for b in self.batches:
            out.extend(b.row_values())
        return sorted(out)


def hll_events(seed=7, n=6000, keys=40, windows=3):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, keys, n)
    u = rng.integers(0, 1 << 40, n)
    ts = np.sort(rng.integers(0, windows * 1000, n))
    return [((int(a), int(b)), int(t)) for a, b, t in zip(k, u, ts)]


def run_hll_job(events, door, sink=None):
    """The job of config #2 at a tiny size.  ``door``: "batch" (a
    vectorized source, nothing pinned), "record" (the same rows one at
    a time), "heap" (the scalar WindowOperator on the heap backend)."""
    env = StreamExecutionEnvironment()
    sink = sink or CollectSink()
    if door == "record":
        stream = env.from_collection(events, timestamped=True)
    else:
        stream = env.add_source(VectorizedCollectionSource(
            events, timestamped=True, chunk=512))
    windowed = stream.key_by(0).window(TumblingEventTimeWindows.of(1000))
    if door == "heap":
        windowed.disable_device_operator()
    windowed.aggregate(UserHll(8), window_function=emit_row).add_sink(sink)
    made = []
    for node in env.get_stream_graph().nodes.values():
        def factory(inner=node.operator_factory):
            op = inner()
            made.append(op)
            return op
        node.operator_factory = factory
    env.execute(f"door-{door}")
    return sink, made


def test_batch_door_equals_the_record_door_row_for_row():
    events = hll_events()
    batch, _ = run_hll_job(events, "batch")
    record, _ = run_hll_job(events, "record")
    assert sorted(batch.values) == sorted(record.values)
    assert len(batch.values) == len({(k, ws) for k, ws, _ in batch.values})


def test_batch_door_equals_the_scalar_operator_on_the_heap_backend():
    """Same (key, window) rows, each once.  Both sides hash a user
    with splitmix64 and keep the same registers; the heap side turns
    them into an estimate in float32 (``HyperLogLogAggregate._estimate``)
    and the log engine's finish in float64, hence the 1e-5."""
    events = hll_events()
    batch, _ = run_hll_job(events, "batch")
    heap, _ = run_hll_job(events, "heap")
    got, want = sorted(batch.values), sorted(heap.values)
    assert [r[:2] for r in got] == [r[:2] for r in want]
    assert len(got) == len(set(r[:2] for r in got)) == 120
    assert [r[2] for r in got] == pytest.approx([r[2] for r in want],
                                                rel=1e-5)


def test_batch_door_counts_every_row_columnar_and_none_boxed():
    events = hll_events()
    _, made = run_hll_job(events, "batch")
    (op,) = [o for o in made if isinstance(o, DeviceWindowOperator)
             and o.engine is not None]
    assert isinstance(op.engine, LogStructuredTumblingWindows)
    assert op.boxed_fallbacks == 0 and op.boxed_rows == 0
    assert op.columnar_rows == len(events)
    assert op.columnar_fallback_reason is None


def test_a_fire_reaches_the_sink_as_one_batch_per_window():
    events = hll_events(windows=3)
    sink, _ = run_hll_job(events, "batch", BatchSink())
    assert not sink.singles
    starts = [np.unique(b.cols["f1"]).tolist() for b in sink.batches]
    assert starts == [[0], [1000], [2000]]
    for b in sink.batches:
        assert len(b) == 40 and b.ts.tolist() == (b.cols["f1"] + 999).tolist()
        assert b.cols["f0"].dtype == np.int64
        assert b.cols["f2"].dtype == np.float64
    record, _ = run_hll_job(events, "record")
    assert sink.rows() == sorted(record.values)


# ---- the operator alone, in the harness ------------------------------

def harness(agg, assigner=None, window_function=emit_row, key=0):
    op = DeviceWindowOperator(
        assigner or TumblingEventTimeWindows.of(Time.seconds(1)), agg,
        window_function)
    h = OneInputStreamOperatorTestHarness(op, key_selector=key)
    h.open()
    return op, h


def emitted_rows(h):
    """Every result row the harness saw, whether it left as records or
    (boxed by the harness's output) as one batch."""
    return sorted(h.extract_output_values())


def test_a_record_buffered_before_a_batch_keeps_its_place():
    op, h = harness(UserHll(8))
    h.process_element((5, 111), 10)
    assert op._keys == [5]
    h.process_batch(batch_from_records([(6, 222), (5, 333)], [20, 30]))
    # the record was flushed ahead of the batch: it lies first in the
    # window's log, and nothing is buffered
    assert op._keys == [] and op._ts == [] and op._values == []
    log = op.engine.windows[0]
    assert [k.tolist() for k in log.keys] == [[5], [6, 5]]
    assert op.columnar_rows == 2 and op.boxed_fallbacks == 0
    h.process_watermark(999)
    assert emitted_rows(h) == [(5, 0, pytest.approx(2.0, rel=1e-2)),
                               (6, 0, pytest.approx(1.0, rel=1e-2))]


def test_a_batch_behind_the_watermark_is_late_as_its_records_would_be():
    rows = [(1, 10), (2, 20), (1, 30), (3, 40)]
    ts = [500, 1500, 2500, 900]     # 500 and 900 lie behind 999

    def drive(batched):
        op, h = harness(UserHll(8))
        h.process_watermark(999)
        if batched:
            h.process_batch(batch_from_records(rows, ts))
        else:
            for row, t in zip(rows, ts):
                h.process_element(row, t)
        h.process_watermark(2999)
        return op.num_late_records_dropped, emitted_rows(h)

    assert drive(True) == drive(False)
    late, out = drive(True)
    assert late == 2 and [r[:2] for r in out] == [(1, 2000), (2, 1000)]


@pytest.mark.parametrize("dtype, engine", [
    (np.float32, StringSumTumblingWindows),       # the fused word count
    (np.int64, LogStructuredTumblingWindows),     # interned ids, log tier
], ids=["float_fused", "int_interned"])
def test_string_keyed_sum_through_the_batch_door(dtype, engine):
    rng = np.random.default_rng(3)
    words = [f"w{int(i)}" for i in rng.integers(0, 12, 400)]
    rows = [(w, int(v)) for w, v in zip(words, rng.integers(1, 9, 400))]
    ts = np.sort(rng.integers(0, 2000, 400)).tolist()

    def drive(batched):
        op, h = harness(FieldSum(dtype))
        if batched:
            for lo in range(0, 400, 100):
                h.process_batch(batch_from_records(rows[lo:lo + 100],
                                           ts[lo:lo + 100]))
        else:
            for row, t in zip(rows, ts):
                h.process_element(row, t)
        h.process_watermark(1999)
        return op, emitted_rows(h)

    op, got = drive(True)
    assert isinstance(op.engine, engine)
    assert (op._interner is not None) == (engine
                                          is LogStructuredTumblingWindows)
    assert op.columnar_rows == 400 and op.boxed_fallbacks == 0
    want = {}
    for (w, v), t in zip(rows, ts):
        want[(w, t - t % 1000)] = want.get((w, t - t % 1000), 0) + v
    assert got == sorted((w, s, float(v)) for (w, s), v in want.items())
    assert got == drive(False)[1]
    assert all(isinstance(k, str) for k, _, _ in got)


def test_a_session_aggregate_through_the_batch_door():
    """VectorizedSessionWindows hands its results over one tuple at a
    time (no ``fired``): they still leave through the shared tail."""
    rng = np.random.default_rng(11)
    rows = [(int(k), float(v)) for k, v in
            zip(rng.integers(0, 6, 300), rng.integers(1, 5, 300))]
    ts = np.sort(rng.integers(0, 6000, 300)).tolist()

    def session_row(key, window, vals):
        return [(key, window.start, window.end, float(vals[0]))]

    def drive(batched):
        op, h = harness(FieldSum(np.float32),
                        EventTimeSessionWindows.with_gap(
                            Time.milliseconds_of(150)), session_row)
        if batched:
            h.process_batch(batch_from_records(rows[:150], ts[:150]))
            h.process_batch(batch_from_records(rows[150:], ts[150:]))
        else:
            for row, t in zip(rows, ts):
                h.process_element(row, t)
        h.process_watermark(10_000)
        return op, emitted_rows(h)

    op, got = drive(True)
    assert isinstance(op.engine, VectorizedSessionWindows)
    assert not hasattr(op.engine, "fired")
    assert op.columnar_rows == 300 and op.boxed_fallbacks == 0
    assert got and got == drive(False)[1]
    assert sum(r[3] for r in got) == sum(v for _, v in rows)


def test_snapshot_between_two_batches_of_one_window_restores_the_same():
    events = hll_events(n=800, windows=1)
    rows = [r for r, _ in events]
    ts = [t for _, t in events]

    def feed(h, lo, hi):
        h.process_batch(batch_from_records(rows[lo:hi], ts[lo:hi]))

    op, h = harness(UserHll(8))
    feed(h, 0, 400)
    snap = h.snapshot()
    # the batch door buffers nothing: the snapshot is the engine's
    assert op._keys == [] and "device_engine" in snap
    feed(h, 400, 800)
    h.process_watermark(999)
    uninterrupted = emitted_rows(h)

    op2, h2 = harness(UserHll(8))
    h2.initialize_state(snap)
    feed(h2, 400, 800)
    h2.process_watermark(999)
    assert emitted_rows(h2) == uninterrupted
    assert len(uninterrupted) == 40
    assert op2.columnar_rows == 400     # counters are the new operator's


def test_a_batch_without_timestamps_is_refused_like_such_a_record():
    _, h = harness(UserHll(8))
    with pytest.raises(ValueError, match="event-time records"):
        h.process_batch(RecordBatch({"f0": np.arange(3),
                                     "f1": np.arange(3)}))
    with pytest.raises(ValueError, match="event-time records"):
        h.process_batch(RecordBatch(
            {"f0": np.arange(3), "f1": np.arange(3)},
            np.array([1, 2, 3]), np.array([True, False, True])))
    with pytest.raises(ValueError, match="event-time records"):
        h.process_element((1, 2), None)


def test_results_without_a_window_function_leave_as_one_column():
    class Collect:
        def __init__(self):
            self.batches, self.records = [], []

        def collect(self, record):
            self.records.append(record)

        def collect_batch(self, batch):
            self.batches.append(batch)

        def emit_watermark(self, watermark):
            pass

    op, h = harness(UserHll(8), window_function=None)
    op.output = out = Collect()
    events = hll_events(n=900, windows=2)
    h.process_batch(batch_from_records([r for r, _ in events],
                               [t for _, t in events]))
    h.process_watermark(1999)
    assert not out.records and len(out.batches) == 2
    for batch, end in zip(out.batches, (1000, 2000)):
        assert list(batch.cols) == ["v"] and len(batch) == 40
        assert batch.cols["v"].dtype == np.float64
        assert (batch.ts == end - 1).all()


def test_a_custom_key_selector_still_gives_one_key_array_per_batch():
    """Not a field selector: the rows' keys by get_key, the values by
    extract_value — still no boxed fallback and the same results."""
    events = hll_events(n=500, windows=1)
    rows = [r for r, _ in events]
    ts = [t for _, t in events]
    op, h = harness(UserHll(8), key=lambda row: row[0] % 7)
    h.process_batch(batch_from_records(rows, ts))
    h.process_watermark(999)
    op2, h2 = harness(UserHll(8), key=lambda row: row[0] % 7)
    for row, t in zip(rows, ts):
        h2.process_element(row, t)
    h2.process_watermark(999)
    assert emitted_rows(h) == emitted_rows(h2)
    assert len(emitted_rows(h)) == 7
    assert op.columnar_rows == 500 and op.boxed_fallbacks == 0


def test_an_empty_batch_touches_nothing():
    tr = get_tracer()
    op, h = harness(UserHll(8))
    tr.reset()
    h.process_batch(RecordBatch({"f0": np.zeros(0, np.int64),
                                 "f1": np.zeros(0, np.int64)},
                                np.zeros(0, np.int64)))
    assert op.engine is None and op.columnar_rows == 0
    assert "window.ingest" not in tr.stats()


# ---- the fire's emit tail: rows straight into the fire buffer --------
# Every fire the engine hands over is also put through the old tail
# (one StreamRecord per row, fire_tail_reference) in the test: what
# the operator hands on must be that, cell for cell.

def _one_row(key, window, vals):
    return [(key, window.start, float(vals[0]))]


def _several_rows(key, window, vals):
    return [(key, float(vals[0])), (key, float(window.end))]


def _some_keys_only(key, window, vals):
    return [] if int(vals[0]) % 2 else [(key, float(vals[0]))]


def _nothing(key, window, vals):
    return None if int(vals[0]) % 2 else []


def _mixed_types(key, window, vals):
    return [(key, int(vals[0]) if int(vals[0]) % 2 else float(vals[0]))]


def _a_bool(key, window, vals):
    return [(key, vals[0] > 2)]


def _beyond_int64(key, window, vals):
    return [(key, 2 ** 63 + int(vals[0]))]


DOOR_WINDOW_FUNCTIONS = {
    "one_row": _one_row, "several_rows": _several_rows,
    "some_keys_only": _some_keys_only, "nothing": _nothing,
    "mixed_types": _mixed_types, "a_bool": _a_bool,
    "beyond_int64": _beyond_int64, "no_function": None,
}


def _door_case(engine):
    """(aggregate, assigner, rows, timestamps, watermarks, engine class)
    of a small stream that reaches the named engine."""
    rng = np.random.default_rng(21)
    n = 600
    ts = np.sort(rng.integers(0, 4000, n)).tolist()
    watermarks = [999, 1999, 2999, 3999]
    assigner = TumblingEventTimeWindows.of(Time.seconds(1))
    if engine == "interned_strings":
        rows = [(f"w{int(k)}", int(v)) for k, v in
                zip(rng.integers(0, 9, n), rng.integers(1, 9, n))]
        return (FieldSum(np.int64), assigner, rows, ts, watermarks,
                LogStructuredTumblingWindows)
    if engine == "sessions":
        rows = [(int(k), float(v)) for k, v in
                zip(rng.integers(0, 6, n), rng.integers(1, 5, n))]
        return (FieldSum(np.float32),
                EventTimeSessionWindows.with_gap(Time.milliseconds_of(40)),
                rows, ts, [1500, 10_000], VectorizedSessionWindows)
    rows = [(int(k), int(u)) for k, u in
            zip(rng.integers(0, 25, n), rng.integers(0, 1 << 40, n))]
    if engine == "sliding":
        return (UserHll(8), SlidingEventTimeWindows.of(
            Time.seconds(2), Time.seconds(1)), rows, ts, watermarks,
            LogStructuredSlidingWindows)
    if engine == "several_windows_a_watermark":
        watermarks = [3999]
    return (UserHll(8), assigner, rows, ts, watermarks,
            LogStructuredTumblingWindows)


def _old_tail_rows(op, fire, fn):
    """The (value, timestamp) rows the old loop made of one fire."""
    keys, results, starts, ends = fire
    if isinstance(keys, np.ndarray) and keys.ndim == 1:
        keys = keys.tolist()
    if op._interner is not None:
        keys = [op._id_to_key[k] for k in keys]
    if isinstance(results, np.ndarray) and results.ndim == 1:
        results = results.tolist()
    if np.ndim(starts) == 0:
        windows = [TimeWindow(starts, ends)] * len(keys)
    else:
        windows = [TimeWindow(s, e) for s, e in zip(
            np.asarray(starts).tolist(), np.asarray(ends).tolist())]
    rows = []
    for key, result, window in zip(keys, results, windows):
        if fn is None:
            rows.append((result, window.end - 1))
            continue
        out = fn(key, window, [result])
        if out is not None:
            rows.extend((v, window.end - 1) for v in out)
    return rows


@pytest.mark.parametrize("engine", [
    "log_tumbling", "several_windows_a_watermark", "sliding",
    "interned_strings", "sessions"])
@pytest.mark.parametrize("shape", list(DOOR_WINDOW_FUNCTIONS))
def test_a_door_fire_leaves_as_the_record_tail_left_it(shape, engine,
                                                       monkeypatch):
    from flink_tpu.streaming import device_window_operator as dwo
    from flink_tpu.streaming import operators, window_operator
    fn = DOOR_WINDOW_FUNCTIONS[shape]
    agg, assigner, rows, ts, watermarks, engine_class = _door_case(engine)
    op, h = harness(agg, assigner, fn)
    spy = op.output = FireSpy()
    fires = []
    emit = op._emit_fires

    def one_fire_at_a_time(fired):
        for fire in fired:
            emit([fire])
            fires.append((fire, spy.take()))

    op._emit_fires = one_fire_at_a_time
    h.process_batch(batch_from_records(rows, ts))
    made = CountedRecord.made = 0
    for module in (operators, window_operator, dwo):
        monkeypatch.setattr(module, "StreamRecord", CountedRecord)
    for wm in watermarks:
        h.process_watermark(wm)
    assert isinstance(op.engine, engine_class)
    assert (op._interner is not None) == (engine == "interned_strings")
    assert len(fires) >= (2 if engine == "sessions" else 4)
    if engine == "sessions":    # a window of its own for every key
        assert all(np.ndim(starts) == 1 for (_, _, starts, _), _ in fires)
    emitted = 0
    for fire, events in fires:
        results = fire[1]
        if fn is None and len(fire[0]) > 1 \
                and isinstance(results, np.ndarray):
            # the result column itself, as before
            ((kind, batch),) = events
            assert kind == "batch" and list(batch.cols) == ["v"]
            assert batch.cols["v"] is results
            assert batch.ts.tolist() == (np.zeros(len(results), np.int64)
                                         + fire[3] - 1).tolist()
            emitted += len(results)
            continue
        want = _old_tail_rows(op, fire, fn)
        assert_fire_left_as(events, want)
        emitted += len(want)
        if reference_batch(want) is None:
            made += len(want)   # per-row records, built at the flush
    assert op.fire_rows_direct == emitted
    assert op.fire_rows_via_records == 0
    assert CountedRecord.made == made
    if shape != "nothing":
        assert emitted

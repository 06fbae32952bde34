"""Differential suite for the batched window FIRE path.

`WindowOperator.batch_fires` toggles the columnar watermark fire
(bulk timer sweep → one trigger decision per swept run → one backend
gather → RecordBatch emit → batch clear) against the per-timer scalar
drain.
Every combination of assigner {tumbling, sliding} x allowed lateness
{0, positive} x backend {heap, tpu} x ingest {batched, per-row} must
produce BIT-EQUAL output: values, timestamps, and emission order —
including when a watermark fires windows whose timers straddle a
checkpoint barrier (registered before the snapshot, fired after the
restore)."""

import itertools

import numpy as np
import pytest
from fire_tail_reference import (
    CountedRecord,
    FireSpy,
    assert_fire_left_as,
)

from flink_tpu.core.state import (
    AggregatingStateDescriptor,
    ListStateDescriptor,
    ValueStateDescriptor,
)
from flink_tpu.ops.device_agg import SumAggregate
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu.streaming.elements import RecordBatch
from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
from flink_tpu.streaming.operators import Output
from flink_tpu.streaming.window_operator import (
    ProcessWindowFunction,
    WindowFunction,
    WindowOperator,
)
from flink_tpu.streaming.windowing import (
    EventTimeTrigger,
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)

N_CHUNKS = 4
CHUNK = 192
N_KEYS = 7


class _KVSum(SumAggregate):
    def __init__(self):
        super().__init__(np.float32)

    def extract_value(self, value):
        return value[1] if isinstance(value, tuple) else value


def _assigner(kind):
    if kind == "tumbling":
        return TumblingEventTimeWindows.of(100)
    return SlidingEventTimeWindows.of(200, 100)


def _chunks():
    """Chunks whose timestamps overlap the watermark cadence: each
    chunk carries on-time rows, rows for windows not yet due (their
    timers must survive any mid-stream snapshot), and rows behind the
    watermark (late / within-lateness grace)."""
    rng = np.random.default_rng(77)
    for c in range(N_CHUNKS):
        keys = rng.integers(0, N_KEYS, CHUNK)
        vals = rng.integers(0, 50, CHUNK).astype(np.float64)
        ts = rng.integers(max(0, c * 400 - 250), c * 400 + 400,
                          CHUNK).astype(np.int64)
        yield keys, vals, ts, c * 400


def _run(kind, lateness, backend, batch_fires, snapshot_at=None,
         ingest="batch", state="agg", chunks=None):
    if state == "agg":
        descriptor = AggregatingStateDescriptor("fire-sum", _KVSum())

        def fn(key, window, elements):
            for v in elements:
                yield (key, float(v), window.start)
    else:
        descriptor = ListStateDescriptor("fire-list")

        def fn(key, window, elements):
            yield (key, float(sum(v for _, v in elements)), window.start)

    def fresh():
        op = WindowOperator(_assigner(kind), descriptor,
                            window_function=fn, allowed_lateness=lateness)
        op.batch_fires = batch_fires
        h = OneInputStreamOperatorTestHarness(
            op, key_selector=lambda x: x[0], state_backend=backend)
        h.open()
        assert op._batch_demote_reason is None
        return h

    h = fresh()
    out = []
    for keys, vals, ts, wm in chunks or _chunks():
        if ingest == "batch":
            h.process_batch(RecordBatch({"f0": keys, "f1": vals}, ts=ts))
        else:
            for r in RecordBatch({"f0": keys, "f1": vals},
                                 ts=ts).to_records():
                h.process_element(r)
        h.process_watermark(wm)
        out.extend((r.value, r.timestamp) for r in h.get_output())
        h.clear_output()
        if snapshot_at is not None and snapshot_at == wm // 400:
            # the barrier: timers registered for not-yet-due windows
            # must cross it and fire on the other side
            assert h.operator.timer_service.num_event_time_timers() > 0
            snap = h.snapshot()
            h = fresh()
            h.initialize_state(snap)
    h.process_watermark(10 ** 13)
    out.extend((r.value, r.timestamp) for r in h.get_output())
    return out


@pytest.mark.parametrize("backend", ["heap", "tpu"])
@pytest.mark.parametrize("lateness", [0, 100, 150])
@pytest.mark.parametrize("kind", ["tumbling", "sliding"])
def test_batch_fire_bit_equal(kind, lateness, backend):
    scalar = _run(kind, lateness, backend, batch_fires=False)
    batched = _run(kind, lateness, backend, batch_fires=True)
    assert scalar  # the config must actually fire windows
    assert batched == scalar


@pytest.mark.parametrize("backend", ["heap", "tpu"])
@pytest.mark.parametrize("kind", ["tumbling", "sliding"])
def test_batch_fire_across_checkpoint_barrier(kind, backend):
    """Windows whose fire timers straddle the checkpoint barrier
    (registered before the snapshot, due after the restore) fire
    bit-equal on both paths.  The reference is the scalar drain run
    over the SAME restore schedule — a restore rebuilds the timer
    heap, so fire order is only comparable restore-to-restore."""
    scalar = _run(kind, 150, backend, batch_fires=False, snapshot_at=2)
    batched = _run(kind, 150, backend, batch_fires=True, snapshot_at=2)
    assert scalar
    assert batched == scalar
    # and the restore run is the same multiset as the plain run
    plain = _run(kind, 150, backend, batch_fires=True)
    assert sorted(plain) == sorted(batched)


@pytest.mark.parametrize("backend", ["heap", "tpu"])
def test_batch_fire_per_row_ingest(backend):
    """The sweep also batches fires when ingest was per-element (the
    timers were registered one at a time)."""
    scalar = _run("tumbling", 0, backend, batch_fires=False,
                  ingest="rows")
    batched = _run("tumbling", 0, backend, batch_fires=True,
                   ingest="rows")
    assert batched == scalar


@pytest.mark.parametrize("backend", ["heap", "tpu"])
def test_batch_fire_list_state(backend):
    """ListState windows (native column get_batch on the heap backend,
    generic per-row fallback elsewhere) fire bit-equal."""
    scalar = _run("tumbling", 0, backend, batch_fires=False,
                  state="list")
    batched = _run("tumbling", 0, backend, batch_fires=True,
                   state="list")
    assert scalar
    assert batched == scalar


def test_batch_fires_of_three_key_counts_compile_one_result_program():
    """Three consecutive windows fire 9, 12 and 15 keys, one watermark
    each: the batched fire stays bit-equal to the scalar drain and
    finds the program of its bucket (16 slots) from the second fire
    on, where a program per key count compiled three times."""
    from flink_tpu.runtime import tracing
    rng = np.random.default_rng(3)
    chunks = []
    for window, n_keys in enumerate((9, 12, 15)):
        keys = np.repeat(np.arange(n_keys), 3)
        chunks.append((keys, rng.integers(0, 50, keys.size).astype(float),
                       window * 100 + rng.integers(0, 100, keys.size),
                       window * 100 + 99))
    scalar = _run("tumbling", 0, "tpu", batch_fires=False, chunks=chunks)
    tracing.reset_jit_stats()
    batched = _run("tumbling", 0, "tpu", batch_fires=True, chunks=chunks)
    assert len(scalar) == 9 + 12 + 15
    assert batched == scalar
    result = tracing.jit_stats()["state.result"]
    assert result["recompiles"] == 1 and result["cache_hits"] == 2
    assert result["last_shape_sig"].endswith("int32[16])")


class _SpyOutput(Output):
    def __init__(self, inner):
        self.inner = inner
        self.batches = []

    def collect(self, record):
        self.inner.collect(record)

    def collect_batch(self, batch):
        self.batches.append(batch)
        self.inner.collect_batch(batch)

    def emit_watermark(self, watermark):
        self.inner.emit_watermark(watermark)

    def collect_side(self, tag, record):
        self.inner.collect_side(tag, record)

    def emit_latency_marker(self, marker):
        self.inner.emit_latency_marker(marker)


def test_fired_results_emit_as_one_record_batch():
    """A firing sweep's emissions leave the operator as a single
    RecordBatch (layer 4), carrying the same rows the scalar path
    emits one record at a time."""
    op = WindowOperator(
        TumblingEventTimeWindows.of(100),
        AggregatingStateDescriptor("fire-sum", _KVSum()),
        window_function=lambda k, w, vs: [(int(k), float(vs[0]), int(w.start))])
    h = OneInputStreamOperatorTestHarness(
        op, key_selector=lambda x: x[0], state_backend="tpu")
    h.open()
    spy = op.output = _SpyOutput(op.output)
    keys = np.arange(8, dtype=np.int64) % 4
    vals = np.ones(8, np.float64)
    ts = np.arange(8, dtype=np.int64) * 50  # windows 0..350
    h.process_batch(RecordBatch({"f0": keys, "f1": vals}, ts=ts))
    h.process_watermark(10 ** 6)
    assert len(spy.batches) == 1
    assert len(spy.batches[0]) == 8  # 8 distinct (key, window) fires
    got = sorted((r.value, r.timestamp) for r in h.get_output())
    assert got == sorted(
        ((int(k), 1.0, int(t - t % 100)), int(t - t % 100) + 99)
        for k, t in zip(keys.tolist(), ts.tolist()))


def test_custom_trigger_demotes_to_scalar_drain():
    """A custom trigger (even a subclass of the default) pins the
    per-timer path — and the output still matches the default-trigger
    job, since the subclass changes nothing."""

    class MyTrigger(EventTimeTrigger):
        pass

    op = WindowOperator(
        TumblingEventTimeWindows.of(100),
        AggregatingStateDescriptor("fire-sum", _KVSum()),
        window_function=lambda k, w, vs: [(int(k), float(vs[0]))],
        trigger=MyTrigger())
    h = OneInputStreamOperatorTestHarness(
        op, key_selector=lambda x: x[0], state_backend="heap")
    h.open()
    assert op._batch_demote_reason is not None
    sweeps = []
    orig = op.timer_service.pop_due_event_time_timers
    op.timer_service.pop_due_event_time_timers = \
        lambda wm: sweeps.append(wm) or orig(wm)
    h.process_batch(RecordBatch(
        {"f0": np.zeros(4, np.int64), "f1": np.ones(4, np.float64)},
        ts=np.arange(4, dtype=np.int64) * 60))
    h.process_watermark(10 ** 6)
    assert sweeps == []  # scalar drain, never the sweep
    assert sorted(h.extract_output_values()) == [(0, 2.0), (0, 2.0)]


def test_batch_fires_kill_switch():
    """batch_fires=False pins the scalar path even for an eligible
    operator (the bench A/B contract)."""
    op = WindowOperator(
        TumblingEventTimeWindows.of(100),
        AggregatingStateDescriptor("fire-sum", _KVSum()),
        window_function=lambda k, w, vs: [(int(k), float(vs[0]))])
    op.batch_fires = False
    h = OneInputStreamOperatorTestHarness(
        op, key_selector=lambda x: x[0], state_backend="heap")
    h.open()
    assert op._batch_demote_reason is None
    called = []
    op.on_watermark_batch = lambda wm: called.append(wm)
    h.process_batch(RecordBatch(
        {"f0": np.zeros(4, np.int64), "f1": np.ones(4, np.float64)},
        ts=np.arange(4, dtype=np.int64) * 60))
    h.process_watermark(10 ** 6)
    assert called == []
    assert sorted(h.extract_output_values()) == [(0, 2.0), (0, 2.0)]


# ---- the emit tail: rows straight into the fire buffer ---------------
# The scalar drain (batch_fires=False) still wraps every row in a
# StreamRecord; what it emits per watermark, put through the old tail
# (fire_tail_reference), is what a batched fire must hand on.

def _one_row(key, window, vs):
    return [(key, float(vs[0]), window.start)]


def _several_rows(key, window, vs):
    return [(key, float(vs[0])), (key, -1.0), (key, float(window.end))]


def _some_keys_only(key, window, vs):
    return [(key, float(vs[0]))] if key % 2 else []


def _nothing(key, window, vs):
    return None if key % 2 else []


def _generator(key, window, vs):
    for v in vs:
        yield (key, float(v))
        yield (key, float(window.start))


def _scalar_rows(key, window, vs):
    return [float(vs[0])]


def _string_cells(key, window, vs):
    return [(f"k{key}", float(vs[0]))]


def _mixed_types(key, window, vs):
    return [(key, float(vs[0]) if key % 2 else int(vs[0]))]


def _a_bool(key, window, vs):
    return [(key, vs[0] > 100.0)]


def _beyond_int64(key, window, vs):
    return [(key, 2 ** 63 if key == 3 else key)]


def _uneven_arity(key, window, vs):
    return [(key,) if key == 2 else (key, float(vs[0]))]


WINDOW_FUNCTIONS = {
    "one_row": _one_row, "several_rows": _several_rows,
    "some_keys_only": _some_keys_only, "nothing": _nothing,
    "generator": _generator, "scalar_rows": _scalar_rows,
    "string_cells": _string_cells, "mixed_types": _mixed_types,
    "a_bool": _a_bool, "beyond_int64": _beyond_int64,
    "uneven_arity": _uneven_arity, "no_function": None,
}


def _tail_harness(kind, backend, fn, batch_fires, lateness=0):
    from flink_tpu.runtime.metrics import MetricRegistry
    op = WindowOperator(_assigner(kind),
                        AggregatingStateDescriptor("fire-sum", _KVSum()),
                        window_function=fn, allowed_lateness=lateness)
    op.batch_fires = batch_fires
    h = OneInputStreamOperatorTestHarness(
        op, key_selector=lambda x: x[0], state_backend=backend)
    op.register_standard_metrics(MetricRegistry().job_group("tail"))
    spy = op.output = FireSpy()  # before open(): the collector binds it
    h.open()
    #: (timestamp, namespace) of every timer the per-timer drain fires
    op.timers_fired = []
    op.fired_at_watermark = []
    drain_fires = op.on_event_time

    def on_event_time(timer):
        op.timers_fired.append((timer.timestamp, timer.namespace))
        op.fired_at_watermark.append(op.timer_service.current_watermark)
        drain_fires(timer)

    op.on_event_time = on_event_time
    return op, h, spy


def _drive_fires(kind, backend, fn, batch_fires, lateness=0, chunks=None):
    """One list of what left the operator per watermark; the sweeps
    take in four tumbling (or five sliding) windows each."""
    op, h, spy = _tail_harness(kind, backend, fn, batch_fires, lateness)
    per_watermark = []
    for keys, vals, ts, wm in chunks or _chunks():
        h.process_batch(RecordBatch({"f0": keys, "f1": vals}, ts=ts))
        h.process_watermark(wm)
        per_watermark.append(spy.take())
    h.process_watermark(10 ** 13)
    per_watermark.append(spy.take())
    return op, per_watermark


@pytest.mark.parametrize("backend", ["heap", "tpu"])
@pytest.mark.parametrize("kind", ["tumbling", "sliding"])
@pytest.mark.parametrize("shape", list(WINDOW_FUNCTIONS))
def test_a_fire_leaves_as_the_record_tail_left_it(shape, kind, backend):
    fn = WINDOW_FUNCTIONS[shape]
    scalar_op, scalar = _drive_fires(kind, backend, fn, False)
    op, batched = _drive_fires(kind, backend, fn, True)
    assert len(batched) == len(scalar)
    fired_rows = 0
    for events, reference in zip(batched, scalar):
        rows = [what for _, what in reference]
        assert all(kind == "record" for kind, _ in reference)
        assert_fire_left_as(events, rows)
        fired_rows += len(rows)
    if shape != "nothing":
        assert fired_rows
    assert op.fire_rows_direct == fired_rows
    assert op.fire_rows_via_records == 0
    assert scalar_op.fire_rows_direct == 0
    # the backend's key context and the histogram, as the loop left them
    assert op.keyed_backend.current_key == scalar_op.keyed_backend.current_key
    hist, want = op._emit_batch_hist, scalar_op._emit_batch_hist
    assert hist.total_count == want.total_count > 0
    assert (hist._values, hist._pos) == (want._values, want._pos)


def _timers_by_watermark(op):
    """The drain's firing order, one list per watermark."""
    fired = zip(op.fired_at_watermark, op.timers_fired)
    return [[timer for _, timer in group] for _, group in
            itertools.groupby(fired, key=lambda pair: pair[0])]


def _maximal_runs(timers):
    """How many stretches of one (timestamp, namespace) a firing
    order is made of."""
    return sum(1 for a, b in zip([None] + timers, timers) if a != b)


@pytest.mark.parametrize("backend", ["heap", "tpu"])
@pytest.mark.parametrize("kind, lateness", [
    ("tumbling", 0), ("sliding", 0),
    # lateness = size: window A's cleanup timers tie with window B's
    # fire timers, and the chunks' out-of-order rows interleave them
    ("tumbling", 100),
    # lateness = slide, = size: ties among three sliding windows
    ("sliding", 100), ("sliding", 200), ("tumbling", 150)])
@pytest.mark.parametrize("shape", ["one_row", "several_rows"])
def test_swept_runs_fire_as_the_per_timer_drain(shape, kind, lateness,
                                                backend):
    """The batched fire over the sweep's runs against the per-timer
    drain, cell for cell, where timers of several windows tie on a
    timestamp; and the counters that say how the timers came: every
    timer the drain fires is swept once, in as many runs as the
    drain's order has stretches of one (timestamp, window)."""
    fn = WINDOW_FUNCTIONS[shape]
    scalar_op, scalar = _drive_fires(kind, backend, fn, False, lateness)
    op, batched = _drive_fires(kind, backend, fn, True, lateness)
    assert len(batched) == len(scalar)
    for events, reference in zip(batched, scalar):
        assert_fire_left_as(events, [what for _, what in reference])
    assert sum(len(reference) for reference in scalar)
    timers = scalar_op.timers_fired
    assert op.timers_fired == [] and scalar_op.timers_swept == 0
    assert op.timers_swept == len(timers) > 0
    # fired + cleaned: with lateness every (key, window) has two timers
    fire_timers = sum(ts == ns[1] - 1 for ts, ns in timers)
    assert len(timers) == fire_timers * (2 if lateness else 1)
    distinct = len(set(timers))
    assert op.timer_runs >= distinct
    if lateness == 0:
        assert op.timer_runs == distinct  # one run per fired window
    # a run per stretch, counted within each watermark's sweep
    sweeps = _timers_by_watermark(scalar_op)
    assert op.timer_runs == sum(_maximal_runs(sweep) for sweep in sweeps)
    assert op.keyed_backend.current_key == scalar_op.keyed_backend.current_key


def _rows(keys, ts, wm):
    return (np.array(keys, np.int64), np.ones(len(keys)),
            np.array(ts, np.int64), wm)


@pytest.mark.parametrize("backend", ["heap", "tpu"])
def test_tied_runs_are_cut_where_their_registrations_interleave(backend):
    """Tumbling 100 with lateness 100: at 199 window [0, 100)'s
    cleanup timers tie with window [100, 200)'s fire timers, and keys
    reached the two windows in turns, batch after batch.  The sweep
    hands the tie over in registration order, cut into five runs, and
    the batched fire emits and clears as the per-timer drain."""
    chunks = [_rows([1, 2], [50, 150], 0), _rows([3, 4, 1], [60, 160, 170], 10),
              _rows([5], [70], 99), _rows([6], [250], 199)]
    scalar_op, scalar = _drive_fires("tumbling", backend, _one_row, False,
                                     100, chunks)
    op, batched = _drive_fires("tumbling", backend, _one_row, True, 100,
                               chunks)
    for events, reference in zip(batched, scalar):
        assert_fire_left_as(events, [what for _, what in reference])
    tie = [sweep for sweep in _timers_by_watermark(scalar_op)
           if sweep[0][0] == 199]
    assert tie == [[(199, (0, 100)), (199, (100, 200)), (199, (0, 100)),
                    (199, (100, 200)), (199, (100, 200)), (199, (0, 100))]]
    assert [[value[0] for _, (value, _) in reference]
            for reference in scalar] \
        == [[], [], [1, 3, 5], [2, 4, 1], [6]]
    assert op.timers_swept == len(scalar_op.timers_fired) == 14
    # [0,100) fires: 1; the tie: 5; [100,200) cleans, [200,300) fires
    # and cleans at the last watermark: 3
    assert op.timer_runs == 9
    assert len(set(scalar_op.timers_fired)) == 6


def test_a_sweep_over_several_windows_holds_one_run_per_window():
    """A watermark that jumps four windows: the buffer keeps one
    (timestamp, rows) run per fired window, not a timestamp per row,
    and the batch's timestamp column still follows each row's window."""
    from flink_tpu.streaming import window_operator as wo
    made = []

    class Buffer(wo._FireBufferOutput):
        def flush(self):
            made.append([list(run) for run in self.runs])
            super().flush()

    op, h, spy = _tail_harness("tumbling", "heap", _one_row, True)
    keys, vals, ts, _ = next(iter(_chunks()))
    h.process_batch(RecordBatch({"f0": keys, "f1": vals}, ts=ts))
    orig, wo._FireBufferOutput = wo._FireBufferOutput, Buffer
    try:
        h.process_watermark(399)
    finally:
        wo._FireBufferOutput = orig
    ((kind, batch),) = spy.take()
    assert kind == "batch"
    assert made == [[[99, N_KEYS], [199, N_KEYS], [299, N_KEYS],
                     [399, N_KEYS]]]
    assert batch.ts.tolist() == (batch.cols["f2"] + 99).tolist()


class _CountsPerWindowAndKey(ProcessWindowFunction):
    """Reads and writes keyed state under the fired key's context:
    per-window state, and per-key state shared across windows."""

    def process(self, key, context, elements, out):
        per_window = context.window_state(ValueStateDescriptor("fires"))
        per_key = context.global_state(ValueStateDescriptor("seen"))
        per_window.update((per_window.value() or 0) + 1)
        per_key.update((per_key.value() or 0) + 1)
        out.collect((key, float(elements[0]), context.window.start,
                     per_window.value(), per_key.value()))


class _WritesThroughOut(WindowFunction):
    def apply(self, key, window, inputs, out):
        out.collect((key, float(inputs[0])))
        out.collect((key, float(window.start)))


@pytest.mark.parametrize("backend", ["heap", "tpu"])
@pytest.mark.parametrize("fn", [_CountsPerWindowAndKey, _WritesThroughOut],
                         ids=["process_window_function", "window_function"])
def test_collector_functions_keep_key_context_and_records(fn, backend):
    scalar_op, scalar = _drive_fires("sliding", backend, fn(), False, 150)
    op, batched = _drive_fires("sliding", backend, fn(), True, 150)
    fired_rows = 0
    for events, reference in zip(batched, scalar):
        rows = [what for _, what in reference]
        assert_fire_left_as(events, rows)
        fired_rows += len(rows)
    assert fired_rows
    if fn is _CountsPerWindowAndKey:
        # a key is seen in several windows: the shared count went up
        assert max(v[4] for events in scalar for _, (v, _) in events) > 1
    assert op.fire_rows_via_records == fired_rows
    assert op.fire_rows_direct == 0
    assert op.keyed_backend.current_key == scalar_op.keyed_backend.current_key


@pytest.mark.parametrize("fn, direct", [
    (_one_row, True), (None, True), (_CountsPerWindowAndKey(), False),
    (_WritesThroughOut(), False)],
    ids=["callable", "no_function", "process_window_function",
         "window_function"])
def test_a_plain_callable_fire_builds_no_stream_record(fn, direct,
                                                       monkeypatch):
    """The mechanism: through a plain callable (or none) every row
    reaches the buffer by the direct entry and no StreamRecord is
    built; through the two collector-taking classes, one per row."""
    from flink_tpu.streaming import operators, window_operator
    op, h, spy = _tail_harness("tumbling", "tpu", fn, True)
    n = 500
    keys = np.arange(n, dtype=np.int64)
    h.process_batch(RecordBatch(
        {"f0": keys, "f1": np.ones(n)}, ts=np.full(n, 50, np.int64)))
    monkeypatch.setattr(CountedRecord, "made", 0)
    for module in (operators, window_operator):
        monkeypatch.setattr(module, "StreamRecord", CountedRecord)
    tr = get_tracer()
    tr.reset()
    monkeypatch.setattr(tr, "enabled", True)
    h.process_watermark(99)
    rows = len(spy.rows())
    assert rows == (2 * n if isinstance(fn, _WritesThroughOut) else n)
    assert [kind for kind, _ in spy.events] == ["batch"]
    # the phase of the loop carries the two counts beside its keys
    (loop,) = [e for e in tr.recent() if e["name"] == "window.fire.batch"]
    assert loop["args"] == {
        "keys": n, "fire_rows_direct": rows if direct else 0,
        "fire_rows_via_records": 0 if direct else rows}
    if direct:
        assert (op.fire_rows_direct, op.fire_rows_via_records) == (rows, 0)
        assert CountedRecord.made == 0
    else:
        assert (op.fire_rows_direct, op.fire_rows_via_records) == (0, rows)
        assert CountedRecord.made == rows


def test_timer_phases_carry_keys_timers_and_runs(monkeypatch):
    """`timers.register` says how many distinct keys a (window, batch)
    handed to the store, `timers.sweep` how many timers a watermark
    took out and in how many runs: 300 timers in one run for a
    tumbling window, whatever the number of batches that brought them."""
    op, h, spy = _tail_harness("tumbling", "heap", _one_row, True)
    tr = get_tracer()
    tr.reset()
    monkeypatch.setattr(tr, "enabled", True)
    for lo in (0, 100):
        keys = np.arange(lo, lo + 200, dtype=np.int64).repeat(2)
        h.process_batch(RecordBatch(
            {"f0": keys, "f1": np.ones(400)}, ts=np.full(400, 50, np.int64)))
    h.process_watermark(98)
    h.process_watermark(99)
    events = tr.recent()
    assert [e["args"] for e in events if e["name"] == "timers.register"] \
        == [{"keys": 200}, {"keys": 200}]
    assert [e["args"] for e in events if e["name"] == "timers.sweep"] \
        == [{"timers": 0, "runs": 0}, {"timers": 300, "runs": 1}]
    assert (op.timers_swept, op.timer_runs) == (300, 1)
    assert len(spy.rows()) == 300


def test_rows_that_do_not_fit_become_records_at_the_flush(monkeypatch):
    """... and only there: n rows, n StreamRecords, in fire order."""
    from flink_tpu.streaming import operators, window_operator
    op, h, spy = _tail_harness("tumbling", "heap", _mixed_types, True)
    keys = np.arange(6, dtype=np.int64)
    h.process_batch(RecordBatch(
        {"f0": keys, "f1": np.ones(6)}, ts=np.full(6, 50, np.int64)))
    monkeypatch.setattr(CountedRecord, "made", 0)
    for module in (operators, window_operator):
        monkeypatch.setattr(module, "StreamRecord", CountedRecord)
    h.process_watermark(99)
    assert spy.events == [("record", ((k, 1.0 if k % 2 else 1), 99))
                          for k in range(6)]
    assert CountedRecord.made == 6
    assert (op.fire_rows_direct, op.fire_rows_via_records) == (6, 0)


@pytest.mark.parametrize("n", [0, 1, 5, 1023, 1024, 1025, 3000])
@pytest.mark.parametrize("filled", [0, 1000, 1024, 2500])
def test_histogram_update_many_is_update_for_each(filled, n):
    from flink_tpu.runtime.metrics import Histogram
    one, many = Histogram(), Histogram()
    for v in range(filled):
        one.update(v)
        many.update(v)
    values = [10_000 + v for v in range(n)]
    for v in values:
        one.update(v)
    many.update_many(values)
    assert (many.total_count, many._values, many._pos) \
        == (one.total_count, one._values, one._pos)

"""Differential suite for the batched window FIRE path.

`WindowOperator.batch_fires` toggles the columnar watermark fire
(bulk timer sweep → vectorized trigger decision → one backend gather →
RecordBatch emit → batch clear) against the per-timer scalar drain.
Every combination of assigner {tumbling, sliding} x allowed lateness
{0, positive} x backend {heap, tpu} x ingest {batched, per-row} must
produce BIT-EQUAL output: values, timestamps, and emission order —
including when a watermark fires windows whose timers straddle a
checkpoint barrier (registered before the snapshot, fired after the
restore)."""

import numpy as np
import pytest

from flink_tpu.core.state import (
    AggregatingStateDescriptor,
    ListStateDescriptor,
)
from flink_tpu.ops.device_agg import SumAggregate
from flink_tpu.streaming.elements import RecordBatch
from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
from flink_tpu.streaming.operators import Output
from flink_tpu.streaming.window_operator import WindowOperator
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
@pytest.mark.parametrize("lateness", [0, 150])
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

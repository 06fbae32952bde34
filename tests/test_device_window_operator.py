"""DeviceWindowOperator: the vectorized engines running inside the
framework (graph-builder auto-selection, parity with the scalar
operator, and barrier-checkpoint recovery through engine snapshots)."""

import numpy as np
import pytest

from flink_tpu.core.functions import MapFunction
from flink_tpu.ops.device_agg import CountAggregate, SumAggregate
from flink_tpu.ops.sketches import HyperLogLogAggregate
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.device_window_operator import DeviceWindowOperator
from flink_tpu.streaming.window_engines import batched_operator_kind
from flink_tpu.streaming.sources import CollectSink
from flink_tpu.streaming.windowing import (
    CountTrigger,
    EventTimeSessionWindows,
    SlidingEventTimeWindows,
    Time,
    TumblingEventTimeWindows,
)


class TupleSum(SumAggregate):
    def __init__(self):
        super().__init__(np.float32)

    def extract_value(self, value):
        return value[1]


def _job_output(env_builder, records, device=True):
    sink = CollectSink()
    env = StreamExecutionEnvironment()
    ws = env_builder(env, records)
    if not device:
        ws.disable_device_operator()
    ws.aggregate(TupleSum(),
                 window_function=lambda k, w, els: [
                     (k, round(float(els[0]), 2), w.start, w.end)]
                 ).add_sink(sink)
    env.execute("device-vs-scalar")
    return sorted(sink.values)


@pytest.mark.parametrize("assigner_factory", [
    lambda: TumblingEventTimeWindows.of(Time.seconds(1)),
    lambda: SlidingEventTimeWindows.of(Time.seconds(3), Time.seconds(1)),
    lambda: EventTimeSessionWindows.with_gap(Time.milliseconds_of(400)),
])
def test_device_path_matches_scalar_through_api(assigner_factory):
    rng = np.random.default_rng(31)
    n = 3000
    records = [((int(rng.integers(0, 20)), float(rng.random())),
                int(rng.integers(0, 8000))) for _ in range(n)]
    records = [((k, v), ts) for ((k, v), ts) in records]

    def build(env, recs):
        return (env.from_collection(recs, timestamped=True)
                .key_by(lambda t: t[0])
                .window(assigner_factory()))

    got = _job_output(build, records, device=True)
    want = _job_output(build, records, device=False)
    assert got == want


def test_eligibility_gate():
    tumbling = TumblingEventTimeWindows.of(Time.seconds(1))
    dev_agg = SumAggregate(np.float32)
    kind = batched_operator_kind
    assert kind(tumbling, dev_agg, None, None, 0, None, None) == "device"
    # custom trigger → scalar
    assert kind(tumbling, dev_agg, CountTrigger(5), None, 0, None,
                None) is None
    # lateness → scalar
    assert kind(tumbling, dev_agg, None, None, 100, None, None) is None

    # plain (non-device) AggregateFunction → the generic operator
    class Plain:
        pass
    assert kind(tumbling, Plain(), None, None, 0, None, None) == "generic"
    # unaligned sliding → scalar, whatever the aggregate
    s = SlidingEventTimeWindows.of(Time.milliseconds_of(2500),
                                   Time.seconds(1))
    assert kind(s, dev_agg, None, None, 0, None, None) is None
    assert kind(s, Plain(), None, None, 0, None, None) is None


def test_graph_selects_device_operator():
    env = StreamExecutionEnvironment()
    (env.from_collection([((1, 1.0), 10)], timestamped=True)
        .key_by(lambda t: t[0])
        .time_window(Time.seconds(1))
        .aggregate(TupleSum())
        .add_sink(CollectSink()))
    ops = [n.operator_factory() for n in env.graph.nodes.values()]
    assert any(isinstance(op, DeviceWindowOperator) for op in ops)


class FailOnce(MapFunction):
    def __init__(self):
        self.ckpt = False
        self.failed = False

    def notify_checkpoint_complete(self, cid):
        self.ckpt = True

    def map(self, v):
        if self.ckpt and not self.failed:
            self.failed = True
            raise RuntimeError("induced")
        return v


@pytest.mark.parametrize("assigner_factory", [
    lambda: TumblingEventTimeWindows.of(Time.seconds(1)),
    lambda: SlidingEventTimeWindows.of(Time.seconds(2), Time.seconds(1)),
    lambda: EventTimeSessionWindows.with_gap(Time.milliseconds_of(300)),
])
def test_device_operator_exactly_once_recovery(assigner_factory):
    """Kill-and-restore through the engine snapshot path: sums stay
    exactly-once on the device operator."""
    n_keys, per_key = 5, 400
    records = []
    for i in range(per_key):
        for k in range(n_keys):
            records.append(((f"k{k}", 1.0), i * 5))
    failer = FailOnce()
    sink = CollectSink()
    env = StreamExecutionEnvironment()
    env.enable_checkpointing(10)
    env.set_restart_strategy("fixed_delay", restart_attempts=3, delay_ms=0)
    (env.from_collection(records, timestamped=True)
        .map(failer)
        .key_by(lambda t: t[0])
        .window(assigner_factory())
        .aggregate(TupleSum())
        .add_sink(sink))
    result = env.execute("device-recovery")
    assert failer.failed and result.restarts == 1
    assert result.checkpoints_completed >= 1
    assigner = assigner_factory()
    if isinstance(assigner, SlidingEventTimeWindows):
        overlap = assigner.size // assigner.slide
        assert sum(sink.values) == pytest.approx(n_keys * per_key * overlap)
    else:
        # tumbling / sessions: every record counted exactly once
        assert sum(sink.values) == pytest.approx(n_keys * per_key)


def test_device_hll_through_api():
    class UserHLL(HyperLogLogAggregate):
        def __init__(self):
            super().__init__(precision=11)

        def extract_value(self, value):
            return value[1]

    sink = CollectSink()
    env = StreamExecutionEnvironment()
    records = [((i % 4, 10_000 + i), (i % 1000) * 2) for i in range(20_000)]
    (env.from_collection(records, timestamped=True)
        .key_by(lambda t: t[0])
        .time_window(Time.seconds(2))
        .aggregate(UserHLL())
        .add_sink(sink))
    env.execute("device-hll")
    assert len(sink.values) == 4  # one window [0,2000) x 4 keys
    for est in sink.values:
        # 5000 distinct at precision 11 sits in the raw-HLL bias zone
        # (~2.5*m): allow the known high bias, not just stddev
        assert abs(est - 5000) / 5000 < 0.12


def test_engine_tier_selection_by_key_dtype():
    """Integer-keyed jobs ride the log combiner tier; STRING keys
    dictionary-encode to dense ids (C++ interner) and ride it too;
    non-string object keys ride the device-resident scatter tier
    (the lazy first-flush choice)."""
    import numpy as np
    from flink_tpu.ops.sketches import HyperLogLogAggregate
    from flink_tpu.streaming.device_window_operator import DeviceWindowOperator
    from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
    from flink_tpu.streaming.log_windows import LogStructuredTumblingWindows
    from flink_tpu.streaming.vectorized import VectorizedTumblingWindows
    from flink_tpu.streaming.windowing import TumblingEventTimeWindows, Time

    def build(keys):
        op = DeviceWindowOperator(
            TumblingEventTimeWindows.of(Time.seconds(1)),
            HyperLogLogAggregate(precision=8))
        h = OneInputStreamOperatorTestHarness(op, key_selector=lambda v: v)
        h.open()
        for i, k in enumerate(keys):
            h.process_element(k, 100 + i)
        h.process_watermark(10_000)
        return op

    op_int = build([5, 7, 5])
    assert isinstance(op_int.engine, LogStructuredTumblingWindows)
    op_str = build(["a", "b", "a"])
    assert isinstance(op_str.engine, LogStructuredTumblingWindows)
    assert op_str._interner is not None and op_str._interner.n == 2
    op_obj = build([(1, "x"), (2, "y"), (1, "x")])
    assert isinstance(op_obj.engine, VectorizedTumblingWindows)


def test_string_keys_ride_log_tier_with_exact_results():
    """keyBy(word) over real strings: interned ids feed the log tier,
    emission maps ids back to the original words (the
    SocketWindowWordCount shape, ref :70-84)."""
    import collections
    rng = np.random.default_rng(5)
    words = [f"word{int(i)}" for i in rng.integers(0, 50, 4000)]
    records = [((w, 1.0), int(ts)) for w, ts in
               zip(words, rng.integers(0, 3000, 4000))]
    sink = CollectSink()
    env = StreamExecutionEnvironment()
    (env.from_collection(records, timestamped=True)
        .key_by(lambda t: t[0])
        .time_window(Time.seconds(1))
        .aggregate(TupleSum(),
                   window_function=lambda k, w, els: [
                       (k, w.start, round(float(els[0]), 1))])
        .add_sink(sink))
    env.execute("wordcount-str")
    expect = collections.Counter()
    for (w, _one), ts in records:
        expect[(w, ts - ts % 1000)] += 1
    got = {(k, s): v for (k, s, v) in sink.values}
    assert got == {k: float(v) for k, v in expect.items()}
    # keys came back as real strings, not ids
    assert all(isinstance(k, str) and k.startswith("word")
               for (k, _, _) in sink.values)


def test_string_sum_fused_engine_multi_flush():
    """More records than flush_batch: every flush after the first must
    keep feeding the fused engine raw strings (regression: the second
    flush started interning and fed integer ids)."""
    import collections
    from flink_tpu.streaming.log_windows import StringSumTumblingWindows
    rng = np.random.default_rng(9)
    n = 30_000  # >> flush_batch (8192) -> several flushes
    words = [f"w{int(i)}" for i in rng.integers(0, 40, n)]
    records = [((w, 1.0), int(t)) for w, t in
               zip(words, rng.integers(0, 2000, n))]
    sink = CollectSink()
    env = StreamExecutionEnvironment()
    (env.from_collection(records, timestamped=True)
        .key_by(lambda t: t[0])
        .time_window(Time.seconds(1))
        .aggregate(TupleSum(),
                   window_function=lambda k, w, els: [
                       (k, w.start, int(els[0]))])
        .add_sink(sink))
    env.execute("fused-multi-flush")
    expect = collections.Counter()
    for (w, _), ts in records:
        expect[(w, ts - ts % 1000)] += 1
    assert {(k, s): v for (k, s, v) in sink.values} == dict(expect)


def test_lazy_engine_fast_forwards_watermark():
    """A watermark that arrives before any element must make later
    behind-watermark records LATE, not aggregate them (the lazily
    created engine starts at the operator's current watermark)."""
    import numpy as np
    from flink_tpu.ops.device_agg import SumAggregate
    from flink_tpu.streaming.device_window_operator import DeviceWindowOperator
    from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
    from flink_tpu.streaming.windowing import TumblingEventTimeWindows, Time

    op = DeviceWindowOperator(
        TumblingEventTimeWindows.of(Time.seconds(1)),
        SumAggregate(np.float64))
    h = OneInputStreamOperatorTestHarness(op, key_selector=lambda v: v)
    h.open()
    h.process_watermark(10_000)
    h.process_element(5, 100)      # behind the watermark -> late
    h.process_watermark(11_000)
    assert h.extract_output_values() == []
    assert op.num_late_records_dropped == 1


def test_log_ineligible_params_fall_back_to_vectorized():
    """precision 18 exceeds the log tier's u16 cells: integer keys must
    still run (on the scatter tier), not crash at first flush."""
    from flink_tpu.ops.sketches import HyperLogLogAggregate
    from flink_tpu.streaming.device_window_operator import DeviceWindowOperator
    from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
    from flink_tpu.streaming.vectorized import VectorizedTumblingWindows
    from flink_tpu.streaming.windowing import TumblingEventTimeWindows, Time

    op = DeviceWindowOperator(
        TumblingEventTimeWindows.of(Time.seconds(1)),
        HyperLogLogAggregate(precision=18))
    h = OneInputStreamOperatorTestHarness(op, key_selector=lambda v: v)
    h.open()
    for i in range(50):
        h.process_element(i % 5, 100 + i)
    h.process_watermark(10_000)
    assert isinstance(op.engine, VectorizedTumblingWindows)
    assert len(h.extract_output_values()) == 5

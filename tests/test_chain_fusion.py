"""Differential suite for fused operator chains: one jitted columnar
program per typeflow-proven run (streaming/chain_fusion.py) must be
bit-identical to the per-operator kernel path — values, timestamps,
ts-validity masks, per-channel routing — and any failure must demote
the whole chain back to per-operator dispatch, never produce wrong
output."""

import numpy as np
import pytest

from flink_tpu.core.functions import FilterFunction, MapFunction
from flink_tpu.streaming import chain_fusion as cf
from flink_tpu.streaming.elements import RecordBatch
from flink_tpu.streaming.operators import StreamFilter, StreamMap


class _LMap(MapFunction):
    def __init__(self, fn):
        self._fn = fn

    def map(self, value):
        return self._fn(value)


class _LFilter(FilterFunction):
    def __init__(self, fn):
        self._fn = fn

    def filter(self, value):
        return self._fn(value)


class _CapOut:
    def __init__(self):
        self.batches = []
        self.records = []

    def collect_batch(self, batch):
        self.batches.append(batch)

    def collect(self, record):
        self.records.append(record)


class _ChainOut:
    def __init__(self, op):
        self.op = op

    def collect_batch(self, batch):
        self.op.process_batch(batch)

    def collect(self, record):
        self.op.process_element(record)


def _mk_chain(out, map_fn=None, filter_fn=None):
    m = StreamMap(_LMap(map_fn or (lambda t: (t[0], t[1] * 3))))
    f = StreamFilter(_LFilter(filter_fn or (lambda t: (t[1] % 7) != 0)))
    m.setup(_ChainOut(f))
    f.setup(out)
    m.operator_id = "map-1"
    f.operator_id = "filter-2"
    return m, f


@pytest.fixture(autouse=True)
def _fusion_env():
    """Every test sees fusion enabled with a low row floor, and leaves
    the module flags as it found them."""
    saved = (cf.FUSION_ENABLED, cf.MIN_FUSED_ROWS,
             cf.MESH_MIN_ROWS_PER_SHARD)
    cf.FUSION_ENABLED = True
    cf.MIN_FUSED_ROWS = 256
    cf.FUSION_STATS.reset()
    yield
    (cf.FUSION_ENABLED, cf.MIN_FUSED_ROWS,
     cf.MESH_MIN_ROWS_PER_SHARD) = saved


def _assert_batches_equal(got, ref):
    assert len(got) == len(ref)
    for gb, rb in zip(got, ref):
        assert list(gb.cols) == list(rb.cols)
        for k in rb.cols:
            assert gb.cols[k].dtype == rb.cols[k].dtype, k
            assert np.array_equal(gb.cols[k], rb.cols[k],
                                  equal_nan=gb.cols[k].dtype.kind == "f"), k
        if rb.ts is None:
            assert gb.ts is None
        else:
            assert np.array_equal(gb.ts, rb.ts)
        if rb.ts_mask is None:
            assert gb.ts_mask is None
        else:
            assert np.array_equal(gb.ts_mask, rb.ts_mask)


# ---------------------------------------------------------------------
# plain mode: map + filter compaction, dtype zoo


@pytest.mark.parametrize("dtype", [
    np.int8, np.int16, np.int32, np.int64, np.uint8, np.uint32,
    np.float32, np.float64, np.bool_,
])
def test_fused_plain_bit_equality(dtype):
    rng = np.random.default_rng(3)
    vals = (rng.random(1024) * 50).astype(dtype)
    cols = {"f0": rng.integers(0, 9, 1024).astype(np.int64), "f1": vals}
    ts = rng.integers(0, 10_000, 1024).astype(np.int64)
    tsm = rng.random(1024) > 0.2

    ref_out = _CapOut()
    m1, f1 = _mk_chain(ref_out)
    m1.process_batch(RecordBatch({k: v.copy() for k, v in cols.items()},
                                 ts.copy(), tsm.copy()))

    fused_out = _CapOut()
    m2, f2 = _mk_chain(fused_out)
    prog = cf.compile_chain([m2, f2])
    assert prog is not None
    batch = RecordBatch(dict(cols), ts.copy(), tsm.copy())
    assert prog.wants(batch)
    prog.run(batch)
    assert prog.active, prog.demoted_reason
    _assert_batches_equal(fused_out.batches, ref_out.batches)
    # accounting parity: fused rows count into the columnar totals the
    # per-operator kernels would have reported
    assert (m2.columnar_rows, f2.columnar_rows) == \
        (m1.columnar_rows, f1.columnar_rows)
    assert m2.fused_rows == 1024
    assert m2.columnar_decided_by == "fused"


def test_small_batches_stay_per_operator():
    cols = {"f0": np.arange(64, dtype=np.int64),
            "f1": np.arange(64, dtype=np.int64)}
    out = _CapOut()
    m, f = _mk_chain(out)
    prog = cf.compile_chain([m, f])
    assert prog is not None
    assert not prog.wants(RecordBatch(dict(cols)))
    assert prog.active


# ---------------------------------------------------------------------
# routed mode: fused splitmix64 + channel compaction vs split_batch


def test_fused_routing_matches_split_batch():
    from flink_tpu.core.functions import _FieldKeySelector
    from flink_tpu.streaming.partitioners import KeyGroupStreamPartitioner

    class _Ch:
        def __init__(self):
            self.got = []

        def push(self, element):
            self.got.append(element)

    class _Router:
        def __init__(self, part, channels):
            self.routes = [(part, channels, None)]
            self.records_out_counter = None

        def flush_records(self):
            pass

        def collect_batch(self, batch):
            for part, channels, _tag in self.routes:
                for idx, sub in part.split_batch(batch, len(channels)):
                    channels[idx].push(sub)

    rng = np.random.default_rng(7)
    n = 1500
    cols = {"f0": rng.integers(0, 100, n).astype(np.int64),
            "f1": rng.integers(-50, 50, n).astype(np.int64)}
    ts = rng.integers(0, 10_000, n).astype(np.int64)
    nch = 4

    ref_chs = [_Ch() for _ in range(nch)]
    ref_router = _Router(
        KeyGroupStreamPartitioner(_FieldKeySelector(0), 128), ref_chs)
    m1, f1 = _mk_chain(ref_router)
    m1.process_batch(RecordBatch({k: v.copy() for k, v in cols.items()},
                                 ts.copy()))

    fu_chs = [_Ch() for _ in range(nch)]
    fu_router = _Router(
        KeyGroupStreamPartitioner(_FieldKeySelector(0), 128), fu_chs)
    m2, f2 = _mk_chain(fu_router)
    prog = cf.compile_chain([m2, f2], router=fu_router)
    assert prog is not None and prog.route_field == 0
    prog.run(RecordBatch(dict(cols), ts.copy()))
    assert prog.active, prog.demoted_reason
    for c in range(nch):
        _assert_batches_equal(fu_chs[c].got, ref_chs[c].got)


def test_precomputed_routing_hashes_match_per_row():
    """The device splitmix64 twin must be bit-identical to the numpy
    hash the per-row routing path uses, so precomputed batch.routing
    lands every row on the same channel."""
    from flink_tpu.core.keygroups import splitmix64_np

    keys = np.array([0, 1, -7, 2**40, -2**40, 12345], np.int64)
    from flink_tpu.streaming.chain_fusion import _jnp_splitmix64
    pytest.importorskip("jax")
    import jax
    from jax import enable_x64
    with enable_x64():
        dev = np.asarray(jax.jit(_jnp_splitmix64)(
            jax.device_put(keys.view(np.uint64))))
    assert np.array_equal(dev, splitmix64_np(keys.view(np.uint64)))


# ---------------------------------------------------------------------
# window mode: fused pane assignment through the harness


@pytest.mark.parametrize("kind", ["tumbling", "sliding"])
def test_fused_window_differential(kind):
    from flink_tpu.core.state import AggregatingStateDescriptor
    from flink_tpu.ops.device_agg import SumAggregate
    from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
    from flink_tpu.streaming.window_operator import WindowOperator
    from flink_tpu.streaming.windowing import (
        SlidingEventTimeWindows,
        TumblingEventTimeWindows,
    )

    class _KVSum(SumAggregate):
        def __init__(self):
            super().__init__(np.float64)

        def extract_value(self, value):
            return value[1] if isinstance(value, tuple) else value

    def run(fused):
        descriptor = AggregatingStateDescriptor("w-sum", _KVSum())

        def wfn(key, window, elements):
            for v in elements:
                yield (key, float(v), window.start)

        assigner = (TumblingEventTimeWindows.of(100) if kind == "tumbling"
                    else SlidingEventTimeWindows.of(200, 100))
        wop = WindowOperator(assigner, descriptor, window_function=wfn,
                             allowed_lateness=0)
        h = OneInputStreamOperatorTestHarness(
            wop, key_selector=lambda x: x[0], state_backend="heap")
        h.open()
        m, f = _mk_chain(_ChainOut(wop),
                         map_fn=lambda t: (t[0], t[1] * 3.0))
        prog = cf.compile_chain([m, f, wop]) if fused else None
        if fused:
            assert prog is not None and prog.window_op is wop
        out = []
        rng = np.random.default_rng(5)
        for c in range(4):
            n = 800
            batch = RecordBatch(
                {"f0": rng.integers(0, 7, n).astype(np.int64),
                 "f1": rng.integers(0, 50, n).astype(np.int64)},
                rng.integers(max(0, c * 300 - 150), c * 300 + 300,
                             n).astype(np.int64))
            if fused and prog.wants(batch):
                prog.run(batch)
                assert prog.active, prog.demoted_reason
            else:
                m.process_batch(batch)
            h.process_watermark(c * 300)
            out.extend((r.value, r.timestamp) for r in h.get_output())
            h.clear_output()
        h.process_watermark(10 ** 13)
        out.extend((r.value, r.timestamp) for r in h.get_output())
        return out

    ref = run(fused=False)
    got = run(fused=True)
    assert ref
    assert got == ref


# ---------------------------------------------------------------------
# mesh variant


def test_fused_mesh_variant_bit_exact():
    cf.MESH_MIN_ROWS_PER_SHARD = 64  # force the sharded program
    rng = np.random.default_rng(11)
    n = 5000
    cols = {"f0": rng.integers(0, 100, n).astype(np.int64),
            "f1": rng.integers(-50, 50, n).astype(np.int64)}
    ts = rng.integers(0, 10_000, n).astype(np.int64)
    tsm = rng.random(n) > 0.1

    ref_out = _CapOut()
    m1, _f1 = _mk_chain(ref_out)
    m1.process_batch(RecordBatch({k: v.copy() for k, v in cols.items()},
                                 ts.copy(), tsm.copy()))

    fused_out = _CapOut()
    m2, f2 = _mk_chain(fused_out)
    prog = cf.compile_chain([m2, f2])
    assert prog is not None
    assert prog.mesh_shards > 1, "conftest forces 8 virtual devices"
    prog.run(RecordBatch(dict(cols), ts.copy(), tsm.copy()))
    assert prog.active, prog.demoted_reason
    _assert_batches_equal(fused_out.batches, ref_out.batches)


def test_fused_mesh_route_matches_split_batch():
    """Routing on the mesh: per-shard partitions merged channel-major
    on the host must reproduce split_batch's global stable order
    bit-for-bit on every channel."""
    from flink_tpu.core.functions import _FieldKeySelector
    from flink_tpu.streaming.partitioners import KeyGroupStreamPartitioner

    class _Ch:
        def __init__(self):
            self.got = []

        def push(self, element):
            self.got.append(element)

    class _Router:
        def __init__(self, part, channels):
            self.routes = [(part, channels, None)]
            self.records_out_counter = None

        def flush_records(self):
            pass

        def collect_batch(self, batch):
            for part, channels, _tag in self.routes:
                for idx, sub in part.split_batch(batch, len(channels)):
                    channels[idx].push(sub)

    cf.MESH_MIN_ROWS_PER_SHARD = 64  # force the sharded program
    rng = np.random.default_rng(17)
    n = 4096
    cols = {"f0": rng.integers(0, 100, n).astype(np.int64),
            "f1": rng.integers(-50, 50, n).astype(np.int64)}
    ts = rng.integers(0, 10_000, n).astype(np.int64)
    nch = 4

    ref_chs = [_Ch() for _ in range(nch)]
    ref_router = _Router(
        KeyGroupStreamPartitioner(_FieldKeySelector(0), 128), ref_chs)
    m1, _f1 = _mk_chain(ref_router)
    m1.process_batch(RecordBatch({k: v.copy() for k, v in cols.items()},
                                 ts.copy()))

    fu_chs = [_Ch() for _ in range(nch)]
    fu_router = _Router(
        KeyGroupStreamPartitioner(_FieldKeySelector(0), 128), fu_chs)
    m2, _f2 = _mk_chain(fu_router)
    prog = cf.compile_chain([m2, _f2], router=fu_router)
    assert prog is not None and prog.route_field == 0
    assert prog.mesh_shards > 1, "conftest forces 8 virtual devices"
    prog.run(RecordBatch(dict(cols), ts.copy()))
    assert prog.active, prog.demoted_reason
    assert ("route", False, True) in prog._fns, \
        "the batch must have taken the sharded route program"
    for c in range(nch):
        _assert_batches_equal(fu_chs[c].got, ref_chs[c].got)


# ---------------------------------------------------------------------
# demotion: any kernel failure locks the chain boxed with a reason


def test_probe_failure_demotes_whole_chain():
    out = _CapOut()
    m, f = _mk_chain(out)
    prog = cf.compile_chain([m, f])
    assert prog is not None
    bad = RecordBatch({"f0": np.array(["a", "b"] * 300, dtype=object),
                       "f1": np.arange(600, dtype=np.int64)})
    assert prog.wants(bad)
    prog.run(bad)
    assert not prog.active
    assert prog.demoted_reason
    assert cf.FUSION_STATS.last_demotion is not None
    assert cf.FUSION_STATS.last_demotion[0] == prog.label
    # the failing batch replayed through the per-operator path
    assert m.columnar_rows + m.boxed_rows == 600
    assert m.fused_rows == 0
    # demotion resets the introspection verdicts
    from flink_tpu.analysis.columnar_eligibility import operator_decided_by
    assert operator_decided_by(m) != "fused"
    assert m._fused_member is None
    # the chain stays demoted: later clean batches go per-operator
    good = RecordBatch({"f0": np.arange(600, dtype=np.int64),
                        "f1": np.arange(600, dtype=np.int64)})
    assert not prog.wants(good)
    m.process_batch(good)
    assert out.batches, "per-operator path must keep flowing"


def test_demoted_output_matches_per_operator():
    """The batch that triggers demotion must still produce exactly the
    per-operator output (replayed, nothing emitted twice)."""
    out = _CapOut()
    m, f = _mk_chain(out)
    prog = cf.compile_chain([m, f])
    bad = RecordBatch({"f0": np.array(["x"] * 600, dtype=object),
                       "f1": np.arange(600, dtype=np.int64)})
    prog.run(bad)

    ref_out = _CapOut()
    m2, f2 = _mk_chain(ref_out)
    m2.process_batch(RecordBatch(
        {"f0": np.array(["x"] * 600, dtype=object),
         "f1": np.arange(600, dtype=np.int64)}))
    assert len(out.batches) == len(ref_out.batches)
    for gb, rb in zip(out.batches, ref_out.batches):
        for k in rb.cols:
            assert np.array_equal(gb.cols[k], rb.cols[k])


def test_own_code_exception_fails_instead_of_demoting(monkeypatch):
    """Demotion is for what the data or a UDF can cause.  An exception
    out of the fusion code itself (here: its jit wrapper) is a bug and
    must surface, not turn into a silently unfused chain."""
    def broken_jit(fn, name=None, **kw):
        def dispatch(*args):
            raise RuntimeError("bug in the fused program's own code")
        return dispatch

    monkeypatch.setattr(cf, "traced_jit", broken_jit)
    out = _CapOut()
    m, f = _mk_chain(out)
    prog = cf.compile_chain([m, f])
    batch = RecordBatch({"f0": np.arange(600, dtype=np.int64),
                         "f1": np.arange(600, dtype=np.int64)})
    with pytest.raises(RuntimeError, match="own code"):
        prog.run(batch)
    assert prog.active and cf.FUSION_STATS.demotions == 0
    assert not out.batches, "nothing may be emitted by a failed batch"


def test_own_code_exception_fails_the_job(monkeypatch):
    from flink_tpu.streaming.columnar import VectorizedCollectionSource
    from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    from flink_tpu.streaming.sources import CollectSink

    def broken_verify(self, *args, **kw):
        raise RuntimeError("bug in the fused program's own code")

    monkeypatch.setattr(cf.FusedChainProgram, "_verify", broken_verify)
    env = StreamExecutionEnvironment()
    (env.add_source(VectorizedCollectionSource(
        [(i % 7, i) for i in range(2000)], chunk=512))
        .map(lambda t: (t[0], t[1] * 3))
        .filter(lambda t: t[1] % 7 != 0)
        .add_sink(CollectSink()))
    with pytest.raises(Exception, match="own code"):
        env.execute("fusion-own-bug")
    assert cf.FUSION_STATS.demotions == 0


def test_untraceable_udf_still_demotes():
    """A LIFTABLE numpy kernel that jax cannot trace (a numpy ufunc
    called on a tracer) is the UDF's property: the chain demotes and
    the batch flows per-operator."""
    out = _CapOut()
    m, f = _mk_chain(out, map_fn=lambda t: (t[0], np.add(t[1], 1)),
                     filter_fn=lambda t: t[1] >= 0)
    prog = cf.compile_chain([m, f])
    assert prog is not None
    prog.run(RecordBatch({"f0": np.arange(600, dtype=np.int64),
                          "f1": np.arange(600, dtype=np.int64)}))
    assert not prog.active
    assert "not device-traceable" in prog.demoted_reason
    assert sum(len(b) for b in out.batches) + len(out.records) == 600


# ---------------------------------------------------------------------
# introspection: reports + kernel table


def test_chain_report_carries_fusion_verdict():
    from flink_tpu.analysis.columnar_eligibility import chain_report

    m, f = _mk_chain(_CapOut())
    rep = chain_report([m, f])
    assert rep["fusion"]["fusable"]
    assert rep["fusion"]["fused_ops"] == ["map-1", "filter-2"]
    assert rep["fusion"]["first_blocker"] is None

    class _Opaque(MapFunction):
        def map(self, value):
            return hash(repr(value))  # not liftable

    blocked = StreamMap(_Opaque())
    blocked.setup(_CapOut())
    blocked.operator_id = "opaque-3"
    rep = chain_report([m, f, blocked])
    assert rep["fusion"]["fusable"]
    assert rep["fusion"]["first_blocker"] == "opaque-3"
    assert rep["fusion"]["blocker_reason"]


def test_fused_kernel_label_reaches_device_ledger():
    from flink_tpu.runtime.device_stats import TELEMETRY

    out = _CapOut()
    m, f = _mk_chain(out)
    prog = cf.compile_chain([m, f])
    cols = {"f0": np.arange(1024, dtype=np.int64),
            "f1": np.arange(1024, dtype=np.int64)}
    TELEMETRY.enabled = True
    TELEMETRY.reset()
    try:
        prog.run(RecordBatch(dict(cols)))
        payload = TELEMETRY.payload()
    finally:
        TELEMETRY.enabled = False
    assert prog.active, prog.demoted_reason
    assert prog.label in payload["kernels"]
    assert payload["kernels"][prog.label]["dispatches"] >= 1
    # inside the fused region the only boundary crossings are the
    # chain's own in/out transfers — no per-operator intermediates
    transfer_tags = {t.split(".", 1)[1] for t in payload["transfers"]}
    assert transfer_tags == {"chain.boundary"}


# ---------------------------------------------------------------------
# exactly-once: chaos run with barriers straddling fused batches


def test_chaos_exactly_once_with_fused_chain():
    import collections
    import tempfile

    from flink_tpu.runtime import faults
    from flink_tpu.runtime.faults import FaultInjector
    from flink_tpu.streaming.datastream import StreamExecutionEnvironment

    rng = np.random.default_rng(17)
    data = [((int(k), int(v)), int(t)) for t, (k, v) in enumerate(
        zip(rng.integers(0, 7, 4000), rng.integers(0, 100, 4000)))]

    def run():
        from flink_tpu.core.functions import AggregateFunction
        from flink_tpu.streaming.columnar import VectorizedCollectionSource
        from flink_tpu.streaming.sources import CollectSink
        from flink_tpu.streaming.windowing import Time

        class SumAgg(AggregateFunction):
            def create_accumulator(self):
                return 0

            def add(self, value, acc):
                return acc + value[1]

            def get_result(self, acc):
                return acc

            def merge(self, a, b):
                return a + b

        sink = CollectSink()
        env = StreamExecutionEnvironment()
        env.enable_checkpointing(10, tolerable_failures=16)
        env.set_checkpoint_storage(
            "filesystem",
            directory=tempfile.mkdtemp(prefix="flink_tpu_fusedchaos_"))
        env.set_restart_strategy("fixed_delay", restart_attempts=5,
                                 delay_ms=0)
        (env.add_source(VectorizedCollectionSource(data, timestamped=True,
                                                   chunk=512))
            .map(lambda t: (t[0], t[1] * 3))
            .filter(lambda t: t[1] % 7 != 0)
            .key_by(0)
            .time_window(Time.milliseconds_of(100))
            .aggregate(SumAgg())
            .add_sink(sink))
        before = cf.FUSION_STATS.fused_batches
        result = env.execute("fused-chaos")
        engaged = cf.FUSION_STATS.fused_batches - before
        return collections.Counter(sink.values), result, engaged

    faults.deactivate()
    baseline, _, engaged = run()
    assert engaged > 0, "the fused chain must actually run"
    inj = FaultInjector(seed=13)
    inj.fail_n_times("storage.persist", 1)
    inj.fail_n_times("task.process", 1, after=4)
    inj.delay("task.process", 2)
    faults.install(inj)
    try:
        chaos, result, engaged = run()
    finally:
        faults.deactivate()
    assert result.restarts >= 1, "the injected crash must have fired"
    assert engaged > 0, "replayed batches must ride the fused chain too"
    assert chaos == baseline
    assert cf.FUSION_STATS.demotions == 0


# ---------------------------------------------------------------------
# end-to-end: fused and unfused executions of the same job are equal


@pytest.mark.parametrize("keyer", ["field", "lambda"])
def test_e2e_fused_matches_unfused(keyer):
    from flink_tpu.core.functions import AggregateFunction
    from flink_tpu.streaming.columnar import VectorizedCollectionSource
    from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    from flink_tpu.streaming.sources import CollectSink
    from flink_tpu.streaming.windowing import Time

    rng = np.random.default_rng(11)
    data = [((int(k), int(v)), int(t)) for t, (k, v) in enumerate(
        zip(rng.integers(0, 7, 3000), rng.integers(0, 100, 3000)))]

    class SumAgg(AggregateFunction):
        def create_accumulator(self):
            return 0

        def add(self, value, acc):
            return acc + value[1]

        def get_result(self, acc):
            return acc

        def merge(self, a, b):
            return a + b

    def run(fused):
        sink = CollectSink()
        env = StreamExecutionEnvironment()
        (env.add_source(VectorizedCollectionSource(data, timestamped=True,
                                                   chunk=512))
            .map(lambda t: (t[0], t[1] * 3))
            .filter(lambda t: t[1] % 7 != 0)
            .key_by(0 if keyer == "field" else (lambda v: v[0]))
            .time_window(Time.milliseconds_of(100))
            .aggregate(SumAgg())
            .add_sink(sink))
        saved = cf.FUSION_ENABLED
        cf.FUSION_ENABLED = fused
        before = cf.FUSION_STATS.fused_batches
        try:
            env.execute("fusion-e2e")
        finally:
            cf.FUSION_ENABLED = saved
        return sorted(sink.values), cf.FUSION_STATS.fused_batches - before

    ref, engaged_off = run(fused=False)
    got, engaged_on = run(fused=True)
    assert engaged_off == 0
    assert engaged_on > 0
    assert ref
    assert got == ref
    assert cf.FUSION_STATS.demotions == 0

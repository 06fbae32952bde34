"""Differential suite for batched keyed-state ingest and columnar
snapshots (docs/state.md): heap-vs-TPU and boxed-vs-columnar must be
bit-equal — values AND timestamps — across batch ingest, snapshot
round-trips in all four backend directions, rescale re-split,
eviction/spill boundaries, a batch straddling a checkpoint barrier,
and a seeded chaos restore.
"""

import sys
import threading

import numpy as np
import pytest

from flink_tpu.core.config import Configuration
from flink_tpu.core.keygroups import (
    KeyGroupRange,
    assign_key_groups_np,
    assign_to_key_group,
    compute_key_group_range_for_operator_index,
    stable_hashes_np,
)
from flink_tpu.core.state import (
    AggregatingStateDescriptor,
    FoldingStateDescriptor,
    ListStateDescriptor,
    ReducingStateDescriptor,
)
from flink_tpu import native
from flink_tpu.core.key_columns import KeyTable
from flink_tpu.ops.device_agg import SumAggregate
from flink_tpu.state.loader import load_state_backend
from flink_tpu.state.stats import STATE_STATS
from flink_tpu.streaming.elements import RecordBatch
from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
from flink_tpu.streaming.window_operator import (
    EvictingWindowOperator,
    WindowOperator,
)
from flink_tpu.streaming.windowing import (
    CountEvictor,
    CountTrigger,
    DynamicEventTimeSessionWindows,
    EventTimeSessionWindows,
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
    TumblingProcessingTimeWindows,
)

MAX_PAR = 128
FULL_RANGE = KeyGroupRange(0, MAX_PAR - 1)
BACKENDS = ["heap", "tpu"]


def make_backend(name, **kw):
    return load_state_backend(name, FULL_RANGE, MAX_PAR, **kw)


# ---------------------------------------------------------------------
# backend.add_batch contract
# ---------------------------------------------------------------------

def _scalar_reference(name, keys, nss, vals):
    """Per-row adds — the semantics batch ingest must reproduce."""
    b = make_backend(name)
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    for k, ns, v in zip(keys, nss, vals):
        b.set_current_key(k)
        st.set_current_namespace(ns)
        st.add(v)
    return b, st


@pytest.mark.parametrize("name", BACKENDS)
def test_add_batch_matches_scalar(name):
    rng = np.random.default_rng(3)
    keys = [int(k) for k in rng.integers(0, 23, 400)]
    nss = [("w", int(n)) for n in rng.integers(0, 4, 400)]
    vals = rng.integers(0, 100, 400).astype(np.float64)

    ref_b, ref_st = _scalar_reference(name, keys, nss,
                                      [float(v) for v in vals])
    b = make_backend(name)
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    path = b.add_batch(st, keys, None, vals, namespaces=nss)
    assert path == "batch"
    for k, ns in set(zip(keys, nss)):
        for bk, s in ((ref_b, ref_st), (b, st)):
            bk.set_current_key(k)
            s.set_current_namespace(ns)
        assert st.get() == ref_st.get(), (k, ns)


@pytest.mark.parametrize("name", BACKENDS)
def test_add_batch_single_namespace(name):
    b = make_backend(name)
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    assert b.add_batch(st, [1, 2, 1], ("w",), [1.0, 2.0, 3.0]) == "batch"
    b.set_current_key(1)
    st.set_current_namespace(("w",))
    assert st.get() == 4.0


@pytest.mark.parametrize("name", BACKENDS)
def test_add_batch_row_fallback_for_opaque_state(name):
    """A state without a native add_batch (folding) takes the exact
    per-row path and reports it."""
    b = make_backend(name)
    st = b.get_or_create_keyed_state(
        FoldingStateDescriptor("f", "", lambda acc, v: acc + v))
    calls_before = STATE_STATS.row_fallback_calls
    assert b.add_batch(st, ["a", "b", "a"], ("n",), ["x", "y", "z"]) == "rows"
    assert STATE_STATS.row_fallback_calls == calls_before + 1
    b.set_current_key("a")
    st.set_current_namespace(("n",))
    assert st.get() == "xz"


def test_heap_float_fold_order_bit_equal():
    """The heap grouped fold must preserve arrival order per (key, ns)
    — float rounding is order-sensitive, and batch ingest must not
    reorder."""
    rng = np.random.default_rng(11)
    vals = (rng.random(300) * 1e6).astype(np.float64)
    keys = [int(k) for k in rng.integers(0, 7, 300)]

    b1 = make_backend("heap")
    s1 = b1.get_or_create_keyed_state(
        ReducingStateDescriptor("r", lambda a, c: a + c * 1.0000001))
    s1.set_current_namespace(("w",))
    for k, v in zip(keys, vals):
        b1.set_current_key(k)
        s1.set_current_namespace(("w",))
        s1.add(float(v))

    b2 = make_backend("heap")
    s2 = b2.get_or_create_keyed_state(
        ReducingStateDescriptor("r", lambda a, c: a + c * 1.0000001))
    assert b2.add_batch(s2, keys, ("w",), [float(v) for v in vals]) == "batch"
    for k in set(keys):
        b1.set_current_key(k)
        s1.set_current_namespace(("w",))
        b2.set_current_key(k)
        s2.set_current_namespace(("w",))
        assert s1.get() == s2.get()  # bit-equal, not approx


def test_assign_key_groups_batch_parity():
    keys = ["a", "b", 7, -3, 2 ** 70, ("t", 1), 3.5]
    b = make_backend("heap")
    kgs = b.assign_key_groups_batch(keys)
    assert kgs.tolist() == [assign_to_key_group(k, MAX_PAR) for k in keys]
    # int fast path uses splitmix64 — same parity
    ints = [int(i) for i in range(50)]
    assert b.assign_key_groups_batch(ints).tolist() == [
        assign_to_key_group(k, MAX_PAR) for k in ints]


# ---------------------------------------------------------------------
# WindowOperator.process_batch vs process_element
# ---------------------------------------------------------------------

class _KVSum(SumAggregate):
    def __init__(self):
        super().__init__(np.float32)

    def extract_value(self, value):
        return value[1] if isinstance(value, tuple) else value


def _window_op(assigner, **kw):
    def fn(key, window, elements):
        for v in elements:
            yield (key, float(v), window.start, window.end)
    return WindowOperator(
        assigner, AggregatingStateDescriptor("win-sum", _KVSum()),
        window_function=fn, **kw)


#: a function of the row boxes a batch's keys into a list; `key_by(0)`
#: over the integer field keeps them as one int64 column
SELECTORS = {"lambda": lambda x: x[0], "field": 0}


def _drive(mode, backend, assigner, seed=7, chunks=6, late_every=0,
           selector="lambda", **kw):
    op = _window_op(assigner, **kw)
    h = OneInputStreamOperatorTestHarness(
        op, key_selector=SELECTORS[selector], state_backend=backend)
    h.open()
    rng = np.random.default_rng(seed)
    for chunk in range(chunks):
        n = 50
        keys = rng.integers(0, 5, n)
        vals = rng.integers(0, 100, n).astype(np.float64)
        ts = np.abs(rng.integers(chunk * 1000 - 500, chunk * 1000 + 2500,
                                 n).astype(np.int64))
        if late_every:
            ts[::late_every] = 5  # fully late once the watermark moves
        batch = RecordBatch({"f0": keys, "f1": vals}, ts=ts)
        if mode == "batch":
            h.process_batch(batch)
        else:
            for r in batch.to_records():
                h.process_element(r)
        h.process_watermark(chunk * 1000 + 800)
    h.process_watermark(10 ** 13)
    out = [(r.value, r.timestamp) for r in h.get_output()]
    return out, op, h


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("lateness", [0, 700])
@pytest.mark.parametrize("selector", list(SELECTORS))
def test_window_batch_vs_row_tumbling(selector, backend, lateness):
    asg = TumblingEventTimeWindows.of(1000)
    a, op_a, _ = _drive("row", backend, asg, allowed_lateness=lateness,
                        late_every=17)
    asg = TumblingEventTimeWindows.of(1000)
    demoted = KeyTable.demotions
    b, op_b, _ = _drive("batch", backend, asg, allowed_lateness=lateness,
                        late_every=17, selector=selector)
    assert a == b  # values AND timestamps, in emission order
    assert op_a.num_late_records_dropped == op_b.num_late_records_dropped
    # every batch row was consumed columnar — no boxed fallback
    assert op_b.boxed_fallbacks == 0 and op_b.columnar_rows == 300
    assert op_b.key_column_rows == (300 if selector == "field" else 0)
    assert KeyTable.demotions == demoted


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("selector", list(SELECTORS))
def test_window_batch_vs_row_sliding(selector, backend):
    a, _, _ = _drive("row", backend, SlidingEventTimeWindows.of(1500, 500))
    b, op_b, _ = _drive("batch", backend,
                        SlidingEventTimeWindows.of(1500, 500),
                        selector=selector)
    assert a == b
    assert op_b.boxed_fallbacks == 0
    assert op_b.key_column_rows == (300 if selector == "field" else 0)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("before, after", [
    ("lambda", "lambda"), ("field", "field"), ("field", "lambda"),
    ("lambda", "field")])
def test_window_batch_timers_survive_snapshot(before, after, backend):
    """Timers registered by the bulk path are part of operator state:
    snapshot mid-stream, restore into a fresh harness, watermark fires
    the same windows, whichever path (a key list, an int64 column)
    took the keys on either side of the barrier."""
    asg = TumblingEventTimeWindows.of(1000)
    op = _window_op(asg)
    h = OneInputStreamOperatorTestHarness(op, key_selector=SELECTORS[before],
                                          state_backend=backend)
    h.open()
    keys = np.array([1, 2, 1, 3], np.int64)
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    h.process_batch(RecordBatch({"f0": keys, "f1": vals},
                                ts=np.array([100, 200, 300, 1500], np.int64)))
    snap = h.snapshot()

    op2 = _window_op(TumblingEventTimeWindows.of(1000))
    h2 = OneInputStreamOperatorTestHarness(op2, key_selector=SELECTORS[after],
                                           state_backend=backend)
    h2.open()
    h2.initialize_state(snap)
    h2.process_watermark(2500)
    out = sorted(h2.extract_output_values())
    assert out == [(1, 4.0, 0, 1000), (2, 2.0, 0, 1000), (3, 4.0, 1000, 2000)]


def test_window_batch_demotions_and_eligibility():
    from flink_tpu.analysis.columnar_eligibility import (
        BOXED,
        NATIVE,
        operator_batch_report,
    )

    def fn(key, window, elements):
        yield from elements

    native = _window_op(TumblingEventTimeWindows.of(1000))
    mode, reason = operator_batch_report(native)
    assert mode == NATIVE and native._batch_eligibility() is None

    # event-time sessions of a static gap over pre-aggregated state
    # take batches (PR 37); over raw elements, or with a gap per
    # element, they stay per-row and say why
    session = _window_op(EventTimeSessionWindows.with_gap(100))
    mode, reason = operator_batch_report(session)
    assert mode == NATIVE and session._batch_eligibility() is None
    session = WindowOperator(
        EventTimeSessionWindows.with_gap(100),
        ListStateDescriptor("w"), window_function=fn)
    mode, reason = operator_batch_report(session)
    assert mode == BOXED and "raw elements are per-row" in reason
    session = _window_op(DynamicEventTimeSessionWindows(lambda v: 100))
    mode, reason = operator_batch_report(session)
    assert mode == BOXED and "merging" in reason

    proc = WindowOperator(
        TumblingProcessingTimeWindows.of(1000),
        ListStateDescriptor("w"), window_function=fn)
    mode, reason = operator_batch_report(proc)
    assert mode == BOXED and "TumblingProcessingTimeWindows" in reason

    custom = WindowOperator(
        TumblingEventTimeWindows.of(1000),
        ListStateDescriptor("w"), window_function=fn,
        trigger=CountTrigger(3))
    mode, reason = operator_batch_report(custom)
    assert mode == BOXED and "trigger" in reason

    evicting = EvictingWindowOperator(
        TumblingEventTimeWindows.of(1000), fn,
        evictor=CountEvictor.of(2))
    mode, reason = operator_batch_report(evicting)
    assert mode == BOXED and "evictor" in reason


@pytest.mark.parametrize("backend", BACKENDS)
def test_window_batch_demoted_path_still_correct(backend):
    """A demoted operator consumes batches through the boxed loop —
    same output as the row path, reason recorded."""
    a, _, _ = _drive("row", backend,
                     DynamicEventTimeSessionWindows(lambda v: 400))
    b, op_b, _ = _drive("batch", backend,
                        DynamicEventTimeSessionWindows(lambda v: 400))
    assert a == b
    assert op_b.boxed_fallbacks > 0
    assert "merging" in op_b.columnar_fallback_reason


@pytest.mark.parametrize("backend", BACKENDS)
def test_window_batch_vs_row_sessions(backend):
    """Static-gap event-time sessions take the batch path (PR 37): the
    rows of the row path, no boxed fallback."""
    a, op_a, _ = _drive("row", backend,
                        EventTimeSessionWindows.with_gap(400), late_every=17)
    b, op_b, _ = _drive("batch", backend,
                        EventTimeSessionWindows.with_gap(400), late_every=17)
    assert a == b
    assert op_a.num_late_records_dropped == op_b.num_late_records_dropped > 0
    assert op_b.boxed_fallbacks == 0 and op_b.columnar_rows == 300


@pytest.mark.parametrize("backend", BACKENDS)
def test_window_batch_without_timestamps_demotes(backend):
    op = _window_op(TumblingEventTimeWindows.of(1000))
    h = OneInputStreamOperatorTestHarness(op, key_selector=lambda x: x[0],
                                          state_backend=backend)
    h.open()
    with pytest.raises(ValueError):
        # boxed loop raises exactly like the scalar path does for
        # event-time windows without timestamps
        h.process_batch(RecordBatch(
            {"f0": np.array([1]), "f1": np.array([2.0])}))
    assert op.columnar_fallback_reason == "rows without event timestamps"


# ---------------------------------------------------------------------
# columnar snapshots: 4 directions, rescale, chaos
# ---------------------------------------------------------------------

def _populate_batch(name, n=200, seed=5, **kw):
    b = make_backend(name, **kw)
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    rng = np.random.default_rng(seed)
    keys = [int(k) for k in rng.integers(0, 40, n)]
    nss = [(int(w) * 100, int(w) * 100 + 100) for w in rng.integers(0, 3, n)]
    vals = rng.integers(0, 50, n).astype(np.float64)
    b.add_batch(st, keys, None, vals, namespaces=nss)
    # a heap-columnar reducing state rides along in the same snapshot
    red = b.get_or_create_keyed_state(ReducingStateDescriptor(
        "r", lambda a, c: a + c))
    b.add_batch(red, keys, ("fixed",), [int(v) for v in vals])
    return b, keys, nss, vals


def _expected(keys, nss, vals):
    sums = {}
    for k, ns, v in zip(keys, nss, vals):
        sums[(k, ns)] = sums.get((k, ns), np.float32(0)) + np.float32(v)
    red = {}
    for k, v in zip(keys, vals):
        red[k] = red.get(k, 0) + int(v)
    return sums, red


def _check_restored(b, keys, nss, vals):
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    red = b.get_or_create_keyed_state(ReducingStateDescriptor(
        "r", lambda a, c: a + c))
    sums, reds = _expected(keys, nss, vals)
    rng = b.key_group_range
    for (k, ns), want in sums.items():
        if not rng.contains(assign_to_key_group(k, MAX_PAR)):
            continue
        b.set_current_key(k)
        st.set_current_namespace(ns)
        assert st.get() == pytest.approx(float(want)), (k, ns)
    for k, want in reds.items():
        if not rng.contains(assign_to_key_group(k, MAX_PAR)):
            continue
        b.set_current_key(k)
        red.set_current_namespace(("fixed",))
        got = red.get()
        assert got == want and type(got) is int, k


@pytest.mark.parametrize("src", BACKENDS)
@pytest.mark.parametrize("dst", BACKENDS)
def test_columnar_snapshot_all_directions(src, dst):
    b1, keys, nss, vals = _populate_batch(src)
    cols_before = STATE_STATS.snapshot_columns
    snap = b1.snapshot()
    assert STATE_STATS.snapshot_columns > cols_before  # went columnar
    b2 = make_backend(dst)
    b2.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    b2.get_or_create_keyed_state(ReducingStateDescriptor(
        "r", lambda a, c: a + c))
    b2.restore([snap])
    _check_restored(b2, keys, nss, vals)


@pytest.mark.parametrize("src", BACKENDS)
@pytest.mark.parametrize("dst", BACKENDS)
def test_columnar_rescale_resplit(src, dst):
    b1, keys, nss, vals = _populate_batch(src, n=300)
    snap = b1.snapshot()
    for idx in range(3):
        rng = compute_key_group_range_for_operator_index(MAX_PAR, 3, idx)
        b = load_state_backend(dst, rng, MAX_PAR)
        b.get_or_create_keyed_state(
            AggregatingStateDescriptor("s", SumAggregate(np.float32)))
        b.get_or_create_keyed_state(ReducingStateDescriptor(
            "r", lambda a, c: a + c))
        b.restore([snap])
        _check_restored(b, keys, nss, vals)


def test_snapshot_straddles_batch_with_pending_ring():
    """A checkpoint barrier can land between two add_batch calls while
    the device pending ring is non-empty — the snapshot must contain
    the flushed prefix, and the restored backend must accept the rest
    and agree with an uninterrupted run."""
    b = make_backend("tpu")
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    keys = [int(k) for k in np.random.default_rng(9).integers(0, 10, 100)]
    vals = np.arange(100, dtype=np.float64)
    b.add_batch(st, keys[:60], ("w",), vals[:60])
    assert len(st._pending_slots) > 0  # ring non-empty at the barrier
    snap = b.snapshot()

    b2 = make_backend("tpu")
    st2 = b2.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    b2.restore([snap])
    b2.add_batch(st2, keys[60:], ("w",), vals[60:])

    ref = make_backend("heap")
    rst = ref.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    ref.add_batch(rst, keys, ("w",), vals)
    for k in set(keys):
        b2.set_current_key(k)
        st2.set_current_namespace(("w",))
        ref.set_current_key(k)
        rst.set_current_namespace(("w",))
        assert st2.get() == pytest.approx(rst.get()), k


class _KeyDoor:
    """A state with the three batch doors, noting the keys each got."""

    def __init__(self, takes_key_column):
        self.takes_key_column = takes_key_column
        self.seen = []

    def add_batch(self, keys, namespace, values, namespaces=None):
        self.seen.append(keys)

    def get_batch(self, keys, namespace, namespaces=None):
        self.seen.append(keys)
        return [None] * len(keys), np.zeros(len(keys), bool)

    def clear_batch(self, keys, namespace, namespaces=None):
        self.seen.append(keys)


@pytest.mark.parametrize("takes", [False, True])
@pytest.mark.parametrize("name", ["heap", "tpu"])
def test_the_backend_hands_a_key_column_on_only_to_a_state_that_takes_one(
        name, takes):
    """An int64 column given to the backend's batch doors reaches a
    state with a column door as it is, and every other state as the
    list of Python ints it always got."""
    b = make_backend(name)
    door = _KeyDoor(takes)
    keys = np.array([7, -3, 2 ** 40], np.int64)
    b.add_batch(door, keys, ("w",), np.ones(3), pre_extracted=False)
    b.get_batch(door, keys, ("w",))
    b.clear_batch(door, keys, ("w",))
    assert len(door.seen) == 3
    for seen in door.seen:
        if takes:
            assert seen is keys
        else:
            assert type(seen) is list and seen == [7, -3, 2 ** 40]
            assert {type(k) for k in seen} == {int}


def test_flush_waits_for_the_table_it_updates(monkeypatch):
    """Every flush, a full micro-batch's or a read's, waits for the
    program last dispatched on the table before it dispatches the next,
    so one update is in flight at most and a fire's gather never queues
    behind a window of them."""
    from flink_tpu.state import tpu_backend
    b = make_backend("tpu", microbatch=16)
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    waited = []
    real = tpu_backend.jax.block_until_ready
    monkeypatch.setattr(tpu_backend.jax, "block_until_ready",
                        lambda x: waited.append(x) or real(x))
    tables = []
    for i in range(3):
        tables.append(st.device_state)
        b.add_batch(st, np.arange(16, dtype=np.int64), ("w",),
                    np.ones(16))
    # a part batch, flushed by the read
    tables.append(st.device_state)
    b.add_batch(st, np.arange(4, dtype=np.int64), ("w",), np.ones(4))
    got, found, _ = b.get_batch(st, np.array([3, 9]), ("w",))
    # each flush waited for the very state the one before it made
    assert waited == tables
    assert found.all() and got.tolist() == pytest.approx([4.0, 3.0])


def test_eviction_spill_boundary_bit_equal():
    """A capped device tier must evict/spill under batch ingest and
    still agree with heap — including across a snapshot taken while
    entries sit in the host spill tier."""
    b, keys, nss, vals = _populate_batch(
        "tpu", n=400, seed=13, initial_capacity=8,
        max_device_slots=16, microbatch=32)
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    assert st.evictions > 0 and len(st.host_tier) > 0
    snap = b.snapshot()
    b2 = make_backend("heap")
    b2.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    b2.get_or_create_keyed_state(ReducingStateDescriptor(
        "r", lambda a, c: a + c))
    b2.restore([snap])
    _check_restored(b2, keys, nss, vals)


def test_chaos_restore_seeded():
    """Seeded chaos: interleave batch/scalar adds, snapshot at random
    points, restore into alternating backends, finish the stream —
    terminal state equals the uninterrupted boxed reference."""
    rng = np.random.default_rng(42)
    n = 500
    keys = [int(k) for k in rng.integers(0, 30, n)]
    nss = [("w", int(w)) for w in rng.integers(0, 2, n)]
    vals = rng.integers(0, 20, n).astype(np.float64)

    ref = make_backend("heap")
    rst = ref.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    for k, ns, v in zip(keys, nss, vals):
        ref.set_current_key(k)
        rst.set_current_namespace(ns)
        rst.add(float(v))

    b = make_backend("tpu", initial_capacity=8,
                     max_device_slots=24, microbatch=16)
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    i = 0
    flip = 0
    while i < n:
        step = int(rng.integers(1, 90))
        j = min(n, i + step)
        if rng.random() < 0.5:
            b.add_batch(st, keys[i:j], None, vals[i:j], namespaces=nss[i:j])
        else:
            for k, ns, v in zip(keys[i:j], nss[i:j], vals[i:j]):
                b.set_current_key(k)
                st.set_current_namespace(ns)
                st.add(float(v))
        i = j
        if rng.random() < 0.4 and i < n:
            snap = b.snapshot()  # crash + restore mid-stream
            flip += 1
            name = "heap" if flip % 2 else "tpu"
            kw = {} if name == "heap" else {
                "initial_capacity": 8, "max_device_slots": 24,
                "microbatch": 16}
            b = make_backend(name, **kw)
            st = b.get_or_create_keyed_state(
                AggregatingStateDescriptor("s", SumAggregate(np.float32)))
            b.restore([snap])
    assert flip > 0
    for k, ns in set(zip(keys, nss)):
        b.set_current_key(k)
        st.set_current_namespace(ns)
        ref.set_current_key(k)
        rst.set_current_namespace(ns)
        assert st.get() == pytest.approx(rst.get()), (k, ns)


def test_merge_namespaces_batch_matches_sequential():
    def run(batched):
        b = make_backend("tpu")
        st = b.get_or_create_keyed_state(
            AggregatingStateDescriptor("m", SumAggregate(np.float32)))
        for k in range(6):
            b.add_batch(st, [k] * 4, None,
                        np.array([1.0, 2.0, 3.0, 4.0]) * (k + 1),
                        namespaces=[("a",), ("b",), ("c",), ("d",)])
        merges = [(k, ("a",), [("b",), ("c",), ("d",)]) for k in range(6)]
        if batched:
            st.merge_namespaces_batch(merges)
        else:
            for k, target, sources in merges:
                b.set_current_key(k)
                st.merge_namespaces(target, sources)
        out = {}
        for k in range(6):
            b.set_current_key(k)
            st.set_current_namespace(("a",))
            out[k] = st.get()
            for ns in (("b",), ("c",), ("d",)):
                st.set_current_namespace(ns)
                assert st.get() is None, (k, ns)
        return out

    assert run(batched=True) == run(batched=False)


# ---------------------------------------------------------------------
# config / gauges
# ---------------------------------------------------------------------

def test_loader_rejects_bad_tuning_keys():
    cfg = Configuration().set("state.backend", "tpu")
    cfg.set("state.backend.tpu.max-device-slots", 64)
    cfg.set("state.backend.tpu.microbatch-size", 512)
    b = load_state_backend(cfg, FULL_RANGE, MAX_PAR)
    assert b.max_device_slots == 64 and b.microbatch == 512
    for key in ("state.backend.tpu.max-device-slots",
                "state.backend.tpu.microbatch-size"):
        bad = Configuration().set("state.backend", "tpu").set(key, 0)
        with pytest.raises(ValueError):
            load_state_backend(bad, FULL_RANGE, MAX_PAR)


def test_config_docs_list_state_backend_keys():
    from flink_tpu.core.config_docs import generate_config_docs
    docs = generate_config_docs()
    assert "state.backend.tpu.max-device-slots" in docs
    assert "state.backend.tpu.microbatch-size" in docs


def test_state_gauges_surface():
    from flink_tpu.runtime.metrics import MetricRegistry, register_state_gauges
    reg = MetricRegistry()
    register_state_gauges(reg)
    b = make_backend("tpu")
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("g", SumAggregate(np.float32)))
    b.add_batch(st, [1, 2, 3], ("w",), np.array([1.0, 2.0, 3.0]))
    st.get()  # forces a flush
    dump = reg.dump()
    assert dump["state.batchRows"] >= 3
    assert dump["state.flushRows"] >= 3
    assert dump["state.device.states"] >= 1
    assert dump["state.device.slotsInUse"] >= 3


# ---------------------------------------------------------------------
# the slot index's two forms (a dict, the native integer table a window
# of integer keys gets): the same job on both, the doors counted, a
# reader on another thread
# ---------------------------------------------------------------------

needs_native = pytest.mark.skipif(
    not native.available(), reason="no native host runtime")


def _observe(st, backend):
    """What the index, the slots and a snapshot of a state hold."""
    keys, namespaces, slots = st.slot_index.columns()
    spilled = st.host_tier.index.columns()
    return {"index": (keys, namespaces, slots.tolist()),
            "spilled": (spilled[0], spilled[1], spilled[2].tolist()),
            "free": list(st._free), "clock": st._clock,
            "stamps": st._access_stamp.tolist(),
            "snapshot": sorted(backend.snapshot().blobs())}


def _tumbling_job(key_of=int, windows=3, rows=64, key_space=150,
                  conf=None, seed=11, selector="lambda"):
    """Tumbling 1 s windows of sums, `rows`-row batches, every window
    fired and cleared; returns the emitted rows, an observation after
    every batch and fire, the state and `STATE_STATS`' counts."""
    op = _window_op(TumblingEventTimeWindows.of(1000))
    h = OneInputStreamOperatorTestHarness(
        op, key_selector=SELECTORS[selector], state_backend=conf or "tpu")
    h.open()
    STATE_STATS.reset()
    demoted = KeyTable.demotions
    rng = np.random.default_rng(seed)
    seen = []
    for window in range(windows):
        for _ in range(4):
            keys = rng.integers(0, key_space, rows)
            ts = window * 1000 + np.sort(rng.integers(0, 1000, rows))
            column = keys if key_of is int else \
                np.array([key_of(k) for k in keys.tolist()], object)
            h.process_batch(RecordBatch(
                {"f0": column,
                 "f1": rng.integers(0, 100, rows).astype(np.float64)}, ts=ts))
            seen.append(_observe(op.window_state, op.keyed_backend))
        h.process_watermark(window * 1000 + 999)
        seen.append(_observe(op.window_state, op.keyed_backend))
    out = [(r.value, r.timestamp) for r in h.get_output()]
    counts = {name: getattr(STATE_STATS, name) for name in (
        "bulk_probe_rows", "per_key_probe_rows", "int_table_rows")}
    counts.update(key_column_rows=op.key_column_rows,
                  columnar_rows=op.columnar_rows,
                  demotions=KeyTable.demotions - demoted)
    return out, seen, op.window_state, counts


@needs_native
def test_an_integer_keyed_job_is_the_same_on_both_forms(monkeypatch):
    """Add, fire, clear over three windows: the integer tables give the
    slots, the free list, the stamps, the results and the snapshot
    bytes the dicts give, step for step."""
    out, seen, st, counts = _tumbling_job()
    assert counts["int_table_rows"] == counts["bulk_probe_rows"] > 0
    monkeypatch.setattr(native, "available", lambda: False)
    want_out, want_seen, want_st, want_counts = _tumbling_job()
    assert want_counts["int_table_rows"] == 0
    assert want_counts["bulk_probe_rows"] == counts["bulk_probe_rows"]
    assert out == want_out and len(out) > 300
    for step, (got, want) in enumerate(zip(seen, want_seen)):
        assert got == want, step
    assert not st.slot_index and not want_st.slot_index
    assert st.capacity == want_st.capacity


@needs_native
@pytest.mark.parametrize("key_of", [int, lambda k: f"k{k}"],
                         ids=["int", "str"])
def test_a_job_is_the_same_on_both_key_paths(key_of):
    """The keys as one int64 column (`key_by(0)` over an integer field)
    against the list the parent made of them: the slots, the free
    list, the stamps, the results and the snapshot bytes step for
    step.  A string column takes the list path on both."""
    out, seen, st, counts = _tumbling_job(key_of, selector="field")
    want_out, want_seen, _, want_counts = _tumbling_job(key_of)
    assert out == want_out and len(out) > 300
    for step, (got, want) in enumerate(zip(seen, want_seen)):
        assert got == want, step
    assert counts["key_column_rows"] == (
        counts["columnar_rows"] if key_of is int else 0)
    assert want_counts["key_column_rows"] == 0
    assert counts["demotions"] == want_counts["demotions"] == 0
    for name in ("bulk_probe_rows", "per_key_probe_rows", "int_table_rows"):
        assert counts[name] == want_counts[name], name


@needs_native
@pytest.mark.parametrize("job", ["tumbling_int", "tumbling_str", "tumbling_mixed",
                                 "sessions_int", "tumbling_int_field",
                                 "sessions_int_field"])
def test_which_jobs_probe_integer_tables(job):
    """Integer keys under one window a batch: every bulk-probed row on
    an integer table, none demoted.  String keys, and session windows
    (a namespace per row): none."""
    if job.startswith("sessions_int"):
        STATE_STATS.reset()
        demoted = KeyTable.demotions
        _, op, _ = _drive("batch", "tpu", EventTimeSessionWindows.with_gap(400),
                          selector="field" if "field" in job else "lambda")
        assert op.boxed_fallbacks == 0
        assert op.key_column_rows == 0
        assert STATE_STATS.bulk_probe_rows > 300
        assert STATE_STATS.int_table_rows == 0
        assert KeyTable.demotions == demoted
        return
    key_of = {"tumbling_int": int, "tumbling_int_field": int,
              "tumbling_str": lambda k: f"k{k}",
              "tumbling_mixed": lambda k: k if k % 7 else float(k) + 0.5}[job]
    field = job.endswith("_field")
    _, _, st, counts = _tumbling_job(key_of,
                                     selector="field" if field else "lambda")
    assert counts["key_column_rows"] == (counts["columnar_rows"] if field
                                         else 0)
    assert counts["per_key_probe_rows"] == 0 and not st.slot_index
    assert counts["bulk_probe_rows"] > 3 * 4 * 64
    assert counts["demotions"] == 0
    assert counts["int_table_rows"] == (
        counts["bulk_probe_rows"] if key_of is int else 0)


@needs_native
def test_a_query_from_another_thread_while_the_owner_inserts_a_million_keys():
    """`query_by_key` probes the slot index from a foreign thread while
    the owner's batches grow the window's integer table from 16 cells
    to 2^21: every read finds the key's own slot (its sum) or, for a
    key not flushed yet, nothing."""
    b = make_backend("tpu")
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    settled = np.arange(0, 2_000_000, 2000)  # 1,000 keys, value key % 97 + 1
    value_of = lambda keys: (keys % 97 + 1).astype(np.float32)
    b.add_batch(st, settled, "w", value_of(settled))
    b.flush_all()
    wrong, reads, stop = [], [0], threading.Event()

    def reader():
        rng = np.random.default_rng(3)
        while not stop.is_set():
            for key in rng.integers(0, 2_000_000, 64).tolist():
                got = st.query_by_key(key, "w")
                want = float(key % 97 + 1)
                if not (got == want or (got is None and key % 2000)):
                    wrong.append((key, got))
                reads[0] += 1

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    thread = threading.Thread(target=reader)
    thread.start()
    try:
        rng = np.random.default_rng(4)
        fresh = rng.permutation(np.arange(1, 2_000_000, 2))[:1_000_000]
        for lo in range(0, len(fresh), 8192):
            keys = fresh[lo:lo + 8192]
            b.add_batch(st, keys, "w", value_of(keys))
    finally:
        stop.set()
        thread.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not thread.is_alive()
    assert not wrong and reads[0] > 1000
    assert isinstance(getattr(st.slot_index.tables["w"], "_ints", None),
                      native.NativeIntTable)
    assert len(st.slot_index) == 1_001_000
    b.flush_all()
    res, found, _ = b.get_batch(st, fresh[:5000], "w")
    assert found.all() and res.tolist() == value_of(fresh[:5000]).tolist()

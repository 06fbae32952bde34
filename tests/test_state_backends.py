"""State-backend contract suite, run against heap AND tpu backends.

Ports the intent of the reference's StateBackendTestBase.java (3,726
LoC abstract suite run against every backend — SURVEY.md §4.3): value/
list/map/reducing/aggregating semantics, namespaces, snapshot/restore,
rescale re-split, and (tpu-only) device/heap differential equivalence.
"""

import numpy as np
import pytest

from flink_tpu.core.config import Configuration
from flink_tpu.core.keygroups import (
    KeyGroupRange,
    assign_to_key_group,
    compute_key_group_range_for_operator_index,
)
from flink_tpu.core.state import (
    AggregatingStateDescriptor,
    ListStateDescriptor,
    MapStateDescriptor,
    ReducingStateDescriptor,
    ValueStateDescriptor,
)
from flink_tpu.ops.device_agg import (
    AvgAggregate,
    CountAggregate,
    MinAggregate,
    SumAggregate,
)
from flink_tpu.ops.sketches import (
    CountMinSketchAggregate,
    HyperLogLogAggregate,
    QuantileSketchAggregate,
)
from flink_tpu.runtime import tracing
from flink_tpu.state import (
    HeapKeyedStateBackend,
    TpuKeyedStateBackend,
    load_state_backend,
)
from flink_tpu.state import tpu_backend
from flink_tpu.state.operator_state import (
    OperatorStateBackend,
    OperatorStateSnapshot,
)
from flink_tpu.state.stats import STATE_STATS

MAX_PAR = 128
FULL_RANGE = KeyGroupRange(0, MAX_PAR - 1)

BACKENDS = ["heap", "tpu"]


def make_backend(name):
    return load_state_backend(name, FULL_RANGE, MAX_PAR)


@pytest.fixture(params=BACKENDS)
def backend(request):
    b = make_backend(request.param)
    yield b
    b.dispose()


# ---------------------------------------------------------------------
# loader
# ---------------------------------------------------------------------

def test_loader_config_switch():
    cfg = Configuration()
    assert isinstance(load_state_backend(cfg, FULL_RANGE, MAX_PAR),
                      HeapKeyedStateBackend)
    cfg.set("state.backend", "tpu")
    assert isinstance(load_state_backend(cfg, FULL_RANGE, MAX_PAR),
                      TpuKeyedStateBackend)
    with pytest.raises(ValueError):
        load_state_backend("nope", FULL_RANGE, MAX_PAR)


# ---------------------------------------------------------------------
# value / list / map state
# ---------------------------------------------------------------------

def test_value_state(backend):
    st = backend.get_or_create_keyed_state(ValueStateDescriptor("v"))
    backend.set_current_key("a")
    assert st.value() is None
    st.update(42)
    assert st.value() == 42
    backend.set_current_key("b")
    assert st.value() is None
    st.update(7)
    backend.set_current_key("a")
    assert st.value() == 42
    st.clear()
    assert st.value() is None
    backend.set_current_key("b")
    assert st.value() == 7


def test_value_state_default(backend):
    st = backend.get_or_create_keyed_state(
        ValueStateDescriptor("vd", default_value=99))
    backend.set_current_key("x")
    assert st.value() == 99
    st.update(1)
    assert st.value() == 1


def test_list_state(backend):
    st = backend.get_or_create_keyed_state(ListStateDescriptor("l"))
    backend.set_current_key("k1")
    assert st.get() is None
    st.add(1)
    st.add(2)
    st.add_all([3, 4])
    assert list(st.get()) == [1, 2, 3, 4]
    st.update([9])
    assert list(st.get()) == [9]
    backend.set_current_key("k2")
    assert st.get() is None
    backend.set_current_key("k1")
    st.clear()
    assert st.get() is None


def test_map_state(backend):
    st = backend.get_or_create_keyed_state(MapStateDescriptor("m"))
    backend.set_current_key("k")
    assert st.is_empty()
    st.put("a", 1)
    st.put_all({"b": 2, "c": 3})
    assert st.get("a") == 1
    assert st.contains("b")
    assert not st.contains("z")
    assert sorted(st.keys()) == ["a", "b", "c"]
    assert sorted(st.values()) == [1, 2, 3]
    st.remove("a")
    assert st.get("a") is None
    assert sorted(dict(st.entries()).keys()) == ["b", "c"]
    st.clear()
    assert st.is_empty()


# ---------------------------------------------------------------------
# reducing / aggregating
# ---------------------------------------------------------------------

def test_reducing_state(backend):
    st = backend.get_or_create_keyed_state(
        ReducingStateDescriptor("r", lambda a, b: a + b))
    backend.set_current_key("k")
    assert st.get() is None
    st.add(5)
    st.add(6)
    assert st.get() == 11
    backend.set_current_key("other")
    st.add(1)
    assert st.get() == 1


def test_aggregating_state_device_sum(backend):
    st = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("agg", SumAggregate(np.float32)))
    backend.set_current_key("k")
    assert st.get() is None
    st.add(1.5)
    st.add(2.5)
    assert st.get() == pytest.approx(4.0)
    backend.set_current_key("j")
    st.add(10.0)
    assert st.get() == pytest.approx(10.0)
    backend.set_current_key("k")
    st.clear()
    assert st.get() is None


def test_aggregating_state_namespaces(backend):
    st = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("aggns", CountAggregate()))
    backend.set_current_key("k")
    st.set_current_namespace(("w", 0))
    st.add(object())
    st.add(object())
    st.set_current_namespace(("w", 1))
    st.add(object())
    assert st.get() == 1
    st.set_current_namespace(("w", 0))
    assert st.get() == 2


def test_merge_namespaces(backend):
    st = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("m_agg", SumAggregate(np.float32)))
    backend.set_current_key("k")
    for ns, v in [(("s", 1), 1.0), (("s", 2), 2.0), (("s", 3), 4.0)]:
        st.set_current_namespace(ns)
        st.add(v)
    st.merge_namespaces(("s", 9), [("s", 1), ("s", 2), ("s", 3)])
    st.set_current_namespace(("s", 9))
    assert st.get() == pytest.approx(7.0)
    for ns in [("s", 1), ("s", 2), ("s", 3)]:
        st.set_current_namespace(ns)
        assert st.get() is None


def test_hll_aggregating(backend):
    st = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("hll", HyperLogLogAggregate(precision=10)))
    backend.set_current_key("page1")
    for i in range(1000):
        st.add(f"user-{i}")
    est = st.get()
    assert abs(est - 1000) / 1000 < 0.12


def test_get_keys(backend):
    st = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("gk", CountAggregate()))
    for k in ["a", "b", "c"]:
        backend.set_current_key(k)
        st.set_current_namespace("ns0")
        st.add(1)
    assert sorted(backend.get_keys("gk", "ns0")) == ["a", "b", "c"]
    assert backend.get_keys("gk", "nsX") == []


# ---------------------------------------------------------------------
# snapshot / restore / rescale
# ---------------------------------------------------------------------

def _populate(backend, n=50):
    v = backend.get_or_create_keyed_state(ValueStateDescriptor("v"))
    agg = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("agg", SumAggregate(np.float32)))
    for i in range(n):
        backend.set_current_key(f"key-{i}")
        v.update(i)
        agg.set_current_namespace("w0")
        agg.add(float(i))
        agg.add(1.0)


def _check(backend, n=50):
    v = backend.get_or_create_keyed_state(ValueStateDescriptor("v"))
    agg = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("agg", SumAggregate(np.float32)))
    for i in range(n):
        backend.set_current_key(f"key-{i}")
        assert v.value() == i
        agg.set_current_namespace("w0")
        assert agg.get() == pytest.approx(i + 1.0)


@pytest.mark.parametrize("name", BACKENDS)
def test_snapshot_restore_roundtrip(name):
    b1 = make_backend(name)
    _populate(b1)
    snap = b1.snapshot()
    assert snap.total_bytes > 0
    b2 = make_backend(name)
    # bind states first (descriptors must be known before restore)
    b2.get_or_create_keyed_state(ValueStateDescriptor("v"))
    b2.get_or_create_keyed_state(
        AggregatingStateDescriptor("agg", SumAggregate(np.float32)))
    b2.restore([snap])
    _check(b2)


@pytest.mark.parametrize("name", BACKENDS)
def test_cross_backend_restore(name):
    """heap snapshot restores into tpu backend and vice versa — the
    `state.backend` switch must be transparent across restarts."""
    other = "tpu" if name == "heap" else "heap"
    b1 = make_backend(name)
    _populate(b1, 20)
    snap = b1.snapshot()
    b2 = make_backend(other)
    b2.get_or_create_keyed_state(ValueStateDescriptor("v"))
    b2.get_or_create_keyed_state(
        AggregatingStateDescriptor("agg", SumAggregate(np.float32)))
    b2.restore([snap])
    _check(b2, 20)


@pytest.mark.parametrize("name", BACKENDS)
def test_rescale_resplit(name):
    """Snapshot at parallelism 1, restore at parallelism 2: each new
    subtask takes only the chunks in its key-group range (ref:
    RescalingITCase, StateAssignmentOperation)."""
    b1 = make_backend(name)
    _populate(b1, 60)
    snap = b1.snapshot()

    parts = []
    for idx in range(2):
        rng = compute_key_group_range_for_operator_index(MAX_PAR, 2, idx)
        b = load_state_backend(name, rng, MAX_PAR)
        b.get_or_create_keyed_state(ValueStateDescriptor("v"))
        b.get_or_create_keyed_state(
            AggregatingStateDescriptor("agg", SumAggregate(np.float32)))
        b.restore([snap])
        parts.append((rng, b))

    seen = set()
    for i in range(60):
        key = f"key-{i}"
        kg = assign_to_key_group(key, MAX_PAR)
        owner = [b for rng, b in parts if rng.contains(kg)]
        assert len(owner) == 1
        b = owner[0]
        v = b.get_or_create_keyed_state(ValueStateDescriptor("v"))
        b.set_current_key(key)
        assert v.value() == i
        seen.add(key)
    assert len(seen) == 60
    # both subtasks actually own some keys
    for rng, b in parts:
        assert any(rng.contains(assign_to_key_group(f"key-{i}", MAX_PAR))
                   for i in range(60))


# ---------------------------------------------------------------------
# tpu-specific: batched API + differential vs heap
# ---------------------------------------------------------------------

def test_tpu_add_batch_matches_heap():
    rng_keys = [f"k{i % 17}" for i in range(500)]
    vals = np.arange(500, dtype=np.float32)

    heap = make_backend("heap")
    hs = heap.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    for k, v in zip(rng_keys, vals):
        heap.set_current_key(k)
        hs.set_current_namespace("w")
        hs.add(float(v))

    tpu = make_backend("tpu")
    ts = tpu.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    ts.add_batch(rng_keys, "w", vals)

    for k in set(rng_keys):
        heap.set_current_key(k)
        hs.set_current_namespace("w")
        tpu.set_current_key(k)
        ts.set_current_namespace("w")
        assert ts.get() == pytest.approx(hs.get()), k


def test_tpu_capacity_growth():
    tpu = TpuKeyedStateBackend(FULL_RANGE, MAX_PAR, initial_capacity=8)
    st = tpu.get_or_create_keyed_state(
        AggregatingStateDescriptor("g", CountAggregate()))
    for i in range(100):
        tpu.set_current_key(i)
        st.add(1)
    for i in range(100):
        tpu.set_current_key(i)
        assert st.get() == 1


def test_tpu_get_batch():
    tpu = make_backend("tpu")
    st = tpu.get_or_create_keyed_state(
        AggregatingStateDescriptor("gb", SumAggregate(np.float32)))
    keys = [f"k{i}" for i in range(10)]
    st.add_batch(keys, "w", np.arange(10, dtype=np.float32))
    res, found = st.get_batch(keys + ["missing"], "w")
    assert found[:10].all() and not found[10]
    np.testing.assert_allclose(res[:10], np.arange(10, dtype=np.float32))


# `state.result` runs only at bucketed shapes: a power of two up to one
# tile, whole tiles above it.  The tile is RESULT_SCRATCH_BYTES over
# the state's bytes per slot; the tests shrink the budget to 16 slots.
RESULT_TILE = 16
RESULT_AGGS = {
    "sum": lambda: SumAggregate(np.float32),
    "min": lambda: MinAggregate(np.float32),
    "avg": AvgAggregate,
    "hll_p12": lambda: HyperLogLogAggregate(precision=12),
    "countmin": lambda: CountMinSketchAggregate(depth=2, width=64),
    "quantile": lambda: QuantileSketchAggregate((0.5, 0.99)),
}


def _small_tile_state(agg, monkeypatch, **backend_kw):
    backend = TpuKeyedStateBackend(FULL_RANGE, MAX_PAR, **backend_kw)
    st = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("bucketed", agg))
    bytes_per_slot = sum(spec.dtype.itemsize * int(np.prod(spec.shape))
                         for spec in agg.state_specs().values())
    monkeypatch.setattr(tpu_backend, "RESULT_SCRATCH_BYTES",
                        RESULT_TILE * bytes_per_slot)
    assert st._result_tile() == RESULT_TILE
    return backend, st


@pytest.mark.parametrize("agg, tile_form_runs, in_place", [
    ("quantile", True, True), ("quantile", False, False),
    ("hll_p12", True, True), ("hll_p12", False, False),
    ("countmin", True, False),
    ("sum", True, False)])
def test_a_flush_notes_whether_it_ran_in_place(agg, tile_form_runs, in_place,
                                               monkeypatch):
    """`flush_row_form_batches` beside `flush_batches`: every flush of
    a quantile or HLL sketch that is small against the table where the
    tile kernels run (a TPU; stood in for here, where each flush
    scatters cells and none is noted), none of any other aggregate's,
    and none while the batch is not small."""
    from flink_tpu.ops import sketches
    monkeypatch.setattr(sketches, "_tile_form_runs", lambda: tile_form_runs)
    backend = TpuKeyedStateBackend(FULL_RANGE, MAX_PAR,
                                   initial_capacity=1024, microbatch=64)
    st = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("noted", RESULT_AGGS[agg]()))
    rng = np.random.default_rng(3)
    before = (STATE_STATS.flush_batches, STATE_STATS.flush_row_form_batches)
    for window in range(5):
        st.add_batch(rng.integers(0, 50, 64).tolist(), window,
                     rng.integers(1, 1000, 64).astype(np.float32))
    flushed = STATE_STATS.flush_batches - before[0]
    noted = STATE_STATS.flush_row_form_batches - before[1]
    assert flushed == 5 and st.capacity == 1024
    assert noted == (flushed if in_place else 0)
    # a batch that is not small against the table scatters cells
    st.add_batch(list(range(512)) * 2, 9, np.ones(1024, np.float32))
    assert STATE_STATS.flush_batches - before[0] == 6 \
        and st.capacity == 1024
    assert STATE_STATS.flush_row_form_batches - before[1] == noted


@pytest.mark.parametrize("n, padded", [
    (1, 1), (7, 8), (8, 8), (9, 16), (RESULT_TILE, RESULT_TILE),
    (RESULT_TILE + 1, 2 * RESULT_TILE),
    (2 * RESULT_TILE + 3, 3 * RESULT_TILE)])
@pytest.mark.parametrize("agg", sorted(RESULT_AGGS))
def test_tpu_get_batch_is_scalar_get_at_every_bucket(
        agg, n, padded, monkeypatch):
    """Resident rows, rows in the host spill tier and missing keys in
    one call: every row is the scalar get() of its key bit for bit,
    whatever the gather was padded to."""
    backend, st = _small_tile_state(
        RESULT_AGGS[agg](), monkeypatch, initial_capacity=64,
        microbatch=4, max_device_slots=64)
    keys = list(range(n))
    present = [k for k in keys if k % 5 != 3]
    rng = np.random.default_rng(n)
    rows = np.repeat(present, rng.integers(1, 5, len(present)))
    st.add_batch(rows.tolist(), "w",
                 rng.integers(1, 1000, len(rows)).astype(np.float32))
    # age every slot past the LRU's protected window, then spill the
    # coldest third of the keys to the host tier
    st._clock += 1000
    if len(present) >= 3:
        st._evict_cold(len(present) // 3)
    assert len(st.host_tier) == len(present) // 3
    before = (STATE_STATS.result_rows, STATE_STATS.result_padded_rows)
    res, found = st.get_batch(keys, "w")
    assert (STATE_STATS.result_rows - before[0],
            STATE_STATS.result_padded_rows - before[1]) == (n, padded)
    assert len(res) == n
    assert found.tolist() == [k % 5 != 3 for k in keys]
    st.set_current_namespace("w")
    for k in keys:
        backend.set_current_key(k)
        scalar = st.get()  # promotes a spilled row: after the batch read
        if k % 5 == 3:
            assert scalar is None
        else:
            assert np.asarray(scalar, res.dtype).tobytes() == \
                res[k].tobytes(), (agg, n, k)


def test_tpu_get_batch_of_no_keys_dispatches_nothing():
    tracing.reset_jit_stats()
    backend = make_backend("tpu")
    st = backend.get_or_create_keyed_state(AggregatingStateDescriptor(
        "empty", QuantileSketchAggregate((0.5, 0.99))))
    res, found = st.get_batch([], "w")
    assert res.shape == (0, 2) and res.dtype == np.float32
    assert found.shape == (0,)
    result = tracing.jit_stats()["state.result"]
    assert result["recompiles"] == 0 and result["cache_hits"] == 0


def test_tpu_fires_inside_one_bucket_share_one_program(monkeypatch):
    """Five fires of five key counts, below and above the tile, compile
    `state.result` once; the phase says what each was padded to."""
    tracing.reset_jit_stats()
    backend, st = _small_tile_state(SumAggregate(np.float32), monkeypatch)
    old = tracing.get_tracer()
    tr = tracing.set_tracer(tracing.Tracer())
    tr.enabled = True
    try:
        for window, n in enumerate((9, 13, 16, 29, 45)):
            keys = list(range(n))
            st.add_batch(keys, window, np.ones(n, np.float32))
            res, found = st.get_batch(keys, window)
            assert found.all() and (res == 1.0).all()
            st.clear_batch(keys, window)
        phases = [e["args"] for e in tr.recent(1000)
                  if e["name"] == "state.get.device"]
    finally:
        tracing.set_tracer(old)
    assert [(a["keys"], a["padded"]) for a in phases] == [
        (9, 16), (13, 16), (16, 16), (29, 32), (45, 48)]
    result = tracing.jit_stats()["state.result"]
    assert result["recompiles"] == 1
    assert result["last_shape_sig"].endswith(f"int32[{RESULT_TILE}])")
    assert result["cache_hits"] == 7  # 1 + 1 + 1 + 2 + 3 dispatches


# ---------------------------------------------------------------------
# operator state
# ---------------------------------------------------------------------

def test_operator_list_state_roundtrip():
    b = OperatorStateBackend()
    ls = b.get_list_state("offsets")
    ls.add_all([("p0", 5), ("p1", 7)])
    bs = b.get_broadcast_state("rules")
    bs.put("r1", "drop")
    snap = b.snapshot()

    b2 = OperatorStateBackend()
    b2.restore(snap)
    assert b2.get_list_state("offsets").get() == [("p0", 5), ("p1", 7)]
    assert b2.get_broadcast_state("rules").get("r1") == "drop"


def test_operator_state_redistribute():
    snaps = []
    for subtask in range(2):
        b = OperatorStateBackend()
        b.get_list_state("split").add_all([f"s{subtask}-{i}" for i in range(3)])
        b.get_union_list_state("union").add(f"u{subtask}")
        snaps.append(b.snapshot())

    parts = OperatorStateSnapshot.redistribute(snaps, 3)
    assert len(parts) == 3
    backends = []
    for p in parts:
        b = OperatorStateBackend()
        b.restore(p)
        backends.append(b)
    all_split = sorted(sum((b.get_list_state("split").get() for b in backends), []))
    assert all_split == sorted(f"s{s}-{i}" for s in range(2) for i in range(3))
    for b in backends:
        assert sorted(b.get_union_list_state("union").get()) == ["u0", "u1"]


# ---------------------------------------------------------------------
# regression tests for review findings
# ---------------------------------------------------------------------

def test_restore_drops_inflight_pending_writes():
    """Pre-restore buffered writes must not leak into restored state."""
    tpu = make_backend("tpu")
    st = tpu.get_or_create_keyed_state(
        AggregatingStateDescriptor("p", SumAggregate(np.float32)))
    tpu.set_current_key("a")
    st.add(1.0)
    snap = tpu.snapshot()  # flushes: a=1.0
    tpu.set_current_key("b")
    st.add(100.0)          # in-flight, never snapshotted
    tpu.restore([snap])
    tpu.set_current_key("c")
    st.add(1.0)
    assert st.get() == pytest.approx(1.0)  # not 101.0
    tpu.set_current_key("a")
    assert st.get() == pytest.approx(1.0)


def test_merge_empty_namespaces_leaves_no_state(backend):
    st = backend.get_or_create_keyed_state(
        AggregatingStateDescriptor("me", SumAggregate(np.float32)))
    backend.set_current_key("k")
    st.merge_namespaces(("w", 9), [("w", 1), ("w", 2)])
    st.set_current_namespace(("w", 9))
    assert st.get() is None


def test_nan_inf_keys():
    b = make_backend("heap")
    st = b.get_or_create_keyed_state(ValueStateDescriptor("f"))
    for k in [float("nan"), float("inf"), float("-inf"), 1.5]:
        b.set_current_key(k)
        st.update("ok")
        assert st.value() == "ok"


def test_descriptor_rebind_type_mismatch(backend):
    backend.get_or_create_keyed_state(ValueStateDescriptor("dup"))
    with pytest.raises(ValueError):
        backend.get_or_create_keyed_state(MapStateDescriptor("dup"))


def test_restore_before_bind_then_late_bind():
    """Heap-format snapshot restored before the device descriptor is
    bound: accumulators must surface once the descriptor binds."""
    heap = make_backend("heap")
    hs = heap.get_or_create_keyed_state(
        AggregatingStateDescriptor("lb", SumAggregate(np.float32)))
    heap.set_current_key("x")
    hs.add(5.0)
    snap = heap.snapshot()

    tpu = make_backend("tpu")
    tpu.restore([snap])  # descriptor not bound yet
    st = tpu.get_or_create_keyed_state(
        AggregatingStateDescriptor("lb", SumAggregate(np.float32)))
    tpu.set_current_key("x")
    assert st.get() == pytest.approx(5.0)


# ---------------------------------------------------------------------
# serializer config snapshots + migration compatibility
# (ref: TypeSerializerConfigSnapshot / StateMigrationException)
# ---------------------------------------------------------------------

def test_serializer_compatibility_roundtrip():
    from flink_tpu.core.serialization import LongSerializer

    b1 = make_backend("heap")
    st = b1.get_or_create_keyed_state(
        ValueStateDescriptor("v", serializer=LongSerializer()))
    b1.set_current_key("k")
    st.update(7)
    snap = b1.snapshot()
    assert "serializers" in snap.meta
    assert snap.meta["serializers"]["v"].serializer_name == "LongSerializer"

    # same serializer: restores fine
    b2 = make_backend("heap")
    st2 = b2.get_or_create_keyed_state(
        ValueStateDescriptor("v", serializer=LongSerializer()))
    b2.restore([snap])
    b2.set_current_key("k")
    assert st2.value() == 7


def test_serializer_incompatibility_raises():
    from flink_tpu.core.serialization import (
        DoubleSerializer,
        LongSerializer,
        StateMigrationException,
    )

    b1 = make_backend("heap")
    st = b1.get_or_create_keyed_state(
        ValueStateDescriptor("v", serializer=LongSerializer()))
    b1.set_current_key("k")
    st.update(1)
    snap = b1.snapshot()

    b2 = make_backend("heap")
    b2.get_or_create_keyed_state(
        ValueStateDescriptor("v", serializer=DoubleSerializer()))
    with pytest.raises(StateMigrationException, match="'v'"):
        b2.restore([snap])


# ---------------------------------------------------------------------
# host-RAM spill tier (state > HBM — SURVEY §7 hard part; the
# disk-residency role RocksDB plays in the reference)
# ---------------------------------------------------------------------

def _mk_capped_device_state(cap=64, initial=16, microbatch=4):
    from flink_tpu.ops.device_agg import SumAggregate
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend

    b = TpuKeyedStateBackend(FULL_RANGE, MAX_PAR, initial_capacity=initial,
                             microbatch=microbatch, max_device_slots=cap)
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("agg", SumAggregate()))
    return b, st


def test_spill_tier_evicts_and_promotes():
    b, st = _mk_capped_device_state(cap=64, initial=16, microbatch=4)
    n_keys = 300  # far beyond the 64-slot device budget
    for k in range(n_keys):
        b.set_current_key(f"k{k}")
        st.add(float(k))
    st._flush()
    assert st.evictions > 0, "budget never triggered a spill"
    assert st.capacity <= 128  # soft cap: at most one emergency grow
    assert len(st.host_tier) > 0
    # every value readable — spilled entries promote transparently
    for k in range(n_keys):
        b.set_current_key(f"k{k}")
        assert st.get() == float(k)
    assert st.promotions > 0
    # adding to a previously spilled key keeps aggregating correctly
    b.set_current_key("k0")
    st.add(1000.0)
    assert st.get() == 1000.0


def test_spill_tier_snapshot_includes_host_tier():
    b, st = _mk_capped_device_state(cap=32, initial=8, microbatch=4)
    for k in range(200):
        b.set_current_key(f"k{k}")
        st.add(float(k))
    st._flush()
    assert st.host_tier, "expected spilled entries"
    snap = b.snapshot()
    # restore into an UNCAPPED backend: all 200 entries arrive
    from flink_tpu.ops.device_agg import SumAggregate
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend
    b2 = TpuKeyedStateBackend(FULL_RANGE, MAX_PAR)
    st2 = b2.get_or_create_keyed_state(
        AggregatingStateDescriptor("agg", SumAggregate()))
    b2.restore([snap])
    for k in range(200):
        b2.set_current_key(f"k{k}")
        assert st2.get() == float(k)
    # restore into a CAPPED backend: overflow lands in the host tier
    b3, st3 = _mk_capped_device_state(cap=32, initial=8, microbatch=4)
    b3.restore([snap])
    assert st3.host_tier
    for k in range(0, 200, 17):
        b3.set_current_key(f"k{k}")
        assert st3.get() == float(k)


def test_spill_tier_config_key():
    from flink_tpu.core.config import Configuration

    cfg = Configuration()
    cfg.set("state.backend", "tpu")
    cfg.set("state.backend.tpu.max-device-slots", 4096)
    backend = load_state_backend(cfg, FULL_RANGE, MAX_PAR)
    assert backend.max_device_slots == 4096


# ---------------------------------------------------------------------
# type extraction (TypeInformation / Types / the extractor analogue)
# ---------------------------------------------------------------------

def test_type_extraction_and_serializer_roundtrip():
    from flink_tpu.core.types import Types, extract_type_infos, type_info_of

    cases = [
        (7, "Long"), (1.5, "Double"), (True, "Boolean"),
        ("x", "String"), (b"b", "Bytes"),
        ((1, "a"), "Tuple2<Long, String>"),
        ([1, 2, 3], "List<Long>"),
        ({"k": 2.0}, "Map<String, Double>"),
    ]
    for sample, name in cases:
        info = type_info_of(sample)
        assert info.name == name, (sample, info.name)
        ser = info.create_serializer()
        assert ser.deserialize_from_bytes(
            ser.serialize_to_bytes(sample)) == sample

    # unknown types widen to the pickled generic type
    class Custom:
        pass

    assert type_info_of(Custom()).name == "Pickled"
    assert extract_type_infos([1, 2]).name == "Long"
    assert extract_type_infos([1, "a"]).name == "Pickled"
    # composite constructor
    t = Types.TUPLE(Types.LONG, Types.STRING)
    assert t.arity == 2 and not t.is_basic_type

"""The `tpu` backend's host spill tier: reached through
``env.execute()`` by the documented key, bulk at batch and fire
granularity, and bit for bit the per-key tier it replaced
(``spill_tier_reference.py``)."""

import pickle

import numpy as np
import pytest

from flink_tpu.core.config import Configuration
from flink_tpu.core.keygroups import KeyGroupRange
from flink_tpu.core.state import AggregatingStateDescriptor
from flink_tpu import native
from flink_tpu.ops.device_agg import SumAggregate
from flink_tpu.ops.sketches import (
    CountMinSketchAggregate,
    HyperLogLogAggregate,
)
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu.state.backend import decode_obj_column
from flink_tpu.state import slot_index
from flink_tpu.state.host_tier import HostTier
from flink_tpu.state.stats import STATE_STATS
from flink_tpu.state.tpu_backend import TpuKeyedStateBackend
from flink_tpu.streaming.columnar import VectorizedCollectionSource
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.sources import CollectSink
from flink_tpu.streaming.window_operator import WindowOperator
from flink_tpu.streaming.windowing import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from spill_tier_reference import PerKeySpillBackend

FULL_RANGE = KeyGroupRange(0, 127)
MAX_PAR = 128
BUDGET_KEY = "state.backend.tpu.max-device-slots"
MICROBATCH_KEY = "state.backend.tpu.microbatch-size"


class UserHll(HyperLogLogAggregate):
    """COUNT DISTINCT over field 1 of a (key, user) row."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def emit_row(key, window, vals):
    return [(key, window.start, float(vals[0]))]


# ---------------------------------------------------------------------
# (a) the door: the documented key, set in the environment's
# Configuration, caps the backend a job gets from env.execute()
# ---------------------------------------------------------------------

def _events(seed, batches, rows, key_space, per_window):
    rng = np.random.default_rng(seed)
    n = batches * rows
    keys = rng.integers(0, key_space, n)
    users = rng.integers(0, 1 << 30, n)
    ts = (np.arange(n) // rows // per_window) * 1000 + np.sort(
        rng.integers(0, 1000, (batches, rows)), axis=1).reshape(-1)
    return [((int(k), int(u)), int(t)) for k, u, t in zip(keys, users, ts)]


def _run_hll_job(values, rows, conf=None, backend=None, assigner=None):
    """config #2's job on the state route; returns (sorted rows, the
    WindowOperator that ran)."""
    env = StreamExecutionEnvironment(conf)
    if backend is not None:
        env.set_state_backend(backend)
    sink = CollectSink()
    windowed = (env.add_source(VectorizedCollectionSource(
        values, timestamped=True, chunk=rows))
        .key_by(0)
        .window(assigner or TumblingEventTimeWindows.of(1000)))
    windowed.disable_device_operator()
    windowed.aggregate(UserHll(8), window_function=emit_row).add_sink(sink)
    made = []
    for node in env.get_stream_graph().nodes.values():
        def factory(inner=node.operator_factory):
            op = inner()
            made.append(op)
            return op
        node.operator_factory = factory
    env.execute("spill-door")
    ops = [op for op in made if isinstance(op, WindowOperator)
           and op.columnar_rows]
    assert len(ops) == 1
    return sorted(sink.values), ops[0]


@pytest.mark.parametrize("set_backend", ["configuration", "setter"])
@pytest.mark.parametrize("assigner", ["tumbling", "sliding"])
def test_budget_key_reaches_the_backend_through_execute(
        set_backend, assigner):
    """The capped job evicts, promotes and stays inside its budget,
    and its rows are the heap backend's and the uncapped tpu
    backend's, cell for cell, every (key, window) once."""
    assigner_of = {
        "tumbling": lambda: TumblingEventTimeWindows.of(1000),
        "sliding": lambda: SlidingEventTimeWindows.of(2000, 1000)}[assigner]
    values = _events(seed=11, batches=24, rows=128, key_space=700,
                     per_window=8)
    conf = Configuration().set(BUDGET_KEY, 256).set(MICROBATCH_KEY, 16)
    if set_backend == "configuration":
        conf.set("state.backend", "tpu")
        backend = None
    else:
        backend = "tpu"  # set_state_backend keeps the tuning keys
    before = (STATE_STATS.evicted_rows, STATE_STATS.promoted_rows,
              STATE_STATS.spill_fired_rows, STATE_STATS.budget_overruns)
    capped, op = _run_hll_job(values, 128, conf, backend, assigner_of())
    st = op.window_state
    assert type(op.keyed_backend) is TpuKeyedStateBackend
    assert op.keyed_backend.max_device_slots == st.max_device_slots == 256
    assert st.capacity <= 256 and st.budget_overruns == 0
    assert st.evictions > 0 and st.promotions > 0
    assert (STATE_STATS.evicted_rows - before[0],
            STATE_STATS.promoted_rows - before[1]) == (st.evictions,
                                                       st.promotions)
    assert STATE_STATS.spill_fired_rows > before[2]
    assert STATE_STATS.budget_overruns == before[3]
    assert not st.host_tier and not st.slot_index  # every window cleared
    assert len({(k, w) for k, w, _ in capped}) == len(capped)
    uncapped, op2 = _run_hll_job(values, 128, backend="tpu",
                                 assigner=assigner_of())
    assert op2.window_state.max_device_slots is None
    assert op2.window_state.evictions == 0
    heap, _ = _run_hll_job(values, 128, backend="heap",
                           assigner=assigner_of())
    assert capped == uncapped
    assert capped == heap


def test_a_pinned_node_backend_keeps_the_tuning_keys():
    from flink_tpu.state.loader import load_state_backend
    conf = Configuration().set("state.backend", "heap").set(BUDGET_KEY, 64)
    assert load_state_backend(conf, FULL_RANGE, MAX_PAR).name == "heap"
    pinned = load_state_backend(conf, FULL_RANGE, MAX_PAR, name="tpu")
    assert pinned.name == "tpu" and pinned.max_device_slots == 64
    assert load_state_backend("tpu", FULL_RANGE,
                              MAX_PAR).max_device_slots is None
    assert load_state_backend(None, FULL_RANGE, MAX_PAR).name == "heap"


def test_the_budget_holds_from_the_first_slot():
    """A budget under the default initial capacity caps that too."""
    b = TpuKeyedStateBackend(FULL_RANGE, MAX_PAR, max_device_slots=100)
    st = b.get_or_create_keyed_state(
        AggregatingStateDescriptor("s", SumAggregate(np.float32)))
    assert st.capacity == 100


# ---------------------------------------------------------------------
# (b) bulk against per-key, bit for bit
# ---------------------------------------------------------------------

AGGS = {"hll": lambda: HyperLogLogAggregate(6),
        "countmin": lambda: CountMinSketchAggregate(2, 16),
        "sum": lambda: SumAggregate(np.float32)}


def _state(backend_cls, agg, **kw):
    kw = {"initial_capacity": 8, "microbatch": 4, "max_device_slots": 32,
          **kw}
    b = backend_cls(FULL_RANGE, MAX_PAR, **kw)
    st = b.get_or_create_keyed_state(AggregatingStateDescriptor("s", agg))
    return b, st


def _snapshot_cells(backend):
    """{(key, namespace): {component: bytes}} of a backend's snapshot."""
    cells = {}
    for _, blob in backend.snapshot().blobs():
        chunk = pickle.loads(blob)
        for block in chunk["cols"].get("s", []):
            comps = block["comps"]
            n = len(next(iter(comps.values())))
            keys = decode_obj_column(block["keys"], n)
            nss = decode_obj_column(block["ns"][1], n)
            for i, entry in enumerate(zip(keys, nss)):
                assert entry not in cells
                cells[entry] = {c: np.asarray(a[i]).tobytes()
                                for c, a in comps.items()}
    return cells


def _drive(backend_cls, agg_name, seed):
    """Sliding windows (size 2 s, slide 1 s) with 1 s of lateness over
    a key space four times the budget: every event goes to two
    namespaces, a window is read at its end, again after its late
    events, then cleared; a snapshot is taken with rows spilled and
    the run goes on in a backend restored from it.  Returns what a
    user can observe."""
    rng = np.random.default_rng(seed)
    b, st = _state(backend_cls, AGGS[agg_name]())
    seen = []
    live = {}  # namespace -> keys
    split_tiers = 0

    def window(start):
        return (start, start + 2000)

    def add(keys, namespaces):
        vals = rng.integers(1, 1000, len(keys)).astype(np.float32)
        b.add_batch(st, keys, None, vals, namespaces=namespaces)
        for k, ns in zip(keys, namespaces):
            live.setdefault(ns, set()).add(k)

    def read(ns):
        keys = sorted(live.get(ns, ())) + [10_000]  # one never seen
        res, found, path = b.get_batch(st, keys, ns)
        assert path == "batch"
        # (a row that was not found holds whatever slot 0 does)
        seen.append((ns, keys, np.asarray(res)[found].tobytes(),
                     found.tolist()))

    for step in range(8):
        t = step * 1000
        # keys with one namespace in HBM and another in host RAM
        resident = {k for k, _ in st.slot_index}
        split_tiers += len(resident & {k for k, _ in st.host_tier})
        for _ in range(3):
            keys = [int(k) for k in rng.integers(0, 128, 24)]
            add(keys + keys, [window(t - 1000)] * 24 + [window(t)] * 24)
        read(window(t - 1000))  # its end: [t - 1000, t + 1000) closes
        if step >= 1:
            late = [int(k) for k in rng.integers(0, 128, 10)]
            add(late, [window(t - 2000)] * 10)  # inside the lateness
            read(window(t - 2000))
            gone = sorted(live.pop(window(t - 2000), ()))
            assert b.clear_batch(st, gone, window(t - 2000)) == "batch"
        if step == 4:
            assert len(st.host_tier) > 0
            cells = _snapshot_cells(b)
            seen.append(("snapshot", sorted(cells.items())))
            snap = b.snapshot()
            b, st = _state(backend_cls, AGGS[agg_name]())
            b.restore([snap])
            assert len(st.host_tier) > 0  # the overflow restored there
    seen.append(("end", sorted(_snapshot_cells(b).items())))
    seen.append(("active", sorted(st.active_entries())))
    return seen, st, split_tiers


@pytest.mark.parametrize("seed", [3, 17])
@pytest.mark.parametrize("agg_name", sorted(AGGS))
def test_bulk_tier_is_the_per_key_tier_bit_for_bit(agg_name, seed):
    bulk, st, split_tiers = _drive(TpuKeyedStateBackend, agg_name, seed)
    per_key, ref, _ = _drive(PerKeySpillBackend, agg_name, seed)
    assert type(ref.host_tier) is dict and ref.evictions > 0 \
        and ref.promotions > 0
    assert st.evictions > 0 and st.promotions > 0 and split_tiers > 0
    assert st.capacity <= 32 and st.budget_overruns == 0
    assert len(bulk) == len(per_key)
    for got, want in zip(bulk, per_key):
        assert got == want, got[0]


def _tumbling_spill_drive(rows=48, key_space=200, windows=4, seed=5):
    """Integer keys under ONE window a batch over a key space six
    times the budget, each window read and cleared at its end, a
    snapshot taken (its bytes kept) and restored from mid-way: what
    the index, both tiers and a user see after every call."""
    rng = np.random.default_rng(seed)
    b, st = _state(TpuKeyedStateBackend, SumAggregate(np.float32))
    STATE_STATS.reset()
    seen = []

    def observe(what):
        keys, namespaces, slots = st.slot_index.columns()
        spilled = st.host_tier.index.columns()
        seen.append((what, keys, namespaces, slots.tolist(), spilled[0],
                     spilled[1], spilled[2].tolist(), list(st._free),
                     st._access_stamp.tolist(), st.evictions, st.promotions,
                     len(st.host_tier._blocks)))

    for window in range(windows):
        ns = (window * 1000, window * 1000 + 1000)
        live = {}
        for _ in range(5):
            keys = rng.integers(0, key_space, rows)
            # cold keys once, then a hot set hammered, then fresh ones
            keys[:rows // 2] = keys[:rows // 2] % 12
            b.add_batch(st, keys.tolist(), ns,
                        rng.integers(1, 100, rows).astype(np.float32))
            live.update(dict.fromkeys(keys.tolist()))
            observe("add")
        res, found, _ = b.get_batch(st, list(live) + [10_000], ns)
        seen.append(("fire", np.asarray(res)[found].tobytes(), found.tolist()))
        if window == 1:
            snap = b.snapshot()
            seen.append(("snapshot", sorted(snap.blobs())))
            b, st = _state(TpuKeyedStateBackend, SumAggregate(np.float32))
            b.restore([snap])
            observe("restored")
        b.clear_batch(st, list(live), ns)
        observe("clear")
    return seen, st, {name: getattr(STATE_STATS, name) for name in (
        "bulk_probe_rows", "int_table_rows", "int_table_demotions",
        "evicted_rows", "promoted_rows")}


@pytest.mark.skipif(not native.available(), reason="no native host runtime")
def test_a_spilling_integer_keyed_job_is_the_same_on_both_forms(monkeypatch):
    """Evictions move a window's keys from the device's integer table
    into the host tier's, promotions back: the slots, the host rows,
    the victims, the fires and the snapshot bytes are the dict form's,
    call for call."""
    seen, st, counts = _tumbling_spill_drive()
    assert counts["evicted_rows"] > 0 and counts["promoted_rows"] > 0
    assert counts["int_table_demotions"] == 0
    # (the window restored mid-way came back through per-row
    # namespaces: a dict, so not every row met an integer table)
    assert 0 < counts["int_table_rows"] < counts["bulk_probe_rows"]
    monkeypatch.setattr(slot_index.native, "available", lambda: False)
    want_seen, want_st, want_counts = _tumbling_spill_drive()
    assert want_counts["int_table_rows"] == 0
    assert {**want_counts, "int_table_rows": counts["int_table_rows"]} \
        == counts
    assert len(seen) == len(want_seen)
    for step, (got, want) in enumerate(zip(seen, want_seen)):
        assert got == want, (step, got[0])
    assert st.capacity == want_st.capacity <= 32
    assert not st.slot_index and not st.host_tier


@pytest.mark.parametrize("n", [1, 5, 16, 40])
def test_spilled_fire_goes_up_in_tiles_of_the_result_shape(
        n, monkeypatch):
    """Spilled rows are finalised in tiles of the width the device
    rows are gathered at, whatever their number, and equal scalar
    reads."""
    from flink_tpu.state import tpu_backend
    b, st = _state(TpuKeyedStateBackend, HyperLogLogAggregate(6),
                   initial_capacity=64, max_device_slots=64)
    monkeypatch.setattr(tpu_backend, "RESULT_SCRATCH_BYTES", 16 * 64)
    assert st._result_tile() == 16
    rng = np.random.default_rng(n)
    keys = list(range(48))
    st.add_batch(keys * 3, "w", rng.integers(1, 9999, 144))
    st._clock += 1000
    st._evict_cold(n)
    assert len(st.host_tier) == n
    tr = get_tracer()
    tr.reset()
    before = STATE_STATS.spill_fired_rows
    res, found = st.get_batch(keys, "w")
    assert tr.stats()["state.fire.spill"]["count"] == 1
    assert STATE_STATS.spill_fired_rows - before == n
    assert found.all()
    st.set_current_namespace("w")
    for k in keys:
        b.set_current_key(k)
        assert np.float32(st.get()).tobytes() == res[k].tobytes()


def test_everything_hot_overruns_the_budget_and_says_so():
    b, st = _state(TpuKeyedStateBackend, SumAggregate(np.float32),
                   initial_capacity=8, microbatch=64, max_device_slots=8)
    before = STATE_STATS.budget_overruns
    st.add_batch(list(range(20)), "w", np.ones(20, np.float32))
    assert st.capacity > 8 and st.evictions == 0
    assert st.budget_overruns >= 1
    assert STATE_STATS.budget_overruns - before == st.budget_overruns


def test_eviction_gathers_at_one_shape(monkeypatch):
    """One `state.evict` program per capacity, however many rows were
    cold; one `state.promote` program, however many rows a batch
    touched."""
    from flink_tpu.runtime import tracing
    tracing.reset_jit_stats()
    b, st = _state(TpuKeyedStateBackend, HyperLogLogAggregate(6),
                   initial_capacity=32, microbatch=2, max_device_slots=32)
    rng = np.random.default_rng(0)
    for lo in range(0, 200, 10):
        keys = list(range(lo, lo + 10)) + [int(k) for k in
                                           rng.integers(0, lo + 1, 3)]
        st.add_batch(keys, "w", rng.integers(1, 999, len(keys)))
    assert st.evictions > 30 and st.promotions > 3
    stats = tracing.jit_stats()
    assert stats["state.evict"]["recompiles"] == 1
    assert stats["state.promote"]["recompiles"] == 1
    assert stats["state.evict"]["cache_hits"] > 3
    assert stats["state.upload"]["recompiles"] == 0  # no per-key dispatch


# ---------------------------------------------------------------------
# the host tier alone
# ---------------------------------------------------------------------

def _block(lo, hi):
    return (list(range(lo, hi)), ["w"] * (hi - lo),
            {"a": np.arange(lo, hi, dtype=np.float32),
             "b": (np.arange(lo, hi) % 256).astype(np.uint8).reshape(-1, 1)
             * np.ones((1, 4), np.uint8)})


def _evicted(tier, keys, namespaces, comps):
    """File a block as an eviction out of an integer table does: the
    rows' keys enter the index as one column."""
    base = tier.file(comps)
    tier.index.enter(np.array(keys), namespaces[0],
                     base + np.arange(len(keys)))


@pytest.mark.parametrize("form", [
    "dict", pytest.param("integer", marks=pytest.mark.skipif(
        not native.available(), reason="no native host runtime"))])
def test_host_tier_files_slices_releases_and_compacts(form):
    tier = HostTier()
    put = tier.put if form == "dict" else \
        lambda *block: _evicted(tier, *block)
    put(*_block(0, 3000))
    put(*_block(3000, 5000))
    assert isinstance(tier.index.tables["w"], native.NativeIntTable) \
        == (form == "integer")
    assert len(tier) == 5000 and (4999, "w") in tier
    assert tier.get(4000, "w")["a"] == 4000.0
    assert tier.get(9, "x") is None and tier.get(9000, "w") is None
    ids = np.array([tier.index.get(k, "w") for k in (4500, 7, 3000, 2999)])
    out = {"a": np.empty(4, np.float32), "b": np.empty((4, 4), np.uint8)}
    tier.gather(ids, out)
    assert out["a"].tolist() == [4500.0, 7.0, 3000.0, 2999.0]
    assert out["b"][:, 0].tolist() == [4500 % 256, 7, 3000 % 256,
                                       2999 % 256]
    # a block whose rows are all released is dropped whole
    tier.release([tier.index.pop(k, "w") for k in range(3000, 5000)])
    assert len(tier._blocks) == 1 and tier._rows == 3000
    # released rows outnumber live ones: the live rows move together
    tier.release(tier.index.lookup(range(0, 2000), "w", take=True))
    assert tier._rows == len(tier) == 1000
    assert tier.get(2500, "w")["a"] == 2500.0
    keys, namespaces, comps = tier.columns()
    assert keys == list(range(2000, 3000)) and namespaces == ["w"] * 1000
    assert comps["a"].tolist() == list(map(float, range(2000, 3000)))
    # rows of a second namespace share the blocks and nothing else
    tier.put([2000, 7], ["w", "x"], {"a": np.array([-1.0, -2.0], np.float32),
                                     "b": np.zeros((2, 4), np.uint8)})
    assert tier.get(2000, "w")["a"] == -1.0 and tier.get(7, "x")["a"] == -2.0
    assert len(tier) == 1001 and sorted(tier.index.tables) == ["w", "x"]
    tier.discard(7, "x")
    assert list(tier.index.tables) == ["w"]  # no empty table is kept
    tier.discard(2000, "w")
    tier.discard(2000, "w")
    assert len(tier) == 999 and (2000, "w") not in tier
    tier.clear()
    assert not tier and tier.columns() == ([], [], {})


# ---------------------------------------------------------------------
# (c) the tier's phases follow batches and fires, never rows
# ---------------------------------------------------------------------

SPILL_PHASES = ("state.evict", "state.promote", "state.fire.spill",
                "state.clear.spill")


def _spilling_job(rows):
    """One window of 8 batches: six of `rows` keys nobody saw, then the
    first two again, under a budget of 4 * rows slots."""
    keys = np.concatenate([np.arange(6 * rows), np.arange(2 * rows)])
    rng = np.random.default_rng(rows)
    users = rng.integers(0, 1 << 30, len(keys))
    ts = np.sort(rng.integers(0, 1000, (8, rows)), axis=1).reshape(-1)
    values = [((int(k), int(u)), int(t))
              for k, u, t in zip(keys, users, ts)]
    conf = Configuration().set("state.backend", "tpu")
    conf.set(BUDGET_KEY, 4 * rows).set(MICROBATCH_KEY, rows // 2)
    out, op = _run_hll_job(values, rows, conf)
    assert len(out) == 6 * rows
    return op.window_state


def test_spill_phase_counts_follow_batches_and_fires_never_rows():
    tr = get_tracer()
    assert not tr.enabled
    counts = {}
    for rows in (64, 256):
        tr.reset()
        st = _spilling_job(rows)
        assert st.capacity == 4 * rows
        assert st.evictions == 4 * rows and st.promotions == 2 * rows
        stats = tr.stats()
        counts[rows] = {name: s["count"] for name, s in stats.items()}
        assert set(SPILL_PHASES) <= set(counts[rows])
    assert counts[64] == counts[256]
    # 8 batches in chunks of half a batch, one fire
    assert counts[64]["state.add.slots"] == 16
    assert counts[64]["state.evict"] == 4
    assert counts[64]["state.promote"] == 4
    assert counts[64]["state.fire.spill"] == 1
    assert counts[64]["state.clear.spill"] == 1


def test_a_finished_state_leaves_the_device():
    """No jitted entry point closes over the state object: jax keeps a
    jitted function long after the job, and the registers with it."""
    import gc
    import weakref
    b, st = _state(TpuKeyedStateBackend, HyperLogLogAggregate(6))
    st.add_batch(list(range(100)), "w", np.arange(100))
    st.get_batch(list(range(100)), "w")
    assert st.evictions > 0
    gone = weakref.ref(st)
    b.dispose()
    del b, st
    gc.collect()
    assert gone() is None

"""The `tpu` backend's slot index, keyed by namespace first and
probed in bulk, against the per-key index it replaced
(``spill_tier_reference.py``: one flat ``(key, namespace) → slot``
dict, one `_slot_for` call per row): the same programme of batch and
scalar calls gives the same results, `found` masks, snapshot cells and
active entries on both, and the bulk state's invariants hold after
every step, whichever of its two forms a namespace's table has (a
`dict`, or the native integer table a window of integer keys gets).
Then the integer table alone against a dict-only index, and the count
of work: which door resolved how many rows, how many of them on an
integer table."""

import os
import pickle

import numpy as np
import pytest

from flink_tpu.core.config import Configuration
from flink_tpu.core.keygroups import KeyGroupRange
from flink_tpu.core.state import AggregatingStateDescriptor
from flink_tpu.ops.device_agg import SumAggregate
from flink_tpu.ops.sketches import HyperLogLogAggregate
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu import native
from flink_tpu.core.key_columns import KeyTable
from flink_tpu.state import slot_index
from flink_tpu.state.backend import KeyedStateSnapshot, decode_obj_column
from flink_tpu.state.slot_index import NamespaceIndex, group_rows
from flink_tpu.state.stats import STATE_STATS
from flink_tpu.state.tpu_backend import TpuKeyedStateBackend
from flink_tpu.streaming.columnar import RecordBatch
from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
from flink_tpu.streaming.window_operator import WindowOperator
from flink_tpu.streaming.windowing import (
    DynamicEventTimeSessionWindows,
    EventTimeSessionWindows,
    TumblingEventTimeWindows,
)
from spill_tier_reference import PerKeySpillBackend

FULL_RANGE = KeyGroupRange(0, 127)
MAX_PAR = 128
WINDOWS = [(0, 1000), (1000, 2000), (2000, 3000)]
MERGED = (0, 3000)

AGGS = {"sum": lambda: SumAggregate(np.float32),
        "hll": lambda: HyperLogLogAggregate(6)}

#: how a case draws its keys: (space, index → key)
KEYS = {
    "int": (160, int),
    "str": (160, lambda i: f"k{i}"),
    "tuple": (160, lambda i: (i % 13, f"t{i // 13}")),
    # 1, 1.0 and True are ONE key (dict equality), as are 0, 0.0, False
    "equal": (480, lambda i: (i // 3, float(i // 3),
                              bool(i // 3) if i // 3 < 2 else i // 3)[i % 3]),
}


def _state(backend_cls, agg, capped):
    kw = {"initial_capacity": 8, "microbatch": 4}
    if capped:
        kw["max_device_slots"] = 32
    b = backend_cls(FULL_RANGE, MAX_PAR, **kw)
    st = b.get_or_create_keyed_state(AggregatingStateDescriptor("s", agg))
    return b, st


def _snapshot_cells(snapshot):
    """{(key, namespace): {component: bytes}} of a snapshot."""
    cells = {}
    for _, blob in snapshot.blobs():
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


def check_invariants(st):
    """One index, and everything else agrees with it."""
    index, tier = st.slot_index, st.host_tier
    keys, namespaces, slots = index.columns()
    live = slots.tolist()
    # every live entry has exactly one slot, every slot one entry
    assert len(set(zip(keys, namespaces))) == len(live) == len(set(live))
    # live slots and the free list partition range(capacity)
    assert len(set(st._free)) == len(st._free)
    assert set(live).isdisjoint(st._free)
    assert set(live) | set(st._free) == set(range(st.capacity))
    # slot → entry agrees with the index
    for key, namespace, slot in zip(keys, namespaces, live):
        # (an integer table gives a key back as the int it equals)
        held = st.slot_key[slot]
        assert held is key or (type(key) is int and held == key)
        assert st.slot_ns[slot] == namespace
    assert np.flatnonzero(st._slot_live).tolist() == sorted(live)
    for slot in st._free:
        assert st.slot_key[slot] is None and st.slot_ns[slot] is None
        assert not st._slot_flushed[slot]
    # `len` of the index equals the live slots; no empty table is left
    assert len(index) == len(live) == st.capacity - len(st._free)
    assert all(index.tables.values()) and all(tier.index.tables.values())
    assert bool(index) == bool(live)
    # an entry lives in one tier; a spilled one in one live row
    rows = tier.index.columns()
    assert not set(zip(keys, namespaces)) & set(zip(rows[0], rows[1]))
    assert len(set(rows[2].tolist())) == len(rows[0]) == len(tier)
    alive = np.concatenate([b.base + np.flatnonzero(b.alive)
                            for b in tier._blocks] + [np.zeros(0, np.int64)])
    assert sorted(rows[2].tolist()) == alive.tolist()
    assert sum(b.live for b in tier._blocks) == len(tier)
    assert len(st._access_stamp) == len(st._slot_flushed) == st.capacity


class _Pair:
    """The bulk state and the per-key reference, driven in lockstep."""

    def __init__(self, agg_name, capped):
        self.agg_name, self.capped = agg_name, capped
        self.bulk = _state(TpuKeyedStateBackend, AGGS[agg_name](), capped)
        self.ref = _state(PerKeySpillBackend, AGGS[agg_name](), capped)
        self.steps = 0

    def both(self, call):
        """Run `call(backend, state)` on both; equal answers, sound
        invariants."""
        got, want = call(*self.bulk), call(*self.ref)
        self.steps += 1
        assert got == want, (self.steps, got, want)
        check_invariants(self.bulk[1])
        return got

    # ---- the doors ---------------------------------------------------
    def add_batch(self, keys, namespace, namespaces, values):
        self.both(lambda b, st: b.add_batch(st, keys, namespace, values,
                                            namespaces=namespaces))

    def get_batch(self, keys, namespace, namespaces):
        def call(b, st):
            res, found, path = b.get_batch(st, keys, namespace,
                                           namespaces=namespaces)
            # (a row that was not found holds whatever slot 0 does)
            return np.asarray(res)[found].tobytes(), found.tolist(), path
        return self.both(call)

    def clear_batch(self, keys, namespace, namespaces):
        self.both(lambda b, st: b.clear_batch(st, keys, namespace,
                                              namespaces=namespaces))

    def _scalar(self, key, namespace, then):
        def call(b, st):
            b.set_current_key(key)
            st.set_current_namespace(namespace)
            out = then(st)
            return None if out is None else np.asarray(out).tobytes()
        return self.both(call)

    def add(self, key, namespace, value):
        self._scalar(key, namespace, lambda st: st.add(value))

    def get(self, key, namespace):
        return self._scalar(key, namespace, lambda st: st.get())

    def clear(self, key, namespace):
        self._scalar(key, namespace, lambda st: st.clear())

    def merge(self, key, target, sources):
        self._scalar(key, target,
                     lambda st: st.merge_namespaces(target, sources))

    def merge_batch(self, merges):
        self.both(lambda b, st: st.merge_namespaces_batch(merges))

    def state(self):
        """What a user can observe of the whole state (compared as
        dicts and sets: which of 1, 1.0 and True a tier keeps for the
        key they are depends on when it was last promoted)."""
        def call(b, st):
            active = list(st.active_entries())
            assert len(set(active)) == len(active)
            return _snapshot_cells(b.snapshot()), set(active)
        return self.both(call)

    def swap_through_snapshots(self):
        """The bulk state goes on from the reference's snapshot and
        the reference from the bulk state's."""
        snaps = self.bulk[0].snapshot(), self.ref[0].snapshot()
        assert _snapshot_cells(snaps[0]) == _snapshot_cells(snaps[1])
        self.bulk = _state(TpuKeyedStateBackend, AGGS[self.agg_name](),
                           self.capped)
        self.ref = _state(PerKeySpillBackend, AGGS[self.agg_name](),
                          self.capped)
        self.bulk[0].restore([snaps[1]])
        self.ref[0].restore([snaps[0]])
        check_invariants(self.bulk[1])


def _namespaces(shape, rng, n, round_):
    """(namespace, namespaces=) of a batch of n rows."""
    if shape == "one":
        return WINDOWS[round_ % 2], None
    if shape == "runs":
        a, b = WINDOWS[round_ % 3], WINDOWS[(round_ + 1) % 3]
        cut = int(rng.integers(1, n))
        return None, [a] * cut + [b] * (n - cut)
    return None, [WINDOWS[i] for i in rng.integers(0, 3, n)]  # per row


@pytest.fixture
def dict_tables_only(monkeypatch):
    """No native host runtime as far as the slot index can tell: every
    table is a dict."""
    monkeypatch.setattr(native, "available", lambda: False)


#: the kinds of keys an integer table can take run on both forms
KIND_FORMS = [(kind, form) for kind in sorted(KEYS)
              for form in (("native", "dict") if kind in ("equal", "int")
                           else ("native",))]


@pytest.mark.parametrize("capped", [False, True],
                         ids=["uncapped", "capped"])
@pytest.mark.parametrize("shape", ["one", "runs", "per_row"])
@pytest.mark.parametrize("kind,form", KIND_FORMS,
                         ids=[f"{kind}-{form}" for kind, form in KIND_FORMS])
@pytest.mark.parametrize("agg_name", sorted(AGGS))
def test_bulk_index_is_the_per_key_index(agg_name, kind, form, shape, capped,
                                         request):
    """Batches longer than the microbatch with keys twice in them,
    scalar calls between them, merges, partial clears, and a swap
    through each other's snapshots mid-way.  Capped: a key space five
    times the budget, so evictions and promotions happen all along;
    uncapped: the table doubles three times or more.  `form`: with the
    native host runtime, a window that integer keys reach under one
    namespace holds them in an integer table; without it, in a dict."""
    if form == "dict":
        request.getfixturevalue("dict_tables_only")
    STATE_STATS.reset()
    demoted = KeyTable.demotions
    space, make = KEYS[kind]
    rng = np.random.default_rng(
        [sorted(AGGS).index(agg_name), sorted(KEYS).index(kind),
         ("one", "runs", "per_row").index(shape), capped])
    pair = _Pair(agg_name, capped)
    live = {}  # namespace -> {key: None}, in dict equality

    def draw(n):
        return [make(int(i)) for i in rng.integers(0, space, n)]

    def values(n):
        return rng.integers(1, 1000, n).astype(np.float32)

    for round_ in range(6):
        n = int(rng.integers(30, 48))
        keys = draw(n)
        keys[n // 2] = keys[0]  # a key twice in one batch, always
        namespace, namespaces = _namespaces(shape, rng, n, round_)
        pair.add_batch(keys, namespace, namespaces, values(n))
        for k, ns in zip(keys, namespaces or [namespace] * n):
            live.setdefault(ns, {})[k] = None
        # the scalar door between batches: add, then read in bulk
        for k in draw(3):
            ns = WINDOWS[int(rng.integers(0, 3))]
            pair.add(k, ns, float(rng.integers(1, 1000)))
            live.setdefault(ns, {})[k] = None
        asked = draw(5) + list(live.get(WINDOWS[round_ % 3], ()))[:20]
        namespace, namespaces = _namespaces(shape, rng, len(asked), round_)
        _, found, path = pair.get_batch(asked, namespace, namespaces)
        assert path == "batch"
        assert found == [k in live.get(ns, ()) for k, ns in zip(
            asked, namespaces or [namespace] * len(asked))]
        # ... and written in bulk, read and cleared one by one
        for k in draw(2) + asked[:2]:
            pair.get(k, WINDOWS[round_ % 3])
        if round_ == 2:
            # sessions: fold what two windows hold for a key into one
            both = [k for k in live.get(WINDOWS[0], ())
                    if k in live.get(WINDOWS[1], ())][:4]
            assert both
            pair.merge(both[0], MERGED, [WINDOWS[0], WINDOWS[1]])
            pair.merge_batch([(k, MERGED, [WINDOWS[0], WINDOWS[1], MERGED])
                              for k in both[1:]]
                             + [(make(space + 1), MERGED, [WINDOWS[2]])])
            for k in both:
                del live[WINDOWS[0]][k], live[WINDOWS[1]][k]
                live.setdefault(MERGED, {})[k] = None
                assert pair.get(k, MERGED) is not None
                assert pair.get(k, WINDOWS[0]) is None
        if round_ == 3:
            pair.state()
            pair.swap_through_snapshots()
        if round_ >= 1:
            # a partial clear: half a window's keys, one of them twice,
            # one nobody added, the rest of the window one by one later
            old = WINDOWS[(round_ + 1) % 3]
            gone = list(live.get(old, ()))[::2]
            namespace, namespaces = (old, None) if shape == "one" else \
                (None, [old] * (len(gone) + 2))
            pair.clear_batch(gone + gone[:1] + [make(space + 2)],
                             namespace, namespaces)
            for k in gone:
                del live[old][k]
            for k in list(live.get(old, ()))[:2]:
                pair.clear(k, old)
                del live[old][k]
    cells, active = pair.state()
    assert len(active) == sum(map(len, live.values())) == len(cells)
    st, ref = pair.bulk[1], pair.ref[1]
    if capped:
        assert st.evictions > 0 and st.promotions > 0
        assert ref.evictions > 0 and ref.promotions > 0
        assert st.capacity <= 32 and st.budget_overruns == 0
    else:
        assert st.capacity == ref.capacity >= 64 and st.evictions == 0
    # which form the batch doors met
    if form == "native" and shape == "one" and kind == "int":
        assert 0 < STATE_STATS.int_table_rows <= STATE_STATS.bulk_probe_rows
    elif kind != "equal" or form == "dict":
        # (a batch of 1, 1.0 and True is a column of floats: a dict,
        # unless it happens to hold ints alone)
        assert STATE_STATS.int_table_rows == 0
    assert KeyTable.demotions == demoted or kind == "equal"
    # clear everything, a window at a time: nothing is left behind
    for ns, keys in live.items():
        pair.clear_batch(list(keys), ns, None)
    assert not st.slot_index and not st.host_tier
    assert not st.slot_index.tables and not st.host_tier.index.tables
    assert sorted(st._free) == list(range(st.capacity))


def test_a_table_about_to_fill_counts_its_new_keys_first():
    """Making room for a whole chunk ahead of the probe must not double
    the capacity where the keys that are new would not: 8 slots, 6
    taken, a batch of 8 rows of which 2 are new."""
    b, st = _state(TpuKeyedStateBackend, SumAggregate(np.float32), False)
    ref_b, ref = _state(PerKeySpillBackend, SumAggregate(np.float32), False)
    for backend, state in ((b, st), (ref_b, ref)):
        backend.add_batch(state, list(range(6)), "w", np.ones(6, np.float32))
        backend.add_batch(state, [0, 1, 6, 2, 6, 7, 3, 7], "w",
                          np.ones(8, np.float32))
        assert state.capacity == 8 and not state._free
        check_invariants(st)
        backend.add_batch(state, [0, 8, 8, 1], "w", np.ones(4, np.float32))
        assert state.capacity == 16
    check_invariants(st)
    res = b.get_batch(st, list(range(10)), "w")
    want = ref_b.get_batch(ref, list(range(10)), "w")
    assert res[1].tolist() == want[1].tolist() == [True] * 9 + [False]
    assert res[0][:9].tolist() == want[0][:9].tolist() \
        == [3, 3, 2, 2, 1, 1, 2, 2, 2]


def test_rows_are_stamped_as_the_per_key_loop_stamped_them():
    """The LRU clock advances by a batch's length and every row reads
    the stamp its place in the batch gave it; of a key that comes
    twice, the last."""
    b, st = _state(TpuKeyedStateBackend, SumAggregate(np.float32), False)
    ref_b, ref = _state(PerKeySpillBackend, SumAggregate(np.float32), False)
    for backend, state in ((b, st), (ref_b, ref)):
        backend.add_batch(state, ["a", "b", "a", "c"], "w", np.ones(4))
        backend.get_batch(state, ["c", "x", "a"], "w")
        backend.add_batch(state, ["d", "b"], None, np.ones(2),
                          namespaces=["v", "w"])
    assert st._clock == ref._clock == 8

    def stamps(state, index_of):
        return {e: int(state._access_stamp[index_of(e)])
                for e in state.active_entries()}
    assert stamps(st, lambda e: st.slot_index.get(*e)) \
        == stamps(ref, ref.slot_index.get) \
        == {("a", "w"): 6, ("b", "w"): 8, ("c", "w"): 5, ("d", "v"): 7}


def test_a_snapshot_the_parent_commit_wrote_restores():
    """Bytes on disk as commit 1a80527 (the flat index) wrote them:
    36 entries of int, str, tuple and float keys in two namespaces, 7
    of them spilled at the time."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_snapshot_parent_of_pr32.pkl")
    with open(path, "rb") as f:
        saved = pickle.load(f)
    snap = KeyedStateSnapshot(saved["chunks"], saved["meta"])
    for capped in (False, True):
        b, st = _state(TpuKeyedStateBackend, SumAggregate(np.float32), capped)
        b.restore([snap])
        check_invariants(st)
        assert bool(st.host_tier) == capped
        assert set(st.active_entries()) == set(saved["expected"])
        for (key, namespace), want in saved["expected"].items():
            b.set_current_key(key)
            st.set_current_namespace(namespace)
            assert st.get() == want
        # and what it writes back holds the same cells
        again = _snapshot_cells(b.snapshot())
        assert again == _snapshot_cells(snap)


def test_group_rows_and_the_index_alone():
    groups = group_rows(["b", "a", "b", (1, 2), "a", (1, 2), 1, True, 1.0])
    assert [(ns, rows.tolist()) for ns, rows in groups] == [
        ("b", [0, 2]), ("a", [1, 4]), ((1, 2), [3, 5]), (1, [6, 7, 8])]
    assert [rows.tolist() for _, rows in group_rows(["w"] * 3)] == [[0, 1, 2]]
    index = NamespaceIndex()
    assert not index and len(index) == 0 and ("k", "w") not in index
    index.put("k", "w", 3)
    index.enter([1, 2, 3], "v", np.array([7, 8, 9]))
    assert len(index) == 4 and index and ("k", "w") in index
    assert list(index) == [("k", "w"), (1, "v"), (2, "v"), (3, "v")]
    assert index.get(2.0, "v") == 8 and index.get(2, "w") is None
    assert index.lookup([3, 4, True], "v").tolist() == [9, -1, 7]
    assert index.lookup([3], "nowhere").tolist() == [-1]
    assert index.lookup([1, 1, 2], "v", take=True).tolist() == [7, -1, 8]
    assert index.pop("k", "w") == 3 and index.pop("k", "w") is None
    assert list(index.tables) == ["v"]  # "w" went with its last key
    keys, namespaces, ids = index.columns()
    assert (keys, namespaces, ids.tolist()) == ([3], ["v"], [9])
    assert index.lookup([3], "v", take=True).tolist() == [9]
    assert not index and not index.tables


# ---------------------------------------------------------------------
# the count of work: which door resolved how many rows
# ---------------------------------------------------------------------

class UserHll(HyperLogLogAggregate):
    """COUNT DISTINCT over field 1 of a (key, user) row."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def emit_row(key, window, vals):
    return [(key, window.start, float(vals[0]))]


def _window_job(assigner, backend):
    op = WindowOperator(
        assigner, AggregatingStateDescriptor("window-contents", UserHll(6)),
        window_function=emit_row)
    h = OneInputStreamOperatorTestHarness(
        op, key_selector=lambda v: v[0], state_backend=backend)
    h.open()
    return op, h


#: the benchmark's rehearsal sizes (benchmark/configs/<name>.json):
#: key space, events a window, batch rows, the backend's keys
REHEARSALS = {
    "state_hll_1m": (512, 4096, 512, {}),
    "hll_10m": (6000, 4096, 256,
                {"state.backend.tpu.max-device-slots": 1024,
                 "state.backend.tpu.microbatch-size": 64}),
}


@pytest.mark.parametrize("cell", sorted(REHEARSALS))
def test_a_window_and_its_fire_probe_in_bulk_only(cell):
    """After a window of batches and its fire: no row went through
    `_slot_for`, and the bulk probes are the events, the fired keys
    and the cleared keys."""
    key_space, events, rows, keys_of_backend = REHEARSALS[cell]
    conf = Configuration().set("state.backend", "tpu")
    for name, value in keys_of_backend.items():
        conf.set(name, value)
    op, h = _window_job(TumblingEventTimeWindows.of(1000), conf)
    rng = np.random.default_rng(32)
    tr = get_tracer()
    for window in range(2):  # the second one over a table that is grown
        STATE_STATS.reset()
        tr.reset()
        keys = rng.integers(0, key_space, events)
        users = rng.integers(0, 1 << 30, events)
        ts = window * 1000 + np.sort(rng.integers(0, 1000, events))
        for lo in range(0, events, rows):
            sl = slice(lo, lo + rows)
            h.process_batch(RecordBatch({"f0": keys[sl], "f1": users[sl]},
                                        ts=ts[sl]))
            h.process_watermark(int(ts[sl][-1]) - 1)
        before = len(h.get_output())
        h.process_watermark(window * 1000 + 999)
        fired = len(h.get_output()) - before
        assert fired == len(set(keys.tolist()))
        assert STATE_STATS.per_key_probe_rows == 0
        assert STATE_STATS.bulk_probe_rows == events + fired + fired
        # batch-level attributes of the phase, never a phase per row
        slots_phase = tr.stats()["state.add.slots"]
        chunks = events // min(rows, keys_of_backend.get(
            "state.backend.tpu.microbatch-size", rows))
        assert slots_phase["count"] == chunks
    st = op.window_state
    assert op.boxed_fallbacks == 0 and not st.slot_index and not st.host_tier
    if keys_of_backend:
        assert st.capacity == 1024 and st.budget_overruns == 0
        assert st.evictions > 0 and st.promotions > 0
    check_invariants(st)


def test_phase_attributes_say_rows_and_new_slots():
    b, st = _state(TpuKeyedStateBackend, SumAggregate(np.float32), False)
    tr = get_tracer()
    seen = []
    was = tr.enabled
    tr.enabled = True
    try:
        tr.reset()
        b.add_batch(st, [1, 2, 1, 3], "w", np.ones(4, np.float32))
        b.add_batch(st, [3, 4], "w", np.ones(2, np.float32))
        b.add_batch(st, ["a"], "v", np.ones(1, np.float32))
        b.get_batch(st, [1, 2, 9], "w")
        b.clear_batch(st, [1, 2], "w")
        seen = [(e["args"]["rows"], e["args"]["new"], e["args"]["int_table"])
                for e in tr.recent(50) if e["name"] == "state.add.slots"]
        counted = {name: tr.stats()[name]["counts"]
                   for name in ("state.add.slots", "state.get.lookup",
                                "state.clear.slots")}
    finally:
        tr.enabled = was
        tr.reset()
    column = native.available()  # integer keys under one window
    assert seen == [(4, 3, 4 * column), (2, 1, 2 * column), (1, 1, 0)]
    assert counted == {
        "state.add.slots": {"rows": 7, "new": 5, "int_table": 6 * column},
        "state.get.lookup": {"rows": 3, "int_table": 3 * column},
        "state.clear.slots": {"rows": 2, "int_table": 2 * column}}


@pytest.mark.parametrize("gap", ["static", "per_element"])
def test_a_session_window_job_stays_on_the_per_key_door(gap):
    """Sessions with a gap per element merge namespaces row by row:
    every row of theirs is a per-key probe, none a bulk one.  Sessions
    of a static gap work their merges per batch since PR 37: every row
    a bulk probe under its state window, none per key."""
    assigner = EventTimeSessionWindows.with_gap(100) if gap == "static" \
        else DynamicEventTimeSessionWindows(lambda v: 100)
    op, h = _window_job(assigner, "tpu")
    STATE_STATS.reset()
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 20, 300)
    users = rng.integers(0, 1 << 30, 300)
    ts = np.sort(rng.integers(0, 5000, 300))
    h.process_batch(RecordBatch({"f0": keys, "f1": users}, ts=ts))
    h.process_watermark(10 ** 9)
    fired = len(h.get_output())
    assert fired > 20
    if gap == "static":
        assert STATE_STATS.per_key_probe_rows == 0
        assert STATE_STATS.bulk_probe_rows == 300 + 2 * fired
        assert op.boxed_fallbacks == 0
    else:
        assert STATE_STATS.bulk_probe_rows == 0
        assert STATE_STATS.per_key_probe_rows >= 300
    assert not op.window_state.slot_index
    check_invariants(op.window_state)


# ---------------------------------------------------------------------
# the integer table alone, against an index that holds dicts only: the
# form follows the keys (an int64 column is born an integer table, a
# list a dict), so the same calls with the same keys as a list drive
# the reference
# ---------------------------------------------------------------------

I64 = np.iinfo(np.int64)

needs_native = pytest.mark.skipif(
    not native.available(), reason="no native host runtime")


def _free(n):
    return list(range(n - 1, -1, -1))


def _is_int(table):
    """Whether a `KeyTable` is in its integer form."""
    return isinstance(getattr(table, "_ints", None), native.NativeIntTable)


def _is_int_table(index, namespace):
    return _is_int(index.tables[namespace])


class _BothForms:
    """An index driven with int64 columns beside one driven with the
    same keys as lists: equal answers, equal free lists, equal
    columns."""

    def __init__(self, slots=1 << 16):
        self.ints, self.dicts = NamespaceIndex(), NamespaceIndex()
        self.free = _free(slots), _free(slots)

    def resolve(self, keys, namespace="w"):
        ids, fresh, new_keys = self.ints.resolve(
            np.array(keys, np.int64), namespace, self.free[0])
        want = self.dicts.resolve(list(keys), namespace, self.free[1])
        assert ids.tolist() == want[0].tolist()
        assert fresh.tolist() == want[1].tolist()
        assert new_keys.tolist() == list(want[2])
        self.check()
        return ids, fresh

    def lookup(self, keys, namespace="w", take=False):
        ids = self.ints.lookup(np.array(keys, np.int64), namespace, take)
        want = self.dicts.lookup(list(keys), namespace, take)
        assert ids.tolist() == want.tolist()
        if take:
            for free in self.free:
                free.extend(ids[ids >= 0].tolist())
        self.check()
        return ids

    def check(self):
        assert self.free[0] == self.free[1]
        got, want = self.ints.columns(), self.dicts.columns()
        assert got[:2] == want[:2] and got[2].tolist() == want[2].tolist()
        assert list(self.ints) == list(self.dicts)
        assert len(self.ints) == len(self.dicts)
        assert list(self.ints.tables) == list(self.dicts.tables)
        assert not any(map(_is_int, self.dicts.tables.values()))
        assert all(map(_is_int, self.ints.tables.values()))


@needs_native
def test_an_integer_table_holds_every_int64():
    """0, -1 and both ends of int64 are keys like any other (no value
    stands for an empty cell), a key twice in one batch is new once,
    and a take of a key that comes twice finds it once."""
    both = _BothForms()
    edge = [0, -1, I64.min, I64.max, 0, I64.max, 7, -1]
    columns = KeyTable.column_rows
    ids, fresh = both.resolve(edge)
    assert len(fresh) == 5 and len(set(ids.tolist())) == 5
    assert ids[0] == ids[4] and ids[3] == ids[5] and ids[1] == ids[7]
    assert _is_int_table(both.ints, "w")
    assert KeyTable.column_rows - columns == 8
    assert both.lookup([I64.min, I64.min + 1, I64.max, I64.max - 1, 0, 1]) \
        .tolist() == [ids[2], -1, ids[3], -1, ids[0], -1]
    for key in (0, -1, I64.min, I64.max):
        assert both.ints.get(key, "w") == both.dicts.get(key, "w") >= 0
        assert (key, "w") in both.ints
    taken = both.lookup([I64.max, 0, I64.max, 5, 0], take=True)
    assert taken.tolist() == [ids[3], ids[0], -1, -1, -1]
    assert both.ints.columns()[0] == [-1, I64.min, 7]
    # the freed slots come back, most recently freed first
    both.resolve([I64.max, 0, 9])
    assert both.ints.columns()[0] == [-1, I64.min, 7, I64.max, 0, 9]


@needs_native
def test_deleted_keys_come_back_through_growth():
    """Half of 2,000 keys taken out, 20,000 more entered (the table
    doubles several times over the holes the deletes left), then the
    taken ones again: every key reads its id, `columns()` is the order
    of entry, as a dict's."""
    rng = np.random.default_rng(38)
    both = _BothForms()
    first = rng.permutation(2000) - 1000
    both.resolve(first)
    both.lookup(first[::2], take=True)
    later = rng.permutation(20_000) + 5000
    for lo in range(0, len(later), 3000):
        both.resolve(later[lo:lo + 3000])
    both.resolve(first[::2][::-1])
    assert len(both.ints) == 22_000
    keys, _, ids = both.ints.columns()
    assert keys == [*first[1::2].tolist(), *later.tolist(),
                    *first[::2][::-1].tolist()]
    assert both.lookup(keys).tolist() == ids.tolist()
    assert sorted(ids.tolist() + both.free[0]) == list(range(1 << 16))


@needs_native
def test_an_emptied_integer_table_is_dropped():
    both = _BothForms()
    both.resolve([1, 2, 3], "w")
    both.resolve([1, 2], "v")
    both.lookup([2, 1], "v", take=True)
    assert list(both.ints.tables) == ["w"] and both.ints
    assert both.ints.pop(3, "w") == both.dicts.pop(3, "w")
    both.lookup([1, 5], "w", take=True)
    assert both.ints.pop(2, "w") == both.dicts.pop(2, "w") is not None
    assert not both.ints and not both.ints.tables and len(both.ints) == 0
    # the next integer table is born with room for what this one held
    assert both.ints._room == 3 and both.dicts._room == 0
    both.resolve(list(range(500)), "u")
    both.lookup(list(range(500)), "u", take=True)
    assert both.ints._room == 500
    both.resolve([7, 8], "t")
    assert both.ints.tables["t"].dropped(0) == 2
    both.lookup([7, 8], "t", take=True)
    assert both.ints._room == 2
    # and a namespace is born again as what its next keys ask for
    both.ints.resolve(["a"], "w", both.free[0])
    assert not _is_int_table(both.ints, "w")


@needs_native
def test_the_scalar_doors_keep_dict_equality_on_an_integer_table():
    """1, 1.0 and True find the same entry, "1" none; a key that is no
    int64 reads as absent and changes nothing."""
    index = NamespaceIndex()
    index.resolve(np.array([1, 0, 5]), "w", _free(8))  # ids 0, 1, 2
    for one in (1, 1.0, True, np.int64(1), np.float32(1.0)):
        assert index.get(one, "w") == 0 and (one, "w") in index
    for zero in (0, 0.0, False, -0.0):
        assert index.get(zero, "w") == 1
    for other in ("1", 1.5, (1,), None, 1 << 63, float("nan"), float("inf"),
                  b"1"):
        assert index.get(other, "w") is None and (other, "w") not in index
        assert index.pop(other, "w") is None
    assert index.lookup([1.0, "1", True, 2.5, 5], "w").tolist() \
        == [0, -1, 0, -1, 2]
    index.put(1.0, "w", 6)  # the entry 1 has, not a second one
    index.put(True, "w", 7)
    assert index.get(1, "w") == 7 and len(index) == 3
    assert index.pop(5.0, "w") == 2 and index.pop(5, "w") is None
    assert _is_int_table(index, "w")
    assert index.lookup([0.0, True, "x"], "w", take=True).tolist() \
        == [1, 7, -1]
    assert not index


@needs_native
@pytest.mark.parametrize("door", ["resolve", "enter", "put"])
@pytest.mark.parametrize("stranger", ["k", 2.5, (1, 2), 1 << 63],
                         ids=["str", "float", "tuple", "beyond_int64"])
def test_a_key_it_cannot_hold_turns_an_integer_table_into_a_dict_once(
        door, stranger):
    """Every earlier entry is still found, in its place; the table
    stays a dict when integer columns come again."""
    demoted = KeyTable.demotions
    index = NamespaceIndex()
    free = _free(64)
    ids, _, _ = index.resolve(np.array([5, -3, 9, 5, 0]), "w", free)
    index.resolve(np.array([1, 2]), "v", free)
    assert _is_int_table(index, "w")
    strange_id = 57
    if door == "resolve":
        got, fresh, new_keys = index.resolve([9, stranger, 5], "w", free)
        strange_id = int(got[1])
        assert got.tolist() == [ids[2], strange_id, ids[0]]
        assert strange_id not in free and strange_id not in ids
        assert fresh.tolist() == [strange_id] and list(new_keys) == [stranger]
    elif door == "enter":
        index.enter([stranger, 9], "w", np.array([57, ids[2]]))
    else:
        index.put(stranger, "w", 57)
    assert not _is_int_table(index, "w") and _is_int_table(index, "v")
    assert KeyTable.demotions - demoted == 1
    assert index.columns()[0] == [5, -3, 9, 0, stranger, 1, 2]
    assert list(index.tables) == ["w", "v"]
    assert index.lookup([5, -3, 9, 0, stranger], "w").tolist() \
        == [*ids[[0, 1, 2, 4]].tolist(), strange_id]
    was = KeyTable.column_rows
    index.resolve(np.array([5, 77]), "w", free)
    assert not _is_int_table(index, "w") and KeyTable.column_rows == was
    assert KeyTable.demotions - demoted == 1
    assert index.get(77, "w") is not None


@needs_native
def test_a_lookup_never_turns_a_table_into_a_dict():
    demoted = KeyTable.demotions
    index = NamespaceIndex()
    index.resolve(np.array([1, 2, 3]), "w", _free(8))
    assert index.lookup(["a", 2, (3,)], "w").tolist() == [-1, 1, -1]
    assert index.missing(["a", 2, "a", 4.5], "w") == 2
    assert index.missing(np.array([7, 2, 7, 8]), "w") == 2
    assert index.lookup([2.0, "a"], "w", take=True).tolist() == [1, -1]
    assert _is_int_table(index, "w") and KeyTable.demotions == demoted


@needs_native
def test_what_is_born_an_integer_table():
    """A table is born from the first keys it gets: an int64 column
    makes an integer table, a list or a scalar door a dict; `key_column`
    reads a list as a column where numpy gives it an integer dtype."""
    column = slot_index.key_column
    for keys in ([1, 2, 3], [True, 2], [np.int64(4), 5], (6, 7),
                 np.array([1, 2], np.int32)):
        got = column(keys)
        assert isinstance(got, np.ndarray) and got.dtype == np.int64
        assert got.tolist() == [int(k) for k in keys]
    for keys in ([1, 2.0], [1, "a"], ["a", 1], [1, (1, 2)], [(1, 2), (3, 4)],
                 [1, 1 << 63], [1 << 64], [1, None], [True, False], [],
                 [1.0, 2.0]):
        assert column(keys) is keys
    assert column(np.array([1.5, 2.0])) == [1.5, 2.0]
    assert column(np.array(["a"])) == ["a"]
    assert column(np.array([1, 2], np.uint64)) == [1, 2]
    index = NamespaceIndex()
    index.put(1, "scalar", 0)
    index.enter([2, 3], "list", np.array([1, 2]))
    index.enter(np.array([2, 3]), "column", np.array([3, 4]))
    index.resolve([4], "resolved_list", _free(8))
    index.resolve(np.array([4]), "resolved_column", _free(8))
    assert [name for name in index.tables if _is_int_table(index, name)] \
        == ["column", "resolved_column"]
    # entries that move take their table's form along
    other = NamespaceIndex()
    index.move([3], "column", other, np.array([9]))
    index.move([3], "list", other, np.array([8]))
    assert _is_int_table(other, "column") and not _is_int_table(other, "list")
    assert other.get(3, "column") == 9 and index.get(3, "column") is None
    assert index.get(2, "column") == 3


def test_without_the_native_runtime_every_table_is_a_dict(dict_tables_only):
    b, st = _state(TpuKeyedStateBackend, SumAggregate(np.float32), True)
    STATE_STATS.reset()
    rng = np.random.default_rng(1)
    for _ in range(6):
        keys = rng.integers(0, 100, 40)
        b.add_batch(st, keys.tolist(), "w", np.ones(40, np.float32))
        b.add_batch(st, keys, "v", np.ones(40, np.float32))
    assert st.evictions > 0
    for index in (st.slot_index, st.host_tier.index):
        assert not any(map(_is_int, index.tables.values()))
    assert STATE_STATS.int_table_rows == 0 < STATE_STATS.bulk_probe_rows
    res, found, _ = b.get_batch(st, list(range(100)), "v")
    assert found.sum() == len({k for k, ns in st.active_entries()
                               if ns == "v"})
    check_invariants(st)


@needs_native
@pytest.mark.parametrize("seed", range(8))
def test_any_interleaving_gives_the_ids_a_dict_gives(seed):
    """Random runs of probe-or-insert, lookup and take over random
    integer keys in a few namespaces: the same ids, the same free
    list and the same columns as the dict-only index after every
    call."""
    rng = np.random.default_rng([38, seed])
    both = _BothForms(slots=4096)
    spaces = [(-40, 40), (I64.min, I64.min + 60), (I64.max - 60, I64.max),
              (-(1 << 40), 1 << 40)]
    for _ in range(120):
        namespace = ("w", int(rng.integers(0, 3)))
        lo, hi = spaces[int(rng.integers(0, len(spaces)))]
        n = int(rng.integers(1, 48))
        keys = rng.integers(lo, hi, n, endpoint=True)
        if n > 2:
            keys[-1] = keys[0]
        call = rng.integers(0, 4)
        if call <= 1:
            both.resolve(keys, namespace)
        elif call == 2:
            both.lookup(keys, namespace)
        else:
            both.lookup(keys, namespace, take=True)

"""The `tpu` backend's pending micro-batch as columns, against the
lists it replaced (``spill_tier_reference.py``: three lists of Python
values, `stable_hash64` a value at a time): what reaches
`state.update` and what the device then holds are the reference's bit
for bit, for every kind of value column, and the two counters say
which hash took how many rows."""

import numpy as np
import pytest

from flink_tpu.core.keygroups import KeyGroupRange
from flink_tpu.core.state import AggregatingStateDescriptor
from flink_tpu.ops.device_agg import SumAggregate
from flink_tpu.ops.sketches import (
    CountMinSketchAggregate,
    HyperLogLogAggregate,
    QuantileSketchAggregate,
)
from flink_tpu.state.stats import STATE_STATS
from flink_tpu.state.tpu_backend import TpuKeyedStateBackend, _PendingRing
from spill_tier_reference import PerKeySpillBackend

FULL_RANGE = KeyGroupRange(0, 127)
MAX_PAR = 128
NS = (0, 1000)
#: rows of a case: nine keys, each several times
ROWS = 24
KEYS = [i * 7 % 9 for i in range(ROWS)]

AGGS = {"hll": lambda: HyperLogLogAggregate(6),
        "cms": lambda: CountMinSketchAggregate(2, 64),
        "quantile": lambda: QuantileSketchAggregate(),
        "sum": lambda: SumAggregate(np.float32)}


class _Driven:
    """A backend, its state, and every argument list `state.update`
    was dispatched with."""

    def __init__(self, backend_cls, agg_name, microbatch=64):
        self.backend = backend_cls(FULL_RANGE, MAX_PAR, initial_capacity=16,
                                   microbatch=microbatch)
        self.state = self.backend.get_or_create_keyed_state(
            AggregatingStateDescriptor("s", AGGS[agg_name]()))
        self.state.set_current_namespace(NS)
        self.flushes = []
        update = self.state._jit_update

        def recorded(device_state, *args):
            self.flushes.append([np.array(a) for a in args])
            return update(device_state, *args)

        self.state._jit_update = recorded

    def add_batch(self, keys, values):
        self.state.add_batch(keys, NS, values)

    def add_rows(self, keys, values):
        for key, value in zip(keys, values):
            self.backend.set_current_key(key)
            self.state.add(value)

    def cells(self):
        """{key: {component: bytes}} of what the device holds, every
        pending row flushed."""
        cells = {}
        for keys, _, comps in self.state.snapshot_columns().values():
            for i, key in enumerate(keys):
                assert key not in cells
                cells[key] = {name: np.asarray(arr[i]).tobytes()
                              for name, arr in comps.items()}
        return cells


def _assert_same_flushes(got, want, but_rows=()):
    """Two records of `state.update` dispatches: shapes, dtypes and
    contents (`but_rows`: rows whose hash no two calls repeat)."""
    assert len(got) == len(want) > 0
    for args, ref_args in zip(got, want):
        assert len(args) == len(ref_args) == 5
        for arg, ref in zip(args, ref_args):
            assert arg.dtype == ref.dtype and arg.shape == ref.shape
            keep = np.ones(len(arg), bool)
            keep[list(but_rows)] = False
            assert arg[keep].tobytes() == ref[keep].tobytes()


def _hash_counts():
    return STATE_STATS.hash_column_rows, STATE_STATS.hash_per_value_rows


# ---------------------------------------------------------------------
# integer columns: hashed whole
# ---------------------------------------------------------------------

_I64 = np.array([-1, 0, 1, -(2 ** 62), 2 ** 62, 2 ** 40 + 3, -(2 ** 40),
                 np.iinfo(np.int64).min, np.iinfo(np.int64).max],
                np.int64)
_U64 = np.array([0, 1, 2 ** 63, 2 ** 63 + 5, 2 ** 64 - 1, 2 ** 40],
                np.uint64)
_I32 = np.array([-1, 0, 7, np.iinfo(np.int32).min, np.iinfo(np.int32).max],
                np.int32)

INT_COLUMNS = {
    "int64": np.resize(_I64, ROWS),
    "uint64": np.resize(_U64, ROWS),
    "int32": np.resize(_I32, ROWS),
}


@pytest.mark.parametrize("agg_name", ["hll", "cms"])
@pytest.mark.parametrize("kind", [*INT_COLUMNS, "woven"])
def test_integer_column_hashes_whole_and_equals_the_per_value_loop(
        agg_name, kind):
    bulk = _Driven(TpuKeyedStateBackend, agg_name)
    ref = _Driven(PerKeySpillBackend, agg_name)
    scalar = _Driven(TpuKeyedStateBackend, agg_name)
    if kind == "woven":
        # two columns and scalar adds between and after them, all in
        # one micro-batch: (door, rows)
        column = INT_COLUMNS["int64"]
        programme = [("batch", slice(0, 9)), ("rows", slice(9, 12)),
                     ("batch", slice(12, 22)), ("rows", slice(22, 24))]
    else:
        column = INT_COLUMNS[kind]
        programme = [("batch", slice(0, ROWS))]
    before = _hash_counts()
    for door, rows in programme:
        for driven in (bulk, ref):
            if door == "batch":
                driven.add_batch(KEYS[rows], column[rows])
            else:
                driven.add_rows(KEYS[rows], column[rows])
    in_columns = sum(r.stop - r.start for d, r in programme if d == "batch")
    # (the reference counts nothing: its bodies are the old ones)
    assert _hash_counts() == (before[0] + in_columns,
                              before[1] + ROWS - in_columns)
    scalar.add_rows(KEYS, column)
    assert _hash_counts() == (before[0] + in_columns,
                              before[1] + 2 * ROWS - in_columns)
    # nothing was flushed on the way: one micro-batch, in row order
    assert not bulk.flushes and len(bulk.state._pending_slots) == ROWS
    cells = bulk.cells()
    assert cells == ref.cells() == scalar.cells() and len(cells) == 9
    _assert_same_flushes(bulk.flushes, ref.flushes)
    _assert_same_flushes(scalar.flushes, ref.flushes)
    assert len(bulk.flushes) == 1


# ---------------------------------------------------------------------
# everything else: stable_hash64 a value at a time, as ever
# ---------------------------------------------------------------------

def _object_column(values):
    column = np.empty(len(values), object)
    column[:] = values
    return column


_FLOATS = np.array([1.0, -3.0, 2.5, -0.0, 1e30, np.inf, -np.inf, np.nan,
                    float(2 ** 53)], np.float64)
_BIG_INTS = [2 ** 63, -1, 0, 2 ** 64 - 1, -(2 ** 63), 12345]

OTHER_COLUMNS = {
    "float64": np.resize(_FLOATS, ROWS),
    "float32": np.resize(_FLOATS, ROWS).astype(np.float32),
    "big-int-list": [_BIG_INTS[i % 6] for i in range(ROWS)],
    "int-object-array": _object_column([_BIG_INTS[i % 6]
                                        for i in range(ROWS)]),
    "bool": np.arange(ROWS) % 3 == 0,
    "str-list": [f"user-{i % 11}" for i in range(ROWS)],
    "tuple-list": [(i % 5, f"t{i % 3}") for i in range(ROWS)],
    "mixed-object-array": _object_column(
        [(None, 1.5, "x", (1, 2), 7, b"y")[i % 6] for i in range(ROWS)]),
}
#: the count-min sketch weighs with the value it hashes: numbers only
_NUMERIC = ("float64", "float32", "big-int-list", "int-object-array", "bool")


@pytest.mark.parametrize("agg_name,kind", [
    *(("hll", kind) for kind in OTHER_COLUMNS),
    *(("cms", kind) for kind in _NUMERIC)])
def test_other_columns_keep_the_per_value_hash(agg_name, kind):
    column = OTHER_COLUMNS[kind]
    bulk = _Driven(TpuKeyedStateBackend, agg_name)
    ref = _Driven(PerKeySpillBackend, agg_name)
    before = _hash_counts()
    bulk.add_batch(KEYS, column)
    assert _hash_counts() == (before[0], before[1] + ROWS)
    ref.add_batch(KEYS, column)
    # a NaN hashes by the identity of its (temporary) object: no two
    # calls repeat it, so its rows and its key's cell are left out
    nan_rows = [i for i, v in enumerate(column)
                if isinstance(v, (float, np.floating)) and v != v]
    nan_keys = {KEYS[i] for i in nan_rows}
    cells, ref_cells = bulk.cells(), ref.cells()
    assert cells.keys() == ref_cells.keys() and len(cells) == 9
    for key in cells.keys() - nan_keys:
        assert cells[key] == ref_cells[key]
    _assert_same_flushes(bulk.flushes, ref.flushes, but_rows=nan_rows)


def test_a_two_dimensional_integer_column_is_not_hashed_whole():
    """A row of a 2-d column is an ndarray, which has no stable hash
    (the engines' combined hash of such rows is not `stable_hash64` of
    a tuple): the per-value branch refuses it, as ever."""
    bulk = _Driven(TpuKeyedStateBackend, "hll")
    before = _hash_counts()
    with pytest.raises(TypeError):
        bulk.add_batch(KEYS, np.arange(2 * ROWS, dtype=np.int64)
                       .reshape(ROWS, 2))
    assert _hash_counts()[0] == before[0]


# ---------------------------------------------------------------------
# value columns: the ring keeps the rows in order
# ---------------------------------------------------------------------

@pytest.mark.parametrize("agg_name", ["quantile", "sum"])
@pytest.mark.parametrize("dtype", [np.float64, np.float32, np.int64])
def test_value_columns_and_scalar_adds_flush_in_row_order(agg_name, dtype):
    rng = np.random.default_rng(3)
    column = (rng.lognormal(3.0, 2.0, ROWS) + 1.0).astype(dtype)
    bulk = _Driven(TpuKeyedStateBackend, agg_name)
    ref = _Driven(PerKeySpillBackend, agg_name)
    scalar = _Driven(TpuKeyedStateBackend, agg_name)
    before = _hash_counts()
    for driven in (bulk, ref):
        driven.add_rows(KEYS[:2], column[:2])
        driven.add_batch(KEYS[2:11], column[2:11])
        driven.add_rows(KEYS[11:14], column[11:14])
        # (a list, as the boxed path hands over)
        driven.add_batch(KEYS[14:], column[14:].tolist())
    scalar.add_rows(KEYS, column)
    # nothing to hash: neither counter moves
    assert _hash_counts() == before
    cells = bulk.cells()
    assert cells == ref.cells() == scalar.cells() and len(cells) == 9
    _assert_same_flushes(bulk.flushes, ref.flushes)
    _assert_same_flushes(scalar.flushes, ref.flushes)
    values = bulk.flushes[0][1]
    assert values.dtype == bulk.state.agg.value_dtype
    assert values[:ROWS].tolist() == column.astype(values.dtype).tolist()


def test_a_value_column_is_copied_into_the_ring():
    """The caller may write into its column again once `add_batch`
    returned: the ring holds the rows as they were."""
    bulk = _Driven(TpuKeyedStateBackend, "sum")
    column = np.arange(ROWS, dtype=np.float32)
    bulk.add_batch(KEYS, column)
    column[:] = -1.0
    bulk.state._flush()
    assert bulk.flushes[0][1][:ROWS].tolist() == list(range(ROWS))


def test_micro_batches_cut_at_the_same_rows():
    """A flush comes when the ring holds `microbatch` rows or more,
    whichever door filled it, and `reset` empties every ring."""
    bulk = _Driven(TpuKeyedStateBackend, "cms", microbatch=8)
    ref = _Driven(PerKeySpillBackend, "cms", microbatch=8)
    column = INT_COLUMNS["int64"]
    for driven in (bulk, ref):
        driven.add_batch(KEYS[:5], column[:5])
        driven.add_rows(KEYS[5:9], column[5:9])     # flushes at 8
        driven.add_batch(KEYS[9:20], column[9:20])  # 12 rows: at once
        driven.add_rows(KEYS[20:], column[20:])
    assert [int(f[4].sum()) for f in bulk.flushes] == [8, 12]
    _assert_same_flushes(bulk.flushes, ref.flushes)
    state = bulk.state
    assert len(state._pending_slots) == len(state._pending_values) \
        == len(state._pending_hash) == 4
    state.reset()
    assert len(state._pending_slots) == len(state._pending_values) \
        == len(state._pending_hash) == 0
    state._flush()
    assert len(bulk.flushes) == 2


class UserHll(HyperLogLogAggregate):
    """COUNT DISTINCT over field 1 (the user) of a (key, user) row, as
    ``benchmark/jobs/datastream_state.py`` has it."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def test_state_route_job_hashes_every_event_in_a_column():
    """Config #2's job on the pinned route (`WindowOperator` over the
    `tpu` backend) through `env.execute()`: the user column reaches
    `add_batch` as the integer array the source made it, so no event
    is hashed alone.  A change upstream that boxes the column fails
    here."""
    from flink_tpu.streaming.columnar import VectorizedCollectionSource
    from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    from flink_tpu.streaming.sources import CollectSink
    from flink_tpu.streaming.windowing import Time, TumblingEventTimeWindows
    rng = np.random.default_rng(11)
    events, batch = 384, 64
    keys = rng.integers(0, 40, events)
    users = rng.integers(-(1 << 40), 1 << 40, events)
    ts = np.sort(rng.integers(0, 3000, events))
    env = StreamExecutionEnvironment()
    env.set_state_backend("tpu")
    sink = CollectSink()
    windowed = (env.add_source(VectorizedCollectionSource(
        [((int(k), int(u)), int(t)) for k, u, t in zip(keys, users, ts)],
        timestamped=True, chunk=batch))
        .key_by(0).window(TumblingEventTimeWindows.of(Time.seconds(1))))
    windowed.disable_device_operator()
    windowed.aggregate(
        UserHll(8), window_function=lambda k, w, v:
        [(k, w.start, float(v[0]))]).add_sink(sink)
    before = _hash_counts()
    env.execute("state-route-hash-column")
    assert _hash_counts() == (before[0] + events, before[1])
    # every (key, window) once, each within the sketch's error of its
    # distinct users
    want = {}
    for k, u, t in zip(keys.tolist(), users.tolist(), ts.tolist()):
        want.setdefault((k, t // 1000 * 1000), set()).add(u)
    got = {(k, start): d for k, start, d in sink.values}
    assert len(sink.values) == len(got) and got.keys() == want.keys()
    for entry, distinct in want.items():
        assert abs(got[entry] - len(distinct)) <= max(1.0,
                                                      0.3 * len(distinct))


def test_pending_ring_keeps_columns_and_single_entries_in_order():
    ring = _PendingRing(np.uint64)
    assert len(ring) == 0
    ring.append(2 ** 64 - 1)
    ring.append(3)
    first = np.array([7, 8], np.uint64)
    ring.extend(first)
    ring.append(2 ** 63)
    ring.extend([1, 2])
    assert len(ring) == 7
    taken = ring.take()
    assert taken.dtype == np.uint64
    assert taken.tolist() == [2 ** 64 - 1, 3, 7, 8, 2 ** 63, 1, 2]
    ring.clear()
    assert len(ring) == 0
    ring.append(5)
    assert ring.take().tolist() == [5]
    values = _PendingRing(np.float32)
    values.extend(np.array([1, 2], np.int64))
    values.append(np.float64(0.1))
    assert values.take().tolist() == [1.0, 2.0, float(np.float32(0.1))]

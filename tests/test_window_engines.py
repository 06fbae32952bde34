"""The one decision (flink_tpu/streaming/window_engines.py): which
engine runs a windowed aggregate, what its tier is called in a
checkpoint, and which constructor reads a checkpoint of that tier —
asked through both doors that host an engine, the DataStream door
(DeviceWindowOperator) and the SQL door (ColumnarWindowOperator).

LADDER below is a record of the answers the two operators gave before
the decision had a module of its own (PR 28's tree, its two ladders
driven by this file's `answer` on a copy of it): the places where the
doors differ are in it as they were.
"""

import itertools

import numpy as np
import pytest

import flink_tpu.native as nat
from flink_tpu.ops.device_agg import MaxAggregate, SumAggregate
from flink_tpu.ops.sketches import (
    CountMinSketchAggregate,
    HyperLogLogAggregate,
)
from flink_tpu.streaming.columnar import ColumnarWindowOperator
from flink_tpu.streaming.device_window_operator import DeviceWindowOperator
from flink_tpu.streaming.elements import RecordBatch, StreamRecord
from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
from flink_tpu.streaming.windowing import (
    EventTimeSessionWindows,
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)

pytestmark = pytest.mark.skipif(not nat.available(),
                                reason="native runtime unavailable")

SHAPES = {
    "tumbling": lambda: TumblingEventTimeWindows.of(1000),
    "tumbling_offset": lambda: TumblingEventTimeWindows(1000, 250),
    "sliding": lambda: SlidingEventTimeWindows.of(1000, 500),
    "sliding_unaligned": lambda: SlidingEventTimeWindows.of(1000, 300),
    "session": lambda: EventTimeSessionWindows.with_gap(400),
}
AGGS = {
    "hll": lambda: HyperLogLogAggregate(8),
    "sum_f": lambda: SumAggregate(np.float32),
    "sum_i": lambda: SumAggregate(np.int64),
    "max": lambda: MaxAggregate(np.float32),
    "cm": lambda: CountMinSketchAggregate(2, 64),
}
#: key columns as each door meets them: integers, one fixed-width
#: string column, and keys numpy cannot hold in one typed column
#: (tuples on the DataStream door, an object column on the SQL door)
KEYS = ("int", "str", "composite")
TS = np.array([10, 20, 30, 40, 50, 60], np.int64)


def the_mesh():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:8]), ("kg",))


def key_cells(kind):
    base = [3, 5, 3, 7, 5, 3]
    if kind == "int":
        return base
    if kind == "str":
        return [f"k{b}" for b in base]
    return [(b, f"s{b}") for b in base]


def make_operator(door, shape, agg, mesh):
    if door == "ds":
        agg = AGGS[agg]()
        agg.extract_value = lambda v: v[1]
        agg.extract_column = lambda cols: cols[1]
        op = DeviceWindowOperator(SHAPES[shape](), agg, mesh=mesh)
        return OneInputStreamOperatorTestHarness(
            op, key_selector=lambda v: v[0])
    op = ColumnarWindowOperator(
        SHAPES[shape](), AGGS[agg](), "k", "v",
        [("k", "key"), ("r", "agg"), ("e", "wend")], mesh=mesh)
    return OneInputStreamOperatorTestHarness(op)


def feed(h, door, keys, values=None, ts=TS):
    """One small batch through the door's own entry."""
    cells = key_cells(keys)
    values = values if values is not None else [1, 2, 3, 4, 5, 6]
    if door == "ds":
        for k, v, t in zip(cells, values, ts.tolist()):
            h.process_element((k, v), t)
        h.operator._flush_buffer()
        return
    col = np.empty(len(cells), object)
    col[:] = cells
    if keys != "composite":
        col = np.asarray(cells)
    h.process_element(StreamRecord(
        RecordBatch({"k": col, "v": np.asarray(values, np.int64)}, ts)))


def answer(door, shape, agg, keys, mesh):
    """(engine class name, tier name) — or the exception's class
    name where the door refuses."""
    h = make_operator(door, shape, agg, the_mesh() if mesh else None)
    try:
        h.open()
        feed(h, door, keys)
        snap = h.snapshot()
    except Exception as e:  # noqa: BLE001 — the refusal IS the answer
        return type(e).__name__
    tier_key = "device_tier" if door == "ds" else "columnar_tier"
    return type(h.operator.engine).__name__, snap[tier_key]


#: The record.  One row per (shape, aggregate); four groups of three
#: columns: DataStream door without and with the 8-device mesh, SQL
#: door without and with it; in a group the key columns int, str,
#: composite.  L log · S string_sum · V vectorized (single device) ·
#: ML mesh_log · MS vectorized tier on the sharded scatter engines ·
#: -- refused with ValueError (no engine for the assigner).
#: The doors differ (select_engine's docstring): (i) the MS columns
#: against the SQL door's V under a mesh; (ii) the DataStream door's
#: str column reads as its int column wherever S does not fit, the SQL
#: door's falls to V.
LADDER = """
tumbling          hll   L  L  V   ML ML MS  L  V  V   ML V  V
tumbling          sum_f L  S  V   ML ML MS  L  S  V   ML S  V
tumbling          sum_i L  L  V   ML ML MS  L  V  V   ML V  V
tumbling          max   V  V  V   MS MS MS  V  V  V   V  V  V
tumbling          cm    V  V  V   MS MS MS  V  V  V   V  V  V
tumbling_offset   hll   -- -- --  -- -- --  -- -- --  -- -- --
tumbling_offset   sum_f -- -- --  -- -- --  -- -- --  -- -- --
tumbling_offset   sum_i -- -- --  -- -- --  -- -- --  -- -- --
tumbling_offset   max   -- -- --  -- -- --  -- -- --  -- -- --
tumbling_offset   cm    -- -- --  -- -- --  -- -- --  -- -- --
sliding           hll   L  L  V   ML ML MS  L  V  V   ML V  V
sliding           sum_f L  L  V   ML ML MS  L  V  V   ML V  V
sliding           sum_i L  L  V   ML ML MS  L  V  V   ML V  V
sliding           max   V  V  V   MS MS MS  V  V  V   V  V  V
sliding           cm    V  V  V   MS MS MS  V  V  V   V  V  V
sliding_unaligned hll   -- -- --  -- -- --  -- -- --  -- -- --
sliding_unaligned sum_f -- -- --  -- -- --  -- -- --  -- -- --
sliding_unaligned sum_i -- -- --  -- -- --  -- -- --  -- -- --
sliding_unaligned max   -- -- --  -- -- --  -- -- --  -- -- --
sliding_unaligned cm    -- -- --  -- -- --  -- -- --  -- -- --
session           hll   V  V  V   V  V  V   V  V  V   V  V  V
session           sum_f V  V  V   V  V  V   V  V  V   V  V  V
session           sum_i V  V  V   V  V  V   V  V  V   V  V  V
session           max   V  V  V   V  V  V   V  V  V   V  V  V
session           cm    L  L  V   ML ML V   L  V  V   ML V  V
"""
CODES = {"L": ("LogStructured{}Windows", "log"),
         "S": ("StringSum{}Windows", "string_sum"),
         "V": ("Vectorized{}Windows", "vectorized"),
         "ML": ("MeshLog{}Windows", "mesh_log"),
         "MS": ("Mesh{}Windows", "vectorized")}
COLUMNS = list(itertools.product(("ds", "sql"), (False, True), KEYS))


def ladder_cases():
    for line in LADDER.strip().splitlines():
        shape, agg, *cells = line.split()
        kind = shape.split("_")[0].capitalize()
        for (door, mesh, keys), code in zip(COLUMNS, cells, strict=True):
            want = "ValueError"
            if code != "--":
                cls, tier = CODES[code]
                want = (cls.format(kind), tier)
            yield pytest.param(
                door, shape, agg, keys, mesh, want,
                id=f"{door}-{shape}-{agg}-{keys}-{'mesh' if mesh else 'host'}")


@pytest.mark.parametrize("door,shape,agg,keys,mesh,want", ladder_cases())
def test_ladder_answers_as_before(door, shape, agg, keys, mesh, want):
    assert answer(door, shape, agg, keys, mesh) == want


# ---- tiers through a checkpoint -------------------------------------

#: one way into each tier: (aggregate, keys, mesh)
TIER_ROUTES = {"string_sum": ("sum_f", "str", False),
               "mesh_log": ("hll", "int", True),
               "log": ("sum_i", "int", False),
               "vectorized": ("max", "int", False)}
RESPLITS = {"string_sum", "log"}  # the tiers with restore_many


def fed_operator(door, tier, values=None):
    agg, keys, mesh = TIER_ROUTES[tier]
    h = make_operator(door, "tumbling", agg, the_mesh() if mesh else None)
    h.open()
    feed(h, door, keys, values)
    return h


def fresh_operator(door, tier):
    agg, _, mesh = TIER_ROUTES[tier]
    h = make_operator(door, "tumbling", agg, the_mesh() if mesh else None)
    h.open()
    return h


def fired_rows(h, door):
    """Everything the operator fires at the end of time, as sorted
    (key, result) rows."""
    h.process_watermark(2 ** 62)
    rows = []
    for v in h.extract_output_values():
        if isinstance(v, RecordBatch):
            cols = list(v.cols.values())
            v = zip(*(c.tolist() for c in cols[:2])) if door == "sql" \
                else v.row_values()
        else:
            v = [v]
        rows.extend(v)
    return sorted(map(str, rows))


@pytest.mark.parametrize("door", ["ds", "sql"])
@pytest.mark.parametrize("tier", list(TIER_ROUTES))
def test_tier_survives_a_plain_restore(door, tier):
    src = fed_operator(door, tier)
    snap = src.snapshot()
    assert snap[src.operator.tier_key] == tier
    dst = fresh_operator(door, tier)
    dst.initialize_state([snap])
    assert type(dst.operator.engine) is type(src.operator.engine)
    assert dst.operator.tier == tier
    want = fired_rows(src, door)
    assert fired_rows(dst, door) == want and want
    # the restored operator writes the tier it read
    assert dst.snapshot()[dst.operator.tier_key] == tier


@pytest.mark.parametrize("door", ["ds", "sql"])
@pytest.mark.parametrize("tier", list(TIER_ROUTES))
def test_rescaled_restore_resplits_or_refuses(door, tier):
    """Two old subtasks' snapshots into one new subtask: the log and
    string-sum tiers merge them (restore_many), the others refuse."""
    olds = [fed_operator(door, tier, [1, 2, 3, 4, 5, 6]),
            fed_operator(door, tier, [10, 20, 30, 40, 50, 60])]
    snaps = [dict(h.snapshot(), restore_old_parallelism=2) for h in olds]
    dst = fresh_operator(door, tier)
    if tier not in RESPLITS:
        with pytest.raises(ValueError, match="cannot re-split"):
            dst.initialize_state(snaps)
        return
    dst.initialize_state(snaps)
    assert type(dst.operator.engine) is type(olds[0].operator.engine)
    both = fed_operator(door, tier, [11, 22, 33, 44, 55, 66])
    assert fired_rows(dst, door) == fired_rows(both, door)


@pytest.mark.parametrize("door", ["ds", "sql"])
def test_snapshots_spanning_tiers_are_refused(door):
    snaps = [fed_operator(door, "log").snapshot(),
             fed_operator(door, "vectorized").snapshot()]
    with pytest.raises(ValueError, match="span engine tiers"):
        fresh_operator(door, "log").initialize_state(snaps)


def test_string_directory_does_not_cross_a_rescale():
    """The DataStream door interned the string keys: the ids in the
    engine state mean nothing without the directory, and directories
    of two old subtasks cannot be merged."""
    src = make_operator("ds", "tumbling", "hll", None)
    src.open()
    feed(src, "ds", "str")
    snap = src.snapshot()
    assert snap["string_key_directory"] == ["k3", "k5", "k7"]
    assert snap["device_tier"] == "log"
    dst = make_operator("ds", "tumbling", "hll", None)
    dst.open()
    with pytest.raises(ValueError, match="dictionary-encoded"):
        dst.initialize_state([dict(snap, restore_old_parallelism=2)])
    # at the checkpointed parallelism the directory comes back
    dst.initialize_state([snap])
    assert dst.operator._id_to_key == ["k3", "k5", "k7"]
    assert fired_rows(dst, "ds") == fired_rows(src, "ds")


@pytest.mark.parametrize("door,engine_key,tier_key", [
    ("ds", "device_engine", "device_tier"),
    ("sql", "columnar_engine", "columnar_tier")])
def test_checkpoint_keys_on_disk_are_unchanged(door, engine_key, tier_key):
    """A snapshot dict spelled as PR 28's operators wrote it — an
    engine's own snapshot under the door's key, the tier under the
    door's tier key — restores."""
    from flink_tpu.streaming.log_windows import LogStructuredTumblingWindows
    eng = LogStructuredTumblingWindows(SumAggregate(np.int64), 1000)
    eng.process_batch(np.array(key_cells("int")), TS,
                      np.array([1, 2, 3, 4, 5, 6], np.int64))
    dst = fresh_operator(door, "log")
    dst.initialize_state([{engine_key: eng.snapshot(), tier_key: "log"}])
    assert isinstance(dst.operator.engine, LogStructuredTumblingWindows)
    assert fired_rows(dst, door) == fired_rows(
        fed_operator(door, "log"), door)
    # before tiers had names: no tier key at all is a vectorized one
    vec = fed_operator(door, "vectorized")
    old = {k: v for k, v in vec.snapshot().items() if k != tier_key}
    dst = fresh_operator(door, "vectorized")
    dst.initialize_state([old])
    assert type(dst.operator.engine) is type(vec.operator.engine)


if __name__ == "__main__":
    # the record is made by driving this file's `answer` on the tree
    # whose answers are wanted (XLA_FLAGS as tests/conftest.py sets it)
    for case in itertools.product(("ds", "sql"), SHAPES, AGGS, KEYS,
                                  (False, True)):
        print(case, "->", answer(*case))

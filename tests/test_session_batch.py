"""Differential suite for the batched session-window path.

`SessionBatchPlan` (the MergingWindowSet worked per batch: one
`add_batch` under state windows, one `merge_namespaces_batch`, bulk
timers, a columnar fire; rows handed over through `process_batch`)
against the per-row MergingWindowSet (the same rows one at a time
through `process_element`, `batch_fires = False` for its per-timer
drain).  On both backends the two must be BIT-EQUAL in what they emit
and in what order per watermark, in the window -> state-window mapping
they persist (dict order included), in the live timers and the order
they fire in, in the late side output and in `numLateRecordsDropped`
— over streams that hold every way a session can change."""

import copy

import numpy as np
import pytest

from flink_tpu.core.config import Configuration
from flink_tpu.core.state import (
    AggregatingStateDescriptor,
    ListStateDescriptor,
)
from flink_tpu.ops.sketches import CountMinSketchAggregate
from flink_tpu.state.backend import VOID_NAMESPACE
from flink_tpu.state.stats import STATE_STATS
from flink_tpu.streaming.elements import RecordBatch
from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
from flink_tpu.streaming.operators import OutputTag
from flink_tpu.streaming.window_operator import (
    SessionBatchPlan,
    WindowOperator,
)
from flink_tpu.streaming.windowing import (
    DynamicEventTimeSessionWindows,
    EventTimeSessionWindows,
    ProcessingTimeSessionWindows,
    PurgingTrigger,
    EventTimeTrigger,
)

GAP = 10
LATE = OutputTag("late")


class ItemCounts(CountMinSketchAggregate):
    """Unit-weight Count-Min over field 1 of a (key, item) row."""

    def __init__(self):
        super().__init__(2, 64, unit_weights=True, queries=(1, 2, 3))

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def emit_row(key, window, vals):
    return [(key, window.start, window.end, *(int(v) for v in vals[0]))]


def chunk(rows, watermark):
    """rows: (key, item, ts) in arrival order."""
    keys, items, ts = (np.array(c, np.int64) for c in zip(*rows))
    return keys, items, ts, watermark


# every way a session can change, a stream each; gap 10
STREAMS = {
    "in_order_extension": [
        chunk([(1, 1, 0), (2, 2, 1), (1, 1, 4), (1, 3, 9)], -1),
        chunk([(1, 2, 15), (2, 2, 8), (1, 1, 25)], 5),
        chunk([(3, 1, 30), (1, 1, 33)], 40),
    ],
    "growth_at_the_front": [
        # an earlier event arrives in a later batch ...
        chunk([(1, 1, 20), (2, 1, 22)], -1),
        chunk([(1, 2, 14), (2, 3, 30), (1, 1, 11)], 0),
        # ... and later in the same batch
        chunk([(5, 1, 50), (5, 2, 44), (5, 2, 41), (6, 1, 45)], 20),
    ],
    "bridge_merges_two_state_windows": [
        chunk([(1, 1, 0), (1, 2, 18), (2, 1, 0)], -1),
        chunk([(2, 2, 19), (1, 3, 9)], -1),          # 9 bridges 0 and 18
        chunk([(2, 1, 10), (3, 1, 5), (3, 2, 25), (3, 3, 15)], 0),
    ],
    "bridge_by_the_later_of_two_rows": [
        # key 1: [0,10) and [25,35) open; then 10 (abuts the first)
        # and 18 (joins both): the second row merges two state windows
        chunk([(1, 1, 0), (1, 2, 25)], -1),
        chunk([(1, 3, 10), (1, 1, 18), (1, 1, 60)], -1),
        # key 2: the later window is older in the mapping
        chunk([(2, 2, 25), (2, 1, 0)], -1),
        chunk([(2, 3, 5), (2, 3, 12), (2, 3, 16)], 3),
    ],
    "two_proto_sessions_of_one_key_in_one_batch": [
        chunk([(1, 1, 0), (1, 2, 3), (1, 1, 40), (1, 3, 45), (2, 2, 7)], -1),
        chunk([(1, 1, 50), (1, 2, 100), (1, 2, 8)], 12),
    ],
    "abutting_windows_merge": [
        chunk([(1, 1, 0), (1, 2, 10), (1, 3, 31), (1, 1, 21)], -1),
        chunk([(2, 1, 5), (2, 1, 15), (2, 1, 26)], -1),
        chunk([(2, 2, 36)], 20),
    ],
    "due_rows": [
        chunk([(1, 1, 30), (2, 2, 30)], 25),
        # key 1: own window [18,28) is due but joins the live session;
        # key 3: own and merged window due: dropped; key 2: a run whose
        # first row is late and whose second opens a session
        chunk([(1, 2, 22), (3, 1, 10), (2, 3, 5), (3, 2, 16),
               (4, 1, 14), (4, 2, 19)], 25),
        chunk([(3, 3, 15), (3, 1, 17), (1, 1, 100)], 27),
    ],
    "same_timestamps": [
        chunk([(1, 1, 5), (1, 2, 5), (2, 1, 5), (1, 3, 5), (2, 2, 15)], -1),
        chunk([(1, 1, 15), (2, 3, 15), (1, 1, 15)], 0),
    ],
}


def random_stream(seed, chunks=6, rows=160, keys=9, disorder=25):
    rng = np.random.default_rng(seed)
    out = []
    for c in range(chunks):
        k = rng.integers(0, keys, rows)
        item = rng.integers(0, 6, rows)
        ts = rng.integers(max(0, c * 40 - disorder), c * 40 + 40, rows)
        out.append((k.astype(np.int64), item.astype(np.int64),
                    ts.astype(np.int64), c * 40 - 12))
    return out


for _seed in (5, 6, 7):
    STREAMS[f"random_{_seed}"] = random_stream(_seed)
STREAMS["random_sparse"] = random_stream(11, rows=40, keys=5, disorder=40)
# watermarks that run ahead of a third of the rows
STREAMS["random_late"] = [
    (k, i, ts, wm + 30) for k, i, ts, wm in random_stream(
        12, rows=60, keys=12, disorder=40)]
STREAMS["random_late_sparse"] = [
    (k, i, ts, wm + 45) for k, i, ts, wm in random_stream(
        13, rows=24, keys=16, disorder=40)]


def observe(h):
    """What the two paths must agree on besides their output."""
    op = h.operator
    table = op.keyed_backend._tables.get(WindowOperator.MAPPING_STATE_NAME)
    mappings = {}
    if table is not None:
        for key, mapping in table.by_namespace.get(VOID_NAMESPACE,
                                                   {}).items():
            mappings[key] = list(mapping.items())
            assert all(type(w) is tuple and type(sw) is tuple
                       for w, sw in mappings[key])
    # the live timers in the order they would fire (the store's own
    # iteration order also says which (timestamp, window) run a timer
    # shares with other keys' timers that came and went: not state)
    timers = copy.deepcopy(op.timer_service._event).pop_runs(10 ** 18)
    return {"mappings": mappings,
            "timers": timers,
            "late": op.num_late_records_dropped,
            "side": [(r.value, r.timestamp)
                     for r in h.get_side_output(LATE)]}


def drive(stream, backend, batched, fires=None, late_tag=None,
          snapshot_after=None, restore_batched=None, final=10 ** 9):
    """The per-watermark outputs and the observations after every
    chunk.  `batched` False is the per-row reference: rows one at a
    time through process_element, per-timer fires.  `fires` (the
    operator's `batch_fires`) follows `batched` unless given."""

    def fresh(batched, fires):
        op = WindowOperator(EventTimeSessionWindows.with_gap(GAP),
                            AggregatingStateDescriptor("items", ItemCounts()),
                            window_function=emit_row,
                            late_data_tag=late_tag)
        op.batch_fires = batched if fires is None else fires
        h = OneInputStreamOperatorTestHarness(
            op, key_selector=lambda x: x[0], state_backend=backend)
        h.open()
        assert op._session_batches
        return h

    h = fresh(batched, fires)
    fired, seen = [], []
    for i, (keys, items, ts, wm) in enumerate(stream):
        batch = RecordBatch({"f0": keys, "f1": items}, ts=ts)
        if batched:
            h.process_batch(batch)
        else:
            for record in batch.to_records():
                h.process_element(record)
        h.process_watermark(wm)
        fired.append([(r.value, r.timestamp) for r in h.get_output()])
        h.clear_output()
        seen.append(observe(h))
        if snapshot_after == i:
            snap = h.snapshot()
            late = h.operator.num_late_records_dropped
            batched = restore_batched
            h = fresh(batched, None)
            h.initialize_state(snap)
            h.operator.num_late_records_dropped = late
    h.process_watermark(final)
    fired.append([(r.value, r.timestamp) for r in h.get_output()])
    seen.append(observe(h))
    assert h.operator.boxed_fallbacks == 0
    assert bool(h.operator.columnar_rows) is batched
    return fired, seen, h


@pytest.mark.parametrize("backend", ["heap", "tpu"])
@pytest.mark.parametrize("name", sorted(STREAMS))
def test_batched_sessions_bit_equal(name, backend):
    rows = drive(STREAMS[name], backend, batched=False, fires=False)
    batch = drive(STREAMS[name], backend, batched=True)
    assert sum(map(len, rows[0]))  # the stream fires sessions
    assert batch[0] == rows[0]
    assert batch[1] == rows[1]
    assert not batch[1][-1]["mappings"] and not batch[1][-1]["timers"]


@pytest.mark.parametrize("backend", ["heap", "tpu"])
@pytest.mark.parametrize("name", ["due_rows", "random_late",
                                  "random_late_sparse"])
def test_late_rows_reach_the_side_output_in_row_order(name, backend):
    rows = drive(STREAMS[name], backend, batched=False, fires=False,
                 late_tag=LATE)
    batch = drive(STREAMS[name], backend, batched=True, late_tag=LATE)
    assert rows[1][-1]["side"] and not rows[1][-1]["late"]
    assert batch[0] == rows[0]
    assert batch[1] == rows[1]


@pytest.mark.parametrize("backend", ["heap", "tpu"])
def test_late_rows_are_counted_without_a_side_output(backend):
    rows = drive(STREAMS["due_rows"], backend, batched=False, fires=False)
    batch = drive(STREAMS["due_rows"], backend, batched=True)
    assert rows[1][-1]["late"] == batch[1][-1]["late"] == 5
    # key 3's rows at 10, 16 and 15, key 2's at 5, key 4's at 14; key
    # 1's at 22 joined its session although its own window was due
    assert ((1, 22, 40, 2, 1, 1, 0), 39) in batch[0][-1] + batch[0][-2]


@pytest.mark.parametrize("backend", ["heap", "tpu"])
@pytest.mark.parametrize("name", ["random_6", "growth_at_the_front"])
def test_batched_ingest_with_the_per_timer_drain(name, backend):
    """`batch_fires = False` still pins the per-timer drain, over
    timers the batched ingest registered."""
    rows = drive(STREAMS[name], backend, batched=False, fires=False)
    mixed = drive(STREAMS[name], backend, batched=True, fires=False)
    assert mixed[0] == rows[0] and mixed[1] == rows[1]
    assert mixed[2].operator.timers_swept == 0


@pytest.mark.parametrize("backend", ["heap", "tpu"])
@pytest.mark.parametrize("first", ["rows", "batch"])
def test_a_checkpoint_crosses_between_the_paths(first, backend):
    """A snapshot taken on one path restores on the other: the
    mapping state and the timers are the same state.  The reference
    restores too (a restore rebuilds the timer store: compare
    restore-to-restore)."""
    stream = STREAMS["random_7"]
    taken_batched = first == "batch"
    crossed = drive(stream, backend, batched=taken_batched, snapshot_after=2,
                    restore_batched=not taken_batched)
    reference = drive(stream, backend, batched=False, fires=False,
                      snapshot_after=2, restore_batched=False)
    assert crossed[1][2]["mappings"] and crossed[1][2]["timers"]
    assert crossed[0] == reference[0]
    assert crossed[1] == reference[1]


@pytest.mark.parametrize("batched", [False, True])
def test_mappings_of_an_older_snapshot_load_on_both_paths(batched):
    """Before PR 37 the per-row path persisted the windows themselves,
    not their namespaces: such a mapping still loads, and is written
    back as tuples."""
    from flink_tpu.streaming.windowing import TimeWindow
    stream = STREAMS["in_order_extension"]
    want = drive(stream, "heap", batched=batched)[0]

    def with_old_mappings(h):
        table = h.operator.keyed_backend._tables[
            WindowOperator.MAPPING_STATE_NAME].by_namespace[VOID_NAMESPACE]
        for key, mapping in table.items():
            if type(next(iter(mapping))) is tuple:
                table[key] = {TimeWindow(*w): TimeWindow(*sw)
                              for w, sw in mapping.items()}

    op = WindowOperator(EventTimeSessionWindows.with_gap(GAP),
                        AggregatingStateDescriptor("items", ItemCounts()),
                        window_function=emit_row)
    op.batch_fires = batched
    h = OneInputStreamOperatorTestHarness(
        op, key_selector=lambda x: x[0], state_backend="heap")
    h.open()
    fired = []
    for keys, items, ts, wm in stream:
        batch = RecordBatch({"f0": keys, "f1": items}, ts=ts)
        if batched:
            h.process_batch(batch)
        else:
            for record in batch.to_records():
                h.process_element(record)
        with_old_mappings(h)
        h.process_watermark(wm)
        fired.append([(r.value, r.timestamp) for r in h.get_output()])
        h.clear_output()
        with_old_mappings(h)
    h.process_watermark(10 ** 9)
    fired.append([(r.value, r.timestamp) for r in h.get_output()])
    assert fired == want


def test_a_merge_promotes_a_spilled_source():
    """Under a budget of 8 device slots the two sessions of key 1 go
    cold under two busy keys and are evicted by ten fresh keys'
    sessions; the row that bridges the two merges state windows that
    live in host RAM."""
    conf = Configuration()
    conf.set("state.backend", "tpu")
    conf.set("state.backend.tpu.max-device-slots", 8)
    conf.set("state.backend.tpu.microbatch-size", 2)
    stream = [
        chunk([(1, 1, 0), (1, 2, 3)], -1),
        chunk([(1, 3, 18)], -1),
        chunk([(k, 1, 40 + t % 8) for t in range(16) for k in (10, 12)], -1),
        *[chunk([(k, 1, 40 + k), (k, 2, 41 + k)], -1)
          for k in range(14, 34, 2)],
        chunk([(1, 1, 9), (50, 1, 9)], -1),
        chunk([(1, 2, 30)], 35),
    ]
    STATE_STATS.reset()
    rows = drive(stream, conf, batched=False, fires=False)
    STATE_STATS.reset()
    batch = drive(stream, conf, batched=True)
    state = batch[2].operator.window_state
    assert state.evictions and state.promotions
    assert STATE_STATS.merged_rows == 1
    assert batch[0] == rows[0] and batch[1] == rows[1]
    assert ((1, 0, 28, 4, 2, 1, 1), 27) in sum(batch[0], [])


def test_the_batched_path_probes_a_merge_target_and_no_other_key():
    stream = STREAMS["bridge_merges_two_state_windows"]
    STATE_STATS.reset()
    _, _, h = drive(stream, "tpu", batched=True)
    op = h.operator
    # one per merge that folded a slot: its target's, through _slot_for
    assert STATE_STATS.per_key_probe_rows == 2
    # keys 1 and 2 bridge windows of earlier batches; key 3's three
    # rows of one batch make a state window that never gets a slot
    assert STATE_STATS.merged_rows == 2
    assert op.columnar_rows == sum(len(c[0]) for c in stream)
    assert op.sessions_opened + op.sessions_extended == op.columnar_rows
    assert op.session_windows_merged == 3
    assert op.timers_swept and op.fire_rows_direct == op.timers_swept


SHAPES = {
    "dynamic_gap": dict(assigner=DynamicEventTimeSessionWindows(
        lambda v: GAP)),
    "processing_time": dict(assigner=ProcessingTimeSessionWindows(GAP)),
    "custom_trigger": dict(trigger=PurgingTrigger.of(EventTimeTrigger())),
    "allowed_lateness": dict(allowed_lateness=5),
    "raw_elements": dict(descriptor=ListStateDescriptor("rows")),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_other_merging_shapes_keep_the_per_row_path(shape):
    kwargs = dict(assigner=EventTimeSessionWindows.with_gap(GAP),
                  descriptor=AggregatingStateDescriptor("items",
                                                        ItemCounts()),
                  trigger=None, allowed_lateness=0)
    kwargs.update(SHAPES[shape])
    op = WindowOperator(kwargs["assigner"], kwargs["descriptor"],
                        window_function=None, trigger=kwargs["trigger"],
                        allowed_lateness=kwargs["allowed_lateness"])
    h = OneInputStreamOperatorTestHarness(
        op, key_selector=lambda x: x[0], state_backend="heap")
    h.open()
    assert op._batch_demote_reason and "per-row" in op._batch_demote_reason
    assert op._session_batches is False


def test_plan_takes_whole_runs_where_it_may_and_rows_where_it_must():
    """In-order keys go in run by run; a key whose rows came out of
    order, a run that bridges two open windows and a run whose first
    row is late go in row by row."""
    keys = [1, 1, 2, 1, 3, 3, 4, 4, 4]
    ts = np.array([0, 4, 7, 30, 9, 2, 12, 22, 31], np.int64)
    stored = {4: {(0, 15): (0, 10), (40, 55): (40, 50)}}
    plan = SessionBatchPlan(GAP, -1).run(
        keys, ts, lambda ks: [stored.get(k) for k in ks])
    assert plan.runs == 5   # key 1: two, keys 2, 3, 4: one each
    # key 1's and 2's runs are units 0..2; key 3 (out of order) and
    # key 4 (bridges (0,15) and (40,55)) get a unit a row
    assert len(plan.unit_sw) == 5 + 2 + 3
    # the row at 31 joins (40,55), the older entry of the mapping, and
    # (0,32), which the rows before it re-entered at its end: the
    # older one's state window survives, as under add_window
    assert plan.state_windows().tolist() == [
        (0, 10), (0, 10), (7, 17), (30, 40), (9, 19), (9, 19),
        (40, 50), (40, 50), (40, 50)]
    assert plan.merges == [(4, (40, 50), [(0, 10)])]
    assert dict(zip(plan.changed_keys, plan.changed_maps))[4] == {
        (0, 55): (40, 50)}
    assert (plan.opened, plan.extended, plan.merged) == (4, 5, 1)
    assert [(row, w) for row, w, _ in plan.registered] == [
        (1, (0, 14)), (2, (7, 17)), (3, (30, 40)), (5, (2, 19)),
        (8, (0, 55))]
    assert sorted(plan.deleted) == [((0, 15), 4), ((40, 55), 4)]

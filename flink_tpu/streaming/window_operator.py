"""WindowOperator: windowed keyed aggregation with triggers, allowed
lateness, and merging session windows.

Re-designs flink-streaming-java/.../runtime/operators/windowing/
WindowOperator.java:97 — processElement :291-421, onEventTime :424,
onProcessingTime :472, emitWindowContents :544, cleanup timers
:596-626, lateness :576-589 — and MergingWindowSet.java:54,119,156.
Window state is keyed state under namespace = window
(WindowOperator.java:387), so ALL backends (heap and TPU) serve it
unchanged; on the TPU backend a window-fire is a device gather and
`add` is a micro-batched scatter.

EvictingWindowOperator keeps the raw elements in a ListState and runs
the Evictor before/after the window function
(ref: EvictingWindowOperator.java).
"""

from __future__ import annotations

import abc
import itertools
import operator
from typing import Any, Iterable, List, Optional, Tuple

import numpy as np

from flink_tpu.core.state import (
    AggregatingStateDescriptor,
    ListStateDescriptor,
    ReducingStateDescriptor,
    StateDescriptor,
    ValueStateDescriptor,
)
from flink_tpu.runtime.device_stats import TELEMETRY
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu.state.introspect import INTROSPECTION
from flink_tpu.streaming.elements import MAX_TIMESTAMP, StreamRecord
from flink_tpu.streaming.operators import (
    AbstractUdfStreamOperator,
    Output,
    OutputTag,
    TimestampedCollector,
)
from flink_tpu.streaming.windowing import (
    EventTimeSessionWindows,
    EventTimeTrigger,
    SlidingEventTimeWindows,
    Trigger,
    TriggerContext,
    TriggerResult,
    TumblingEventTimeWindows,
    Window,
    WindowAssigner,
)


# ---------------------------------------------------------------------
# Window functions (ref: runtime/operators/windowing/functions/)
# ---------------------------------------------------------------------

class ProcessWindowFunction(abc.ABC):
    """(ref: ProcessWindowFunction.java) — full access to window
    metadata; elements is the window contents iterable."""

    @abc.abstractmethod
    def process(self, key, context: "WindowContext", elements: Iterable, out) -> None:
        ...

    def clear(self, context: "WindowContext") -> None:  # noqa: B027
        pass


class WindowFunction(abc.ABC):
    """(ref: WindowFunction.java) — apply(key, window, inputs, out)."""

    @abc.abstractmethod
    def apply(self, key, window, inputs: Iterable, out) -> None:
        ...


class PassThroughWindowFunction(WindowFunction):
    """Emit the (single) pre-aggregated value
    (ref: PassThroughWindowFunction.java)."""

    def apply(self, key, window, inputs, out):
        out.collect(inputs)


class WindowContext:
    """(ref: ProcessWindowFunction.Context)"""

    def __init__(self, window, op: "WindowOperator"):
        self.window = window
        self._op = op

    def current_processing_time(self) -> int:
        return self._op.processing_time_service.get_current_processing_time()

    def current_watermark(self) -> int:
        return self._op.timer_service.current_watermark

    def window_state(self, descriptor: StateDescriptor):
        """Per-(key, window) state."""
        return self._op.keyed_backend.get_partitioned_state(
            self._op._namespace_of(self.window), descriptor)

    def global_state(self, descriptor: StateDescriptor):
        """Per-key state shared across windows."""
        from flink_tpu.state.backend import VOID_NAMESPACE
        return self._op.keyed_backend.get_partitioned_state(VOID_NAMESPACE, descriptor)

    def output(self, tag: OutputTag, value) -> None:
        self._op.output.collect_side(
            tag, StreamRecord(value, self.window.max_timestamp()))


class _InternalWindowFunction:
    """Normalizes the three user-function shapes to one call."""

    def __init__(self, fn, single_value: bool):
        self.fn = fn
        #: True when window contents are a single pre-aggregated value
        self.single_value = single_value
        # the function's shape, decided once: the two classes are
        # abc.ABCs, whose isinstance is too slow to ask per fired key
        self._is_process = isinstance(fn, ProcessWindowFunction)
        self._is_window_fn = isinstance(fn, WindowFunction)
        #: True for the two shapes that are handed a collector (and,
        #: for ProcessWindowFunction, a context that reaches keyed
        #: state).  False for a plain callable or None: all such a
        #: function sees is (key, window, elements), so a batched fire
        #: may call it with no key context set and take its rows as
        #: they come (_FireBufferOutput.emit_fired)
        self.takes_collector = self._is_process or self._is_window_fn

    def process(self, key, window, op, contents, collector) -> None:
        if self.fn is None:
            collector.collect(contents)
            return
        elements = [contents] if self.single_value else contents
        if self._is_process:
            self.fn.process(key, WindowContext(window, op), elements, collector)
        elif self._is_window_fn:
            self.fn.apply(key, window, elements, collector)
        else:  # plain callable(key, window, elements) -> iterable
            result = self.fn(key, window, elements)
            if result is not None:
                for v in result:
                    collector.collect(v)

    def clear(self, key, window, op) -> None:
        if self._is_process:
            self.fn.clear(WindowContext(window, op))


# ---------------------------------------------------------------------
# MergingWindowSet (ref: MergingWindowSet.java)
# ---------------------------------------------------------------------

class MergingWindowSet:
    """Per-key mapping window → state window for merging (session)
    assigners.  When windows merge, one pre-existing state window is
    kept as the merge target and the others' state is folded into it —
    so state never has to be re-namespaced (ref: MergingWindowSet.java:54)."""

    def __init__(self, mapping_state, window_type=None):
        #: ValueState holding {window_namespace: state_window_namespace}
        #: (plain tuples: of all a job's long-lived objects the open
        #: sessions' mappings are the most, and tuples of ints are none
        #: of the cyclic collector's business); a snapshot from before
        #: the mappings were persisted so holds the windows themselves
        self._mapping_state = mapping_state
        m = mapping_state.value()
        self.mapping: dict = {}
        if m:
            if window_type is None or isinstance(next(iter(m)), Window):
                self.mapping = dict(m)
            else:
                make = window_type.from_namespace
                self.mapping = {make(w): make(sw) for w, sw in m.items()}

    def persist(self) -> None:
        if self.mapping:
            self._mapping_state.update(
                {w.to_namespace(): sw.to_namespace()
                 for w, sw in self.mapping.items()})
        else:
            self._mapping_state.clear()

    def get_state_window(self, window):
        return self.mapping.get(window)

    def retire_window(self, window) -> None:
        if window in self.mapping:
            del self.mapping[window]

    def add_window(self, new_window, merge_callback):
        """Add `new_window`, eagerly merging all transitively
        intersecting windows.  merge_callback(merge_result,
        merged_windows, state_window_result, merged_state_windows) is
        invoked when a merge happens (ref: addWindow :119)."""
        windows = list(self.mapping.keys()) + [new_window]
        merge_result = new_window
        to_merge = []
        changed = True
        while changed:
            changed = False
            for w in windows:
                if w is merge_result or w in to_merge:
                    continue
                if w.intersects(merge_result):
                    merge_result = merge_result.cover(w)
                    to_merge.append(w)
                    changed = True
        # to_merge = pre-existing windows (and possibly none) swallowed
        to_merge_existing = [w for w in to_merge if w in self.mapping]
        if not to_merge_existing and new_window not in self.mapping:
            # brand-new non-overlapping window: its own state window
            self.mapping[new_window] = new_window
            return new_window
        if not to_merge_existing:
            return new_window  # exact duplicate of an existing window
        # keep the first existing window's state window as target
        state_window_result = self.mapping[to_merge_existing[0]]
        merged_state_windows = []
        for w in to_merge_existing:
            sw = self.mapping.pop(w)
            if sw != state_window_result:
                merged_state_windows.append(sw)
        self.mapping[merge_result] = state_window_result
        merged_windows = to_merge_existing + (
            [new_window] if new_window not in to_merge_existing else [])
        # don't fire the callback for a no-op (new window already covered
        # by one existing window and nothing else merged)
        if len(to_merge_existing) > 1 or (
                merge_result != to_merge_existing[0]) or merged_state_windows:
            if merge_result not in to_merge_existing or merged_state_windows:
                merge_callback(merge_result, merged_windows,
                               state_window_result, merged_state_windows)
        return merge_result


class SessionBatchPlan:
    """:class:`MergingWindowSet` worked for a whole batch: what the
    per-row path does to the window -> state-window mappings of the
    batch's keys, without touching state or timers, so that the
    caller can write the rows with one ``add_batch``, fold the merged
    state windows with one ``merge_namespaces_batch`` and move the
    timers in bulk.  Windows are ``(start, end)`` tuples here.

    The rows are sorted by (key, timestamp), stably, and cut into runs
    where the key changes or two timestamps lie more than `gap` apart
    (``TimeWindow.intersects`` is inclusive: abutting windows merge);
    a run is the proto-session ``[first ts, last ts + gap)``.  A run
    goes into its key's mapping as ONE step where that provably equals
    its rows going in one at a time:

    - the key's rows arrived in timestamp order (so the run's rows
      came one after the other, each intersecting the window the rows
      before it made), and
    - the run touches no open window, or exactly one and that one
      intersects the run's FIRST proto-window (so every row merges
      into the same growing window, whose state window is the target
      from the first row on).

    Every other row goes in by itself, in arrival order: a key whose
    rows arrived out of order (its session may grow at the front, and
    a row may bridge two windows that rows after it made), a run that
    bridges two open windows (their state windows merge, and which
    one survives depends on the mapping's order at that row), a run
    whose first rows are late.  A step is ``add_window`` on tuples:
    the windows it intersects leave the mapping, their cover enters it
    at the END under the state window of the first of them in the
    mapping's order.

    What the per-row path does and this plan leaves out is what a
    later row of the batch undoes: a state window that is made and
    merged away inside the batch never gets a slot (its rows are
    written under the state window that survives), a timer that is
    registered and deleted inside the batch is never registered.  A
    surviving window's timer is registered in the place of the LAST
    row that changed the window, which is where the per-row path
    registered the one that survives (``EventTimeTrigger.on_merge``
    registers the merged window's timer anew at every change)."""

    __slots__ = ("gap", "watermark", "unit_sw", "unit_of_row", "merges",
                 "deleted", "registered", "changed_keys", "changed_maps",
                 "runs", "opened", "extended", "merged")

    def __init__(self, gap: int, watermark: int):
        self.gap = gap
        self.watermark = watermark
        #: unit (a run, or a row that went in by itself) -> state
        #: window, None where the rows are late
        self.unit_sw: list = []
        #: int64[n]: the unit of every row
        self.unit_of_row = None
        #: (key, target state window, [source state windows]) of the
        #: merges of two state windows that hold state from before
        self.merges: list = []
        #: timers to delete: (window, key); to register: (row that
        #: places it, window, key)
        self.deleted: list = []
        self.registered: list = []
        #: keys whose mapping changed (or was re-ordered), with it
        self.changed_keys: list = []
        self.changed_maps: list = []
        self.runs = self.opened = self.extended = self.merged = 0

    def state_windows(self) -> np.ndarray:
        """object[n]: every row's state window, None for a late row."""
        from flink_tpu.state.slot_index import object_column
        units = object_column(self.unit_sw, len(self.unit_sw))
        return units[self.unit_of_row]

    def run(self, keys: list, ts: np.ndarray, load) -> "SessionBatchPlan":
        """Plan the batch; ``load(distinct keys)`` returns each key's
        stored mapping ``{(start, end): (start, end)}`` or None."""
        n = len(keys)
        gap = self.gap
        # rows by (key, timestamp), stably; a key's code is the row it
        # first appeared in, so keys sort in order of first appearance
        # and `distinct` lists them in that order
        codebook: dict = {}
        codes = np.fromiter(
            map(codebook.setdefault, keys, itertools.count()), np.int64, n)
        distinct = list(codebook)
        order = np.lexsort((ts, codes))
        sorted_ts = ts[order]
        new_key = np.ones(n, bool)
        new_key[1:] = np.diff(codes[order]) != 0
        cut = new_key.copy()
        cut[1:] |= np.diff(sorted_ts) > gap
        run_first = np.flatnonzero(cut)
        run_end = np.append(run_first[1:], n)
        nruns = self.runs = len(run_first)
        self.unit_of_row = np.empty(n, np.int64)
        self.unit_of_row[order] = np.cumsum(cut) - 1
        self.unit_sw = [None] * nruns
        # keys with a row that arrived before a row of an earlier
        # timestamp: all their rows go in one at a time
        stepped_back = np.zeros(n, bool)
        stepped_back[1:] = (np.diff(order) < 0) & ~new_key[1:]
        key_of_sorted = np.cumsum(new_key) - 1
        unordered = np.zeros(len(distinct), bool)
        unordered[key_of_sorted[stepped_back]] = True
        # the row that places a run's timer where its window's end
        # moves last: the first to carry the run's last timestamp
        group = cut.copy()
        group[1:] |= np.diff(sorted_ts) != 0
        group_first = np.flatnonzero(group)
        end_row = order[group_first[
            np.searchsorted(group_first, run_end - 1, side="right") - 1]]
        key_first = np.flatnonzero(new_key)
        key_runs = np.searchsorted(run_first, np.append(key_first, n))
        first_ts = sorted_ts[run_first].tolist()
        last_ts = sorted_ts[run_end - 1].tolist()
        start_row = order[run_first].tolist()
        end_row = end_row.tolist()
        run_first, run_end = run_first.tolist(), run_end.tolist()
        key_runs = key_runs.tolist()
        unordered = unordered.tolist()
        rows_by_themselves: list = []  # (row, unit)
        for j, (key, stored) in enumerate(zip(distinct, load(distinct))):
            state = _KeySessions(self, key, stored)
            lo, hi = key_runs[j], key_runs[j + 1]
            if unordered[j]:
                rows = np.sort(order[run_first[lo]:run_end[hi - 1]]).tolist()
                for i, t in zip(rows, ts[rows].tolist()):
                    rows_by_themselves.append((i, state.row(i, t)))
            else:
                for r in range(lo, hi):
                    if state.whole_run(r, first_ts[r], last_ts[r],
                                       run_end[r] - run_first[r],
                                       start_row[r], end_row[r]):
                        continue
                    rows = order[run_first[r]:run_end[r]]
                    for i, t in zip(rows.tolist(), ts[rows].tolist()):
                        rows_by_themselves.append((i, state.row(i, t)))
            state.close()
        if rows_by_themselves:
            rows, units = zip(*rows_by_themselves)
            self.unit_of_row[list(rows)] = units
        self.registered.sort(key=operator.itemgetter(0))
        return self


class _KeySessions:
    """One key's working mapping inside a :class:`SessionBatchPlan`."""

    __slots__ = ("plan", "key", "stored", "mapping", "alias", "placed",
                 "units")

    def __init__(self, plan: SessionBatchPlan, key, stored):
        self.plan = plan
        self.key = key
        self.stored = stored or {}
        #: the working copy, made at the first change
        self.mapping = None
        #: state window merged away -> the one it was folded into
        self.alias = None
        #: window made or changed here -> the row that places its timer
        self.placed: dict = {}
        #: the units of this key's rows
        self.units: list = []

    def _step(self, first: int, end: int, proto_end: int, start_row: int,
              end_row: int, rows: int, whole_run: bool, unit: int) -> bool:
        """``add_window`` of the span ``[first, end)`` whose first
        proto-window ends at `proto_end`; `end_row` is the row that
        moves a window's end where the span does, `start_row` the one
        that moves its start.  False (and nothing done) where a
        `whole_run` may not go in as one step."""
        plan = self.plan
        mapping = self.mapping if self.mapping is not None else self.stored
        hits = [w for w in mapping if w[0] <= end and w[1] >= first]
        if not hits:
            if proto_end - 1 <= plan.watermark:
                if whole_run:
                    return False  # its first rows are late: row by row
                return True       # late, and opens nothing
            window, target = (first, end), (first, proto_end)
            plan.opened += 1
            plan.extended += rows - 1
        else:
            if whole_run and (len(hits) > 1 or hits[0][0] > proto_end):
                return False
            hit = hits[0]
            target = mapping[hit]
            if len(hits) == 1:
                hit_start, hit_end = hit
            else:
                hit_start = min(w[0] for w in hits)
                hit_end = max(w[1] for w in hits)
            window = (first if first < hit_start else hit_start,
                      end if end > hit_end else hit_end)
            if end <= hit_end:
                end_row = start_row  # the span moves a start at most
            plan.extended += rows
            plan.merged += len(hits) - 1
        if self.mapping is None:
            mapping = self.mapping = dict(self.stored)
        for w in hits:
            source = mapping.pop(w)
            if source != target:
                if self.alias is None:
                    self.alias = {}
                self.alias[source] = target
        mapping[window] = target
        if len(hits) != 1 or hits[0] != window:
            # new, or grown: the per-row path registers its timer anew
            # at the last row that changes it
            self.placed[window] = end_row
        plan.unit_sw[unit] = target
        self.units.append(unit)
        return True

    def whole_run(self, unit: int, first_ts: int, last_ts: int, rows: int,
                  start_row: int, end_row: int) -> bool:
        gap = self.plan.gap
        return self._step(first_ts, last_ts + gap, first_ts + gap,
                          start_row, end_row, rows, True, unit)

    def row(self, i: int, t: int) -> int:
        """Row `i` by itself, as a unit of its own; returns the unit."""
        plan = self.plan
        unit = len(plan.unit_sw)
        plan.unit_sw.append(None)
        self._step(t, t + plan.gap, t + plan.gap, i, i, 1, False, unit)
        return unit

    def close(self) -> None:
        """The key's rows are in: its merges, its timers, its mapping."""
        mapping = self.mapping
        if mapping is None:
            return  # every row late
        plan, key, stored = self.plan, self.key, self.stored
        alias = self.alias
        if alias:
            def survivor(sw):
                while sw in alias:
                    sw = alias[sw]
                return sw
            held = set(stored.values())  # state windows that hold state
            sources: dict = {}
            for sw in alias:
                if sw in held:
                    sources.setdefault(survivor(sw), []).append(sw)
            plan.merges.extend((key, target, merged)
                               for target, merged in sources.items())
            unit_sw = plan.unit_sw
            for unit in self.units:
                unit_sw[unit] = survivor(unit_sw[unit])
        placed = self.placed
        if len(stored) == 1 and len(mapping) == 1:
            # the usual key: one open session, which grew or did not
            (old,), (new,) = stored, mapping
            if old != new:
                plan.deleted.append((old, key))
                plan.registered.append((placed[new], new, key))
        else:
            for w in stored:
                if w not in mapping:
                    plan.deleted.append((w, key))
            for w in mapping:
                if w not in stored:
                    plan.registered.append((placed[w], w, key))
        plan.changed_keys.append(key)
        plan.changed_maps.append(mapping)


# ---------------------------------------------------------------------
# WindowOperator
# ---------------------------------------------------------------------

class _WindowTriggerContext(TriggerContext):
    """(ref: WindowOperator.Context :649)"""

    def __init__(self, op: "WindowOperator"):
        self._op = op
        self.window = None

    def register_event_time_timer(self, time):
        self._op.timer_service.register_event_time_timer(
            self._op._namespace_of(self.window), time)

    def register_processing_time_timer(self, time):
        self._op.timer_service.register_processing_time_timer(
            self._op._namespace_of(self.window), time)

    def delete_event_time_timer(self, time):
        self._op.timer_service.delete_event_time_timer(
            self._op._namespace_of(self.window), time)

    def delete_processing_time_timer(self, time):
        self._op.timer_service.delete_processing_time_timer(
            self._op._namespace_of(self.window), time)

    def get_current_watermark(self):
        return self._op.timer_service.current_watermark

    def get_current_processing_time(self):
        return self._op.processing_time_service.get_current_processing_time()

    def get_partitioned_state(self, descriptor):
        """Trigger state, scoped (key, window)."""
        return self._op.keyed_backend.get_partitioned_state(
            self._op._namespace_of(self.window), descriptor)

    #: set before trigger.on_merge fires (ref: OnMergeContext)
    merged_windows = ()

    def merge_partitioned_state(self, descriptor):
        """Merge per-window trigger state of the merged windows into
        the merge result's namespace (ref:
        Trigger.OnMergeContext#mergePartitionedState)."""
        state = self._op.keyed_backend.get_or_create_keyed_state(descriptor)
        if hasattr(state, "merge_namespaces"):
            state.merge_namespaces(
                self._op._namespace_of(self.window),
                [self._op._namespace_of(w) for w in self.merged_windows])


class _AssignerContext:
    """(ref: WindowAssigner.WindowAssignerContext)"""

    def __init__(self, op: "WindowOperator"):
        self._op = op

    def get_current_processing_time(self):
        return self._op.processing_time_service.get_current_processing_time()


class _FireBufferOutput(Output):
    """Captures the main-stream rows emitted during ONE batched fire
    sweep so they can be re-emitted as a single RecordBatch.  It holds
    row values, not records: one list of values and their timestamps
    as runs of ``[timestamp, row count]``, so a fire of one window
    holds one run however many keys fired.  Two ways in:
    :meth:`emit_fired`, the fire's loop for a window function that is
    a plain callable or None, which allocates nothing of the
    framework's per fired key; and :meth:`collect`, for the functions
    that write through a collector.  Watermarks, side outputs, and
    latency markers pass straight through to the real output (a side
    tag has no ordering contract against the main stream)."""

    __slots__ = ("_inner", "values", "runs", "_stamped", "rows_direct",
                 "rows_via_records")

    def __init__(self, inner: Output):
        self._inner = inner
        self.values: list = []
        self.runs: List[list] = []
        #: rows of `values` that a run covers already
        self._stamped = 0
        #: rows that came by emit_fired / wrapped in a StreamRecord
        self.rows_direct = 0
        self.rows_via_records = 0

    def _run(self, timestamp, n: int) -> None:
        runs = self.runs
        if runs and runs[-1][0] == timestamp:
            runs[-1][1] += n
        else:
            runs.append([timestamp, n])
        self._stamped += n

    def collect(self, record: StreamRecord) -> None:
        self.values.append(record.value)
        self._run(record.timestamp, 1)
        self.rows_via_records += 1

    def _stamp(self, timestamp) -> None:
        """The rows put into `values` since the last run leave with
        `timestamp`."""
        n = len(self.values) - self._stamped
        if n:
            self._run(timestamp, n)
            self.rows_direct += n

    def emit_fired(self, fn, keys, contents, wrap: bool, *, window=None,
                   windows=None) -> None:
        """The fire's loop over the fired columns: ``fn(key, window,
        elements)`` once per key, in order, its rows straight into
        `values`; with ``fn`` None the contents are the rows.  Give
        the fire's one `window`, or `windows` with every key's own (a
        sweep over several windows, a session engine): a run of
        timestamps then ends where the window changes.  `wrap` hands
        the function ``[contents]``, a fresh list per key, as a
        pre-aggregated window's single value."""
        values = self.values
        extend = values.extend
        if windows is None:
            if fn is None:
                extend(contents)
            elif wrap:
                for key, c in zip(keys, contents):
                    out = fn(key, window, [c])
                    if out is not None:
                        extend(out)
            else:
                for key, c in zip(keys, contents):
                    out = fn(key, window, c)
                    if out is not None:
                        extend(out)
            self._stamp(window.max_timestamp())
            return
        last = None
        for key, c, w in zip(keys, contents, windows):
            if w is not last:
                if last is not None:
                    self._stamp(last.max_timestamp())
                last = w
            if fn is None:
                values.append(c)
                continue
            out = fn(key, w, [c] if wrap else c)
            if out is not None:
                extend(out)
        if last is not None:
            self._stamp(last.max_timestamp())

    def book(self, op, phase) -> None:
        """The fire's two counts, on the phase of its loop and summed
        on the operator."""
        phase.set_attr("fire_rows_direct", self.rows_direct)
        phase.set_attr("fire_rows_via_records", self.rows_via_records)
        op.fire_rows_direct += self.rows_direct
        op.fire_rows_via_records += self.rows_via_records

    def flush(self) -> None:
        """Send the captured rows on: ONE RecordBatch when there is
        more than one and they columnarize, per-row records in the
        same order otherwise."""
        values = self.values
        if not values:
            return
        tracer = get_tracer()
        batch = None
        if len(values) > 1:
            from flink_tpu.streaming import columnar
            if columnar.PIPELINE_ENABLED:
                with tracer.phase("window.fire.columnarize"):
                    batch = columnar.batch_from_runs(values, self.runs)
        with tracer.phase("window.fire.downstream"):
            if batch is not None:
                self._inner.collect_batch(batch)
            else:
                collect = self._inner.collect
                rows = iter(values)
                for timestamp, n in self.runs:
                    for value in itertools.islice(rows, n):
                        collect(StreamRecord(value, timestamp))

    def release(self, *owned: list) -> None:
        """Free the fire's rows, and empty the `owned` lists the caller
        built for this fire alone (its key and result columns as
        python scalars).  A phase of its own: a million row tuples
        take as long to free as a tenth of the loop that made them."""
        with get_tracer().phase("window.fire.release"):
            self.values.clear()
            self.runs.clear()
            for column in owned:
                column.clear()

    def emit_watermark(self, watermark) -> None:
        self._inner.emit_watermark(watermark)

    def collect_side(self, tag: OutputTag, record: StreamRecord) -> None:
        self._inner.collect_side(tag, record)

    def emit_latency_marker(self, marker) -> None:
        self._inner.emit_latency_marker(marker)


def _run_columns(runs):
    """``[(namespace, keys)]`` end to end: the key column with the one
    namespace they share (a sweep of one run hands its key list on as
    it is), or with a namespace per key."""
    if len(runs) == 1:
        namespace, keys = runs[0]
        return keys, namespace, None
    chain = itertools.chain.from_iterable
    return (list(chain(keys for _, keys in runs)), None,
            list(chain(itertools.repeat(ns, len(keys)) for ns, keys in runs)))


class WindowOperator(AbstractUdfStreamOperator):
    """One-input keyed window operator."""

    MAPPING_STATE_NAME = "window-merge-mapping"

    def __init__(
        self,
        assigner: WindowAssigner,
        state_descriptor: StateDescriptor,
        window_function=None,
        trigger: Optional[Trigger] = None,
        allowed_lateness: int = 0,
        late_data_tag: Optional[OutputTag] = None,
        single_value_contents: Optional[bool] = None,
    ):
        super().__init__(window_function)
        self.assigner = assigner
        self.state_descriptor = state_descriptor
        self.trigger = trigger or assigner.get_default_trigger()
        if allowed_lateness < 0:
            raise ValueError("allowed lateness must be >= 0")
        if assigner.is_merging() and not self.trigger.can_merge():
            raise ValueError(
                f"trigger {self.trigger!r} cannot merge but assigner "
                f"{assigner!r} is a merging assigner")
        self.allowed_lateness = allowed_lateness
        self.late_data_tag = late_data_tag
        if single_value_contents is None:
            single_value_contents = isinstance(
                state_descriptor,
                (ReducingStateDescriptor, AggregatingStateDescriptor))
        self._internal_fn = _InternalWindowFunction(
            window_function, single_value_contents)
        # metrics (ref: numLateRecordsDropped, WindowOperator.java:138)
        self.num_late_records_dropped = 0
        #: rows the batched fires handed on with no StreamRecord of
        #: their own / wrapped in one by a collector on their way
        self.fire_rows_direct = 0
        self.fire_rows_via_records = 0
        #: timers the batched fires swept / the (timestamp, window)
        #: runs they came in: a tumbling window's fire is one run
        self.timers_swept = 0
        self.timer_runs = 0
        #: (batch, window) groups the vectorized ingest cut its batches
        #: into / the rows those groups handed to the state backend: a
        #: sliding assigner of size 10 x slide reads 10 rows per event
        self.windows_touched = 0
        self.window_rows = 0
        #: the batched session ingest: rows that opened a session, rows
        #: that joined an open one, windows a row's merge swallowed
        #: beyond the first it joined
        self.sessions_opened = 0
        self.sessions_extended = 0
        self.session_windows_merged = 0

    # ---- lifecycle --------------------------------------------------
    def open(self):
        super().open()
        # structural demotions, known AOT: custom triggers and
        # evictors are inherently per-row; plain tumbling/sliding
        # event-time windows and static-gap event-time sessions with
        # their default trigger take the vectorized process_batch path
        # (the columnar.ratio gauge and linter FT184 surface the reason)
        self._batch_demote_reason = self._batch_eligibility()
        #: the batched MergingWindowSet is this operator's batch path
        self._session_batches = (self._batch_demote_reason is None
                                 and self.assigner.is_merging())
        self.columnar_fallback_reason = self._batch_demote_reason
        self._emit_batch_hist = None
        if self.metrics is not None:
            # eager so monitoring sees the zero (ref: the counter is
            # constructed in WindowOperator.open, not on first drop);
            # reset = fresh execution attempt (restart replays must not
            # accumulate into the previous attempt's count)
            self.metrics.counter("numLateRecordsDropped").count = 0
            self._emit_batch_hist = self.metrics.histogram("emitBatchSize")
            self.metrics.gauge("windowRowsPerRow", self._window_rows_per_row)
        self.window_state = self.keyed_backend.get_or_create_keyed_state(
            self.state_descriptor)
        self.trigger_ctx = _WindowTriggerContext(self)
        self.assigner_ctx = _AssignerContext(self)
        self.collector = TimestampedCollector(self.output)
        if self.assigner.is_merging():
            self._mapping_desc = ValueStateDescriptor(self.MAPPING_STATE_NAME)
            # a key's mapping is written as a new dict of tuples
            # (`MergingWindowSet.persist`, `_store_mappings`; a reader
            # copies before it changes one)
            self._mapping_desc.copy_on_write = True

    # namespace encoding: window -> hashable tuple (state namespaces)
    def _namespace_of(self, window):
        return window.to_namespace()

    def _state_value(self, record: StreamRecord):
        """What goes into window state for one record; the evicting
        variant stores (timestamp, value) pairs."""
        return record.value

    # ---- element path (ref: processElement :291-421) ----------------
    def process_element(self, record: StreamRecord):
        windows = self.assigner.assign_windows(
            record.value, record.timestamp, self.assigner_ctx)
        skipped = True
        if self.assigner.is_merging():
            skipped = self._process_merging(record, windows, skipped)
        else:
            for window in windows:
                if self._is_window_late(window):
                    continue
                skipped = False
                ns = self._namespace_of(window)
                self.window_state.set_current_namespace(ns)
                self.window_state.add(self._state_value(record))
                if INTROSPECTION.enabled:
                    INTROSPECTION.note_row(
                        self.state_descriptor.name,
                        self.keyed_backend.current_key,
                        self.keyed_backend.max_parallelism)
                self.trigger_ctx.window = window
                result = self.trigger.on_element(
                    record.value, record.timestamp, window, self.trigger_ctx)
                self._react(result, window)
                self._register_cleanup_timer(window)
        if skipped and self._is_element_late(record):
            if self.late_data_tag is not None:
                self.output.collect_side(self.late_data_tag, record)
            else:
                self.num_late_records_dropped += 1
                if self.metrics is not None:
                    self.metrics.counter("numLateRecordsDropped").inc()

    def _window_rows_per_row(self):
        """State rows written per columnar row taken in: the fan-out
        of the window assigner (1 tumbling, size / slide sliding)."""
        if not self.columnar_rows:
            return None  # never saw a batch: undefined
        return self.window_rows / self.columnar_rows

    # ---- batch path -------------------------------------------------
    def _batch_eligibility(self) -> Optional[str]:
        """Structural reason this operator must take the per-row path,
        or None when process_batch can vectorize.  Called at open();
        uses only constructor state."""
        if self.assigner.is_merging():
            return self._session_batch_eligibility()
        if not isinstance(self.assigner,
                          (TumblingEventTimeWindows, SlidingEventTimeWindows)):
            return (f"no vectorized assignment for "
                    f"{type(self.assigner).__name__}")
        if type(self.trigger) is not type(self.assigner.get_default_trigger()):
            return (f"custom trigger {type(self.trigger).__name__} "
                    f"is per-row")
        return None

    def _session_batch_eligibility(self) -> Optional[str]:
        """A merging assigner takes batches as event-time sessions of
        a static gap under the default trigger with no lateness, over
        pre-aggregated state: the shape `SessionBatchPlan` is exact
        for.  A dynamic gap, processing time, a custom trigger (it
        may fire per element, or keep state per window), lateness (a
        merged window may be due and fire row by row) and raw element
        lists (a merge concatenates them in the order the rows came)
        keep the per-row path."""
        if type(self.assigner) is not EventTimeSessionWindows:
            return "merging window assigner is per-row"
        if type(self.trigger) is not EventTimeTrigger:
            return (f"custom trigger {type(self.trigger).__name__} "
                    f"is per-row")
        if self.allowed_lateness:
            return "session windows with allowed lateness are per-row"
        if not isinstance(self.state_descriptor,
                          (ReducingStateDescriptor,
                           AggregatingStateDescriptor)):
            return "session windows over raw elements are per-row"
        return None

    def _value_column(self, batch, n: int):
        """The value column for a device state whose aggregate
        extracts by column: it feeds the scatter as it is
        (``pre_extracted=True``).  None where the rows have to be
        boxed."""
        agg = getattr(self.window_state, "agg", None)
        if agg is not None and hasattr(agg, "extract_column"):
            c = agg.extract_column(batch.value_arrays())
            if isinstance(c, np.ndarray) and c.ndim == 1 and len(c) == n:
                return c
        return None

    def _drop_late(self, batch, values, ts, late: np.ndarray) -> None:
        """Rows `late` (indexes, in row order) of a batch are late: to
        the late side output, or counted, as process_element does."""
        if self.late_data_tag is not None:
            if values is None:
                values = batch.row_values()
            tlist = ts.tolist()
            for i in late.tolist():
                self.output.collect_side(
                    self.late_data_tag, StreamRecord(values[i], tlist[i]))
        else:
            cnt = int(late.size)
            self.num_late_records_dropped += cnt
            if self.metrics is not None:
                self.metrics.counter("numLateRecordsDropped").inc(cnt)

    def _batch_keys(self, batch, values) -> list:
        """Key column for a batch as a python list — bit-identical to
        what set_key_context would have extracted per row (same idiom
        as the generic engine's _batch_keys)."""
        from flink_tpu.streaming.columnar import field_key_column
        sel = self.key_selector
        col = field_key_column(sel, batch)
        if col is not None:
            return col.tolist()
        return [sel.get_key(v) for v in values]

    def process_batch(self, batch) -> None:
        """Columnar ingest: assign tumbling/sliding panes for the whole
        batch in numpy, group rows by (pane start), and feed each
        sub-batch into the backend's add_batch — one vectorized state
        write per (window, batch) instead of one per row.

        Exactness: the watermark is FIXED for the whole batch, so a
        window either fires immediately for ALL its in-batch rows
        (max_timestamp <= watermark, the allowed-lateness grace path)
        or for NONE of them.  Fire-now rows are replayed through the
        scalar per-element path in row order — their incremental
        emissions are part of the operator's contract — while all
        CONTINUE panes (the overwhelming majority) go through the
        column path, which only accumulates state and registers
        dedup'd timers and therefore commutes with the replay."""
        n = len(batch)
        if n == 0:
            return
        with get_tracer().phase("window.ingest", rows=n):
            reason = self._batch_demote_reason
            if reason is None and (
                    batch.ts is None
                    or (batch.ts_mask is not None
                        and not batch.ts_mask.all())):
                reason = "rows without event timestamps"
            if reason is None and self.key_selector is None:
                reason = "no key selector bound"
            if reason is not None:
                self._note_boxed(n, reason)
                for record in batch.to_records():
                    self.set_key_context(record)
                    self.process_element(record)
                return
            if self._session_batches:
                self._process_batch_sessions(batch, n)
            else:
                self._process_batch_vectorized(batch, n)
            self._note_columnar(n)

    def process_batch_fused(self, batch, last_start=None) -> None:
        """Ingest a batch whose first-pane starts were already computed
        on device inside a fused chain program (chain_fusion) —
        identical to :meth:`process_batch` except the pane arithmetic
        is skipped.  Every boxing guard stays armed: when one trips,
        the precomputed column is simply dropped and the ordinary path
        (vectorized or per-row) runs."""
        n = len(batch)
        if n == 0:
            return
        if (last_start is None
                or self._batch_demote_reason is not None
                or self._session_batches
                or batch.ts is None
                or (batch.ts_mask is not None and not batch.ts_mask.all())
                or self.key_selector is None):
            self.process_batch(batch)
            return
        with get_tracer().phase("window.ingest", rows=n):
            self._process_batch_vectorized(batch, n, last_start=last_start)
            self._note_fused(n)

    def _process_batch_vectorized(self, batch, n: int,
                                  last_start=None) -> None:
        tracer = get_tracer()
        ts = np.asarray(batch.ts, np.int64)
        with tracer.phase("window.ingest.box"):
            values = batch.row_values()
            keys = self._batch_keys(batch, values)
        size = self.assigner.size
        lateness = self.allowed_lateness
        state = self.window_state
        backend = self.keyed_backend
        with tracer.phase("window.ingest.assign"):
            wm = self.timer_service.current_watermark
            slide = getattr(self.assigner, "slide", size)
            offset = self.assigner.offset
            vcol = self._value_column(batch, n)
            if last_start is None:
                last_start = ts - ((ts - offset) % slide)
            else:
                last_start = np.asarray(last_start, np.int64)
            npanes = -(-size // slide)  # ceil; 1 for tumbling
            assigned = np.zeros(n, bool)
            immediate = np.zeros(n, bool)
            idx_parts = []
            start_parts = []
            for p in range(npanes):
                starts = last_start - p * slide
                maxts = starts + (size - 1)
                live = starts > (ts - size)
                window_late = (maxts + lateness) <= wm
                ok = live & ~window_late
                if not ok.any():
                    continue
                assigned |= ok
                fire_now = ok & (maxts <= wm)
                immediate |= fire_now
                vi = np.nonzero(ok & ~fire_now)[0]
                if vi.size:
                    idx_parts.append(vi)
                    start_parts.append(starts[vi])
        #: (window start, row indexes, their keys) per touched window
        groups = []
        with tracer.phase("window.ingest.group") as phase:
            if idx_parts:
                all_idx = np.concatenate(idx_parts)
                all_starts = np.concatenate(start_parts)
                # group by window; WITHIN a window restore row order —
                # different rows reach the same sliding window at
                # different pane indexes, and both the state fold order
                # and same-timestamp timer order must match the scalar
                # path's row-major traversal
                order = np.lexsort((all_idx, all_starts))
                sidx = all_idx[order]
                sstarts = all_starts[order]
                bounds = np.nonzero(np.diff(sstarts))[0] + 1
                lo = 0
                for hi in [*bounds.tolist(), len(sidx)]:
                    gidx = sidx[lo:hi]
                    groups.append((int(sstarts[lo]), gidx,
                                   [keys[i] for i in gidx]))
                    lo = hi
                self.window_rows += len(sidx)
            phase.set_attr("windows", len(groups))
            self.windows_touched += len(groups)
        for start, gidx, gkeys in groups:
            ns = (start, start + size)
            if vcol is not None:
                backend.add_batch(state, gkeys, ns, vcol[gidx],
                                  pre_extracted=True)
            else:
                backend.add_batch(state, gkeys, ns,
                                  [values[i] for i in gidx])
            with tracer.phase("timers.register") as phase:
                # first-occurrence order, NOT a set: same-timestamp
                # timers fire in registration order, and the scalar
                # path registers them in row order
                dkeys = dict.fromkeys(gkeys)
                phase.set_attr("keys", len(dkeys))
                maxt = start + size - 1
                # trigger timer (what EventTimeTrigger.on_element
                # registers on CONTINUE) + GC timer, which with
                # lateness 0 is the trigger timer; keys that have
                # theirs keep their place
                register = self.timer_service.register_event_time_timers_bulk
                register(ns, maxt, dkeys)
                cleanup = maxt + lateness
                if maxt < cleanup < MAX_TIMESTAMP:
                    register(ns, cleanup, dkeys)
        if immediate.any():
            tlist = ts.tolist()
            for i in np.nonzero(immediate)[0]:
                backend.set_current_key(keys[i])
                self._replay_immediate(values[i], tlist[i], wm)
        dropped = ~assigned & ~immediate & ((ts + lateness) <= wm)
        if dropped.any():
            self._drop_late(batch, values, ts, np.flatnonzero(dropped))

    # ---- batch path, session windows ---------------------------------
    def _mapping_state(self):
        from flink_tpu.state.backend import VOID_NAMESPACE
        return self.keyed_backend.get_partitioned_state(
            VOID_NAMESPACE, self._mapping_desc)

    def _load_mappings(self, keys: list) -> list:
        """The stored window -> state-window mappings of `keys`, as
        `MergingWindowSet.persist` writes them (``{(start, end):
        (start, end)}``), None where a key has none.  The caller
        copies before it changes one."""
        state = self._mapping_state()
        if hasattr(state, "values_batch"):
            stored = state.values_batch(keys)
        else:
            stored = []
            for key in keys:
                self.keyed_backend.set_current_key(key)
                stored.append(state.value())
        # (a snapshot from before PR 37 holds the windows themselves)
        return [m if not m or type(next(iter(m))) is tuple else
                {w.to_namespace(): sw.to_namespace() for w, sw in m.items()}
                for m in stored]

    def _store_mappings(self, keys: list, mappings: list) -> None:
        """`MergingWindowSet.persist` for many keys: a mapping that is
        empty clears its key's."""
        state = self._mapping_state()
        mappings = [m or None for m in mappings]
        if hasattr(state, "update_batch"):
            state.update_batch(keys, mappings)
            return
        for key, mapping in zip(keys, mappings):
            self.keyed_backend.set_current_key(key)
            state.update(mapping)

    def _process_batch_sessions(self, batch, n: int) -> None:
        """Columnar ingest of event-time session windows: the batch's
        rows go through `SessionBatchPlan` (the per-row path's
        MergingWindowSet, worked for the batch), then ONE
        ``merge_namespaces_batch`` for the state windows that merged,
        ONE ``add_batch`` with every row's state window as its
        namespace, the timers moved in bulk, the mappings persisted.

        Exactness: the watermark is fixed for the batch; with allowed
        lateness 0 a merged window that is due is late, so no row
        fires on arrival and a row either joins a live session or is
        dropped exactly where the per-row path drops it (the plan
        decides that row by row where it matters).  The plan's
        mappings, dict order included, and its surviving timers, in
        the order of the rows that place them, are the per-row path's;
        state is written under the state window that SURVIVES the
        batch, so the rows of a state window the per-row path makes
        and merges away reach the same accumulator without the detour.
        That is the same state where the aggregate's merge is exact
        (associative and commutative, as a sketch's or an integer
        sum's is); a float sum may differ in its last bit, as it may
        between two arrival orders.  tests/test_session_batch.py pins
        the two paths bit-equal."""
        tracer = get_tracer()
        ts = np.asarray(batch.ts, np.int64)
        state = self.window_state
        backend = self.keyed_backend
        values = None
        with tracer.phase("window.ingest.box"):
            from flink_tpu.streaming.columnar import field_key_column
            col = field_key_column(self.key_selector, batch)
            if col is not None:
                keys = col.tolist()
            else:
                values = batch.row_values()
                keys = [self.key_selector.get_key(v) for v in values]
            vcol = self._value_column(batch, n)
            if vcol is None and values is None:
                values = batch.row_values()
        wm = self.timer_service.current_watermark
        with tracer.phase("window.ingest.sessions", rows=n) as phase:
            plan = SessionBatchPlan(self.assigner.gap, wm).run(
                keys, ts, self._load_mappings)
            namespaces = plan.state_windows()
            self._store_mappings(plan.changed_keys, plan.changed_maps)
            for name, count in (("runs", plan.runs),
                                ("opened", plan.opened),
                                ("extended", plan.extended),
                                ("merged", plan.merged)):
                phase.set_attr(name, count)
            self.sessions_opened += plan.opened
            self.sessions_extended += plan.extended
            self.session_windows_merged += plan.merged
            late = np.flatnonzero(namespaces == None)  # noqa: E711
            if late.size:
                keep = np.flatnonzero(namespaces != None)  # noqa: E711
                namespaces = namespaces[keep]
                keys = [keys[i] for i in keep.tolist()]
                if vcol is not None:
                    vcol = vcol[keep]
            namespaces = namespaces.tolist()
        if plan.merges:
            backend.merge_namespaces_batch(state, plan.merges)
        if keys:
            if vcol is not None:
                backend.add_batch(state, keys, None, vcol,
                                  namespaces=namespaces, pre_extracted=True)
            else:
                rows = values if not late.size else \
                    [values[i] for i in keep.tolist()]
                backend.add_batch(state, keys, None, rows,
                                  namespaces=namespaces)
        svc = self.timer_service
        if plan.deleted:
            with tracer.phase("timers.delete", keys=len(plan.deleted)):
                svc.delete_event_time_timers_rows(
                    (w, w[1] - 1, key) for w, key in plan.deleted)
        if plan.registered:
            with tracer.phase("timers.register", keys=len(plan.registered)):
                # the trigger's timer; with lateness 0 the cleanup
                # timer is the same one
                svc.register_event_time_timers_rows(
                    (w, w[1] - 1, key) for _, w, key in plan.registered)
        if late.size:
            self._drop_late(batch, values, ts, late)

    def _replay_immediate(self, value, timestamp: int, wm: int) -> None:
        """Scalar replay for a row with >= 1 window already past the
        watermark: only those windows run here (add + trigger + emit,
        exactly process_element's per-window body); CONTINUE windows
        were vector-ingested."""
        record = StreamRecord(value, timestamp)
        for window in self.assigner.assign_windows(
                value, timestamp, self.assigner_ctx):
            if window.max_timestamp() > wm:
                continue  # handled by the column path
            if self._is_window_late(window):
                continue
            ns = self._namespace_of(window)
            self.window_state.set_current_namespace(ns)
            self.window_state.add(self._state_value(record))
            if INTROSPECTION.enabled:
                INTROSPECTION.note_row(
                    self.state_descriptor.name,
                    self.keyed_backend.current_key,
                    self.keyed_backend.max_parallelism)
            self.trigger_ctx.window = window
            result = self.trigger.on_element(
                value, timestamp, window, self.trigger_ctx)
            self._react(result, window)
            self._register_cleanup_timer(window)

    def _process_merging(self, record, windows, skipped):
        from flink_tpu.state.backend import VOID_NAMESPACE
        mapping_state = self.keyed_backend.get_partitioned_state(
            VOID_NAMESPACE, self._mapping_desc)
        merging = MergingWindowSet(mapping_state,
                                   self.assigner.window_type())

        def on_merge(merge_result, merged_windows, state_window, merged_state_windows):
            # fold merged state windows into the surviving one
            if merged_state_windows and hasattr(self.window_state, "merge_namespaces"):
                self.window_state.merge_namespaces(
                    self._namespace_of(state_window),
                    [self._namespace_of(w) for w in merged_state_windows])
            # trigger merges its per-window state FIRST (ref: the order
            # in WindowOperator's merge callback: onMerge, then clear
            # each merged window), then old windows' trigger state,
            # timers, and cleanup timers are dropped
            self.trigger_ctx.window = merge_result
            self.trigger_ctx.merged_windows = [
                w for w in merged_windows if w != merge_result]
            self.trigger.on_merge(merge_result, self.trigger_ctx)
            self.trigger_ctx.merged_windows = ()
            for w in merged_windows:
                if w == merge_result:
                    continue
                self.trigger_ctx.window = w
                self.trigger.clear(w, self.trigger_ctx)
                self._delete_cleanup_timer(w)

        for window in windows:
            actual = merging.add_window(window, on_merge)
            if self._is_window_late(actual):
                merging.retire_window(actual)
                continue
            skipped = False
            state_window = merging.get_state_window(actual)
            self.window_state.set_current_namespace(
                self._namespace_of(state_window))
            self.window_state.add(self._state_value(record))
            self.trigger_ctx.window = actual
            result = self.trigger.on_element(
                record.value, record.timestamp, actual, self.trigger_ctx)
            if TriggerResult.is_fire(result):
                contents = self._contents_for(actual, merging)
                if contents is not None:
                    self._emit(actual, contents)
            if TriggerResult.is_purge(result):
                self.window_state.clear()
            self._register_cleanup_timer(actual)
        merging.persist()
        return skipped

    # ---- timers (ref: onEventTime :424 / onProcessingTime :472) -----
    def on_event_time(self, timer):
        window = self._window_from_namespace(timer.namespace)
        self.trigger_ctx.window = window
        merging = None
        if self.assigner.is_merging():
            from flink_tpu.state.backend import VOID_NAMESPACE
            mapping_state = self.keyed_backend.get_partitioned_state(
                VOID_NAMESPACE, self._mapping_desc)
            merging = MergingWindowSet(mapping_state,
                                       self.assigner.window_type())
            state_window = merging.get_state_window(window)
            if state_window is None:
                return  # window was merged away; timer is stale
            self.window_state.set_current_namespace(
                self._namespace_of(state_window))
        else:
            self.window_state.set_current_namespace(self._namespace_of(window))

        result = self.trigger.on_event_time(timer.timestamp, window, self.trigger_ctx)
        if TriggerResult.is_fire(result):
            contents = self.window_state.get()
            if contents is not None:
                self._emit(window, contents)
        if TriggerResult.is_purge(result):
            self.window_state.clear()
        if self.assigner.is_event_time() and self._is_cleanup_time(window, timer.timestamp):
            self._clear_all_state(window, merging)
        if merging is not None:
            merging.persist()

    def on_processing_time(self, timer):
        window = self._window_from_namespace(timer.namespace)
        self.trigger_ctx.window = window
        merging = None
        if self.assigner.is_merging():
            from flink_tpu.state.backend import VOID_NAMESPACE
            mapping_state = self.keyed_backend.get_partitioned_state(
                VOID_NAMESPACE, self._mapping_desc)
            merging = MergingWindowSet(mapping_state,
                                       self.assigner.window_type())
            state_window = merging.get_state_window(window)
            if state_window is None:
                return
            self.window_state.set_current_namespace(
                self._namespace_of(state_window))
        else:
            self.window_state.set_current_namespace(self._namespace_of(window))

        result = self.trigger.on_processing_time(
            timer.timestamp, window, self.trigger_ctx)
        if TriggerResult.is_fire(result):
            contents = self.window_state.get()
            if contents is not None:
                self._emit(window, contents)
        if TriggerResult.is_purge(result):
            self.window_state.clear()
        if (not self.assigner.is_event_time()
                and self._is_cleanup_time(window, timer.timestamp)):
            self._clear_all_state(window, merging)
        if merging is not None:
            merging.persist()

    # ---- batched watermark fires ------------------------------------
    #: kill switch / A-B toggle: False pins the per-timer scalar fire
    #: path even for batch-eligible operators (the differential suite
    #: and the bench A/B flip this)
    batch_fires = True

    def snapshot_state(self, checkpoint_id=None) -> dict:
        """At the barrier: the backend's capture (its own phases,
        `state.snapshot.*`) and, as this phase's own time, the copies
        of the host tables (the sessions' window -> state-window
        mappings) and of the timer runs."""
        with get_tracer().phase("window.snapshot"):
            return super().snapshot_state(checkpoint_id)

    def process_watermark(self, watermark) -> None:
        """Watermark: the batch-eligible shape (tumbling/sliding
        event-time windows with their default trigger — the same
        structural test process_batch uses) takes the columnar fire
        sweep; everything else (merging assigners, custom triggers,
        evictors, processing-time assigners) keeps the per-timer drain
        in advance_watermark."""
        with get_tracer().phase("window.watermark",
                                watermark=watermark.timestamp):
            if (self.timer_service is None or not self.batch_fires
                    or getattr(self, "_batch_demote_reason", "unopened")
                    is not None):
                super().process_watermark(watermark)
                return
            self.current_watermark = watermark.timestamp
            if self._session_batches:
                self.on_watermark_sessions(watermark.timestamp)
            else:
                self.on_watermark_batch(watermark.timestamp)
            self.output.emit_watermark(watermark)

    def on_watermark_batch(self, watermark: int) -> None:
        """Columnar fire: ONE timer sweep → one EventTimeTrigger
        decision per swept run → ONE backend gather for every
        firing (key, window) → in-pop-order emit (one RecordBatch when
        the results columnarize) → ONE batch state clear + bulk
        cleanup-timer delete.

        Exactness vs the per-timer loop: the default EventTimeTrigger
        neither writes state nor registers timers from on_event_time,
        distinct (key, window) slots are independent, and within one
        slot the fire timer (max_timestamp) pops before the cleanup
        timer (max_timestamp + lateness) — with lateness 0 the two
        dedup into ONE timer that fires then cleans — so gathering
        every firing slot BEFORE the batch clear reads exactly what
        the interleaved scalar drain read, in the same order.  The
        differential suite (tests/test_fire_batch.py) pins the two
        paths bit-equal."""
        svc = self.timer_service
        lateness = self.allowed_lateness
        with get_tracer().phase("timers.sweep") as phase:
            runs = svc.pop_due_event_time_timers(watermark)
            n = sum(len(keys) for _, _, keys in runs)
            phase.set_attr("timers", n)
            phase.set_attr("runs", len(runs))
        if not runs:
            return
        self.timers_swept += n
        self.timer_runs += len(runs)
        # the timers of a run share timestamp and window, so the
        # trigger decides once per run.  EventTimeTrigger.on_event_time:
        # FIRE iff time == maxTimestamp; cleanup as _is_cleanup_time
        fired = []
        cleaned = []
        for timestamp, ns, keys in runs:
            maxt = ns[1] - 1
            if timestamp == maxt:
                fired.append((ns, keys))
            if lateness and timestamp == min(maxt + lateness, MAX_TIMESTAMP):
                cleaned.append((ns, keys))
        if not lateness:
            cleaned = fired  # fire and cleanup are the SAME dedup'd timer
        if fired:
            get_tracer().note_fire(
                self.operator_id or type(self).__name__, len(fired),
                sum(len(keys) for _, keys in fired),
                max(ns[1] for ns, _ in fired))
        backend = self.keyed_backend
        emitted = 0
        if fired:
            columns = _run_columns(fired)
            contents_col, found_mask, _path = backend.get_batch(
                self.window_state, columns[0], columns[1],
                namespaces=columns[2])
            emitted = self._emit_fired_columns(
                *columns, contents_col, found_mask)
        if TELEMETRY.enabled and emitted:
            TELEMETRY.note_windows_fired(emitted)
        if cleaned:
            if cleaned is not fired:
                columns = _run_columns(cleaned)
            backend.clear_batch(self.window_state, columns[0], columns[1],
                                namespaces=columns[2])
            if lateness:
                # EventTimeTrigger.clear: drop the max_timestamp fire
                # timer (with lateness 0 that timer IS the one just
                # swept — nothing left to delete)
                for ns, keys in cleaned:
                    svc.delete_event_time_timers_bulk(ns, ns[1] - 1, keys)
            if isinstance(self._internal_fn.fn, ProcessWindowFunction):
                wt = self.assigner.window_type()
                for ns, keys in cleaned:
                    window = wt.from_namespace(ns)
                    for key in keys:
                        backend.set_current_key(key)
                        self._internal_fn.clear(key, window, self)

    def on_watermark_sessions(self, watermark: int) -> None:
        """Columnar fire of session windows: ONE timer sweep, each
        swept (window, key) mapped to its state window, ONE gather
        under the state windows, the emit with each row's WINDOW, ONE
        clear, the fired windows retired from their keys' mappings.

        Exactness vs the per-timer loop: as `on_watermark_batch` (the
        default EventTimeTrigger writes no state and registers no
        timer from on_event_time; with lateness 0 the one timer of a
        window fires and cleans; distinct (key, state window) slots
        are independent, so gathering all before the one clear reads
        what the interleaved drain read, in the same order).  A timer
        whose window is not in its key's mapping is stale, as there
        (``on_event_time`` returns at once): the ingest deletes the
        timer of every window it merges away, so none is expected.
        Retiring a window touches only its own key's mapping, and a
        key's windows are retired in the order they fired."""
        svc = self.timer_service
        tracer = get_tracer()
        with tracer.phase("timers.sweep") as phase:
            runs = svc.pop_due_event_time_timers(watermark)
            n = sum(len(keys) for _, _, keys in runs)
            phase.set_attr("timers", n)
            phase.set_attr("runs", len(runs))
        if not runs:
            return
        self.timers_swept += n
        self.timer_runs += len(runs)
        with tracer.phase("window.fire.sessions", timers=n):
            keys, window, windows = _run_columns(
                [(ns, ks) for _, ns, ks in runs])
            if windows is None:
                windows = [window] * len(keys)
            distinct = list(dict.fromkeys(keys))
            held = dict(zip(distinct, self._load_mappings(distinct)))
            fired_keys, fired_windows, state_windows = [], [], []
            for key, ns in zip(keys, windows):
                mapping = held[key]
                sw = mapping.get(ns) if mapping else None
                if sw is not None:  # else stale: merged away
                    fired_keys.append(key)
                    fired_windows.append(ns)
                    state_windows.append(sw)
        if not fired_keys:
            return
        tracer.note_fire(self.operator_id or type(self).__name__,
                         len(fired_keys), len(fired_keys),
                         max(ns[1] for ns in fired_windows))
        backend = self.keyed_backend
        contents_col, found_mask, _path = backend.get_batch(
            self.window_state, fired_keys, None, namespaces=state_windows)
        emitted = self._emit_fired_columns(
            fired_keys, None, fired_windows, contents_col, found_mask)
        if TELEMETRY.enabled and emitted:
            TELEMETRY.note_windows_fired(emitted)
        backend.clear_batch(self.window_state, fired_keys, None,
                            namespaces=state_windows)
        with tracer.phase("window.fire.sessions"):
            retired: dict = {}
            for key, ns in zip(fired_keys, fired_windows):
                mapping = retired.get(key)
                if mapping is None:
                    mapping = retired[key] = dict(held[key])
                del mapping[ns]
            self._store_mappings(list(retired), list(retired.values()))
        if isinstance(self._internal_fn.fn, ProcessWindowFunction):
            from_namespace = self.assigner.window_type().from_namespace
            for key, ns in zip(fired_keys, fired_windows):
                backend.set_current_key(key)
                self._internal_fn.clear(key, from_namespace(ns), self)

    def _emit_fired_columns(self, keys, namespace, namespaces, contents_col,
                            found_mask) -> int:
        """Run the window function over the gathered contents in pop
        order, buffering the emissions; flush as ONE RecordBatch when
        the rows columnarize (per-row records otherwise, same order).
        The fired keys are of one `namespace`, or each of its own in
        `namespaces`.  Returns the number of windows that emitted —
        the scalar path's windowsFired increments, applied in one
        note."""
        buf = _FireBufferOutput(self.output)
        fire = self._fire_through_collector \
            if self._internal_fn.takes_collector else self._fire_over_columns
        with get_tracer().phase("window.fire.batch", keys=len(keys)) as phase:
            fired = fire(buf, keys, namespace, namespaces, contents_col,
                         found_mask)
            buf.book(self, phase)
        buf.flush()
        buf.release()
        return fired

    def _fire_through_collector(self, buf, keys, namespace, namespaces,
                                contents_col, found_mask) -> int:
        """A ProcessWindowFunction or WindowFunction, key by key: each
        is called under its key's context (it may read per-window
        keyed state) and writes through a collector."""
        from_namespace = self.assigner.window_type().from_namespace
        backend = self.keyed_backend
        hist = self._emit_batch_hist
        # a device gather hands back an ndarray: unbox 0-d rows exactly
        # as scalar get() does (`out.item() if np.ndim(out) == 0`);
        # heap results are python objects and pass through untouched
        unbox = isinstance(contents_col, np.ndarray)
        collector = TimestampedCollector(buf)
        if namespaces is None:
            namespaces = itertools.repeat(namespace)
        fired = 0
        for key, ns, found, contents in zip(keys, namespaces, found_mask,
                                            contents_col):
            if not found:
                continue
            if unbox:
                if np.ndim(contents) == 0:
                    contents = contents.item()
            elif contents is None:
                continue
            window = from_namespace(ns)
            backend.set_current_key(key)
            if hist is not None:
                hist.update(len(contents)
                            if hasattr(contents, "__len__") else 1)
            collector.set_absolute_timestamp(window.max_timestamp())
            self._internal_fn.process(key, window, self, contents, collector)
            fired += 1
        return fired

    def _fire_over_columns(self, buf, keys, namespace, namespaces,
                           contents_col, found_mask) -> int:
        """A plain callable, or no window function: it is handed
        (key, window, elements) and nothing else, so nothing is set up
        per key — absent rows are dropped once, the gather is unboxed
        once, every distinct window is built once, and the backend's
        key context is set once, to the last fired key, where the
        per-key loop leaves it."""
        keep = np.asarray(found_mask, bool)
        scalars = False
        if isinstance(contents_col, np.ndarray):
            # as scalar get() unboxes: python scalars out of a 1-d
            # gather, the rows of a wider one as they are
            scalars = contents_col.ndim == 1 \
                and contents_col.dtype.kind != "O"
            contents = contents_col.tolist() if contents_col.ndim == 1 \
                else list(contents_col)
        else:
            contents = contents_col
            keep = keep & np.fromiter((c is not None for c in contents),
                                      bool, len(contents))
        if not keep.all():
            keep = keep.tolist()
            keys = list(itertools.compress(keys, keep))
            contents = list(itertools.compress(contents, keep))
            if namespaces is not None:
                namespaces = list(itertools.compress(namespaces, keep))
        fired = len(keys)
        if not fired:
            return 0
        if self._emit_batch_hist is not None:
            self._emit_batch_hist.update_many(
                [1] * fired if scalars else
                [len(c) if hasattr(c, "__len__") else 1 for c in contents])
        from_namespace = self.assigner.window_type().from_namespace
        fn = self._internal_fn
        if namespaces is None:
            buf.emit_fired(fn.fn, keys, contents, fn.single_value,
                           window=from_namespace(namespace))
        else:
            made = {ns: from_namespace(ns) for ns in set(namespaces)}
            buf.emit_fired(fn.fn, keys, contents, fn.single_value,
                           windows=[made[ns] for ns in namespaces])
        self.keyed_backend.set_current_key(keys[-1])
        return fired

    # ---- helpers ----------------------------------------------------
    def _react(self, result: int, window) -> None:
        if TriggerResult.is_fire(result):
            contents = self.window_state.get()
            if contents is not None:
                self._emit(window, contents)
        if TriggerResult.is_purge(result):
            self.window_state.clear()

    def _contents_for(self, window, merging: Optional[MergingWindowSet]):
        if merging is not None:
            state_window = merging.get_state_window(window)
            if state_window is None:
                return None
            self.window_state.set_current_namespace(
                self._namespace_of(state_window))
        return self.window_state.get()

    def _emit(self, window, contents) -> None:
        """(ref: emitWindowContents :544 — output timestamp =
        window.maxTimestamp)"""
        if self._emit_batch_hist is not None:
            self._emit_batch_hist.update(
                len(contents) if hasattr(contents, "__len__") else 1)
        if TELEMETRY.enabled:
            # per-key timer fire: one emitted (key, window) result —
            # the denominator of the device ledger's transfer-tax ratio
            TELEMETRY.note_windows_fired(1)
        tracer = get_tracer()
        if tracer.enabled:
            with tracer.span("window.fire"):
                self.collector.set_absolute_timestamp(
                    window.max_timestamp())
                key = self.keyed_backend.current_key
                self._internal_fn.process(key, window, self, contents,
                                          self.collector)
            return
        self.collector.set_absolute_timestamp(window.max_timestamp())
        key = self.keyed_backend.current_key
        self._internal_fn.process(key, window, self, contents, self.collector)

    def _window_from_namespace(self, namespace):
        wt = self.assigner.window_type()
        return wt.from_namespace(namespace)

    def _cleanup_time(self, window) -> int:
        if self.assigner.is_event_time():
            # cap at MAX_TIMESTAMP — Python ints don't overflow, so an
            # explicit cap replaces the reference's wraparound check
            # (GlobalWindows + lateness must stay at "end of time")
            t = window.max_timestamp() + self.allowed_lateness
            return t if t < MAX_TIMESTAMP else MAX_TIMESTAMP
        return window.max_timestamp()

    def _is_cleanup_time(self, window, time: int) -> bool:
        return time == self._cleanup_time(window)

    def _register_cleanup_timer(self, window) -> None:
        cleanup = self._cleanup_time(window)
        if cleanup == MAX_TIMESTAMP:
            return  # end of time — nothing to GC (ref: :596-626)
        self.trigger_ctx.window = window
        if self.assigner.is_event_time():
            self.timer_service.register_event_time_timer(
                self._namespace_of(window), cleanup)
        else:
            self.timer_service.register_processing_time_timer(
                self._namespace_of(window), cleanup)

    def _delete_cleanup_timer(self, window) -> None:
        cleanup = self._cleanup_time(window)
        if cleanup == MAX_TIMESTAMP:
            return
        if self.assigner.is_event_time():
            self.timer_service.delete_event_time_timer(
                self._namespace_of(window), cleanup)
        else:
            self.timer_service.delete_processing_time_timer(
                self._namespace_of(window), cleanup)

    def _is_window_late(self, window) -> bool:
        """(ref: isWindowLate :576)"""
        return (self.assigner.is_event_time()
                and self._cleanup_time(window) <= self.timer_service.current_watermark)

    def _is_element_late(self, record: StreamRecord) -> bool:
        """(ref: isElementLate :589)"""
        return (self.assigner.is_event_time()
                and record.timestamp is not None
                and record.timestamp + self.allowed_lateness
                <= self.timer_service.current_watermark)

    def _clear_all_state(self, window, merging: Optional[MergingWindowSet]) -> None:
        """(ref: clearAllState :517)"""
        self.window_state.clear()
        self.trigger_ctx.window = window
        self.trigger.clear(window, self.trigger_ctx)
        key = self.keyed_backend.current_key
        self._internal_fn.clear(key, window, self)
        if merging is not None:
            merging.retire_window(window)


# ---------------------------------------------------------------------
# Evicting variant (ref: EvictingWindowOperator.java)
# ---------------------------------------------------------------------

class EvictingWindowOperator(WindowOperator):
    """Keeps raw (timestamp, value) pairs and applies the evictor
    around the window function."""

    def __init__(self, assigner, window_function, trigger=None,
                 evictor=None, allowed_lateness=0, late_data_tag=None,
                 pre_aggregator=None):
        if evictor is None:
            raise ValueError("EvictingWindowOperator requires an evictor")
        super().__init__(
            assigner,
            ListStateDescriptor("window-contents-evicting"),
            window_function,
            trigger,
            allowed_lateness,
            late_data_tag,
            single_value_contents=False,
        )
        self.evictor = evictor
        #: with an evictor, pre-aggregation is impossible (raw elements
        #: must be retained), so reduce/aggregate run at fire time over
        #: the surviving elements (ref: WindowedStream.reduce's
        #: evictor branch wrapping into ReduceApplyWindowFunction)
        self.pre_aggregator = pre_aggregator
        if pre_aggregator is not None:
            self._internal_fn = _InternalWindowFunction(
                window_function, single_value=True)

    def _batch_eligibility(self) -> Optional[str]:
        return "evictor retains raw per-row elements"

    def _state_value(self, record: StreamRecord):
        # store (timestamp, value) so time-based eviction works; the
        # raw record still flows to triggers and late-data side output
        return (record.timestamp, record.value)

    def _emit(self, window, contents) -> None:
        elements: List[Tuple[int, Any]] = list(contents)
        now = (self.timer_service.current_watermark
               if self.assigner.is_event_time()
               else self.processing_time_service.get_current_processing_time())
        kept = self.evictor.evict_before(elements, len(elements), window, now)
        self.collector.set_absolute_timestamp(window.max_timestamp())
        key = self.keyed_backend.current_key
        values = [v for _, v in kept]
        if self.pre_aggregator is not None:
            if values:
                self._internal_fn.process(
                    key, window, self, self.pre_aggregator(values),
                    self.collector)
        else:
            self._internal_fn.process(key, window, self, values, self.collector)
        after = self.evictor.evict_after(kept, len(kept), window, now)
        # write back the surviving elements
        self.window_state.update([(ts, v) for ts, v in after])

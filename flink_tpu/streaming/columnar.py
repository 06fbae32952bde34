"""Columnar (vectorized) execution tier: RecordBatch elements.

The reference executes one Java object per record through the operator
chain; its Table planner closes the per-record interpretation gap with
Janino codegen (codegen/CodeGenerator.scala).  A Python runtime cannot
codegen its way out of per-record overhead — the TPU-first equivalent
is COLUMNAR flow: a stream element may be a :class:`RecordBatch`
(numpy columns + a timestamp column), sources emit batches, and
eligible operators consume whole batches.  This is the same design
point as Flink's later Blink planner / Arrow-based vectorized
execution: per-element costs amortize over thousands of rows, and the
window engines receive ready numpy columns.

Used by the Table/SQL layer (flink_tpu/table/api.py lowers eligible
windowed GROUP BY plans onto :class:`ColumnarWindowOperator`) and
available directly via
``StreamExecutionEnvironment`` sources built from
:class:`ColumnarSource`.

Parallelism: RecordBatches cross forward edges whole; a keyBy edge at
parallelism > 1 goes through :class:`BatchKeyGroupSplitOperator` (one
hash pass + one mask per target subtask — the columnar keyBy
exchange).  Plans that don't fit the tier fall back to the
row-at-a-time path — same split the reference drew between codegen'd
and interpreted operators.  NOTE: re-lowering a columnar plan at a
DIFFERENT parallelism changes the topology shape, so checkpoints do
not carry across such a change (the runtime warns on restore).
"""

from __future__ import annotations

from operator import itemgetter
from typing import Dict, List, Optional, Sequence

import numpy as np

from flink_tpu.ops.device_agg import DeviceAggregateFunction
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu.streaming.elements import (  # noqa: F401 — RecordBatch
    RecordBatch,   # re-exported: the batch element moved to elements.py
    StreamRecord,  # when it became a first-class StreamElement
    Watermark,
)
from flink_tpu.streaming.operators import StreamOperator
from flink_tpu.streaming.sources import SinkFunction, SourceFunction
from flink_tpu.streaming.window_engines import WindowEngineHost

#: kill switch for the end-to-end batch pipeline (RecordBatch flowing
#: as stream elements through sources, operator chains, and the
#: netchannel consumer).  Off, vectorized sources emit per-row records
#: and remote bindings decode row-at-a-time — the boxed path the
#: differential tests and the bench A/B compare against.  The wire
#: CODEC has its own independent flag (netchannel.COLUMNAR_ENABLED).
PIPELINE_ENABLED = True


def columns_from_values(values: Sequence) -> Optional[Dict[str, np.ndarray]]:
    """Lower a list of row values onto the pipeline column convention
    ("v" for scalar rows, "f0".."fk" for tuple rows) — or None when the
    values don't fit a column shape (heterogeneous types, bools, ints
    beyond int64, nested tuples...).  Mirrors the netchannel codec's
    strict type tiers so a batch born here round-trips the wire
    columnar.  One pass per check and per column, none of them a
    Python-level loop: a window fire hands a million rows here."""
    if not values:
        return None
    v0 = values[0]
    if type(v0) is tuple:
        arity = len(v0)
        if arity == 0 or set(map(type, values)) != {tuple} \
                or set(map(len, values)) != {arity}:
            return None
        cols = {}
        for i in range(arity):
            col = _column_from_cells(list(map(itemgetter(i), values)))
            if col is None:
                return None
            cols[f"f{i}"] = col
        return cols
    col = _column_from_cells(values)
    if col is None:
        return None
    return {"v": col}


def _column_from_cells(cells: Sequence) -> Optional[np.ndarray]:
    """One homogeneous cell list → ndarray, or None.  `bool` is a
    subclass of int and floats don't survive an int64 cast, hence the
    exact `type is` checks (same discipline as the wire codec)."""
    t = type(cells[0])
    if set(map(type, cells)) != {t}:
        return None
    if t is int:
        try:
            return np.array(cells, np.int64)
        except OverflowError:
            return None
    if t is float:
        return np.array(cells, np.float64)
    if t is str:
        arr = np.empty(len(cells), object)
        arr[:] = cells
        return arr
    return None


def batch_from_records(values: Sequence, timestamps: Optional[Sequence]
                       ) -> Optional[RecordBatch]:
    """Values + per-row Optional[int] timestamps → RecordBatch (with a
    validity mask when timestamps are mixed None/int), or None when the
    values don't columnarize."""
    cols = columns_from_values(values)
    if cols is None:
        return None
    if timestamps is None or all(t is None for t in timestamps):
        return RecordBatch(cols)
    if any(t is None for t in timestamps):
        mask = np.array([t is not None for t in timestamps], bool)
        stamps = np.array([t if t is not None else 0
                           for t in timestamps], np.int64)
        return RecordBatch(cols, stamps, mask)
    return RecordBatch(cols, np.array(list(timestamps), np.int64))


def batch_from_runs(values: Sequence, runs: Sequence) -> Optional[RecordBatch]:
    """:func:`batch_from_records` for rows whose timestamps come as
    runs of ``(timestamp, row count)`` in row order — a window fire's
    rows, which share one timestamp per fired window.  The timestamp
    column is filled per run, never per row."""
    stamps = [t for t, _ in runs]
    if None in stamps:
        return batch_from_records(
            values, [t for t, count in runs for _ in range(count)])
    cols = columns_from_values(values)
    if cols is None:
        return None
    if len(runs) == 1:
        return RecordBatch(cols, np.full(len(values), stamps[0], np.int64))
    return RecordBatch(cols, np.repeat(np.array(stamps, np.int64),
                                       [count for _, count in runs]))


def batch_from_arrays(arrays, ts=None, ts_mask=None) -> RecordBatch:
    """Build a pipeline-convention batch from ready numpy columns: one
    array → scalar rows ("v"), a tuple/list of arrays → tuple rows
    ("f0".."fk")."""
    if isinstance(arrays, (tuple, list)):
        return RecordBatch(
            {f"f{i}": np.asarray(a) for i, a in enumerate(arrays)},
            ts, ts_mask)
    return RecordBatch({"v": np.asarray(arrays)}, ts, ts_mask)


def field_key_column(selector, batch) -> Optional[np.ndarray]:
    """The key column of a tuple-row batch when the key selector is a
    positional field (``key_by(0)``): cell for cell what ``get_key``
    extracts per row.  None for any other selector or batch shape —
    the caller then boxes the rows and asks the selector."""
    from flink_tpu.core.functions import _FieldKeySelector
    if isinstance(selector, _FieldKeySelector) \
            and type(selector._field) is int and not batch.is_scalar:
        col = batch.cols.get(f"f{selector._field}")
        if col is not None:
            return np.asarray(col)
    return None


class VectorizedCollectionSource(SourceFunction):
    """Bounded source over a Python collection that emits RecordBatch
    elements (columns built ONCE at construction) — the vectorized
    twin of FromCollectionSource, so a batch is *born* columnar
    instead of being re-derived per hop.  Values that don't fit the
    column convention raise at construction: callers fall back to
    FromCollectionSource (datastream.from_collection does this
    automatically when `vectorize=True` fails).

    With ``timestamped=True`` the input is (value, ts) pairs, same as
    FromCollectionSource.  Implements the cooperative emit_step +
    offset-checkpoint contract; one step emits ONE batch (the batch is
    the indivisible element)."""

    #: eligibility marker read by analysis.columnar_eligibility
    emits_batches = True

    def __init__(self, values: Sequence, timestamped: bool = False,
                 chunk: int = 16384):
        values = list(values)
        self.timestamped = timestamped
        self.chunk = chunk
        if timestamped:
            raw = [v for v, _ in values]
            ts = [t for _, t in values]
        else:
            raw, ts = values, None
        batch = batch_from_records(raw, ts)
        if batch is None and values:
            raise TypeError(
                "collection does not fit the columnar convention "
                "(heterogeneous / non-scalar rows) — use "
                "FromCollectionSource")
        #: the whole input as one master batch; emit_step slices it
        self._batch = batch
        self._n = len(values)
        self._running = True
        #: resume offset in ROWS (always a chunk boundary)
        self.offset = 0

    def run(self, ctx) -> None:
        while self.emit_step(ctx, self.chunk):
            pass

    def emit_step(self, ctx, max_records: int) -> bool:
        if self.offset < self._n and self._running:
            if not PIPELINE_ENABLED:
                # boxed A/B path: same rows, per-record records
                end = min(self.offset + self.chunk, self._n)
                sl = self._batch.take(slice(self.offset, end))
                self.offset = end
                if self.timestamped:
                    for v, t in zip(sl.row_values(), sl.timestamps()):
                        ctx.collect_with_timestamp(v, t)
                else:
                    for v in sl.row_values():
                        ctx.collect(v)
            else:
                end = min(self.offset + self.chunk, self._n)
                ctx.collect_batch(
                    self._batch.take(slice(self.offset, end)))
                self.offset = end
        return self.offset < self._n and self._running

    def cancel(self) -> None:
        self._running = False

    def __deepcopy__(self, memo):
        # batches are immutable — a clone only needs a fresh cursor
        clone = type(self).__new__(type(self))
        clone.__dict__.update(self.__dict__)
        clone._running = True
        return clone

    def snapshot_function_state(self, checkpoint_id=None) -> dict:
        return {"offset": self.offset}

    def restore_function_state(self, state: dict) -> None:
        self.offset = state["offset"]


class ColumnarSource(SourceFunction):
    """Bounded source over column arrays; emits RecordBatch chunks and
    a watermark after each chunk (input must be time-sorted on the
    rowtime column, the usual replayed-log shape).

    Implements the cooperative-stepping + offset-checkpoint contract
    (same as FromCollectionSource): snapshots at step boundaries see
    only fully-emitted batches, so recovery resumes exactly-once."""

    def __init__(self, cols: Dict[str, np.ndarray], rowtime: str,
                 chunk: int = 1 << 19, ooo_slack_ms: int = 0):
        self.cols = {k: np.asarray(v) for k, v in cols.items()}
        self.cols[rowtime] = np.asarray(self.cols[rowtime], np.int64)
        self.rowtime = rowtime
        self.chunk = chunk
        self.ooo_slack_ms = ooo_slack_ms
        self._running = True
        #: resume offset in ROWS (always a chunk boundary)
        self.offset = 0
        self._final_watermark = True

    def run(self, ctx) -> None:
        while self.emit_step(ctx, self.chunk):
            pass

    def emit_step(self, ctx, max_records: int) -> bool:
        """One cooperative step = ONE RecordBatch (`max_records` counts
        stream ELEMENTS, same per-element accounting as
        FromCollectionSource; a batch is the indivisible element here —
        slicing it to max_records rows would cap every batch at the
        executor's step size and destroy the columnar amortization)."""
        from flink_tpu.streaming.elements import MAX_WATERMARK
        ts_all = self.cols[self.rowtime]
        n = len(ts_all)
        if self.offset < n and self._running:
            sl = slice(self.offset, self.offset + self.chunk)
            batch = RecordBatch({k: v[sl] for k, v in self.cols.items()},
                                ts_all[sl])
            ctx.collect(batch)
            self.offset = min(self.offset + self.chunk, n)
            ctx.emit_watermark(Watermark(
                int(ts_all[self.offset - 1]) - self.ooo_slack_ms - 1))
        if self.offset < n and self._running:
            return True
        if self._final_watermark:
            ctx.emit_watermark(MAX_WATERMARK)
            self._final_watermark = False
        return False

    def cancel(self) -> None:
        self._running = False

    def __deepcopy__(self, memo):
        # per-attempt source cloning must not copy the input columns
        # (the source only ever slices them — views, no mutation); a
        # fresh cursor is all a clone needs.  type(self), not
        # ColumnarSource: a subclass (e.g. a test's gated source) must
        # survive the per-attempt clone
        clone = type(self).__new__(type(self))
        clone.__dict__.update(self.__dict__)
        clone._running = True
        return clone

    # checkpoint hooks (CheckpointedFunction-shaped source state)
    def snapshot_function_state(self, checkpoint_id=None) -> dict:
        return {"offset": self.offset,
                "final_watermark": self._final_watermark}

    def restore_function_state(self, state: dict) -> None:
        self.offset = state["offset"]
        self._final_watermark = state["final_watermark"]


class ColumnarCollectSink(SinkFunction):
    """Collects fired RecordBatches; row-style access for asserts."""

    def __init__(self):
        self.batches: List[RecordBatch] = []

    def invoke(self, value, context=None):
        self.batches.append(value)

    def total_rows(self) -> int:
        return sum(len(b) for b in self.batches)

    def rows(self):
        for b in self.batches:
            yield from b.rows()


class _ExplodeBatches(StreamOperator):
    """RecordBatch → per-row StreamRecords (field order = column
    order), each carrying its row's event timestamp.  The bridge from
    the columnar tier back to the row-at-a-time operators when a plan
    leaves the columnar shape."""

    def process_element(self, record: StreamRecord):
        batch: RecordBatch = record.value
        lists = [c.tolist() for c in batch.cols.values()]
        ts_list = (batch.ts.tolist() if batch.ts is not None
                   else [record.timestamp] * len(batch))
        out = self.output
        for ts, row in zip(ts_list, zip(*lists)):
            out.collect(StreamRecord(row, ts))


def explode_to_rows(stream):
    """Wrap a RecordBatch stream with the row-explode operator."""
    return stream._add_op("explode_batches", _ExplodeBatches)


class ColumnarWindowOperator(WindowEngineHost):
    """keyBy().window().aggregate(device_agg) over RecordBatch input.

    The columnar twin of DeviceWindowOperator: batches feed the engine
    directly (no per-record objects), fires leave as RecordBatches.
    The engine is chosen from the key column's dtype on the first batch
    (streaming/window_engines.py).

    out_fields maps each output column name to one of
    ("key", "agg", "wstart", "wend").
    """

    engine_key, tier_key = "columnar_engine", "columnar_tier"

    def __init__(self, assigner, agg: DeviceAggregateFunction,
                 key_col: str, input_col: Optional[str],
                 out_fields: Sequence[tuple],
                 initial_capacity: int = 1 << 14,
                 mesh=None, mesh_axis: str = "kg"):
        super().__init__(assigner, agg, initial_capacity, mesh, mesh_axis)
        self.key_col = key_col
        self.input_col = input_col
        self.out_fields = list(out_fields)

    # ---- input ------------------------------------------------------
    def process_element(self, record: StreamRecord):
        batch = record.value
        if isinstance(batch, tuple):
            # (target, sub_batch) carrier from the key-group split
            # exchange (parallelism > 1)
            batch = batch[1]
        if len(batch) == 0:
            return
        with get_tracer().phase("window.ingest", rows=len(batch)):
            self._ingest(batch)

    def _ingest(self, batch):
        keys = batch.cols[self.key_col]
        if self.engine is None:
            self._build_engine(np.asarray(keys).dtype)
        values = None
        value_hashes = None
        if self.input_col is not None:
            col = batch.cols[self.input_col]
            if self.agg.needs_value_hash:
                from flink_tpu.streaming.vectorized import hash_keys_np
                with get_tracer().phase("columnar.ingest.hash"):
                    value_hashes = hash_keys_np(np.asarray(col))
            if self.agg.needs_value:
                values = np.asarray(col)
        self.engine.process_batch(keys, batch.ts, values,
                                  value_hashes=value_hashes)

    def process_watermark(self, watermark: Watermark):
        with get_tracer().phase("window.watermark",
                                watermark=watermark.timestamp):
            if self.engine is not None:
                getattr(self.engine, "flush", lambda: None)()
                self.engine.advance_watermark(watermark.timestamp)
                if getattr(self.engine, "emit_arrays", False):
                    self._emit_fired()
                else:
                    self._emit_rows()
                self.num_late_records_dropped = self.engine.num_late_dropped
            self.current_watermark = watermark.timestamp
            self.output.emit_watermark(watermark)

    def _emit_rows(self):
        """Row-delivering engines (e.g. VectorizedSessionWindows):
        batch their .emitted tuples into one output RecordBatch."""
        emitted = self.engine.emitted
        if not emitted:
            return
        keys_np = np.asarray([e[0] for e in emitted])
        results = np.asarray([e[1] for e in emitted])
        starts = np.asarray([e[2] for e in emitted], np.int64)
        ends = np.asarray([e[3] for e in emitted], np.int64)
        del emitted[:]
        get_tracer().note_fire(self.operator_id or type(self).__name__,
                               len(ends), len(ends), int(ends.max()))
        out = self._out_batch(keys_np, results, starts, ends)
        self.output.collect(StreamRecord(out, timestamp=int(ends.max()) - 1))

    def _out_batch(self, keys, results, starts, ends) -> RecordBatch:
        by_kind = {"key": keys, "agg": results, "wstart": starts,
                   "wend": ends}
        return RecordBatch({name: by_kind[kind]
                            for name, kind in self.out_fields}, ends - 1)

    def _emit_fired(self):
        tracer = get_tracer()
        fired = self.engine.fired
        if fired:
            tracer.note_fire(
                self.operator_id or type(self).__name__, len(fired),
                sum(len(entry[0]) for entry in fired),
                int(max(np.max(entry[3]) for entry in fired)))
        for entry in fired:
            keys_np, results, start, end = entry
            with tracer.phase("window.fire.batch", keys=len(keys_np)):
                if isinstance(start, np.ndarray):
                    # session engines fire (keys, totals, starts, ends)
                    starts, ends = start, end
                    out_ts = int(ends.max()) - 1 if len(ends) else 0
                else:
                    starts = np.full(len(keys_np), start, np.int64)
                    ends = np.full(len(keys_np), end, np.int64)
                    out_ts = end - 1
                out = self._out_batch(keys_np, results, starts, ends)
            with tracer.phase("window.fire.downstream"):
                self.output.collect(StreamRecord(out, timestamp=out_ts))
        del fired[:]


class BatchKeyGroupSplitOperator(StreamOperator):
    """The keyBy exchange for RecordBatch flow at parallelism > 1:
    splits each batch by key-group-derived target subtask (the same
    range-partition arithmetic as KeyGroupRangeAssignment, computed
    vectorized in C++ — nat.key_groups), emitting (target, sub_batch)
    carriers the downstream custom partitioner routes by tag.  The
    columnar answer to the reference's per-record hash partitioner:
    one hash pass and one mask per target instead of a channel choice
    per record (round-2 verdict item 7)."""

    def __init__(self, key_col: str, max_parallelism: int, n_out: int):
        super().__init__()
        if n_out < 2:
            raise ValueError("the split exchange exists only for "
                             "parallelism > 1")
        self.key_col = key_col
        self.max_parallelism = max_parallelism
        self.n_out = n_out

    def set_key_context(self, record):
        pass

    def process_element(self, record: StreamRecord):
        batch: RecordBatch = record.value
        if len(batch) == 0:
            return
        from flink_tpu.streaming.vectorized import hash_keys_np
        kh = hash_keys_np(np.asarray(batch.cols[self.key_col]))
        try:
            import flink_tpu.native as nat
            targets = nat.key_groups(kh, self.max_parallelism,
                                     self.n_out)
        except Exception:  # noqa: BLE001 — numpy twin of ft_key_groups
            from flink_tpu.core.keygroups import (
                assign_operator_indexes_np,
            )
            targets = assign_operator_indexes_np(
                kh, self.max_parallelism, self.n_out)
        ts = np.asarray(batch.ts, np.int64) if batch.ts is not None \
            else None
        for t in range(self.n_out):
            m = targets == t
            if not m.any():
                continue
            sub = RecordBatch({k: np.asarray(v)[m]
                               for k, v in batch.cols.items()},
                              None if ts is None else ts[m])
            self.output.collect(StreamRecord((int(t), sub),
                                             record.timestamp))


class ColumnarIntervalJoinOperator(StreamOperator):
    """Vectorized stream-stream interval join over RecordBatch inputs
    (the columnar twin of the row-level interval join,
    flink_tpu/streaming/joining.py; ref role:
    DataStreamWindowJoin.scala's time-bounded join).

    Input elements are (tag, RecordBatch) carriers from the tagged
    union (0 = left, 1 = right).  Each side keeps a columnar buffer;
    an incoming batch probes the OTHER side's buffer with one
    vectorized hash-join pass:

      sort the buffer by 64-bit key hash (cached until the buffer
      changes) -> searchsorted the batch's hashes for candidate group
      ranges -> expand ranges with repeat/cumsum arithmetic -> filter
      by the time bound r.ts - l.ts in [lower, upper] AND exact key
      equality (hash-collision safe) -> gather the joined RecordBatch.

    Buffers prune by watermark (left rows die once wm >= ts + upper,
    right rows once wm >= ts - lower).  Single-parallelism, like the
    rest of the columnar tier."""

    def __init__(self, key_l: str, key_r: str, lower_ms: int,
                 upper_ms: int, out_fields_l, out_fields_r):
        super().__init__()
        self.key_l = key_l
        self.key_r = key_r
        self.lower = lower_ms
        self.upper = upper_ms
        #: [(out_name, src_col)] per side
        self.out_l = list(out_fields_l)
        self.out_r = list(out_fields_r)
        self._buf = [self._empty(), self._empty()]
        self.current_watermark = -(2 ** 63)
        # native fast path: the batched C++ join core probes per-key
        # time-sorted buffers with phase-split slot resolution; the
        # operator keeps append-only column storage per side and
        # gathers emitted pairs by global row id.  (Row-id addressed
        # storage is append-only; bounded inputs / replayed logs.)
        self._native = None
        self._store = None
        try:
            import flink_tpu.native as nat
            if nat.available():
                self._native = nat.NativeIntervalJoin(lower_ms, upper_ms)
                self._store = [self._new_store(), self._new_store()]
        except Exception:  # noqa: BLE001 — numpy path below
            self._native = None

    @staticmethod
    def _new_store():
        return {"cols": {}, "ts": None, "kh": None, "n": 0, "cap": 0}

    def _store_append(self, side: int, batch: RecordBatch,
                      kh: np.ndarray):
        st = self._store[side]
        n_new = len(batch)
        need = st["n"] + n_new
        if need > st["cap"]:
            cap = max(1 << 16, 1 << int(need - 1).bit_length())
            for name in batch.cols:
                old = st["cols"].get(name)
                arr = np.empty(cap, np.asarray(batch.cols[name]).dtype)
                if old is not None:
                    arr[:st["n"]] = old[:st["n"]]
                st["cols"][name] = arr
            for key in ("ts", "kh"):
                old = st[key]
                arr = np.empty(cap, np.int64 if key == "ts"
                               else np.uint64)
                if old is not None:
                    arr[:st["n"]] = old[:st["n"]]
                st[key] = arr
            st["cap"] = cap
        for name, col in batch.cols.items():
            st["cols"][name][st["n"]:need] = np.asarray(col)
        st["ts"][st["n"]:need] = np.asarray(batch.ts, np.int64)
        st["kh"][st["n"]:need] = kh
        st["n"] = need

    @staticmethod
    def _empty():
        return {"cols": None, "ts": None, "kh": None,
                "order": None, "sorted_kh": None}

    def set_key_context(self, record):
        pass

    def _hash(self, col: np.ndarray) -> np.ndarray:
        # hash_keys_np routes integral arrays through the native
        # splitmix64 itself
        from flink_tpu.streaming.vectorized import hash_keys_np
        return hash_keys_np(np.asarray(col))

    def _append(self, side: int, batch: RecordBatch, kh: np.ndarray):
        b = self._buf[side]
        if b["cols"] is None:
            b["cols"] = {k: np.asarray(v) for k, v in batch.cols.items()}
            b["ts"] = np.asarray(batch.ts, np.int64)
            b["kh"] = kh
        else:
            b["cols"] = {k: np.concatenate([b["cols"][k], batch.cols[k]])
                         for k in b["cols"]}
            b["ts"] = np.concatenate([b["ts"],
                                      np.asarray(batch.ts, np.int64)])
            b["kh"] = np.concatenate([b["kh"], kh])
        b["order"] = None  # sort cache dirtied

    def _sorted(self, side: int):
        # NOTE: correctness fallback only (no native runtime): every
        # append dirties the cache, so each probing batch re-argsorts
        # the (watermark-pruned) buffer — O(B log B) per batch.  The
        # native core is the performance path (counting-sorted batch,
        # monotone two-pointer probes).
        b = self._buf[side]
        if b["order"] is None and b["kh"] is not None:
            b["order"] = np.argsort(b["kh"], kind="stable")
            b["sorted_kh"] = b["kh"][b["order"]]
        return b

    def process_element(self, record: StreamRecord):
        tag, batch = record.value
        if len(batch) == 0:
            return
        key_col = self.key_l if tag == 0 else self.key_r
        kh = self._hash(batch.cols[key_col])
        if self._native is not None:
            self._store_append(tag, batch, kh)
            lrows, rrows = self._native.push(
                tag, kh, np.asarray(batch.ts, np.int64))
            if len(lrows):
                sl, sr = self._store[0], self._store[1]
                # exact key equality: the native core joins on 64-bit
                # hashes.  INTEGER keys hash via splitmix64 of their
                # 64-bit pattern — a BIJECTION, so collisions are
                # impossible and the recheck is skipped.  The two
                # sides must share signedness (a negative's bit
                # pattern aliases a huge unsigned); strings and
                # composites hash lossily and always verify.
                lkd = sl["cols"][self.key_l].dtype
                rkd = sr["cols"][self.key_r].dtype
                int_keys = lkd.kind == rkd.kind and lkd.kind in "iu"
                if not int_keys:
                    eq = (sl["cols"][self.key_l][lrows]
                          == sr["cols"][self.key_r][rrows])
                    if not eq.all():
                        lrows, rrows = lrows[eq], rrows[eq]
                        if not len(lrows):
                            return
                l_cols = {n: sl["cols"][c][lrows] for n, c in self.out_l}
                r_cols = {n: sr["cols"][c][rrows] for n, c in self.out_r}
                out_ts = np.maximum(sl["ts"][lrows], sr["ts"][rrows])
                out = RecordBatch({**l_cols, **r_cols}, out_ts)
                self.output.collect(
                    StreamRecord(out, timestamp=int(out_ts.max())))
            return
        self._append(tag, batch, kh)
        other = self._sorted(1 - tag)
        if other["cols"] is None or not len(other["kh"]):
            return
        starts = np.searchsorted(other["sorted_kh"], kh, "left")
        ends = np.searchsorted(other["sorted_kh"], kh, "right")
        counts = ends - starts
        total = int(counts.sum())
        if total == 0:
            return
        mine = np.repeat(np.arange(len(kh)), counts)
        cum0 = np.concatenate([[0], np.cumsum(counts)[:-1]])
        offs = np.arange(total) - np.repeat(cum0, counts)
        theirs = other["order"][np.repeat(starts, counts) + offs]
        ts_mine = np.asarray(batch.ts, np.int64)[mine]
        ts_other = other["ts"][theirs]
        if tag == 0:
            d = ts_other - ts_mine          # r.ts - l.ts
        else:
            d = ts_mine - ts_other
        ok = (d >= self.lower) & (d <= self.upper)
        # exact key equality (64-bit hash ties broken by content)
        okey = self.key_l if tag == 1 else self.key_r
        ok &= (np.asarray(batch.cols[key_col])[mine]
               == other["cols"][okey][theirs])
        if not ok.any():
            return
        mine, theirs = mine[ok], theirs[ok]
        if tag == 0:
            l_cols = {n: np.asarray(batch.cols[c])[mine]
                      for n, c in self.out_l}
            r_cols = {n: other["cols"][c][theirs] for n, c in self.out_r}
            out_ts = np.maximum(ts_mine[ok], ts_other[ok])
        else:
            l_cols = {n: other["cols"][c][theirs] for n, c in self.out_l}
            r_cols = {n: np.asarray(batch.cols[c])[mine]
                      for n, c in self.out_r}
            out_ts = np.maximum(ts_other[ok], ts_mine[ok])
        out = RecordBatch({**l_cols, **r_cols}, out_ts)
        self.output.collect(StreamRecord(out, timestamp=int(out_ts.max())))

    def process_watermark(self, watermark: Watermark):
        wm = watermark.timestamp
        self.current_watermark = wm
        if self._native is not None:
            self._native.prune(wm)
            self.output.emit_watermark(watermark)
            return
        for side, horizon in ((0, self.upper), (1, -self.lower)):
            b = self._buf[side]
            if b["cols"] is None:
                continue
            keep = b["ts"] + horizon > wm
            if not keep.all():
                b["cols"] = {k: v[keep] for k, v in b["cols"].items()}
                b["ts"] = b["ts"][keep]
                b["kh"] = b["kh"][keep]
                b["order"] = None
        self.output.emit_watermark(watermark)

    # checkpoint: the buffers ARE the operator state
    def snapshot_state(self, checkpoint_id=None) -> dict:
        snap = super().snapshot_state(checkpoint_id)
        if self._native is not None:
            snap["iv_join_store"] = [
                {"cols": {k: v[:s["n"]].copy()
                          for k, v in s["cols"].items()},
                 "ts": s["ts"][:s["n"]].copy() if s["ts"] is not None
                 else np.empty(0, np.int64),
                 "kh": s["kh"][:s["n"]].copy() if s["kh"] is not None
                 else np.empty(0, np.uint64)}
                for s in self._store]
            snap["iv_join_watermark"] = self.current_watermark
            return snap
        snap["iv_join_buffers"] = [
            None if b["cols"] is None else
            {"cols": {k: v.copy() for k, v in b["cols"].items()},
             "ts": b["ts"].copy(), "kh": b["kh"].copy()}
            for b in self._buf]
        return snap

    def restore_state(self, snapshots) -> None:
        super().restore_state(snapshots)
        for s in snapshots:
            if "iv_join_store" in s:
                import flink_tpu.native as nat
                if not nat.available():
                    # native-format snapshot on a host without the
                    # library: rebuild the numpy buffers instead
                    self._native = None
                    self._buf = []
                    for st in s["iv_join_store"]:
                        nb = self._empty()
                        if len(st["ts"]):
                            nb["cols"] = {k: np.asarray(v) for k, v
                                          in st["cols"].items()}
                            nb["ts"] = np.asarray(st["ts"], np.int64)
                            nb["kh"] = np.asarray(st["kh"], np.uint64)
                        self._buf.append(nb)
                    continue
                self._native = nat.NativeIntervalJoin(self.lower,
                                                      self.upper)
                self._store = [self._new_store(), self._new_store()]
                # replay each side into the core — pairs produced by
                # the replay were all emitted before the checkpoint
                # barrier, so they are DROPPED (push drains them;
                # left replays first, probing an empty right buffer)
                for side, st in enumerate(s["iv_join_store"]):
                    ts = np.asarray(st["ts"], np.int64)
                    kh = np.asarray(st["kh"], np.uint64)
                    if len(ts):
                        self._store_append(
                            side,
                            RecordBatch(dict(st["cols"]), ts), kh)
                        self._native.push(side, kh, ts)
                wm = s.get("iv_join_watermark")
                if wm is not None and wm > -(2 ** 63):
                    self.current_watermark = wm
                    self._native.prune(wm)
                continue
            if "iv_join_buffers" in s:
                # numpy-format snapshot: the restored rows live in the
                # numpy buffers, so the numpy path must serve them
                # even when this host could build the native core
                self._native = None
                self._buf = []
                for b in s["iv_join_buffers"]:
                    nb = self._empty()
                    if b is not None:
                        nb["cols"] = {k: np.asarray(v)
                                      for k, v in b["cols"].items()}
                        nb["ts"] = np.asarray(b["ts"], np.int64)
                        nb["kh"] = np.asarray(b["kh"], np.uint64)
                    self._buf.append(nb)

"""DeviceWindowOperator: the vectorized engines inside the framework.

Makes `keyBy().window(...).aggregate(device_agg)` run on the TPU hot
path (flink_tpu.streaming.vectorized / vectorized_sessions) while
living as a normal operator in the task layer.  Two doors feed the
engine: a RecordBatch goes in whole (`process_batch`: key, value and
timestamp columns, no per-row objects); scalar records buffer on the
host and every watermark (and every `flush_batch` records) flushes
them as one such batch.  A fire leaves as ONE RecordBatch per window
with the scalar operator's timestamp contract (window.maxTimestamp —
ref: WindowOperator.java:544 emitWindowContents), per-row records when
the rows do not columnarize.  Checkpoints snapshot the engine (device arrays
DMA'd to host + host indexes) so barrier checkpointing, recovery, and
restarts work identically to the scalar path.

Which jobs get this operator (DeviceAggregateFunction + an aligned
event-time assigner + default trigger, no evictor, lateness 0) and
which engine it hosts are decided in flink_tpu.streaming.window_engines;
anything else stays on the scalar WindowOperator — same split the
reference drew between its (removed) aligned-window fast operators and
the general WindowOperator (WindowOperator.java:192-195).  What is
this operator's own: the two doors, the watermark policy and the emit
tail.
"""

from __future__ import annotations

from typing import Any, List, Optional

import numpy as np

from flink_tpu.ops.device_agg import DeviceAggregateFunction
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu.streaming import columnar
from flink_tpu.streaming.elements import (MAX_TIMESTAMP, RecordBatch,
    StreamRecord, Watermark)
from flink_tpu.streaming.vectorized import hash_keys_np
from flink_tpu.streaming.window_engines import (
    WindowEngineHost,
    string_sum_fits,
)
from flink_tpu.streaming.window_operator import _FireBufferOutput
from flink_tpu.streaming.windowing import TimeWindow


class DeviceWindowOperator(WindowEngineHost):
    """Batched, device-backed twin of WindowOperator for the eligible
    aggregate path.  The key selector is applied per record at buffer
    time, per column at the batch door (the operator IS the keyed
    state; no keyed backend needed)."""

    engine_key, tier_key = "device_engine", "device_tier"
    mesh_scatter = True

    def __init__(self, assigner, aggregate_function: DeviceAggregateFunction,
                 window_function=None, flush_batch: int = 8192,
                 initial_capacity: int = 1 << 14, mesh=None,
                 mesh_axis: str = "kg"):
        super().__init__(assigner, aggregate_function, initial_capacity,
                         mesh, mesh_axis)
        self.window_function = window_function
        self.flush_batch = flush_batch
        self._keys: List[Any] = []
        self._ts: List[int] = []
        self._values: List[Any] = []
        self._last_fireable = None
        #: rows the fires handed on with no StreamRecord of their own
        #: / wrapped in one on their way (no window function this
        #: operator takes writes through a collector: always 0)
        self.fire_rows_direct = 0
        self.fire_rows_via_records = 0
        # string keys dictionary-encode to dense uint64 ids in ONE C++
        # pass per batch (native.NativeStringInterner), so
        # keyBy("word") over strings rides the integer-keyed fast
        # tiers; emission maps ids back through _id_to_key (ref shape:
        # SocketWindowWordCount.java:70-84)
        self._interner = None
        self._id_to_key: List[Any] = []

    # ---- lifecycle --------------------------------------------------
    def open(self):
        if self.shape is None:
            # fail fast at open, not at the first flush
            raise ValueError(
                f"no device engine for assigner {self.assigner!r}")
        # metric parity with the scalar WindowOperator (ref:
        # WindowOperator.java:138 numLateRecordsDropped); reset = this
        # execution attempt
        self._emit_batch_hist = None
        if self.metrics is not None:
            c = self.metrics.counter("numLateRecordsDropped")
            c.count = 0
            self._emit_batch_hist = self.metrics.histogram("emitBatchSize")

    # ---- input ------------------------------------------------------
    def process_element(self, record: StreamRecord):
        if record.timestamp is None:
            raise ValueError(
                "device window operator requires event-time records "
                "(assign timestamps upstream)")
        self._keys.append(self.key_selector.get_key(record.value)
                          if self.key_selector is not None else record.value)
        self._ts.append(record.timestamp)
        self._values.append(record.value)
        if len(self._keys) >= self.flush_batch:
            with get_tracer().phase("window.ingest", rows=len(self._keys)):
                self._flush_buffer()

    def process_batch(self, batch) -> None:
        """The batch door: key, value and timestamp columns go to the
        engine as they are — no per-row object, nothing buffered."""
        n = len(batch)
        if n == 0:
            return
        tracer = get_tracer()
        with tracer.phase("window.ingest", rows=n):
            # records the scalar door buffered came first
            self._flush_buffer()
            with tracer.phase("device_window.columns"):
                keys, vals = self._batch_columns(batch, n)
            self._feed_engine(keys, batch.ts, vals)
            self._note_columnar(n)

    def _batch_columns(self, batch, n: int):
        """(keys, values) of a batch as the arrays the record door's
        flush would have built from its rows."""
        if batch.ts is None or (batch.ts_mask is not None
                                and not batch.ts_mask.all()):
            raise ValueError(
                "device window operator requires event-time records "
                "(assign timestamps upstream)")
        sel = self.key_selector
        rows = None  # boxed row values, made at most once
        keys = columnar.field_key_column(sel, batch)
        if keys is None:
            rows = batch.row_values()
            keys = np.asarray([sel.get_key(v) for v in rows]
                              if sel is not None else rows)
        elif keys.dtype == object:
            # a column of strings: the dtype numpy infers from the
            # cells decides the engine tier, as at the record door
            keys = np.asarray(keys.tolist())
        vals = None
        agg = self.agg
        if agg.needs_value or agg.needs_value_hash:
            col = agg.extract_column(batch.value_arrays())
            if isinstance(col, np.ndarray) and col.ndim == 1 \
                    and len(col) == n:
                vals = col
            else:
                vals = self._extract_values(
                    rows if rows is not None else batch.row_values())
        return self._maybe_intern(keys), vals

    def _feeds_raw_strings(self) -> bool:
        """The fused string-sum engine consumes raw strings (intern +
        dense sum in one C++ pass) and emits the original words itself:
        no interning here where the ladder will pick it — or has, and
        later batches must keep feeding it raw strings."""
        if self.engine is not None:
            return self.tier == "string_sum"
        return self.mesh is None and string_sum_fits(self.shape, self.agg)

    def _flush_buffer(self):
        if not self._keys:
            return
        with get_tracer().phase("device_window.flush",
                                batch=len(self._keys)):
            agg = self.agg
            vals = None
            if agg.needs_value or agg.needs_value_hash:
                vals = self._extract_values(self._values)
            self._feed_engine(self._maybe_intern(np.asarray(self._keys)),
                              np.asarray(self._ts, np.int64), vals)
            self._keys.clear()
            self._ts.clear()
            self._values.clear()

    def _extract_values(self, values: list) -> np.ndarray:
        extract = self.agg.extract_value
        # overridden either on the class or per-instance (a plain
        # function set on the instance has no __func__)
        if getattr(extract, "__func__",
                   None) is not DeviceAggregateFunction.extract_value:
            values = [extract(v) for v in values]
        return np.asarray(values)

    def _feed_engine(self, keys_arr, ts, vals) -> None:
        """One batch into the engine, whichever door it came by; the
        value column is hashed here, once, where the aggregate is a
        distinct-count sketch."""
        if self.engine is None:
            # composite keys coerce to 2-D string arrays whose rows
            # are tuples: not a string column
            self._build_engine(keys_arr.dtype if keys_arr.ndim == 1
                               else np.dtype(object))
        hashes = None
        if self.agg.needs_value_hash:
            with get_tracer().phase("columnar.ingest.hash"):
                hashes = hash_keys_np(vals)
            if not self.agg.needs_value:
                vals = None
        self.engine.process_batch(keys_arr, ts, vals, value_hashes=hashes)

    def _maybe_intern(self, keys_arr: np.ndarray) -> np.ndarray:
        """Dictionary-encode fixed-width string keys to dense uint64
        ids (first batch decides; later batches coerce to the locked
        representation).  Without the native runtime the raw keys pass
        through to the object-key fallback path."""
        if self._interner is None:
            # 1-D only: composite keys coerce to 2-D string arrays
            # whose rows must stay tuples on emission
            if keys_arr.dtype.kind not in "US" or keys_arr.ndim != 1:
                return keys_arr
            import flink_tpu.native as nat
            if not nat.available():
                return keys_arr
            if self._feeds_raw_strings():
                return keys_arr
            self._interner = nat.NativeStringInterner()
        elif keys_arr.dtype.kind not in "US":
            keys_arr = keys_arr.astype(np.str_)
        ids, first_idx = self._interner.intern(keys_arr)
        if len(first_idx):
            self._id_to_key.extend(keys_arr[first_idx].tolist())
        return ids

    def process_watermark(self, watermark: Watermark):
        # Fires only happen when the watermark crosses a window-end
        # boundary (multiples of size/slide for the aligned engines).
        # Upstreams may emit a watermark per ELEMENT; paying a device
        # flush + advance (or even a phase) for each would serialize
        # the pipeline on per-record work.  Between boundaries nothing
        # can fire, so the watermark forwards without touching the
        # engine.
        wm = watermark.timestamp
        grid = self.shape.grid
        if grid is not None and wm != MAX_TIMESTAMP:
            fireable = ((wm + 1) // grid) * grid if wm >= 0 else None
            if fireable is not None and fireable == self._last_fireable:
                self.current_watermark = wm
                self.output.emit_watermark(watermark)
                return
            self._last_fireable = fireable
        with get_tracer().phase("window.watermark", watermark=wm):
            self._flush_buffer()
            if self.engine is not None:
                self._fire(wm)
            self.current_watermark = wm
            self.output.emit_watermark(watermark)

    def _fire(self, wm: int) -> None:
        engine = self.engine
        as_arrays = getattr(engine, "emit_arrays", False)
        with get_tracer().phase("device_window.fire", watermark=wm):
            engine.advance_watermark(wm)
        if as_arrays:
            fires, engine.fired = engine.fired, []
        else:
            fires = [tuple(zip(*engine.emitted))] if engine.emitted else []
            del engine.emitted[:]
        if fires:
            get_tracer().note_fire(
                self.operator_id or type(self).__name__, len(fires),
                sum(len(f[0]) for f in fires),
                int(max(np.max(f[3]) for f in fires)))
        self._emit_fires(fires)
        self.num_late_records_dropped = engine.num_late_dropped
        if self.metrics is not None:
            self.metrics.counter(
                "numLateRecordsDropped").count = engine.num_late_dropped

    def _emit_fires(self, fires) -> None:
        """Each fire — (keys, results, window start, window end), the
        last two scalars, or one per key from a session engine —
        leaves as ONE RecordBatch: the result column itself, or what
        the window function returned for every key, its rows straight
        into the fire buffer and columnarized there (per-row records
        where that does not fit)."""
        if self._emit_batch_hist is not None and fires:
            self._emit_batch_hist.update(sum(len(f[0]) for f in fires))
        tracer = get_tracer()
        fn = self.window_function
        id_to_key = self._id_to_key if self._interner is not None else None
        for keys, results, starts, ends in fires:
            n = len(keys)
            one_window = np.ndim(starts) == 0
            if fn is None and n > 1 and columnar.PIPELINE_ENABLED \
                    and isinstance(results, np.ndarray) \
                    and results.ndim == 1 and results.dtype.kind in "iuf":
                with tracer.phase("window.fire.batch", keys=n,
                                  fire_rows_direct=n,
                                  fire_rows_via_records=0):
                    out = RecordBatch(
                        {"v": results},
                        np.full(n, ends - 1, np.int64) if one_window
                        else np.asarray(ends, np.int64) - 1)
                self.fire_rows_direct += n
                with tracer.phase("window.fire.downstream"):
                    self.output.collect_batch(out)
                continue
            buf = _FireBufferOutput(self.output)
            owned = []  # the columns made here, freed in the release
            with tracer.phase("window.fire.batch", keys=n) as phase:
                # python scalars, as the scalar operator hands them on
                if isinstance(keys, np.ndarray) and keys.ndim == 1:
                    keys = keys.tolist()
                    owned.append(keys)
                if id_to_key is not None:
                    keys = [id_to_key[k] for k in keys]
                    owned.append(keys)
                if isinstance(results, np.ndarray) and results.ndim == 1:
                    results = results.tolist()
                    owned.append(results)
                if one_window:
                    buf.emit_fired(fn, keys, results, True,
                                   window=TimeWindow(starts, ends))
                else:
                    buf.emit_fired(
                        fn, keys, results, True, windows=list(map(
                            TimeWindow, np.asarray(starts).tolist(),
                            np.asarray(ends).tolist())))
                buf.book(self, phase)
            buf.flush()
            buf.release(*owned)

    # ---- checkpoint -------------------------------------------------
    def snapshot_state(self, checkpoint_id: Optional[int] = None) -> dict:
        self._flush_buffer()
        snap = super().snapshot_state(checkpoint_id)
        if self._interner is not None:
            # ids are dense first-seen: the directory alone rebuilds
            # the interner on restore (re-interning in order
            # reproduces every id)
            snap["string_key_directory"] = list(self._id_to_key)
        return snap

    def restore_state(self, snapshots) -> None:
        directories = [s["string_key_directory"] for s in snapshots
                       if s.get("string_key_directory") is not None]
        if directories and self._resplits(snapshots):
            raise ValueError(
                "device window operator cannot re-split "
                "dictionary-encoded string-keyed engine state "
                "across a parallelism change; restore at the "
                "checkpointed parallelism")
        super().restore_state(snapshots)
        import flink_tpu.native as nat
        for directory in directories:
            self._interner = nat.NativeStringInterner(
                max(16, 2 * len(directory)))
            self._id_to_key = list(directory)
            if directory:
                ids, _ = self._interner.intern(np.asarray(directory))
                assert int(ids[-1]) == len(directory) - 1

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

Eligibility is decided by the graph builder (see
WindowedStream._build): DeviceAggregateFunction + event-time
tumbling/sliding/session assigner + default trigger, no evictor,
lateness 0.  Anything else stays on the scalar WindowOperator — same
split the reference drew between its (removed) aligned-window fast
operators and the general WindowOperator (WindowOperator.java:192-195).
"""

from __future__ import annotations

from typing import Any, List, Optional

import numpy as np

from flink_tpu.ops.device_agg import DeviceAggregateFunction
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu.streaming import columnar
from flink_tpu.streaming.elements import (MAX_TIMESTAMP, RecordBatch,
    StreamRecord, Watermark)
from flink_tpu.streaming.operators import StreamOperator
from flink_tpu.streaming.vectorized import (
    VectorizedSlidingWindows,
    VectorizedTumblingWindows,
    hash_keys_np,
)
from flink_tpu.streaming.vectorized_sessions import VectorizedSessionWindows
from flink_tpu.streaming.window_operator import _FireBufferOutput
from flink_tpu.streaming.windowing import (
    EventTimeSessionWindows,
    SlidingEventTimeWindows,
    TimeWindow,
    TumblingEventTimeWindows,
)


def assigner_supported(assigner) -> bool:
    """Shape check shared by the fail-fast open() and the planner: the
    assigners the single-device engines (either tier) cover."""
    if isinstance(assigner, TumblingEventTimeWindows):
        return assigner.offset == 0
    if isinstance(assigner, SlidingEventTimeWindows):
        return assigner.offset == 0 and assigner.size % assigner.slide == 0
    return isinstance(assigner, EventTimeSessionWindows)


def string_sum_engine_for_assigner(assigner, agg: DeviceAggregateFunction):
    """Fused intern+sum engine for STRING-keyed tumbling sums, or None
    when the shape doesn't fit.  Floating accumulation only: the C++
    kernel sums in double, so integer value dtypes (exact beyond 2^53)
    must stay on the exact tiers."""
    from flink_tpu.ops.device_agg import SumAggregate
    from flink_tpu.streaming.log_windows import StringSumTumblingWindows
    if (isinstance(agg, SumAggregate)
            and np.issubdtype(agg.value_dtype, np.floating)
            and isinstance(assigner, TumblingEventTimeWindows)
            and assigner.offset == 0):
        return StringSumTumblingWindows(agg, assigner.size)
    return None


def log_engine_for_assigner(assigner, agg: DeviceAggregateFunction):
    """Log-structured combiner tier for this assigner+aggregate, or
    None when the cell decomposition / assigner shape doesn't fit
    (streaming/log_windows.py scope: integer keys, HLL/Sum/Quantile
    cells, Count-Min sessions).  A missing native runtime is an error
    (the engines raise RuntimeError), never a reason to hand the job
    to another engine."""
    from flink_tpu.streaming import log_windows as lw
    try:
        if isinstance(assigner, TumblingEventTimeWindows) \
                and assigner.offset == 0:
            return lw.LogStructuredTumblingWindows(agg, assigner.size)
        if (isinstance(assigner, SlidingEventTimeWindows)
                and assigner.offset == 0
                and assigner.size % assigner.slide == 0):
            return lw.LogStructuredSlidingWindows(agg, assigner.size,
                                                  assigner.slide)
        if isinstance(assigner, EventTimeSessionWindows):
            return lw.LogStructuredSessionWindows(agg, assigner.gap)
    except (TypeError, ValueError):
        pass  # unsupported cell decomposition / params
    return None


def engine_for_assigner(assigner, agg: DeviceAggregateFunction,
                        initial_capacity: int = 1 << 14, mesh=None,
                        mesh_axis: str = "kg", max_parallelism: int = 128):
    """Assigner → engine, or None when no device engine applies.  With
    a mesh, tumbling windows run on the sharded multi-window engine
    (SPMD over the mesh axis, flink_tpu.parallel.mesh_windows); other
    assigners fall back to the single-device engines."""
    if isinstance(assigner, TumblingEventTimeWindows) and assigner.offset == 0:
        if mesh is not None:
            from flink_tpu.parallel.mesh_windows import MeshTumblingWindows
            return MeshTumblingWindows(
                agg, assigner.size, mesh, axis=mesh_axis,
                max_parallelism=max_parallelism,
                capacity_per_window_shard=max(
                    1 << 8, initial_capacity // mesh.shape[mesh_axis]))
        return VectorizedTumblingWindows(agg, assigner.size,
                                         initial_capacity=initial_capacity)
    if isinstance(assigner, SlidingEventTimeWindows):
        if assigner.size % assigner.slide == 0 and assigner.offset == 0:
            if mesh is not None:
                from flink_tpu.parallel.mesh_windows import (
                    MeshSlidingWindows,
                )
                return MeshSlidingWindows(
                    agg, assigner.size, assigner.slide, mesh,
                    axis=mesh_axis, max_parallelism=max_parallelism,
                    capacity_per_window_shard=max(
                        1 << 8, initial_capacity // mesh.shape[mesh_axis]))
            return VectorizedSlidingWindows(agg, assigner.size,
                                            assigner.slide,
                                            initial_capacity=initial_capacity)
        return None
    if isinstance(assigner, EventTimeSessionWindows):
        return VectorizedSessionWindows(agg, assigner.gap,
                                        initial_capacity=initial_capacity)
    return None


def is_mesh_factory(mesh) -> bool:
    """True for a callable that BUILDS a mesh (the pod-topology
    per-process factory) as opposed to a Mesh instance — jax's Mesh is
    itself callable (a context decorator), so `callable` alone cannot
    discriminate; factories have no device grid `.shape`."""
    return callable(mesh) and not hasattr(mesh, "shape")


def resolve_mesh(mesh):
    """Mesh | mesh-factory | None → Mesh | None (factories resolve in
    the CURRENT process; device handles cannot ride a pickled graph)."""
    return mesh() if is_mesh_factory(mesh) else mesh


def is_device_eligible(assigner, aggregate_function, trigger, evictor,
                       allowed_lateness, late_tag, window_function) -> bool:
    """The graph-builder gate for the device fast path."""
    if not isinstance(aggregate_function, DeviceAggregateFunction):
        return False
    if trigger is not None or evictor is not None:
        return False
    if allowed_lateness != 0 or late_tag is not None:
        return False
    if window_function is not None and not callable(window_function):
        return False
    if isinstance(assigner, SlidingEventTimeWindows):
        return assigner.size % assigner.slide == 0 and assigner.offset == 0
    if isinstance(assigner, TumblingEventTimeWindows):
        return assigner.offset == 0
    return isinstance(assigner, EventTimeSessionWindows)


class DeviceWindowOperator(StreamOperator):
    """Batched, device-backed twin of WindowOperator for the eligible
    aggregate path.  The key selector is applied per record at buffer
    time, per column at the batch door (the operator IS the keyed
    state; no keyed backend needed)."""

    def __init__(self, assigner, aggregate_function: DeviceAggregateFunction,
                 window_function=None, flush_batch: int = 8192,
                 initial_capacity: int = 1 << 14, mesh=None,
                 mesh_axis: str = "kg"):
        super().__init__()
        self.assigner = assigner
        self.agg = aggregate_function
        self.window_function = window_function
        self.flush_batch = flush_batch
        self.initial_capacity = initial_capacity
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.engine = None
        self._keys: List[Any] = []
        self._ts: List[int] = []
        self._values: List[Any] = []
        self._last_fireable = None
        self.num_late_records_dropped = 0  # metric parity
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
        if not assigner_supported(self.assigner):
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
    def set_key_context(self, record):
        pass  # no keyed backend; keys resolve vectorized at flush

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

    def _wants_fused_string_sum(self) -> bool:
        from flink_tpu.ops.device_agg import SumAggregate
        from flink_tpu.streaming.log_windows import StringSumTumblingWindows
        if self.engine is not None:
            # locked at first flush; later batches must keep feeding
            # the fused engine raw strings
            return isinstance(self.engine, StringSumTumblingWindows)
        return (self.mesh is None
                and isinstance(self.agg, SumAggregate)
                and np.issubdtype(self.agg.value_dtype, np.floating)
                and isinstance(self.assigner, TumblingEventTimeWindows)
                and self.assigner.offset == 0)

    def _ensure_engine(self, keys_arr: np.ndarray):
        """Tier selection on the first flush: integer-keyed streams get
        the log-structured combiner tier when the aggregate has a cell
        decomposition (string keys reach it through the interner);
        string-keyed tumbling sums get the fused wordcount engine;
        everything else (and every aggregate the log tier doesn't
        cover) runs the device-resident scatter tier.  With a mesh,
        the sharded twins take over: the mesh log tier (all_to_all
        keyBy exchange + per-shard log fires, parallel/mesh_log.py)
        when eligible, else the sharded scatter engines."""
        if self.engine is not None:
            return
        self.mesh = resolve_mesh(self.mesh)
        if self.mesh is not None:
            if np.issubdtype(keys_arr.dtype, np.integer):
                from flink_tpu.parallel.mesh_log import (
                    mesh_log_engine_for_assigner,
                )
                self.engine = mesh_log_engine_for_assigner(
                    self.assigner, self.agg, self.mesh,
                    axis=self.mesh_axis,
                    max_parallelism=self.max_parallelism)
            if self.engine is None:
                self.engine = engine_for_assigner(
                    self.assigner, self.agg, self.initial_capacity,
                    mesh=self.mesh, mesh_axis=self.mesh_axis,
                    max_parallelism=self.max_parallelism)
            if self.engine is None:
                raise ValueError(
                    f"no device engine for assigner {self.assigner!r}")
        if self.engine is None \
                and keys_arr.dtype.kind in "US" and keys_arr.ndim == 1 \
                and self._wants_fused_string_sum():
            self.engine = string_sum_engine_for_assigner(self.assigner,
                                                         self.agg)
        if self.engine is None and np.issubdtype(keys_arr.dtype, np.integer):
            self.engine = log_engine_for_assigner(self.assigner, self.agg)
        if self.engine is None:
            self.engine = engine_for_assigner(self.assigner, self.agg,
                                              self.initial_capacity)
        if self.engine is None:
            raise ValueError(
                f"no device engine for assigner {self.assigner!r}")
        # fast-forward a lazily created engine to the operator's
        # watermark — records behind it must count as LATE, not be
        # aggregated into windows that already passed downstream
        wm = getattr(self, "current_watermark", None)
        if wm is not None and wm > -(2 ** 63):
            self.engine.advance_watermark(wm)

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
        self._ensure_engine(keys_arr)
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
            if self._wants_fused_string_sum():
                # the fused wordcount engine consumes raw strings
                # (intern + dense sum in one C++ pass) and emits the
                # original words itself
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
        grid = self._fire_grid()
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
        # engines that can hand a fire over as arrays do; the rest
        # (VectorizedSessionWindows) deliver one tuple per result
        as_arrays = hasattr(engine, "fired")
        if as_arrays:
            engine.emit_arrays = True
        with get_tracer().phase("device_window.fire", watermark=wm):
            engine.advance_watermark(wm)
        if as_arrays:
            fires, engine.fired = engine.fired, []
        else:
            fires = [tuple(zip(*engine.emitted))] if engine.emitted else []
            del engine.emitted[:]
        self._emit_fires(fires)
        self.num_late_records_dropped = engine.num_late_dropped
        if self.metrics is not None:
            self.metrics.counter(
                "numLateRecordsDropped").count = engine.num_late_dropped

    def _fire_grid(self):
        """Window-end alignment grid of the assigner, or None when
        fires can happen at arbitrary times (sessions)."""
        if isinstance(self.assigner, SlidingEventTimeWindows):
            return self.assigner.slide
        if isinstance(self.assigner, TumblingEventTimeWindows):
            return self.assigner.size
        return None

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
            with tracer.phase("window.fire.batch", keys=n) as phase:
                # python scalars, as the scalar operator hands them on
                if isinstance(keys, np.ndarray) and keys.ndim == 1:
                    keys = keys.tolist()
                if id_to_key is not None:
                    keys = [id_to_key[k] for k in keys]
                if isinstance(results, np.ndarray) and results.ndim == 1:
                    results = results.tolist()
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

    # ---- checkpoint -------------------------------------------------
    def snapshot_state(self, checkpoint_id: Optional[int] = None) -> dict:
        self._flush_buffer()
        snap = super().snapshot_state(checkpoint_id)
        if self.engine is not None:
            from flink_tpu.parallel.mesh_log import _MeshShardedLogEngine
            from flink_tpu.streaming import log_windows as lw
            snap["device_engine"] = self.engine.snapshot()
            if isinstance(self.engine, lw.StringSumTumblingWindows):
                snap["device_tier"] = "string_sum"
            elif isinstance(self.engine, _MeshShardedLogEngine):
                snap["device_tier"] = "mesh_log"
            elif isinstance(self.engine, (lw.LogStructuredTumblingWindows,
                                          lw.LogStructuredSessionWindows)):
                snap["device_tier"] = "log"
            else:
                snap["device_tier"] = "vectorized"
        if self._interner is not None:
            # ids are dense first-seen: the directory alone rebuilds
            # the interner on restore (re-interning in order
            # reproduces every id)
            snap["string_key_directory"] = list(self._id_to_key)
        return snap

    def _kg_keep_fn(self):
        """Key-group filter for rescaled restores (the shared
        definition, so re-split engine state lands where the runtime's
        keyBy partitioner routes live records)."""
        from flink_tpu.core.keygroups import make_key_group_keep_fn
        return make_key_group_keep_fn(self.max_parallelism,
                                      self.num_subtasks,
                                      self.subtask_index)

    def restore_state(self, snapshots) -> None:
        super().restore_state(snapshots)
        engine_snaps = [s for s in snapshots if "device_engine" in s]
        rescaled = any(
            s.get("restore_old_parallelism", self.num_subtasks)
            != self.num_subtasks for s in snapshots)
        if rescaled or len(engine_snaps) > 1:
            if any(s.get("string_key_directory") is not None
                   for s in snapshots):
                raise ValueError(
                    "device window operator cannot re-split "
                    "dictionary-encoded string-keyed engine state "
                    "across a parallelism change; restore at the "
                    "checkpointed parallelism")
            tiers = {s.get("device_tier") for s in engine_snaps}
            if len(tiers) > 1:
                raise ValueError(
                    f"snapshots span engine tiers {sorted(tiers)}")
            if engine_snaps:
                tier = tiers.pop()
                if self.engine is None:
                    if tier == "log":
                        self.engine = log_engine_for_assigner(
                            self.assigner, self.agg)
                    elif tier == "string_sum":
                        self.engine = string_sum_engine_for_assigner(
                            self.assigner, self.agg)
                    if self.engine is None \
                            or not hasattr(self.engine, "restore_many"):
                        raise ValueError(
                            f"the {tier!r} engine tier cannot re-split "
                            "its state across a parallelism change; "
                            "restore at the checkpointed parallelism")
                self.engine.restore_many(
                    [s["device_engine"] for s in engine_snaps],
                    keep_fn=self._kg_keep_fn())
            return
        for s in snapshots:
            if s.get("string_key_directory") is not None:
                import flink_tpu.native as nat
                directory = s["string_key_directory"]
                self._interner = nat.NativeStringInterner(
                    max(16, 2 * len(directory)))
                self._id_to_key = list(directory)
                if directory:
                    ids, _ = self._interner.intern(np.asarray(directory))
                    assert int(ids[-1]) == len(directory) - 1
            if "device_engine" in s:
                if self.engine is None:
                    if s.get("device_tier") == "string_sum":
                        from flink_tpu.streaming.log_windows import (
                            StringSumTumblingWindows,
                        )
                        self.engine = StringSumTumblingWindows(
                            self.agg, self.assigner.size)
                    elif s.get("device_tier") == "log":
                        self.engine = log_engine_for_assigner(
                            self.assigner, self.agg)
                        if self.engine is None:
                            raise RuntimeError(
                                "checkpoint was taken on the log engine "
                                "tier, which is unavailable here (native "
                                "runtime required)")
                    elif s.get("device_tier") == "mesh_log":
                        from flink_tpu.parallel.mesh_log import (
                            mesh_log_engine_for_assigner,
                        )
                        self.mesh = resolve_mesh(self.mesh)
                        if self.mesh is None:
                            raise RuntimeError(
                                "checkpoint was taken on the mesh log "
                                "tier; restoring requires a mesh "
                                "(env.set_mesh)")
                        self.engine = mesh_log_engine_for_assigner(
                            self.assigner, self.agg, self.mesh,
                            axis=self.mesh_axis,
                            max_parallelism=self.max_parallelism)
                        if self.engine is None:
                            raise RuntimeError(
                                "checkpoint was taken on the mesh log "
                                "tier, which is unavailable here "
                                "(native runtime required)")
                    else:
                        self.engine = engine_for_assigner(
                            self.assigner, self.agg, self.initial_capacity,
                            mesh=self.mesh, mesh_axis=self.mesh_axis,
                            max_parallelism=self.max_parallelism)
                self.engine.restore(s["device_engine"])

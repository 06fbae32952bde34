"""Stream operators: lifecycle + the stateless/keyed operator family.

Re-designs flink-streaming-java/.../api/operators/:
AbstractStreamOperator (state/timer plumbing), AbstractUdfStreamOperator,
StreamMap/StreamFlatMap/StreamFilter, ProcessOperator,
KeyedProcessOperator, StreamGroupedReduce, StreamSink, and the co-
(two-input) operators.  An operator receives StreamElements from its
input(s) and emits to an `Output`; chains of operators are built by the
task layer (ref: OperatorChain.java).
"""

from __future__ import annotations

import abc
import logging
import threading
import time as _time_mod
from typing import List, Optional, TypeVar

from flink_tpu.core.functions import (
    KeySelector,
    ReduceFunction,
    RichFunction,
)
from flink_tpu.core.state import ReducingStateDescriptor, StateDescriptor
from flink_tpu.state.backend import VOID_NAMESPACE, KeyedStateBackend
from flink_tpu.state.operator_state import OperatorStateBackend
from flink_tpu.streaming.elements import (
    MAX_TIMESTAMP,
    MIN_TIMESTAMP,
    LatencyMarker,
    StreamRecord,
    Watermark,
)
from flink_tpu.streaming.timers import (
    InternalTimerService,
    ProcessingTimeService,
)

IN = TypeVar("IN")
OUT = TypeVar("OUT")

log = logging.getLogger("flink_tpu.operators")


class _KernelStats:
    """Process-wide first-batch probe accounting for the map/filter
    column kernels.  The differential typeflow suite asserts
    ``probes == 0`` for statically proven chains."""

    __slots__ = ("probes", "static_skips")

    def __init__(self):
        self.reset()

    def reset(self):
        self.probes = 0
        self.static_skips = 0


KERNEL_STATS = _KernelStats()

#: (operator class name, reason prefix) pairs already warned about —
#: the boxed fallback is once-per-class noise, not per-instance spam
_FALLBACK_WARNED = set()


class OutputTag:
    """Side-output tag (ref: org.apache.flink.util.OutputTag)."""

    __slots__ = ("tag_id",)

    def __init__(self, tag_id: str):
        self.tag_id = tag_id

    def __eq__(self, other):
        return isinstance(other, OutputTag) and self.tag_id == other.tag_id

    def __hash__(self):
        return hash(self.tag_id)

    def __repr__(self):
        return f"OutputTag({self.tag_id!r})"


class Output(abc.ABC):
    """Where an operator emits (ref: Output.java extends Collector)."""

    @abc.abstractmethod
    def collect(self, record: StreamRecord) -> None: ...

    @abc.abstractmethod
    def emit_watermark(self, watermark: Watermark) -> None: ...

    def collect_batch(self, batch) -> None:
        """Emit a whole RecordBatch element.  Default: box into
        per-row records — outputs that can carry batches natively
        (chained operators, the router) override this, so once a
        batch survives an operator nothing downstream reboxes it."""
        for record in batch.to_records():
            self.collect(record)

    def collect_side(self, tag: OutputTag, record: StreamRecord) -> None:
        pass  # dropped unless a side output is wired

    def emit_latency_marker(self, marker: LatencyMarker) -> None:  # noqa: B027
        pass

    def close(self) -> None:  # noqa: B027
        pass


class CollectorOutput(Output):
    """Buffers emissions in lists — test harness + chain tails."""

    def __init__(self):
        self.records: List[StreamRecord] = []
        self.watermarks: List[Watermark] = []
        self.side: dict = {}
        self.latency_markers: List[LatencyMarker] = []

    def collect(self, record):
        self.records.append(record)

    def emit_watermark(self, watermark):
        self.watermarks.append(watermark)

    def collect_side(self, tag, record):
        self.side.setdefault(tag.tag_id, []).append(record)

    def emit_latency_marker(self, marker):
        self.latency_markers.append(marker)

    def extract_values(self):
        return [r.value for r in self.records]


class TimestampedCollector:
    """Collector bound to one timestamp (ref:
    api/operators/TimestampedCollector.java)."""

    __slots__ = ("_output", "timestamp")

    def __init__(self, output: Output, timestamp: Optional[int] = None):
        self._output = output
        self.timestamp = timestamp

    def collect(self, value) -> None:
        self._output.collect(StreamRecord(value, self.timestamp))

    def set_absolute_timestamp(self, ts: Optional[int]) -> None:
        self.timestamp = ts


class StreamOperator(abc.ABC):
    """Operator lifecycle (ref: StreamOperator.java + lifecycle docs
    docs/internals/task_lifecycle.md): setup → open → process* →
    snapshot* → close → dispose."""

    #: chain_fusion.FusedChainProgram anchored at this operator, or
    #: None — a class attribute so the hot-path check in the task
    #: layer is one attribute load with no per-instance cost
    _fused_chain = None
    #: the FusedChainProgram this operator is a MEMBER of (any
    #: position in the chain, not just the anchor); cleared on demote
    _fused_member = None

    def __init__(self):
        self.output: Optional[Output] = None
        self.keyed_backend: Optional[KeyedStateBackend] = None
        self.operator_state_backend: Optional[OperatorStateBackend] = None
        self.processing_time_service: Optional[ProcessingTimeService] = None
        self.timer_service: Optional[InternalTimerService] = None
        self.current_watermark: int = MIN_TIMESTAMP
        self.key_selector: Optional[KeySelector] = None
        self.operator_id: str = ""
        self.metrics = None  # OperatorMetricGroup, set by task layer
        self.subtask_index: int = 0
        self.num_subtasks: int = 1
        self.max_parallelism: int = 128
        # columnar-pipeline accounting (over rows DELIVERED AS
        # BATCHES; pure row streams leave the ratio undefined)
        self.columnar_rows: int = 0
        self.boxed_rows: int = 0
        self.boxed_fallbacks: int = 0
        self.columnar_fallback_reason: Optional[str] = None
        self._boxed_fallbacks_counter = None
        # who decided the column-kernel path: "static" (typeflow
        # verdict, probe skipped), "probe" (first-batch probe) or
        # "fused" (member of a chain_fusion program)
        self.columnar_decided_by: Optional[str] = None
        self.kernel_probes: int = 0
        # rows this operator processed INSIDE a fused chain program
        # (counted into columnar_rows too: fused is a strict subset
        # of the columnar path)
        self.fused_rows: int = 0

    # ---- wiring -----------------------------------------------------
    def setup(self, output: Output,
              keyed_backend: Optional[KeyedStateBackend] = None,
              operator_state_backend: Optional[OperatorStateBackend] = None,
              processing_time_service: Optional[ProcessingTimeService] = None,
              key_selector: Optional[KeySelector] = None,
              operator_id: str = "",
              subtask_index: int = 0,
              num_subtasks: int = 1,
              max_parallelism: int = 128) -> None:
        self.output = output
        self.keyed_backend = keyed_backend
        self.operator_state_backend = operator_state_backend or OperatorStateBackend()
        self.processing_time_service = processing_time_service
        self.key_selector = key_selector
        self.operator_id = operator_id or type(self).__name__
        self.subtask_index = subtask_index
        self.num_subtasks = num_subtasks
        self.max_parallelism = max_parallelism
        if keyed_backend is not None and processing_time_service is not None:
            self.timer_service = InternalTimerService(
                f"{self.operator_id}-timers", keyed_backend,
                processing_time_service, self)

    def register_standard_metrics(self, group) -> None:
        """Attach the operator's MetricGroup and publish the standard
        pipeline-health gauges every operator gets for free:
        ``currentWatermark`` and ``watermarkLag`` (event-time vs wall
        clock, ms) — the per-operator lag the web monitor and
        Prometheus endpoint surface (ref: the reference's
        currentInputWatermark / task metric group)."""
        self.metrics = group
        group.gauge("currentWatermark", lambda: self.current_watermark)
        group.gauge("watermarkLag", self._watermark_lag_ms)
        col = group.add_group("columnar")
        col.gauge("ratio", self._columnar_ratio)
        col.gauge("fused_ratio", self._fused_ratio)
        col.gauge("fallback_reason",
                  lambda: self.columnar_fallback_reason or "")
        col.gauge("decided_by",
                  lambda: self.columnar_decided_by or "")
        col.gauge("probes", lambda: self.kernel_probes)
        self._boxed_fallbacks_counter = col.counter("boxed_fallbacks")
        self._boxed_fallbacks_counter.count = self.boxed_fallbacks

    def _columnar_ratio(self):
        total = self.columnar_rows + self.boxed_rows
        if total == 0:
            return None  # never saw a batch: ratio undefined
        return self.columnar_rows / total

    def _fused_ratio(self):
        total = self.columnar_rows + self.boxed_rows
        if total == 0:
            return None  # never saw a batch: ratio undefined
        return self.fused_rows / total

    def _note_columnar(self, n: int) -> None:
        self.columnar_rows += n

    def _note_fused(self, n: int) -> None:
        """Rows handled inside a fused chain program on this
        operator's behalf — its own kernel never dispatched."""
        self.fused_rows += n
        self.columnar_rows += n
        self.columnar_decided_by = "fused"

    def _note_boxed(self, n: int, reason: str) -> None:
        self.boxed_rows += n
        self.boxed_fallbacks += 1
        if self.columnar_fallback_reason is None:
            self.columnar_fallback_reason = reason
        if self._boxed_fallbacks_counter is not None:
            self._boxed_fallbacks_counter.inc()

    def _watermark_lag_ms(self):
        wm = self.current_watermark
        if wm <= MIN_TIMESTAMP:
            return None  # no watermark seen yet: lag undefined
        if wm >= MAX_TIMESTAMP:
            return 0.0  # final watermark: stream drained, no lag
        return max(0.0, _time_mod.time() * 1000.0 - wm)

    def open(self) -> None:  # noqa: B027
        pass

    def finish(self) -> None:  # noqa: B027
        """End of input reached (after the final watermark, before
        close): flush buffered output.  The drain-then-flush step of
        stop-with-savepoint, applied at natural end of input so finite
        jobs don't strand a 2PC sink's tail transaction."""
        pass

    def close(self) -> None:  # noqa: B027
        pass

    def dispose(self) -> None:  # noqa: B027
        pass

    # ---- elements ---------------------------------------------------
    @abc.abstractmethod
    def process_element(self, record: StreamRecord) -> None: ...

    def process_batch(self, batch) -> None:
        """Consume a whole RecordBatch.  The universal fallback boxes
        the batch into per-row records ONCE at this operator (counted
        in `columnar.boxed_fallbacks`) and runs the scalar path —
        operators with a column kernel override this.  Downstream of
        a boxing operator the stream is rows; downstream of a
        surviving operator it stays a batch."""
        self._note_boxed(
            len(batch),
            f"no batch kernel on {type(self).__name__}")
        for record in batch.to_records():
            self.set_key_context(record)
            self.process_element(record)

    def process_watermark(self, watermark: Watermark) -> None:
        """(ref: AbstractStreamOperator.processWatermark :737)"""
        self.current_watermark = watermark.timestamp
        if self.timer_service is not None:
            self.timer_service.advance_watermark(watermark.timestamp)
        self.output.emit_watermark(watermark)

    def process_latency_marker(self, marker: LatencyMarker) -> None:
        self.output.emit_latency_marker(marker)

    # ---- keyed context ----------------------------------------------
    def set_key_context(self, record: StreamRecord) -> None:
        """(ref: setKeyContextElement1 — key extraction + backend key)"""
        if self.key_selector is not None and self.keyed_backend is not None:
            self.keyed_backend.set_current_key(
                self.key_selector.get_key(record.value))

    # ---- timers (Triggerable contract) ------------------------------
    def on_event_time(self, timer) -> None:  # noqa: B027
        pass

    def on_processing_time(self, timer) -> None:  # noqa: B027
        pass

    # ---- snapshot ---------------------------------------------------
    #: set by an executor whose checkpoint coordinator resolves the
    #: acks' `DeferredSnapshot` handles (`LocalExecutor`): a keyed
    #: backend with an asynchronous part (`capture_snapshot`) then
    #: finishes its snapshot, and the timers', after the barrier
    deferred_snapshots = False

    def snapshot_state(self, checkpoint_id: Optional[int] = None) -> dict:
        snap = {}
        capture = (getattr(self.keyed_backend, "capture_snapshot", None)
                   if self.deferred_snapshots else None)
        if self.keyed_backend is not None:
            if hasattr(self.keyed_backend, "flush_all"):
                self.keyed_backend.flush_all()
            snap["keyed"] = (capture() if capture is not None
                             else self.keyed_backend.snapshot())
        if self.operator_state_backend is not None:
            snap["operator"] = self.operator_state_backend.snapshot()
        if self.timer_service is not None:
            snap["timers"] = (self.timer_service.capture_snapshot()
                              if capture is not None
                              else self.timer_service.snapshot())
        return snap

    def restore_state(self, snapshots: List[dict]) -> None:
        keyed = [s["keyed"] for s in snapshots if "keyed" in s]
        if keyed and self.keyed_backend is not None:
            self.keyed_backend.restore(keyed)
        ops = [s["operator"] for s in snapshots if "operator" in s]
        if ops and self.operator_state_backend is not None:
            from flink_tpu.state.operator_state import OperatorStateSnapshot
            if len(ops) == 1:
                self.operator_state_backend.restore(ops[0])
            else:
                self.operator_state_backend.restore(
                    OperatorStateSnapshot.redistribute(ops, 1)[0])
        timers = [s["timers"] for s in snapshots if "timers" in s]
        if timers and self.timer_service is not None:
            self.timer_service.restore(timers)

    def notify_checkpoint_complete(self, checkpoint_id: int) -> None:  # noqa: B027
        pass


class KeyedStateStore:
    """Adapter giving user functions keyed-state access in the VOID
    namespace (ref: DefaultKeyedStateStore.java)."""

    def __init__(self, backend: KeyedStateBackend):
        self._backend = backend

    def _bind(self, descriptor):
        return self._backend.get_partitioned_state(VOID_NAMESPACE, descriptor)

    get_value_state = _bind
    get_list_state = _bind
    get_reducing_state = _bind
    get_aggregating_state = _bind
    get_map_state = _bind


class AbstractUdfStreamOperator(StreamOperator):
    """Hosts a user function, forwarding open/close
    (ref: AbstractUdfStreamOperator.java)."""

    #: at parallelism > 1, rich functions are copied per subtask so each
    #: gets its own RuntimeContext and state (the reference serializes
    #: the function into every subtask).  At parallelism 1 the instance
    #: is shared — tests rely on reading e.g. a CollectSink's buffer.
    #: Sources opt out: their factory already deep-copies.
    COPY_UDF_PER_SUBTASK = True

    def __init__(self, user_function):
        super().__init__()
        self.user_function = user_function

    def setup(self, *args, **kwargs):
        super().setup(*args, **kwargs)
        # EVERY function is per-subtask at parallelism > 1, not just
        # RichFunctions — the reference deserializes a fresh instance
        # per task, and any stateful function (e.g. a periodic
        # watermark assigner's running max) silently corrupts its
        # siblings when shared across worker threads.  Sinks opt out
        # (COPY_UDF_PER_SUBTASK=False): tests/drivers read a shared
        # CollectSink buffer, and accumulator gathering dedupes by
        # instance.
        if self.COPY_UDF_PER_SUBTASK and self.num_subtasks > 1:
            import copy
            self.user_function = copy.deepcopy(self.user_function)

    def open(self):
        if isinstance(self.user_function, RichFunction):
            from flink_tpu.core.functions import RuntimeContext
            store = (KeyedStateStore(self.keyed_backend)
                     if self.keyed_backend is not None else None)
            ctx = RuntimeContext(
                task_name=self.operator_id,
                index_of_subtask=self.subtask_index,
                parallelism=self.num_subtasks,
                keyed_state_store=store,
                operator_state_store=self.operator_state_backend,
            )
            self.user_function.set_runtime_context(ctx)
            self.user_function.open(None)
        # CheckpointedFunction-style operator-state access for plain
        # functions (ref: FunctionInitializationContext — the seam the
        # Kafka/Kinesis consumers use for UNION offset state).  Called
        # AFTER restore_state has repopulated the backend when the
        # runtime opens operators post-restore.
        fn = self.user_function
        if hasattr(fn, "initialize_state"):
            fn.initialize_state(self)

    def finish(self):
        fn = self.user_function
        if hasattr(fn, "finish"):
            fn.finish()

    def close(self):
        if isinstance(self.user_function, RichFunction):
            self.user_function.close()

    def notify_checkpoint_complete(self, checkpoint_id: int) -> None:
        fn = self.user_function
        if hasattr(fn, "notify_checkpoint_complete"):
            fn.notify_checkpoint_complete(checkpoint_id)

    def snapshot_state(self, checkpoint_id: Optional[int] = None) -> dict:
        """Functions with checkpoint hooks (2PC sinks, replayable
        sources) ride in the operator snapshot (ref: the
        CheckpointedFunction path in AbstractUdfStreamOperator
        .snapshotState)."""
        snap = super().snapshot_state(checkpoint_id)
        fn = self.user_function
        if hasattr(fn, "snapshot_function_state"):
            snap["function"] = fn.snapshot_function_state(checkpoint_id)
        return snap

    def restore_state(self, snapshots) -> None:
        super().restore_state(snapshots)
        fn = self.user_function
        if hasattr(fn, "restore_function_state"):
            for s in snapshots:
                if "function" in s:
                    fn.restore_function_state(s["function"])


# ---------------------------------------------------------------------
# Columnar kernels for the stateless UDF operators: a proven-LIFTABLE
# UDF (PR 4's AOT bytecode analysis) applies directly to the batch's
# numpy columns — arithmetic bytecode vectorizes through ndarray
# operator overloading.  The first surviving batch is probe-validated
# (vectorized row vs the scalar UDF on the same row); any exception,
# shape mismatch, or probe divergence locks the operator onto the
# boxed path permanently.  Verdicts and probes are per-operator, so an
# opaque UDF boxes only its own hop.
# ---------------------------------------------------------------------

def _np_scalar(x):
    import numpy as np
    return x.item() if isinstance(x, np.generic) else x


def _batch_row_value(batch, i):
    arrays = tuple(batch.cols.values())
    if batch.is_scalar:
        return _np_scalar(arrays[0][i])
    return tuple(_np_scalar(a[i]) for a in arrays)


def _kernel_row_value(out, i):
    """Row i of a kernel result (ndarray or tuple of ndarrays)."""
    if type(out) is tuple:
        return tuple(_np_scalar(a[i]) for a in out)
    return _np_scalar(out[i])


def _same_scalar(a, b) -> bool:
    if type(a) is tuple or type(b) is tuple:
        return (type(a) is tuple and type(b) is tuple
                and len(a) == len(b)
                and all(_same_scalar(x, y) for x, y in zip(a, b)))
    if type(a) is not type(b):
        return False
    try:
        if a == b:
            return True
        return a != a and b != b  # NaN == NaN for probe purposes
    except Exception:  # noqa: BLE001
        return False


def _normalize_kernel_output(out, n):
    """Kernel result → ndarray (scalar rows) or tuple of ndarrays
    (tuple rows), broadcasting constant fields; None = not columnar."""
    import numpy as np
    if isinstance(out, np.ndarray):
        return out if out.shape == (n,) else None
    if type(out) is tuple and out:
        cols = []
        for item in out:
            if isinstance(item, np.ndarray):
                if item.shape != (n,):
                    return None
                cols.append(item)
            elif isinstance(item, (int, float, str, np.generic)):
                cols.append(np.full(n, item))
            else:
                return None
        return tuple(cols)
    return None


def _kernel_output_batch(batch, arrays):
    """Wrap normalized kernel output as a batch keeping timestamps."""
    from flink_tpu.streaming.elements import RecordBatch
    if type(arrays) is tuple:
        cols = {f"f{i}": a for i, a in enumerate(arrays)}
    else:
        cols = {"v": arrays}
    return RecordBatch(cols, batch.ts, batch.ts_mask)


def _kernel_fn(user_function, attr: str):
    """The callable the kernel path applies to column arrays: the raw
    wrapped lambda when present (lambda adapters like _LambdaFilter
    coerce their method's return — bool() chokes on a mask array), so
    the kernel runs exactly the function the analyzer proved liftable."""
    fn = getattr(user_function, "_fn", None)
    if callable(fn):
        return fn
    return getattr(user_function, attr, user_function)


def _udf_liftable(user_function, attr: str):
    """(liftable, reason) for the wrapped UDF — conclusive LIFTABLE
    from the AOT analyzer rides columns; everything else boxes."""
    fn = _kernel_fn(user_function, attr)
    try:
        from flink_tpu.analysis.liftability import LIFTABLE, analyze_udf
        rep = analyze_udf(fn)
        if rep.verdict == LIFTABLE:
            return True, ""
        return False, f"{attr} UDF not liftable ({rep.verdict}: " \
                      + "; ".join(rep.reasons[:2]) + ")"
    except Exception as e:  # noqa: BLE001
        return False, f"liftability analysis failed: {e!r}"


class _ColumnKernelMixin:
    """Shared decide/probe/fallback state machine for StreamMap and
    StreamFilter.  `_batch_kernel` is None (undecided), True (riding
    columns, probe passed or statically proven), or False (locked onto
    the boxed path).

    ``_static_kernel`` is stamped by the type-flow prover
    (:func:`flink_tpu.analysis.typeflow.apply_static`) when the whole
    dtype flow of the kernel was proven AOT — the first-batch probe is
    skipped and ``decided_by`` records "static".  The output-shape
    validation in ``_emit_kernel_result`` stays armed either way, so a
    runtime mismatch still demotes boxed with a recorded reason."""

    _batch_kernel = None
    _KERNEL_ATTR = ""
    _static_kernel = False
    _typeflow_verdict = None

    def _decide_kernel(self) -> bool:
        if self._static_kernel:
            return True
        ok, reason = _udf_liftable(self.user_function, self._KERNEL_ATTR)
        if not ok:
            self._batch_kernel = False
            self.columnar_fallback_reason = reason
        return ok

    def _kernel_fallback(self, batch, reason: str):
        self._batch_kernel = False
        self.columnar_fallback_reason = reason
        self.columnar_decided_by = None
        key = (type(self).__name__, reason.split(":")[0])
        if key not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(key)
            verdict = self._typeflow_verdict
            log.warning(
                "%s '%s' falls back to the boxed path: %s%s",
                type(self).__name__, self.operator_id, reason,
                f" (typeflow verdict was: {verdict})" if verdict
                else "")
        StreamOperator.process_batch(self, batch)

    def process_batch(self, batch):
        n = len(batch)
        if n == 0:
            return
        decided = self._batch_kernel
        if decided is False or (decided is None
                                and not self._decide_kernel()):
            StreamOperator.process_batch(self, batch)
            return
        fn = _kernel_fn(self.user_function, self._KERNEL_ATTR)
        try:
            out = fn(batch.value_arrays())
        except Exception as e:  # noqa: BLE001
            self._kernel_fallback(batch, f"kernel raised {e!r}")
            return
        if decided is None:
            if self._static_kernel:
                # the type-flow prover certified the dtype flow AOT:
                # no probe (the emit-side shape validation still
                # demotes on any runtime divergence)
                self._batch_kernel = True
                self.columnar_decided_by = "static"
                KERNEL_STATS.static_skips += 1
            else:
                # first surviving batch: validate the vectorized
                # result against the scalar UDF on the edge rows
                # (LIFTABLE UDFs are proven pure, so replaying rows
                # is safe)
                self.kernel_probes += 1
                KERNEL_STATS.probes += 1
                err = self._probe(batch, fn, out, n)
                if err is not None:
                    self._kernel_fallback(batch, err)
                    return
                self._batch_kernel = True
                self.columnar_decided_by = "probe"
        self._emit_kernel_result(batch, out, n)


class StreamMap(_ColumnKernelMixin, AbstractUdfStreamOperator):
    """(ref: StreamMap.java)"""

    _KERNEL_ATTR = "map"

    def process_element(self, record):
        self.output.collect(record.replace(self.user_function.map(record.value)))

    def _probe(self, batch, fn, out, n):
        arrays = _normalize_kernel_output(out, n)
        if arrays is None:
            return "kernel output is not a column shape"
        for i in (0, n - 1):
            if not _same_scalar(fn(_batch_row_value(batch, i)),
                                _kernel_row_value(arrays, i)):
                return "probe mismatch (vectorized != scalar result)"
        return None

    def _emit_kernel_result(self, batch, out, n):
        arrays = _normalize_kernel_output(out, n)
        if arrays is None:
            self._kernel_fallback(batch,
                                  "kernel output is not a column shape")
            return
        self._note_columnar(n)
        self.output.collect_batch(_kernel_output_batch(batch, arrays))


class StreamFlatMap(AbstractUdfStreamOperator):
    """(ref: StreamFlatMap.java)"""

    def process_element(self, record):
        out = self.user_function.flat_map(record.value)
        if out is not None:
            for value in out:
                self.output.collect(record.replace(value))


class StreamFilter(_ColumnKernelMixin, AbstractUdfStreamOperator):
    """(ref: StreamFilter.java)"""

    _KERNEL_ATTR = "filter"

    def process_element(self, record):
        if self.user_function.filter(record.value):
            self.output.collect(record)

    def _probe(self, batch, fn, out, n):
        import numpy as np
        if not (isinstance(out, np.ndarray) and out.shape == (n,)
                and out.dtype == np.bool_):
            return "filter kernel did not produce a bool mask"
        for i in (0, n - 1):
            if bool(fn(_batch_row_value(batch, i))) != bool(out[i]):
                return "probe mismatch (vectorized != scalar result)"
        return None

    def _emit_kernel_result(self, batch, out, n):
        import numpy as np
        if not (isinstance(out, np.ndarray) and out.shape == (n,)
                and out.dtype == np.bool_):
            self._kernel_fallback(
                batch, "filter kernel did not produce a bool mask")
            return
        self._note_columnar(n)
        if out.all():
            self.output.collect_batch(batch)
        elif out.any():
            self.output.collect_batch(batch.take(out))


class StreamSink(AbstractUdfStreamOperator):
    """(ref: StreamSink.java) — user_function is a SinkFunction."""

    #: parallel sink subtasks in one process share the instance:
    #: tests/drivers read a CollectSink's buffer directly, and
    #: accumulator gathering dedupes by instance identity
    COPY_UDF_PER_SUBTASK = False

    def process_element(self, record):
        self.user_function.invoke(record.value,
                                  SinkContext(record.timestamp, self))

    def process_batch(self, batch):
        """Vectorized collect: a sink function exposing invoke_batch
        takes the whole batch in one call (a batch dies columnar);
        plain sinks box per row."""
        fn = self.user_function
        if hasattr(fn, "invoke_batch"):
            self._note_columnar(len(batch))
            fn.invoke_batch(batch)
        else:
            StreamOperator.process_batch(self, batch)


class SinkContext:
    """(ref: SinkFunction.Context)"""

    __slots__ = ("timestamp", "_op")

    def __init__(self, timestamp, op):
        self.timestamp = timestamp
        self._op = op

    def current_processing_time(self):
        pts = self._op.processing_time_service
        return pts.get_current_processing_time() if pts else 0

    def current_watermark(self):
        return self._op.current_watermark


class StreamGroupedReduce(AbstractUdfStreamOperator):
    """Rolling keyed reduce: emits the running reduction per element
    (ref: StreamGroupedReduce.java)."""

    STATE_NAME = "_reduce_state"

    def __init__(self, reduce_function: ReduceFunction):
        super().__init__(reduce_function)

    def open(self):
        super().open()
        self._state = self.keyed_backend.get_or_create_keyed_state(
            ReducingStateDescriptor(self.STATE_NAME, self.user_function))

    def process_element(self, record):
        self._state.set_current_namespace(VOID_NAMESPACE)
        self._state.add(record.value)
        self.output.collect(record.replace(self._state.get()))


class ProcessOperator(AbstractUdfStreamOperator):
    """Non-keyed ProcessFunction host (ref: ProcessOperator.java)."""

    def open(self):
        super().open()
        self._collector = TimestampedCollector(self.output)

    def process_element(self, record):
        self._collector.set_absolute_timestamp(record.timestamp)
        ctx = ProcessFunctionContext(record, self)
        self.user_function.process_element(record.value, ctx, self._collector)


class KeyedProcessOperator(AbstractUdfStreamOperator):
    """Keyed ProcessFunction with timer access
    (ref: KeyedProcessOperator.java)."""

    def open(self):
        super().open()
        self._collector = TimestampedCollector(self.output)

    def process_element(self, record):
        self._collector.set_absolute_timestamp(record.timestamp)
        ctx = KeyedProcessFunctionContext(record, self)
        self.user_function.process_element(record.value, ctx, self._collector)

    def on_event_time(self, timer):
        self._collector.set_absolute_timestamp(timer.timestamp)
        ctx = OnTimerContext(timer, self, "event")
        self.user_function.on_timer(timer.timestamp, ctx, self._collector)

    def on_processing_time(self, timer):
        self._collector.set_absolute_timestamp(None)
        ctx = OnTimerContext(timer, self, "processing")
        self.user_function.on_timer(timer.timestamp, ctx, self._collector)


class ProcessFunctionContext:
    """(ref: ProcessFunction.Context)"""

    def __init__(self, record: StreamRecord, op: StreamOperator):
        self._record = record
        self._op = op

    def timestamp(self) -> Optional[int]:
        return self._record.timestamp

    def current_processing_time(self) -> int:
        pts = self._op.processing_time_service
        return pts.get_current_processing_time() if pts else 0

    def current_watermark(self) -> int:
        return self._op.current_watermark

    def output(self, tag: OutputTag, value) -> None:
        self._op.output.collect_side(tag, StreamRecord(value, self._record.timestamp))


class KeyedProcessFunctionContext(ProcessFunctionContext):
    """Adds timers + current key (ref: KeyedProcessFunction.Context)."""

    def get_current_key(self):
        return self._op.keyed_backend.current_key

    def register_event_time_timer(self, timestamp: int) -> None:
        self._op.timer_service.register_event_time_timer(VOID_NAMESPACE, timestamp)

    def register_processing_time_timer(self, timestamp: int) -> None:
        self._op.timer_service.register_processing_time_timer(VOID_NAMESPACE, timestamp)

    def delete_event_time_timer(self, timestamp: int) -> None:
        self._op.timer_service.delete_event_time_timer(VOID_NAMESPACE, timestamp)

    def delete_processing_time_timer(self, timestamp: int) -> None:
        self._op.timer_service.delete_processing_time_timer(VOID_NAMESPACE, timestamp)

    # state access for ProcessFunctions
    def get_state(self, descriptor: StateDescriptor):
        return self._op.keyed_backend.get_partitioned_state(VOID_NAMESPACE, descriptor)


class OnTimerContext(KeyedProcessFunctionContext):
    """(ref: ProcessFunction.OnTimerContext)"""

    def __init__(self, timer, op, time_domain: str):
        self._timer = timer
        self._op = op
        self._record = StreamRecord(None, timer.timestamp)
        self.time_domain = time_domain

    def timestamp(self):
        return self._timer.timestamp

    def get_current_key(self):
        return self._timer.key


class ProcessFunction(abc.ABC):
    """(ref: api/functions/ProcessFunction.java)"""

    @abc.abstractmethod
    def process_element(self, value, ctx, out) -> None: ...

    def on_timer(self, timestamp: int, ctx, out) -> None:  # noqa: B027
        pass


KeyedProcessFunction = ProcessFunction  # same shape; keyed ctx at runtime


# ---------------------------------------------------------------------
# Two-input (co-) operators (ref: api/operators/co/)
# ---------------------------------------------------------------------

class TwoInputStreamOperator(StreamOperator):
    @abc.abstractmethod
    def process_element1(self, record: StreamRecord) -> None: ...

    @abc.abstractmethod
    def process_element2(self, record: StreamRecord) -> None: ...

    def process_element(self, record):
        raise RuntimeError("two-input operator: use process_element1/2")

    def process_watermark1(self, watermark: Watermark) -> None:
        self._wm1 = watermark.timestamp
        self._combine_watermarks()

    def process_watermark2(self, watermark: Watermark) -> None:
        self._wm2 = watermark.timestamp
        self._combine_watermarks()

    def _combine_watermarks(self):
        """min-combine the two input watermarks
        (ref: AbstractStreamOperator.processWatermark1/2)."""
        wm1 = getattr(self, "_wm1", MIN_TIMESTAMP)
        wm2 = getattr(self, "_wm2", MIN_TIMESTAMP)
        combined = min(wm1, wm2)
        if combined > self.current_watermark:
            self.process_watermark(Watermark(combined))


class CoStreamMap(TwoInputStreamOperator, AbstractUdfStreamOperator):
    """(ref: CoStreamMap.java) — user_function is a CoMapFunction."""

    def __init__(self, fn):
        AbstractUdfStreamOperator.__init__(self, fn)

    def process_element1(self, record):
        self.output.collect(record.replace(self.user_function.map1(record.value)))

    def process_element2(self, record):
        self.output.collect(record.replace(self.user_function.map2(record.value)))


class CoStreamFlatMap(TwoInputStreamOperator, AbstractUdfStreamOperator):
    """(ref: CoStreamFlatMap.java)"""

    def __init__(self, fn):
        AbstractUdfStreamOperator.__init__(self, fn)

    def process_element1(self, record):
        out = self.user_function.flat_map1(record.value)
        if out is not None:
            for v in out:
                self.output.collect(record.replace(v))

    def process_element2(self, record):
        out = self.user_function.flat_map2(record.value)
        if out is not None:
            for v in out:
                self.output.collect(record.replace(v))


class CoProcessOperator(TwoInputStreamOperator, AbstractUdfStreamOperator):
    """(ref: CoProcessOperator.java / KeyedCoProcessOperator.java)"""

    def __init__(self, fn):
        AbstractUdfStreamOperator.__init__(self, fn)
        self.key_selector2: Optional[KeySelector] = None

    def open(self):
        AbstractUdfStreamOperator.open(self)
        self._collector = TimestampedCollector(self.output)

    def set_key_context2(self, record: StreamRecord) -> None:
        if self.key_selector2 is not None and self.keyed_backend is not None:
            self.keyed_backend.set_current_key(
                self.key_selector2.get_key(record.value))

    def process_element1(self, record):
        self._collector.set_absolute_timestamp(record.timestamp)
        ctx = KeyedProcessFunctionContext(record, self)
        self.user_function.process_element1(record.value, ctx, self._collector)

    def process_element2(self, record):
        self._collector.set_absolute_timestamp(record.timestamp)
        ctx = KeyedProcessFunctionContext(record, self)
        self.user_function.process_element2(record.value, ctx, self._collector)

    def on_event_time(self, timer):
        self._collector.set_absolute_timestamp(timer.timestamp)
        ctx = OnTimerContext(timer, self, "event")
        if hasattr(self.user_function, "on_timer"):
            self.user_function.on_timer(timer.timestamp, ctx, self._collector)

    def on_processing_time(self, timer):
        self._collector.set_absolute_timestamp(None)
        ctx = OnTimerContext(timer, self, "processing")
        if hasattr(self.user_function, "on_timer"):
            self.user_function.on_timer(timer.timestamp, ctx, self._collector)


# ---------------------------------------------------------------------
# Broadcast-connected operators (ref: api/operators/co/
# CoBroadcastWithKeyedOperator.java / CoBroadcastWithNonKeyedOperator.java
# + the broadcast state pattern)
# ---------------------------------------------------------------------

class BroadcastProcessFunction(abc.ABC):
    """(ref: api/functions/co/BroadcastProcessFunction.java;
    the keyed variant adds timers — KeyedBroadcastProcessFunction)."""

    @abc.abstractmethod
    def process_element(self, value, ctx, out) -> None: ...

    @abc.abstractmethod
    def process_broadcast_element(self, value, ctx, out) -> None: ...

    def on_timer(self, timestamp: int, ctx, out) -> None:  # noqa: B027
        pass


KeyedBroadcastProcessFunction = BroadcastProcessFunction


class _ReadOnlyBroadcastState:
    """Read view of a BroadcastState (the non-broadcast side must not
    write — ref: ReadOnlyBroadcastState.java)."""

    def __init__(self, state):
        self._s = state

    def get(self, key):
        return self._s.get(key)

    def contains(self, key):
        return self._s.contains(key)

    def immutable_entries(self):
        return self._s.immutable_entries()

    def keys(self):
        return self._s.keys()


class _BroadcastBaseContext(ProcessFunctionContext):
    def __init__(self, record, op, writable: bool):
        super().__init__(record, op)
        self._writable = writable

    def get_broadcast_state(self, descriptor_or_name):
        name = getattr(descriptor_or_name, "name", descriptor_or_name)
        state = self._op.operator_state_backend.get_broadcast_state(name)
        return state if self._writable else _ReadOnlyBroadcastState(state)


class _BroadcastReadOnlyContext(_BroadcastBaseContext):
    """Keyed-side context: read-only broadcast state + keyed state +
    timers (when the data side is keyed)."""

    def __init__(self, record, op):
        super().__init__(record, op, writable=False)

    def get_current_key(self):
        return self._op.keyed_backend.current_key

    def get_state(self, descriptor):
        return self._op.keyed_backend.get_partitioned_state(
            VOID_NAMESPACE, descriptor)

    def register_event_time_timer(self, timestamp):
        self._op.timer_service.register_event_time_timer(
            VOID_NAMESPACE, timestamp)

    def register_processing_time_timer(self, timestamp):
        self._op.timer_service.register_processing_time_timer(
            VOID_NAMESPACE, timestamp)


class CoBroadcastOperator(TwoInputStreamOperator, AbstractUdfStreamOperator):
    """Input 1 = the (possibly keyed) data stream; input 2 = the
    broadcast stream whose elements update broadcast state on EVERY
    parallel instance (the broadcast partitioner delivers to all)."""

    def __init__(self, fn: BroadcastProcessFunction):
        AbstractUdfStreamOperator.__init__(self, fn)

    def open(self):
        super().open()
        self._collector = TimestampedCollector(self.output)

    def process_element1(self, record):
        self._collector.set_absolute_timestamp(record.timestamp)
        ctx = _BroadcastReadOnlyContext(record, self)
        self.user_function.process_element(record.value, ctx,
                                           self._collector)

    def process_element2(self, record):
        self._collector.set_absolute_timestamp(record.timestamp)
        ctx = _BroadcastBaseContext(record, self, writable=True)
        self.user_function.process_broadcast_element(record.value, ctx,
                                                     self._collector)

    def on_event_time(self, timer):
        self._collector.set_absolute_timestamp(timer.timestamp)
        ctx = OnTimerContext(timer, self, "event")
        self.user_function.on_timer(timer.timestamp, ctx, self._collector)

    def on_processing_time(self, timer):
        self._collector.set_absolute_timestamp(None)
        ctx = OnTimerContext(timer, self, "processing")
        self.user_function.on_timer(timer.timestamp, ctx, self._collector)


# ---------------------------------------------------------------------
# Async I/O (ref: api/operators/async/AsyncWaitOperator.java + the
# ordered/unordered stream element queues under queue/)
# ---------------------------------------------------------------------

class AsyncFunction(abc.ABC):
    """(ref: api/functions/async/AsyncFunction.java).  async_invoke
    runs ON A POOL THREAD here (Python has no JVM-style callback
    futures baked in), so a blocking client call inside it overlaps
    with other records' calls — the same throughput effect the
    reference gets from callback-style clients."""

    @abc.abstractmethod
    def async_invoke(self, value, result_future: "ResultFuture") -> None:
        ...

    def timeout(self, value, result_future: "ResultFuture") -> None:
        result_future.complete_exceptionally(
            TimeoutError(f"async I/O timed out for {value!r}"))


class ResultFuture:
    """(ref: api/functions/async/ResultFuture.java)"""

    __slots__ = ("_results", "_error", "_done", "_notify")

    def __init__(self, notify=None):
        self._results = None
        self._error = None
        self._done = threading.Event()
        #: operator-level "any completion" event (wait-any support)
        self._notify = notify

    def complete(self, results) -> None:
        self._results = list(results)
        self._done.set()
        if self._notify is not None:
            self._notify.set()

    def complete_exceptionally(self, error: BaseException) -> None:
        self._error = error
        self._done.set()
        if self._notify is not None:
            self._notify.set()

    @property
    def done(self) -> bool:
        return self._done.is_set()


class AsyncWaitOperator(AbstractUdfStreamOperator):
    """Bounded in-flight async requests with ordered or unordered
    result emission.  Watermarks act as order barriers: every pending
    request drains before the watermark forwards, in BOTH modes (the
    reference's unordered queue also never reorders across
    watermarks)."""

    def __init__(self, fn: AsyncFunction, capacity: int = 100,
                 timeout_ms: Optional[int] = None, ordered: bool = True):
        super().__init__(fn)
        self.capacity = capacity
        self.timeout_ms = timeout_ms
        self.ordered = ordered
        self._pending = None  # deque of (record, ResultFuture, deadline)

    def open(self):
        super().open()
        from collections import deque as _deque
        from concurrent.futures import ThreadPoolExecutor
        self._pending = _deque()
        self._any_done = threading.Event()
        self._pool = ThreadPoolExecutor(
            max_workers=min(self.capacity, 64),
            thread_name_prefix="async-io")

    def process_element(self, record):
        while len(self._pending) >= self.capacity:
            self._drain(block_one=True)
        rf = ResultFuture(notify=self._any_done)
        value = record.value
        deadline = (None if self.timeout_ms is None
                    else _time_mod.monotonic() + self.timeout_ms / 1000.0)
        self._pool.submit(self._invoke, value, rf)
        self._pending.append((record, rf, deadline, value))
        self._drain()

    def _invoke(self, value, rf):
        try:
            self.user_function.async_invoke(value, rf)
        except BaseException as e:  # noqa: BLE001
            rf.complete_exceptionally(e)

    def _drain(self, block_one: bool = False, block_all: bool = False):
        """Emit completed results; ordered mode emits only from the
        head, unordered emits any completed entry."""
        while self._pending:
            if self.ordered:
                entry = self._pending[0]
                if not self._entry_ready(entry, block_one or block_all):
                    if not (block_one or block_all):
                        return
                self._pending.popleft()
                self._emit(entry)
            else:
                # wait-any: a blocked unordered drain must wake on ANY
                # completion, not poll the head (head-of-line blocking
                # is exactly what unordered mode exists to avoid)
                while True:
                    ready = [e for e in self._pending if e[1].done
                             or self._expired(e)]
                    if ready or not (block_one or block_all):
                        break
                    self._any_done.clear()
                    self._any_done.wait(0.005)
                if not ready:
                    return
                for entry in ready:
                    self._pending.remove(entry)
                    self._emit(entry)
            if block_one and not block_all:
                return

    def _entry_ready(self, entry, block: bool) -> bool:
        record, rf, deadline, value = entry
        if rf.done:
            return True
        if self._expired(entry):
            return True
        if not block:
            return False
        while not rf.done and not self._expired(entry):
            rf._done.wait(0.005)
        return True

    def _expired(self, entry) -> bool:
        _, rf, deadline, _ = entry
        return (deadline is not None and not rf.done
                and _time_mod.monotonic() > deadline)

    def _emit(self, entry):
        record, rf, deadline, value = entry
        if not rf.done and self._expired(entry):
            self.user_function.timeout(value, rf)
            rf._done.wait(1.0)
        if rf._error is not None:
            raise rf._error
        for v in rf._results or []:
            self.output.collect(record.replace(v))

    def process_watermark(self, watermark):
        self._drain(block_all=True)
        super().process_watermark(watermark)

    def snapshot_state(self, checkpoint_id=None):
        # a barrier must not leave records in flight: upstream will not
        # replay records consumed before it, so drain-and-emit before
        # the snapshot (the reference instead persists its queue; a
        # full drain gives the same exactly-once guarantee at some
        # checkpoint-latency cost)
        self._drain(block_all=True)
        return super().snapshot_state(checkpoint_id)

    def finish(self):
        self._drain(block_all=True)
        super().finish()

    def close(self):
        if getattr(self, "_pool", None) is not None:
            self._pool.shutdown(wait=False)
        super().close()

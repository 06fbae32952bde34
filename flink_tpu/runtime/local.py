"""Single-process streaming job execution.

Re-designs the task layer of flink-streaming-java — StreamTask.java
(lifecycle :233-392, run loop, performCheckpoint :618-668),
OperatorChain.java, StreamInputProcessor.java:176 (the hot input loop),
BarrierBuffer.java:222 (exactly-once alignment), BarrierTracker.java
(at-least-once), StatusWatermarkValve, and SourceStreamTask — as a
cooperative in-process dataflow:

- Every cross-vertex edge delivers through per-channel bounded queues
  (the credit-based-flow-control analogue of RemoteInputChannel.java:
  285-298: a producer is runnable only while its output channels have
  capacity, so backpressure propagates upstream for free).
- Subtasks are STEPPED by one executor loop thread — all element
  processing, timer firing, alignment, and snapshots for a subtask
  happen on that loop, replacing the reference's checkpoint lock
  (SURVEY.md §5 race-detection note) with single-owner execution.
- Sources emit in steps on the same loop when they support it
  (`emit_step`); blocking sources (sockets, external consumers) run on
  a dedicated thread and emit under a per-subtask emission lock — the
  literal checkpoint-lock contract of SourceContext
  (SourceFunction.java "emit under checkpoint lock").
- Checkpoint barriers are injected at sources at record boundaries,
  align in-band at multi-input subtasks (blocked channels simply stop
  being polled — their queues are the BufferSpiller analogue), and
  each subtask acks its snapshot to the CheckpointCoordinator, which
  persists completed checkpoints and broadcasts the commit signal.
- Failure → restart via the configured strategy, restoring every
  operator (and source read positions) from the latest completed
  checkpoint (ref: ExecutionGraph.restart :1148 →
  restoreLatestCheckpointedState :1223).
"""

from __future__ import annotations

import random as _random_mod
import threading
import time as _time
from collections import deque
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from flink_tpu.core.keygroups import (
    compute_key_group_range_for_operator_index,
)
from flink_tpu.runtime.checkpoints import (
    CheckpointCoordinator,
    make_checkpoint_storage,
    make_restart_strategy,
    resolve_task_snapshot,
)
from flink_tpu.runtime import faults
from flink_tpu.runtime.backpressure import (
    derive_upstreams,
    locate_bottleneck,
    observe_subtask,
    observe_threaded_source,
    read_vertex_stats,
)
from flink_tpu.runtime.failover import (
    TaskFailureException,
    build_region_index,
    compute_pipelined_regions,
    region_of,
)
from flink_tpu.runtime.device_stats import register_device_gauges
from flink_tpu.runtime.profiler import get_profiler, register_profiler_gauges
from flink_tpu.runtime.metrics import (
    LatencyStats,
    MetricRegistry,
    TaskIOMetricGroup,
    register_checkpoint_gauges,
    register_faulttolerance_gauges,
    register_state_gauges,
    register_state_introspection_gauges,
)
from flink_tpu.runtime.tracing import (
    get_tracer,
    register_runtime_profile_gauges,
)
from flink_tpu.state.loader import load_state_backend
from flink_tpu.state.operator_state import OperatorStateBackend
from flink_tpu.streaming.elements import (
    END_OF_STREAM,
    MAX_WATERMARK,
    MIN_TIMESTAMP,
    CheckpointBarrier,
    EndOfStream,
    LatencyMarker,
    StreamRecord,
    Watermark,
)
from flink_tpu.streaming.graph import JobGraph, JobVertex
from flink_tpu.streaming.operators import (
    Output,
    StreamOperator,
    TwoInputStreamOperator,
)
from flink_tpu.streaming.sources import StreamSource
from flink_tpu.streaming.timers import TestProcessingTimeService

#: soft per-channel queue bound (the exclusive-buffer count analogue,
#: NetworkEnvironmentConfiguration.java:45-47)
DEFAULT_CHANNEL_CAPACITY = 1024

#: channel choice for latency-marker forwarding
_rand = _random_mod.Random(0)


class JobExecutionResult:
    def __init__(self, job_name: str):
        self.job_name = job_name
        self.accumulators: Dict[str, Any] = {}
        self.checkpoints_completed = 0
        self.restarts = 0
        #: restarts that were scoped to the failed pipelined region
        #: (healthy regions carried their live state across)
        self.region_restarts = 0
        self.cancelled = False


class JobCancelledException(Exception):
    pass


class SuppressRestartsException(Exception):
    """Wraps a failure that must NOT trigger the restart strategy
    (ref: flink-runtime/.../execution/SuppressRestartsException.java).
    Raised for failures in the end-of-input finish phase: input is
    fully consumed and final transactions may already be committed, so
    a replay could not be exactly-once."""

    def __init__(self, cause: BaseException):
        super().__init__(str(cause))
        self.cause = cause


class _ChainedOutput(Output):
    """Direct call into the next operator in the chain
    (ref: ChainingOutput in OperatorChain.java)."""

    __slots__ = ("op", "router")

    def __init__(self, op: StreamOperator, router: "_RouterOutput"):
        self.op = op
        self.router = router

    def collect(self, record):
        self.op.set_key_context(record)
        self.op.process_element(record)

    def collect_batch(self, batch):
        # batches chain whole: a fused chain program anchored on the
        # next operator takes the whole run in one jitted dispatch;
        # otherwise the operator's kernel (or its boxing fallback)
        # decides, never this output
        op = self.op
        fused = op._fused_chain
        if fused is not None and fused.wants(batch):
            fused.run(batch)
            return
        op.process_batch(batch)

    def emit_watermark(self, watermark):
        self.op.process_watermark(watermark)

    def collect_side(self, tag, record):
        # side outputs bypass the chain and route at the task boundary
        self.router.collect_side(tag, record)

    def emit_latency_marker(self, marker):
        self.op.process_latency_marker(marker)


#: records buffered in a router before the batched fan-out runs; any
#: control emission (watermark/barrier/EOS/marker/side output) and the
#: end of every subtask step flush earlier, so this only caps memory
#: under very chatty operators
_ROUTER_BUFFER_CAP = 4096


class _RouterOutput(Output):
    """Chain-tail output: routes records through each out-edge's
    partitioner to downstream subtask channels
    (ref: RecordWriterOutput + RecordWriter).

    Records BUFFER here and fan out in batches: the partitioner's
    vectorized `select_channels_batch` indexes a whole emit batch at
    once and a stable argsort splits it into per-channel sub-batches —
    replacing the per-record Python dispatch loop.  Element order per
    (producer, channel) pair is preserved exactly (the stable sort),
    and every control element flushes the buffer first, so barriers,
    watermarks, and EOS never overtake records."""

    def __init__(self):
        #: (partitioner, channels: List[_InputChannel], side_tag)
        self.routes: List[Tuple[Any, List["_InputChannel"], Any]] = []
        #: routes that are iteration back edges (records/watermarks
        #: flow; EOS and barriers do not — iterations sit outside the
        #: exactly-once guarantee, as in the reference)
        self.feedback_routes: set = set()
        #: numRecordsOut counter, set by the task layer when metrics
        #: are enabled (ref: RecordWriterOutput's outputs counter)
        self.records_out_counter = None
        #: pending records awaiting the batched fan-out
        self._buf: list = []
        #: monotonic time of the last observed out-of-capacity moment;
        #: producer wait loops stamp it so the backpressure gauge can
        #: report "blocked recently" instead of racing the refill
        #: window of a blocked producer thread with a point read
        self.last_blocked_mono = 0.0

    def add_route(self, partitioner, channels, side_tag=None,
                  feedback: bool = False):
        partitioner.setup(len(channels))
        if feedback:
            self.feedback_routes.add(len(self.routes))
        self.routes.append((partitioner, channels, side_tag))

    def collect(self, record):
        if self.records_out_counter is not None:
            self.records_out_counter.count += 1
        buf = self._buf
        buf.append(record)
        if len(buf) >= _ROUTER_BUFFER_CAP:
            self.flush_records()

    def flush_records(self):
        """Fan the buffered records out to every non-side route."""
        buf = self._buf
        if not buf:
            return
        self._buf = []
        for partitioner, channels, side_tag in self.routes:
            if side_tag is not None:
                continue
            n_ch = len(channels)
            if getattr(partitioner, "broadcast_all", False):
                for ch in channels:
                    ch.push_batch(buf)
            elif not partitioner.supports_batch or len(buf) == 1:
                # multicast (tagged broadcast) or trivial batch: the
                # per-record scalar path
                for record in buf:
                    for idx in partitioner.select_channels(record.value,
                                                           n_ch):
                        channels[idx].push(record)
            elif n_ch == 1:
                channels[0].push_batch(buf)
            else:
                idx = partitioner.select_channels_batch(
                    [r.value for r in buf], n_ch)
                order = np.argsort(idx, kind="stable")
                bounds = np.searchsorted(idx[order],
                                         np.arange(n_ch + 1))
                ol = order.tolist()
                for c in range(n_ch):
                    lo, hi = int(bounds[c]), int(bounds[c + 1])
                    if lo < hi:
                        channels[c].push_batch([buf[j]
                                                for j in ol[lo:hi]])

    def collect_batch(self, batch):
        """Route a whole RecordBatch: vectorized key-group split (one
        hash pass + a stable argsort per route), whole-batch push on
        single-channel/broadcast/rebalance routes, and per-row boxing
        only for partitioners with no batch split (multicast, custom).
        Buffered rows flush FIRST — they predate the batch, and the
        per-(producer, channel) order contract must hold."""
        n = len(batch)
        if n == 0:
            return
        if self.records_out_counter is not None:
            self.records_out_counter.count += n
        self.flush_records()
        boxed = None
        for partitioner, channels, side_tag in self.routes:
            if side_tag is not None:
                continue
            n_ch = len(channels)
            if getattr(partitioner, "broadcast_all", False):
                for ch in channels:
                    ch.push(batch)  # immutable: shared, never copied
                continue
            if n_ch == 1:
                channels[0].push(batch)
                continue
            split = partitioner.split_batch(batch, n_ch)
            if split is not None:
                for idx, sub in split:
                    channels[idx].push(sub)
                continue
            if boxed is None:
                boxed = batch.to_records()
            for record in boxed:
                for idx in partitioner.select_channels(record.value,
                                                       n_ch):
                    channels[idx].push(record)

    def collect_side(self, tag, record):
        self.flush_records()
        for partitioner, channels, side_tag in self.routes:
            if side_tag is not None and side_tag.tag_id == tag.tag_id:
                for idx in partitioner.select_channels(record.value, len(channels)):
                    channels[idx].push(record)

    def emit_watermark(self, watermark):
        # watermarks broadcast to every channel of every route
        self.flush_records()
        for _, channels, _ in self.routes:
            for ch in channels:
                ch.push(watermark)

    def emit_latency_marker(self, marker):
        # ONE random channel per route, not a broadcast: fan-out would
        # multiply marker traffic by parallelism at every shuffle stage
        # (O(p^depth) at the sink) and duplicate histogram samples
        # (ref: RecordWriterOutput forwards each marker to a single
        # random channel for the same reason)
        self.flush_records()
        for _, channels, side_tag in self.routes:
            if side_tag is None and channels:
                channels[_rand.randrange(len(channels))].push(marker)

    def broadcast_barrier(self, barrier: CheckpointBarrier):
        """(ref: OperatorChain.broadcastCheckpointBarrier)"""
        self.flush_records()
        for i, (_, channels, _) in enumerate(self.routes):
            if i in self.feedback_routes:
                continue
            for ch in channels:
                ch.push(barrier)

    def broadcast_end_of_stream(self):
        self.flush_records()
        for i, (_, channels, _) in enumerate(self.routes):
            if i in self.feedback_routes:
                continue
            for ch in channels:
                ch.push(END_OF_STREAM)

    def has_queued_output(self) -> bool:
        return bool(self._buf)

    def has_capacity(self) -> bool:
        """Producer runnable check — credit-based flow control
        analogue.  Channels blocked for alignment don't count (their
        growth is the BufferSpiller analogue)."""
        for _, channels, _ in self.routes:
            for ch in channels:
                if not ch.blocked and (len(ch.queue)
                                       + getattr(ch, "extra_rows", 0)
                                       >= ch.capacity):
                    return False
        return True


class _InputChannel:
    """One logical channel into a subtask: a bounded FIFO of
    StreamElements (ref: InputChannel + its queued buffers).

    While alignment-blocked, elements past the spill threshold go to
    disk instead of growing the in-memory queue (ref:
    BufferSpiller.java:67 — the reference spills post-barrier buffers
    so a long alignment never stalls upstream producers or exhausts
    memory)."""

    __slots__ = ("subtask", "input_index", "channel_id", "queue",
                 "capacity", "blocked", "eos", "is_feedback",
                 "extra_rows", "_spill_file", "spilled_count",
                 "_spill_disabled")

    def __init__(self, subtask: "SubtaskInstance", input_index: int,
                 channel_id: int, capacity: int = DEFAULT_CHANNEL_CAPACITY):
        self.subtask = subtask
        self.input_index = input_index
        self.channel_id = channel_id
        self.queue: deque = deque()
        self.capacity = capacity
        #: rows queued beyond the element count: each queued
        #: RecordBatch adds len-1, so len(queue) + extra_rows is the
        #: ROW depth and the capacity check stays row-bounded for
        #: batch flow (plain records never touch this)
        self.extra_rows = 0
        #: alignment-blocked (exactly-once barrier received, waiting
        #: for the rest — ref: BarrierBuffer blocked channels)
        self.blocked = False
        self.eos = False
        #: iteration back edge: exempt from EOS and barrier alignment
        self.is_feedback = False
        self._spill_file = None
        self.spilled_count = 0
        self._spill_disabled = False

    def push(self, element) -> None:
        if self.blocked:
            st = self.subtask
            st.note_alignment_element()
            # the cap check may have ABORTED the alignment (releasing
            # and unspilling this channel) — re-check before spilling,
            # else the element strands in a fresh spill file
            if self.blocked and not self._spill_disabled:
                threshold = st.alignment_spill_threshold
                if threshold is not None \
                        and len(self.queue) >= threshold:
                    if self._try_spill(element):
                        return
                    # unpicklable element: restore order (spilled
                    # rows are older) and stop spilling this channel
                    self.unspill()
                    self._spill_disabled = True
        if element.is_batch:
            self.extra_rows += len(element) - 1
        self.queue.append(element)

    def push_batch(self, elements: list) -> None:
        """Bulk append for the batched router fan-out; alignment-
        blocked channels take the per-element path (spill
        accounting)."""
        if self.blocked:
            for el in elements:
                self.push(el)
        else:
            self.queue.extend(elements)

    def _try_spill(self, element) -> bool:
        import pickle as _pickle
        import tempfile as _tempfile
        try:
            payload = _pickle.dumps(element,
                                    protocol=_pickle.HIGHEST_PROTOCOL)
        except Exception:  # noqa: BLE001 — unpicklable user value:
            return False   # keep it in memory (spill is best-effort)
        if self._spill_file is None:
            self._spill_file = _tempfile.TemporaryFile(
                prefix="flink_tpu_align_spill_")
        f = self._spill_file
        f.write(len(payload).to_bytes(8, "little"))
        f.write(payload)
        self.spilled_count += 1
        self.subtask.alignment_spilled_total += 1
        return True

    def unspill(self) -> None:
        """Move spilled elements back behind the in-memory queue (they
        are strictly newer than every queued element)."""
        if self._spill_file is None:
            return
        import pickle as _pickle
        f = self._spill_file
        f.seek(0)
        while True:
            header = f.read(8)
            if len(header) < 8:
                break
            n = int.from_bytes(header, "little")
            el = _pickle.loads(f.read(n))
            if el.is_batch:
                self.extra_rows += len(el) - 1
            self.queue.append(el)
        f.close()
        self._spill_file = None
        self.spilled_count = 0


class SubtaskInstance:
    """One parallel instance of a JobVertex: the operator chain plus
    input channels and barrier alignment (ref: StreamTask +
    OperatorChain + BarrierBuffer)."""

    def __init__(self, vertex: JobVertex, subtask_index: int,
                 state_backend, max_parallelism: int,
                 processing_time_service,
                 channel_capacity: int = DEFAULT_CHANNEL_CAPACITY,
                 metrics_group=None, latency_stats=None):
        self.vertex = vertex
        self.subtask_index = subtask_index
        self.task_key = (vertex.id, subtask_index)
        self.max_parallelism = max_parallelism
        self.operators: List[StreamOperator] = []
        self.pts = processing_time_service
        self.channel_capacity = channel_capacity
        self._watermarks: Dict[int, Dict[int, int]] = {}  # input -> channel -> wm
        self._current_wm: Dict[int, int] = {}
        self._channel_count = 0
        self.input_channels: List[_InputChannel] = []
        self._rr = 0  # round-robin cursor over channels
        self.finished = False
        self.closed = False
        #: teardown signal observed by the threaded-source
        #: backpressure wait (set before joining the thread)
        self.cancelling = False

        # barrier alignment state (exactly-once)
        self._align_id: Optional[int] = None
        self._align_barrier: Optional[CheckpointBarrier] = None
        self._align_received: Set[int] = set()  # channel ids
        #: elements buffered on blocked channels past this spill to
        #: disk (ref BufferSpiller.java:67); None disables spilling
        self.alignment_spill_threshold: Optional[int] = channel_capacity
        #: total elements buffered during ONE alignment beyond this
        #: ABORT the checkpoint instead of buffering on (the
        #: reference's alignment cap, TaskManagerOptions.java:342);
        #: None = unbounded
        self.alignment_abort_limit: Optional[int] = None
        self._align_buffered = 0
        #: lifetime count of alignment-spilled elements (metric)
        self.alignment_spilled_total = 0
        #: checkpoints aborted by the alignment cap (metric)
        self.alignment_aborts = 0
        #: set by the executor: callable(checkpoint_id) declining at
        #: the coordinator
        self.decline_fn = None
        # at-least-once barrier counting (ref: BarrierTracker)
        self._tracker_counts: Dict[int, Tuple[CheckpointBarrier, Set[int]]] = {}

        #: set by the executor: callable(task_key, checkpoint_id, snapshot)
        self.ack_fn = None
        #: source-only: (checkpoint_id, timestamp, options) to inject
        self.pending_trigger: Optional[Tuple[int, int, dict]] = None
        #: source-only (threaded): checkpoint-complete notifications
        #: awaiting delivery under the emission lock
        self.pending_notifications: deque = deque()
        #: source-only: serializes emissions vs. barrier injection for
        #: threaded sources (the checkpoint lock, StreamTask.java:106).
        #: Reentrant so a source can hold it across emit+offset-advance
        #: (SourceContext.get_checkpoint_lock contract) while collect
        #: re-acquires it.
        self.emission_lock = threading.RLock()
        self._source_ctx = None
        self._thread: Optional[threading.Thread] = None
        self.thread_error: Optional[BaseException] = None

        # metrics (ref: TaskMetricGroup / TaskIOMetricGroup wiring in
        # Task + StreamInputProcessor.java:182)
        self.metrics_group = metrics_group
        self.latency_stats = latency_stats
        self.io_metrics = (TaskIOMetricGroup(metrics_group)
                           if metrics_group is not None else None)
        #: busy/idle/backPressured attribution, observed once per
        #: executor-loop pass (ref: TaskIOMetricGroup's
        #: busyTimeMsPerSecond family)
        from flink_tpu.runtime.backpressure import (
            TimeAccounting,
            register_time_attribution_gauges,
        )
        self.time_accounting = TimeAccounting()
        if metrics_group is not None:
            register_time_attribution_gauges(metrics_group,
                                             self.time_accounting)
        # precomputed span names (the per-element tracing fast path
        # must not format strings)
        self._span_process = f"op.{vertex.name}.process"
        self._span_checkpoint = "checkpoint.barrier"

        # build the chain, tail first so outputs exist when wiring heads
        chain = vertex.chain
        self.router = _RouterOutput()
        if self.io_metrics is not None:
            self.router.records_out_counter = self.io_metrics.num_records_out
        ops_by_node: Dict[int, StreamOperator] = {}
        for node in reversed(chain):
            out_edge = next((e for e in vertex.chain_edges
                             if e.source_id == node.id), None)
            if out_edge is None:
                output: Output = self.router
            else:
                output = _ChainedOutput(ops_by_node[out_edge.target_id],
                                        self.router)
            op = node.operator_factory()
            keyed = None
            if node.key_selector is not None:
                rng = compute_key_group_range_for_operator_index(
                    max_parallelism, vertex.parallelism, subtask_index)
                keyed = load_state_backend(
                    state_backend, rng, max_parallelism,
                    name=node.state_backend)
            op.setup(
                output,
                keyed_backend=keyed,
                operator_state_backend=OperatorStateBackend(),
                processing_time_service=processing_time_service,
                key_selector=node.key_selector,
                operator_id=node.uid,
                subtask_index=subtask_index,
                num_subtasks=vertex.parallelism,
                max_parallelism=max_parallelism,
            )
            if metrics_group is not None:
                op.register_standard_metrics(
                    metrics_group.add_group(node.uid))
            ops_by_node[node.id] = op
        # operators in chain order (head first)
        self.operators = [ops_by_node[n.id] for n in chain]

    @property
    def head(self) -> StreamOperator:
        return self.operators[0]

    @property
    def is_source(self) -> bool:
        return isinstance(self.head, StreamSource)

    def new_channel(self, input_index: int) -> _InputChannel:
        ch = _InputChannel(self, input_index, self._channel_count,
                           self.channel_capacity)
        self._channel_count += 1
        self.input_channels.append(ch)
        self._watermarks.setdefault(input_index, {})[ch.channel_id] = MIN_TIMESTAMP
        return ch

    # ---- lifecycle --------------------------------------------------
    def open(self):
        for op in self.operators:
            op.open()
        # routes are wired before open() in every executor, so the
        # fused-chain compiler sees the final channel fan-out
        from flink_tpu.streaming.chain_fusion import try_fuse_subtask
        try_fuse_subtask(self)

    def close(self):
        if self.closed:
            return
        self.closed = True
        for op in self.operators:
            op.close()

    # ---- source path (ref: SourceStreamTask / StreamSource) ---------
    def source_context(self):
        if self._source_ctx is None:
            self._source_ctx = self.head.make_context()
        return self._source_ctx

    @property
    def supports_stepping(self) -> bool:
        return hasattr(self.head.user_function, "emit_step")

    def source_step(self, max_records: int) -> int:
        """Cooperative source: emit up to max_records on the executor
        loop; inject a pending barrier first (record boundary)."""
        if self.finished:
            return 0
        self.handle_pending_trigger()
        if not self.router.has_capacity():
            self.router.last_blocked_mono = _time.monotonic()
            return 0
        more = self.head.user_function.emit_step(
            self.source_context(), max_records)
        if not more:
            self.finish_source()
        self.router.flush_records()
        return 1

    def finish_source(self):
        """End of input: flush a pending barrier, then event time, then
        signal end-of-stream downstream (ref: StreamSource closes with
        MAX_WATERMARK so windows drain)."""
        if self.finished:
            return
        self.handle_pending_trigger()
        # through the chain (head.output), not the router: chained
        # operators must see the final watermark too (timer flushes)
        self.head.output.emit_watermark(MAX_WATERMARK)
        self.router.broadcast_end_of_stream()
        self.finished = True

    def run_source_threaded(self):
        """Blocking source on its own thread, emitting under the
        emission lock (the SourceContext checkpoint-lock contract)."""
        assert self.is_source

        def target():
            try:
                # static profiler attribution for this thread: every
                # stack sampled here belongs to this source subtask
                get_profiler().set_scope(self)
                ctx = self.head.make_context(
                    output=_LockedSourceOutput(self))
                ctx._checkpoint_lock = self.emission_lock
                self._source_ctx = ctx
                self.head.user_function.run(ctx)
                with self.emission_lock:
                    self.finish_source()
            except BaseException as e:  # noqa: BLE001
                self.thread_error = e

        self._thread = threading.Thread(target=target, daemon=True,
                                        name=f"source-{self.task_key}")
        self._thread.start()

    def cancel_source(self):
        if self.is_source:
            self.cancelling = True  # unblocks a backpressured emit wait
            try:
                self.head.cancel()
            except Exception:  # noqa: BLE001
                pass

    def join_source(self, timeout: float = 5.0):
        if self._thread is not None:
            self._thread.join(timeout)

    # ---- barrier injection (sources) --------------------------------
    def handle_pending_trigger(self):
        """Snapshot + inject the barrier at a record boundary (ref:
        StreamTask.performCheckpoint :618-668 — barrier broadcast and
        snapshot happen atomically w.r.t. element processing)."""
        trig = self.pending_trigger
        if trig is None or self.finished:
            return
        self.pending_trigger = None
        cid, ts, options = trig
        barrier = CheckpointBarrier(cid, ts, options)
        # causally link the source-side snapshot+broadcast span to the
        # coordinator's trigger (the context rides the barrier options)
        ctx = options.get("trace") if isinstance(options, dict) else None
        tracer = get_tracer()
        with tracer.phase("checkpoint.sync", checkpoint_id=cid), \
                tracer.span_linked(self._span_checkpoint, ctx,
                                   checkpoint_id=cid,
                                   task=self.vertex.name,
                                   subtask=self.subtask_index):
            snapshot = self.snapshot(cid)
            self.router.broadcast_barrier(barrier)
            if self.ack_fn is not None:
                self.ack_fn(self.task_key, cid, snapshot)

    def try_inject_threaded_trigger(self):
        """Executor-side injection for blocking sources: take the
        emission lock opportunistically (the trigger thread acquiring
        the checkpoint lock, StreamTask.java:563)."""
        if self.pending_trigger is None or self.finished:
            return
        if self.emission_lock.acquire(blocking=False):
            try:
                self.handle_pending_trigger()
            finally:
                self.emission_lock.release()

    # ---- input stepping (ref: StreamInputProcessor.processInput) ----
    def step(self, budget: int) -> int:
        """Process up to `budget` queued elements, round-robin over
        non-blocked channels.  Returns elements processed.  Finished
        tasks still drain stray queued elements (end-of-job timer
        firings can emit after EOS propagated)."""
        if not self.input_channels:
            return 0
        processed = 0
        n = len(self.input_channels)
        idle_scan = 0
        while processed < budget and idle_scan < n:
            ch = self.input_channels[self._rr % n]
            self._rr += 1
            if ch.blocked or not ch.queue:
                idle_scan += 1
                continue
            idle_scan = 0
            element = ch.queue.popleft()
            if element.is_batch:
                ch.extra_rows -= len(element) - 1
            self._dispatch(ch, element)
            # a batch debits its row count, so step latency (barrier
            # reaction, flush cadence) stays bounded in rows
            processed += len(element) if element.is_batch else 1
        # the step boundary is a flush point: downstream (and the
        # executor's quiescence check) must see everything this step
        # emitted
        self.router.flush_records()
        return processed

    def _dispatch(self, ch: _InputChannel, element):
        # records, batches and watermarks are the operator chain's work:
        # one span each while the tracer is on (per element, so gated;
        # the always-on phases lie inside the operators)
        tracer = get_tracer()
        if tracer.enabled and (element.__class__ is StreamRecord
                               or element.is_record or element.is_batch
                               or element.is_watermark):
            with tracer.span(self._span_process):
                self._dispatch_element(ch, element)
        else:
            self._dispatch_element(ch, element)

    def _dispatch_element(self, ch: _InputChannel, element):
        if element.__class__ is StreamRecord or element.is_record:
            self.process_record(ch.input_index, element)
        elif element.is_batch:
            self.process_batch_element(ch.input_index, element)
        elif element.is_watermark:
            self.process_channel_watermark(ch.input_index, ch.channel_id,
                                           element)
        elif element.is_barrier:
            self._on_barrier(ch, element)
        elif isinstance(element, EndOfStream):
            self._on_end_of_stream(ch)
        elif element.is_latency_marker:
            if self.latency_stats is not None:
                self.latency_stats.record(
                    element, self.head.operator_id,
                    _time.time() * 1000.0 - element.marked_time)
            self.head.process_latency_marker(element)

    # ---- barrier handling -------------------------------------------
    def _live_channel_ids(self) -> Set[int]:
        return {c.channel_id for c in self.input_channels
                if not c.eos and not c.is_feedback}

    def _on_barrier(self, ch: _InputChannel, barrier: CheckpointBarrier):
        if barrier.options.get("mode") == "at_least_once":
            # ref: BarrierTracker — count, never block
            entry = self._tracker_counts.setdefault(
                barrier.checkpoint_id, (barrier, set()))
            entry[1].add(ch.channel_id)
            if entry[1] >= self._live_channel_ids():
                del self._tracker_counts[barrier.checkpoint_id]
                self._complete_checkpoint(barrier)
            return
        # exactly-once alignment (ref: BarrierBuffer.processBarrier :222)
        if barrier.checkpoint_id <= getattr(self, "_aborted_cid", -1):
            return  # stragglers of alignment-cap aborts: ignore every
            # barrier at or below the newest aborted id (ids ascend)
        if self._align_id is None:
            self._align_id = barrier.checkpoint_id
            self._align_barrier = barrier
            self._align_received = set()
            tracer = get_tracer()
            if tracer.enabled:
                # one marker per alignment episode, causally linked to
                # the coordinator trigger via the barrier's context
                ctx = barrier.options.get("trace") \
                    if isinstance(barrier.options, dict) else None
                tracer.record_instant(
                    "checkpoint.align.begin",
                    checkpoint_id=barrier.checkpoint_id,
                    task=self.vertex.name, subtask=self.subtask_index,
                    **({"trace_id": ctx["trace_id"],
                        "parent_span_id": ctx["span_id"]} if ctx else {}))
        elif barrier.checkpoint_id != self._align_id:
            # a newer barrier cancels the in-flight alignment
            self._release_alignment()
            self._align_id = barrier.checkpoint_id
            self._align_barrier = barrier
            self._align_received = set()
        self._align_received.add(ch.channel_id)
        ch.blocked = True
        self._maybe_complete_alignment()

    def _maybe_complete_alignment(self):
        if self._align_id is None:
            return
        if self._align_received >= self._live_channel_ids():
            barrier = self._align_barrier
            self._release_alignment()
            self._complete_checkpoint(barrier)

    def note_alignment_element(self) -> None:
        """One more element buffered behind the alignment; past the
        configured cap the checkpoint ABORTS (release + decline)
        rather than buffering without bound (ref: the alignment-size
        abort of TaskManagerOptions.java:342)."""
        self._align_buffered += 1
        cap = self.alignment_abort_limit
        if cap is not None and self._align_id is not None \
                and self._align_buffered > cap:
            cid = self._align_id
            barrier = self._align_barrier
            self.alignment_aborts += 1
            self._aborted_cid = max(
                getattr(self, "_aborted_cid", -1), cid)
            self._release_alignment()
            # forward the barrier WITHOUT snapshotting here (the
            # CancelCheckpointMarker role): downstream paths still see
            # cid on every channel, so no stale-barrier inversion; the
            # decline below makes the coordinator drop their acks
            self.router.broadcast_barrier(barrier)
            if self.decline_fn is not None:
                self.decline_fn(cid)

    def _release_alignment(self):
        for c in self.input_channels:
            c.blocked = False
            c.unspill()
        self._align_id = None
        self._align_barrier = None
        self._align_received = set()
        self._align_buffered = 0

    def _complete_checkpoint(self, barrier: CheckpointBarrier):
        """All channels aligned: snapshot, forward barrier, ack (ref:
        StreamTask.triggerCheckpointOnBarrier :586 →
        performCheckpoint :618 — barrier forwarded first, then
        snapshot, both atomically on this loop)."""
        ctx = (barrier.options.get("trace")
               if isinstance(barrier.options, dict) else None)
        tracer = get_tracer()
        # the synchronous part of a checkpoint on this task: barrier
        # taken -> ack handed over (a backend with an asynchronous
        # part acks a handle the checkpoint's writer resolves)
        with tracer.phase("checkpoint.sync",
                          checkpoint_id=barrier.checkpoint_id), \
                tracer.span_linked(self._span_checkpoint, ctx,
                                   checkpoint_id=barrier.checkpoint_id,
                                   task=self.vertex.name,
                                   subtask=self.subtask_index):
            snapshot = self.snapshot(barrier.checkpoint_id)
            self.router.broadcast_barrier(barrier)
            if self.ack_fn is not None:
                self.ack_fn(self.task_key, barrier.checkpoint_id,
                            snapshot)

    def _on_end_of_stream(self, ch: _InputChannel):
        ch.eos = True
        ch.blocked = False
        self._maybe_complete_alignment()
        if all(c.eos for c in self.input_channels if not c.is_feedback):
            self.finished = True
            self.router.broadcast_end_of_stream()

    def has_queued_input(self) -> bool:
        # un-flushed router output counts: a quiescence check must not
        # terminate the job while records sit in the emit buffer
        return (self.router.has_queued_output()
                or any(c.queue for c in self.input_channels))

    # ---- input path (ref: StreamInputProcessor.processInput :176) ---
    def process_record(self, input_index: int, record: StreamRecord):
        if faults._active is not None:
            faults.fire("task.process")
        if self.io_metrics is not None:
            self.io_metrics.num_records_in.count += 1
        head = self.head
        if isinstance(head, TwoInputStreamOperator):
            if input_index == 0:
                head.set_key_context(record)
                head.process_element1(record)
            else:
                if hasattr(head, "set_key_context2"):
                    head.set_key_context2(record)
                head.process_element2(record)
        else:
            head.set_key_context(record)
            head.process_element(record)

    def process_batch_element(self, input_index: int, batch):
        """RecordBatch through the head: the operator's process_batch
        path (kernel or one-time boxing fallback).  Two-input heads
        have per-input key contexts, so they box here."""
        if faults._active is not None:
            faults.fire("task.process")
        if self.io_metrics is not None:
            self.io_metrics.num_records_in.count += len(batch)
        head = self.head
        if isinstance(head, TwoInputStreamOperator):
            if input_index == 0:
                for record in batch.to_records():
                    head.set_key_context(record)
                    head.process_element1(record)
            else:
                has_kc2 = hasattr(head, "set_key_context2")
                for record in batch.to_records():
                    if has_kc2:
                        head.set_key_context2(record)
                    head.process_element2(record)
        else:
            fused = head._fused_chain
            if fused is not None and fused.wants(batch):
                fused.run(batch)
            else:
                head.process_batch(batch)

    def process_channel_watermark(self, input_index: int, channel_id: int,
                                  watermark: Watermark):
        """Per-channel min-combine (ref: StatusWatermarkValve)."""
        chans = self._watermarks.setdefault(input_index, {})
        if channel_id not in chans:
            chans[channel_id] = MIN_TIMESTAMP
        if watermark.timestamp <= chans[channel_id]:
            return
        chans[channel_id] = watermark.timestamp
        new_min = min(chans.values())
        if new_min <= self._current_wm.get(input_index, MIN_TIMESTAMP):
            return
        self._current_wm[input_index] = new_min
        head = self.head
        wm = Watermark(new_min)
        if isinstance(head, TwoInputStreamOperator):
            if input_index == 0:
                head.process_watermark1(wm)
            else:
                head.process_watermark2(wm)
        else:
            head.process_watermark(wm)

    # ---- snapshot ---------------------------------------------------
    def snapshot(self, checkpoint_id: Optional[int] = None) -> dict:
        return {"operators": {op.operator_id: op.snapshot_state(checkpoint_id)
                              for op in self.operators}}

    def restore(self, snapshots: List[dict]) -> None:
        for op in self.operators:
            per_op = [s["operators"][op.operator_id] for s in snapshots
                      if op.operator_id in s.get("operators", {})]
            if per_op:
                op.restore_state(per_op)

    def notify_checkpoint_complete(self, checkpoint_id: int) -> None:
        if self._thread is not None:
            # a thread-hosted source's run() may mutate the same state
            # its commit callback touches — the callback must run under
            # the emission lock.  A BLOCKING acquire here would
            # deadlock: the source can hold the lock across a
            # backpressure wait that only this executor loop relieves.
            # So queue it; it is delivered at the next emission
            # boundary (or opportunistically from the loop).
            self.pending_notifications.append(checkpoint_id)
            self.try_deliver_notifications()
            return
        for op in self.operators:
            op.notify_checkpoint_complete(checkpoint_id)

    def try_deliver_notifications(self):
        if not self.pending_notifications:
            return
        if self.emission_lock.acquire(blocking=False):
            try:
                self._deliver_notifications_locked()
            finally:
                self.emission_lock.release()

    def _deliver_notifications_locked(self):
        while self.pending_notifications:
            cid = self.pending_notifications.popleft()
            for op in self.operators:
                op.notify_checkpoint_complete(cid)


class _LockedSourceOutput(Output):
    """Head output for threaded sources: every emission takes the
    subtask's emission lock, handles a pending barrier trigger at the
    record boundary, applies backpressure (bounded downstream queues),
    then forwards to the head operator's real output."""

    def __init__(self, subtask: SubtaskInstance):
        self._st = subtask
        self._inner = subtask.head.output

    def _emit(self, fn, element):
        st = self._st
        # backpressure outside the lock so barrier injection can
        # proceed while we wait; a closing task stops applying it so
        # the thread can observe cancellation instead of spinning
        while (not st.router.has_capacity() and not st.closed
               and not st.cancelling):
            st.router.last_blocked_mono = _time.monotonic()
            _time.sleep(0.0005)
        with st.emission_lock:
            st._deliver_notifications_locked()
            st.handle_pending_trigger()
            fn(element)
            # threaded sources flush per emission: the executor loop
            # never steps them, so nothing else would drain the buffer
            st.router.flush_records()

    def collect(self, record):
        self._emit(self._inner.collect, record)

    def collect_batch(self, batch):
        self._emit(self._inner.collect_batch, batch)

    def emit_watermark(self, watermark):
        self._emit(self._inner.emit_watermark, watermark)

    def collect_side(self, tag, record):
        with self._st.emission_lock:
            self._inner.collect_side(tag, record)

    def emit_latency_marker(self, marker):
        self._emit(self._inner.emit_latency_marker, marker)


class JobClient:
    """Handle on a running job (ref: the client side of
    ClusterClient/JobMaster: cancel + result retrieval)."""

    def __init__(self):
        self._cancel = threading.Event()
        self._done = threading.Event()
        self._result: Optional[JobExecutionResult] = None
        self._error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        #: live view for tests/monitoring; swapped on restart
        self.executor_state: Optional[dict] = None
        #: per-attempt failure records (ref: the JobExceptionsHandler
        #: payload behind /jobs/:jobid/exceptions), newest last
        self.exception_history: List[dict] = []

    def _record_failure(self, error: BaseException, attempt: int) -> None:
        entry = {
            "attempt": attempt,
            "timestamp": _time.time(),
            "exception": f"{type(error).__name__}: {error}",
        }
        task_key = getattr(error, "task_key", None)
        if task_key is not None:
            entry["task_key"] = list(task_key)
        cause = getattr(error, "cause", None)
        if cause is not None:
            entry["root_exception"] = f"{type(cause).__name__}: {cause}"
        self.exception_history.append(entry)
        del self.exception_history[:-32]  # bounded history

    def cancel(self) -> None:
        self._cancel.set()

    @property
    def cancel_requested(self) -> bool:
        return self._cancel.is_set()

    def wait(self, timeout: Optional[float] = None) -> JobExecutionResult:
        self._done.wait(timeout)
        if not self._done.is_set():
            raise TimeoutError("job still running")
        if self._error is not None:
            raise self._error
        return self._result

    # ---- savepoints (ref: the `flink savepoint` / `cancel -s` CLI
    # verbs on ClusterClient.triggerSavepoint / cancelWithSavepoint) --
    def trigger_savepoint(self, directory: str,
                          timeout: float = 60.0) -> str:
        """Blocks until the savepoint is written; returns its path."""
        # the executor thread publishes executor_state during attempt
        # setup — an immediate post-submit request must wait for it
        deadline = _time.monotonic() + min(timeout, 5.0)
        coordinator = None
        while _time.monotonic() < deadline and not self.done:
            coordinator = (self.executor_state or {}).get("coordinator")
            if coordinator is not None:
                break
            _time.sleep(0.002)
        if coordinator is None:
            if self.done:
                raise RuntimeError(
                    "cannot savepoint: the job is no longer running")
            raise RuntimeError(
                "savepoints require checkpointing to be enabled "
                "(env.enable_checkpointing)")
        return coordinator.trigger_savepoint(directory).wait(timeout)

    def stop_with_savepoint(self, directory: str,
                            timeout: float = 60.0) -> str:
        """Savepoint, then cancel (ref: cancel -s).  The cancellation
        lands after the savepoint completes — records processed in the
        window between are at-least-once for external side effects, as
        with the reference's cancelWithSavepoint (vs the later
        stop-with-savepoint's drain)."""
        path = self.trigger_savepoint(directory, timeout)
        self.cancel()
        self._done.wait(timeout)
        return path

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def _finish(self, result=None, error=None):
        self._result = result
        self._error = error
        self._done.set()


def make_health_plane(metrics, sample_interval_ms: Optional[int],
                      history_size: int, job_name: str, client):
    """Journal + health evaluator for one job — created once per job
    (shared across restart attempts so history survives failover).
    Returns (None, None) when sampling is disabled, so the executor
    loop's tick is a single None check.  Shared by LocalExecutor and
    MiniCluster."""
    if sample_interval_ms is None:
        return None, None
    from flink_tpu.runtime.timeseries import (
        HealthEvaluator, MetricsJournal, register_health_gauges)
    journal = MetricsJournal(metrics, interval_ms=sample_interval_ms,
                             history_size=history_size)

    def bottleneck_supplier():
        state = getattr(client, "executor_state", None) or {}
        return locate_bottleneck(
            state.get("upstreams") or {},
            read_vertex_stats(metrics.dump(), job_name))

    evaluator = HealthEvaluator(
        journal,
        coordinator_supplier=lambda: (
            getattr(client, "executor_state", None) or {}
        ).get("coordinator"),
        bottleneck_supplier=bottleneck_supplier)
    register_health_gauges(metrics, job_name, evaluator)
    return journal, evaluator


def archive_finished_job(archive_dir: Optional[str], metrics,
                         job_graph: JobGraph, client,
                         journal, evaluator) -> None:
    """Write the finished job's post-mortem bundle (summary + metrics
    + journal + checkpoint stats + alerts + trace) when archive_dir is
    set; archiving never fails the job.  Shared by LocalExecutor and
    MiniCluster (the cluster Dispatcher archives in _archive_job)."""
    if archive_dir is None:
        return
    try:
        from flink_tpu.runtime.history import (
            FsJobArchivist, build_archive_summary)
        from flink_tpu.runtime.rest import WebMonitor
        state = getattr(client, "executor_state", None) or {}
        result = getattr(client, "_result", None)
        FsJobArchivist.archive(
            archive_dir, job_graph.job_name,
            build_archive_summary(
                job_graph.job_name,
                WebMonitor._job_status(client)["status"],
                restarts=getattr(result, "restarts", 0) or 0,
                checkpoints_completed=getattr(
                    result, "checkpoints_completed", 0) or 0,
                registry=metrics, journal=journal,
                evaluator=evaluator,
                coordinator=state.get("coordinator"),
                checkpoints_base=state.get("checkpoints_base", 0),
                exceptions=list(
                    getattr(client, "exception_history", None) or []),
                upstreams=state.get("upstreams")))
    except Exception:  # noqa: BLE001 — post-mortem only
        pass


class LocalExecutor:
    """Runs a JobGraph in-process with a cooperative streaming loop
    (the single-worker MiniCluster analogue)."""

    #: elements per subtask per loop iteration
    STEP_BUDGET = 256
    #: records per cooperative source step
    SOURCE_BATCH = 128

    def __init__(self, state_backend: str = "heap", max_parallelism: int = 128,
                 restart_strategy: Optional[dict] = None,
                 processing_time_service=None,
                 channel_capacity: int = DEFAULT_CHANNEL_CAPACITY,
                 metric_registry=None,
                 latency_interval_ms: Optional[int] = None,
                 failover_strategy: str = "full",
                 sample_interval_ms: Optional[int] = None,
                 metrics_history_size: int = 1024,
                 archive_dir: Optional[str] = None):
        self.state_backend = state_backend
        self.max_parallelism = max_parallelism
        self.restart_strategy_config = restart_strategy or {"strategy": "none"}
        self.pts = processing_time_service or TestProcessingTimeService()
        self.channel_capacity = channel_capacity
        self.metrics = metric_registry or MetricRegistry()
        register_state_gauges(self.metrics)
        register_state_introspection_gauges(self.metrics)
        register_device_gauges(self.metrics)
        register_profiler_gauges(self.metrics)
        self.latency_interval_ms = latency_interval_ms
        #: "full" | "region" (ref: FailoverStrategyLoader /
        #: jobmanager.execution.failover-strategy)
        self.failover_strategy = failover_strategy
        #: metrics time-series journal cadence (None = disabled: no
        #: journal object exists, zero per-loop cost)
        self.sample_interval_ms = sample_interval_ms
        self.metrics_history_size = metrics_history_size
        #: when set, finished jobs archive their post-mortem bundle
        #: here for the HistoryServer (history.archive.dir)
        self.archive_dir = archive_dir

    # ---- graph → subtasks ------------------------------------------
    def build_subtasks(self, job_graph: JobGraph) -> Dict[int, List[SubtaskInstance]]:
        return build_and_wire_subtasks(
            job_graph, self.state_backend, self.max_parallelism,
            lambda vid, i: self.pts, self.channel_capacity, self.metrics)

    # ---- public API -------------------------------------------------
    #: callables handed the JobClient of a job `execute` is about to
    #: run (the environment's job listeners)
    job_listeners: tuple = ()

    def execute(self, job_graph: JobGraph) -> JobExecutionResult:
        client = JobClient()
        for listener in self.job_listeners:
            listener(client)
        self._run_job(job_graph, client)
        return client.wait()

    def execute_async(self, job_graph: JobGraph) -> JobClient:
        client = JobClient()
        t = threading.Thread(target=self._run_job,
                             args=(job_graph, client),
                             daemon=True, name="job-executor")
        client._thread = t
        t.start()
        return client

    # ---- job driver (with restarts) ---------------------------------
    def _make_health_plane(self, job_name: str, client):
        return make_health_plane(self.metrics, self.sample_interval_ms,
                                 self.metrics_history_size, job_name,
                                 client)

    def _maybe_archive(self, job_graph: JobGraph, client,
                       journal, evaluator) -> None:
        archive_finished_job(self.archive_dir, self.metrics, job_graph,
                             client, journal, evaluator)

    def _run_job(self, job_graph: JobGraph, client: JobClient) -> None:
        result = JobExecutionResult(job_graph.job_name)
        cp_config = job_graph.checkpoint_config
        storage = make_checkpoint_storage(cp_config) if cp_config else None
        restart = make_restart_strategy(self.restart_strategy_config)
        restore_from = initial_restore_point(job_graph)
        carryover = None
        journal, evaluator = self._make_health_plane(
            job_graph.job_name, client)
        regions = (compute_pipelined_regions(job_graph)
                   if self.failover_strategy == "region" else None)
        # TaskKey -> region, built once per job: per-failure lookups
        # must not scan every region of a wide embarrassingly
        # parallel graph
        region_index = (build_region_index(regions)
                        if regions is not None else None)
        try:
            while True:
                try:
                    self._run_attempt(job_graph, client, result, storage,
                                      restore_from, carryover,
                                      journal, evaluator)
                    client._finish(result=result)
                    return
                except JobCancelledException:
                    result.cancelled = True
                    client._finish(result=result)
                    return
                except SuppressRestartsException as e:
                    client._record_failure(e.cause, result.restarts)
                    raise e.cause
                except Exception as e:  # noqa: BLE001
                    client._record_failure(e, result.restarts)
                    restart.notify_failure(_time.monotonic() * 1000.0)
                    if client.cancel_requested or not restart.can_restart():
                        if isinstance(e, TaskFailureException):
                            raise e.cause from e
                        raise
                    result.restarts += 1
                    if restart.delay_ms:
                        _time.sleep(restart.delay_ms / 1000.0)
                    restore_from = storage.latest() if storage else None
                    carryover = None
                    if (regions is not None
                            and isinstance(e, TaskFailureException)
                            and getattr(e, "live_state", None) is not None):
                        failed_region = set(region_of(
                            regions, e.task_key, region_index))
                        # a healthy subtask whose capture failed pulls
                        # its whole region into the restart scope
                        for fk in getattr(e, "capture_failed_keys", []):
                            failed_region |= region_of(
                                regions, fk, region_index)
                        healthy = {k for k, v in e.live_state.items()
                                   if k not in failed_region}
                        if healthy:
                            # restart-pipelined-region: healthy regions
                            # carry their live state (operators, queued
                            # elements, watermarks, alignment) across
                            # the restart; only the failed region
                            # restores from the checkpoint
                            carryover = {k: e.live_state[k]
                                         for k in healthy}
                            result.region_restarts += 1
                            if restore_from is not None:
                                restore_from = {
                                    **restore_from,
                                    "tasks": {
                                        k: v for k, v
                                        in restore_from["tasks"].items()
                                        if k in failed_region}}
        except BaseException as e:  # noqa: BLE001
            client._finish(error=e)
        finally:
            self._maybe_archive(job_graph, client, journal, evaluator)

    def _run_attempt(self, job_graph: JobGraph, client: JobClient,
                     result: JobExecutionResult, storage,
                     restore_from: Optional[dict],
                     carryover: Optional[dict] = None,
                     journal=None, evaluator=None) -> None:
        subtasks = self.build_subtasks(job_graph)
        all_tasks: List[SubtaskInstance] = [
            st for v in job_graph.topological_vertices() for st in subtasks[v.id]]
        sources = [st for st in all_tasks if st.is_source]
        non_sources = [st for st in all_tasks if not st.is_source]
        coop_sources = [s for s in sources if s.supports_stepping]
        threaded_sources = [s for s in sources if not s.supports_stepping]

        # restore BEFORE open: descriptors bind in open(), but keyed
        # backends require registered descriptors before restore — so
        # open first, then restore (matches StreamTask.initializeState
        # ordering: state handles assigned, then operators opened; our
        # backends support restore-after-bind)
        for st in all_tasks:
            st.open()
        if carryover is not None:
            # region failover: healthy subtasks resume their LIVE state
            # (operators + queued elements + watermarks + alignment);
            # the failed region restores from the checkpoint below
            for st in all_tasks:
                cap = carryover.get(st.task_key)
                if cap is not None:
                    _restore_live_capture(st, cap)
                elif restore_from is not None \
                        and st.task_key in restore_from["tasks"]:
                    st.restore([restore_from["tasks"][st.task_key]])
        elif restore_from is not None:
            # failover restores one-to-one; savepoint restore handles
            # rescale (key-group re-split + operator-state round robin)
            assign_restore_snapshots(job_graph, restore_from, subtasks)

        # checkpoint coordination
        ack_queue: deque = deque()
        coordinator = None
        if storage is not None and job_graph.checkpoint_config.get("interval"):
            cfg = job_graph.checkpoint_config

            def trigger_sources(cid, ts, options):
                # 1.5 likewise fails checkpoints once a task finished
                if any(s.finished for s in sources):
                    return False
                for s in sources:
                    s.pending_trigger = (cid, ts, options)
                return True

            def notify_complete(cid):
                for st in all_tasks:
                    st.notify_checkpoint_complete(cid)

            coordinator = CheckpointCoordinator(
                interval_ms=cfg["interval"],
                mode=cfg.get("mode", "exactly_once"),
                storage=storage,
                expected_tasks={st.task_key for st in all_tasks},
                trigger_sources=trigger_sources,
                notify_complete=notify_complete,
                min_pause_ms=cfg.get("min_pause", 0),
                async_persist=bool(cfg.get("async_persist", False)),
                checkpoint_timeout_ms=cfg.get("timeout"),
                tolerable_checkpoint_failures=cfg.get("tolerable_failures"),
            )
            coordinator.vertex_parallelisms = {
                vid: v.parallelism for vid, v in job_graph.vertices.items()}
            register_checkpoint_gauges(self.metrics, job_graph.job_name,
                                       coordinator)
            register_faulttolerance_gauges(self.metrics, job_graph.job_name,
                                           coordinator)
            # continue the id sequence across restarts
            ids = storage.checkpoint_ids()
            if ids:
                coordinator._id_counter = ids[-1]

        def ack(task_key, cid, snapshot):
            if faults.check("checkpoint.ack"):
                return  # ack lost in transit — coordinator times out
            ack_queue.append((task_key, cid, snapshot))

        def decline(cid):
            ack_queue.append((None, cid, None))   # decline marker

        cp_cfg = job_graph.checkpoint_config or {}
        for st in all_tasks:
            st.ack_fn = ack
            st.decline_fn = decline
            # the coordinator resolves what an ack defers
            # (`CheckpointCoordinator._do_persist`)
            for op in st.operators:
                op.deferred_snapshots = coordinator is not None
            if "alignment_spill_threshold" in cp_cfg:
                st.alignment_spill_threshold = \
                    cp_cfg["alignment_spill_threshold"]
            if "alignment_abort_limit" in cp_cfg:
                st.alignment_abort_limit = \
                    cp_cfg["alignment_abort_limit"]

        client.executor_state = {
            "subtasks": subtasks, "coordinator": coordinator,
            # checkpoints completed by PRIOR attempts: live views add
            # the current coordinator's count so totals never reset
            # across restarts (same accumulation as the result object)
            "checkpoints_base": getattr(result, "_cp_base", 0),
            "journal": journal, "health": evaluator,
            "upstreams": derive_upstreams(job_graph),
        }

        for s in threaded_sources:
            s.run_source_threaded()

        try:
            self._loop(client, result, coordinator, ack_queue,
                       all_tasks, sources, coop_sources, threaded_sources,
                       non_sources, journal, evaluator)
        except TaskFailureException as tfe:
            if self.failover_strategy == "region" and not any(
                    not s.supports_stepping for s in sources):
                # capture live state BEFORE teardown for region
                # carryover (thread-hosted sources can't carry over:
                # their run() would restart from scratch — fall back
                # to full restart by not capturing)
                tfe.live_state, tfe.capture_failed_keys = \
                    _capture_live_state(all_tasks, tfe.task_key)
            raise
        finally:
            if coordinator is not None:
                try:
                    coordinator.drain()  # land in-flight async writes
                except Exception:  # noqa: BLE001 — teardown: the attempt's
                    pass               # outcome is already decided
                # completed_count is per attempt; accumulate across restarts
                result.checkpoints_completed = (
                    getattr(result, "_cp_base", 0) + coordinator.completed_count)
                result._cp_base = result.checkpoints_completed
                coordinator.stopped = True
                coordinator.fail_pending_savepoints(
                    RuntimeError("job attempt ended before the savepoint "
                                 "completed"))
            for s in sources:
                s.cancel_source()
            for s in threaded_sources:
                s.join_source()
            for st in all_tasks:
                st.close()

    # ---- the loop ---------------------------------------------------
    def _loop(self, client, result, coordinator, ack_queue, all_tasks,
              sources, coop_sources, threaded_sources, non_sources,
              journal=None, evaluator=None):
        pts = self.pts
        pts_poll = getattr(pts, "fire_due", None)
        profiler = get_profiler()
        last_latency_emit = _time.monotonic()
        while True:
            if client.cancel_requested:
                raise JobCancelledException()
            progress = 0

            # periodic latency markers from sources (ref: the
            # latencyMarksInterval emission in StreamSource.run)
            if self.latency_interval_ms is not None:
                now = _time.monotonic()
                if (now - last_latency_emit) * 1000.0 >= self.latency_interval_ms:
                    last_latency_emit = now
                    now_ms = _time.time() * 1000.0
                    for s in sources:
                        if s.finished:
                            continue
                        marker = LatencyMarker(now_ms, s.head.operator_id,
                                               s.subtask_index)
                        with s.emission_lock:
                            s.head.output.emit_latency_marker(marker)

            # 0. trigger before sources step, so a due checkpoint's
            # barrier rides ahead of this iteration's records
            if coordinator is not None and all(not s.finished for s in sources):
                coordinator.maybe_trigger()

            # 1. sources
            for s in coop_sources:
                if not s.finished:
                    if profiler.enabled:
                        profiler.set_scope(s)
                    try:
                        n = s.source_step(self.SOURCE_BATCH)
                    except Exception as e:  # noqa: BLE001
                        raise TaskFailureException(s.task_key, e) from e
                    progress += n
                    observe_subtask(s, n > 0)
            for s in threaded_sources:
                if s.thread_error is not None:
                    raise TaskFailureException(s.task_key, s.thread_error) \
                        from s.thread_error
                observe_threaded_source(s)
                s.try_inject_threaded_trigger()
                s.try_deliver_notifications()
                if s.router.has_queued_output() \
                        and s.emission_lock.acquire(blocking=False):
                    # executor-side emissions (timer callbacks) into a
                    # threaded source's router flush under its
                    # emission lock, opportunistically like triggers
                    try:
                        s.router.flush_records()
                    finally:
                        s.emission_lock.release()

            # 2. operators
            for st in non_sources:
                if profiler.enabled:
                    profiler.set_scope(st)
                try:
                    n = st.step(self.STEP_BUDGET)
                except Exception as e:  # noqa: BLE001
                    raise TaskFailureException(st.task_key, e) from e
                progress += n
                observe_subtask(st, n > 0)

            # 3. processing time (polled services fire on this loop —
            # the single-owner replacement for the reference's timer
            # thread + checkpoint lock)
            if pts_poll is not None:
                fired = pts_poll()
                if fired:
                    # timer callbacks emit outside step()/source_step —
                    # flush their router buffers so the output is
                    # visible (termination check + downstream queues).
                    # Threaded sources flush above, under their lock.
                    for st in non_sources:
                        st.router.flush_records()
                    for s in coop_sources:
                        s.router.flush_records()
                progress += fired

            # 4. checkpoints
            if coordinator is not None:
                while ack_queue:
                    task_key, cid, snapshot = ack_queue.popleft()
                    if task_key is None:   # alignment-cap decline
                        coordinator.decline(cid)
                    else:
                        coordinator.acknowledge(task_key, cid, snapshot)
                # a source that finished with an unhandled trigger can
                # never ack — decline that checkpoint (threaded-source
                # race; cooperative sources handle triggers in-step)
                for s in sources:
                    if s.finished and s.pending_trigger is not None:
                        cid = s.pending_trigger[0]
                        s.pending_trigger = None
                        coordinator.decline(cid)

            # 4.5 metrics journal tick (two comparisons when no
            # journal exists or none is due) + health rules on sample
            if journal is not None and journal.maybe_sample():
                evaluator.evaluate()

            # 5. termination: sources done, every queue drained, and
            # no source thread still able to produce
            if (all(s.finished for s in sources)
                    and not any(st.has_queued_input() for st in non_sources)
                    and all(s._thread is None or not s._thread.is_alive()
                            for s in threaded_sources)):
                break
            if progress == 0:
                # nothing runnable on this loop; threaded sources or
                # wall-clock timers may produce work
                _time.sleep(0.0002)

        # end of input: drain processing-time timers so finite jobs
        # with processing-time windows emit their tails (a local-
        # runtime convenience; a long-running job's clock keeps going).
        # Timer firings can EMIT across vertex edges, whose queued
        # records must then be processed — and that processing can
        # register further timers, so alternate until quiescent.
        if isinstance(pts, TestProcessingTimeService):
            for _ in range(1000):  # bounded cascade
                pts.fire_all_pending()
                for st in all_tasks:
                    st.router.flush_records()
                moved = sum(st.step(1 << 30) for st in non_sources)
                if moved == 0 and not pts.has_pending():
                    break
        # final acks (a checkpoint may complete exactly at the end)
        if coordinator is not None:
            while ack_queue:
                task_key, cid, snapshot = ack_queue.popleft()
                coordinator.acknowledge(task_key, cid, snapshot)
        # finish phase: end-of-input flush (2PC tail commits, source
        # offset commits), topologically, draining any emissions.  Runs
        # only once EVERY task has drained, and failures here suppress
        # the restart strategy: input is fully consumed and committed
        # transactions cannot be replayed exactly-once.
        try:
            for st in all_tasks:
                for op in st.operators:
                    op.finish()
                st.router.flush_records()
                for t in non_sources:
                    t.step(1 << 30)
        except Exception as e:  # noqa: BLE001
            raise SuppressRestartsException(e) from e
        gather_accumulators(all_tasks, result.accumulators)


def merge_accumulators(into: Dict[str, Any], accs: Dict[str, Any]) -> None:
    """Lists concatenate, numbers add, anything else last-wins (the
    Accumulator.merge contract, flink-core/.../accumulators/)."""
    for name, value in accs.items():
        if name in into and isinstance(into[name], list) \
                and isinstance(value, list):
            into[name] = into[name] + value
        elif name in into and isinstance(into[name], (int, float)) \
                and isinstance(value, (int, float)):
            into[name] = into[name] + value
        else:
            into[name] = value


def _op_snap_has_state(opsnap: dict) -> bool:
    """Does one operator's snapshot carry anything whose loss would
    change results?  Standard keys check their payloads; any custom
    key (engine state, function state, buffers) counts."""
    for k, v in opsnap.items():
        if k == "keyed":
            if getattr(v, "key_group_bytes", None):
                return True
        elif k == "operator":
            if getattr(v, "list_states", None) \
                    or getattr(v, "broadcast_states", None):
                return True
        elif k == "timers":
            if isinstance(v, dict) and (v.get("event") or v.get("proc")):
                return True
        elif k == "restore_old_parallelism":
            continue
        else:
            return True
    return False


def _vertex_has_state(snaps: List[dict]) -> bool:
    return any(_op_snap_has_state(op)
               for s in snaps
               for op in s.get("operators", {}).values())


def compute_restore_assignments(vertex_parallelisms: Dict[int, int],
                                restore_from: dict,
                                vertex_uids: Optional[Dict[int, set]] = None,
                                allow_non_restored: bool = False
                                ) -> Dict[Tuple[int, int], List[dict]]:
    """Map a checkpoint/savepoint's task snapshots onto (possibly
    rescaled) subtasks (ref: StateAssignmentOperation.java — key-group
    range re-split on rescale).  Returns task_key -> snapshot list.

    Vertex identity: with `vertex_uids` (new-graph vid -> set of chain
    operator uids), old vertices match new ones by OPERATOR-UID
    OVERLAP — the snapshot itself records which operator uids it
    holds, so state survives topology re-shapes (a re-lowered plan
    inserting/removing nodes, or chaining changes splitting a vertex;
    ref: the uid matching of StateAssignmentOperation + the
    `uid()`/`setUidHash` contract).  An old vertex carrying REAL state
    that matches nothing raises unless allow_non_restored (the
    reference's --allowNonRestoredState); stateless unmatched
    snapshots drop silently.  Without vertex_uids the mapping is
    positional (vid == vid).

    Same parallelism → one-to-one.  Parallelism changed:
    - keyed state + timers go to every new subtask (backends and timer
      services filter by their key-group range); each per-operator
      snapshot is annotated with `restore_old_parallelism` so
      engine-carrying operators can re-split their own keyed state;
    - operator list state re-splits round-robin
      (RoundRobinOperatorStateRepartitioner);
    - CheckpointedFunction ('function') state assigns each OLD
      subtask's state to exactly ONE new subtask, round-robin — never
      broadcast (a 2PC sink's pending transactions must recover
      exactly once; scale-down hands several states to one subtask,
      whose restore hook runs once per state)."""
    from flink_tpu.state.operator_state import OperatorStateSnapshot

    task_snaps: Dict[Tuple[int, int], dict] = restore_from["tasks"]
    # old parallelism: recorded by savepoints; derived from snapshot
    # keys otherwise
    old_par: Dict[int, int] = dict(restore_from.get("parallelisms") or {})
    for (vid, idx) in task_snaps:
        old_par[vid] = max(old_par.get(vid, 0), idx + 1)

    def vsnaps_of(vid):
        return [task_snaps[(vid, i)] for i in range(old_par[vid])
                if (vid, i) in task_snaps]

    # old vid -> new vids it feeds
    edges: Dict[int, List[int]] = {}
    if vertex_uids is None:
        for vid in old_par:
            if vid in vertex_parallelisms:
                edges[vid] = [vid]
    else:
        for vid in old_par:
            uids = {op_id for s in vsnaps_of(vid)
                    for op_id in s.get("operators", {})}
            edges[vid] = [nvid for nvid, nuids in vertex_uids.items()
                          if uids & nuids]
    # orphan detection is OPERATOR-granular when uids are available: a
    # vertex may match via one pinned uid while a chained operator's
    # positional uid shifted — that operator's state would pass the
    # vertex check yet be silently filtered out by operator-id
    # matching at restore time
    if vertex_uids is not None:
        live_uids = set()
        for uids in vertex_uids.values():
            live_uids |= uids
        orphan_ops = sorted({
            op_id
            for vid in old_par
            for s in vsnaps_of(vid)
            for op_id, opsnap in s.get("operators", {}).items()
            if op_id not in live_uids and _op_snap_has_state(opsnap)})
        detail = (
            f"checkpoint state for operators {orphan_ops} matches no "
            f"operator uid in the restored topology (did the plan "
            f"shape change without stable .uid()s?)")
    else:
        orphaned = [vid for vid in old_par
                    if vid not in vertex_parallelisms]
        orphan_ops = sorted(vid for vid in orphaned
                            if _vertex_has_state(vsnaps_of(vid)))
        detail = (
            f"checkpoint state for vertices {orphan_ops} matches no "
            f"vertex in the restored topology")
    if orphan_ops:
        if not allow_non_restored:
            raise RuntimeError(
                detail + "; restoring would silently drop state. Set "
                "allow_non_restored_state to proceed without it.")
        import warnings
        warnings.warn(detail + "; DROPPED (allow_non_restored_state)",
                      stacklevel=2)

    out: Dict[Tuple[int, int], List[dict]] = {}
    for vid, new_vids in edges.items():
        if old_par.get(vid, 0) == 0:
            continue  # vertex had no snapshot (e.g. newly added)
        for nvid in new_vids:
            new_p = vertex_parallelisms[nvid]
            if old_par[vid] == new_p:
                for i in range(new_p):
                    if (vid, i) in task_snaps:
                        out.setdefault((nvid, i), []).append(
                            task_snaps[(vid, i)])
                continue
            # rescale: split out operator + function state, broadcast
            # the keyed/timer remainder (annotated with the old
            # parallelism so operators can key-group-filter)
            vsnaps = vsnaps_of(vid)
            stripped = []
            op_state_parts: Dict[str, List] = {}
            fn_states: Dict[str, List] = {}
            for snap in vsnaps:
                ops = {}
                for op_id, opsnap in snap.get("operators", {}).items():
                    cp = {k: v for k, v in opsnap.items()
                          if k not in ("operator", "function")}
                    cp["restore_old_parallelism"] = old_par[vid]
                    ops[op_id] = cp
                    if "operator" in opsnap:
                        op_state_parts.setdefault(op_id, []).append(
                            opsnap["operator"])
                    if "function" in opsnap:
                        fn_states.setdefault(op_id, []).append(
                            opsnap["function"])
                stripped.append({"operators": ops})
            redistributed = {
                op_id: OperatorStateSnapshot.redistribute(parts, new_p)
                for op_id, parts in op_state_parts.items()}
            for i in range(new_p):
                extras = [{"operators": {
                    op_id: {"operator": parts[i]}
                    for op_id, parts in redistributed.items()}}]
                for op_id, states in fn_states.items():
                    for fstate in states[i::new_p]:
                        extras.append({"operators": {op_id:
                                                     {"function": fstate}}})
                out.setdefault((nvid, i), []).extend(stripped + extras)
    return out


def assign_restore_snapshots(job_graph: JobGraph, restore_from: dict,
                             subtasks: Dict[int, List["SubtaskInstance"]]
                             ) -> None:
    mapping = compute_restore_assignments(
        {vid: v.parallelism for vid, v in job_graph.vertices.items()},
        restore_from,
        vertex_uids={vid: {n.uid for n in v.chain}
                     for vid, v in job_graph.vertices.items()},
        allow_non_restored=getattr(job_graph,
                                   "allow_non_restored_state", False))
    for sts in subtasks.values():
        for st in sts:
            snaps = mapping.get(st.task_key)
            if snaps:
                st.restore(snaps)


def initial_restore_point(job_graph: JobGraph) -> Optional[dict]:
    """A savepoint path attached to the job graph (execute-from-
    savepoint, the `flink run -s <path>` contract)."""
    path = getattr(job_graph, "savepoint_restore_path", None)
    if path is None:
        return None
    from flink_tpu.runtime.checkpoints import load_savepoint
    return load_savepoint(path)


def gather_accumulators(all_tasks, into: Dict[str, Any]) -> None:
    """Collect user-function accumulators into the job result (ref:
    the accumulator snapshot returned with the final ExecutionState).
    Deduplicated by function INSTANCE: parallel subtasks of an
    operator whose function is not per-subtask-copied (sinks) share
    one instance, which must contribute exactly once."""
    seen: Set[int] = set()
    for st in all_tasks:
        for op in st.operators:
            fn = getattr(op, "user_function", None)
            get_accs = getattr(fn, "accumulators", None)
            if callable(get_accs) and id(fn) not in seen:
                seen.add(id(fn))
                merge_accumulators(into, get_accs())


def _capture_live_state(all_tasks, failed_key):
    """Per-subtask live capture for region failover: operator
    snapshots, channel queues/flags, watermark valve state.  Returns
    (captured, capture_failed_keys); a subtask whose capture raises is
    reported so its WHOLE REGION joins the restart scope.

    In-flight checkpoint machinery does NOT carry over: queued
    CheckpointBarriers are dropped and alignment state resets — the
    in-flight checkpoint can never complete (the failed region never
    acks it), and the new attempt's coordinator reuses ids from the
    last COMPLETED checkpoint, so a carried barrier would collide with
    a re-issued id at a different stream position (an inconsistent
    cut)."""
    import copy as _copy
    out = {}
    capture_failed = []
    for st in all_tasks:
        if st.task_key == failed_key:
            continue
        try:
            out[st.task_key] = {
                "snap": resolve_task_snapshot(st.snapshot()),
                "finished": st.finished,
                "queues": [[el for el in ch.queue if not el.is_barrier]
                           for ch in st.input_channels],
                "eos": [ch.eos for ch in st.input_channels],
                "wm": (_copy.deepcopy(st._watermarks),
                       dict(st._current_wm)),
            }
        except Exception:  # noqa: BLE001 — expand the restart scope
            capture_failed.append(st.task_key)
    return out, capture_failed


def _restore_live_capture(st, cap) -> None:
    st.restore([cap["snap"]])
    st.finished = cap["finished"]
    for ch, q, eos in zip(st.input_channels, cap["queues"], cap["eos"]):
        ch.queue.extend(q)
        ch.eos = eos
    st._watermarks, st._current_wm = cap["wm"]


def _clone_partitioner(p):
    import copy
    return copy.copy(p)


def build_and_wire_subtasks(job_graph: JobGraph, state_backend: str,
                            max_parallelism: int, pts_selector,
                            channel_capacity: int,
                            metrics: MetricRegistry
                            ) -> Dict[int, List[SubtaskInstance]]:
    """Fan each JobVertex out to parallelism subtasks and wire edge
    channels: all-to-all for shuffling partitioners, contiguous groups
    for pointwise ones (ref: the DistributionPattern.POINTWISE wiring
    in ExecutionGraph).  `pts_selector(vertex_id, subtask_index)` picks
    the processing-time service — the MiniCluster gives each
    TaskManager its own so timers fire on the owning worker thread."""
    job_group = metrics.job_group(job_graph.job_name)
    latency_stats = LatencyStats(job_group)
    # native-kernel / jit-compile / span-aggregate gauges land at the
    # registry root (process-wide stores; both executors route here)
    register_runtime_profile_gauges(metrics)
    from flink_tpu.runtime.backpressure import register_backpressure_gauges
    subtasks: Dict[int, List[SubtaskInstance]] = {}
    for vid, vertex in job_graph.vertices.items():
        vertex_group = job_group.add_group(f"{vid}_{vertex.name}")
        subtasks[vid] = [
            SubtaskInstance(vertex, i, state_backend,
                            max_parallelism, pts_selector(vid, i),
                            channel_capacity,
                            metrics_group=vertex_group.add_group(str(i)),
                            latency_stats=latency_stats)
            for i in range(vertex.parallelism)
        ]
        # stamp attribution for the sampling profiler once at wiring
        # time — the sampler never derives scope on the hot path
        for i, st in enumerate(subtasks[vid]):
            st.profiler_scope = (job_graph.job_name,
                                 f"{vid}_{vertex.name}", i)
        register_backpressure_gauges(vertex_group, subtasks[vid])
    for edge in job_graph.edges:
        ups = subtasks[edge.source_vertex_id]
        downs = subtasks[edge.target_vertex_id]
        for i, up in enumerate(ups):
            if edge.partitioner.is_pointwise:
                from flink_tpu.runtime.failover import pointwise_targets
                targets = [downs[t] for t in
                           pointwise_targets(i, len(ups), len(downs))]
            else:
                targets = downs
            channels = [d.new_channel(edge.type_number) for d in targets]
            feedback = getattr(edge, "is_feedback", False)
            for ch in channels:
                ch.is_feedback = feedback
            partitioner = _clone_partitioner(edge.partitioner)
            up.router.add_route(partitioner, channels, edge.side_output_tag,
                                feedback=feedback)
    return subtasks

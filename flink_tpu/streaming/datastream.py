"""DataStream API: fluent stream-graph building.

Re-designs flink-streaming-java/.../api/datastream/ (DataStream.java,
KeyedStream.java, WindowedStream.java:305-850, AllWindowedStream,
ConnectedStreams) and api/environment/StreamExecutionEnvironment.java
(execute :1508, getStreamGraph :1532).  SURVEY.md §2.9 lists the
surface this mirrors.

Naming is pythonic snake_case; the call shapes match the reference:
env.from_collection(...).key_by(...).time_window(Time.seconds(5))
   .aggregate(agg).add_sink(sink); env.execute().
"""

from __future__ import annotations

from typing import Any, Iterable, List, Optional, Union

from flink_tpu.core.config import Configuration
from flink_tpu.core.functions import (
    AggregateFunction,
    as_filter_function,
    as_flat_map_function,
    as_key_selector,
    as_map_function,
    as_reduce_function,
)
from flink_tpu.core.state import (
    AggregatingStateDescriptor,
    FoldingStateDescriptor,
    ListStateDescriptor,
    ReducingStateDescriptor,
)
from flink_tpu.streaming.graph import (
    StreamEdge,
    StreamGraph,
    StreamNode,
    create_job_graph,
)
from flink_tpu.streaming.operators import (
    CoProcessOperator,
    CoStreamFlatMap,
    CoStreamMap,
    KeyedProcessOperator,
    ProcessOperator,
    StreamFilter,
    StreamFlatMap,
    StreamGroupedReduce,
    StreamMap,
    StreamSink,
)
from flink_tpu.streaming.partitioners import (
    BroadcastPartitioner,
    CustomPartitionerWrapper,
    ForwardPartitioner,
    GlobalPartitioner,
    KeyGroupStreamPartitioner,
    RebalancePartitioner,
    RescalePartitioner,
    ShufflePartitioner,
    StreamPartitioner,
)
from flink_tpu.streaming.sources import (
    CollectSink,
    FileTextSource,
    FromCollectionSource,
    PrintSink,
    SocketTextStreamSource,
    SourceFunction,
    StreamSource,
    TimestampsAndWatermarksOperator,
    WriteAsTextSink,
)
from flink_tpu.streaming.window_operator import (
    EvictingWindowOperator,
    WindowOperator,
)
from flink_tpu.streaming.windowing import (
    GlobalWindows,
    SlidingEventTimeWindows,
    SlidingProcessingTimeWindows,
    Time,
    TumblingEventTimeWindows,
    TumblingProcessingTimeWindows,
    CountTrigger,
    PurgingTrigger,
    WindowAssigner,
)


#: what `python -m flink_tpu run -s <path> <script>` sets: the restore
#: point of every job of the process whose environment names none
DEFAULT_RESTORE_PATH: Optional[str] = None


class StreamExecutionEnvironment:
    """(ref: StreamExecutionEnvironment.java)"""

    def __init__(self, configuration: Optional[Configuration] = None):
        self.config = configuration or Configuration()
        self.graph = StreamGraph()
        self.parallelism = 1
        self.max_parallelism = 128
        self.time_characteristic = "event"  # event | processing | ingestion
        self.checkpoint_interval: Optional[int] = None
        self.checkpoint_mode = "exactly_once"
        self.checkpoint_storage: dict = {"storage": "memory", "retain": 1}
        self._job_listeners: list = []
        self.processing_time_service = None  # executor default if None
        self.state_backend: str = self.config.get_string("state.backend", "heap")
        self.restart_strategy: Optional[dict] = {"strategy": "none"}
        self.latency_tracking_interval: Optional[int] = None
        #: device mesh for sharded window aggregation (None = 1 chip)
        self.mesh = None
        self.mesh_axis = "kg"
        #: None → LocalExecutor; int n → MiniCluster with n workers
        self.num_task_managers: Optional[int] = None
        #: "host:port" of a running Dispatcher → RemoteExecutor
        self.remote_address: Optional[str] = None
        self.remote_secret: Optional[str] = None
        self.remote_tls = None
        self._last_executor = None
        self._executed = False
        #: most recent pre-flight Diagnostics (validate()/execute())
        self._last_validation = None

    # ---- factory ----------------------------------------------------
    @staticmethod
    def get_execution_environment(configuration=None) -> "StreamExecutionEnvironment":
        return StreamExecutionEnvironment(configuration)

    # ---- configuration ----------------------------------------------
    def set_parallelism(self, parallelism: int) -> "StreamExecutionEnvironment":
        self.parallelism = parallelism
        return self

    def set_max_parallelism(self, max_parallelism: int) -> "StreamExecutionEnvironment":
        self.max_parallelism = max_parallelism
        return self

    def set_stream_time_characteristic(self, tc: str) -> "StreamExecutionEnvironment":
        assert tc in ("event", "processing", "ingestion")
        self.time_characteristic = tc
        return self

    def set_state_backend(self, backend: str) -> "StreamExecutionEnvironment":
        self.state_backend = backend
        return self

    def enable_checkpointing(self, interval_ms: int,
                             mode: str = "exactly_once",
                             async_persist: bool = False,
                             timeout_ms: Optional[int] = None,
                             tolerable_failures: Optional[int] = None
                             ) -> "StreamExecutionEnvironment":
        """``async_persist=True`` materializes completed checkpoints on
        a writer thread (processing continues during the storage
        write; operators are notified only after durability — the 2PC
        ordering).  Opt-in, like the reference's incremental/async
        snapshot flags: a non-transactional sink observing replay
        after a failure sees a wider post-barrier gap.

        ``timeout_ms`` aborts a pending checkpoint that has not fully
        acked within the window, releasing its concurrency slot so the
        coordinator can re-trigger (ref: checkpointing timeout).
        ``tolerable_failures`` = N tolerates N CONSECUTIVE
        failed/aborted checkpoints before escalating to a task failure
        (ref: execution.checkpointing.tolerable-failed-checkpoints);
        None keeps the legacy behavior (aborts never escalate, a
        failed persist fails the job)."""
        self.checkpoint_interval = interval_ms
        self.checkpoint_mode = mode
        self.checkpoint_async_persist = async_persist
        self.checkpoint_timeout_ms = timeout_ms
        self.checkpoint_tolerable_failures = tolerable_failures
        return self

    _UNSET = object()

    def set_alignment_limits(self, spill_threshold=_UNSET,
                             abort_limit=_UNSET
                             ) -> "StreamExecutionEnvironment":
        """Exactly-once alignment buffering policy: elements queued
        on alignment-blocked channels past ``spill_threshold`` spill
        to disk (ref BufferSpiller.java:67; default: the channel
        capacity); an alignment that buffers more than ``abort_limit``
        elements in total ABORTS its checkpoint instead of buffering
        on (ref the alignment cap of TaskManagerOptions.java:342;
        default: unbounded)."""
        if spill_threshold is not self._UNSET:
            self.alignment_spill_threshold = spill_threshold
        if abort_limit is not self._UNSET:
            self.alignment_abort_limit = abort_limit
        return self

    def set_checkpoint_storage(self, storage: str, directory: Optional[str] = None,
                               retain: int = 1) -> "StreamExecutionEnvironment":
        """`memory` | `filesystem` (with directory) — the checkpoint-
        storage half of the state.backend switch (ref:
        MemoryStateBackend vs FsStateBackend checkpoint streams)."""
        self.checkpoint_storage = {"storage": storage, "retain": retain}
        if directory is not None:
            self.checkpoint_storage["dir"] = directory
        return self

    def set_mesh(self, mesh, axis: str = "kg") -> "StreamExecutionEnvironment":
        """Shard device window aggregation over `mesh[axis]` — the
        keyBy exchange runs as lax.all_to_all over ICI inside the
        jitted step (flink_tpu.parallel.mesh_windows), the TPU-native
        replacement for the reference's Netty key-group shuffle.

        `mesh` may be a CALLABLE returning a Mesh: the pod topology,
        where each TaskExecutor process builds a mesh over its OWN
        device subset at operator open (a Mesh holds live device
        handles and cannot ship inside the pickled job graph).  With a
        factory the operator runs at the env parallelism — the keyed
        exchange shards keys across processes over the DCN data plane,
        and each subtask's mesh shards its key range over ICI."""
        self.mesh = mesh
        self.mesh_axis = axis
        return self

    def use_mini_cluster(self, num_task_managers: int = 2
                         ) -> "StreamExecutionEnvironment":
        """Execute on the in-process multi-worker MiniCluster
        (flink_tpu.runtime.minicluster) instead of the single-loop
        LocalExecutor (ref: MiniCluster.java — multi-TM in one JVM)."""
        self.num_task_managers = num_task_managers
        return self

    def use_remote_cluster(self, jm_address: str, secret=None, tls=None
                           ) -> "StreamExecutionEnvironment":
        """Submit to a running cluster's Dispatcher at
        "host:port" (ref: RemoteStreamEnvironment /
        ClusterClient.run — flink_tpu.runtime.cluster).  The job graph
        is cloudpickled and shipped via the blob server; results come
        back through accumulators.  `secret` authenticates against a
        --secret cluster; `tls` (a runtime.tls.TlsConfig) speaks
        mutual TLS to a --tls-dir cluster."""
        self.remote_address = jm_address
        self.remote_secret = secret
        self.remote_tls = tls
        return self

    def set_restart_strategy(self, strategy: str, **kw) -> "StreamExecutionEnvironment":
        """fixed_delay(restart_attempts, delay_ms) | failure_rate | none
        (ref: RestartStrategies)"""
        self.restart_strategy = {"strategy": strategy, **kw}
        return self

    def set_failover_strategy(self, strategy: str
                              ) -> "StreamExecutionEnvironment":
        """"full" (default) | "region" — scope of a restart on task
        failure (ref: jobmanager.execution.failover-strategy,
        RestartPipelinedRegionStrategy).  "region" restarts only the
        failed task's pipelined region on the local executor; healthy
        regions carry their live state across the restart."""
        assert strategy in ("full", "region")
        self.failover_strategy = strategy
        return self

    def register_job_listener(self, on_job_submitted
                              ) -> "StreamExecutionEnvironment":
        """`on_job_submitted(client)` is called with the `JobClient` of
        every job this environment submits, before `execute()` waits
        for it (ref: StreamExecutionEnvironment.registerJobListener /
        JobListener.onJobSubmitted): the handle on a job run through
        the blocking `execute()` (savepoints, cancel, its live
        state)."""
        self._job_listeners.append(on_job_submitted)
        return self

    def set_savepoint_restore(self, path: str,
                              allow_non_restored_state: bool = False
                              ) -> "StreamExecutionEnvironment":
        """Start the next execution from a savepoint — the
        `flink run -s <path>` contract.  `path` is a savepoint file,
        or a retained checkpoint of the filesystem checkpoint storage:
        its directory (the newest `chk-N` in it that loads) or one
        `chk-N` file, the chunks it shares with other checkpoints read
        from the directory's `shared/`.  Restoring at a different
        parallelism re-splits keyed state by key-group range and
        operator list state round-robin (ref: SavepointRestoreSettings
        + StateAssignmentOperation).  Snapshot state whose operator
        uids match nothing in the new topology FAILS the restore
        unless allow_non_restored_state (the reference's
        --allowNonRestoredState)."""
        self.savepoint_restore_path = path
        self.allow_non_restored_state = allow_non_restored_state
        return self

    # ---- sources ----------------------------------------------------
    def add_source(self, source_function: SourceFunction,
                   name: str = "source", parallelism: int = 1) -> "DataStream":
        node = self.graph.add_node(StreamNode(
            self.graph.new_node_id(), name,
            _source_factory(source_function, self.time_characteristic),
            parallelism=parallelism,
            max_parallelism=self.max_parallelism,
            is_source=True,
            time_characteristic=self.time_characteristic,
        ))
        return DataStream(self, node)

    def from_collection(self, items: Iterable[Any], timestamped: bool = False) -> "DataStream":
        return self.add_source(
            FromCollectionSource(list(items), timestamped=timestamped),
            name="from_collection")

    def from_elements(self, *items) -> "DataStream":
        return self.from_collection(list(items))

    def socket_text_stream(self, hostname: str, port: int,
                           delimiter: str = "\n", max_retries: int = 0) -> "DataStream":
        return self.add_source(
            SocketTextStreamSource(hostname, port, delimiter, max_retries),
            name="socket_source")

    def read_text_file(self, path: str) -> "DataStream":
        return self.add_source(FileTextSource(path), name="file_source")

    # ---- execution --------------------------------------------------
    def get_stream_graph(self) -> StreamGraph:
        return self.graph

    def get_job_graph(self):
        jg = create_job_graph(self.graph)
        if self.checkpoint_interval is not None:
            jg.checkpoint_config = {
                "interval": self.checkpoint_interval,
                "mode": self.checkpoint_mode,
                "async_persist": getattr(self, "checkpoint_async_persist",
                                         False),
                **self.checkpoint_storage,
            }
            if getattr(self, "checkpoint_timeout_ms", None) is not None:
                jg.checkpoint_config["timeout"] = self.checkpoint_timeout_ms
            if getattr(self, "checkpoint_tolerable_failures",
                       None) is not None:
                jg.checkpoint_config["tolerable_failures"] = \
                    self.checkpoint_tolerable_failures
            if hasattr(self, "alignment_spill_threshold"):
                jg.checkpoint_config["alignment_spill_threshold"] = \
                    self.alignment_spill_threshold
            if hasattr(self, "alignment_abort_limit"):
                jg.checkpoint_config["alignment_abort_limit"] = \
                    self.alignment_abort_limit
        jg.savepoint_restore_path = getattr(
            self, "savepoint_restore_path", None) or DEFAULT_RESTORE_PATH
        jg.allow_non_restored_state = getattr(
            self, "allow_non_restored_state", False)
        return jg

    def set_latency_tracking_interval(self, interval_ms: Optional[int]
                                      ) -> "StreamExecutionEnvironment":
        """Periodic LatencyMarker emission from sources (ref:
        ExecutionConfig.setLatencyTrackingInterval / the
        metrics.latency.interval config)."""
        self.latency_tracking_interval = interval_ms
        return self

    def get_metric_registry(self):
        """The registry of the last/most recent executor (populated
        after execute()/execute_async())."""
        return self._last_executor.metrics if self._last_executor else None

    def enable_tracing(self, enabled: bool = True
                       ) -> "StreamExecutionEnvironment":
        """Turn the process-global tracer on (or off): spans for
        operator processing, device flush/fire, native kernel
        dispatches, and checkpoint barriers land in the Chrome
        trace-event buffer (runtime.tracing).  Export after the job
        with ``env.get_tracer().write_chrome_trace(path)``."""
        from flink_tpu.runtime.tracing import get_tracer
        get_tracer().enabled = enabled
        return self

    def get_tracer(self):
        """The process-global :class:`~flink_tpu.runtime.tracing.Tracer`."""
        from flink_tpu.runtime.tracing import get_tracer
        return get_tracer()

    def _make_executor(self):
        from flink_tpu.core.config import HistoryServerOptions, MetricOptions
        kw = dict(
            # the whole configuration under the backend this job chose:
            # the executors hand it to load_state_backend, which reads
            # the backend's tuning keys (state.backend.tpu.*) off it
            state_backend=self.config.clone().set("state.backend",
                                                  self.state_backend),
            max_parallelism=self.max_parallelism,
            restart_strategy=self.restart_strategy,
            processing_time_service=self.processing_time_service,
            latency_interval_ms=getattr(self, "latency_tracking_interval",
                                        None),
            sample_interval_ms=self.config.get_integer(
                MetricOptions.SAMPLE_INTERVAL_MS),
            metrics_history_size=self.config.get_integer(
                MetricOptions.HISTORY_SIZE),
            archive_dir=self.config.get_string(
                HistoryServerOptions.ARCHIVE_DIR),
        )
        if self.remote_address is not None:
            from flink_tpu.runtime.cluster import RemoteExecutor
            kw.pop("processing_time_service", None)
            # cluster mode archives Dispatcher-side (its archive dir is
            # a JobManagerProcess setting, not a per-job one)
            kw.pop("archive_dir", None)
            self._last_executor = RemoteExecutor(
                self.remote_address, secret=self.remote_secret,
                tls=self.remote_tls, **kw)
        elif self.num_task_managers is not None:
            from flink_tpu.runtime.minicluster import MiniCluster
            self._last_executor = MiniCluster(
                num_task_managers=self.num_task_managers, **kw)
        else:
            from flink_tpu.runtime.local import LocalExecutor
            # region failover is a LocalExecutor capability; the
            # distributed tiers restart the full job (the reference's
            # "full" strategy)
            kw["failover_strategy"] = getattr(self, "failover_strategy",
                                              "full")
            self._last_executor = LocalExecutor(**kw)
        return self._last_executor

    # ---- pre-flight validation --------------------------------------
    def validate(self, strict: bool = False, types: bool = False):
        """Run the pre-flight static analysis (graph linter + UDF
        liftability) over the current topology WITHOUT executing it.

        Returns a :class:`flink_tpu.analysis.Diagnostics` report; with
        ``strict=True`` raises
        :class:`flink_tpu.analysis.JobValidationError` when the report
        contains any ERROR diagnostic.  With ``types=True`` the column
        type-flow prover (pass 3) also runs: FT185–FT188 findings land
        in the report and the per-edge schema dump is attached as
        ``report.typeflow``.  See docs/static_analysis.md for the code
        catalog.
        """
        from flink_tpu.analysis import JobValidationError, lint_graph
        report = lint_graph(self.graph, config=self.config, env=self,
                            types=types)
        self._last_validation = report
        if strict and report.has_errors():
            raise JobValidationError(report)
        return report

    def _preflight(self, job_name: str):
        """execute()-time lint gate, controlled by the ``lint.mode``
        config key: ``off`` skips it, ``warn`` (default) logs errors
        and warnings, ``strict`` raises on any ERROR diagnostic.

        ``lint.types.mode`` (default ``off``) arms the column
        type-flow prover the same way: ``warn`` runs it, logs its
        FT185–FT188 findings, and feeds conclusive verdicts into the
        runtime (probe-free map/filter kernels, per-edge codec hints,
        device-state pre-sizing); ``strict`` additionally raises when
        any FT185–FT188 finding fires."""
        from flink_tpu.core.config import LintOptions, lint_mode_of
        mode = lint_mode_of(self.config, LintOptions.MODE)
        tmode = lint_mode_of(self.config, LintOptions.TYPES_MODE)
        if mode == "off" and tmode == "off":
            return None
        self.graph.job_name = job_name
        report = self.validate(strict=(mode == "strict"),
                               types=(tmode != "off"))
        typeflow = getattr(report, "typeflow", None)
        self._last_typeflow = typeflow
        if typeflow is not None:
            from flink_tpu.analysis.typeflow import apply_static
            apply_static(self.graph, typeflow)
            if tmode == "strict":
                findings = [d for d in report
                            if d.code in ("FT185", "FT186", "FT187",
                                          "FT188")]
                if findings:
                    from flink_tpu.analysis import JobValidationError
                    raise JobValidationError(report)
        if len(report):
            report.log()
        return report

    def _publish_lint_metrics(self, report):
        if report is None or self._last_executor is None:
            return
        registry = getattr(self._last_executor, "metrics", None)
        if registry is None:
            return
        try:
            from flink_tpu.runtime.metrics import register_lint_gauges
            register_lint_gauges(registry, self.graph.job_name, report)
        except Exception:
            pass  # metrics are best-effort; never block submission
        typeflow = getattr(report, "typeflow", None)
        if typeflow is None:
            return
        try:
            from flink_tpu.runtime.metrics import (
                register_typeflow_gauges,
            )
            register_typeflow_gauges(registry, self.graph.job_name,
                                     typeflow)
        except Exception:
            pass

    def execute(self, job_name: str = "job"):
        """(ref: execute :1508) — runs on the local executor."""
        report = self._preflight(job_name)
        self.graph.job_name = job_name
        executor = self._make_executor()
        self._publish_lint_metrics(report)
        if self._job_listeners:
            executor.job_listeners = list(self._job_listeners)
        return executor.execute(self.get_job_graph())

    def execute_async(self, job_name: str = "job"):
        """Submit and return a JobClient with cancel()/wait() — the
        detached-submission shape of ClusterClient.run()."""
        report = self._preflight(job_name)
        self.graph.job_name = job_name
        executor = self._make_executor()
        self._publish_lint_metrics(report)
        client = executor.execute_async(self.get_job_graph())
        for listener in self._job_listeners:
            listener(client)
        return client


def _source_factory(source_function: SourceFunction, time_characteristic: str):
    import copy

    def factory():
        return StreamSource(copy.deepcopy(source_function), time_characteristic)
    return factory


def _op_factory(cls, fn_factory):
    def factory():
        return cls(fn_factory())
    return factory


class DataStream:
    """(ref: DataStream.java)"""

    def __init__(self, env: StreamExecutionEnvironment, node: StreamNode,
                 partitioner: Optional[StreamPartitioner] = None,
                 side_tag=None):
        self.env = env
        self.node = node
        #: pending partitioner for the NEXT edge out of this stream
        self._partitioner = partitioner
        #: set → edges out of this stream carry this side-output tag
        self._side_tag = side_tag

    # ---- plumbing ---------------------------------------------------
    def _edge_partitioner(self, target_parallelism: int) -> StreamPartitioner:
        if self._partitioner is not None:
            return self._partitioner
        if self.node.parallelism == target_parallelism:
            return ForwardPartitioner()
        return RebalancePartitioner()

    def _add_op(self, name: str, operator_factory, parallelism=None,
                key_selector=None, type_number: int = 0,
                extra_inputs: Optional[List["DataStream"]] = None,
                chaining: str = "always") -> "DataStream":
        # default = the ENVIRONMENT parallelism (ref: every
        # StreamTransformation is created with env.getParallelism and
        # overridden per-operator via setParallelism), not the upstream
        # node's — matching StreamExecutionEnvironment.setParallelism
        p = parallelism if parallelism is not None else self.env.parallelism
        node = self.env.graph.add_node(StreamNode(
            self.env.graph.new_node_id(), name, operator_factory,
            parallelism=p,
            max_parallelism=self.env.max_parallelism,
            key_selector=key_selector,
            chaining_strategy=chaining,
            time_characteristic=self.env.time_characteristic,
        ))
        self.env.graph.add_edge(StreamEdge(
            self.node.id, node.id, self._edge_partitioner(p), type_number,
            side_output_tag=self._side_tag))
        for i, s in enumerate(extra_inputs or [], start=1):
            self.env.graph.add_edge(StreamEdge(
                s.node.id, node.id, s._edge_partitioner(p), i,
                side_output_tag=s._side_tag))
        return DataStream(self.env, node)

    # ---- basic transforms -------------------------------------------
    def map(self, fn, name: str = "map") -> "DataStream":
        f = as_map_function(fn)
        return self._add_op(name, _op_factory(StreamMap, lambda: f))

    def flat_map(self, fn, name: str = "flat_map") -> "DataStream":
        f = as_flat_map_function(fn)
        return self._add_op(name, _op_factory(StreamFlatMap, lambda: f))

    def filter(self, fn, name: str = "filter") -> "DataStream":
        f = as_filter_function(fn)
        return self._add_op(name, _op_factory(StreamFilter, lambda: f))

    def process(self, process_function, name: str = "process") -> "DataStream":
        return self._add_op(name, _op_factory(ProcessOperator, lambda: process_function))

    def set_parallelism(self, parallelism: int) -> "DataStream":
        self.node.parallelism = parallelism
        return self

    def name(self, name: str) -> "DataStream":
        self.node.name = name
        return self

    def uid(self, uid: str) -> "DataStream":
        self.node.uid = uid
        return self

    def disable_chaining(self) -> "DataStream":
        self.node.chaining_strategy = "never"
        return self

    def start_new_chain(self) -> "DataStream":
        self.node.chaining_strategy = "head"
        return self

    # ---- partitioning (ref: DataStream.java :395-410 etc.) ----------
    def key_by(self, key_selector) -> "KeyedStream":
        ks = as_key_selector(key_selector)
        return KeyedStream(self.env, self.node, ks)

    def rebalance(self) -> "DataStream":
        return DataStream(self.env, self.node, RebalancePartitioner())

    def rescale(self) -> "DataStream":
        return DataStream(self.env, self.node, RescalePartitioner())

    def shuffle(self) -> "DataStream":
        return DataStream(self.env, self.node, ShufflePartitioner())

    def broadcast(self, *broadcast_state_descriptors) -> "DataStream":
        """Without arguments: broadcast-partitioned stream (every
        record to every downstream subtask).  With MapStateDescriptors:
        a BroadcastStream for the broadcast state pattern
        (ref: DataStream.broadcast :395-410)."""
        bs = DataStream(self.env, self.node, BroadcastPartitioner())
        if broadcast_state_descriptors:
            return BroadcastStream(bs, broadcast_state_descriptors)
        return bs

    def global_(self) -> "DataStream":
        return DataStream(self.env, self.node, GlobalPartitioner())

    def forward(self) -> "DataStream":
        return DataStream(self.env, self.node, ForwardPartitioner())

    def get_side_output(self, tag) -> "DataStream":
        """Consume a side output of this operator
        (ref: SingleOutputStreamOperator#getSideOutput)."""
        return DataStream(self.env, self.node, side_tag=tag)

    def partition_custom(self, partitioner, key_selector=None) -> "DataStream":
        ks = as_key_selector(key_selector) if key_selector is not None else None
        return DataStream(self.env, self.node,
                          CustomPartitionerWrapper(partitioner, ks))

    # ---- union / connect (ref: union :212, connect :252) ------------
    def union(self, *streams: "DataStream") -> "DataStream":
        """Merge same-type streams: a pass-through node with N inputs."""
        f = as_map_function(lambda x: x)
        node = self.env.graph.add_node(StreamNode(
            self.env.graph.new_node_id(), "union",
            _op_factory(StreamMap, lambda: f),
            parallelism=self.node.parallelism,
            max_parallelism=self.env.max_parallelism,
            chaining_strategy="never",
        ))
        for s in (self,) + streams:
            self.env.graph.add_edge(StreamEdge(
                s.node.id, node.id, s._edge_partitioner(node.parallelism), 0))
        return DataStream(self.env, node)

    def split(self, output_selector) -> "SplitStream":
        """(ref: DataStream.split :238 — deprecated there in favor of
        side outputs, kept for API parity).  `output_selector(value)`
        returns an iterable of route names."""
        return SplitStream(self.env, self.node, output_selector,
                           partitioner=self._partitioner,
                           side_tag=self._side_tag)

    def join(self, other: "DataStream"):
        """(ref: DataStream.join :709) —
        .where(k1).equal_to(k2).window(w).apply(fn)."""
        from flink_tpu.streaming.joining import JoinedStreams
        return JoinedStreams(self, other)

    def interval_join(self, other: "DataStream"):
        """Time-bounded stream-stream join:
        a.interval_join(b).where(k1).equal_to(k2)
         .between(lower_ms, upper_ms).apply(fn) — pairs with
        b.ts - a.ts in [lower, upper] and equal keys (the reference's
        windowed table join bounds, WindowJoinUtil.scala)."""
        from flink_tpu.streaming.joining import IntervalJoinedStreams
        return IntervalJoinedStreams(self, other)

    def co_group(self, other: "DataStream"):
        """(ref: DataStream.coGroup :701)."""
        from flink_tpu.streaming.joining import CoGroupedStreams
        return CoGroupedStreams(self, other)

    def iterate(self) -> "IterativeStream":
        """(ref: DataStream.iterate :514) — returns the iteration head;
        call close_with(feedback) to wire the loop.  Records on the
        feedback edge bypass EOS/barrier propagation (iterations are
        outside the exactly-once guarantee, as in the reference)."""
        head = self._add_op("iteration_head",
                            _op_factory(StreamMap,
                                        lambda: as_map_function(lambda v: v)),
                            chaining="never")
        return IterativeStream(self.env, head.node)

    def connect(self, other) -> "ConnectedStreams":
        if isinstance(other, BroadcastStream):
            return BroadcastConnectedStream(self.env, self, other)
        return ConnectedStreams(self.env, self, other)

    # ---- windows over non-keyed streams -----------------------------
    def window_all(self, assigner: WindowAssigner) -> "AllWindowedStream":
        return AllWindowedStream(self.key_by(lambda x: 0), assigner)

    def count_window_all(self, size: int) -> "AllWindowedStream":
        ws = AllWindowedStream(self.key_by(lambda x: 0), GlobalWindows.create())
        ws._trigger = PurgingTrigger.of(CountTrigger(size))
        return ws

    # ---- timestamps -------------------------------------------------
    def assign_timestamps_and_watermarks(self, assigner,
                                         watermark_interval: int = 1) -> "DataStream":
        return self._add_op(
            "timestamps",
            lambda: TimestampsAndWatermarksOperator(assigner, watermark_interval))

    # ---- sinks ------------------------------------------------------
    def add_sink(self, sink_function, name: str = "sink") -> "DataStreamSink":
        # Table.to_retract_stream marks its result; retract-aware
        # sinks opt into pair decoding here instead of sniffing
        # (bool, x)-shaped values on every stream
        if getattr(self, "carries_retract_pairs", False) and \
                hasattr(sink_function, "enable_retract_decoding"):
            sink_function.enable_retract_decoding()
        node = self._add_op(name, _op_factory(StreamSink, lambda: sink_function))
        return DataStreamSink(node)

    def print_(self, prefix: str = "") -> "DataStreamSink":
        return self.add_sink(PrintSink(prefix), name="print")

    def write_as_text(self, path: str) -> "DataStreamSink":
        return self.add_sink(WriteAsTextSink(path), name="write_text")

    def collect_into(self, target: list) -> "DataStreamSink":
        """Convenience: sink into a Python list (test/driver use)."""
        return self.add_sink(CollectSink(target), name="collect")


class DataStreamSink:
    def __init__(self, stream: DataStream):
        self._stream = stream
        self.node = stream.node

    def set_parallelism(self, parallelism: int) -> "DataStreamSink":
        self.node.parallelism = parallelism
        return self

    def name(self, name: str) -> "DataStreamSink":
        self.node.name = name
        return self


class KeyedStream(DataStream):
    """(ref: KeyedStream.java)"""

    def __init__(self, env, node, key_selector):
        super().__init__(env, node,
                         KeyGroupStreamPartitioner(key_selector, env.max_parallelism))
        self.key_selector = key_selector

    def _add_keyed_op(self, name: str, operator_factory, chaining="always") -> DataStream:
        ks = self.key_selector
        return self._add_op(name, operator_factory, key_selector=ks,
                            chaining=chaining)

    # ---- keyed transforms -------------------------------------------
    def process(self, process_function, name: str = "keyed_process") -> DataStream:
        return self._add_keyed_op(
            name, _op_factory(KeyedProcessOperator, lambda: process_function))

    def reduce(self, fn, name: str = "reduce") -> DataStream:
        f = as_reduce_function(fn)
        return self._add_keyed_op(name, _op_factory(StreamGroupedReduce, lambda: f))

    def sum(self, field=None) -> DataStream:
        return self.reduce(_field_reduce(field, lambda a, b: a + b), name="sum")

    def min(self, field=None) -> DataStream:
        return self.reduce(_field_reduce(field, min), name="min")

    def max(self, field=None) -> DataStream:
        return self.reduce(_field_reduce(field, max), name="max")

    def min_by(self, field) -> DataStream:
        getter = _field_getter(field)
        return self.reduce(lambda a, b: a if getter(a) <= getter(b) else b, name="min_by")

    def max_by(self, field) -> DataStream:
        getter = _field_getter(field)
        return self.reduce(lambda a, b: a if getter(a) >= getter(b) else b, name="max_by")

    # ---- windows (ref: KeyedStream.timeWindow :352-370) -------------
    def window(self, assigner: WindowAssigner) -> "WindowedStream":
        return WindowedStream(self, assigner)

    def time_window(self, size: Time, slide: Optional[Time] = None) -> "WindowedStream":
        if self.env.time_characteristic == "processing":
            assigner = (TumblingProcessingTimeWindows.of(size) if slide is None
                        else SlidingProcessingTimeWindows.of(size, slide))
        else:
            assigner = (TumblingEventTimeWindows.of(size) if slide is None
                        else SlidingEventTimeWindows.of(size, slide))
        return WindowedStream(self, assigner)

    def count_window(self, size: int, slide: Optional[int] = None) -> "WindowedStream":
        ws = WindowedStream(self, GlobalWindows.create())
        if slide is None:
            ws._trigger = PurgingTrigger.of(CountTrigger(size))
        else:
            from flink_tpu.streaming.windowing import CountEvictor
            ws._trigger = CountTrigger(slide)
            ws._evictor = CountEvictor.of(size)
        return ws

    def connect(self, other) -> "ConnectedStreams":
        if isinstance(other, BroadcastStream):
            return BroadcastConnectedStream(self.env, self, other)
        return ConnectedStreams(self.env, self, other)

    def as_queryable_state(self, name: str, descriptor=None):
        """(ref: KeyedStream.asQueryableState :745-788) — registers the
        stream's latest value per key as externally queryable; read it
        with flink_tpu.runtime.queryable.QueryableStateClient
        .get_kv_state(name, key) while the job runs (dirty reads, the
        reference's contract)."""
        from flink_tpu.core.state import ValueStateDescriptor
        from flink_tpu.runtime.queryable import DEFAULT_REGISTRY

        desc = descriptor or ValueStateDescriptor(name)
        desc.set_queryable(name)

        class _QueryableOp(KeyedProcessOperator):
            def open(self):
                super().open()
                self._qstate = self.keyed_backend.get_or_create_keyed_state(desc)
                # the AbstractKeyedStateBackend.java:382-389 hook
                DEFAULT_REGISTRY.register(
                    name, self.keyed_backend.key_group_range,
                    self.keyed_backend, desc)

            def process_element(self, record):
                from flink_tpu.state.backend import VOID_NAMESPACE
                self._qstate.set_current_namespace(VOID_NAMESPACE)
                # ValueState-backed (default): last value wins;
                # aggregating/reducing descriptors accumulate instead
                # (the reference registers any InternalKvState kind)
                if hasattr(self._qstate, "update"):
                    self._qstate.update(record.value)
                else:
                    self._qstate.add(record.value)

            def close(self):
                # device states micro-batch their adds; make the final
                # values visible to queries once the task stops
                flush_all = getattr(self.keyed_backend, "flush_all",
                                    None)
                if flush_all is not None:
                    flush_all()
                super().close()

        class _Noop:
            def process_element(self, value, ctx, out):
                pass

        return self._add_keyed_op(f"queryable-{name}",
                                  lambda: _QueryableOp(_Noop()))


def _field_getter(field):
    if field is None:
        return lambda x: x
    if callable(field):
        return field
    return lambda x: x[field] if isinstance(x, (tuple, list)) else getattr(x, field)


def _field_reduce(field, combine):
    if field is None:
        return lambda a, b: combine(a, b)

    def reducer(a, b):
        if isinstance(a, tuple):
            lst = list(a)
            lst[field] = combine(a[field], b[field])
            return tuple(lst)
        if isinstance(a, list):
            lst = list(a)
            lst[field] = combine(a[field], b[field])
            return lst
        setattr(a, field, combine(getattr(a, field), getattr(b, field)))
        return a

    return reducer


class WindowedStream:
    """(ref: WindowedStream.java :305-850)"""

    def __init__(self, keyed: KeyedStream, assigner: WindowAssigner):
        self._keyed = keyed
        self._assigner = assigner
        self._trigger = None
        self._evictor = None
        self._allowed_lateness = 0
        self._late_tag = None
        self._device_enabled = True

    def disable_device_operator(self) -> "WindowedStream":
        """Force the scalar WindowOperator even for device-eligible
        aggregates (debugging / semantics comparison)."""
        self._device_enabled = False
        return self

    def trigger(self, trigger) -> "WindowedStream":
        self._trigger = trigger
        return self

    def evictor(self, evictor) -> "WindowedStream":
        self._evictor = evictor
        return self

    def allowed_lateness(self, lateness: Union[Time, int]) -> "WindowedStream":
        self._allowed_lateness = (lateness.milliseconds
                                  if isinstance(lateness, Time) else int(lateness))
        return self

    def side_output_late_data(self, tag) -> "WindowedStream":
        self._late_tag = tag
        return self

    def _build(self, name, state_descriptor, window_function,
               single_value=None) -> DataStream:
        assigner = self._assigner
        trigger = self._trigger
        evictor = self._evictor
        lateness = self._allowed_lateness
        late_tag = self._late_tag

        if evictor is not None:
            pre = _pre_aggregator_for(state_descriptor) if single_value else None

            def factory():
                return EvictingWindowOperator(
                    assigner, window_function, trigger, evictor,
                    lateness, late_tag, pre_aggregator=pre)
        else:
            def factory():
                return WindowOperator(
                    assigner, state_descriptor, window_function, trigger,
                    lateness, late_tag, single_value_contents=single_value)
        return self._keyed._add_keyed_op(name, factory, chaining="head")

    # ---- terminal ops -----------------------------------------------
    def aggregate(self, aggregate_function: AggregateFunction,
                  window_function=None, name: str = "window_aggregate") -> DataStream:
        """(ref: WindowedStream.aggregate :687-716).  Windows the
        batched engines cover (window_engines.batched_operator_kind:
        event-time tumbling/sliding/session, default trigger, no
        evictor, lateness 0) run on DeviceWindowOperator where the
        aggregate is a DeviceAggregateFunction and on
        GenericWindowOperator where it is any other; the rest stay on
        the scalar WindowOperator."""
        from flink_tpu.streaming.window_engines import (
            batched_operator_kind,
            is_mesh_factory,
        )
        kind = None
        if (self._device_enabled
                and self._keyed.env.time_characteristic == "event"):
            kind = batched_operator_kind(
                self._assigner, aggregate_function, self._trigger,
                self._evictor, self._allowed_lateness, self._late_tag,
                window_function)
        assigner = self._assigner
        if kind == "device":
            from flink_tpu.streaming.device_window_operator import (
                DeviceWindowOperator,
            )
            env = self._keyed.env
            mesh, mesh_axis = env.mesh, env.mesh_axis
            from flink_tpu.streaming.windowing import (
                TumblingEventTimeWindows as _Tumbling,
            )
            if mesh is not None and not isinstance(assigner, _Tumbling):
                # sliding and session windows have sharded engines too
                # (window_engines), but no DataStream job has been given
                # a mesh for them yet: ROADMAP Design 1
                mesh = None

            def factory():
                return DeviceWindowOperator(assigner, aggregate_function,
                                            window_function,
                                            mesh=mesh, mesh_axis=mesh_axis)
            if mesh is not None and not is_mesh_factory(mesh):
                # the mesh IS the parallelism: one host subtask drives
                # the SPMD program over all devices; upstream edges
                # still hash-route (to the single subtask) so the
                # operator sees the keyed contract
                return self._keyed._add_op(
                    name, factory, parallelism=1,
                    key_selector=self._keyed.key_selector, chaining="head")
            # a mesh FACTORY runs per subtask (pod topology: the keyed
            # exchange spans processes, each subtask's own mesh spans
            # its local devices)
            return self._keyed._add_keyed_op(name, factory, chaining="head")
        if kind == "generic":
            # arbitrary Python aggregates ride the generic vectorized
            # log tier (sort + diagonal-round fold of the user's add
            # over numpy columns) instead of the per-record scalar
            # WindowOperator
            from flink_tpu.streaming.generic_agg import (
                GenericWindowOperator,
            )

            def gfactory():
                return GenericWindowOperator(assigner,
                                             aggregate_function,
                                             window_function)
            return self._keyed._add_keyed_op(name, gfactory,
                                             chaining="head")
        return self._build(
            name,
            AggregatingStateDescriptor("window-contents", aggregate_function),
            window_function,
            single_value=True)

    def reduce(self, fn, window_function=None, name: str = "window_reduce") -> DataStream:
        f = as_reduce_function(fn)
        return self._build(
            name,
            ReducingStateDescriptor("window-contents", f),
            window_function,
            single_value=True)

    def fold(self, initial_value, fold_function, window_function=None) -> DataStream:
        return self._build(
            "window_fold",
            FoldingStateDescriptor("window-contents", initial_value, fold_function),
            window_function,
            single_value=True)

    def apply(self, window_function, name: str = "window_apply") -> DataStream:
        return self._build(
            name, ListStateDescriptor("window-contents"), window_function,
            single_value=False)

    def process(self, process_window_function, name: str = "window_process") -> DataStream:
        return self._build(
            name, ListStateDescriptor("window-contents"),
            process_window_function, single_value=False)

    def sum(self, field=None) -> DataStream:
        return self.reduce(_field_reduce(field, lambda a, b: a + b), name="window_sum")

    def min(self, field=None) -> DataStream:
        return self.reduce(_field_reduce(field, min), name="window_min")

    def max(self, field=None) -> DataStream:
        return self.reduce(_field_reduce(field, max), name="window_max")


def _pre_aggregator_for(state_descriptor):
    """Fire-time aggregation over raw elements for the evictor path
    (ref: the Reduce/Aggregate/FoldApplyWindowFunction wrappers the
    reference's WindowedStream builds when an evictor is set)."""
    if isinstance(state_descriptor, ReducingStateDescriptor):
        reduce = state_descriptor.reduce_function.reduce

        def pre(values):
            it = iter(values)
            acc = next(it)
            for v in it:
                acc = reduce(acc, v)
            return acc
        return pre
    if isinstance(state_descriptor, AggregatingStateDescriptor):
        agg = state_descriptor.aggregate_function

        def pre(values):
            acc = agg.create_accumulator()
            for v in values:
                acc = agg.add(v, acc)
            return agg.get_result(acc)
        return pre
    if isinstance(state_descriptor, FoldingStateDescriptor):
        fold = state_descriptor.fold_function

        def pre(values):
            acc = state_descriptor.get_default_value()
            for v in values:
                acc = fold(acc, v)
            return acc
        return pre
    return None


class AllWindowedStream(WindowedStream):
    """Non-keyed windows — parallelism forced to 1
    (ref: AllWindowedStream.java)."""

    def _build(self, name, state_descriptor, window_function, single_value=None):
        stream = super()._build(name, state_descriptor, window_function, single_value)
        stream.node.parallelism = 1
        return stream


class ConnectedStreams:
    """(ref: ConnectedStreams.java)"""

    def __init__(self, env, first: DataStream, second: DataStream):
        self.env = env
        self.first = first
        self.second = second

    def _add_two_input(self, name, factory) -> DataStream:
        ks1 = getattr(self.first, "key_selector", None)

        def wrapped_factory():
            op = factory()
            if hasattr(op, "key_selector2"):
                op.key_selector2 = getattr(self.second, "key_selector", None)
            return op

        return self.first._add_op(
            name, wrapped_factory,
            key_selector=ks1,
            extra_inputs=[self.second],
            chaining="never")

    def map(self, co_map_function) -> DataStream:
        return self._add_two_input("co_map", lambda: CoStreamMap(co_map_function))

    def flat_map(self, co_flat_map_function) -> DataStream:
        return self._add_two_input("co_flat_map",
                                   lambda: CoStreamFlatMap(co_flat_map_function))

    def process(self, co_process_function) -> DataStream:
        return self._add_two_input("co_process",
                                   lambda: CoProcessOperator(co_process_function))

    def key_by(self, key_selector1, key_selector2) -> "ConnectedStreams":
        return ConnectedStreams(
            self.env,
            self.first.key_by(key_selector1),
            self.second.key_by(key_selector2))


class SplitStream(DataStream):
    """(ref: SplitStream.java) — route names from the output selector;
    select(names) keeps records routed to any of them."""

    def __init__(self, env, node, output_selector, partitioner=None,
                 side_tag=None):
        super().__init__(env, node, partitioner, side_tag)
        self._selector = output_selector

    def select(self, *names: str) -> DataStream:
        wanted = set(names)
        selector = self._selector

        def keep(value):
            routes = selector(value)
            return any(r in wanted for r in (routes or ()))

        return self.filter(keep, name=f"select[{','.join(names)}]")


class IterativeStream(DataStream):
    """(ref: IterativeStream.java) — the iteration head; downstream
    transforms consume it like any stream, and close_with(feedback)
    adds the back edge."""

    def close_with(self, feedback: DataStream) -> DataStream:
        partitioner = (ForwardPartitioner()
                       if feedback.node.parallelism == self.node.parallelism
                       else RebalancePartitioner())
        edge = StreamEdge(feedback.node.id, self.node.id, partitioner,
                          type_number=0)
        edge.is_feedback = True
        self.env.graph.add_edge(edge)
        return feedback


class BroadcastStream:
    """A broadcast-partitioned stream plus the broadcast state
    descriptors its elements update (ref: BroadcastStream.java)."""

    def __init__(self, stream: DataStream, descriptors):
        self.stream = stream
        self.descriptors = tuple(descriptors)


class BroadcastConnectedStream:
    """(ref: BroadcastConnectedStream.java) — process with a
    (Keyed)BroadcastProcessFunction; input 1 is the data side, input 2
    the broadcast side updating broadcast state on every instance."""

    def __init__(self, env, data_stream: DataStream,
                 broadcast: BroadcastStream):
        self.env = env
        self.data = data_stream
        self.broadcast = broadcast

    def process(self, fn, name: str = "broadcast_process") -> DataStream:
        from flink_tpu.streaming.operators import CoBroadcastOperator
        ks = getattr(self.data, "key_selector", None)
        return self.data._add_op(
            name, lambda: CoBroadcastOperator(fn),
            key_selector=ks,
            extra_inputs=[self.broadcast.stream],  # broadcast-partitioned
            chaining="never")


class AsyncDataStream:
    """(ref: AsyncDataStream.java — orderedWait/unorderedWait)."""

    @staticmethod
    def ordered_wait(stream: DataStream, async_function,
                     timeout_ms: Optional[int] = None,
                     capacity: int = 100) -> DataStream:
        return AsyncDataStream._wait(stream, async_function, timeout_ms,
                                     capacity, ordered=True)

    @staticmethod
    def unordered_wait(stream: DataStream, async_function,
                       timeout_ms: Optional[int] = None,
                       capacity: int = 100) -> DataStream:
        return AsyncDataStream._wait(stream, async_function, timeout_ms,
                                     capacity, ordered=False)

    @staticmethod
    def _wait(stream, fn, timeout_ms, capacity, ordered):
        from flink_tpu.streaming.operators import AsyncWaitOperator
        mode = "ordered" if ordered else "unordered"
        return stream._add_op(
            f"async_wait_{mode}",
            lambda: AsyncWaitOperator(fn, capacity=capacity,
                                      timeout_ms=timeout_ms,
                                      ordered=ordered),
            chaining="head")

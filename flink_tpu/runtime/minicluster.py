"""MiniCluster: in-process multi-worker job execution.

The rebuild of the reference's MiniCluster
(flink-runtime/.../minicluster/MiniCluster.java — several TaskManagers,
one Dispatcher/JobMaster, real scheduling and checkpointing inside one
JVM; the spine of every ITCase, SURVEY.md §4.4).  Here:

- N TaskManager worker THREADS each own a disjoint set of subtasks
  (slot assignment = round-robin over vertices' subtask indexes, the
  slot-sharing analogue: one subtask of each vertex lands on each TM).
  All element processing, timer firing, barrier alignment, and
  snapshots of a subtask happen on its owner thread — the same
  single-owner discipline as LocalExecutor, now with true cross-worker
  channel traffic (deque append/popleft are atomic; each end is touched
  by exactly one loop).
- The master thread is the JobMaster analogue: it triggers periodic
  checkpoints (CheckpointCoordinator), drains snapshot acks, delivers
  checkpoint-complete notifications TO the owner workers via per-TM
  mailboxes (the RPC hop of Execution.notifyCheckpointComplete —
  operators are only ever touched from their owner thread), watches
  worker failures, and detects termination by a pause-and-verify
  protocol (quiesce all workers at a step boundary, re-check that all
  sources finished and every channel drained, resume if not).
- Worker failure → cancel all → restart per the configured strategy,
  restoring from the latest completed checkpoint — the
  ExecutionGraph.failGlobal :1095 → restart :1148 →
  restoreLatestCheckpointedState :1223 path.
- Each TaskManager has its OWN processing-time service so wall-clock
  timers fire on the owning worker loop.

Used by tests as the multi-worker tier (MiniClusterResource analogue)
and by `StreamExecutionEnvironment.use_mini_cluster(n)`.
"""

from __future__ import annotations

import threading
import time as _time
from collections import deque
from typing import List, Optional

from flink_tpu.runtime.checkpoints import (
    CheckpointCoordinator,
    make_checkpoint_storage,
    make_restart_strategy,
)
from flink_tpu.runtime.local import (
    DEFAULT_CHANNEL_CAPACITY,
    JobCancelledException,
    JobClient,
    JobExecutionResult,
    SubtaskInstance,
    SuppressRestartsException,
    archive_finished_job,
    assign_restore_snapshots,
    build_and_wire_subtasks,
    gather_accumulators,
    initial_restore_point,
    make_health_plane,
)
from flink_tpu.runtime import faults
from flink_tpu.runtime.backpressure import (
    derive_upstreams,
    observe_subtask,
    observe_threaded_source,
)
from flink_tpu.runtime.device_stats import register_device_gauges
from flink_tpu.runtime.profiler import get_profiler, register_profiler_gauges
from flink_tpu.runtime.metrics import (
    MetricRegistry,
    register_checkpoint_gauges,
    register_faulttolerance_gauges,
    register_state_gauges,
    register_state_introspection_gauges,
)
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu.streaming.elements import LatencyMarker
from flink_tpu.streaming.graph import JobGraph
from flink_tpu.streaming.timers import TestProcessingTimeService


class TaskManagerRunner:
    """One worker thread owning a set of subtasks (the TaskExecutor
    analogue, reduced to the execution loop — slots, RPC, and the
    network stack collapse into in-process structures)."""

    STEP_BUDGET = 256
    SOURCE_BATCH = 128

    def __init__(self, tm_id: int, processing_time_service=None,
                 latency_interval_ms: Optional[int] = None):
        self.tm_id = tm_id
        self.pts = processing_time_service or TestProcessingTimeService()
        self.latency_interval_ms = latency_interval_ms
        self._last_latency_emit = _time.monotonic()
        self.subtasks: List[SubtaskInstance] = []
        self.sources: List[SubtaskInstance] = []
        self.coop_sources: List[SubtaskInstance] = []
        self.threaded_sources: List[SubtaskInstance] = []
        self.non_sources: List[SubtaskInstance] = []
        #: checkpoint-complete notifications from the master (mailbox)
        self.notifications: deque = deque()
        self.error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._pause = threading.Event()
        self._paused = threading.Event()
        self._thread: Optional[threading.Thread] = None
        #: monotonically increasing progress counter (read by master)
        self.progress = 0

    def assign(self, st: SubtaskInstance) -> None:
        self.subtasks.append(st)
        if st.is_source:
            self.sources.append(st)
            if st.supports_stepping:
                self.coop_sources.append(st)
            else:
                self.threaded_sources.append(st)
        else:
            self.non_sources.append(st)

    # ---- lifecycle ----------------------------------------------------
    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"taskmanager-{self.tm_id}")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._pause.clear()

    def join(self, timeout: float = 10.0) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    def pause(self) -> None:
        self._pause.set()

    def resume(self) -> None:
        self._pause.clear()
        self._paused.clear()

    def wait_paused(self, timeout: float = 5.0) -> bool:
        return self._paused.wait(timeout)

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # ---- the worker loop ------------------------------------------------
    def _run(self) -> None:
        try:
            # logical process lane: this worker thread's spans group
            # under one pid in the merged cluster trace
            get_tracer().set_lane(f"tm-{self.tm_id}")
            profiler = get_profiler()
            pts_poll = getattr(self.pts, "fire_due", None)
            while not self._stop.is_set():
                if self._pause.is_set():
                    self._paused.set()
                    _time.sleep(0.0002)
                    continue
                progress = 0
                while self.notifications:
                    cid = self.notifications.popleft()
                    for st in self.subtasks:
                        st.notify_checkpoint_complete(cid)
                # periodic latency markers from THIS worker's sources
                # (ref: the latencyMarksInterval emission in
                # StreamSource.run; emitted on the owner thread)
                if self.latency_interval_ms is not None:
                    now = _time.monotonic()
                    if ((now - self._last_latency_emit) * 1000.0
                            >= self.latency_interval_ms):
                        self._last_latency_emit = now
                        now_ms = _time.time() * 1000.0
                        for s in self.sources:
                            if s.finished:
                                continue
                            marker = LatencyMarker(
                                now_ms, s.head.operator_id, s.subtask_index)
                            with s.emission_lock:
                                s.head.output.emit_latency_marker(marker)
                for s in self.coop_sources:
                    if not s.finished:
                        if profiler.enabled:
                            profiler.set_scope(s)
                        n = s.source_step(self.SOURCE_BATCH)
                        progress += n
                        observe_subtask(s, n > 0)
                for s in self.threaded_sources:
                    if s.thread_error is not None:
                        raise s.thread_error
                    observe_threaded_source(s)
                    s.try_inject_threaded_trigger()
                    s.try_deliver_notifications()
                    if s.router.has_queued_output() \
                            and s.emission_lock.acquire(blocking=False):
                        try:
                            s.router.flush_records()
                        finally:
                            s.emission_lock.release()
                for st in self.non_sources:
                    if profiler.enabled:
                        profiler.set_scope(st)
                    n = st.step(self.STEP_BUDGET)
                    progress += n
                    observe_subtask(st, n > 0)
                if pts_poll is not None:
                    fired = pts_poll()
                    if fired:
                        # timer callbacks emit outside step() — flush
                        # so the master's quiescence check (and the
                        # data plane) see the output
                        for st in self.non_sources:
                            st.router.flush_records()
                        for s in self.coop_sources:
                            s.router.flush_records()
                    progress += fired
                if progress:
                    self.progress += progress
                else:
                    _time.sleep(0.0002)
        except BaseException as e:  # noqa: BLE001
            self.error = e
        finally:
            self._paused.set()


class MiniCluster:
    """Multi-worker in-process executor with the LocalExecutor API
    (execute / execute_async on a JobGraph)."""

    def __init__(self, num_task_managers: int = 2,
                 state_backend: str = "heap", max_parallelism: int = 128,
                 restart_strategy: Optional[dict] = None,
                 processing_time_service=None,
                 channel_capacity: int = DEFAULT_CHANNEL_CAPACITY,
                 metric_registry=None,
                 latency_interval_ms: Optional[int] = None,
                 sample_interval_ms: Optional[int] = None,
                 metrics_history_size: int = 1024,
                 archive_dir: Optional[str] = None,
                 columnar_pipeline: Optional[bool] = None,
                 chain_fusion: Optional[bool] = None):
        self.num_task_managers = num_task_managers
        self.state_backend = state_backend
        self.max_parallelism = max_parallelism
        self.restart_strategy_config = restart_strategy or {"strategy": "none"}
        self.shared_pts = processing_time_service  # None → per-TM services
        self.channel_capacity = channel_capacity
        self.metrics = metric_registry or MetricRegistry()
        register_state_gauges(self.metrics)
        register_state_introspection_gauges(self.metrics)
        register_device_gauges(self.metrics)
        register_profiler_gauges(self.metrics)
        self.latency_interval_ms = latency_interval_ms
        #: metrics time-series journal cadence (None = disabled)
        self.sample_interval_ms = sample_interval_ms
        self.metrics_history_size = metrics_history_size
        #: when set, finished jobs archive their post-mortem bundle
        self.archive_dir = archive_dir
        #: force the columnar batch pipeline on/off for jobs this
        #: cluster runs (None = leave the global flag alone); the
        #: differential suite executes the same graph both ways
        self.columnar_pipeline = columnar_pipeline
        #: force fused chain programs on/off the same way (None =
        #: leave chain_fusion.FUSION_ENABLED alone); the fused-vs-
        #: per-operator differential suite runs the same graph both
        #: ways on one process
        self.chain_fusion = chain_fusion

    # ---- public API -----------------------------------------------------
    #: the environment's job listeners (as LocalExecutor's)
    job_listeners: tuple = ()

    def execute(self, job_graph: JobGraph) -> JobExecutionResult:
        client = JobClient()
        for listener in self.job_listeners:
            listener(client)
        self._run_job(job_graph, client)
        return client.wait()

    def execute_async(self, job_graph: JobGraph) -> JobClient:
        client = JobClient()
        t = threading.Thread(target=self._run_job, args=(job_graph, client),
                             daemon=True, name="minicluster-master")
        client._thread = t
        t.start()
        return client

    # ---- job driver (restarts) -------------------------------------------
    def _run_job(self, job_graph: JobGraph, client: JobClient) -> None:
        result = JobExecutionResult(job_graph.job_name)
        cp_config = job_graph.checkpoint_config
        storage = make_checkpoint_storage(cp_config) if cp_config else None
        restart = make_restart_strategy(self.restart_strategy_config)
        restore_from = initial_restore_point(job_graph)
        journal, evaluator = make_health_plane(
            self.metrics, self.sample_interval_ms,
            self.metrics_history_size, job_graph.job_name, client)
        from flink_tpu.streaming import chain_fusion as _fusion
        from flink_tpu.streaming import columnar as _columnar
        saved_pipeline = _columnar.PIPELINE_ENABLED
        if self.columnar_pipeline is not None:
            _columnar.PIPELINE_ENABLED = self.columnar_pipeline
        saved_fusion = _fusion.FUSION_ENABLED
        if self.chain_fusion is not None:
            _fusion.FUSION_ENABLED = self.chain_fusion
        try:
            while True:
                try:
                    self._run_attempt(job_graph, client, result, storage,
                                      restore_from, journal, evaluator)
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
                        raise
                    result.restarts += 1
                    if restart.delay_ms:
                        _time.sleep(restart.delay_ms / 1000.0)
                    restore_from = storage.latest() if storage else None
        except BaseException as e:  # noqa: BLE001
            client._finish(error=e)
        finally:
            if self.columnar_pipeline is not None:
                _columnar.PIPELINE_ENABLED = saved_pipeline
            if self.chain_fusion is not None:
                _fusion.FUSION_ENABLED = saved_fusion
            archive_finished_job(self.archive_dir, self.metrics,
                                 job_graph, client, journal, evaluator)

    # ---- one attempt -------------------------------------------------------
    def _run_attempt(self, job_graph: JobGraph, client: JobClient,
                     result: JobExecutionResult, storage,
                     restore_from: Optional[dict],
                     journal=None, evaluator=None) -> None:
        tms = [TaskManagerRunner(i, self.shared_pts,
                                 latency_interval_ms=self.latency_interval_ms)
               for i in range(self.num_task_managers)]

        # slot assignment: subtask i of every vertex → TM (i mod N); a
        # vertex with parallelism >= N spreads over all workers (the
        # spread-out slot strategy)
        def pts_for(vid: int, idx: int):
            return tms[idx % len(tms)].pts

        subtasks = build_and_wire_subtasks(
            job_graph, self.state_backend, self.max_parallelism, pts_for,
            self.channel_capacity, self.metrics)
        all_tasks: List[SubtaskInstance] = [
            st for v in job_graph.topological_vertices()
            for st in subtasks[v.id]]
        for vid, sts in subtasks.items():
            for i, st in enumerate(sts):
                tms[i % len(tms)].assign(st)
        sources = [st for st in all_tasks if st.is_source]
        non_sources = [st for st in all_tasks if not st.is_source]
        threaded_sources = [s for s in sources if not s.supports_stepping]

        for st in all_tasks:
            st.open()
        if restore_from is not None:
            assign_restore_snapshots(job_graph, restore_from, subtasks)

        ack_queue: deque = deque()
        coordinator = None
        if storage is not None and job_graph.checkpoint_config.get("interval"):
            cfg = job_graph.checkpoint_config

            def trigger_sources(cid, ts, options):
                if any(s.finished for s in sources):
                    return False
                for s in sources:
                    s.pending_trigger = (cid, ts, options)
                return True

            def notify_complete(cid):
                # RPC analogue: enqueue to the owner workers' mailboxes
                for tm in tms:
                    tm.notifications.append(cid)

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
            if "alignment_spill_threshold" in cp_cfg:
                st.alignment_spill_threshold = \
                    cp_cfg["alignment_spill_threshold"]
            if "alignment_abort_limit" in cp_cfg:
                st.alignment_abort_limit = \
                    cp_cfg["alignment_abort_limit"]

        client.executor_state = {
            "subtasks": subtasks, "coordinator": coordinator,
            "task_managers": tms,
            # live checkpoint views add the current coordinator's
            # count to this — totals survive restarts (see local.py)
            "checkpoints_base": getattr(result, "_cp_base", 0),
            "journal": journal, "health": evaluator,
            "upstreams": derive_upstreams(job_graph),
        }

        for s in threaded_sources:
            s.run_source_threaded()
        for tm in tms:
            tm.start()

        try:
            self._master_loop(client, coordinator, ack_queue, tms,
                              all_tasks, sources, non_sources,
                              threaded_sources, journal, evaluator)
            gather_accumulators(all_tasks, result.accumulators)
        finally:
            if coordinator is not None:
                try:
                    coordinator.drain()  # land in-flight async writes
                except Exception:  # noqa: BLE001 — teardown: the attempt's
                    pass               # outcome is already decided
                result.checkpoints_completed = (
                    getattr(result, "_cp_base", 0)
                    + coordinator.completed_count)
                result._cp_base = result.checkpoints_completed
                coordinator.stopped = True
                coordinator.fail_pending_savepoints(
                    RuntimeError("job attempt ended before the savepoint "
                                 "completed"))
            for tm in tms:
                tm.stop()
            for s in sources:
                s.cancel_source()
            for s in threaded_sources:
                s.join_source()
            for tm in tms:
                tm.join()
            for st in all_tasks:
                st.close()

    # ---- master (JobMaster analogue) ---------------------------------------
    def _master_loop(self, client: JobClient, coordinator, ack_queue,
                     tms: List[TaskManagerRunner],
                     all_tasks, sources, non_sources,
                     threaded_sources, journal=None,
                     evaluator=None) -> None:
        while True:
            if client.cancel_requested:
                raise JobCancelledException()
            for tm in tms:
                if tm.error is not None:
                    raise tm.error
            # metrics journal tick: the master samples the shared
            # registry — workers publish into it in-process, so no
            # shipping is needed here (contrast cluster.py)
            if journal is not None and journal.maybe_sample():
                evaluator.evaluate()
            if coordinator is not None:
                if all(not s.finished for s in sources):
                    coordinator.maybe_trigger()
                while ack_queue:
                    task_key, cid, snapshot = ack_queue.popleft()
                    if task_key is None:   # alignment-cap decline
                        coordinator.decline(cid)
                    else:
                        coordinator.acknowledge(task_key, cid, snapshot)
                for s in sources:
                    if s.finished and s.pending_trigger is not None:
                        cid = s.pending_trigger[0]
                        s.pending_trigger = None
                        coordinator.decline(cid)

            if self._quiescent(sources, non_sources, threaded_sources):
                # pause-and-verify: freeze all workers at a step
                # boundary, re-check under the freeze
                for tm in tms:
                    tm.pause()
                for tm in tms:
                    tm.wait_paused()
                for tm in tms:
                    if tm.error is not None:
                        raise tm.error
                if self._quiescent(sources, non_sources, threaded_sources):
                    break
                for tm in tms:
                    tm.resume()
            _time.sleep(0.001)

        # workers are paused and verified idle: the master takes over
        # single-threaded for the end-of-job phases (the owner handover
        # is safe because every worker sits at a step boundary)
        for tm in tms:
            tm.stop()
        for tm in tms:
            tm.join()
        for tm in tms:
            if tm.error is not None:
                raise tm.error
        # deliver any straggler notifications
        for tm in tms:
            while tm.notifications:
                cid = tm.notifications.popleft()
                for st in tm.subtasks:
                    st.notify_checkpoint_complete(cid)
        # drain processing-time timers (per-TM services), cascading
        for _ in range(1000):
            for tm in tms:
                if isinstance(tm.pts, TestProcessingTimeService):
                    tm.pts.fire_all_pending()
            for st in all_tasks:
                st.router.flush_records()
            moved = sum(st.step(1 << 30) for st in non_sources)
            if moved == 0 and not any(
                    isinstance(tm.pts, TestProcessingTimeService)
                    and tm.pts.has_pending() for tm in tms):
                break
        if coordinator is not None:
            while ack_queue:
                task_key, cid, snapshot = ack_queue.popleft()
                coordinator.acknowledge(task_key, cid, snapshot)
        try:
            for st in all_tasks:
                for op in st.operators:
                    op.finish()
                st.router.flush_records()
                for t in non_sources:
                    t.step(1 << 30)
        except Exception as e:  # noqa: BLE001
            raise SuppressRestartsException(e) from e

    @staticmethod
    def _quiescent(sources, non_sources, threaded_sources) -> bool:
        return (all(s.finished for s in sources)
                and not any(st.has_queued_input() for st in non_sources)
                and all(s._thread is None or not s._thread.is_alive()
                        for s in threaded_sources))

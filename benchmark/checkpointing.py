"""What the checkpointed session cell needs besides ``session``'s
counters: the job's checkpointing switched on from the configuration's
``checkpoint`` block, the handle on the running job (its checkpoint
coordinator's books), every offset commit the log received, marks at
``t0`` and at the end of the measured window, the bytes function of
the ``state.snapshot.*`` programs, and, after the
timed window, the ledger check and the recovery that
``references/session_countmin_ckpt.py`` adds to the session cell's
comparison.

A program without the counters, the phases or the doors named here
gives ``None`` from every reader, and nothing raises.

**The traced run.**  The harness profiles ONE period, and a checkpoint
comes every ``interval_ms`` of wall clock, one period in nine.  The
deployment is left as it is (no checkpoint is asked for, nothing is
waited for): the slice is AIMED instead.  From the harness's own
profile window on, the first period into which a barrier falls is the
one traced (:func:`_aim_slice` says how that is told at the period's
start): the capture's device programs run in it and behind it.
``state_snapshot_roofline`` counts, program by program, only the
dispatches whose device time lies inside the slice.
"""

from __future__ import annotations

import os
import shutil
import time
import types

import numpy as np

import loader
import session
import spill

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: ``STATE_STATS`` fields of the backend's captures
COUNTERS = ("snapshot_columns", "snapshot_captures", "snapshot_tiles",
            "snapshot_bytes_device", "snapshot_bytes_written")
#: the phase of a checkpoint's synchronous part on a task's thread
SYNC_PHASE = "checkpoint.sync"
#: the job the recovery builds: the deployment's, without the
#: checkpointing this module switches on
PLAIN_JOB = "datastream_state_session"

_held = {"commits": [], "marks": {}}


def checkpoint_dir(config):
    return os.path.join(ROOT, "benchmark_out", "checkpoints", config["name"])


# ---- switching it on ---------------------------------------------------

def enable(env, source, config):
    """Called by the job once it is built: checkpointing as the
    configuration's ``checkpoint`` block says, into an emptied
    directory; the source, its log and the directory kept for the
    check; marks on the run's timeline."""
    block = config["checkpoint"]
    if not hasattr(env, "register_job_listener"):
        raise SystemExit(
            f"benchmark: {config['name']} needs the running job's client "
            f"(register_job_listener); this tree's environment has no "
            f"such door")
    directory = checkpoint_dir(config)
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    env.enable_checkpointing(block["interval_ms"], mode=block["mode"],
                             async_persist=block["async"])
    env.set_checkpoint_storage(block["storage"], directory,
                               retain=block["retain"])
    handed = env.get_job_graph().checkpoint_config or {}
    if not handed.get("interval") or handed.get("storage") != block["storage"] \
            or bool(handed.get("async_persist")) != block["async"]:
        raise SystemExit(
            f"benchmark: {config['name']} needs a checkpoint coordinator; "
            f"this tree's executor would be handed {handed!r}")
    env.register_job_listener(lambda client: _held.__setitem__("client",
                                                              client))
    _held.update(source=source, log=source.log, directory=directory,
                 config=config, commits=[], marks={})
    _record_commits(source.log)
    timeline = source.timeline
    timeline.on_t0.append(lambda: _at_t0(timeline))
    timeline.on_end.append(lambda: _mark("end"))


def coordinator():
    client = _held.get("client")
    state = client.executor_state if client is not None else None
    return state.get("coordinator") if state else None


def _record_commits(log):
    """Every offset commit the log receives, with the checkpoint that
    was the newest completed one when it came (a checkpoint's own
    commit is the first under its id: it is notified right after its
    id becomes the newest; the connector's commit at the end of input
    comes later, under an id seen before)."""
    inner = log.commit_offsets

    def commit_offsets(offsets):
        coord = coordinator()
        cid = coord.latest_completed_id if coord is not None else None
        _held["commits"].append((cid, dict(offsets)))
        inner(offsets)
    log.commit_offsets = commit_offsets


def _books():
    from flink_tpu.runtime import tracing
    from flink_tpu.state.stats import STATE_STATS
    coord = coordinator()
    noted = {name: getattr(STATE_STATS, name) for name in COUNTERS
             if hasattr(STATE_STATS, name)}
    noted["host_s"] = time.perf_counter()
    sync = tracing.get_tracer().stats().get(SYNC_PHASE)
    if sync is not None:
        noted.update(sync_ms=sync["total_ms"], sync_count=sync["count"])
    if coord is not None:
        noted.update(clock_ms=coord._clock(),
                     completed=coord.completed_count,
                     failed=coord.failed_count,
                     aborted=coord.aborted_count,
                     latest_completed=coord.latest_completed_id,
                     commits=len(_held["commits"]),
                     committed=dict(_held["log"].committed_offsets))
    return noted


def _mark(name):
    _held["marks"][name] = _books()


def _at_t0(timeline):
    _mark("t0")
    if timeline.profiler is not None:
        _aim_slice(timeline)


#: a traced run that has not found its period by this share of the
#: measured seconds traces the next one, whatever it holds
AIM_UNTIL = 0.7


def _aim_slice(timeline):
    """The traced period becomes one in which a checkpoint's barrier
    is injected (the module's docstring says why), not before the
    harness's own ``profile_window``.  The executor's loop looks at
    the coordinator's timer between a period's two source steps (the
    chunks; the watermark) and after them, and a barrier goes in at
    the source's next step.  So the period is traced at whose start
    either a checkpoint is pending (triggered behind the last step:
    its barrier has just gone in), or the coordinator is free and its
    timer has less left to run than the shortest first step seen
    since ``t0`` takes (its barrier goes in before the watermark).
    Nothing of the job is touched: the harness's ``window_starts`` is
    asked for another window."""
    first, starts = timeline.profile_window, timeline.window_starts
    interval_ms = _held["config"]["checkpoint"]["interval_ms"]
    unset = timeline.profile_window = 1 << 62
    seen = {"w": None, "at": None, "first_step_s": None}

    def window_starts(w):
        now = timeline.clock()
        # (the watermark step of period w stamps closes[w - 1])
        closed = timeline.closes.get(seen["w"] - 1) \
            if seen["w"] is not None else None
        if closed is not None and timeline.t0 is not None \
                and seen["at"] >= timeline.t0:
            took = closed - seen["at"]
            if seen["first_step_s"] is None or took < seen["first_step_s"]:
                seen["first_step_s"] = took
        seen.update(w=w, at=now)
        coord = coordinator()
        if timeline.profile_window == unset and w >= first \
                and timeline.t0 is not None and coord is not None:
            left_ms = interval_ms - (coord._clock()
                                     - coord._last_triggered_at)
            free = not coord.pending and not coord._inflight
            soon = (free and seen["first_step_s"] is not None
                    and 0 < left_ms <= 0.9e3 * seen["first_step_s"])
            if coord.pending or soon \
                    or timeline.elapsed() >= AIM_UNTIL * timeline.seconds:
                timeline.profile_window = w
                _held["marks"]["aimed"] = {
                    "window": w, "pending": bool(coord.pending),
                    "timer_left_ms": left_ms}
        starts(w)
    timeline.window_starts = window_starts


# ---- what the metric readers ask ----------------------------------------

def noted(mark, name):
    return (_held["marks"].get(mark) or {}).get(name)


def counted(name, first="t0", last="end"):
    a, b = noted(first, name), noted(last, name)
    return None if a is None or b is None else b - a


def checkpoints_in_window():
    """The coordinator's stats of the checkpoints triggered inside the
    measured window, oldest first; ``None`` without the books."""
    coord = coordinator()
    t0, end = noted("t0", "clock_ms"), noted("end", "clock_ms")
    if coord is None or t0 is None or end is None:
        return None
    return [coord.stats[cid] for cid in sorted(coord.stats)
            if t0 <= coord.stats[cid].trigger_ms <= end]


def completed_durations_ms():
    stats = checkpoints_in_window()
    if stats is None:
        return None
    return [s.duration_ms for s in stats if s.duration_ms is not None]


def written_bytes():
    stats = checkpoints_in_window()
    if stats is None:
        return None
    return [s.state_bytes for s in stats
            if s.complete_ms is not None and s.state_bytes >= 0]


def sync_share(run):
    """``checkpoint.sync``'s total between ``t0`` and the end of the
    measured window as % of the window, off the tracer's always-on
    books (marked as the counters are): a longer stall reads higher,
    whatever it does to the period history."""
    spent = counted("sync_ms")
    return None if spent is None else 100.0 * spent / (run["window_s"] * 1e3)


def sync_ms_max():
    """The longest synchronous part of a checkpoint in the measured
    window, ms: ``checkpoint.sync``'s total in the fire period that
    holds it (a barrier's parts on the source's and the window
    operator's tasks lie inside one), the largest over the program's
    own fire periods (``Tracer.periods()``) that ended between the two
    marks.  ``None`` without the phase or the history."""
    from flink_tpu.runtime import tracing
    t0, end = noted("t0", "host_s"), noted("end", "host_s")
    periods = getattr(tracing.get_tracer(), "periods", None)
    if t0 is None or end is None or periods is None \
            or not counted("sync_count"):
        return None
    inside = [p["phases"].get(SYNC_PHASE, {}).get("total_ms", 0.0)
              for p in periods() if t0 < p["end_s"] <= end]
    return max(inside) if inside else None


# ---- bytes the capture must move through HBM ---------------------------

def snapshot_row_bytes(config):
    """One captured row: the slot's accumulator read once, whatever
    encodes it on its way out (a dense copy writes as much again, the
    cells off the fill a few bytes)."""
    return session.slot_bytes(config)


def snapshot_tile_rows():
    """Program label -> rows one dispatch of it reads, of the timed
    job's capture programs (``SnapshotPlan.tile_rows``); ``None``
    where the program has no such plan."""
    for op in session._operators:
        plan = getattr(getattr(op, "window_state", None),
                       "_snapshot_plan", None)
        if getattr(plan, "tile_rows", None):
            return dict(plan.tile_rows)
    return None


def snapshot_roofline(run):
    """Over the capture's programs (``state.snapshot.*``) and only
    their dispatches inside the traced slice: rows a dispatch reads
    (its tile's width) x :func:`snapshot_row_bytes` / their device
    time / the HBM rate, in %.  Silent where the slice holds none."""
    import jax

    import peaks
    import span_slice
    tile_rows = snapshot_tile_rows()
    path = span_slice.newest_trace() if run.get("slice_s") else None
    if tile_rows is None or path is None:
        return None
    # an "XLA Modules" event is named jit_<label, dots as _> and some
    # suffix: the longest label it starts with (cells16 is a prefix
    # of nothing then, cells1024 is tried before it)
    width = sorted((("jit_" + label.replace(".", "_"), rows)
                    for label, rows in tile_rows.items()),
                   key=lambda pair: -len(pair[0]))
    rows = seconds = 0.0
    for name, ns in spill.first_device_modules(path):
        for module, tile in width:
            if name.startswith(module):
                rows += tile
                seconds += ns * 1e-9
                break
    if not seconds:
        return None
    peak = peaks.for_device(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * rows * snapshot_row_bytes(run["config"]) / seconds / peak


# ---- the ledger ----------------------------------------------------------

def ledger(config, window_s):
    """(facts, problems) of the run's checkpoints: those triggered in
    the measured window all completed, enough of them, consecutive
    completions at most two intervals apart, none failed or aborted,
    one offset commit each, and the log's committed offsets at the end
    of the window were those of the newest completed checkpoint."""
    block = config["checkpoint"]
    interval_s = block["interval_ms"] / 1e3
    problems = []
    stats = checkpoints_in_window()
    coord = coordinator()
    if stats is None:
        return {}, ["no checkpoint coordinator ran, or the timeline's "
                    "marks are missing"]
    triggered = len(stats)
    completed = [s for s in stats if s.status == "completed"]
    want = int(window_s // interval_s) - 1
    if len(completed) != triggered:
        problems.append(
            f"{triggered} checkpoints triggered in the measured window, "
            f"{len(completed)} completed: "
            f"{[(s.checkpoint_id, s.status, s.failure_cause) for s in stats if s.status != 'completed']}")
    if len(completed) < want:
        problems.append(f"{len(completed)} checkpoints completed in "
                        f"{window_s:.1f} s at an interval of {interval_s} s: "
                        f"fewer than {want}")
    for name in ("failed", "aborted"):
        if counted(name):
            problems.append(f"{counted(name)} checkpoints {name} in the "
                            f"measured window")
    # `guarantees.delivery`: "consecutive completions lie at most 10 s
    # apart (the recovery point)", two intervals: read over the
    # completions inside the measured window, from the last one before
    # it.  (A checkpoint triggered in the window's last seconds
    # becomes durable behind the end of the stream, which fires every
    # open session at once: it has to complete, above, and is no
    # completion of the window.)
    t0_ms, end_ms = noted("t0", "clock_ms"), noted("end", "clock_ms")
    durable = sorted(s.complete_ms for s in coord.stats.values()
                     if s.complete_ms is not None
                     and s.complete_ms <= end_ms)
    times = [t for t in durable if t < t0_ms][-1:] \
        + [t for t in durable if t >= t0_ms]
    gaps = [b - a for a, b in zip(times, times[1:])] or [0.0]
    if max(gaps) > 2 * block["interval_ms"]:
        problems.append(f"consecutive completions {max(gaps) / 1e3:.2f} s "
                        f"apart inside the window: more than "
                        f"{2 * interval_s} s (the recovery point)")
    # one commit a completed checkpoint, the first under its id
    first_commit = {}
    for cid, offsets in _held["commits"]:
        first_commit.setdefault(cid, offsets)
    # (a checkpoint that became durable after the end of the input
    # commits nothing: the connector has committed its last positions)
    uncommitted = [s.checkpoint_id for s in completed
                   if s.checkpoint_id not in first_commit
                   and s.complete_ms <= end_ms]
    if uncommitted:
        problems.append(f"checkpoints {uncommitted} completed and "
                        f"committed no offsets")
    newest = noted("end", "latest_completed")
    if newest is not None and noted("end", "committed") \
            != first_commit.get(newest):
        problems.append(
            f"at the end of the window the log's committed offsets "
            f"{noted('end', 'committed')} were not checkpoint {newest}'s "
            f"{first_commit.get(newest)}")
    facts = {"checkpoints_triggered": triggered,
             "checkpoints_completed": len(completed),
             "checkpoints_required": want,
             "largest_gap_s": max(gaps) / 1e3,
             "triggered_at_s": [round((s.trigger_ms - t0_ms) / 1e3, 2)
                                for s in stats],
             "duration_ms": [round(s.duration_ms, 1) for s in completed],
             "ack_ms": [round(s.sync_duration_ms, 1) for s in completed
                        if s.sync_duration_ms is not None],
             "written_bytes": [s.state_bytes for s in completed],
             "snapshot_rows": counted("snapshot_columns"),
             "snapshot_tiles": counted("snapshot_tiles"),
             "snapshot_bytes_device": counted("snapshot_bytes_device"),
             "snapshot_bytes_encoded": counted("snapshot_bytes_written")}
    if "aimed" in _held["marks"]:
        facts["traced_period"] = _held["marks"]["aimed"]
    return facts, problems


# ---- the recovery --------------------------------------------------------

def consumer(log, config):
    """The program's connector as a user sets it up, bounded, reading
    a chunk of every partition a step as the timed run did."""
    from flink_tpu.connectors.log_connector import ReplayableLogSource

    class Consumer(ReplayableLogSource):
        emits_batches = True

        def emit_step(self, ctx, max_records):
            _held["recovery"].setdefault("first_step", time.perf_counter())
            return super().emit_step(
                ctx, self.batch_per_partition * self.log.num_partitions)

    return Consumer(log, bounded=True,
                    watermark_lag_ms=config["watermark_lag_ms"],
                    batch_per_partition=config["batch_rows"])


def _restore_point(directory):
    """(checkpoint id, the consumer's offsets, the window operator's
    watermark) of the newest checkpoint the directory retains."""
    from flink_tpu.runtime.checkpoints import load_retained_checkpoint
    point = load_retained_checkpoint(directory)
    offsets, watermark = {}, None
    for task in point["tasks"].values():
        for snap in task.get("operators", {}).values():
            fn = snap.get("function")
            if isinstance(fn, dict) and "offsets" in fn:
                offsets.update(fn["offsets"])
            timers = snap.get("timers")
            if timers is not None and (timers.get("event")
                                       or watermark is None):
                watermark = timers.get("watermark")
    return point["checkpoint_id"], offsets, watermark


def release_first_job():
    """The timed job's device state goes, so that the recovery's table
    has the chip to itself."""
    for op in session._operators:
        state = getattr(op, "window_state", None)
        tree = getattr(state, "device_state", None)
        if tree:
            for arr in tree.values():
                arr.delete()
            state.device_state = {}
        backend = getattr(op, "keyed_backend", None)
        if backend is not None:
            backend.dispose()


def _rows(results):
    """Result columns {period: columns} as one int64 [rows, columns]
    array."""
    if not results:
        return np.zeros((0, 0), np.int64)
    return np.concatenate(
        [np.stack([np.asarray(c, np.int64) for c in cols], axis=1)
         for cols in results.values()])


def recover(config, results):
    """After the timed window, in the same process: a fresh
    environment with the deployment's job over a plain bounded
    consumer of the same log, started from the retained checkpoint
    through ``set_savepoint_restore``, run to the end of the log.
    Returns (facts, problems): every session that fired after the
    checkpoint in the timed run must come again, integer for
    integer."""
    from flink_tpu.runtime import tracing
    from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    from timeline import ArrivalSink, Timeline
    held = _held
    problems = []
    if "log" not in held:
        return {}, ["the job kept no log for the recovery"]
    log, directory = held["log"], held["directory"]
    ends = {p: log.end_offset(p) for p in range(log.num_partitions)}
    try:  # what the storage retains, whatever it left to replay
        cid, offsets, watermark = _restore_point(directory)
    except Exception as e:  # noqa: BLE001 — the verdict, not a crash
        return {}, [f"no checkpoint to recover from in {directory}: {e!r}"]
    replayed = sum(ends[p] - offsets.get(p, 0) for p in ends)
    first_commit = {}
    for commit_id, committed in held["commits"]:
        first_commit.setdefault(commit_id, committed)
    if cid in first_commit and first_commit[cid] != offsets:
        problems.append(f"checkpoint {cid} holds offsets {offsets}; the "
                        f"log was committed {first_commit.get(cid)} for it")
    release_first_job()
    held["recovery"] = {}
    built_before = len(session._operators)
    window_ms = config["window_ms"]
    source = consumer(log, config)
    source.watch_items = held["source"].watch_items
    source.timeline = types.SimpleNamespace(on_t0=[], on_end=[])
    sink = ArrivalSink(Timeline(0, 0.0), window_ms,
                       config["result_columns"].index("window_start"))
    env = StreamExecutionEnvironment()
    loader.load_module("jobs", PLAIN_JOB).build(env, source, sink, config)
    env.set_savepoint_restore(directory)
    ours = tracing.get_tracer()
    tracing.set_tracer(tracing.Tracer())  # the run's books stay the run's
    t_start = time.perf_counter()
    try:
        env.execute(f"recovery-{config['name']}")
    finally:
        tracing.set_tracer(ours)
    t_end = time.perf_counter()
    sink.finish()
    got = _rows(sink.by_window())
    first = _rows(results)
    end_col = config["result_columns"].index("session_end")
    start_col = config["result_columns"].index("session_start")
    # a session fires when the watermark passes its last millisecond
    after = first[first[:, end_col] - 1 > watermark] if len(first) else first
    read_until = min(offsets.get(p, 0) for p in ends) \
        // config["batch_rows"] * window_ms
    across = int((after[:, start_col] < read_until).sum()) if len(after) \
        else 0

    def ordered(rows):
        return rows[np.lexsort(rows.T[::-1])] if len(rows) else rows
    if got.shape != after.shape or not np.array_equal(ordered(got),
                                                      ordered(after)):
        a = {tuple(r) for r in after.tolist()}
        g = {tuple(r) for r in got.tolist()}
        problems.append(
            f"the recovery emitted {len(got)} rows, the timed run "
            f"{len(after)} for the sessions that fired after checkpoint "
            f"{cid}: {len(a - g)} of the run's missing or different, "
            f"{len(g - a)} of the recovery's not the run's")
    if not across:
        problems.append("no session was open across the checkpoint")
    late = [op.num_late_records_dropped
            for op in session._operators[built_before:]]
    if any(late):
        problems.append(f"the recovery dropped {late} rows as late")
    first_step = held["recovery"].get("first_step", t_end)
    facts = {"recovered_from_checkpoint": cid,
             "recovery_offsets": offsets,
             "recovery_watermark": watermark,
             "recovery_replayed_events": replayed,
             "recovery_replayed_periods":
                 replayed / config["events_per_window"],
             "recovery_rows": len(got),
             "recovery_rows_expected": len(after),
             "recovery_sessions_open_across": across,
             "recovery_late_rows": sum(late),
             "restore_s": first_step - t_start,
             "catch_up_s": t_end - first_step}
    return facts, problems

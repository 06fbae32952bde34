"""The `tpu` backend's snapshot in two parts (state/device_snapshot.py):
a capture at the barrier, resolved later.  Three jobs' worth of state
(tumbling HLL, sliding quantiles, session Count-Min), with and without
a device budget that leaves rows in the host tier:

- a capture taken while batches keep arriving holds, bit for bit, what
  the table held at the barrier (read straight off the device arrays
  at that instant) and what the synchronous `snapshot_columns` gave:
  pending micro-batch rows, merged sessions, spilled rows, integer and
  dict slot tables;
- restore and continue equals the uninterrupted run, results and
  state;
- through `env.execute()`: a `ReplayableLogSource`, checkpoints to a
  filesystem directory, one injected task failure and a fixed-delay
  restart give exact totals, and every commit to the log is its
  checkpoint's offsets; a fresh job starts from the retained
  checkpoint directory through `set_savepoint_restore`; with
  checkpointing off no snapshot bookkeeping runs.
"""

import pickle

import numpy as np
import pytest
import session_countmin_reference as reference

from flink_tpu.connectors.log_connector import ReplayableLogSource
from flink_tpu.connectors.partitioned_log import ColumnarPartitionedLog
from flink_tpu.core.config import Configuration
from flink_tpu.core.keygroups import KeyGroupRange
from flink_tpu.core.state import AggregatingStateDescriptor
from flink_tpu.ops.sketches import (
    CountMinSketchAggregate,
    HyperLogLogAggregate,
    QuantileSketchAggregate,
)
from flink_tpu.runtime import faults
from flink_tpu.runtime.checkpoints import load_retained_checkpoint
from flink_tpu.state import slot_index
from flink_tpu.state.sparse_rows import SparseRows
from flink_tpu.state.stats import STATE_STATS
from flink_tpu.state.tpu_backend import TpuKeyedStateBackend
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.operators import ProcessFunction
from flink_tpu.streaming.sources import CollectSink
from flink_tpu.streaming.windowing import EventTimeSessionWindows

MAX_PAR = 16
FULL = KeyGroupRange(0, MAX_PAR - 1)
BUDGETS = pytest.mark.parametrize("budget", [None, 64],
                                  ids=["no-budget", "host-tier"])


# ---- three jobs' worth of calls on a backend ----------------------------

class Drive:
    """One job's calls on a backend, batch by batch."""

    def __init__(self, budget, seed=0):
        # (under a budget a micro-batch small against it, or nothing
        # is ever cold enough to leave the device)
        kw = {"microbatch": 64} if budget is None else {
            "max_device_slots": budget, "initial_capacity": 8,
            "microbatch": 8}
        self.backend = TpuKeyedStateBackend(FULL, MAX_PAR, **kw)
        self.state = self.backend.get_partitioned_state(
            (), AggregatingStateDescriptor("s", self.aggregate()))
        self.rng = np.random.default_rng(seed)
        self.step = 0

    def values(self, n):
        return self.rng.integers(1, 10_000, n).astype(np.int64)

    def batch(self):
        self.step += 1
        self._write(self.step, 40)
        self._tidy(self.step)

    def write_behind(self):
        """A few rows that stay in the pending micro-batch."""
        self.step += 1
        for n in (10, 3, 5, 2):
            self._write(self.step, n)
            if len(self.state._pending_slots):
                break

    def read(self):
        """What a fire of everything live would emit."""
        entries = sorted(self.state.active_entries(), key=repr)
        keys = [k for k, _ in entries]
        res, found = self.state.get_batch(
            keys, None, namespaces=[ns for _, ns in entries])
        assert found.all()
        return entries, np.asarray(res)


class TumblingHll(Drive):
    """One namespace a batch: integer keys go onto an integer table."""

    def aggregate(self):
        return HyperLogLogAggregate(8)

    def keys(self, n):
        return self.rng.integers(0, 300, n)

    def _write(self, step, n):
        window = (step // 3 * 1000, step // 3 * 1000 + 1000)
        self.backend.add_batch(self.state, self.keys(n), window,
                               self.values(n))

    def _tidy(self, step):
        if step % 3 == 0 and step > 3:  # an old window fires and goes
            old = ((step // 3 - 2) * 1000, (step // 3 - 1) * 1000)
            keys = [k for k, ns in self.state.active_entries() if ns == old]
            self.state.clear_batch(keys, old)


class TumblingHllStrings(TumblingHll):
    """... string keys onto a dict."""

    def keys(self, n):
        return [f"user-{k}" for k in self.rng.integers(0, 300, n)]


class SlidingQuantiles(Drive):
    """Every batch under three overlapping windows."""

    def aggregate(self):
        return QuantileSketchAggregate()

    def values(self, n):
        return self.rng.lognormal(3.0, 1.0, n).astype(np.float32)

    def _write(self, step, n):
        n = n * 3 // 4
        keys, values = self.rng.integers(0, 120, n), self.values(n)
        for back in range(3):
            start = (step - back) * 1000
            self.backend.add_batch(self.state, keys, (start, start + 3000),
                                   values)

    def _tidy(self, step):
        old = ((step - 4) * 1000, (step - 1) * 1000)
        gone = [k for k, ns in self.state.active_entries() if ns == old]
        if gone:
            self.state.clear_batch(gone, old)


class SessionCountMin(Drive):
    """A namespace per row, and merges of a key's windows a batch."""

    def aggregate(self):
        return CountMinSketchAggregate(2, 128, unit_weights=True,
                                       queries=(1, 2, 3))

    def _write(self, step, n):
        keys = self.rng.integers(0, 150, n).tolist()
        windows = [(step * 100 + int(k) % 3, step * 100 + 50) for k in keys]
        self.backend.add_batch(self.state, keys, None, self.values(n),
                               namespaces=windows)

    def _tidy(self, step):
        by_key = {}
        for key, ns in self.state.active_entries():
            by_key.setdefault(key, []).append(ns)
        # (as MergingWindowSet: the oldest keeps the state, the target
        # is never among the sources)
        merges = [(key, sorted(w)[0], sorted(w)[1:3])
                  for key, w in sorted(by_key.items())
                  if len(w) > 1][:3]
        if merges:
            self.state.merge_namespaces_batch(merges)


JOBS = pytest.mark.parametrize(
    "job", [TumblingHll, TumblingHllStrings, SlidingQuantiles,
            SessionCountMin], ids=lambda j: j.__name__)


def table_of(state):
    """{(key, namespace): {component: bytes}} read straight off the
    device arrays and the host tier (no capture program runs)."""
    host = {name: np.asarray(arr)
            for name, arr in state.device_state.items()}
    keys, nss, slots = state.slot_index.columns()
    out = {(k, ns): {n: host[n][s].tobytes() for n in host}
           for k, ns, s in zip(keys, nss, slots.tolist())}
    if state.host_tier:
        s_keys, s_nss, s_comps = state.host_tier.columns()
        for i, entry in enumerate(zip(s_keys, s_nss)):
            assert entry not in out
            out[entry] = {n: s_comps[n][i].tobytes() for n in s_comps}
    return out


def cells_of(columns):
    """The same of per-key-group columns (dense or sparse)."""
    out = {}
    for keys, nss, comps in columns.values():
        dense = {n: np.asarray(c) for n, c in comps.items()}
        for i, entry in enumerate(zip(keys, nss)):
            assert entry not in out
            out[entry] = {n: dense[n][i].tobytes() for n in dense}
    return out


@JOBS
@BUDGETS
def test_a_capture_holds_the_barriers_state_while_batches_keep_arriving(
        job, budget):
    drive = job(budget)
    for _ in range(7):
        drive.batch()
    state = drive.state
    drive.write_behind()
    assert len(state._pending_slots)  # a micro-batch is pending
    capture = state.capture()         # the barrier: flushes, dispatches
    at_barrier = table_of(state)
    synchronous = state.snapshot_columns()
    for _ in range(6):                # updates, clears, merges, growth
        drive.batch()
    assert table_of(state) != at_barrier
    columns = capture.columns()
    assert cells_of(columns) == at_barrier == cells_of(synchronous)
    if budget is not None:
        assert len(capture.spilled) > 0
    # a sketch's rows travel as the cells off their fill
    sparse = [c for _, _, comps in columns.values() for c in comps.values()
              if isinstance(c, SparseRows)]
    assert sparse and all(c.stored_nbytes < c.nbytes for c in sparse)
    if job is TumblingHll:
        assert STATE_STATS.int_table_rows > 0
        assert any(isinstance(t, slot_index.native.NativeIntTable)
                   for t in state.slot_index.tables.values())


@JOBS
@BUDGETS
def test_restore_and_continue_equals_the_uninterrupted_run(job, budget):
    whole, first = job(budget, seed=3), job(budget, seed=3)
    for _ in range(7):
        whole.batch()
        first.batch()
    handle = first.backend.capture_snapshot()
    first.batch()  # behind the barrier: must not reach the snapshot
    # resolved late, and as storage keeps it
    blobs = pickle.loads(pickle.dumps(handle.resolve()))
    resumed = job(budget, seed=3)
    resumed.backend.restore([blobs])
    assert table_of(resumed.state) == table_of_flushed(whole.state)
    # the same generator state and step as the uninterrupted run
    resumed.rng, resumed.step = whole.rng, whole.step
    twin = job(budget, seed=3)
    for _ in range(7):
        twin.batch()
    for _ in range(5):
        twin.batch()
        resumed.batch()
    a_entries, a_results = twin.read()
    b_entries, b_results = resumed.read()
    assert a_entries == b_entries
    np.testing.assert_array_equal(a_results, b_results)
    assert table_of_flushed(twin.state) == table_of_flushed(resumed.state)


def table_of_flushed(state):
    state._flush()
    return table_of(state)


def test_sparse_rows_round_trip_and_read_like_the_array():
    rng = np.random.default_rng(5)
    dense = np.zeros((50, 4, 64), np.int32)
    for row in range(50):
        at = rng.integers(0, 256, rng.integers(0, 30))
        dense[row].reshape(-1)[at] = rng.integers(1, 99, len(at))
    rows = SparseRows.from_dense(dense, 0)
    assert rows.shape == dense.shape and len(rows) == 50
    assert rows.nbytes == dense.nbytes
    np.testing.assert_array_equal(np.asarray(rows), dense)
    np.testing.assert_array_equal(rows[7], dense[7])
    pick = np.array([9, 2, 2, 41])
    np.testing.assert_array_equal(rows.take(pick).dense(), dense[pick])
    back = pickle.loads(pickle.dumps(rows))
    np.testing.assert_array_equal(back.dense(), dense)
    both = SparseRows.concatenate([rows.take(slice(0, 10)),
                                   rows.take(slice(10, 50))])
    np.testing.assert_array_equal(both.dense(), dense)


# ---- through env.execute() ------------------------------------------------

PARTS, PERIODS, PER = 4, 12, 256
GAP_MS, PERIOD_MS = 3000, 1000
WATCH = (3, 1, 4, 15)
DEPTH, WIDTH = 2, 128


class ItemCounts(CountMinSketchAggregate):
    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def filled_log(seed):
    rng = np.random.default_rng(seed)
    n = PERIODS * PER
    keys = rng.zipf(1.3, n).astype(np.int64) % 400
    items = rng.zipf(1.5, n).astype(np.int64) % 40
    ts = ((np.arange(n) // PER) * PERIOD_MS
          + 1 + ((np.arange(n) % PER) * (PERIOD_MS - 1)) // PER)
    log = ColumnarPartitionedLog(PARTS)
    for p in range(PERIODS):
        for part in range(PARTS):
            rows = slice(p * PER + part, (p + 1) * PER, PARTS)
            log.append_columns(part, {"f0": keys[rows], "f1": items[rows]},
                               ts[rows].astype(np.int64))
    arrival = np.concatenate([
        np.arange(p * PER + part, (p + 1) * PER, PARTS)
        for p in range(PERIODS) for part in range(PARTS)])
    return log, (keys[arrival], items[arrival], ts[arrival].astype(np.int64))


class GatedConsumer(ReplayableLogSource):
    """Reads half the log, then nothing until two checkpoints have
    completed since (one of them holds that half), then the rest.
    Class attributes: the executor deep-copies a function per
    attempt."""

    completed_at_half = None
    completed = 0
    snapshots = {}
    commits = []

    def emit_step(self, ctx, max_records):
        cls = type(self)
        half = PERIODS // 2 * PER // PARTS
        if min(self.offsets.values()) >= half:
            if cls.completed_at_half is None:
                cls.completed_at_half = cls.completed
            if cls.completed < cls.completed_at_half + 2:
                return True
        return super().emit_step(ctx, PER)

    def snapshot_function_state(self, checkpoint_id):
        state = super().snapshot_function_state(checkpoint_id)
        type(self).snapshots[checkpoint_id] = dict(state["offsets"])
        return state

    def notify_checkpoint_complete(self, checkpoint_id):
        parked = bool(self._pending_offset_commits)
        super().notify_checkpoint_complete(checkpoint_id)
        cls = type(self)
        cls.completed += 1
        if parked:  # (the end of the input commits the last positions
            # and drops what was parked)
            cls.commits.append((checkpoint_id,
                                dict(self.log.committed_offsets)))


def session_job(log, source, directory=None, restore=None):
    env = StreamExecutionEnvironment(Configuration().set(
        "state.backend.tpu.max-device-slots", 4096))
    env.set_state_backend("tpu")
    sink = CollectSink()

    def emit_row(key, window, vals):
        return [(key, (window.end - 1) // PERIOD_MS * PERIOD_MS,
                 window.start, window.end, *np.asarray(vals[0]).tolist())]

    windowed = (env.add_source(source).key_by(0)
                .window(EventTimeSessionWindows.with_gap(GAP_MS)))
    windowed.disable_device_operator()
    windowed.aggregate(ItemCounts(DEPTH, WIDTH, unit_weights=True,
                                  queries=WATCH),
                       window_function=emit_row).add_sink(sink)
    if directory is not None:
        env.enable_checkpointing(1, async_persist=True)
        env.set_checkpoint_storage("filesystem", str(directory), retain=1)
    if restore is not None:
        env.set_savepoint_restore(str(restore))
    return env, sink


def check_rows(rows, stream):
    cols = tuple(np.asarray(c) for c in zip(*rows))
    config = {"gap_ms": GAP_MS, "window_ms": PERIOD_MS, "depth": DEPTH,
              "width": WIDTH}
    return reference.check(config, [(0, None, lambda: (*stream, WATCH))],
                           {0: cols})


@pytest.fixture
def gated():
    GatedConsumer.completed_at_half = None
    GatedConsumer.completed = 0
    GatedConsumer.snapshots = {}
    GatedConsumer.commits = []
    faults.reset_counters()
    yield GatedConsumer
    faults.deactivate()


def test_a_task_failure_recovers_from_a_checkpoint_with_exact_totals(
        tmp_path, gated):
    log, stream = filled_log(11)
    source = gated(log, bounded=True, watermark_lag_ms=PERIOD_MS,
                   batch_per_partition=PER // PARTS)
    env, sink = session_job(log, source, tmp_path / "chk")
    env.set_restart_strategy("fixed_delay", restart_attempts=2, delay_ms=1)
    # the window task fails once, a few batches after the gate opened
    half_batches = PERIODS // 2 * PARTS
    faults.FaultInjector(1).fail_n_times(
        "task.process", 1, after=half_batches + 6).install()
    STATE_STATS.reset()
    result = env.execute("recovering-sessions")
    assert result.restarts == 1
    assert STATE_STATS.snapshot_captures > 0
    # the sink is not transactional: what fired between the checkpoint
    # and the failure came twice, the same row both times
    rows = set(sink.values)
    assert len(rows) < len(sink.values)
    verdict = check_rows(sorted(rows), stream)
    assert verdict["failed"] == 0 and not verdict["problems"], verdict
    # a commit to the log is its checkpoint's offsets
    assert gated.commits
    for cid, committed in gated.commits:
        assert committed == gated.snapshots[cid], cid
    # ... and the retained checkpoint holds state, encoded sparse
    point = load_retained_checkpoint(str(tmp_path / "chk"))
    assert point["checkpoint_id"] == max(gated.snapshots)


def test_a_job_starts_from_a_retained_checkpoint_directory(tmp_path, gated):
    log, stream = filled_log(12)
    source = gated(log, bounded=True, watermark_lag_ms=PERIOD_MS,
                   batch_per_partition=PER // PARTS)
    env, sink = session_job(log, source, tmp_path / "chk")
    env.execute("first-run")
    first = set(sink.values)
    assert check_rows(sorted(first), stream)["failed"] == 0
    point = load_retained_checkpoint(str(tmp_path / "chk"))
    offsets, watermark = None, None
    for task in point["tasks"].values():
        for snap in task["operators"].values():
            if "function" in snap:
                offsets = snap["function"]["offsets"]
            if snap.get("timers") and snap["timers"]["event"]:
                watermark = snap["timers"]["watermark"]
    assert offsets and 0 < min(offsets.values()) < log.end_offset(0)
    # a fresh job over a plain consumer, from the directory
    plain = ReplayableLogSource(log, bounded=True,
                                watermark_lag_ms=PERIOD_MS,
                                batch_per_partition=PER // PARTS)
    env2, sink2 = session_job(log, plain, restore=tmp_path / "chk")
    env2.execute("second-run")
    again = set(sink2.values)
    assert len(again) == len(sink2.values)
    # every session that fired after the checkpoint, integer for
    # integer, and nothing else
    assert again == {row for row in first if row[3] - 1 > watermark}
    assert any(row[2] <= watermark for row in again)  # open across it
    # the chk-N file itself is a valid argument too
    env3, sink3 = session_job(
        log, ReplayableLogSource(log, bounded=True,
                                 watermark_lag_ms=PERIOD_MS,
                                 batch_per_partition=PER // PARTS),
        restore=tmp_path / "chk" / f"chk-{point['checkpoint_id']}")
    env3.execute("third-run")
    assert set(sink3.values) == again


def test_with_checkpointing_off_no_snapshot_bookkeeping_runs():
    log, stream = filled_log(13)
    env, sink = session_job(log, ReplayableLogSource(
        log, bounded=True, watermark_lag_ms=PERIOD_MS,
        batch_per_partition=PER // PARTS))
    ops = []
    for node in env.get_stream_graph().nodes.values():
        def factory(inner=node.operator_factory):
            ops.append(inner())
            return ops[-1]
        node.operator_factory = factory
    STATE_STATS.reset()
    env.execute("no-checkpoints")
    assert check_rows(sink.values, stream)["failed"] == 0
    assert STATE_STATS.snapshot_captures == STATE_STATS.snapshot_tiles == 0
    assert STATE_STATS.snapshot_bytes_device == 0
    assert STATE_STATS.snapshot_bytes_written == 0
    states = [op.window_state for op in ops
              if getattr(op, "window_state", None) is not None]
    assert states and all(s._snapshot_plan is None for s in states)


# ---- host tables: a value changed in place behind the barrier ----------

@pytest.mark.parametrize("kind", ["value", "value-copy-on-write", "list"])
def test_a_value_changed_in_place_behind_the_barrier_stays_out(kind):
    """`HeapValueState.value()` hands out the stored object, and a
    join's buffer or an accumulator is changed in place and put back:
    a snapshot finished after the barrier holds the barrier's values
    all the same, whether it serialized them there or, under the
    owner's `copy_on_write` promise, held them by reference."""
    from flink_tpu.core.state import (
        ListStateDescriptor,
        ValueStateDescriptor,
    )
    descriptor = (ListStateDescriptor("h") if kind == "list"
                  else ValueStateDescriptor("h"))
    descriptor.copy_on_write = kind == "value-copy-on-write"
    backend = TpuKeyedStateBackend(FULL, MAX_PAR)
    state = backend.get_partitioned_state((), descriptor)

    def put(key, behind_the_barrier):
        backend.set_current_key(key)
        if kind == "list":
            state.add((key, behind_the_barrier))
        elif kind == "value":  # streaming/joining.py's pattern
            buf = state.value() or {}
            buf.setdefault(key % 3, []).append(behind_the_barrier)
            state.update(buf)
        else:                  # a new object every time
            state.update({**(state.value() or {}),
                          behind_the_barrier: (key,)})

    def read(from_backend):
        st = from_backend.get_partitioned_state((), descriptor)
        out = {}
        for key in range(60):
            from_backend.set_current_key(key)
            out[key] = (list(st.get() or ()) if kind == "list"
                        else st.value())
        return out

    for key in range(40):
        put(key, False)
    at_barrier = pickle.loads(pickle.dumps(read(backend)))
    handle = backend.capture_snapshot()
    for key in range(20, 60):
        put(key, True)
    assert read(backend) != at_barrier
    restored = TpuKeyedStateBackend(FULL, MAX_PAR)
    restored.get_partitioned_state((), descriptor)
    restored.restore([pickle.loads(pickle.dumps(handle.resolve()))])
    assert read(restored) == at_barrier
    # ... and resolved at once it is the same snapshot
    again = TpuKeyedStateBackend(FULL, MAX_PAR)
    again.get_partitioned_state((), descriptor)
    again.restore([backend.snapshot()])
    assert read(again) == read(backend)


class CountInPlace(ProcessFunction):
    """Per key a dict in a ValueState, changed in place."""

    def process_element(self, value, ctx, out):
        from flink_tpu.core.state import ValueStateDescriptor
        state = ctx.get_state(ValueStateDescriptor("seen"))
        seen = state.value() or {"n": 0}
        seen["n"] += 1
        state.update(seen)
        out.collect((value[0], seen["n"]))


def test_a_checkpoint_resolved_late_holds_the_barriers_values(
        tmp_path, monkeypatch):
    """Through `env.execute()`: every checkpoint's asynchronous part
    runs only after the source has read on, a task fails, and the
    restart counts every event once."""
    from flink_tpu.runtime.checkpoints import CheckpointCoordinator
    log, (keys, _, _) = filled_log(15)
    steps = []

    class Counting(ReplayableLogSource):
        def emit_step(self, ctx, max_records):
            import time
            time.sleep(0.005)  # (a dozen checkpoints in the run)
            steps.append(None)
            return super().emit_step(ctx, PER // PARTS)

    inner = CheckpointCoordinator._do_persist

    def late(self, pc):
        import time
        taken, deadline = len(steps), time.monotonic() + 5
        while len(steps) < taken + 3 and time.monotonic() < deadline:
            time.sleep(0.001)
        return inner(self, pc)
    monkeypatch.setattr(CheckpointCoordinator, "_do_persist", late)
    env = StreamExecutionEnvironment()
    env.set_state_backend("tpu")
    sink = CollectSink()
    (env.add_source(Counting(log, bounded=True, watermark_lag_ms=PERIOD_MS,
                             batch_per_partition=PER // PARTS))
        .key_by(0).process(CountInPlace()).add_sink(sink))
    env.enable_checkpointing(1, async_persist=True)
    env.set_checkpoint_storage("filesystem", str(tmp_path / "chk"), retain=1)
    env.set_restart_strategy("fixed_delay", restart_attempts=2, delay_ms=1)
    faults.reset_counters()
    faults.FaultInjector(1).fail_n_times(
        "task.process", 1, after=PERIODS // 2 * PARTS).install()
    try:
        result = env.execute("counted-in-place")
    finally:
        faults.deactivate()
    assert result.restarts == 1 and result.checkpoints_completed >= 4
    counted = {}
    for key, n in sink.values:
        counted[key] = max(counted.get(key, 0), n)
    uniq, true = np.unique(keys, return_counts=True)
    assert counted == dict(zip(uniq.tolist(), true.tolist()))


# ---- the doors a job's owner has, and the storage's pack files -------------

def test_a_job_listener_hands_over_the_client_of_the_running_job(
        tmp_path, gated):
    log, stream = filled_log(14)
    source = gated(log, bounded=True, watermark_lag_ms=PERIOD_MS,
                   batch_per_partition=PER // PARTS)
    env, sink = session_job(log, source, tmp_path / "chk")
    clients = []
    env.register_job_listener(clients.append)
    seen = []

    class Looking(type(source)):
        def emit_step(self, ctx, max_records):
            seen.append(clients[0].executor_state["coordinator"]
                        .latest_completed_id)
            return super().emit_step(ctx, max_records)
    source.__class__ = Looking
    result = env.execute("listened-to")
    assert len(clients) == 1
    # the listener had the client before the job's first step, and
    # the client shows the running job's coordinator
    assert seen[0] is None and max(c for c in seen if c) >= 2
    assert result.checkpoints_completed >= max(c for c in seen if c)
    assert check_rows(sink.values, stream)["failed"] == 0


def test_new_chunks_go_into_one_pack_file_and_rotate_out(tmp_path):
    from flink_tpu.runtime.checkpoints import FsCheckpointStorage
    from flink_tpu.state.backend import KeyedStateSnapshot
    directory = str(tmp_path / "chk")
    storage = FsCheckpointStorage(directory, retain=2)

    def tasks(version):
        # key group 0 never changes, 1 and 2 change every checkpoint
        return {(1, 0): {"operators": {"op": {"keyed": KeyedStateSnapshot(
            {0: b"steady", 1: b"one-%d" % version, 2: b"two-%d" % version}
        )}}}}
    import os
    for cid in (1, 2, 3):
        storage.persist(cid, {"timestamp": cid}, tasks(cid))
        shared = sorted(os.listdir(os.path.join(directory, "shared")))
        # one file a checkpoint, however many chunks it stored anew;
        # pack-1 stays while the chunk every checkpoint shares is in it
        assert shared == sorted({"pack-1"} | {
            f"pack-{c}" for c in range(max(1, cid - 1), cid + 1)})
    for read in (storage.latest(),
                 load_retained_checkpoint(directory),
                 FsCheckpointStorage(directory, retain=2).latest()):
        assert read["checkpoint_id"] == 3
        snap = read["tasks"][(1, 0)]["operators"]["op"]["keyed"]
        assert dict(snap.blobs()) == {0: b"steady", 1: b"one-3",
                                      2: b"two-3"}
    assert dict(load_retained_checkpoint(directory, 2)["tasks"][(1, 0)][
        "operators"]["op"]["keyed"].blobs())[1] == b"one-2"
    # a storage opened on the directory by a later process keeps the
    # books: the next rotation takes checkpoint 2's pack away
    later = FsCheckpointStorage(directory, retain=2)
    later.persist(4, {"timestamp": 4}, tasks(4))
    assert sorted(os.listdir(os.path.join(directory, "shared"))) == [
        "pack-1", "pack-3", "pack-4"]
    assert later.latest()["checkpoint_id"] == 4


# ---- the capture's programs, compiled for the chip at the cell's widths ----

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_capture_compiles_for_the_chip_at_the_session_cells_widths(
        one_chip):
    import jax
    import jax.numpy as jnp
    from flink_tpu.state.device_snapshot import SnapshotPlan
    agg = CountMinSketchAggregate(4, 2048, unit_weights=True)
    specs = agg.state_specs()
    plan = SnapshotPlan(specs, 4 * 4 * 2048 + 4)
    assert plan.classes == (16, 128, 1024) and set(plan.sparse) == {"table"}

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state = {n: shape((131072, *s.shape), s.dtype)
             for n, s in specs.items()}
    copied = {n: shape((plan.copy_rows, *s.shape), s.dtype)
              for n, s in specs.items()}
    assert plan.copy_rows == 16384  # 512 MiB of rows a buffer
    # (a compile for a described chip can be written to the persistent
    # cache but not read back: the cache is kept out of it)
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        for jit, args in [
                (plan.jit_copy, (state, shape((plan.copy_rows,), jnp.int32))),
                (plan.jit_count, (copied,)),
                (plan.jit_cells[128],
                 (copied, shape((plan.class_rows[128],), jnp.int32))),
                (plan.jit_rows,
                 (copied, shape((plan.dense_rows,), jnp.int32)))]:
            compiled = jit._jitted.lower(*args).compile()
            # a tile's scratch, not a copy of the table
            assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()

"""Timer services.

Re-designs flink-streaming-java/.../api/operators/
HeapInternalTimerService.java:43 (two priority queues of
InternalTimer(timestamp, key, namespace), advanceWatermark :276-288
draining event-time timers; here the queues order the RUNS of keys
that share a (timestamp, namespace), :class:`_TimerStore`) and
runtime/tasks/SystemProcessingTimeService.java /
TestProcessingTimeService.java.

Timers are exactly-once: registering the same (key, namespace,
timestamp) twice is a no-op; they are part of operator snapshots, keyed
per key group (ref: InternalTimerServiceSerializationProxy.java).
"""

from __future__ import annotations

import abc
import heapq
import itertools
import operator
import threading
import time as _time
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from flink_tpu import native
from flink_tpu.core.key_columns import (
    INT64_MAX,
    INT64_MIN,
    as_list,
    int_column,
    int_key,
    object_column,
)
from flink_tpu.core.keygroups import assign_to_key_group
from flink_tpu.streaming.elements import MIN_TIMESTAMP


class ProcessingTimeService(abc.ABC):
    """(ref: ProcessingTimeService.java)"""

    @abc.abstractmethod
    def get_current_processing_time(self) -> int:
        ...

    @abc.abstractmethod
    def register_timer(self, timestamp: int, callback: Callable[[int], None]):
        ...

    def shutdown(self) -> None:  # noqa: B027
        pass


class SystemProcessingTimeService(ProcessingTimeService):
    """Wall-clock timers on a scheduler thread; callbacks run under the
    owner's callback lock, mirroring how the reference fires timers
    under the checkpoint lock (SystemProcessingTimeService.java)."""

    def __init__(self, lock: Optional[threading.Lock] = None):
        self._lock = lock or threading.Lock()
        self._timers: Set[threading.Timer] = set()
        self._shutdown = False

    def get_current_processing_time(self) -> int:
        return int(_time.time() * 1000)

    def register_timer(self, timestamp: int, callback):
        delay = max(0.0, (timestamp - self.get_current_processing_time()) / 1000.0)
        t_box = []

        def fire():
            with self._lock:
                self._timers.discard(t_box[0])  # fired → drop the ref
                if not self._shutdown:
                    callback(timestamp)

        t = threading.Timer(delay, fire)
        t_box.append(t)
        t.daemon = True
        self._timers.add(t)
        t.start()
        return t

    def shutdown(self):
        with self._lock:
            self._shutdown = True
            timers = list(self._timers)
            self._timers.clear()
        for t in timers:
            t.cancel()


class PolledProcessingTimeService(ProcessingTimeService):
    """Wall-clock timers fired on the CALLER's thread via fire_due() —
    the executor loop polls it each iteration, keeping timer callbacks
    on the single-owner loop (the reference instead fires on a
    scheduler thread under the checkpoint lock,
    SystemProcessingTimeService.java)."""

    def __init__(self):
        self._queue: List[Tuple[int, int, Callable]] = []
        self._seq = 0
        # register_timer may be called from a source thread (ingestion-
        # time contexts register inside collect) while fire_due pops on
        # the executor loop — guard the heap
        self._lock = threading.Lock()

    def get_current_processing_time(self) -> int:
        return int(_time.time() * 1000)

    def register_timer(self, timestamp: int, callback):
        with self._lock:
            heapq.heappush(self._queue, (timestamp, self._seq, callback))
            self._seq += 1

    def fire_due(self) -> int:
        """Fire every timer due at the current wall clock; returns the
        number fired (loop-progress signal).  Callbacks run OUTSIDE the
        heap lock, on the caller's (executor-loop) thread."""
        now = self.get_current_processing_time()
        fired = 0
        while True:
            with self._lock:
                if not self._queue or self._queue[0][0] > now:
                    break
                ts, _, cb = heapq.heappop(self._queue)
            cb(ts)
            fired += 1
        return fired

    def fire_all_pending(self) -> None:
        """End-of-input drain for finite jobs: fire every timer
        registered at entry regardless of wall clock, bounded by the
        entry horizon so self-re-arming timers (continuous triggers)
        terminate — same contract as TestProcessingTimeService."""
        with self._lock:
            if not self._queue:
                return
            horizon = max(ts for ts, _, _ in self._queue)
        while True:
            with self._lock:
                if not self._queue or self._queue[0][0] > horizon:
                    return
                ts, _, cb = heapq.heappop(self._queue)
            cb(ts)

    def has_pending(self) -> bool:
        with self._lock:
            return bool(self._queue)


class TestProcessingTimeService(ProcessingTimeService):
    """Manually advanced clock for harness tests
    (ref: TestProcessingTimeService.java)."""

    def __init__(self):
        self._now = 0
        #: (timestamp, seq, callback) min-heap
        self._queue: List[Tuple[int, int, Callable]] = []
        self._seq = 0

    def get_current_processing_time(self) -> int:
        return self._now

    def register_timer(self, timestamp: int, callback):
        heapq.heappush(self._queue, (timestamp, self._seq, callback))
        self._seq += 1

    def set_current_time(self, now: int) -> None:
        """Advance the clock, firing due timers in order."""
        self._now = now
        while self._queue and self._queue[0][0] <= now:
            ts, _, cb = heapq.heappop(self._queue)
            cb(ts)

    def advance(self, delta: int) -> None:
        self.set_current_time(self._now + delta)

    def fire_all_pending(self) -> None:
        """Advance the clock to the latest currently-registered timer,
        firing everything due.  Timers that re-arm themselves past that
        horizon (continuous triggers) stop firing — this bounds the
        end-of-input drain of a finite job."""
        if not self._queue:
            return
        horizon = max(ts for ts, _, _ in self._queue)
        self.set_current_time(max(horizon, self._now))

    def has_pending(self) -> bool:
        return bool(self._queue)


class InternalTimer:
    __slots__ = ("timestamp", "key", "namespace")

    def __init__(self, timestamp: int, key, namespace):
        self.timestamp = timestamp
        self.key = key
        self.namespace = namespace

    def __repr__(self):
        return f"Timer({self.timestamp}, {self.key!r}, {self.namespace!r})"


def _extend(spans: list, how_many: int, seq: int) -> None:
    last = spans[-1]
    if last[0] + last[1] == seq:
        last[0] += how_many
    else:
        spans.append([how_many, seq])


def _keys(run) -> list:
    return [run[0]] if type(run) is tuple else list(run)


class _IntRun(native.NativeIntTable):
    """A run of integer keys: int64 key -> registration number in the
    native table.  Numbers only grow and the table exports its entries
    in the order they came, so its entry order is its firing order, as
    a dict run's is.  It iterates as the Python ints a dict run of the
    same keys would."""

    __slots__ = ()

    def __iter__(self):
        return iter(self.export()[0].tolist())

    def __reduce__(self):
        # (a copy or a pickle gets a table of its own, never the handle)
        return _int_run_of, self.export()


def _int_run_of(keys: np.ndarray, numbers: np.ndarray) -> _IntRun:
    run = _IntRun(len(keys))
    run.set(keys, numbers)
    return run


class _TimerStore:
    """The timers of one time domain, kept by run: the keys registered
    for ONE (timestamp, namespace) are one dict, in registration
    order.  The dict is the exactly-once contract (a key is in it
    once) and the firing order among them; the heap orders RUNS, one
    node each, not timers.  A tumbling window's 230k fire timers are
    one run and one heap node.  A process function that arms a timer
    per event has runs of one key: such a run is kept as the pair
    ``(key, number)`` until a second key joins it, which spares the
    store a dict for each.

    Every timer has a registration number, and the timers of one
    timestamp fire in the order of their numbers, across namespaces.
    A run that only ever grew by the bulk call holds its numbers in
    `spans`, ``[how many, first number]`` per stretch of consecutive
    numbers, so a bulk registration is one ``dict.update`` however
    many keys it brings (the dict's values mean nothing then).  The
    first removal of a key, a per-timer drain or a tie that has to be
    merged numbers every key on its own: the run then maps key ->
    number and has no `spans`.

    A run born from an int64 COLUMN of keys (`add_many`) is an
    `_IntRun` instead: key -> number in a native table, a batch's keys
    probed and numbered in one C call, the keys of a sweep handed on
    as the int64 column the table exports.  The same calls give it the
    keys, numbers and order a dict run gets.  The first key it cannot
    hold (a string, a tuple, a float, an int beyond int64) turns it
    into a dict, once (`int_run_demotions`); the per-timer drain reads
    it as one.

    Removing a key is a real removal and an emptied run goes, so every
    run here has a key and a heap node.  The node of a run that went
    stays behind only until such nodes outnumber the runs (then the
    heap is rebuilt from the runs), so deletes never pile up."""

    __slots__ = ("runs", "spans", "heap", "seq", "room", "int_run_demotions")

    def __init__(self):
        #: (timestamp, namespace) -> {key: number} | (key, number) | _IntRun
        self.runs: Dict[Tuple[int, Any], Any] = {}
        #: (timestamp, namespace) -> [[how many, first number], ...]
        self.spans: Dict[Tuple[int, Any], list] = {}
        #: (timestamp, the run's first number, the run's key in `runs`):
        #: the number keeps two nodes from ever comparing beyond it
        self.heap: List[Tuple[int, int, Tuple[int, Any]]] = []
        #: the next registration number
        self.seq = 0
        #: keys the integer run swept last held at its fullest: the next
        #: one is born with room for as many
        self.room = 0
        #: integer runs a key they could not hold turned into dicts
        self.int_run_demotions = 0

    def clear(self) -> None:
        self.runs.clear()
        self.spans.clear()
        del self.heap[:]

    def __len__(self) -> int:
        return sum(1 if type(run) is tuple else len(run)
                   for run in self.runs.values())

    def capture(self) -> Tuple[list, list, list]:
        """The runs as of now, in the order they were first seen, as
        three columns no later registration or removal reaches: each
        run's (timestamp, namespace), its keys frozen (`tuple(run)`:
        of a dict or an integer run its keys in registration order, of
        a run of one key the pair it is), and its type, which tells
        the two apart.  One C-level pass a column: a session job has a
        run a session."""
        runs = list(self.runs.values())
        return list(self.runs), list(map(tuple, runs)), list(map(type, runs))

    def __iter__(self):
        """Live (timestamp, key, namespace), runs in the order they
        were first seen, keys in registration order."""
        for (timestamp, namespace), run in self.runs.items():
            for key in _keys(run):
                yield timestamp, key, namespace

    def _dict(self, at, run) -> dict:
        if type(run) is tuple:
            run = self.runs[at] = {run[0]: run[1]}
        elif type(run) is _IntRun:
            keys, numbers = run.export()
            run = self.runs[at] = dict(zip(keys.tolist(), numbers.tolist()))
        return run

    def _demote(self, at, run) -> dict:
        """An integer run as a dict from now on: a key came that it
        cannot hold."""
        self.int_run_demotions += 1
        return self._dict(at, run)

    def add(self, timestamp: int, namespace, key) -> bool:
        """Register one timer; False if it is registered already."""
        at = (timestamp, namespace)
        run = self.runs.get(at)
        seq = self.seq
        if run is None:
            self.runs[at] = (key, seq)
            heapq.heappush(self.heap, (timestamp, seq, at))
        else:
            if type(run) is _IntRun:
                if type(key) is int and INT64_MIN <= key <= INT64_MAX:
                    if run.get(key) >= 0:
                        return False
                    run.put(key, seq)
                    self.seq = seq + 1
                    return True
                run = self._demote(at, run)
            run = self._dict(at, run)
            if key in run:
                return False
            run[key] = seq
            spans = self.spans.get(at)
            if spans is not None:
                _extend(spans, 1, seq)
        self.seq = seq + 1
        return True

    def add_many(self, timestamp: int, namespace, keys) -> int:
        """`keys` in row order, repeats allowed (a dict is taken for
        its keys; an integer `ndarray` is a column).  Keys that are in
        the run keep their place, the others append in first-occurrence
        order under consecutive numbers.  Returns how many timers it
        added."""
        at = (timestamp, namespace)
        run = self.runs.get(at)
        if isinstance(keys, np.ndarray) \
                and (keys.ndim != 1 or keys.dtype.kind != "i"):
            # an unsigned, float or object array: the Python objects it
            # holds, which an integer run takes or is demoted by below
            keys = keys.tolist()
        if type(run) is _IntRun and not isinstance(keys, np.ndarray):
            keys = list(keys)
            if not keys:
                return 0
            column = int_column(keys, kinds="i")
            if column is None:
                run = self._demote(at, run)
            else:
                keys = column
        if isinstance(keys, np.ndarray):
            if (run is None or type(run) is _IntRun) and native.available():
                return self._add_column(at, run, keys)
            keys = keys.tolist()
        if not isinstance(keys, dict):
            keys = dict.fromkeys(keys)
        if not keys:
            return 0
        seq = self.seq
        if run is None:
            self.runs[at] = dict(keys)
            self.spans[at] = [[len(keys), seq]]
            heapq.heappush(self.heap, (timestamp, seq, at))
            self.seq = seq + len(keys)
            return len(keys)
        spans = self.spans.get(at)
        if spans is None:
            run = self._dict(at, run)
            for key in keys:
                if key not in run:
                    run[key] = seq
                    seq += 1
            added = seq - self.seq
            self.seq = seq
            return added
        before = len(run)
        run.update(keys)
        added = len(run) - before
        if added:
            _extend(spans, added, seq)
            self.seq = seq + added
        return added

    def _add_column(self, at, run, keys: np.ndarray) -> int:
        """`add_many` of an int64 column onto an integer run (made if
        there is none): one probe, the new keys numbered in the order
        they first came."""
        if not len(keys):
            return 0
        seq = self.seq
        if run is None:
            run = self.runs[at] = _IntRun(self.room)
            heapq.heappush(self.heap, (at[0], seq, at))
        numbers, first = run.probe(np.ascontiguousarray(keys, np.int64))
        added = len(first)
        if added:
            run.assign(np.arange(seq, seq + added, dtype=np.int64), numbers)
            self.seq = seq + added
        return added

    def _numbered(self, at, run) -> dict:
        """`run` as the dict key -> registration number, which it is
        from here on."""
        spans = self.spans.pop(at, None)
        if spans is not None:
            run.update(zip(list(run), itertools.chain.from_iterable(
                range(first, first + n) for n, first in spans)))
        return self._dict(at, run)

    def _number_range(self, at, run) -> Tuple[int, int]:
        """First and last registration number of a run (numbers only
        grow, so they are those of the first and the last key)."""
        if type(run) is tuple:
            return run[1], run[1]
        if type(run) is _IntRun:
            numbers = run.export()[1]
            return int(numbers[0]), int(numbers[-1])
        spans = self.spans.get(at)
        if spans is not None:
            return spans[0][1], spans[-1][1] + spans[-1][0] - 1
        numbers = run.values()
        return next(iter(numbers)), next(reversed(numbers))

    def discard(self, timestamp: int, namespace, key) -> None:
        self.discard_many(timestamp, namespace, (key,))

    def discard_many(self, timestamp: int, namespace, keys) -> None:
        at = (timestamp, namespace)
        run = self.runs.get(at)
        if run is None:
            return
        if type(run) is _IntRun:
            if isinstance(keys, np.ndarray) and keys.dtype.kind == "i":
                column = np.ascontiguousarray(keys, np.int64)
            else:
                # each key as the int64 it equals as a dict key, if any
                column = np.fromiter(
                    (k for k in map(int_key, as_list(keys))
                     if k is not None), np.int64)
            run.lookup(column, take=True)
            if len(run):
                return
        elif type(run) is tuple:
            if run[0] not in as_list(keys):
                return
        else:
            for key in as_list(keys):
                if key in run:
                    del self._numbered(at, run)[key]
            if run:
                return
        del self.runs[at]  # its heap node is stale now
        heap = self.heap
        if len(heap) > 2 * len(self.runs) + 32:
            heap[:] = [(at[0], self._number_range(at, run)[0], at)
                       for at, run in self.runs.items()]
            heapq.heapify(heap)

    def first(self) -> Optional[int]:
        """The earliest timestamp that has a timer."""
        heap = self.heap
        while heap:
            timestamp, _, at = heap[0]
            if at in self.runs:
                return timestamp
            heapq.heappop(heap)
        return None

    def _pop_tied(self, timestamp: int, keep_nodes: bool) -> dict:
        """Take the heap nodes of `timestamp` out and return the runs
        they stand for, ``{(timestamp, namespace): run}``; with
        `keep_nodes`, put one node back for each."""
        heap = self.heap
        runs = self.runs
        nodes = {}
        while heap and heap[0][0] == timestamp:
            node = heapq.heappop(heap)
            at = node[2]
            if at in runs:
                nodes[at] = node
        if keep_nodes:
            for node in nodes.values():
                heapq.heappush(heap, node)
        return {at: runs[at] for at in nodes}

    def _by_number(self, tied: dict, exported: Optional[dict] = None) -> list:
        """``(registration number, key, (timestamp, namespace), run)``
        of every timer of the runs in `tied`, in firing order; an
        integer run in `exported` gives the numbers and keys it
        exported, every other run is numbered key by key."""
        rows = []
        for at, run in tied.items():
            if exported and at in exported:
                keys, numbers = exported[at]
                rows.extend(zip(numbers.tolist(), keys.tolist(),
                                itertools.repeat(at), itertools.repeat(run)))
            else:
                run = self._numbered(at, run)
                rows.extend((seq, key, at, run) for key, seq in run.items())
        if len(tied) > 1:
            rows.sort(key=operator.itemgetter(0))
        return rows

    def pop_runs(self, limit: int) -> List[Tuple[int, Any, Any]]:
        """Take every timer <= limit out: ``(timestamp, namespace,
        keys)`` runs in firing order, the keys a list, or of an integer
        run the int64 column it exported.  A timestamp with one
        namespace is its run, whole; runs that tie on a timestamp are
        cut where their registration numbers interleave."""
        out = []
        heap = self.heap
        while heap and heap[0][0] <= limit:
            timestamp = heap[0][0]
            tied = self._pop_tied(timestamp, keep_nodes=False)
            exported = {}
            for at, run in tied.items():
                if type(run) is _IntRun:
                    exported[at] = run.export()
                    self.room = run.peak()
            ranges = sorted(
                ((int(exported[at][1][0]), int(exported[at][1][-1]))
                 if at in exported else self._number_range(at, run))
                + (at,) for at, run in tied.items())
            if any(a[1] > b[0] for a, b in zip(ranges, ranges[1:])):
                out.extend(
                    (timestamp, at[1], [row[1] for row in rows])
                    for at, rows in itertools.groupby(
                        self._by_number(tied, exported),
                        key=operator.itemgetter(2)))
            else:
                out.extend((timestamp, at[1], exported[at][0]
                            if at in exported else _keys(tied[at]))
                           for _, _, at in ranges)
            for at in tied:
                del self.runs[at]
                self.spans.pop(at, None)
        return out

    def drain(self, limit: int, backend, on_timer) -> None:
        """Fire every timer <= limit, one ``on_timer(InternalTimer)``
        each under its key's context, in (timestamp, registration
        number) order.  The callback may register and delete timers:
        one registered <= limit fires in this drain, at the place its
        timestamp and number give it; one deleted before its turn does
        not fire."""
        heap = self.heap
        runs = self.runs
        set_current_key = backend.set_current_key
        while heap and heap[0][0] <= limit:
            node = heapq.heappop(heap)
            timestamp, _, at = node
            run = runs.get(at)
            if run is None:
                continue  # the node of a run that went
            tied = bool(heap) and heap[0][0] == timestamp
            if not tied and type(run) is tuple:
                # a timer of its own
                del runs[at]
                set_current_key(run[0])
                on_timer(InternalTimer(timestamp, run[0], at[1]))
                continue
            # the callbacks find every run with its node in the heap
            heapq.heappush(heap, node)
            rows = self._by_number(
                self._pop_tied(timestamp, True) if tied else {at: run})
            for seq, key, at, run in rows:
                if run.get(key) != seq:
                    continue  # deleted; registered anew: a later number
                del run[key]
                if not run:
                    del runs[at]
                set_current_key(key)
                on_timer(InternalTimer(timestamp, key, at[1]))
                if heap and heap[0][0] < timestamp:
                    break  # an earlier timer was registered: it goes first


class TimerRows:
    """One key group's timers inside a snapshot, as three columns
    (timestamps, keys, namespaces, the last two through the wire
    codec's column tier); it iterates as the ``(timestamp, key,
    namespace)`` tuples a list of them would.  A checkpoint's storage
    walks a snapshot for shared chunks: there are none in here, and it
    is told so instead of visiting a tuple per timer."""

    __slots__ = ("stamps", "keys", "namespaces")

    def __init__(self, stamps, keys, namespaces):
        from flink_tpu.state.backend import encode_obj_column
        self.stamps = np.asarray(stamps, np.int64)
        self.keys = encode_obj_column(keys)
        # windows are tuples of ints, all of one length: one int64
        # array (numpy infers int64 for nothing else: a float, a bool,
        # a longer int or a ragged row gives another dtype or shape)
        rows = np.array(namespaces) if len(namespaces) else None
        if rows is not None and rows.dtype == np.int64 and rows.ndim == 2:
            self.namespaces = ("int-tuples", rows)
        else:
            self.namespaces = encode_obj_column(namespaces)

    def __len__(self) -> int:
        return len(self.stamps)

    def __iter__(self):
        from flink_tpu.state.backend import decode_obj_column
        n = len(self.stamps)
        namespaces = (map(tuple, self.namespaces[1].tolist())
                      if self.namespaces[0] == "int-tuples"
                      else decode_obj_column(self.namespaces, n))
        return zip(self.stamps.tolist(), decode_obj_column(self.keys, n),
                   namespaces)

    def __eq__(self, other):
        return list(self) == list(other)

    def __getstate__(self):
        return (self.stamps, self.keys, self.namespaces)

    def __setstate__(self, state):
        self.stamps, self.keys, self.namespaces = state

    def _map_chunks_(self, fn):
        return self


def _runs_by_key_group(captured: Tuple[list, list, list],
                       max_parallelism: int) -> Dict[int, "TimerRows"]:
    """`(timestamp, key, namespace)` of every timer of the captured
    runs (`_TimerStore.capture`), per key group, in the store's order:
    one vectorized hash of the key column."""
    from flink_tpu.state.heap_backend import split_column_by_key_group
    stamps, keys, namespaces = [], [], []
    for (timestamp, namespace), run, kind in zip(*captured):
        if kind is tuple:  # (key, registration number)
            keys.append(run[0])
            stamps.append(timestamp)
            namespaces.append(namespace)
            continue
        keys.extend(run)
        stamps.extend(itertools.repeat(timestamp, len(run)))
        namespaces.extend(itertools.repeat(namespace, len(run)))
    n = len(keys)
    stamps = np.array(stamps, np.int64)
    key_column, ns_column = object_column(keys, n), \
        object_column(namespaces, n)
    return {kg: TimerRows(stamps[sel], key_column[sel].tolist(),
                          ns_column[sel].tolist())
            for kg, sel in split_column_by_key_group(keys, max_parallelism)}


class InternalTimerService:
    """Keyed event-time + processing-time timers for one operator
    (ref: HeapInternalTimerService.java), both kept in a
    :class:`_TimerStore`.

    A timer deleted and registered again is a new timer: it fires
    after every timer of its timestamp that was registered before the
    second registration (where the heap of one node per timer that
    this replaced took the stale node for the live one and fired it
    at its first place)."""

    def __init__(self, name: str, keyed_backend, processing_time_service: ProcessingTimeService,
                 triggerable):
        self.name = name
        self._backend = keyed_backend
        self._pts = processing_time_service
        #: the operator: has on_event_time(timer) / on_processing_time(timer)
        self._triggerable = triggerable
        self.current_watermark = MIN_TIMESTAMP
        self._event = _TimerStore()
        self._proc = _TimerStore()
        self._next_proc_registered: Optional[int] = None

    # ---- registration (key = backend's current key) -----------------
    def register_event_time_timer(self, namespace, timestamp: int) -> None:
        self._event.add(timestamp, namespace, self._backend.current_key)

    def register_event_time_timers_bulk(self, namespace, timestamp: int,
                                        keys) -> int:
        """Register the same (namespace, timestamp) timer for MANY keys
        without touching the backend's current-key context: one update
        of that run with the keys in row order (repeats allowed; a
        dict is taken for its keys; an int64 column keeps the run in a
        native table).  Per key as register_event_time_timer: a key
        that has the timer keeps its place, the others follow in
        first-occurrence order.  Returns how many timers it added."""
        return self._event.add_many(timestamp, namespace, keys)

    def delete_event_time_timer(self, namespace, timestamp: int) -> None:
        self._event.discard(timestamp, namespace, self._backend.current_key)

    def delete_event_time_timers_bulk(self, namespace, timestamp: int,
                                      keys) -> None:
        """delete_event_time_timer for many keys of one (namespace,
        timestamp), the backend's current-key context untouched: the
        batched fire drops every cleaned window's trigger timers in
        one call, which finds no run where they fired already."""
        self._event.discard_many(timestamp, namespace, keys)

    def register_event_time_timers_rows(self, rows) -> None:
        """register_event_time_timer for `rows` of (namespace,
        timestamp, key), in their order, the backend's current-key
        context untouched: the batched session ingest registers every
        window that grew under its key's own namespace."""
        add = self._event.add
        for namespace, timestamp, key in rows:
            add(timestamp, namespace, key)

    def delete_event_time_timers_rows(self, rows) -> None:
        """delete_event_time_timer for `rows` of (namespace,
        timestamp, key)."""
        discard = self._event.discard
        for namespace, timestamp, key in rows:
            discard(timestamp, namespace, key)

    def register_processing_time_timer(self, namespace, timestamp: int) -> None:
        if self._proc.add(timestamp, namespace, self._backend.current_key):
            self._arm_processing_time(timestamp)

    def _arm_processing_time(self, timestamp: int) -> None:
        if self._next_proc_registered is None or timestamp < self._next_proc_registered:
            self._next_proc_registered = timestamp
            self._pts.register_timer(timestamp, self._on_processing_time)

    def delete_processing_time_timer(self, namespace, timestamp: int) -> None:
        self._proc.discard(timestamp, namespace, self._backend.current_key)

    @property
    def int_run_demotions(self) -> int:
        """Integer runs that a key they could not hold turned into
        dicts (`_TimerStore`)."""
        return self._event.int_run_demotions + self._proc.int_run_demotions

    def num_event_time_timers(self) -> int:
        return len(self._event)

    def num_processing_time_timers(self) -> int:
        return len(self._proc)

    def event_time_timers(self):
        """Every live event-time timer as (timestamp, key, namespace)."""
        return iter(self._event)

    def processing_time_timers(self):
        return iter(self._proc)

    # ---- firing -----------------------------------------------------
    def advance_watermark(self, watermark: int) -> None:
        """Fire all event-time timers <= watermark
        (ref: HeapInternalTimerService.advanceWatermark :276-288)."""
        self.current_watermark = watermark
        self._event.drain(watermark, self._backend,
                          self._triggerable.on_event_time)

    def pop_due_event_time_timers(
            self, watermark: int) -> List[Tuple[int, Any, Any]]:
        """Bulk sweep: take EVERY due event-time timer <= watermark
        and return them as runs ``(timestamp, namespace, keys)``, the
        keys a list or an int64 column (`_TimerStore.pop_runs`); the
        runs end to end are the exact order advance_watermark would
        have fired them in.  The watermark advances exactly as
        advance_watermark does; FIRING is the caller's job.

        Contract: only valid when the caller's timer callbacks would
        not have registered NEW timers <= watermark mid-drain (the
        batched window fire path qualifies: the default
        EventTimeTrigger registers nothing from on_event_time) — a
        timer registered during the sweep's processing fires on the
        NEXT watermark instead of the current one."""
        self.current_watermark = watermark
        return self._event.pop_runs(watermark)

    def _on_processing_time(self, fired_at: int) -> None:
        self._next_proc_registered = None
        self._proc.drain(self._pts.get_current_processing_time(),
                         self._backend, self._triggerable.on_processing_time)
        nxt = self._proc.first()
        if nxt is not None:
            self._arm_processing_time(nxt)

    # ---- snapshot (timers are state, keyed per key group) -----------
    def snapshot(self) -> dict:
        return {"watermark": self.current_watermark,
                "event": self._by_key_group(self._event),
                "proc": self._by_key_group(self._proc)}

    def _by_key_group(self, store: _TimerStore) -> Dict[int, list]:
        mp = self._backend.max_parallelism
        per_kg: Dict[int, list] = {}
        for ts, key, namespace in store:
            per_kg.setdefault(assign_to_key_group(key, mp), []).append(
                (ts, key, namespace))
        return per_kg

    def capture_snapshot(self):
        """`snapshot` in two parts, for an operator whose keyed
        backend finishes its own after the barrier: here a copy of
        each store's runs (`_TimerStore.capture`); the handle's
        `resolve()` cuts them by key group, a `TimerRows` each, which
        `restore` reads as it reads a list of tuples."""
        from flink_tpu.state.backend import DeferredSnapshot
        watermark, mp = self.current_watermark, self._backend.max_parallelism
        event, proc = self._event.capture(), self._proc.capture()
        return DeferredSnapshot(lambda: {
            "watermark": watermark,
            "event": _runs_by_key_group(event, mp),
            "proc": _runs_by_key_group(proc, mp)})

    def restore(self, snapshots: List[dict]) -> None:
        self._event.clear()
        self._proc.clear()
        rng = self._backend.key_group_range
        for snap in snapshots:
            for kg, timers in snap.get("event", {}).items():
                if rng.contains(kg):
                    for ts, key, namespace in timers:
                        self._event.add(ts, namespace, key)
            for kg, timers in snap.get("proc", {}).items():
                if rng.contains(kg):
                    for ts, key, namespace in timers:
                        if self._proc.add(ts, namespace, key):
                            self._arm_processing_time(ts)

"""Partitioned, replayable log — the broker the connector tests run
against.

Models the contract the reference's Kafka connector consumes
(flink-connectors/flink-connector-kafka-base/.../FlinkKafkaConsumerBase
.java:83): numbered partitions of append-only records addressed by
offset, re-readable from any offset, with a committed-offsets side
channel (the consumer-group offset commit that Flink performs on
checkpoint completion, `pendingOffsetsToCommit` :160,756).

Three implementations: in-memory (unit tests, single process),
file-backed JSON-lines (survives process exit — the durability tier
the recovery tests need) and in-memory columnar (a producer appends
array chunks, a consumer reads them back as zero-copy slices: the
form a vectorized source hands on as one ``RecordBatch``).  All are
thread-safe: test feeders append from their own threads while the
executor loop reads.
"""

from __future__ import annotations

import bisect
import json
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


class PartitionedLog:
    """Log contract: (offset, timestamp, value) records per partition."""

    def __deepcopy__(self, memo):
        """A log is an external-system handle (the broker): deep-copying
        a source function per subtask must NOT clone the log, or
        subtasks would read private snapshots and never see appends."""
        return self

    @property
    def num_partitions(self) -> int:
        raise NotImplementedError

    def append(self, partition: int, value: Any,
               timestamp: Optional[int] = None) -> int:
        """Returns the record's offset."""
        raise NotImplementedError

    def append_keyed(self, key, value, timestamp: Optional[int] = None) -> int:
        """Route by key hash, like a keyed Kafka producer."""
        return self.append(hash(key) % self.num_partitions, value, timestamp)

    def read(self, partition: int, offset: int,
             max_records: int) -> List[Tuple[int, Optional[int], Any]]:
        """Records from `offset` (inclusive), at most `max_records`."""
        raise NotImplementedError

    def read_columns(self, partition: int, offset: int, max_records: int
                     ) -> Optional[Tuple[int, np.ndarray,
                                         Dict[str, np.ndarray]]]:
        """The records `read` would return, as columns: ``(first
        offset, int64 timestamps, {name: column})``, at most
        `max_records` rows (none at the head of the log: empty
        columns).  ``None`` from a log that holds no columns: the
        consumer then reads records."""
        return None

    def end_offset(self, partition: int) -> int:
        raise NotImplementedError

    def commit_offsets(self, offsets: Dict[int, int]) -> None:
        """Consumer-group offset commit (observable by tests)."""
        raise NotImplementedError

    @property
    def committed_offsets(self) -> Dict[int, int]:
        raise NotImplementedError

    def append_transaction(self, txn_id,
                           records: List[Tuple[int, Optional[int], Any]]) -> bool:
        """Atomically append `records` ([(partition, timestamp, value)])
        exactly once per txn_id — the idempotent-commit contract of
        TwoPhaseCommitSinkFunction (ref: FlinkKafkaProducer011.java:94,
        Kafka transactions).  Returns False on duplicate replay."""
        raise NotImplementedError

    def all_values(self, partition: Optional[int] = None) -> List[Any]:
        raise NotImplementedError


class InMemoryPartitionedLog(PartitionedLog):
    def __init__(self, num_partitions: int = 1):
        self._n = num_partitions
        self._parts: List[List[Tuple[Optional[int], Any]]] = [
            [] for _ in range(num_partitions)]
        self._committed: Dict[int, int] = {}
        self._committed_txns: set = set()
        self._lock = threading.Lock()

    @property
    def num_partitions(self) -> int:
        return self._n

    def append(self, partition, value, timestamp=None) -> int:
        with self._lock:
            part = self._parts[partition]
            part.append((timestamp, value))
            return len(part) - 1

    def read(self, partition, offset, max_records):
        with self._lock:
            part = self._parts[partition]
            return [(offset + i, ts, v)
                    for i, (ts, v) in enumerate(part[offset:offset + max_records])]

    def end_offset(self, partition) -> int:
        with self._lock:
            return len(self._parts[partition])

    def commit_offsets(self, offsets):
        with self._lock:
            self._committed.update(offsets)

    @property
    def committed_offsets(self):
        with self._lock:
            return dict(self._committed)

    # ---- transactional producer side (Kafka-0.11 analogue) ----------
    def append_transaction(self, txn_id, records) -> bool:
        with self._lock:
            if txn_id in self._committed_txns:
                return False
            self._committed_txns.add(txn_id)
            for partition, ts, v in records:
                self._parts[partition].append((ts, v))
            return True

    def all_values(self, partition: Optional[int] = None) -> List[Any]:
        with self._lock:
            parts = (self._parts if partition is None
                     else [self._parts[partition]])
            return [v for p in parts for (_ts, v) in p]


class FilePartitionedLog(PartitionedLog):
    """JSON-lines file per partition under `directory` — records and
    committed offsets survive process exit (the cross-restart
    durability tier; ref: Kafka's on-disk log, reduced to what the
    recovery tests exercise)."""

    def __init__(self, directory: str, num_partitions: int = 1):
        self.directory = directory
        self._n = num_partitions
        self._lock = threading.Lock()
        self._txn_cache = None  # lazy: committed txn ids
        os.makedirs(directory, exist_ok=True)
        #: cached records per partition (files are append-only)
        self._cache: List[List[Tuple[Optional[int], Any]]] = [
            [] for _ in range(num_partitions)]
        for p in range(num_partitions):
            path = self._part_path(p)
            if os.path.exists(path):
                with open(path) as f:
                    for line in f:
                        ts, v = json.loads(line)
                        self._cache[p].append((ts, v))

    def _part_path(self, p: int) -> str:
        return os.path.join(self.directory, f"part-{p}.jsonl")

    def _offsets_path(self) -> str:
        return os.path.join(self.directory, "committed-offsets.json")

    @property
    def num_partitions(self) -> int:
        return self._n

    def append(self, partition, value, timestamp=None) -> int:
        with self._lock:
            with open(self._part_path(partition), "a") as f:
                f.write(json.dumps([timestamp, value]) + "\n")
            self._cache[partition].append((timestamp, value))
            return len(self._cache[partition]) - 1

    def read(self, partition, offset, max_records):
        with self._lock:
            part = self._cache[partition]
            return [(offset + i, ts, v)
                    for i, (ts, v) in enumerate(part[offset:offset + max_records])]

    def end_offset(self, partition) -> int:
        with self._lock:
            return len(self._cache[partition])

    def commit_offsets(self, offsets):
        with self._lock:
            current = self.committed_offsets_unlocked()
            current.update({str(k): v for k, v in offsets.items()})
            tmp = self._offsets_path() + ".part"
            with open(tmp, "w") as f:
                json.dump(current, f)
            os.replace(tmp, self._offsets_path())

    def committed_offsets_unlocked(self) -> dict:
        if not os.path.exists(self._offsets_path()):
            return {}
        with open(self._offsets_path()) as f:
            return json.load(f)

    @property
    def committed_offsets(self):
        with self._lock:
            return {int(k): v for k, v in self.committed_offsets_unlocked().items()}

    def _txns_path(self) -> str:
        return os.path.join(self.directory, "committed-txns.jsonl")

    def _seen_txns(self) -> set:
        """Cached committed-txn ids (append-only file, loaded once)."""
        if self._txn_cache is None:
            self._txn_cache = set()
            if os.path.exists(self._txns_path()):
                with open(self._txns_path()) as f:
                    self._txn_cache = {line.strip() for line in f}
        return self._txn_cache

    def append_transaction(self, txn_id, records) -> bool:
        with self._lock:
            seen = self._seen_txns()
            if str(txn_id) in seen:
                return False
            seen.add(str(txn_id))
            for partition, ts, v in records:
                with open(self._part_path(partition), "a") as f:
                    f.write(json.dumps([ts, v]) + "\n")
                self._cache[partition].append((ts, v))
            # record the txn id LAST: a crash mid-append re-appends on
            # replay (at-least-once within the commit itself, like a
            # file sink's truncate-on-recovery would be needed for
            # stronger guarantees)
            with open(self._txns_path(), "a") as f:
                f.write(f"{txn_id}\n")
            return True

    def all_values(self, partition: Optional[int] = None) -> List[Any]:
        with self._lock:
            parts = (self._cache if partition is None
                     else [self._cache[partition]])
            return [v for p in parts for (_ts, v) in p]


class ColumnarPartitionedLog(PartitionedLog):
    """In-memory log whose partitions are appended array chunks: a
    record is one row of named columns with an int64 timestamp, its
    value the tuple of its fields in column order (the cell itself
    where the one column is named ``"v"``: ``RecordBatch``'s
    convention).  `read_columns`
    answers with slices of the chunks as they were appended (no copy;
    the producer must not write into an array it has appended); `read`
    boxes the same rows for a per-record consumer.  A read never
    crosses a chunk: it returns the rows from `offset` to the end of
    the chunk that holds it, `max_records` at most."""

    def __init__(self, num_partitions: int = 1):
        self._n = num_partitions
        #: per partition: the chunks, and the offset each starts at
        self._chunks: List[List[Tuple[np.ndarray, Dict[str, np.ndarray]]]] \
            = [[] for _ in range(num_partitions)]
        self._starts: List[List[int]] = [[] for _ in range(num_partitions)]
        self._ends = [0] * num_partitions
        self._names: Optional[Tuple[str, ...]] = None
        self._committed: Dict[int, int] = {}
        self._lock = threading.Lock()

    @property
    def num_partitions(self) -> int:
        return self._n

    def append_columns(self, partition: int, columns: Dict[str, np.ndarray],
                       timestamps) -> int:
        """Append one chunk of rows; returns the first row's offset."""
        ts = np.asarray(timestamps, np.int64)
        cols = {name: np.asarray(col) for name, col in columns.items()}
        if any(len(col) != len(ts) for col in cols.values()):
            raise ValueError("columns and timestamps differ in length")
        with self._lock:
            if self._names is None:
                self._names = tuple(cols)
            elif tuple(cols) != self._names:
                raise ValueError(f"columns {tuple(cols)}, the log holds "
                                 f"{self._names}")
            first = self._ends[partition]
            if len(ts):
                self._chunks[partition].append((ts, cols))
                self._starts[partition].append(first)
                self._ends[partition] = first + len(ts)
            return first

    def append(self, partition, value, timestamp=None) -> int:
        """One record: `value` is the tuple of its fields, or a
        scalar (the one column ``"v"``)."""
        if timestamp is None:
            raise ValueError("a columnar log's records carry timestamps")
        if isinstance(value, tuple):
            fields = value
            names = self._names or tuple(f"f{i}" for i in range(len(fields)))
        else:
            fields, names = (value,), self._names or ("v",)
        return self.append_columns(
            partition, {name: np.array([field])
                        for name, field in zip(names, fields)}, [timestamp])

    def read_columns(self, partition, offset, max_records):
        with self._lock:
            starts = self._starts[partition]
            if offset >= self._ends[partition] or max_records <= 0:
                names = self._names or ()
                return offset, np.zeros(0, np.int64), \
                    {name: np.zeros(0, np.int64) for name in names}
            # the chunk that holds `offset`: the last that starts <= it
            lo = bisect.bisect_right(starts, offset) - 1
            ts, cols = self._chunks[partition][lo]
            at = offset - starts[lo]
            rows = slice(at, at + max_records)
            return offset, ts[rows], {name: col[rows]
                                      for name, col in cols.items()}

    def read(self, partition, offset, max_records):
        first, ts, cols = self.read_columns(partition, offset, max_records)
        if not len(ts):
            return []
        fields = [col.tolist() for col in cols.values()]
        values = fields[0] if tuple(cols) == ("v",) else list(zip(*fields))
        return [(first + i, t, v)
                for i, (t, v) in enumerate(zip(ts.tolist(), values))]

    def end_offset(self, partition) -> int:
        with self._lock:
            return self._ends[partition]

    def commit_offsets(self, offsets):
        with self._lock:
            self._committed.update(offsets)

    @property
    def committed_offsets(self):
        with self._lock:
            return dict(self._committed)

    def all_values(self, partition: Optional[int] = None) -> List[Any]:
        parts = range(self._n) if partition is None else [partition]
        out: List[Any] = []
        for p in parts:
            offset = 0
            while offset < self.end_offset(p):
                records = self.read(p, offset, 1 << 30)
                out.extend(v for _, _, v in records)
                offset += len(records)
        return out

"""Key groups: max-parallelism-granular sharding of keyed state.

Re-designs the reference's key-group machinery
(flink-runtime/.../state/KeyGroupRangeAssignment.java:30-115,
KeyGroupRange.java) with one TPU-first addition: all assignment
functions have vectorized numpy twins (``assign_key_groups_np``) so the
micro-batcher can bucket a whole record batch into key groups without a
Python loop, and a stable 64-bit record hash (``stable_hash64``) used
both host-side (numpy) and device-side (flink_tpu.ops.hashing) so host
bucketing and device probing agree.
"""

from __future__ import annotations

import math
from typing import Any, Iterator, Tuple

import numpy as np

DEFAULT_LOWER_BOUND_MAX_PARALLELISM = 128
UPPER_BOUND_MAX_PARALLELISM = 1 << 15  # 32768 (ref: KeyGroupRangeAssignment.java:30-33)


def murmur_hash(code: int) -> int:
    """MurmurHash3 32-bit finalizer over an int
    (ref: flink-core/.../util/MathUtils.java murmurHash, used by
    KeyGroupRangeAssignment.java:58-70)."""
    h = code & 0xFFFFFFFF
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    h ^= h >> 16
    return h


def stable_hash64(key: Any) -> int:
    """Deterministic 64-bit hash of an arbitrary (hashable) key.

    Python's ``hash`` is salted per-process for str/bytes, which would
    make checkpoints non-portable; instead use FNV-1a over the repr for
    strings/bytes and a splitmix64 finalizer for ints.  Must stay in
    sync with the device-side hashing in flink_tpu/ops/hashing.py for
    integer keys.
    """
    if isinstance(key, (int, np.integer)):
        return splitmix64(int(key))
    if isinstance(key, str):
        key = key.encode("utf-8")
    if isinstance(key, bytes):
        h = 0xCBF29CE484222325
        for b in key:
            h ^= b
            h = (h * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
        # finalize so short strings spread over high bits too
        return splitmix64(h)
    if isinstance(key, tuple):
        h = 0x345678
        for item in key:
            h = splitmix64(h ^ stable_hash64(item))
        return h
    if isinstance(key, float):
        # NaN/inf are valid keys; int(key) would raise on them
        if math.isfinite(key) and key == int(key):
            return splitmix64(int(key))
        return splitmix64(hash(key) & 0xFFFFFFFFFFFFFFFF)
    if key is None:
        return splitmix64(0x9E3779B97F4A7C15)
    if isinstance(key, bool):
        return splitmix64(int(key))
    return splitmix64(hash(key) & 0xFFFFFFFFFFFFFFFF)


def splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return x ^ (x >> 31)


def splitmix64_np(x: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 over uint64 arrays (host twin of the
    device kernel in flink_tpu/ops/hashing.py)."""
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def hash_int_column_np(col: np.ndarray) -> np.ndarray:
    """``stable_hash64`` of every element of a one-dimensional integer
    column, in one pass: the native splitmix64 when the host runtime
    is loaded, ``splitmix64_np`` otherwise.  A signed column is
    reinterpreted as uint64, which wraps a negative value as the
    scalar hash's ``& 0xFFFFFFFFFFFFFFFF`` does."""
    try:
        import flink_tpu.native as nat
        if nat.available():
            return nat.splitmix64(col.astype(np.uint64, copy=False))
    except Exception:  # noqa: BLE001 — numpy twin below
        pass
    return splitmix64_np(col.astype(np.uint64))


def stable_hashes_np(keys) -> np.ndarray:
    """64-bit stable hash per key, EXACTLY matching ``stable_hash64`` —
    the scalar routing/assignment path.  All-int key columns vectorize
    fully (splitmix64 over an int64 array is the same masked arithmetic
    as the scalar hash); anything else hashes per key in Python with
    only the downstream murmur+index math vectorized.  NOTE: the 2-D
    tuple combine in ``native.vectorized.hash_keys_np`` intentionally
    differs from ``stable_hash64(tuple)`` and must never be used for
    routing or key-group assignment — keyed state would land on the
    wrong subtask."""
    n = len(keys)
    for k in keys:
        if type(k) is not int:
            return np.fromiter((stable_hash64(k) for k in keys),
                               np.uint64, n)
    try:
        arr = np.array(keys, np.int64)
    except OverflowError:
        return np.fromiter((stable_hash64(k) for k in keys), np.uint64, n)
    return splitmix64_np(arr)


def assign_to_key_group(key: Any, max_parallelism: int) -> int:
    """key → key group (ref: KeyGroupRangeAssignment.java:58-70:
    ``murmurHash(key.hashCode()) % maxParallelism``)."""
    return murmur_hash(stable_hash64(key) & 0xFFFFFFFF) % max_parallelism


def assign_key_groups_np(hashes64: np.ndarray, max_parallelism: int) -> np.ndarray:
    """Vectorized key-group assignment from precomputed 64-bit hashes."""
    h = (hashes64 & np.uint64(0xFFFFFFFF)).astype(np.uint64)
    h ^= h >> np.uint64(16)
    with np.errstate(over="ignore"):
        h = (h * np.uint64(0x85EBCA6B)) & np.uint64(0xFFFFFFFF)
        h ^= h >> np.uint64(13)
        h = (h * np.uint64(0xC2B2AE35)) & np.uint64(0xFFFFFFFF)
    h ^= h >> np.uint64(16)
    return (h % np.uint64(max_parallelism)).astype(np.int32)


def assign_operator_indexes_np(hashes64: np.ndarray,
                               max_parallelism: int,
                               parallelism: int) -> np.ndarray:
    """Vectorized hash -> key group -> operator subtask index (the
    twin of assign_key_groups_np + compute_operator_index_for_key_group
    and of the C++ ft_key_groups kernel — ONE place for the range
    arithmetic)."""
    kg = assign_key_groups_np(hashes64, max_parallelism)
    return (kg.astype(np.int64) * parallelism
            // max_parallelism).astype(np.int32)


def compute_operator_index_for_key_group(
    max_parallelism: int, parallelism: int, key_group: int
) -> int:
    """key group → operator subtask index (range partition)
    (ref: KeyGroupRangeAssignment.java:115)."""
    return key_group * parallelism // max_parallelism


def assign_key_to_parallel_operator(key: Any, max_parallelism: int, parallelism: int) -> int:
    return compute_operator_index_for_key_group(
        max_parallelism, parallelism, assign_to_key_group(key, max_parallelism))


def compute_key_group_range_for_operator_index(
    max_parallelism: int, parallelism: int, operator_index: int
) -> "KeyGroupRange":
    """operator subtask → contiguous range of key groups
    (ref: KeyGroupRangeAssignment.java:47-56)."""
    start = (operator_index * max_parallelism + parallelism - 1) // parallelism
    end = ((operator_index + 1) * max_parallelism - 1) // parallelism
    return KeyGroupRange(start, end)


def compute_default_max_parallelism(parallelism: int) -> int:
    """(ref: KeyGroupRangeAssignment.java:120-130: round up to power of
    two of 1.5×parallelism, clamped to [128, 32768])."""
    bound = min(
        max(round_up_to_power_of_two(parallelism + parallelism // 2),
            DEFAULT_LOWER_BOUND_MAX_PARALLELISM),
        UPPER_BOUND_MAX_PARALLELISM,
    )
    return bound


def round_up_to_power_of_two(x: int) -> int:
    if x <= 1:
        return 1
    return 1 << (x - 1).bit_length()


class KeyGroupRange:
    """Inclusive range [start, end] of key groups
    (ref: flink-runtime/.../state/KeyGroupRange.java)."""

    __slots__ = ("start_key_group", "end_key_group")

    EMPTY: "KeyGroupRange"

    def __init__(self, start: int, end: int):
        if start > end:
            # normalized empty range
            self.start_key_group = 0
            self.end_key_group = -1
        else:
            self.start_key_group = start
            self.end_key_group = end

    @property
    def number_of_key_groups(self) -> int:
        return max(0, self.end_key_group - self.start_key_group + 1)

    def contains(self, key_group: int) -> bool:
        return self.start_key_group <= key_group <= self.end_key_group

    def get_intersection(self, other: "KeyGroupRange") -> "KeyGroupRange":
        return KeyGroupRange(
            max(self.start_key_group, other.start_key_group),
            min(self.end_key_group, other.end_key_group),
        )

    def __iter__(self) -> Iterator[int]:
        return iter(range(self.start_key_group, self.end_key_group + 1))

    def __len__(self) -> int:
        return self.number_of_key_groups

    def __contains__(self, kg: int) -> bool:
        return self.contains(kg)

    def __eq__(self, other):
        return (isinstance(other, KeyGroupRange)
                and self.start_key_group == other.start_key_group
                and self.end_key_group == other.end_key_group)

    def __hash__(self):
        return hash((self.start_key_group, self.end_key_group))

    def __repr__(self):
        return f"KeyGroupRange[{self.start_key_group}, {self.end_key_group}]"

    @staticmethod
    def of(start: int, end: int) -> "KeyGroupRange":
        return KeyGroupRange(start, end)


KeyGroupRange.EMPTY = KeyGroupRange(0, -1)


class KeyGroupRangeOffsets:
    """Maps each key group in a range to an offset in a snapshot stream
    (ref: flink-runtime/.../state/KeyGroupRangeOffsets.java)."""

    def __init__(self, key_group_range: KeyGroupRange):
        self.key_group_range = key_group_range
        self._offsets = [0] * key_group_range.number_of_key_groups

    def set_key_group_offset(self, key_group: int, offset: int) -> None:
        self._offsets[self._index(key_group)] = offset

    def get_key_group_offset(self, key_group: int) -> int:
        return self._offsets[self._index(key_group)]

    def _index(self, key_group: int) -> int:
        if not self.key_group_range.contains(key_group):
            raise KeyError(f"key group {key_group} not in {self.key_group_range}")
        return key_group - self.key_group_range.start_key_group

    def __iter__(self) -> Iterator[Tuple[int, int]]:
        for kg in self.key_group_range:
            yield kg, self.get_key_group_offset(kg)


def make_key_group_keep_fn(max_parallelism: int, num_subtasks: int,
                           subtask_index: int):
    """Vectorized ownership filter for rescaled state restores: keys
    (any array hash_keys_np accepts — integer bit-patterns or word
    arrays) → bool mask of the keys whose key group routes to
    `subtask_index`.  ONE definition shared by every engine-carrying
    operator so restored state and live-record routing can never
    disagree (ref: KeyGroupRangeAssignment + StateAssignmentOperation's
    re-split).  None when a single subtask owns everything."""
    if num_subtasks <= 1:
        return None

    def keep(keys):
        from flink_tpu.streaming.vectorized import hash_keys_np
        kh = hash_keys_np(np.asarray(keys))
        try:
            import flink_tpu.native as nat
            tgt = nat.key_groups(kh, max_parallelism, num_subtasks)
        except Exception:  # noqa: BLE001 — numpy twin
            tgt = assign_operator_indexes_np(kh, max_parallelism,
                                             num_subtasks)
        return tgt == subtask_index

    return keep

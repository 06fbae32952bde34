"""Vectorized tumbling-window engine: differential tests vs the
per-record heap baseline and the scalar WindowOperator."""

import numpy as np
import pytest

from flink_tpu.ops.device_agg import CountAggregate, SumAggregate
from flink_tpu.ops.sketches import HyperLogLogAggregate
from flink_tpu.streaming.vectorized import (
    ScalarHeapTumblingWindows,
    VectorizedSlotIndex,
    VectorizedTumblingWindows,
    hash_keys_np,
)


def test_slot_index_dedup_and_persistence():
    idx = VectorizedSlotIndex()
    allocated = []

    def alloc(n):
        start = sum(len(a) for a in allocated)
        arr = np.arange(start, start + n)
        allocated.append(arr)
        return arr

    h = np.array([5, 3, 5, 9, 3], np.uint64)
    slots, new, first = idx.lookup_or_insert(h, alloc)
    # same hash → same slot within batch
    assert slots[0] == slots[2] and slots[1] == slots[4]
    assert len(set(slots.tolist())) == 3
    # second batch: all found, no new allocations
    slots2, new2, _ = idx.lookup_or_insert(np.array([3, 9], np.uint64), alloc)
    assert not new2.any()
    assert slots2[0] == slots[1] and slots2[1] == slots[3]


def test_full_arena_fire_matches_heap():
    """The full-arena fire fast path (one fused full-state reduce +
    host index + state rebuild) must emit identical results to the
    scalar baseline — and must actually trigger: one live window whose
    slots are >= capacity/4."""
    rng = np.random.default_rng(11)
    n = 4000
    keys = rng.integers(0, 300, n)
    ts = rng.integers(0, 1000, n)  # ONE tumbling window
    vals = rng.random(n).astype(np.float32)

    vec = VectorizedTumblingWindows(SumAggregate(np.float32), 1000,
                                    initial_capacity=512)
    heap = ScalarHeapTumblingWindows(SumAggregate(np.float32), 1000)
    vec.process_batch(keys, ts, vals)
    for i in range(n):
        heap.process(int(keys[i]), int(ts[i]), float(vals[i]))
    vec.flush()
    # pin the fast-path precondition before firing
    slots = vec.windows[0].all_slots()
    assert len(slots) == vec.arena.live_count
    assert 4 * len(slots) >= vec.capacity
    vec.advance_watermark(1999)
    heap.advance_watermark(1999)

    def norm(items):
        return sorted((int(k), s, e, round(float(r), 2))
                      for k, r, s, e in items)

    assert norm(vec.emitted) == norm(heap.emitted)
    # state was rebuilt: a second window re-uses the cleared slots
    vec.process_batch(keys[:100], ts[:100] + 2000, vals[:100])
    heap2 = ScalarHeapTumblingWindows(SumAggregate(np.float32), 1000)
    for i in range(100):
        heap2.process(int(keys[i]), int(ts[i]) + 2000, float(vals[i]))
    vec.advance_watermark(3999)
    heap2.advance_watermark(3999)
    assert norm(vec.emitted[len(heap.emitted):]) == norm(heap2.emitted)


def test_hash_keys_int_matches_scalar():
    from flink_tpu.core.keygroups import stable_hash64
    keys = np.array([0, 1, 2, 123456789], np.int64)
    h = hash_keys_np(keys)
    for k, hh in zip(keys, h):
        assert stable_hash64(int(k)) == int(hh)


@pytest.mark.parametrize("agg_factory", [
    lambda: SumAggregate(np.float32),
    lambda: CountAggregate(),
])
def test_vectorized_matches_heap_sum_count(agg_factory):
    rng = np.random.default_rng(7)
    n = 5000
    keys = rng.integers(0, 200, n)
    ts = rng.integers(0, 10_000, n)
    vals = rng.random(n).astype(np.float32)

    vec = VectorizedTumblingWindows(agg_factory(), 1000,
                                    initial_capacity=64)
    heap = ScalarHeapTumblingWindows(agg_factory(), 1000)

    # two batches with an intermediate watermark
    half = n // 2
    vec.process_batch(keys[:half], ts[:half], vals[:half])
    for i in range(half):
        heap.process(int(keys[i]), int(ts[i]), float(vals[i]))
    vec.advance_watermark(4999)
    heap.advance_watermark(4999)
    vec.process_batch(keys[half:], ts[half:], vals[half:])
    for i in range(half, n):
        heap.process(int(keys[i]), int(ts[i]), float(vals[i]))
    vec.advance_watermark(10_999)
    heap.advance_watermark(10_999)

    def norm(items):
        return sorted((int(k), s, e, round(float(r), 2))
                      for k, r, s, e in items)

    assert norm(vec.emitted) == norm(heap.emitted)
    assert vec.num_late_dropped == heap.num_late_dropped


def test_vectorized_hll_matches_heap():
    rng = np.random.default_rng(1)
    n = 20_000
    keys = rng.integers(0, 50, n)
    ts = rng.integers(0, 2000, n)
    users = rng.integers(0, 5000, n)

    vec = VectorizedTumblingWindows(HyperLogLogAggregate(10), 1000,
                                    initial_capacity=32)
    heap = ScalarHeapTumblingWindows(HyperLogLogAggregate(10), 1000)
    vec.process_batch(keys, ts, users)
    for i in range(n):
        heap.process(int(keys[i]), int(ts[i]), int(users[i]))
    vec.advance_watermark(1999)
    heap.advance_watermark(1999)

    v = {(k, s): r for k, r, s, e in vec.emitted}
    h = {(k, s): r for k, r, s, e in heap.emitted}
    assert set(v) == set(h)
    for key in v:
        # identical sketches → identical estimates (same hash path)
        assert v[key] == pytest.approx(h[key], rel=1e-6), key


def test_late_records_dropped():
    vec = VectorizedTumblingWindows(CountAggregate(), 1000)
    vec.process_batch(np.array([1]), np.array([500]))
    vec.advance_watermark(999)
    vec.process_batch(np.array([1, 2]), np.array([400, 1500]))  # 400 late
    assert vec.num_late_dropped == 1
    vec.advance_watermark(1999)
    assert [(k, int(r)) for k, r, s, e in vec.emitted] == [(1, 1), (2, 1)]


def test_slot_reuse_after_fire():
    vec = VectorizedTumblingWindows(SumAggregate(np.float32), 1000,
                                    initial_capacity=8)
    for round_i in range(5):
        base = round_i * 1000
        keys = np.arange(8)
        ts = np.full(8, base + 10)
        vals = np.ones(8, np.float32)
        vec.process_batch(keys, ts, vals)
        vec.advance_watermark(base + 999)
    # 5 rounds x 8 keys but only 8 live slots at a time: no growth
    assert vec.capacity == 8
    assert len(vec.emitted) == 40
    assert all(r == 1.0 for _, r, _, _ in vec.emitted)


def test_growth_mid_stream():
    vec = VectorizedTumblingWindows(SumAggregate(np.float32), 10_000,
                                    initial_capacity=4)
    keys = np.arange(100)
    vec.process_batch(keys, np.full(100, 5), np.ones(100, np.float32))
    vec.advance_watermark(9999)
    assert len(vec.emitted) == 100
    assert vec.capacity >= 100


def test_string_keys():
    vec = VectorizedTumblingWindows(CountAggregate(), 1000)
    keys = ["alpha", "beta", "alpha", "gamma"]
    vec.process_batch(keys, np.array([1, 2, 3, 4]))
    vec.advance_watermark(999)
    out = {k: int(r) for k, r, _, _ in vec.emitted}
    assert out == {"alpha": 2, "beta": 1, "gamma": 1}

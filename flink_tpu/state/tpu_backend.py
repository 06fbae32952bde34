"""TPU-HBM keyed-state backend — the replacement for the reference's
native (RocksDB/JNI) backend.

The reference's RocksDB backend pays two JNI hops per record
(RocksDBAggregatingState.java:108-131: db.get → deserialize → add →
serialize → db.put).  Here, aggregation state for ALL keys of this
subtask's key-group range lives as struct-of-arrays in device HBM
(`{component: f32/u8/i32 [capacity, ...]}`), a host-side index maps
(key, namespace) → dense slot, and updates are micro-batched: records
accumulate in host ring buffers and one `jax.jit` scatter dispatch
applies the whole batch (donated buffers → in-place HBM update, no
reallocation).  Reads (window fires) flush pending writes then gather.

States whose values are arbitrary Python objects (ValueState, ListState,
MapState, reducing/aggregating with non-device functions) are kept in
host tables exactly like the heap backend — mirroring how RocksDB
stores opaque bytes for everything while the hot path here is the
numeric aggregation state (the north-star workload).

Key-group layout: every slot records its key group so snapshots chunk
per key group (rescale re-splits ranges, ref:
KeyGroupRangeAssignment.java:47-56, StateAssignmentOperation.java).
"""

from __future__ import annotations

import pickle
import threading
import time
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.core.keygroups import (
    KeyGroupRange,
    assign_to_key_group,
    hash_int_column_np,
    stable_hash64,
)
from flink_tpu.core.state import (
    AggregatingState,
    AggregatingStateDescriptor,
    ListStateDescriptor,
    MapStateDescriptor,
    ReducingStateDescriptor,
    FoldingStateDescriptor,
    ValueStateDescriptor,
)
from flink_tpu.ops.device_agg import DeviceAggregateFunction
from flink_tpu.ops.hashing import split_hash64_np
from flink_tpu.state.backend import (
    VOID_NAMESPACE,
    KeyedStateBackend,
    KeyedStateSnapshot,
    DeferredSnapshot,
)
from flink_tpu.state.heap_backend import (
    HeapAggregatingState,
    HeapFoldingState,
    HeapListState,
    HeapMapState,
    HeapReducingState,
    HeapValueState,
    StateTable,
    split_column_by_key_group,
)
from flink_tpu.runtime.device_stats import TELEMETRY, tree_nbytes
from flink_tpu.runtime.tracing import get_tracer, traced_jit
from flink_tpu.state.device_snapshot import SnapshotPlan, StateCapture
from flink_tpu.state.host_tier import HostTier
from flink_tpu.state.slot_index import (
    NamespaceIndex,
    cut_by_namespace,
    object_column,
    pick,
)
from flink_tpu.state.sparse_rows import (
    SparseRows,
    concat_columns,
    dense_rows,
    take_rows,
)
from flink_tpu.state.stats import STATE_STATS, register_device_state

_perf_ns = time.perf_counter_ns

DEFAULT_INITIAL_CAPACITY = 4096
DEFAULT_MICROBATCH = 16384
#: scratch one `state.result` dispatch may materialise beside the
#: state: XLA writes the gathered [rows, *slot_shape] out before it
#: reduces it, so the fire's tile is sized by bytes, not by slots (as
#: vectorized.py sizes FIRE_TILE: 2^16 slots for HLL p12)
RESULT_SCRATCH_BYTES = 256 << 20
#: rows one `state.promote` dispatch uploads, in bytes: the spilled
#: rows a batch touches go back up together, in tiles of this one shape
PROMOTE_TILE_BYTES = 4 << 20
#: an eviction's one gather comes back in pieces of this many bytes,
#: their copies to the host all in flight at once (one copy of 1 GiB
#: ran at half the rate of sixteen of 64 MiB on a v5e's host link)
EVICT_PIECE_BYTES = 64 << 20
#: rows one `state.restore` dispatch uploads, in bytes, and the (slot,
#: cell, value) triples one `state.restore_cells` dispatch scatters: a
#: restore goes up in tiles of these shapes, never as one array the
#: size of the state beside the table
RESTORE_TILE_BYTES = 16 << 20
RESTORE_TILE_CELLS = 1 << 18
#: dense columns of a restore up to this many bytes are joined across
#: key groups into one call (sparse ones always are)
RESTORE_MERGE_BYTES = 256 << 20
#: fewest (destination, source) pairs one `state.merge_rows` dispatch
#: is shaped for: a session job merges a state window or two a batch
MERGE_MIN_WIDTH = 8


def _round_up_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def _pad_slots(slots, width: int) -> np.ndarray:
    """`slots` as int32[width]; pad entries repeat slots[0], a valid
    slot whose row the caller cuts off (get_batch) or clears again
    (clear_batch)."""
    arr = np.full(width, slots[0], np.int32)
    arr[:len(slots)] = slots
    return arr


def _count_probed(phase, rows: int, on_int_tables: int) -> None:
    """On a slot phase's books: the rows it probed in bulk, and those
    of them that went as a column since `on_int_tables`
    (`STATE_STATS.int_table_rows` then)."""
    phase.add_count("rows", rows)
    phase.add_count("int_table", STATE_STATS.int_table_rows - on_int_tables)


class _PendingRing:
    """One column of the pending micro-batch (its slots, its values or
    their hashes), in the order of its rows: `add_batch`'s columns as
    the arrays they are, the single entries of `add` gathered between
    them."""

    __slots__ = ("_dtype", "_parts", "_tail", "_n")

    def __init__(self, dtype) -> None:
        self._dtype = dtype
        self._parts: List[np.ndarray] = []
        self._tail: list = []
        self._n = 0

    def __len__(self) -> int:
        return self._n

    def append(self, entry) -> None:
        self._tail.append(entry)
        self._n += 1

    def _seal_tail(self) -> None:
        if self._tail:
            self._parts.append(np.array(self._tail, self._dtype))
            self._tail = []

    def extend(self, column) -> None:
        self._seal_tail()
        column = np.asarray(column, self._dtype)
        self._parts.append(column)
        self._n += len(column)

    def take(self) -> np.ndarray:
        """All of them as one vector (there is at least one)."""
        self._seal_tail()
        return np.concatenate(self._parts)

    def clear(self) -> None:
        self._parts = []
        self._tail = []
        self._n = 0


class DeviceAggregatingState(AggregatingState):
    """Slot-indexed, micro-batched device aggregation state.

    The device twin of RocksDBAggregatingState / HeapAggregatingState:
    identical observable semantics through the AggregatingState
    interface, but `add` enqueues into a pending batch and `get`
    flushes + gathers, so the per-batch cost is one bulk probe of the
    slot index per namespace and one XLA scatter over the whole key
    group.
    """

    def __init__(self, backend: "TpuKeyedStateBackend",
                 descriptor: AggregatingStateDescriptor,
                 initial_capacity: int = DEFAULT_INITIAL_CAPACITY,
                 microbatch: int = DEFAULT_MICROBATCH,
                 max_device_slots: Optional[int] = None):
        agg = descriptor.aggregate_function
        assert isinstance(agg, DeviceAggregateFunction)
        self._backend = backend
        self._descriptor = descriptor
        self.agg: DeviceAggregateFunction = agg
        self._namespace = VOID_NAMESPACE
        if max_device_slots is not None:
            # the budget holds from the first slot on
            initial_capacity = min(initial_capacity, max_device_slots)
        self.device_state: Dict[str, jnp.ndarray] = agg.init_state(initial_capacity)
        self.microbatch = microbatch
        # ---- host-RAM spill tier (SURVEY §7 hard-part: state > HBM;
        # the role RocksDB's disk residency plays in the reference) ----
        #: device-slot budget; None = unbounded (grow-on-demand)
        self.max_device_slots = max_device_slots
        #: entries evicted out of HBM, in the blocks their evictions
        #: gathered them in; promoted back on access
        self.host_tier = HostTier()
        #: its index, namespace → table of key → row id
        self._spilled = self.host_tier.index
        self._clock = 0
        #: observability: spill/promotion counters
        self.evictions = 0
        self.promotions = 0
        #: times nothing was cold enough to evict and the capacity grew
        #: past the budget instead (the soft cap)
        self.budget_overruns = 0
        #: (tile width, two sets of host buffers) of the spilled fire
        self._fire_buffers = (0, [])
        #: the pending micro-batch, a column each: slots, values (an
        #: aggregate that needs them) and 64-bit value hashes (one that
        #: needs those), split into the device's two lanes at the flush
        self._pending_slots = _PendingRing(np.int64)
        self._pending_values = _PendingRing(agg.value_dtype)
        self._pending_hash = _PendingRing(np.uint64)
        self._reset_slots(initial_capacity)
        # jit-compiled entry points (cached per state object; XLA caches
        # per padded batch shape), under labels jit_stats() keeps.  None
        # of them may close over this object: jax keeps a jitted
        # function, and with it the registers, long after the job
        self._jit_update = traced_jit(self.agg.update, name="state.update",
                                      donate_argnums=0)
        self._jit_upload = traced_jit(
            lambda st, slot, row: {k: st[k].at[slot].set(row[k])
                                   for k in st},
            name="state.upload", donate_argnums=0)
        # the spill tier's two bulk programs: one gather of an
        # eviction's rows (in pieces, see _evict_piece_rows), one
        # scatter of a batch's promoted rows
        piece = self._evict_piece_rows()
        self._jit_evict = traced_jit(
            lambda st, slots: [{k: st[k][slots[i:i + piece]] for k in st}
                               for i in range(0, slots.shape[0], piece)],
            name="state.evict")
        self._jit_promote = traced_jit(
            lambda st, slots, rows: {k: st[k].at[slots].set(rows[k])
                                     for k in st},
            name="state.promote", donate_argnums=0)
        self._jit_merge = traced_jit(self.agg.merge_slots,
                                     name="state.merge", donate_argnums=0)
        #: the jit(vmap(merge)) pairwise kernel — unique-dst dispatches
        #: only (merge_namespaces_batch rounds multi-source merges)
        self._jit_merge_rows = traced_jit(self.agg.merge_rows,
                                          name="state.merge_rows",
                                          donate_argnums=0)
        self._jit_clear = traced_jit(self.agg.clear_slots,
                                     name="state.clear", donate_argnums=0)
        self._jit_result = traced_jit(self.agg.result, name="state.result")
        # a restore's two uploads: whole rows of some components, and
        # the cells of a sparse column (a triple past the table's last
        # slot is padding and dropped)
        self._jit_restore = traced_jit(
            lambda st, slots, rows: {
                **st, **{k: st[k].at[slots].set(rows[k]) for k in rows}},
            name="state.restore", donate_argnums=0)
        self._jit_restore_cells = traced_jit(
            lambda table, at, vals: table.at[at].set(vals, mode="drop"),
            name="state.restore_cells", donate_argnums=0)
        #: the capture programs of a snapshot, made by the first one:
        #: a state nobody snapshots keeps no books and warms nothing
        self._snapshot_plan: Optional[SnapshotPlan] = None
        # queryable-state reads come from foreign threads; every
        # device_state REPLACEMENT donates the old tree's buffers, so
        # a concurrent gather on the old tree would read freed memory.
        # This lock serializes state swaps against query gathers (the
        # owner thread's swap sites take it; cost is one uncontended
        # acquire per micro-batch)
        self._device_lock = threading.RLock()
        register_device_state(self)

    def _reset_slots(self, capacity: int) -> None:
        """Every slot of `capacity` free, the index empty."""
        self.capacity = capacity
        #: namespace → table of key → slot: the ONE index of the device
        #: tier; `_slot_for` reads and writes it a key at a time, the
        #: batch doors a namespace's table at a time
        self.slot_index = NamespaceIndex()
        #: slot → key / namespace / whether it holds an entry at all
        #: (what an eviction files its victims under)
        self.slot_key = np.full(capacity, None, object)
        self.slot_ns = np.full(capacity, None, object)
        self._slot_live = np.zeros(capacity, bool)
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        #: per-slot last-access stamps (approximate LRU clock)
        self._access_stamp = np.zeros(capacity, np.int64)
        #: per-slot flag: some update has actually LANDED on device —
        #: queryable reads must not surface the init accumulator of a
        #: slot whose first adds are still pending (heap returns None)
        self._slot_flushed = np.zeros(capacity, bool)

    def reset(self) -> None:
        """Before a restore: the device state back to its initial
        accumulators in place (descriptor bindings survive), nothing
        indexed, nothing spilled.  Pending micro-batches are
        pre-failure writes: dropped, the checkpoint supersedes them."""
        self.device_state = self.agg.init_state(self.capacity)
        self._reset_slots(self.capacity)
        self.host_tier.clear()
        self._pending_slots.clear()
        self._pending_values.clear()
        self._pending_hash.clear()

    def _bytes_per_slot(self) -> int:
        return max(1, tree_nbytes(self.device_state) // self.capacity)

    def _evict_piece_rows(self) -> int:
        """Rows of one piece of an eviction's gather: `state.evict`
        returns the rows of its slots, each component in pieces of
        EVICT_PIECE_BYTES that travel to the host side by side."""
        return max(1, EVICT_PIECE_BYTES // self._bytes_per_slot())

    # ---- namespace / key context ------------------------------------
    def set_current_namespace(self, namespace) -> None:
        self._namespace = namespace

    # ---- slot management --------------------------------------------
    def _slot_for(self, key, namespace, create: bool = True) -> Optional[int]:
        """The per-key door into the slot index (scalar add / get,
        merges)."""
        STATE_STATS.per_key_probe_rows += 1
        slot = self.slot_index.get(key, namespace)
        if slot is None and self._spilled \
                and self._spilled.get(key, namespace) is not None:
            slot = self._promote(key, namespace)
        if slot is None and create:
            if not self._free:
                self._make_room()
            slot = self._free.pop()
            self.slot_index.put(key, namespace, slot)
            self._claim_one(slot, key, namespace)
        if slot is not None:
            self._clock += 1
            self._access_stamp[slot] = self._clock
        return slot

    def _claim_one(self, slot: int, key, namespace) -> None:
        self.slot_key[slot] = key
        self.slot_ns[slot] = namespace
        self._slot_live[slot] = True

    def _claim(self, slots: np.ndarray, keys, namespace) -> None:
        """`slots` hold `keys` of one namespace from now on."""
        # (an int64 column goes in as the Python ints it holds)
        self.slot_key[slots] = keys if isinstance(keys, np.ndarray) \
            else object_column(keys, len(slots))
        boxed = np.empty(1, object)  # a tuple would broadcast its fields
        boxed[0] = namespace
        self.slot_ns[slots] = boxed
        self._slot_live[slots] = True

    def _release(self, slots) -> None:
        """`slots` hold no entry any more (their rows on the device
        are the caller's to clear)."""
        self.slot_key[slots] = None
        self.slot_ns[slots] = None
        self._slot_live[slots] = False

    def _stamp(self, slots: np.ndarray) -> None:
        """Touch `slots` in order: each row gets the stamp a per-key
        loop would have given it (of a slot that comes twice, the
        last)."""
        n = len(slots)
        self._access_stamp[slots] = np.arange(self._clock + 1,
                                              self._clock + 1 + n)
        self._clock += n

    def _make_room(self, ahead: int = 0) -> None:
        """No free slots: grow HBM state, or — at the device budget —
        spill the coldest quarter of slots to the host tier (the
        RocksDB-disk-residency role; SURVEY §7 'state larger than
        HBM')."""
        if (self.max_device_slots is None
                or self.capacity * 2 <= self.max_device_slots):
            self._grow(self.capacity * 2)
            return
        self._evict_cold(max(1, self.capacity // 4), ahead)

    def _evict_cold(self, n: int, ahead: int = 0) -> None:
        """Spill the (up to) `n` coldest slots: ONE device gather of
        their rows, at a shape `n` fixes, filed in the host tier as
        the block it came back as.  `ahead`: rows the caller is about
        to stamp — the batch door makes room before it resolves a
        chunk, and what is cold is judged as of the chunk's last row,
        as when the per-key loop ran into the full table mid-chunk."""
        self._flush()
        # never evict recently touched slots: a batch mid-assembly
        # references up to `microbatch` freshly assigned slots (the
        # chunked add_batch bound; get_batch never allocates), and a
        # merge mid-flight re-stamps its sources just before
        # allocating the target — the +16 margin covers the merge's
        # source set
        protected = self._clock + ahead - (2 * self.microbatch + 16)
        stamps = self._access_stamp
        # (free slots and a merge's sources mid-flight are not live)
        cand = np.flatnonzero((stamps < protected) & self._slot_live)
        if cand.size > n:
            # the n smallest stamps, ties by slot
            s = stamps[cand]
            kth = np.partition(s, n - 1)[n - 1]
            below = cand[s < kth]
            cand = np.concatenate(
                [below, cand[s == kth][:n - below.size]])
        victims = cand[np.argsort(stamps[cand], kind="stable")]
        m = len(victims)
        if not m:
            # everything is hot: grow past the budget rather than
            # corrupt in-flight batches (soft cap)
            self.budget_overruns += 1
            STATE_STATS.budget_overruns += 1
            self._grow(self.capacity * 2)
            return
        with get_tracer().phase("state.evict", rows=m):
            idx = jnp.asarray(_pad_slots(victims, n))
            t0 = _perf_ns()
            pieces = self._jit_evict(self.device_state, idx)
            for piece in pieces:
                for arr in piece.values():
                    arr.copy_to_host_async()
            # (while the copies are on their way)
            entries = list(cut_by_namespace(
                self.slot_key[victims].tolist(), None,
                self.slot_ns[victims].tolist()))
            # filed before it leaves the slot index: a concurrent
            # query finds the entry in one tier or the other
            step = self._evict_piece_rows()
            bases = [self.host_tier.file(
                {name: np.asarray(arr)[:m - i] for name, arr in piece.items()})
                for i, piece in zip(range(0, m, step), pieces)]
            if TELEMETRY.enabled:
                TELEMETRY.record_transfer(
                    "d2h", m * self._bytes_per_slot(), t0, _perf_ns(),
                    "state.evict")
            for namespace, rows, keys in entries:
                self.slot_index.move(keys, namespace, self._spilled,
                                     rows + bases[0])
            self._release(victims)
            with self._device_lock:
                self.device_state = self._jit_clear(self.device_state, idx)
                self._slot_flushed[victims] = False
            self._free.extend(victims.tolist())
        self.evictions += m
        STATE_STATS.evicted_rows += m

    def _promote(self, key, namespace) -> int:
        """Host-tier entry accessed through the scalar path: lift its
        row back into HBM (donated single-row upload — in-place, no
        full-array copy).  The index entry publishes only AFTER the
        upload, inside the lock: a concurrent query must see either
        the spilled row or the uploaded slot, never a zeroed
        in-between slot."""
        if not self._free:
            self._make_room()
        slot = self._free.pop()
        row = self.host_tier.get(key, namespace)
        with self._device_lock:
            t0 = _perf_ns()
            self.device_state = self._jit_upload(
                self.device_state, jnp.int32(slot),
                {name: jnp.asarray(val) for name, val in row.items()})
            if TELEMETRY.enabled:
                TELEMETRY.record_transfer(
                    "h2d", sum(v.nbytes for v in row.values()),
                    t0, _perf_ns(), "state.promote")
            self.host_tier.discard(key, namespace)
            self.slot_index.put(key, namespace, slot)
            self._slot_flushed[slot] = True
        self._claim_one(slot, key, namespace)
        # freshly promoted slots are HOT: stamp them or a later
        # promotion in the same batch could evict them right back
        self._clock += 1
        self._access_stamp[slot] = self._clock
        self.promotions += 1
        STATE_STATS.promoted_rows += 1
        return slot

    def _promote_spilled(self, keys, namespace) -> None:
        """The spilled entries a batch of one namespace is about to
        touch go back into HBM together, before its slots are
        resolved: one bulk probe of the host index's table for that
        namespace, one `state.promote` scatter per tile of
        `_promote_tile()` rows, whatever their number.  The caller
        has made the room."""
        ids = self._spilled.lookup(keys, namespace)
        hit = ids >= 0
        if not hit.any():
            return
        # a key twice: one row; in order of first appearance
        ids, first = np.unique(ids[hit], return_index=True)
        order = np.argsort(first)
        ids = ids[order]
        keys = pick(keys, np.flatnonzero(hit)[first[order]])
        m = len(ids)
        with get_tracer().phase("state.promote", rows=m):
            free = self._free
            assert len(free) >= m
            slots = np.array(free[:-m - 1:-1], np.int64)
            del free[-m:]
            tile = self._promote_tile()
            with self._device_lock:
                t0 = _perf_ns()
                for i in range(0, m, tile):
                    part = ids[i:i + tile]
                    host_rows = self._host_rows(tile)
                    self.host_tier.gather(part, host_rows)
                    for arr in host_rows.values():
                        arr[len(part):] = arr[0]  # pad: slot 0's row again
                    self.device_state = self._jit_promote(
                        self.device_state,
                        jnp.asarray(_pad_slots(slots[i:i + tile], tile)),
                        {name: jnp.asarray(arr)
                         for name, arr in host_rows.items()})
                if TELEMETRY.enabled:
                    TELEMETRY.record_transfer(
                        "h2d", m * self._bytes_per_slot(), t0, _perf_ns(),
                        "state.promote")
                self._spilled.move(keys, namespace, self.slot_index, slots)
                self._claim(slots, keys, namespace)
                self._slot_flushed[slots] = True
                # promoted slots are HOT, as in _promote
                self._stamp(slots)
            self.host_tier.release(ids)
        self.promotions += m
        STATE_STATS.promoted_rows += m

    def _host_rows(self, n: int) -> Dict[str, np.ndarray]:
        """Host buffers for `n` rows of every component, to be filled
        and uploaded.  An upload may alias them: they are filled again
        only after what was computed from them came back."""
        return {name: np.empty((n, *arr.shape[1:]), arr.dtype)
                for name, arr in self.device_state.items()}

    def _promote_tile(self) -> int:
        """Rows of one `state.promote` dispatch: the power of two
        whose rows fit PROMOTE_TILE_BYTES, and no more than a chunk of
        `add_batch` can hold."""
        return min(_round_up_pow2(self.microbatch),
                   1 << max(0, (PROMOTE_TILE_BYTES
                                // self._bytes_per_slot()).bit_length() - 1))

    def _grow(self, new_capacity: int) -> None:
        self._flush()
        with self._device_lock:
            self.device_state = self.agg.grow_state(self.device_state,
                                                    new_capacity)
        if self._snapshot_plan is not None:
            # a capture's gather follows the table's shape: whoever
            # snapshots this state finds it compiled
            self._snapshot_plan.warm(self.device_state,
                                     all_programs=False)
        extra = new_capacity - self.capacity
        self._free.extend(range(new_capacity - 1, self.capacity - 1, -1))
        for name in ("slot_key", "slot_ns", "_slot_live", "_access_stamp",
                     "_slot_flushed"):
            column = getattr(self, name)
            fill = None if column.dtype == object else 0
            setattr(self, name, np.concatenate(
                [column, np.full(extra, fill, column.dtype)]))
        self.capacity = new_capacity

    def _resolve(self, keys, namespace) -> Tuple[np.ndarray, int]:
        """The batch door into the slot index: the slots of one
        namespace's `keys` (a list, or the int64 column they are) as
        int64[n], new keys taking theirs in the same pass, and how many
        were new.  Room first, then the chunk's spilled entries come
        up, then ONE probe of the namespace's table; nothing is evicted
        between the last two, so no entry the probe misses has a row in
        the host tier."""
        n = len(keys)
        if n == 0:
            return np.zeros(0, np.int64), 0
        free = self._free
        index = self.slot_index
        if len(free) < n:
            # fewer free slots than rows: count the keys that have no
            # slot before room is made for them, so the capacity
            # doubles (or the cold quarter leaves) when the per-key
            # door would have done it, not for rows that need nothing
            while len(free) < index.missing(keys, namespace):
                self._make_room(ahead=n)
        if self._spilled:
            self._promote_spilled(keys, namespace)
        on_int_tables = index.int_rows
        slots, fresh, new_keys = index.resolve(keys, namespace, free)
        if index.int_rows != on_int_tables:
            STATE_STATS.int_table_rows += index.int_rows - on_int_tables
        if len(fresh):
            self._claim(fresh, new_keys, namespace)
        self._stamp(slots)
        STATE_STATS.bulk_probe_rows += n
        return slots, len(fresh)

    def _resolve_column(self, keys, namespace,
                        namespaces) -> Tuple[np.ndarray, int]:
        """`_resolve` for a column of rows of ONE namespace, or
        (`namespaces=`) each of its own: the rows of a namespace go
        through the batch door together."""
        slots = np.empty(len(keys), np.int64)
        new = 0
        for namespace, rows, part in cut_by_namespace(keys, namespace,
                                                      namespaces):
            slots[rows], made = self._resolve(part, namespace)
            new += made
        return slots, new

    def _find(self, keys: list, namespace, namespaces, take: bool = False):
        """Where a column of entries lives, one bulk probe per
        namespace and tier: their device slots as int64[n] (-1: none),
        and of those rows the host tier holds, positions and row ids.
        `take` takes what it finds out of both indexes.  Allocates
        nothing, promotes nothing."""
        n = len(keys)
        STATE_STATS.bulk_probe_rows += n
        slots = np.empty(n, np.int64)
        spill_rows = [np.zeros(0, np.int64)]
        spill_ids = [np.zeros(0, np.int64)]
        index, spilled = self.slot_index, self._spilled
        on_int_tables = index.int_rows
        for namespace, rows, part in cut_by_namespace(keys, namespace,
                                                      namespaces):
            got = index.lookup(part, namespace, take)
            slots[rows] = got
            if namespace in spilled.tables:
                miss = got < 0
                ids = spilled.lookup(pick(part, miss), namespace, take)
                spill_rows.append(rows[miss][ids >= 0])
                spill_ids.append(ids[ids >= 0])
        STATE_STATS.int_table_rows += index.int_rows - on_int_tables
        return slots, np.concatenate(spill_rows), np.concatenate(spill_ids)

    # ---- write path -------------------------------------------------
    def add(self, value) -> None:
        slot = self._slot_for(self._backend.current_key, self._namespace)
        self._pending_slots.append(slot)
        value = self.agg.extract_value(value)
        if self.agg.needs_value:
            self._pending_values.append(value)
        if self.agg.needs_value_hash:
            self._pending_hash.append(stable_hash64(value))
            STATE_STATS.hash_per_value_rows += 1
        if len(self._pending_slots) >= self.microbatch:
            self._flush()

    def add_batch(self, keys: Iterable[Any], namespace, values,
                  namespaces=None, pre_extracted: bool = False) -> None:
        """Vectorized write: one bulk probe of the slot index per
        namespace, no per-record method dispatch.  `keys` is a list or
        an ndarray.  `namespace` is ONE namespace shared by the whole
        batch (a window tuple is a single namespace); pass a parallel
        sequence via `namespaces=` to override per record.  `values`
        is a sequence/ndarray parallel to keys; `pre_extracted=True`
        means the caller already ran extract_value/extract_column over
        it (a numeric column straight off a RecordBatch)."""
        if not isinstance(keys, (list, np.ndarray)):
            keys = list(keys)
        if self.max_device_slots is not None \
                and len(keys) > self.microbatch:
            # capped backend: resolve slots in microbatch-sized chunks,
            # so the room one chunk can need is bounded (and a chunk's
            # slots lie inside the eviction-protected stamp window)
            for i in range(0, len(keys), self.microbatch):
                sl = slice(i, i + self.microbatch)
                self.add_batch(
                    keys[sl], namespace,
                    values[sl] if values is not None else None,
                    namespaces=None if namespaces is None
                    else namespaces[sl],
                    pre_extracted=pre_extracted)
            return
        tracer = get_tracer()
        n = len(keys)
        with tracer.phase("state.add.slots") as phase:
            on_int_tables = STATE_STATS.int_table_rows
            slots, new = self._resolve_column(keys, namespace, namespaces)
            phase.add_count("new", new)
            _count_probed(phase, n, on_int_tables)
            self._pending_slots.extend(slots)
        with tracer.phase("state.add.hash", rows=n) as phase:
            extract = self.agg.extract_value
            # overridden on the class or per-instance (an
            # instance-attached plain function has no __func__)
            if not pre_extracted and getattr(
                    extract, "__func__",
                    None) is not DeviceAggregateFunction.extract_value:
                values = [extract(v) for v in values]
            if self.agg.needs_value:
                # (a copy: the ring outlives the caller's column)
                self._pending_values.extend(
                    np.array(values, self.agg.value_dtype))
            if self.agg.needs_value_hash:
                # an integer column hashes whole; whatever else a
                # value may be (a list's ints beyond int64, floats,
                # strings, tuples, objects) goes through the scalar
                # hash, which alone defines theirs
                column = (isinstance(values, np.ndarray)
                          and values.ndim == 1 and values.dtype.kind in "iu")
                if column:
                    hashes = hash_int_column_np(values)
                    STATE_STATS.hash_column_rows += n
                else:
                    hashes = np.fromiter(map(stable_hash64, values),
                                         np.uint64, n)
                    STATE_STATS.hash_per_value_rows += n
                self._pending_hash.extend(hashes)
                phase.set_attr("column", column)
        if len(self._pending_slots) >= self.microbatch:
            self._flush()

    def _flush(self) -> None:
        n = len(self._pending_slots)
        if n == 0:
            return
        with get_tracer().phase("state.flush", rows=n), self._device_lock:
            self._flush_locked(n)

    def _flush_locked(self, n: int) -> None:
        padded = _round_up_pow2(n)
        pending = self._pending_slots.take()
        slots = np.zeros(padded, np.int32)
        slots[:n] = pending
        mask = np.zeros(padded, bool)
        mask[:n] = True
        values = np.zeros(padded, self.agg.value_dtype)
        if self.agg.needs_value:
            values[:n] = self._pending_values.take()
        hi = np.zeros(padded, np.uint32)
        lo = np.zeros(padded, np.uint32)
        if self.agg.needs_value_hash:
            hi[:n], lo[:n] = split_hash64_np(self._pending_hash.take())
        if TELEMETRY.enabled:
            t0 = _perf_ns()
            self.device_state = self._jit_update(
                self.device_state, slots, values, hi, lo, mask)
            TELEMETRY.record_transfer(
                "h2d",
                slots.nbytes + mask.nbytes + values.nbytes
                + hi.nbytes + lo.nbytes,
                t0, _perf_ns(), "state.flush")
            TELEMETRY.note_flush(n)
        else:
            self.device_state = self._jit_update(
                self.device_state, slots, values, hi, lo, mask)
        STATE_STATS.note_flush(
            n, self.agg.update_runs_in_place(padded, self.capacity))
        self._slot_flushed[pending] = True
        self._pending_slots.clear()
        self._pending_values.clear()
        self._pending_hash.clear()

    # ---- read path --------------------------------------------------
    def get(self):
        slot = self._slot_for(self._backend.current_key, self._namespace,
                              create=False)
        if slot is None:
            return None
        self._flush()
        if TELEMETRY.enabled:
            t0 = _perf_ns()
            res = np.asarray(self._jit_result(
                self.device_state, jnp.asarray(np.array([slot], np.int32))))
            TELEMETRY.record_transfer("d2h", res.nbytes, t0, _perf_ns(),
                                      "state.fire")
            TELEMETRY.note_fire_read()
            out = res[0]
        else:
            out = np.asarray(self._jit_result(
                self.device_state,
                jnp.asarray(np.array([slot], np.int32))))[0]
        return out.item() if np.ndim(out) == 0 else out

    def get_batch(self, keys, namespace, namespaces=None) -> Tuple[np.ndarray, np.ndarray]:
        """Gather results for many (key, namespace) pairs in ONE device
        round-trip: one bulk probe of each tier's index per namespace,
        one pending-ring flush, one fused jit gather per tile of
        slots, one wait — the batched window-fire read.  Spill-tier
        rows are finalized from their host-resident accumulators
        WITHOUT promotion (a fire is a read; lifting cold rows into
        HBM per fired window would re-pay the per-row transfer tax
        this path exists to amortize).  No slot allocation or eviction
        can happen here, so no chunking is needed.  Returns
        (results, found_mask); namespace semantics as in `add_batch`."""
        tracer = get_tracer()
        with tracer.phase("state.get.lookup") as phase:
            if not isinstance(keys, (list, tuple, np.ndarray)):
                keys = list(keys)
            n = len(keys)
            on_int_tables = STATE_STATS.int_table_rows
            slots, spill_idx, spill_ids = self._find(keys, namespace,
                                                     namespaces)
            _count_probed(phase, n, on_int_tables)
            found = slots >= 0
            # reads stamp the LRU clock exactly as scalar get()
            self._stamp(slots[found])
            slots[~found] = 0  # a row not found reads slot 0, cut off
            found[spill_idx] = True
        self._flush()  # ONE flush for the whole sweep
        if n == 0:  # nothing to gather, and no program for int32[0]
            none = jax.eval_shape(self.agg.result, self.device_state,
                                  jax.ShapeDtypeStruct((0,), jnp.int32))
            return np.zeros(none.shape, none.dtype), found
        # `state.result` runs only at shapes that do not follow the
        # data: a power of two up to the tile, above it that one shape
        # again for every tile, so a fire of any size finds its program
        width = min(_round_up_pow2(n), self._result_tile())
        padded = -(-n // width) * width
        with tracer.phase("state.get.device", keys=n, padded=padded):
            t0 = _perf_ns()
            arr = _pad_slots(slots, padded)
            state = self.device_state
            # every tile is dispatched before the first is waited for
            parts = [self._jit_result(state, jnp.asarray(arr[i:i + width]))
                     for i in range(0, padded, width)]
            res = np.concatenate([np.asarray(p) for p in parts])[:n]
            if TELEMETRY.enabled:
                TELEMETRY.record_transfer("d2h", res.nbytes, t0,
                                          _perf_ns(), "state.fire")
                TELEMETRY.note_fire_read()
        STATE_STATS.note_result(n, padded)
        if len(spill_idx):
            res[spill_idx] = self._finalize_spilled(spill_ids)
        return res, found

    def _result_tile(self) -> int:
        """Most rows one `state.result` dispatch gathers: the power of
        two whose [rows, *slot_shape] fits RESULT_SCRATCH_BYTES."""
        return 1 << max(0, (RESULT_SCRATCH_BYTES
                            // self._bytes_per_slot()).bit_length() - 1)

    def _finalize_spilled(self, ids: np.ndarray) -> np.ndarray:
        """Result extraction for spill-tier rows without promotion:
        the host-resident accumulator rows go up in tiles of the
        width `get_batch` gathers device rows at and through the SAME
        jit result kernel — bit-identical finalization, zero HBM slot
        traffic, never more than two tiles of rows on the device."""
        m = len(ids)
        width = min(_round_up_pow2(m), self._result_tile())
        tiles = -(-m // width)
        with get_tracer().phase("state.fire.spill", rows=m, tiles=tiles):
            t0 = _perf_ns()
            order = np.argsort(ids, kind="stable")  # block by block
            ids = ids[order]
            every_row = jnp.asarray(np.arange(width, dtype=np.int32))
            # two sets of host buffers, kept from fire to fire (fresh
            # pages cost ten times the copy into them): a tile's set is
            # filled again only after its result came back
            if self._fire_buffers[0] != width:
                self._fire_buffers = (width, [self._host_rows(width),
                                              self._host_rows(width)])
            buffers = self._fire_buffers[1]
            in_flight, done = [], []
            for i in range(0, m, width):
                part = ids[i:i + width]
                rows = buffers[(i // width) % 2]
                self.host_tier.gather(part, rows)
                for arr in rows.values():
                    arr[len(part):] = arr[0]  # pad rows, cut off below
                in_flight.append(self._jit_result(
                    {name: jnp.asarray(arr) for name, arr in rows.items()},
                    every_row))
                if len(in_flight) > 1:
                    done.append(np.asarray(in_flight.pop(0)))
            done.extend(np.asarray(p) for p in in_flight)
            sorted_res = np.concatenate(done)[:m]
            res = np.empty_like(sorted_res)
            res[order] = sorted_res
            if TELEMETRY.enabled:
                TELEMETRY.record_transfer(
                    "h2d", m * self._bytes_per_slot(), t0, t0,
                    "state.fire.spill")
                TELEMETRY.record_transfer("d2h", res.nbytes, t0,
                                          _perf_ns(), "state.fire.spill")
        STATE_STATS.spill_fired_rows += m
        return res

    def query_by_key(self, key, namespace):
        """Queryable-state read from a FOREIGN thread (ref:
        AbstractKeyedStateBackend.java:382-389 getPartitionedState for
        queries + KvStateServerHandler).  Dirty-read semantics match
        the heap path: pending (unflushed) adds are invisible; no
        owner-side structures mutate (no promotion, no access-stamp
        touch).  The device gather serializes against state swaps via
        the device lock."""
        with self._device_lock:
            slot = self.slot_index.get(key, namespace)
            if slot is not None and not self._slot_flushed[slot]:
                # the key's first adds are still pending: invisible
                # (matches the heap path's None-for-absent contract)
                slot = None
            if slot is not None:
                out = np.asarray(self._jit_result(
                    self.device_state,
                    jnp.asarray(np.array([slot], np.int32))))[0]
                return out.item() if np.ndim(out) == 0 else out
        row = self.host_tier.get(key, namespace)
        if row is not None:
            # spilled entry: finalize its single row host-side (lift
            # to a 1-slot state; compiles once per aggregate)
            state1 = {name: jnp.asarray(val)[None]
                      for name, val in row.items()}
            out = np.asarray(self._jit_result(
                state1, jnp.asarray(np.zeros(1, np.int32))))[0]
            return out.item() if np.ndim(out) == 0 else out
        return None

    # ---- lifecycle --------------------------------------------------
    def clear(self) -> None:
        key = self._backend.current_key
        STATE_STATS.per_key_probe_rows += 1
        self.host_tier.discard(key, self._namespace)
        slot = self.slot_index.pop(key, self._namespace)
        if slot is None:
            return
        self._flush()
        with self._device_lock:
            self.device_state = self._jit_clear(
                self.device_state, jnp.asarray(np.array([slot], np.int32)))
            self._slot_flushed[slot] = False
        self._release(slot)
        self._free.append(slot)

    def clear_batch(self, keys, namespace, namespaces=None) -> None:
        tracer = get_tracer()
        with tracer.phase("state.clear.slots") as phase:
            if not isinstance(keys, (list, tuple, np.ndarray)):
                keys = list(keys)
            on_int_tables = STATE_STATS.int_table_rows
            slots, _, spilled_ids = self._find(keys, namespace, namespaces,
                                               take=True)
            _count_probed(phase, len(keys), on_int_tables)
            slots = slots[slots >= 0]
            self._release(slots)
        if len(spilled_ids):
            with tracer.phase("state.clear.spill", rows=len(spilled_ids)):
                self.host_tier.release(spilled_ids)
        if not len(slots):
            return
        self._flush()
        with tracer.phase("state.clear.device"):
            arr = _pad_slots(slots, _round_up_pow2(len(slots)))
            with self._device_lock:
                self.device_state = self._jit_clear(self.device_state,
                                                    jnp.asarray(arr))
                self._slot_flushed[slots] = False
            self._free.extend(slots.tolist())

    def _merge_plan(self, key, target, sources) -> Tuple[int, List[int]]:
        """One session merge's slots: the target's (made if it has
        none) and its live sources', which leave the index here; (-1,
        []) if no source holds state.  The caller folds and clears
        them on the device and frees them."""
        spilled = self._spilled
        # spilled sources participate in the merge: promote them first
        for src in sources:
            if spilled.get(key, src) is not None:
                self._promote(key, src)
        if spilled.get(key, target) is not None:
            self._promote(key, target)
        # touch every source slot BEFORE any allocation below: the
        # target slot allocation may need to make room, and eviction
        # must not take a slot this merge still references (fresh
        # stamps fall inside _evict_cold's protected window; slots
        # stay fully registered in the index until after the
        # allocation, so eviction bookkeeping stays consistent)
        live = []
        for src in sources:
            s = self.slot_index.get(key, src)
            if s is not None:
                self._clock += 1
                self._access_stamp[s] = self._clock
                live.append((src, s))
        # don't materialize a target slot unless some source has state
        # (matches heap: merging all-empty namespaces leaves no state)
        if not live:
            return -1, []  # nothing to fold in; target (if any) stays
        dst = self._slot_for(key, target)
        src_slots = []
        for src, s in live:
            self.slot_index.pop(key, src)
            if s != dst:
                src_slots.append(s)
                # (not live, not yet free: an eviction a later merge
                # of the batch triggers passes it by)
                self._release(s)
        return dst, src_slots

    def merge_namespaces(self, target, sources) -> None:
        """Session-window merge: device merge_slots(dst, src), then
        free source slots (ref: mergeNamespaces,
        WindowOperator.java:338 / MergingWindowSet.java:156)."""
        self._flush()
        dst, src_slots = self._merge_plan(self._backend.current_key,
                                          target, sources)
        if not src_slots:
            return
        dsts = np.full(len(src_slots), dst, np.int32)
        srcs = np.array(src_slots, np.int32)
        with self._device_lock:
            self.device_state = self._jit_merge(
                self.device_state, jnp.asarray(dsts), jnp.asarray(srcs))
            self.device_state = self._jit_clear(self.device_state,
                                                jnp.asarray(srcs))
            self._slot_flushed[dst] = True
            self._slot_flushed[srcs] = False
        self._free.extend(src_slots)

    def merge_namespaces_batch(self, merges) -> None:
        """Batched session merge: `merges` is a list of
        (key, target_namespace, [source_namespaces]).  One flush up
        front, then the whole merge set runs in ROUNDS through the
        jit(vmap(agg.merge)) pairwise kernel — round r folds each
        target's r-th live source, so every dispatch has UNIQUE
        destination slots (distinct merges own distinct (key, target)
        slots) — and one clear frees every source slot at the end.
        Both programs run at a power of two of at least
        MERGE_MIN_WIDTH pairs, so a batch with a merge or two more
        than the last finds its program: a pad pair's destination is
        the slot past the table, which the scatter drops.
        Observable state after this call is identical to running
        merge_namespaces per (key, target)."""
        self._flush()
        with get_tracer().phase("state.merge") as phase:
            plans = []  # (dst_slot, [src_slots])
            for key, target, sources in merges:
                dst, srcs = self._merge_plan(key, target, sources)
                if srcs:
                    plans.append((dst, srcs))
            rows = sum(len(srcs) for _, srcs in plans)
            phase.set_attr("rows", rows)
            STATE_STATS.merged_rows += rows
            if not plans:
                return
            rounds = max(len(srcs) for _, srcs in plans)
            width = max(MERGE_MIN_WIDTH, _round_up_pow2(len(plans)))
            all_srcs: List[int] = []
            with self._device_lock:
                for r in range(rounds):
                    pairs = [(dst, srcs[r]) for dst, srcs in plans
                             if len(srcs) > r]
                    # (a pad pair reads slot 0 and writes nowhere)
                    dsts = np.full(width, self.capacity, np.int32)
                    srcs = np.zeros(width, np.int32)
                    dsts[:len(pairs)] = [dst for dst, _ in pairs]
                    srcs[:len(pairs)] = [src for _, src in pairs]
                    self.device_state = self._jit_merge_rows(
                        self.device_state, jnp.asarray(dsts),
                        jnp.asarray(srcs))
                    all_srcs.extend(src for _, src in pairs)
                self.device_state = self._jit_clear(
                    self.device_state, jnp.asarray(_pad_slots(
                        all_srcs, max(MERGE_MIN_WIDTH,
                                      _round_up_pow2(len(all_srcs))))))
                self._slot_flushed[[dst for dst, _ in plans]] = True
                self._slot_flushed[all_srcs] = False
            self._free.extend(all_srcs)

    # ---- snapshot ---------------------------------------------------
    def restore_entries(self, entries: List[Tuple[Any, Any, Dict[str, np.ndarray]]]) -> None:
        if not entries:
            return
        self.restore_columns(
            [key for key, _, _ in entries],
            [namespace for _, namespace, _ in entries],
            {name: np.stack([row[name] for _, _, row in entries])
             for name in entries[0][2]})

    def capture(self) -> StateCapture:
        """The synchronous part of a snapshot, on the task's thread:
        the pending micro-batch flushed, the live entries read off the
        slot arrays, the host tier's rows taken by reference, and the
        gathers dispatched that copy the live rows into buffers of
        their own (``state/device_snapshot.py``).  What comes back
        holds the state as of now whatever is written behind it;
        ``columns()`` on it, from any thread, is the rest."""
        tracer = get_tracer()
        with tracer.phase("state.snapshot.flush"):
            self._flush()
        with tracer.phase("state.snapshot.index") as phase:
            slots = np.flatnonzero(self._slot_live)
            keys = self.slot_key[slots]
            namespaces = self.slot_ns[slots]
            spilled = self.host_tier.capture() if self.host_tier else None
            phase.set_attr("rows", len(slots))
            phase.set_attr("spilled", len(spilled) if spilled else 0)
        with tracer.phase("state.snapshot.dispatch") as phase:
            plan = self._snapshot_plan
            if plan is None:
                plan = self._snapshot_plan = SnapshotPlan(
                    self.agg.state_specs(), self._bytes_per_slot())
                plan.warm(self.device_state, all_programs=True)
            copies = plan.copy(self.device_state, slots)
            phase.set_attr("tiles", len(copies))
        return StateCapture(plan, keys, namespaces, copies, spilled,
                            self._backend.max_parallelism)

    def snapshot_columns(self) -> Dict[int, Tuple[list, list, Dict[str, np.ndarray]]]:
        """The whole snapshot at once, dense: per key group, (keys,
        namespaces, {component: stacked rows})."""
        return {kg: (keys, nss, {name: dense_rows(col)
                                 for name, col in comps.items()})
                for kg, (keys, nss, comps) in self.capture().columns().items()}

    def restore_columns(self, keys: list, namespaces: list,
                        comps: Dict[str, Any]) -> None:
        """Columnar restore: the rows' slots through the batch door,
        then the rows go up in tiles of fixed shapes, a dense column
        `RESTORE_TILE_BYTES` of rows a dispatch, a sparse one
        (`SparseRows`) `RESTORE_TILE_CELLS` of its cells.  Rows past
        the device budget go to the host tier."""
        n = len(keys)
        if n == 0:
            return
        live = len(self.slot_index)
        needed = live + n
        if self.max_device_slots is not None \
                and needed > self.max_device_slots:
            # beyond the device budget: the overflow restores straight
            # into the host tier (promoted lazily on first access)
            budget = max(self.max_device_slots - live, 0)
            self.host_tier.put(
                keys[budget:], namespaces[budget:],
                {name: np.array(dense_rows(col, slice(budget, None)))
                 for name, col in comps.items()})
            keys = keys[:budget]
            namespaces = namespaces[:budget]
            comps = {name: take_rows(col, slice(0, budget))
                     for name, col in comps.items()}
            n = budget
            if n == 0:
                return
            needed = live + n
        if needed > self.capacity - len(self._pending_slots):
            self._grow(max(self.capacity * 2, _round_up_pow2(needed)))
        slots, new = self._restore_slots(list(keys), list(namespaces))
        with self._device_lock:
            if new < n:
                # an entry that was here already: a sparse column only
                # sets cells, so its row starts from the fill
                for i in range(0, n, self.microbatch):
                    part = slots[i:i + self.microbatch]
                    self.device_state = self._jit_clear(
                        self.device_state, jnp.asarray(
                            _pad_slots(part, _round_up_pow2(len(part)))))
            self._upload_rows(slots, {name: col for name, col in comps.items()
                                      if not isinstance(col, SparseRows)})
            for name, col in comps.items():
                if isinstance(col, SparseRows):
                    self._upload_cells(name, slots, col)
            self._slot_flushed[slots] = True

    def _restore_slots(self, keys: list,
                       namespaces: list) -> Tuple[np.ndarray, int]:
        """Slots for a restore's entries, and how many are new.  Into
        an index that holds nothing (a restore's one call, after the
        reset) every entry of a snapshot is new: they take their slots
        in one go and enter the index a namespace at a time, with none
        of the batch door's probing, promoting and stamping per
        namespace (a session job's restore brings a namespace per
        row).  Otherwise, or if an entry comes twice, the batch door."""
        n = len(keys)
        if len(self.slot_index) or len(self._spilled) \
                or len(set(zip(keys, namespaces))) != n:
            return self._resolve_column(keys, None, namespaces)
        free = self._free
        slots = np.array(free[:-n - 1:-1], np.int64)
        del free[-n:]
        for namespace, rows, part in cut_by_namespace(keys, None,
                                                      namespaces):
            self.slot_index.enter(part, namespace, slots[rows])
        self.slot_key[slots] = object_column(keys, n)
        self.slot_ns[slots] = object_column(namespaces, n)
        self._slot_live[slots] = True
        self._stamp(slots)
        STATE_STATS.bulk_probe_rows += n
        return slots, n

    def _upload_rows(self, slots: np.ndarray,
                     comps: Dict[str, np.ndarray]) -> None:
        if not comps:
            return
        n = len(slots)
        row_bytes = sum(col[:1].nbytes for col in comps.values())
        tile = max(1, min(_round_up_pow2(n),
                          1 << max(0, (RESTORE_TILE_BYTES
                                       // max(1, row_bytes)).bit_length() - 1)))
        for i in range(0, n, tile):
            m = min(tile, n - i)
            rows = {}
            for name, col in comps.items():
                part = np.empty((tile, *col.shape[1:]), col.dtype)
                part[:m] = col[i:i + m]
                part[m:] = part[0]  # pad: the tile's first row again
                rows[name] = jnp.asarray(part)
            self.device_state = self._jit_restore(
                self.device_state,
                jnp.asarray(_pad_slots(slots[i:i + m], tile)), rows)

    def _upload_cells(self, name: str, slots: np.ndarray,
                      col: SparseRows) -> None:
        total = len(col.cells)
        if not total:
            return
        at_slot = np.repeat(slots.astype(np.int32), col.counts)
        at_cell = np.unravel_index(col.cells.astype(np.int64), col.row_shape)
        tile = RESTORE_TILE_CELLS
        for i in range(0, total, tile):
            m = min(tile, total - i)
            # (a pad triple's slot is past the table: dropped)
            index = [np.full(tile, self.capacity, np.int32)]
            index[0][:m] = at_slot[i:i + m]
            for dim in at_cell:
                part = np.zeros(tile, np.int32)
                part[:m] = dim[i:i + m]
                index.append(part)
            vals = np.zeros(tile, col.dtype)
            vals[:m] = col.vals[i:i + m]
            self.device_state = {
                **self.device_state,
                name: self._jit_restore_cells(
                    self.device_state[name],
                    tuple(jnp.asarray(a) for a in index),
                    jnp.asarray(vals))}

    def active_entries(self) -> Iterable[Tuple[Any, Any]]:
        yield from self.slot_index
        yield from self.host_tier


def _merged_blocks(blocks: list) -> list:
    """A state's key-group blocks as ONE block where that costs no
    large copy (sparse columns, or dense ones of RESTORE_MERGE_BYTES at
    most): the table then grows once, to its last size, the entries
    take their slots in one pass and the uploads' tiles are full.
    Dense columns beyond that restore a key group at a time."""
    if len(blocks) < 2:
        return blocks
    dense = sum(col.nbytes for _, _, comps in blocks
                for col in comps.values() if not isinstance(col, SparseRows))
    if dense > RESTORE_MERGE_BYTES:
        return blocks
    return [([k for keys, _, _ in blocks for k in keys],
             [ns for _, nss, _ in blocks for ns in nss],
             {name: concat_columns([comps[name] for _, _, comps in blocks])
              for name in blocks[0][2]})]


def _join_rows(at_barrier, rows: list) -> list:
    """A key group's host-table rows: those read at the barrier (a
    list, or its pickle where the snapshot was finished later) and
    those of the tables held by reference."""
    if isinstance(at_barrier, bytes):
        at_barrier = pickle.loads(at_barrier)
    return (at_barrier or []) + rows


def _encode_chunks(at_barrier: Dict[int, object], held: list,
                   captures: Dict[str, StateCapture],
                   max_parallelism: int) -> Dict[int, bytes]:
    """What `TpuKeyedStateBackend` does of a snapshot after the
    barrier: `at_barrier`, per key group the host-table rows read
    there (serialized there, where this runs later); `held`,
    ``(name, namespace, {key: value})`` of the host tables whose
    values were taken by reference; `captures`, the device states'."""
    from flink_tpu.state.backend import encode_obj_column
    per_kg_cols: Dict[int, Dict[str, list]] = defaultdict(dict)
    for name, capture in captures.items():
        for kg, (keys, nss, comps) in capture.columns().items():
            per_kg_cols[kg].setdefault(name, []).append({
                "keys": encode_obj_column(keys),
                "ns": ("col", encode_obj_column(nss)),
                "comps": comps,
                "kind": "acc",
            })
    with get_tracer().phase("checkpoint.encode"):
        per_kg_rows: Dict[int, list] = defaultdict(list)
        for name, namespace, by_key in held:
            keys = list(by_key)
            values = list(by_key.values())
            for kg, sel in split_column_by_key_group(keys, max_parallelism):
                per_kg_rows[kg].extend((name, namespace, keys[i], values[i])
                                       for i in sel.tolist())
        chunks = {}
        for kg in set(at_barrier) | set(per_kg_rows) | set(per_kg_cols):
            chunks[kg] = pickle.dumps(
                {"v": 2,
                 "rows": _join_rows(at_barrier.get(kg),
                                    per_kg_rows.get(kg, [])),
                 "cols": per_kg_cols.get(kg, {})},
                protocol=pickle.HIGHEST_PROTOCOL)
        STATE_STATS.snapshot_bytes_written += sum(
            len(blob) for blob in chunks.values())
    return chunks


class TpuKeyedStateBackend(KeyedStateBackend):
    """Hybrid backend: device slots for DeviceAggregateFunction
    aggregation state, host tables for everything else."""

    name = "tpu"

    def __init__(self, key_group_range: KeyGroupRange, max_parallelism: int,
                 initial_capacity: int = DEFAULT_INITIAL_CAPACITY,
                 microbatch: int = DEFAULT_MICROBATCH,
                 max_device_slots: Optional[int] = None):
        super().__init__(key_group_range, max_parallelism)
        self._tables: Dict[str, StateTable] = {}
        self._device_states: Dict[str, DeviceAggregatingState] = {}
        self.initial_capacity = initial_capacity
        self.microbatch = microbatch
        #: per-state HBM slot budget; beyond it cold entries spill to
        #: host RAM (config key state.backend.tpu.max-device-slots)
        self.max_device_slots = max_device_slots

    def _table(self, name: str) -> StateTable:
        t = self._tables.get(name)
        if t is None:
            t = StateTable()
            self._tables[name] = t
        return t

    # ---- factories --------------------------------------------------
    def create_value_state(self, d: ValueStateDescriptor):
        return HeapValueState(self, d, self._table(d.name))

    def create_list_state(self, d: ListStateDescriptor):
        return HeapListState(self, d, self._table(d.name))

    def create_reducing_state(self, d: ReducingStateDescriptor):
        return HeapReducingState(self, d, self._table(d.name))

    def create_aggregating_state(self, d: AggregatingStateDescriptor):
        if isinstance(d.aggregate_function, DeviceAggregateFunction):
            st = DeviceAggregatingState(
                self, d, self.initial_capacity, self.microbatch,
                max_device_slots=self.max_device_slots)
            self._device_states[d.name] = st
            # a restore() that ran before this descriptor was bound
            # parked this state's accumulators in a host table (it had
            # no way to know they were device-resident) — migrate them
            leftover = self._tables.pop(d.name, None)
            if leftover is not None:
                specs = d.aggregate_function.state_specs()
                entries = []
                for namespace, key, value in leftover.entries():
                    row = {n: np.asarray(value[n]).reshape(specs[n].shape)
                           for n in specs}
                    entries.append((key, namespace, row))
                st.restore_entries(entries)
            return st
        return HeapAggregatingState(self, d, self._table(d.name))

    def create_folding_state(self, d: FoldingStateDescriptor):
        return HeapFoldingState(self, d, self._table(d.name))

    def create_map_state(self, d: MapStateDescriptor):
        return HeapMapState(self, d, self._table(d.name))

    # ---- introspection ----------------------------------------------
    def get_keys(self, state_name: str, namespace) -> Iterable[Any]:
        if state_name in self._device_states:
            return [k for (k, ns) in self._device_states[state_name].active_entries()
                    if ns == namespace]
        t = self._tables.get(state_name)
        return list(t.keys(namespace)) if t else []

    def accounting_breakdown(self) -> Dict[str, Dict[int, dict]]:
        """Per-(state, key-group) rows/bytes/namespaces: host tables
        count standalone pickled lengths (the snapshot's per-row tier);
        device states count active entries (HBM slots + host spill,
        INCLUDING rows still pending in the micro-batch ring — the
        slot index is updated at add time) at the per-row component
        width from ``agg.state_specs()``, which equals the snapshot's
        gathered column nbytes — no D2H transfer needed."""
        from flink_tpu.core.keygroups import assign_key_groups_np, \
            stable_hashes_np
        from flink_tpu.state.introspect import pickled_len
        out: Dict[str, Dict[int, dict]] = {}
        mp = self.max_parallelism

        def entry(per_kg, kg):
            e = per_kg.get(kg)
            if e is None:
                e = per_kg[kg] = {"rows": 0, "bytes": 0, "_ns": set()}
            return e

        for name, table in self._tables.items():
            per_kg = out.setdefault(name, {})
            for namespace, key, value in table.entries():
                kg = assign_to_key_group(key, mp)
                e = entry(per_kg, kg)
                e["rows"] += 1
                e["bytes"] += pickled_len(value)
                e["_ns"].add(namespace)
        for name, dstate in self._device_states.items():
            per_kg = out.setdefault(name, {})
            specs = dstate.agg.state_specs()
            row_bytes = sum(
                int(np.prod(spec.shape, dtype=np.int64))
                * np.dtype(spec.dtype).itemsize
                for spec in specs.values())
            entries = list(dstate.active_entries())
            if not entries:
                continue
            keys = [k for k, _ns in entries]
            kgs = assign_key_groups_np(stable_hashes_np(keys), mp)
            for (key, namespace), kg in zip(entries, kgs):
                e = entry(per_kg, int(kg))
                e["rows"] += 1
                e["bytes"] += row_bytes
                e["_ns"].add(namespace)
        return {name: {kg: {"rows": e["rows"], "bytes": e["bytes"],
                            "namespaces": len(e["_ns"])}
                       for kg, e in per_kg.items()}
                for name, per_kg in out.items()}

    # ---- snapshot / restore -----------------------------------------
    def snapshot(self) -> KeyedStateSnapshot:
        """v2 columnar chunk format: `capture_snapshot` resolved at
        once, on the caller's thread (nothing can change a value
        between the two parts, so the host tables' rows are
        serialized once, with their chunk)."""
        return self._capture(deferred=False).resolve()

    def capture_snapshot(self) -> DeferredSnapshot:
        """The snapshot in two parts.  Here, at the barrier, on the
        caller's thread: every device state is captured
        (`DeviceAggregatingState.capture`: index columns, and gathers
        dispatched that copy its live rows aside), and the host
        tables' rows are serialized per key group, as their values may
        be changed in place behind the barrier (a join's buffer, an
        accumulator, a list); only of a table whose owner declares its
        values `copy_on_write` (the window operator's session
        mappings) is a copy of the entries taken, the values by
        reference.  The handle's `resolve()`, on the thread that
        calls it (a checkpoint's writer), gives the
        `KeyedStateSnapshot`: the captures are reduced, come to the
        host and are cut by key group, one column per component (key
        and namespace columns through the wire codec, a sketch's rows
        as the cells off their fill), and the chunks are pickled."""
        return self._capture(deferred=True)

    def _capture(self, deferred: bool) -> DeferredSnapshot:
        captures = {name: dstate.capture()
                    for name, dstate in self._device_states.items()}
        per_kg_rows: Dict[int, list] = defaultdict(list)
        #: (name, namespace, {key: value}), the values by reference
        held = []
        for name, table in self._tables.items():
            if getattr(self._descriptors.get(name), "copy_on_write", False):
                for namespace, by_key in table.by_namespace.items():
                    held.append((name, namespace, dict(by_key)))
                    STATE_STATS.snapshot_rows += len(by_key)
                continue
            for namespace, key, value in table.entries():
                kg = assign_to_key_group(key, self.max_parallelism)
                per_kg_rows[kg].append((name, namespace, key, value))
                STATE_STATS.snapshot_rows += 1
        at_barrier = per_kg_rows if not deferred else {
            kg: pickle.dumps(entries, protocol=pickle.HIGHEST_PROTOCOL)
            for kg, entries in per_kg_rows.items()}
        STATE_STATS.snapshot_columns += sum(
            len(capture) for capture in captures.values())
        mp = self.max_parallelism
        meta = {"backend": self.name,
                "max_parallelism": self.max_parallelism,
                "serializers": self.serializer_config_snapshots()}
        return DeferredSnapshot(lambda: KeyedStateSnapshot(
            _encode_chunks(at_barrier, held, captures, mp), meta))

    def _restore_norm_rows(self, rows, pending_device) -> None:
        """Per-row entries: values in the scalar-twin accumulator
        format (dict of per-component arrays, see
        DeviceAggregateFunction.create_accumulator) whose state is
        device-resident here normalize to device rows; everything else
        goes to host tables."""
        for name, namespace, key, value in rows:
            dstate = self._device_states.get(name)
            if dstate is not None and isinstance(value, dict):
                specs = dstate.agg.state_specs()
                row = {n: np.asarray(value[n]).reshape(specs[n].shape)
                       for n in specs}
                pending_device[name].append((key, namespace, row))
            else:
                self._table(name).put(key, namespace, value)

    def _restore_v2_cols(self, cols: dict, pending_device,
                         pending_cols) -> None:
        from flink_tpu.state.backend import decode_obj_column
        for name, blocks in cols.items():
            for block in blocks:
                comps = block["comps"]
                n = len(next(iter(comps.values()))) if comps else 0
                keys = decode_obj_column(block["keys"], n)
                ns_field = block["ns"]
                namespaces = ([ns_field[1]] * n if ns_field[0] == "const"
                              else decode_obj_column(ns_field[1], n))
                if block["kind"] == "scalar":
                    # heap column block: plain scalar values
                    table = self._table(name)
                    vals = comps["value"]
                    for k, ns, v in zip(keys, namespaces, vals):
                        table.put(k, ns, v.item())
                    continue
                pending_cols.setdefault(name, []).append(
                    (keys, namespaces, comps))

    def restore(self, snapshots) -> None:
        self.check_serializer_compatibility(snapshots)
        # clear in place: bound state objects hold table references
        for table in self._tables.values():
            table.clear_all()
        for dstate in self._device_states.values():
            dstate.reset()
        pending_device: Dict[str, list] = defaultdict(list)
        pending_cols: Dict[str, list] = {}
        for snap in snapshots:
            for kg, blob in snap.blobs():
                if not self.key_group_range.contains(kg):
                    continue
                chunk = pickle.loads(blob)
                if isinstance(chunk, list):
                    # chunk written by the legacy heap backend
                    self._restore_norm_rows(chunk, pending_device)
                    continue
                if chunk.get("v") == 2:
                    self._restore_norm_rows(chunk["rows"], pending_device)
                    self._restore_v2_cols(chunk["cols"], pending_device,
                                          pending_cols)
                    continue
                for name, namespace, key, value in chunk["host"]:
                    self._table(name).put(key, namespace, value)
                for name, entries in chunk["device"].items():
                    pending_device[name].extend(entries)
        for name, blocks in pending_cols.items():
            dstate = self._device_states.get(name)
            if dstate is not None:
                for keys, namespaces, comps in _merged_blocks(blocks):
                    dstate.restore_columns(keys, namespaces, comps)
            else:
                # descriptor not bound yet: park per-row accumulator
                # dicts in a host table; create_aggregating_state's
                # migration lifts them onto the device at bind time
                table = self._table(name)
                for keys, namespaces, comps in blocks:
                    for i in range(len(keys)):
                        row = {c: np.array(arr[i])
                               for c, arr in comps.items()}
                        table.put(keys[i], namespaces[i], row)
        for name, entries in pending_device.items():
            dstate = self._device_states.get(name)
            if dstate is not None:
                dstate.restore_entries(entries)
            else:
                # descriptor not bound yet (standard recovery order is
                # restore-then-open): park rows in a host table; the
                # migration in create_aggregating_state picks them up
                table = self._table(name)
                for key, namespace, row in entries:
                    table.put(key, namespace, row)
        self._apply_restored_migrations()

    def _migrate_state_values(self, descriptor, serializer,
                              restored_cfg) -> None:
        """Value migration for HOST-table states (the same pass as the
        heap backend); device-resident states are numeric accumulator
        rows the record serializers never apply to, so only live host
        tables migrate."""
        from flink_tpu.state.backend import migrate_table_values
        name = descriptor.name
        table = self._tables.get(name)
        if table is None or name in self._device_states:
            return
        migrate_table_values(table, descriptor, serializer,
                             restored_cfg)

    def flush_all(self) -> None:
        """Barrier hook: push all pending micro-batches to HBM before a
        snapshot is taken (SURVEY.md §7 hard-parts list)."""
        for dstate in self._device_states.values():
            dstate._flush()

    def dispose(self) -> None:
        super().dispose()
        self._tables.clear()
        self._device_states.clear()

"""Vectorized tumbling-window aggregation engine — the TPU hot path.

This is the performance centerpiece (SURVEY.md §7 stage 1, BASELINE.md
north star): where the reference walks one record at a time through
WindowOperator.processElement → HeapAggregatingState.add (hashmap
probe) or RocksDBAggregatingState.add (two JNI hops + serde,
RocksDBAggregatingState.java:108-131), this engine consumes whole
record batches:

  host:   vectorized key hashing (numpy), vectorized window
          assignment (ts - ts % size), slot resolution via
          searchsorted over sorted hash arrays (no Python dict on the
          hot path),
  device: ONE jit-compiled scatter per micro-batch updating the whole
          key-group range's accumulators in HBM
          (add/max/min combiner per DeviceAggregateFunction), and ONE
          gather per window fire.

Semantics match WindowOperator + EventTimeTrigger for tumbling
event-time windows with allowed_lateness=0 (the batched counterpart of
the scalar operator — differentially tested against it).  Sliding
windows reduce to this engine by pane replication; session windows
stay on the scalar operator (they merge, SURVEY.md §7 hard parts).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.core.keygroups import (
    hash_int_column_np,
    splitmix64_np,
    stable_hash64,
)
from flink_tpu.ops.device_agg import DeviceAggregateFunction
from flink_tpu.ops.hashing import split_hash64_np
from flink_tpu.runtime.device_stats import TELEMETRY
from flink_tpu.runtime.tracing import traced_jit

_perf_ns = time.perf_counter_ns


def hash_keys_np(keys) -> np.ndarray:
    """Vectorized stable 64-bit key hashing: integer arrays go through
    splitmix64 in one numpy pass; object arrays fall back to per-key
    stable_hash64 (paid once per record batch, not per state access).
    Uniform numeric TUPLES (composite keys / distinct-count over
    composites) arrive as a 2-D array — per-column hashes combine
    order-sensitively into one 64-bit hash per row."""
    arr = np.asarray(keys)
    if arr.dtype.kind == "f" and arr.size \
            and np.all(arr == arr.astype(np.int64)):
        arr = arr.astype(np.int64)
    if arr.dtype.kind in "iu":
        if arr.ndim == 1:
            return hash_int_column_np(arr)
        h = np.zeros(len(arr), np.uint64)
        for j in range(arr.shape[1]):
            h = splitmix64_np(
                h ^ splitmix64_np(arr[:, j].astype(np.uint64))
                ^ np.uint64(0x9E3779B97F4A7C15 * (j + 1) & (2**64 - 1)))
        return h
    if arr.ndim > 1:
        return np.fromiter((stable_hash64(tuple(r)) for r in arr),
                           dtype=np.uint64, count=len(arr))
    return np.fromiter((stable_hash64(k) for k in arr),
                       dtype=np.uint64, count=len(arr))


_EMPTY = np.uint64(0)
_ZERO_REMAP = np.uint64(0x9E3779B97F4A7C15)


class VectorizedSlotIndex:
    """hash64 → dense slot via a vectorized open-addressing table.

    The replacement for the per-record dict probe: a whole batch
    resolves in a handful of numpy gather/compare rounds over a
    linear-probing table (load kept < 0.6).  A steady-state batch (all
    keys known, few collisions) costs ~2 vector passes — far cheaper
    per record than the reference heap backend's per-record hashmap
    probe, and ~4x cheaper than binary search over a sorted array
    (random binary searches are cache-miss bound).

    Intra-batch insert races resolve exactly like the device table
    (flink_tpu.ops.device_table): unresolved records write their hash
    at their probe position, re-read to find winners, losers advance.
    Slots are handed out by an external allocator callback so multiple
    windows share one device-state arena."""

    __slots__ = ("table_hash", "table_slot", "cap", "n")

    def __init__(self, capacity: int = 1 << 12):
        cap = 1 << max(4, (capacity - 1).bit_length())
        self.table_hash = np.zeros(cap, np.uint64)   # 0 = empty
        self.table_slot = np.zeros(cap, np.int64)
        self.cap = cap
        self.n = 0

    def _pos0(self, h: np.ndarray) -> np.ndarray:
        return ((h ^ (h >> np.uint64(32)))
                & np.uint64(self.cap - 1)).astype(np.int64)

    def _grow(self, need: int) -> None:
        new_cap = self.cap
        while (self.n + need) * 5 > new_cap * 3:   # load < 0.6
            new_cap *= 2
        if new_cap == self.cap:
            return
        old_hash, old_slot = self.table_hash, self.table_slot
        occ = old_hash != _EMPTY
        self.table_hash = np.zeros(new_cap, np.uint64)
        self.table_slot = np.zeros(new_cap, np.int64)
        self.cap = new_cap
        self.n = 0
        if occ.any():
            self._insert_existing(old_hash[occ], old_slot[occ])

    def _insert_existing(self, hashes: np.ndarray, slots: np.ndarray) -> None:
        """Rehash unique entries into the (empty, larger) table."""
        pos = self._pos0(hashes)
        pending = np.arange(len(hashes))
        mask_c = np.int64(self.cap - 1)
        while len(pending):
            pi = pos[pending]
            empty = self.table_hash[pi] == _EMPTY
            idx = pending[empty]
            if len(idx):
                self.table_hash[pos[idx]] = hashes[idx]
                won = self.table_hash[pos[idx]] == hashes[idx]
                w = idx[won]
                self.table_slot[pos[w]] = slots[w]
                self.n += len(w)
                done = np.zeros(len(hashes), bool)
                done[w] = True
                pending = pending[~done[pending]]
            if len(pending):
                pos[pending] = (pos[pending] + 1) & mask_c

    def lookup_or_insert(
        self, batch_hashes: np.ndarray,
        alloc: Callable[[int], np.ndarray],
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Resolve a batch to slots; new keys get slots from `alloc`.
        Returns (slots[N] int64, new_mask_over_new_uniques (all True),
        first_idx) where first_idx gives, for each inserted unique
        hash, one position in the batch holding that key (for
        first-seen key capture)."""
        h = np.where(batch_hashes == _EMPTY, _ZERO_REMAP, batch_hashes)
        self._grow(len(h))
        n = len(h)
        out = np.full(n, -1, np.int64)
        pos = self._pos0(h)
        pending = np.arange(n)
        mask_c = np.int64(self.cap - 1)
        new_first: List[np.ndarray] = []
        while len(pending):
            hp = h[pending]
            p = pos[pending]
            cur = self.table_hash[p]
            match = cur == hp
            if match.any():
                m = pending[match]
                out[m] = self.table_slot[pos[m]]
            empty = cur == _EMPTY
            if empty.any():
                idx = pending[empty]
                pi = pos[idx]
                # last-write-wins per position; re-read to find winners
                self.table_hash[pi] = h[idx]
                won = self.table_hash[pi] == h[idx]
                w = idx[won]
                if len(w):
                    # dedupe winners sharing a position AND hash (batch
                    # duplicates): keep the first per position
                    pw, first_per_pos = np.unique(pos[w], return_index=True)
                    w = w[first_per_pos]
                    new_slots = alloc(len(w))
                    self.table_slot[pos[w]] = new_slots
                    out[w] = new_slots
                    self.n += len(w)
                    new_first.append(w)
            resolved = out[pending] >= 0
            pending = pending[~resolved]
            if len(pending):
                # duplicates of a just-inserted key re-check their
                # current position (it now matches); others advance
                cur2 = self.table_hash[pos[pending]]
                advance = pending[cur2 != h[pending]]
                pos[advance] = (pos[advance] + 1) & mask_c
        if new_first:
            first_idx = np.concatenate(new_first)
        else:
            first_idx = np.zeros(0, np.int64)
        return out, np.ones(len(first_idx), bool), first_idx


def make_slot_index(capacity: int = 1 << 12):
    """Fastest available slot index: the C++ open-addressing table
    (flink_tpu.native.NativeSlotIndex, ~10-30x the numpy passes) when
    the native runtime built, else the numpy VectorizedSlotIndex."""
    try:
        import flink_tpu.native as nat
        if nat.available():
            return nat.NativeSlotIndex(capacity)
    except Exception:  # noqa: BLE001
        pass
    return VectorizedSlotIndex(capacity)


class _SlotArena:
    """Dense slot allocator over the device-state arrays."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.next = 0
        self.free: List[np.ndarray] = []  # freed slot arrays

    def alloc(self, n: int) -> np.ndarray:
        out = np.empty(n, np.int64)
        filled = 0
        while self.free and filled < n:
            chunk = self.free[-1]
            take = min(len(chunk), n - filled)
            out[filled:filled + take] = chunk[:take]
            if take == len(chunk):
                self.free.pop()
            else:
                self.free[-1] = chunk[take:]
            filled += take
        fresh = n - filled
        if fresh:
            out[filled:] = np.arange(self.next, self.next + fresh)
            self.next += fresh
        return out

    def release(self, slots: np.ndarray) -> None:
        if len(slots):
            self.free.append(np.asarray(slots, np.int64))

    @property
    def high_water(self) -> int:
        return self.next

    @property
    def live_count(self) -> int:
        """Slots currently allocated (handed out and not released)."""
        return self.next - sum(len(c) for c in self.free)


def pad_pow2(a: np.ndarray, fill) -> np.ndarray:
    """Pad to the next power of two (stable jit shapes)."""
    n = len(a)
    padded = 1 << max(0, (n - 1)).bit_length()
    out = np.full(padded, fill, a.dtype)
    out[:n] = a
    return out


def make_masked_update(agg: DeviceAggregateFunction):
    """Jitted scatter-update where the mask derives on device from the
    live count — one scalar over the wire instead of a bool array."""

    def update_fn(state, slots, values, hi, lo, n):
        mask = jnp.arange(slots.shape[0], dtype=jnp.int32) < n
        return agg.update(state, slots, values, hi, lo, mask)

    return traced_jit(update_fn, name="window.masked_update",
                      donate_argnums=0)


class _ScratchMergeMixin:
    """Device-side slot merging shared by the sliding and session
    engines: state[dst] ⊕= state[src] in one jit call, padded to
    power-of-two shapes with a sacrificial scratch slot (allocated from
    the arena, never gathered).  Requires self.agg / self.arena /
    self.state / self._jit_merge and a _ensure_state_capacity hook."""

    _scratch_slot_id: Optional[int] = None

    def _scratch(self) -> int:
        if self._scratch_slot_id is None:
            self._scratch_slot_id = int(self.arena.alloc(1)[0])
        return self._scratch_slot_id

    def _ensure_state_capacity(self) -> None:
        """Grow the device arrays if the arena outran them — fire-time
        union allocations bypass the ingest-path growth check, and an
        out-of-bounds scatter under jit drops writes SILENTLY."""
        if self.arena.high_water > self.capacity:
            new_cap = max(self.capacity * 2,
                          1 << (self.arena.high_water - 1).bit_length())
            self.state = self.agg.grow_state(self.state, new_cap)
            self.capacity = new_cap

    def _merge_tiled(self, dst, src) -> None:
        n = len(dst)
        if n == 0:
            return
        self._ensure_state_capacity()
        scratch = self._scratch()
        d = pad_pow2(np.asarray(dst, np.int32), scratch)
        s = pad_pow2(np.asarray(src, np.int32), scratch)
        self.state = self._jit_merge(self.state, jnp.asarray(d),
                                     jnp.asarray(s))


class _WindowShard:
    """Per-live-window bookkeeping: its own slot index + first-seen
    keys (and their hashes, for cross-window merging), all slots drawn
    from the shared arena.  Keys stay as numpy arrays end to end —
    per-record Python boxing (.tolist) measurably dominates the host
    side of the ingest loop at 1M+ keys/window."""

    __slots__ = ("start", "index", "key_list", "slot_list", "hash_list")

    def __init__(self, start: int):
        self.start = start
        self.index = make_slot_index()
        self.key_list: List[np.ndarray] = []
        self.slot_list: List[np.ndarray] = []
        self.hash_list: List[np.ndarray] = []

    @property
    def n_keys(self) -> int:
        return sum(len(a) for a in self.key_list)

    def all_keys(self) -> np.ndarray:
        if not self.key_list:
            return np.empty(0, object)
        if len(self.key_list) > 1:
            self.key_list = [np.concatenate(self.key_list)]
        return self.key_list[0]

    def all_slots(self) -> np.ndarray:
        if not self.slot_list:
            return np.empty(0, np.int64)
        if len(self.slot_list) > 1:
            self.slot_list = [np.concatenate(self.slot_list)]
        return self.slot_list[0]

    def all_hashes(self) -> np.ndarray:
        if not self.hash_list:
            return np.empty(0, np.uint64)
        if len(self.hash_list) > 1:
            self.hash_list = [np.concatenate(self.hash_list)]
        return self.hash_list[0]


class VectorizedTumblingWindows:
    """Batched keyBy().window(Tumbling...).aggregate(device_agg)."""

    def __init__(self, aggregate: DeviceAggregateFunction, window_size_ms: int,
                 initial_capacity: int = 1 << 16,
                 microbatch: int = 1 << 17,
                 emit: Optional[Callable[[Any, Any, int, int], None]] = None):
        self.agg = aggregate
        self.size = window_size_ms
        #: how far past a (pane) start a record stays live — subclasses
        #: with multi-pane windows widen this
        self.lateness_horizon = window_size_ms
        self.capacity = initial_capacity
        self.state = aggregate.init_state(initial_capacity)
        self.arena = _SlotArena(initial_capacity)
        self.windows: Dict[int, _WindowShard] = {}
        self.watermark = -(2**63)
        self.microbatch = microbatch
        #: emit(key, result, window_start, window_end); None → collect
        self.emit = emit
        self.emitted: List[Tuple[Any, Any, int, int]] = []
        #: True → skip per-key tuples; fires land in `fired` as
        #: (keys_np, results_np, start, end) batches, both in
        #: slot-sorted fire order
        self.emit_arrays = False
        self.fired: List[Tuple[np.ndarray, np.ndarray, int, int]] = []
        self.num_late_dropped = 0
        # pending micro-batch (pre-allocated growing buffers)
        self._p_slots: List[np.ndarray] = []
        self._p_values: List[np.ndarray] = []
        self._p_hi: List[np.ndarray] = []
        self._p_lo: List[np.ndarray] = []
        self._p_count = 0
        self._jit_update = make_masked_update(self.agg)
        self._jit_result = traced_jit(self.agg.result,
                                      name="window.result")
        self._jit_clear = traced_jit(self.agg.clear_slots,
                                     name="window.clear", donate_argnums=0)
        # contiguous fire fast path: slots handed out by the arena are
        # dense, so a full tile of consecutive slots fires as ONE
        # dynamic_slice + dense reduction instead of a row gather
        # (XLA gathers ~2.5M rows/s vs memory-bandwidth slicing)
        agg = self.agg

        def _result_contig(state, start, tile):
            sub = {k: jax.lax.dynamic_slice_in_dim(v, start, tile, 0)
                   for k, v in state.items()}
            return agg.result_dense(sub)

        self._jit_result_contig = traced_jit(_result_contig,
                                             name="window.result_contig",
                                             static_argnums=(2,))

        specs = agg.state_specs()

        def _clear_contig(state, start, tile):
            out = dict(state)
            for name, spec in specs.items():
                fill = jnp.full((tile, *spec.shape), spec.fill,
                                dtype=spec.dtype)
                out[name] = jax.lax.dynamic_update_slice_in_dim(
                    out[name], fill, start, 0)
            return out

        self._jit_clear_contig = traced_jit(_clear_contig,
                                            name="window.clear_contig",
                                            static_argnums=(2,),
                                            donate_argnums=0)
        # full-arena fire: when the fired window owns EVERY live slot
        # (the steady tumbling cadence — one window live at a time) and
        # covers enough of the arena, one fused full-array reduce beats
        # tiled dynamic-slice gathers (a [tile, m] dynamic_slice out of
        # a multi-GB array materializes unfused, ~4x the bandwidth
        # cost), and the clear becomes one donated full fill at write
        # bandwidth
        self._jit_result_all = traced_jit(agg.result_dense,
                                          name="window.result_all")
        # fire/clear tile bounded by BYTES not slot count: a gather or
        # clear materializes [tile, *slot_shape] intermediates, so wide
        # per-slot state (Count-Min: depth*width ints) must shrink the
        # tile (16GB HBM budget, ~256MB per intermediate)
        bytes_per_slot = max(
            sum(int(np.prod(spec.shape, dtype=np.int64)) * spec.dtype.itemsize
                for spec in aggregate.state_specs().values()), 1)
        budget = 256 << 20
        tile = 1 << max(9, (budget // bytes_per_slot).bit_length() - 1)
        self.FIRE_TILE = min(tile, type(self).FIRE_TILE)

    # ---- ingestion --------------------------------------------------
    def process_batch(
        self,
        keys,
        timestamps: np.ndarray,
        values: Optional[np.ndarray] = None,
        key_hashes: Optional[np.ndarray] = None,
        value_hashes: Optional[np.ndarray] = None,
    ) -> None:
        """One batch of records: assign windows, resolve slots, buffer
        the scatter. `keys` may be any sequence; pass `key_hashes` to
        skip hashing (e.g. when the exchange already hashed them)."""
        ts = np.asarray(timestamps, np.int64)
        kh = key_hashes if key_hashes is not None else hash_keys_np(keys)
        starts = ts - np.mod(ts, self.size)
        # drop late records (latest containing window's end <= watermark,
        # lateness 0); for tumbling the horizon is the window size, for
        # pane-based sliding it is the full window size over pane starts
        live = starts + self.lateness_horizon - 1 > self.watermark
        if not live.all():
            self.num_late_dropped += int((~live).sum())
            if not live.any():
                return
            ts, kh, starts = ts[live], kh[live], starts[live]
            # keep numeric dtype — boxing to object arrays is only for
            # non-array key sequences
            keys = (keys[live] if isinstance(keys, np.ndarray)
                    else np.asarray(keys, dtype=object)[live])
            if values is not None:
                values = np.asarray(values)[live]
            if value_hashes is not None:
                value_hashes = np.asarray(value_hashes)[live]

        if self.agg.needs_value_hash and value_hashes is None:
            value_hashes = hash_keys_np(values)

        keys_arr = keys if isinstance(keys, np.ndarray) else np.asarray(
            keys, dtype=object)
        uniq_starts = np.unique(starts)
        single_window = len(uniq_starts) == 1
        for start in uniq_starts:
            shard = self.windows.get(start)
            if shard is None:
                shard = _WindowShard(int(start))
                self.windows[int(start)] = shard
            if single_window:
                bh, masked_keys = kh, keys_arr
                m_values = values
                m_vhashes = value_hashes
            else:
                mask = starts == start
                bh = kh[mask]
                masked_keys = keys_arr[mask]
                m_values = None if values is None else np.asarray(values)[mask]
                m_vhashes = None if value_hashes is None else value_hashes[mask]
            slots, new_uniq, first_idx = shard.index.lookup_or_insert(
                bh, self.arena.alloc)
            if len(first_idx):
                shard.key_list.append(masked_keys[first_idx])
                shard.slot_list.append(np.asarray(slots[first_idx], np.int64))
                shard.hash_list.append(np.asarray(bh[first_idx], np.uint64))
            self._buffer(slots, m_values, m_vhashes)
        if self._p_count >= self.microbatch:
            self.flush()

    def _buffer(self, slots, values, value_hashes) -> None:
        self._p_slots.append(slots.astype(np.int32))
        if self.agg.needs_value:
            self._p_values.append(np.asarray(values, self.agg.value_dtype))
        if self.agg.needs_value_hash:
            hi, lo = split_hash64_np(value_hashes)
            self._p_hi.append(hi)
            self._p_lo.append(lo)
        self._p_count += len(slots)
        # grow device arrays before slots overflow capacity
        if self.arena.high_water > self.capacity:
            self.flush(grow_to=max(self.capacity * 2,
                                   1 << (self.arena.high_water - 1).bit_length()))

    def flush(self, grow_to: Optional[int] = None) -> None:
        if grow_to is not None and grow_to > self.capacity:
            # growing reallocates; flush pending first at old capacity
            # only if slots fit — otherwise grow first
            self.state = self.agg.grow_state(self.state, grow_to)
            self.capacity = grow_to
        if self._p_count == 0:
            return
        n = self._p_count
        padded = 1 << max(0, (n - 1)).bit_length()
        slots = np.zeros(padded, np.int32)
        np.concatenate(self._p_slots, out=slots[:n])
        # unused operands ship as broadcastable dummies — no transfer
        if self.agg.needs_value:
            values = np.zeros(padded, self.agg.value_dtype)
            np.concatenate(self._p_values, out=values[:n])
        else:
            values = np.zeros(1, self.agg.value_dtype)
        if self.agg.needs_value_hash:
            hi0 = np.concatenate(self._p_hi) if len(self._p_hi) > 1 else self._p_hi[0]
            lo0 = np.concatenate(self._p_lo) if len(self._p_lo) > 1 else self._p_lo[0]
            hi0, lo0 = self.agg.compress_value_hash(hi0, lo0)
            hi = np.zeros(padded, hi0.dtype)
            lo = np.zeros(padded, lo0.dtype)
            hi[:n] = hi0
            lo[:n] = lo0
        else:
            hi = np.zeros(1, np.uint32)
            lo = np.zeros(1, np.uint32)
        if TELEMETRY.enabled:
            t0 = _perf_ns()
            self.state = self._jit_update(self.state, slots, values, hi,
                                          lo, np.int32(n))
            TELEMETRY.record_transfer(
                "h2d", slots.nbytes + values.nbytes + hi.nbytes + lo.nbytes,
                t0, _perf_ns(), "window.flush")
            TELEMETRY.note_flush(n)
        else:
            self.state = self._jit_update(self.state, slots, values, hi,
                                          lo, np.int32(n))
        self._p_slots.clear()
        self._p_values.clear()
        self._p_hi.clear()
        self._p_lo.clear()
        self._p_count = 0

    # ---- firing -----------------------------------------------------
    #: gather/clear tile: fixed shape → one compile, bounded
    #: intermediates (HLL result materializes [TILE, m] floats)
    FIRE_TILE = 1 << 18

    def advance_watermark(self, watermark: int) -> int:
        """Fire every window whose end-1 <= watermark; returns the
        number of (key, window) results emitted.  Tiled device gathers
        (the TPU twin of onEventTime → emitWindowContents)."""
        self.watermark = watermark
        fired = 0
        for start in sorted(self.windows):
            if start + self.size - 1 > watermark:
                continue
            shard = self.windows.pop(start)
            self.flush()
            slots = shard.all_slots()
            if len(slots):
                end = start + self.size
                full = (len(slots) == self.arena.live_count
                        and 4 * len(slots) >= self.capacity)
                slots = self._emit_fire(shard.all_keys(), slots, start, end,
                                        full=full)
                fired += len(slots)
                if full:
                    # the fired results are already materialized on the
                    # host; DROP the register file before rebuilding it
                    # (a donated pure fill cannot alias its input —
                    # measured OOM at 2x arena — so peak must stay at
                    # one arena), then refill fresh at write bandwidth
                    self.state = None
                    self.state = self.agg.init_state(self.capacity)
                else:
                    self._clear_tiled(slots)
                self.arena.release(slots)
        if TELEMETRY.enabled:
            TELEMETRY.note_windows_fired(fired)
        return fired

    def _emit_fire(self, keys, slots: np.ndarray, start: int, end: int,
                   full: bool = False):
        """Fire (keys, slots) in slot-sorted order; returns the slots
        in fire order so callers clear/release the same layout.

        Slot order matters: a window's slots are a dense arena range
        (up to free-list fragmentation), so the sorted gather/clear
        collapses to dynamic-slice tiles (memory bandwidth) instead of
        row gathers (~2.5M rows/s); sorted release also keeps future
        allocations ascending, so the property is self-sustaining."""
        if len(slots) == 0:
            return slots
        keys = keys if isinstance(keys, np.ndarray) else np.asarray(
            keys, dtype=object)
        order = np.argsort(slots, kind="stable")
        slots = slots[order]
        keys = keys[order]
        if full:
            # one fused reduce over the whole state (no slice
            # materialization), one D2H of the per-slot results,
            # host-side fancy index into fire order
            if TELEMETRY.enabled:
                t0 = _perf_ns()
                res_all = np.asarray(self._jit_result_all(self.state))
                TELEMETRY.record_transfer("d2h", res_all.nbytes,
                                          t0, _perf_ns(), "window.fire")
                TELEMETRY.note_fire_read()
                results = res_all[slots]
            else:
                results = np.asarray(self._jit_result_all(self.state))[slots]
        if self.emit_arrays:
            self.fired.append((keys,
                               results if full
                               else self._gather_tiled_np(slots),
                               start, end))
        elif self.emit is not None:
            res_list = (results.tolist() if full
                        else self._gather_tiled(slots))
            for key, res in zip(keys, res_list):
                self.emit(key, res, start, end)
        else:
            res_list = (results.tolist() if full
                        else self._gather_tiled(slots))
            self.emitted.extend(zip(keys, res_list,
                                    [start] * len(slots), [end] * len(slots)))
        return slots

    def _is_contiguous_tile(self, chunk: np.ndarray, tile: int) -> bool:
        """Full tile of strictly consecutive slots, fully inside the
        current capacity — eligible for dynamic_slice fire/clear."""
        return (len(chunk) == tile
                and int(chunk[0]) + tile <= self.capacity
                and int(chunk[-1]) - int(chunk[0]) == tile - 1
                and np.array_equal(
                    chunk, np.arange(chunk[0], chunk[0] + tile,
                                     dtype=chunk.dtype)))

    def _fire_tile_future(self, chunk: np.ndarray, tile: int):
        """One tile's result future: contiguous full tiles take the
        dynamic-slice path; ragged/unordered tiles gather."""
        if self._is_contiguous_tile(chunk, tile):
            return self._jit_result_contig(self.state,
                                           np.int32(chunk[0]), tile)
        if len(chunk) < tile:
            padded = np.full(tile, chunk[0], np.int32)
            padded[:len(chunk)] = chunk
        else:
            padded = chunk.astype(np.int32)
        return self._jit_result(self.state, jnp.asarray(padded))

    def _gather_tiled(self, slots: np.ndarray) -> list:
        n = len(slots)
        tile = self.FIRE_TILE
        futures = []
        for i in range(0, n, tile):
            chunk = slots[i:i + tile]
            # dispatch all tiles before materializing any — transfers
            # overlap device compute on the async dispatch queue
            futures.append((self._fire_tile_future(chunk, tile),
                            len(chunk)))
        if TELEMETRY.enabled and futures:
            t0 = _perf_ns()
            outs = [np.asarray(f)[:ln] for f, ln in futures]
            TELEMETRY.record_transfer(
                "d2h", sum(o.nbytes for o in outs), t0, _perf_ns(),
                "window.fire")
            TELEMETRY.note_fire_read(len(futures))
        else:
            outs = [np.asarray(f)[:ln] for f, ln in futures]
        return np.concatenate(outs).tolist() if outs else []

    def _gather_tiled_np(self, slots: np.ndarray) -> np.ndarray:
        n = len(slots)
        tile = self.FIRE_TILE
        futures = []
        for i in range(0, n, tile):
            chunk = slots[i:i + tile]
            futures.append((self._fire_tile_future(chunk, tile),
                            len(chunk)))
        if TELEMETRY.enabled and futures:
            t0 = _perf_ns()
            outs = [np.asarray(f)[:ln] for f, ln in futures]
            TELEMETRY.record_transfer(
                "d2h", sum(o.nbytes for o in outs), t0, _perf_ns(),
                "window.fire")
            TELEMETRY.note_fire_read(len(futures))
            return np.concatenate(outs)
        return np.concatenate([np.asarray(f)[:ln] for f, ln in futures])

    def _clear_tiled(self, slots: np.ndarray) -> None:
        n = len(slots)
        tile = self.FIRE_TILE
        for i in range(0, n, tile):
            chunk = slots[i:i + tile]
            if self._is_contiguous_tile(chunk, tile):
                # contiguous: one dynamic_update_slice of the fill
                # block instead of a 4KB-per-row scatter
                self.state = self._jit_clear_contig(
                    self.state, np.int32(chunk[0]), tile)
                continue
            padded = np.full(tile, chunk[0], np.int32)
            padded[:len(chunk)] = chunk
            self.state = self._jit_clear(self.state, jnp.asarray(padded))

    def block_until_ready(self) -> None:
        jax.tree_util.tree_map(lambda a: a.block_until_ready(), self.state)


class ScalarHeapTumblingWindows:
    """The per-record heap baseline the north star measures against:
    dict-of-dicts accumulator tables updated one record at a time with
    the scalar AggregateFunction contract — the same work
    HeapAggregatingState.add does (HeapAggregatingState.java:80-89)."""

    def __init__(self, aggregate, window_size_ms: int,
                 emit: Optional[Callable] = None):
        self.agg = aggregate
        self.size = window_size_ms
        self.windows: Dict[int, Dict[Any, Any]] = {}
        self.watermark = -(2**63)
        self.emit = emit
        self.emitted: List[Tuple[Any, Any, int, int]] = []
        self.num_late_dropped = 0

    def process(self, key, timestamp: int, value=None) -> None:
        start = timestamp - timestamp % self.size
        if start + self.size - 1 <= self.watermark:
            self.num_late_dropped += 1
            return
        table = self.windows.get(start)
        if table is None:
            table = {}
            self.windows[start] = table
        acc = table.get(key)
        if acc is None:
            acc = self.agg.create_accumulator()
        table[key] = self.agg.add(value, acc)

    def advance_watermark(self, watermark: int) -> int:
        self.watermark = watermark
        fired = 0
        for start in sorted(self.windows):
            if start + self.size - 1 > watermark:
                continue
            table = self.windows.pop(start)
            end = start + self.size
            for key, acc in table.items():
                res = self.agg.get_result(acc)
                if self.emit is not None:
                    self.emit(key, res, start, end)
                else:
                    self.emitted.append((key, res, start, end))
            fired += len(table)
        return fired


class VectorizedSlidingWindows(_ScratchMergeMixin, VectorizedTumblingWindows):
    """Batched keyBy().window(SlidingEventTimeWindows).aggregate(agg) —
    pane-composed (config #3: 10s/1s t-digest at 10M keys).

    Where the reference writes each record into size/slide separate
    window states (WindowOperator.processElement loops the assigned
    windows, multiplying state and writes by the overlap factor —
    SlidingEventTimeWindows.assignWindows), this engine aggregates each
    record ONCE into its slide-sized pane and composes a window's
    result at fire time by merging its size/slide panes on device
    (agg.merge_slots — mergeability is what the sketch kernels are
    built around).  Ingest cost is tumbling-at-slide-granularity
    regardless of overlap; the overlap factor is paid only on the
    per-key fire path, as device merges.

    Semantics match WindowOperator + SlidingEventTimeWindows with
    lateness 0, differentially tested against the scalar operator."""

    def __init__(self, aggregate: DeviceAggregateFunction,
                 window_size_ms: int, slide_ms: int,
                 initial_capacity: int = 1 << 16,
                 microbatch: int = 1 << 17,
                 emit: Optional[Callable[[Any, Any, int, int], None]] = None):
        if window_size_ms % slide_ms != 0:
            raise ValueError("window size must be a multiple of the slide "
                             "(pane composition; ref: the aligned-window "
                             "precondition)")
        super().__init__(aggregate, slide_ms, initial_capacity, microbatch,
                         emit)
        self.window_size = window_size_ms
        self.slide = slide_ms
        self.n_panes = window_size_ms // slide_ms
        self.lateness_horizon = window_size_ms
        self._fired_horizon = -(2**63)  # last watermark fires ran at
        self._jit_merge = traced_jit(self.agg.merge_slots,
                                     name="window.merge", donate_argnums=0)

    def advance_watermark(self, watermark: int) -> int:
        """Fire every sliding window with end-1 in
        (previous watermark, watermark]; prune panes no window needs."""
        prev = self._fired_horizon
        self._fired_horizon = watermark
        self.watermark = watermark
        self.flush()
        fired = 0
        if not self.windows:
            return 0
        # candidate window starts W on the slide grid with
        #   W + size - 1 <= wm      (due now)
        #   W + size - 1 > prev     (not fired on an earlier call)
        #   W >= min_pane - size + slide  (contains at least one pane)
        min_pane = min(self.windows)
        max_pane = max(self.windows)
        # no window starting after the last data-bearing pane holds data
        hi = min(watermark - self.window_size + 1, max_pane)
        start_from = max(min_pane - self.window_size + self.slide,
                         prev - self.window_size + 2)
        first = -(-start_from // self.slide) * self.slide  # ceil to grid
        if first > hi:
            self._prune_panes(watermark)
            return 0
        for W in range(first, hi + 1, self.slide):
            panes = [self.windows[p]
                     for p in range(W, W + self.window_size, self.slide)
                     if p in self.windows and self.windows[p].slot_list]
            if not panes:
                continue
            end = W + self.window_size
            if len(panes) == 1:
                # single-pane window: gather straight from pane slots
                shard = panes[0]
                slots = shard.all_slots()
                keys = shard.all_keys()
                self._emit_fire(keys, slots, W, end)
                fired += len(slots)
                continue
            # union the panes' keys into fresh fire slots, merging on
            # device pane by pane
            union_index = make_slot_index(
                sum(p.n_keys for p in panes))
            union_key_list: List[np.ndarray] = []
            union_slot_list: List[np.ndarray] = []
            for shard in panes:
                ph = shard.all_hashes()
                pslots = shard.all_slots()
                uslots, _, first_idx = union_index.lookup_or_insert(
                    ph, self.arena.alloc)
                if len(first_idx):
                    union_key_list.append(shard.all_keys()[first_idx])
                    union_slot_list.append(uslots[first_idx])
                self._merge_tiled(uslots, pslots)
            union_slots = (np.concatenate(union_slot_list)
                           if union_slot_list else np.empty(0, np.int64))
            union_keys = (np.concatenate(union_key_list)
                          if union_key_list else np.empty(0, object))
            union_slots = self._emit_fire(union_keys, union_slots, W, end)
            fired += len(union_slots)
            self._clear_tiled(union_slots)
            self.arena.release(union_slots)
        self._prune_panes(watermark)
        if TELEMETRY.enabled:
            TELEMETRY.note_windows_fired(fired)
        return fired

    def _prune_panes(self, watermark: int) -> None:
        """Pane [P, P+slide) is dead once its last containing window
        [P, P+size) fired, i.e. watermark >= P+size-1."""
        for P in sorted(self.windows):
            if P + self.window_size - 1 > watermark:
                break
            shard = self.windows.pop(P)
            slots = shard.all_slots()
            if len(slots):
                slots = np.sort(slots)
                self._clear_tiled(slots)
                self.arena.release(slots)


# ---------------------------------------------------------------------
# engine snapshots (checkpoint integration for DeviceWindowOperator)
# ---------------------------------------------------------------------

def _snapshot_arena(arena: _SlotArena) -> dict:
    return {"capacity": arena.capacity, "next": arena.next,
            "free": [np.array(a, np.int64) for a in arena.free]}


def _restore_arena(snap: dict) -> _SlotArena:
    arena = _SlotArena(snap["capacity"])
    arena.next = snap["next"]
    arena.free = [np.array(a, np.int64) for a in snap["free"]]
    return arena


def _snapshot_shard(sh: _WindowShard) -> dict:
    # index state snapshots as occupied (hash, slot) pairs — a format
    # both index implementations (numpy / native C++) restore from
    if hasattr(sh.index, "export"):
        ih, isl = sh.index.export()
    else:
        occ = sh.index.table_hash != _EMPTY
        ih = sh.index.table_hash[occ].copy()
        isl = sh.index.table_slot[occ].copy()
    return {"start": sh.start, "keys": sh.all_keys().copy(),
            "slots": sh.all_slots().copy(), "hashes": sh.all_hashes().copy(),
            "index_hashes": ih, "index_slots": isl}


def _restore_shard(snap: dict) -> _WindowShard:
    sh = _WindowShard(snap["start"])
    ks = snap["keys"]
    if not isinstance(ks, np.ndarray):  # legacy list-format snapshot
        arr = np.empty(len(ks), object)
        arr[:] = ks
        ks = arr
    sh.key_list = [ks] if len(ks) else []
    sh.slot_list = [np.array(snap["slots"], np.int64)]
    sh.hash_list = [np.array(snap["hashes"], np.uint64)]
    if "index_hash" in snap:  # legacy full-table snapshot format
        ih_t = np.array(snap["index_hash"], np.uint64)
        occ = ih_t != _EMPTY
        ih = ih_t[occ]
        isl = np.array(snap["index_slot"], np.int64)[occ]
    else:
        ih = np.array(snap["index_hashes"], np.uint64)
        isl = np.array(snap["index_slots"], np.int64)
    sh.index = make_slot_index(2 * max(len(ih), 8))
    if hasattr(sh.index, "set_bulk"):
        sh.index.set_bulk(ih, isl)
    else:
        sh.index._grow(len(ih))
        sh.index._insert_existing(ih, isl)
    return sh


def _tumbling_snapshot(self) -> dict:
    """Device state lands as host numpy (the device→host DMA half of
    the checkpoint, SURVEY §5 checkpoint row); host-side indexes ride
    along as plain arrays."""
    self.flush()
    if TELEMETRY.enabled:
        t0 = _perf_ns()
        host_state = {k: np.asarray(v) for k, v in self.state.items()}
        TELEMETRY.record_transfer(
            "d2h", sum(a.nbytes for a in host_state.values()),
            t0, _perf_ns(), "window.snapshot")
    else:
        host_state = {k: np.asarray(v) for k, v in self.state.items()}
    return {
        "state": host_state,
        "capacity": self.capacity,
        "arena": _snapshot_arena(self.arena),
        "watermark": self.watermark,
        "num_late_dropped": self.num_late_dropped,
        "windows": {int(s): _snapshot_shard(sh)
                    for s, sh in self.windows.items()},
        "fired_horizon": getattr(self, "_fired_horizon", None),
        "scratch": getattr(self, "_scratch_slot_id", None),
    }


def _tumbling_restore(self, snap: dict) -> None:
    self.capacity = snap["capacity"]
    self.state = {k: jnp.asarray(v) for k, v in snap["state"].items()}
    self.arena = _restore_arena(snap["arena"])
    self.watermark = snap["watermark"]
    self.num_late_dropped = snap["num_late_dropped"]
    self.windows = {int(s): _restore_shard(sh)
                    for s, sh in snap["windows"].items()}
    if snap.get("fired_horizon") is not None:
        self._fired_horizon = snap["fired_horizon"]
    if snap.get("scratch") is not None:
        self._scratch_slot_id = snap["scratch"]
    self._p_slots.clear()
    self._p_values.clear()
    self._p_hi.clear()
    self._p_lo.clear()
    self._p_count = 0


VectorizedTumblingWindows.snapshot = _tumbling_snapshot
VectorizedTumblingWindows.restore = _tumbling_restore

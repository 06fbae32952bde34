"""The `tpu` backend's device state as it was before its bulk paths,
kept as the reference the tests hold them to, bit for bit; the method
bodies are the old ones, word for word.

The spill tier before the bulk tier (PR 31): one dict of numpy rows
per spilled key, one `state.upload` dispatch per promotion, one Python
walk of every slot per eviction (`tests/test_spill_tier.py`).  The
slot index before it was keyed by namespace (PR 32): one flat
``(key, namespace) → slot`` dict, `slot_meta` a list of those tuples,
the stamps a list, and one `_slot_for` call, one tuple and one probe
per event, per fired key and per cleared key
(`tests/test_slot_index_bulk.py`).  The pending micro-batch before it
was columns (PR 36): three lists of Python values, `stable_hash64` a
value at a time, two lane lists turned into arrays at the flush
(`tests/test_state_hash_column.py`).
"""

from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.core.keygroups import assign_to_key_group, stable_hash64
from flink_tpu.ops.device_agg import DeviceAggregateFunction
from flink_tpu.runtime.device_stats import TELEMETRY
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu.state.heap_backend import split_column_by_key_group
from flink_tpu.state.stats import STATE_STATS
from flink_tpu.state.tpu_backend import (
    DeviceAggregatingState,
    TpuKeyedStateBackend,
    _pad_slots,
    _perf_ns,
    _round_up_pow2,
)


class PerKeySpillState(DeviceAggregatingState):
    """`DeviceAggregatingState` with the per-key spill tier."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        #: (key, namespace) → slot
        self.slot_index: Dict[Tuple[Any, Any], int] = {}
        #: slot → (key, namespace) (None = free)
        self.slot_meta: List[Optional[Tuple[Any, Any]]] = \
            [None] * self.capacity
        del self.slot_key, self.slot_ns, self._slot_live
        self._access_stamp: List[int] = [0] * self.capacity
        self._slot_flushed = bytearray(self.capacity)
        self._pending_slots: List[int] = []
        self._pending_values: List[Any] = []
        self._pending_hi: List[int] = []
        self._pending_lo: List[int] = []
        #: (key, namespace) → {component: numpy row}
        self.host_tier: Dict[Tuple[Any, Any], Dict[str, np.ndarray]] = {}
        self._spilled = self.host_tier

    def reset(self) -> None:
        dstate = self
        dstate.device_state = dstate.agg.init_state(dstate.capacity)
        dstate.slot_index.clear()
        dstate.slot_meta = [None] * dstate.capacity
        dstate._free = list(range(dstate.capacity - 1, -1, -1))
        dstate._slot_flushed = bytearray(dstate.capacity)
        dstate.host_tier.clear()
        dstate._pending_slots.clear()
        dstate._pending_values.clear()
        dstate._pending_hi.clear()
        dstate._pending_lo.clear()

    def _slot_for(self, key, namespace, create: bool = True) -> Optional[int]:
        entry = (key, namespace)
        slot = self.slot_index.get(entry)
        if slot is None and entry in self._spilled:
            slot = self._promote(entry)
        if slot is None and create:
            if not self._free:
                self._make_room()
            slot = self._free.pop()
            self.slot_index[entry] = slot
            self.slot_meta[slot] = entry
        if slot is not None:
            self._clock += 1
            self._access_stamp[slot] = self._clock
        return slot

    def add(self, value) -> None:
        slot = self._slot_for(self._backend.current_key, self._namespace)
        self._pending_slots.append(slot)
        value = self.agg.extract_value(value)
        if self.agg.needs_value:
            self._pending_values.append(value)
        if self.agg.needs_value_hash:
            h = stable_hash64(value)
            self._pending_hi.append(h >> 32)
            self._pending_lo.append(h & 0xFFFFFFFF)
        if len(self._pending_slots) >= self.microbatch:
            self._flush()

    def _grow(self, new_capacity: int) -> None:
        self._flush()
        with self._device_lock:
            self.device_state = self.agg.grow_state(self.device_state,
                                                    new_capacity)
        self._free.extend(range(new_capacity - 1, self.capacity - 1, -1))
        self._access_stamp.extend([0] * (new_capacity - self.capacity))
        self._slot_flushed.extend(bytes(new_capacity - self.capacity))
        self.slot_meta.extend([None] * (new_capacity - self.capacity))
        self.capacity = new_capacity

    def add_batch(self, keys: Iterable[Any], namespace, values,
                  namespaces=None, pre_extracted: bool = False) -> None:
        """Vectorized write: one slot lookup loop, no per-record method
        dispatch.  `namespace` is ONE namespace shared by the whole
        batch (a window tuple is a single namespace); pass a parallel
        sequence via `namespaces=` to override per record.  `values` is
        a sequence/ndarray parallel to keys; `pre_extracted=True` means
        the caller already ran extract_value/extract_column over it (a
        numeric column straight off a RecordBatch)."""
        keys = list(keys)
        if self.max_device_slots is not None \
                and len(keys) > self.microbatch:
            # capped backend: resolve slots in microbatch-sized chunks
            # so an eviction triggered late in the loop can never take
            # a slot resolved earlier in the SAME chunk (chunk size <=
            # the eviction-protected stamp window)
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
        with tracer.phase("state.add.slots"):
            if self._spilled:
                self._promote_spilled(keys, namespace, namespaces)
            slot_for = self._slot_for
            if namespaces is None:
                slots = [slot_for(k, namespace) for k in keys]
            else:
                slots = [slot_for(k, namespaces[i])
                         for i, k in enumerate(keys)]
            self._pending_slots.extend(slots)
        with tracer.phase("state.add.hash"):
            extract = self.agg.extract_value
            # overridden on the class or per-instance (an
            # instance-attached plain function has no __func__)
            if not pre_extracted and getattr(
                    extract, "__func__",
                    None) is not DeviceAggregateFunction.extract_value:
                values = [extract(v) for v in values]
            if self.agg.needs_value:
                self._pending_values.extend(values)
            if self.agg.needs_value_hash:
                hi = self._pending_hi
                lo = self._pending_lo
                for v in values:
                    h = stable_hash64(v)
                    hi.append(h >> 32)
                    lo.append(h & 0xFFFFFFFF)
        if len(self._pending_slots) >= self.microbatch:
            self._flush()

    def _flush_locked(self, n: int) -> None:
        padded = _round_up_pow2(n)
        slots = np.zeros(padded, np.int32)
        slots[:n] = self._pending_slots
        mask = np.zeros(padded, bool)
        mask[:n] = True
        if self.agg.needs_value:
            values = np.zeros(padded, self.agg.value_dtype)
            values[:n] = np.asarray(self._pending_values, self.agg.value_dtype)
        else:
            values = np.zeros(padded, self.agg.value_dtype)
        if self.agg.needs_value_hash:
            hi = np.zeros(padded, np.uint32)
            lo = np.zeros(padded, np.uint32)
            hi[:n] = np.asarray(self._pending_hi, np.uint64).astype(np.uint32)
            lo[:n] = np.asarray(self._pending_lo, np.uint64).astype(np.uint32)
        else:
            hi = np.zeros(padded, np.uint32)
            lo = np.zeros(padded, np.uint32)
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
        STATE_STATS.note_flush(n)
        for s_ in self._pending_slots:
            self._slot_flushed[s_] = 1
        self._pending_slots.clear()
        self._pending_values.clear()
        self._pending_hi.clear()
        self._pending_lo.clear()

    def query_by_key(self, key, namespace):
        """Queryable-state read from a FOREIGN thread (ref:
        AbstractKeyedStateBackend.java:382-389 getPartitionedState for
        queries + KvStateServerHandler).  Dirty-read semantics match
        the heap path: pending (unflushed) adds are invisible; no
        owner-side structures mutate (no promotion, no access-stamp
        touch).  The device gather serializes against state swaps via
        the device lock."""
        entry = (key, namespace)
        with self._device_lock:
            slot = self.slot_index.get(entry)
            if slot is not None and not self._slot_flushed[slot]:
                # the key's first adds are still pending: invisible
                # (matches the heap path's None-for-absent contract)
                slot = None
            if slot is not None:
                out = np.asarray(self._jit_result(
                    self.device_state,
                    jnp.asarray(np.array([slot], np.int32))))[0]
                return out.item() if np.ndim(out) == 0 else out
        row = self.host_tier.get(entry)
        if row is not None:
            # spilled entry: finalize its single row host-side (lift
            # to a 1-slot state; compiles once per aggregate)
            state1 = {name: jnp.asarray(val)[None]
                      for name, val in row.items()}
            out = np.asarray(self._jit_result(
                state1, jnp.asarray(np.zeros(1, np.int32))))[0]
            return out.item() if np.ndim(out) == 0 else out
        return None

    def merge_namespaces(self, target, sources) -> None:
        """Session-window merge: device merge_slots(dst, src), then
        free source slots (ref: mergeNamespaces,
        WindowOperator.java:338 / MergingWindowSet.java:156)."""
        key = self._backend.current_key
        self._flush()
        # spilled sources participate in the merge: promote them first
        for src in sources:
            if (key, src) in self.host_tier:
                self._promote((key, src))
        if (key, target) in self.host_tier:
            self._promote((key, target))
        # touch every source slot BEFORE any allocation below: the
        # target slot allocation may need to make room, and eviction
        # must not take a slot this merge still references (fresh
        # stamps fall inside _evict_cold's protected window; slots
        # stay fully registered in slot_index/slot_meta until after
        # the allocation, so eviction bookkeeping stays consistent)
        live_sources = []
        for src in sources:
            s = self.slot_index.get((key, src))
            if s is not None:
                self._clock += 1
                self._access_stamp[s] = self._clock
                live_sources.append((src, s))
        # don't materialize a target slot unless some source has state
        # (matches heap: merging all-empty namespaces leaves no state)
        if not live_sources:
            return  # nothing to fold in; target (if any) stays as-is
        dst = self._slot_for(key, target)
        src_slots = []
        for src, s in live_sources:
            del self.slot_index[(key, src)]
            if s != dst:
                src_slots.append(s)
                self.slot_meta[s] = None
        if not src_slots:
            return
        dsts = np.full(len(src_slots), dst, np.int32)
        srcs = np.array(src_slots, np.int32)
        with self._device_lock:
            self.device_state = self._jit_merge(
                self.device_state, jnp.asarray(dsts), jnp.asarray(srcs))
            self.device_state = self._jit_clear(self.device_state,
                                                jnp.asarray(srcs))
            self._slot_flushed[dst] = 1
            for s_ in src_slots:
                self._slot_flushed[s_] = 0
        self._free.extend(src_slots)

    def merge_namespaces_batch(self, merges) -> None:
        """Batched session merge: `merges` is a list of
        (key, target_namespace, [source_namespaces]).  One flush up
        front, then the whole merge set runs in ROUNDS through the
        jit(vmap(agg.merge)) pairwise kernel — round r folds each
        target's r-th live source, so every dispatch has UNIQUE
        destination slots (distinct merges own distinct (key, target)
        slots) — and one clear frees every source slot at the end.
        Observable state after this call is identical to running
        merge_namespaces per (key, target)."""
        self._flush()
        plans = []  # (dst_slot, [src_slots])
        for key, target, sources in merges:
            for src in sources:
                if (key, src) in self.host_tier:
                    self._promote((key, src))
            if (key, target) in self.host_tier:
                self._promote((key, target))
            live = []
            for src in sources:
                s = self.slot_index.get((key, src))
                if s is not None:
                    self._clock += 1
                    self._access_stamp[s] = self._clock
                    live.append((src, s))
            if not live:
                continue
            dst = self._slot_for(key, target)
            srcs = []
            for src, s in live:
                del self.slot_index[(key, src)]
                if s != dst:
                    srcs.append(s)
                    self.slot_meta[s] = None
            if srcs:
                plans.append((dst, srcs))
        if not plans:
            return
        rounds = max(len(srcs) for _, srcs in plans)
        all_srcs: List[int] = []
        with self._device_lock:
            for r in range(rounds):
                dsts = [dst for dst, srcs in plans if len(srcs) > r]
                srcs = [srcs[r] for _, srcs in plans if len(srcs) > r]
                self.device_state = self._jit_merge_rows(
                    self.device_state,
                    jnp.asarray(np.array(dsts, np.int32)),
                    jnp.asarray(np.array(srcs, np.int32)))
                all_srcs.extend(srcs)
            self.device_state = self._jit_clear(
                self.device_state, jnp.asarray(np.array(all_srcs, np.int32)))
            for dst, _ in plans:
                self._slot_flushed[dst] = 1
            for s_ in all_srcs:
                self._slot_flushed[s_] = 0
        self._free.extend(all_srcs)

    def active_entries(self) -> Iterable[Tuple[Any, Any]]:
        yield from self.slot_index.keys()
        yield from self.host_tier.keys()

    def _promote_spilled(self, keys, namespace, namespaces) -> None:
        """No batch pre-pass: `_slot_for` promotes key by key."""

    def _make_room(self) -> None:
        """No free slots: grow HBM state, or — at the device budget —
        spill the coldest quarter of slots to the host tier (the
        RocksDB-disk-residency role; SURVEY §7 'state larger than
        HBM')."""
        if (self.max_device_slots is None
                or self.capacity * 2 <= self.max_device_slots):
            self._grow(self.capacity * 2)
            return
        self._evict_cold(max(1, self.capacity // 4))

    def _evict_cold(self, n: int) -> None:
        self._flush()
        # never evict recently touched slots: a batch mid-assembly
        # references up to `microbatch` freshly assigned slots (the
        # chunked add_batch bound; get_batch never allocates), and a
        # merge mid-flight re-stamps its sources just before
        # allocating the target — the +16 margin covers the merge's
        # source set
        protected = self._clock - (2 * self.microbatch + 16)
        candidates = [(self._access_stamp[s], s)
                      for s, meta in enumerate(self.slot_meta)
                      if meta is not None
                      and self._access_stamp[s] < protected]
        if not candidates:
            # everything is hot: grow past the budget rather than
            # corrupt in-flight batches (soft cap)
            self._grow(self.capacity * 2)
            return
        candidates.sort()
        victims = [s for _, s in candidates[:n]]
        idx = np.array(victims, np.int32)
        if TELEMETRY.enabled:
            t0 = _perf_ns()
            host_rows = {name: np.asarray(arr[jnp.asarray(idx)])
                         for name, arr in self.device_state.items()}
            TELEMETRY.record_transfer(
                "d2h", sum(a.nbytes for a in host_rows.values()),
                t0, _perf_ns(), "state.evict")
        else:
            host_rows = {name: np.asarray(arr[jnp.asarray(idx)])
                         for name, arr in self.device_state.items()}
        for i, s in enumerate(victims):
            entry = self.slot_meta[s]
            self.host_tier[entry] = {name: host_rows[name][i]
                                     for name in host_rows}
            del self.slot_index[entry]
            self.slot_meta[s] = None
        with self._device_lock:
            self.device_state = self._jit_clear(self.device_state,
                                                jnp.asarray(idx))
            for s_ in victims:
                self._slot_flushed[s_] = 0
        self._free.extend(victims)
        self.evictions += len(victims)

    def _promote(self, entry) -> int:
        """Host-tier entry accessed: lift its row back into HBM
        (donated single-row upload — in-place, no full-array copy).
        The index entry publishes only AFTER the upload, inside the
        lock: a concurrent query must see either the spilled row or
        the uploaded slot, never a zeroed in-between slot."""
        if not self._free:
            self._make_room()
        slot = self._free.pop()
        row = self.host_tier[entry]
        with self._device_lock:
            if TELEMETRY.enabled:
                t0 = _perf_ns()
                self.device_state = self._jit_upload(
                    self.device_state, jnp.int32(slot),
                    {name: jnp.asarray(val) for name, val in row.items()})
                TELEMETRY.record_transfer(
                    "h2d",
                    sum(getattr(v, "nbytes", 0) for v in row.values()),
                    t0, _perf_ns(), "state.promote")
            else:
                self.device_state = self._jit_upload(
                    self.device_state, jnp.int32(slot),
                    {name: jnp.asarray(val) for name, val in row.items()})
            del self.host_tier[entry]
            self.slot_index[entry] = slot
            self._slot_flushed[slot] = 1
        self.slot_meta[slot] = entry
        # freshly promoted slots are HOT: stamp them or a later
        # promotion in the same batch could evict them right back
        self._clock += 1
        self._access_stamp[slot] = self._clock
        self.promotions += 1
        return slot

    def get_batch(self, keys, namespace, namespaces=None) -> Tuple[np.ndarray, np.ndarray]:
        """Gather results for many (key, namespace) pairs in ONE device
        round-trip: one pending-ring flush, one fused jit gather per
        tile of slots, one wait — the batched window-fire read.  Spill-tier
        rows are finalized from their host-resident accumulators
        WITHOUT promotion (a fire is a read; lifting cold rows into
        HBM per fired window would re-pay the per-row transfer tax
        this path exists to amortize).  No slot allocation or eviction
        can happen here, so no chunking is needed.  Returns
        (results, found_mask); namespace semantics as in `add_batch`."""
        tracer = get_tracer()
        with tracer.phase("state.get.lookup"):
            keys = list(keys)
            n = len(keys)
            slot_index = self.slot_index
            host_tier = self.host_tier
            slots = np.zeros(n, np.int32)
            found = np.zeros(n, bool)
            spill_idx: List[int] = []
            spill_rows: List[Dict[str, np.ndarray]] = []
            for i, k in enumerate(keys):
                entry = (k, namespace if namespaces is None
                         else namespaces[i])
                s = slot_index.get(entry)
                if s is not None:
                    slots[i] = s
                    found[i] = True
                    # reads stamp the LRU clock exactly as scalar get()
                    self._clock += 1
                    self._access_stamp[s] = self._clock
                    continue
                row = host_tier.get(entry)
                if row is not None:
                    spill_idx.append(i)
                    spill_rows.append(row)
                    found[i] = True
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
        if spill_idx:
            res[spill_idx] = self._finalize_spilled(spill_rows)
        return res, found

    def _finalize_spilled(self, rows: List[Dict[str, np.ndarray]]) -> np.ndarray:
        """Result extraction for spill-tier rows without promotion:
        stack the host-resident accumulator rows into a pow2-padded
        [m, ...] state and run the SAME jit result kernel over it —
        bit-identical finalization (query_by_key's single-row idiom,
        batched), zero HBM slot traffic."""
        m = len(rows)
        padded = _round_up_pow2(m)
        state = {}
        nbytes_in = 0
        for name in self.device_state:
            col = np.stack([r[name] for r in rows])
            if padded != m:
                pad = np.zeros((padded - m,) + col.shape[1:], col.dtype)
                col = np.concatenate([col, pad])
            nbytes_in += col.nbytes
            state[name] = jnp.asarray(col)
        idx = jnp.asarray(np.arange(padded, dtype=np.int32))
        if TELEMETRY.enabled:
            t0 = _perf_ns()
            out = np.asarray(self._jit_result(state, idx))
            TELEMETRY.record_transfer("h2d", nbytes_in, t0, t0,
                                      "state.fire.spill")
            TELEMETRY.record_transfer("d2h", out.nbytes, t0, _perf_ns(),
                                      "state.fire.spill")
        else:
            out = np.asarray(self._jit_result(state, idx))
        return out[:m]

    def clear(self) -> None:
        entry = (self._backend.current_key, self._namespace)
        self.host_tier.pop(entry, None)
        slot = self.slot_index.pop(entry, None)
        if slot is None:
            return
        self._flush()
        with self._device_lock:
            self.device_state = self._jit_clear(
                self.device_state, jnp.asarray(np.array([slot], np.int32)))
            self._slot_flushed[slot] = 0
        self.slot_meta[slot] = None
        self._free.append(slot)

    def clear_batch(self, keys, namespace, namespaces=None) -> None:
        tracer = get_tracer()
        slots = []
        with tracer.phase("state.clear.slots"):
            for i, k in enumerate(keys):
                ns = namespace if namespaces is None else namespaces[i]
                self.host_tier.pop((k, ns), None)
                s = self.slot_index.pop((k, ns), None)
                if s is not None:
                    slots.append(s)
                    self.slot_meta[s] = None
        if not slots:
            return
        self._flush()
        with tracer.phase("state.clear.device"):
            arr = _pad_slots(slots, _round_up_pow2(len(slots)))
            with self._device_lock:
                self.device_state = self._jit_clear(self.device_state,
                                                    jnp.asarray(arr))
                for s_ in slots:
                    self._slot_flushed[s_] = 0
            self._free.extend(slots)

    def snapshot_entries(self) -> Dict[int, List[Tuple[Any, Any, Dict[str, np.ndarray]]]]:
        """Per key group: [(key, namespace, {component: row})]."""
        self._flush()
        if TELEMETRY.enabled:
            t0 = _perf_ns()
            host = {name: np.asarray(arr)
                    for name, arr in self.device_state.items()}
            TELEMETRY.record_transfer(
                "d2h", sum(a.nbytes for a in host.values()),
                t0, _perf_ns(), "state.snapshot")
        else:
            host = {name: np.asarray(arr)
                    for name, arr in self.device_state.items()}
        per_kg: Dict[int, List[Tuple[Any, Any, Dict[str, np.ndarray]]]] = defaultdict(list)
        mp = self._backend.max_parallelism
        for (key, namespace), slot in self.slot_index.items():
            kg = assign_to_key_group(key, mp)
            row = {name: host[name][slot] for name in host}
            per_kg[kg].append((key, namespace, row))
        # spilled entries are part of the state too
        for (key, namespace), row in self.host_tier.items():
            kg = assign_to_key_group(key, mp)
            per_kg[kg].append((key, namespace, dict(row)))
        return per_kg

    def restore_entries(self, entries: List[Tuple[Any, Any, Dict[str, np.ndarray]]]) -> None:
        if not entries:
            return
        needed = len(self.slot_index) + len(entries)
        if self.max_device_slots is not None \
                and needed > self.max_device_slots:
            # beyond the device budget: the overflow restores straight
            # into the host tier (promoted lazily on first access)
            budget = max(self.max_device_slots - len(self.slot_index), 0)
            for key, namespace, row in entries[budget:]:
                self.host_tier[(key, namespace)] = dict(row)
            entries = entries[:budget]
            if not entries:
                return
            needed = len(self.slot_index) + len(entries)
        if needed > self.capacity - len(self._pending_slots):
            self._grow(max(self.capacity * 2, _round_up_pow2(needed)))
        slots = []
        rows: Dict[str, List[np.ndarray]] = defaultdict(list)
        for key, namespace, row in entries:
            slot = self._slot_for(key, namespace)
            slots.append(slot)
            for name, val in row.items():
                rows[name].append(val)
        idx = jnp.asarray(np.array(slots, np.int32))
        with self._device_lock:
            new_state = dict(self.device_state)
            for name, vals in rows.items():
                new_state[name] = new_state[name].at[idx].set(
                    jnp.asarray(np.stack(vals)))
            self.device_state = new_state
            for s_ in slots:
                self._slot_flushed[s_] = 1

    def capture(self):
        """The reference reads its snapshot at once."""
        from flink_tpu.state.device_snapshot import ColumnsCapture
        return ColumnsCapture(self.snapshot_columns())

    def snapshot_columns(self) -> Dict[int, Tuple[list, list, Dict[str, np.ndarray]]]:
        """Columnar snapshot: per key group, (keys, namespaces,
        {component: stacked rows}) — ONE host transfer per component,
        ONE fancy-index gather, and the key-group split done in one
        vectorized hash pass (replaces snapshot_entries' per-row dict
        building + per-row assign_to_key_group)."""
        self._flush()
        keys: List[Any] = []
        nss: List[Any] = []
        slots: List[int] = []
        for (key, namespace), slot in self.slot_index.items():
            keys.append(key)
            nss.append(namespace)
            slots.append(slot)
        if TELEMETRY.enabled:
            t0 = _perf_ns()
            host = {name: np.asarray(arr)
                    for name, arr in self.device_state.items()}
            TELEMETRY.record_transfer(
                "d2h", sum(a.nbytes for a in host.values()),
                t0, _perf_ns(), "state.snapshot")
        else:
            host = {name: np.asarray(arr)
                    for name, arr in self.device_state.items()}
        idx = np.array(slots, np.int32)
        comps = {name: arr[idx] for name, arr in host.items()}
        if self.host_tier:
            spilled = list(self.host_tier.items())
            for (key, namespace), _ in spilled:
                keys.append(key)
                nss.append(namespace)
            spill_cols = {name: np.stack([row[name] for _, row in spilled])
                          for name in host}
            comps = {name: np.concatenate([comps[name], spill_cols[name]])
                     for name in host}
        out: Dict[int, Tuple[list, list, Dict[str, np.ndarray]]] = {}
        mp = self._backend.max_parallelism
        for kg, sel in split_column_by_key_group(keys, mp):
            out[kg] = ([keys[i] for i in sel], [nss[i] for i in sel],
                       {name: arr[sel] for name, arr in comps.items()})
        return out

    def restore_columns(self, keys: list, namespaces: list,
                        comps: Dict[str, np.ndarray]) -> None:
        """Columnar restore: one slot-resolve loop, ONE device upload
        per component (no per-row dict boxing)."""
        n = len(keys)
        if n == 0:
            return
        needed = len(self.slot_index) + n
        if self.max_device_slots is not None \
                and needed > self.max_device_slots:
            # beyond the device budget: the overflow restores straight
            # into the host tier (promoted lazily on first access)
            budget = max(self.max_device_slots - len(self.slot_index), 0)
            for i in range(budget, n):
                self.host_tier[(keys[i], namespaces[i])] = {
                    name: np.asarray(arr[i]) for name, arr in comps.items()}
            keys = keys[:budget]
            namespaces = namespaces[:budget]
            comps = {name: arr[:budget] for name, arr in comps.items()}
            n = budget
            if n == 0:
                return
            needed = len(self.slot_index) + n
        if needed > self.capacity - len(self._pending_slots):
            self._grow(max(self.capacity * 2, _round_up_pow2(needed)))
        slots = np.empty(n, np.int32)
        for i in range(n):
            slots[i] = self._slot_for(keys[i], namespaces[i])
        idx = jnp.asarray(slots)
        with self._device_lock:
            new_state = dict(self.device_state)
            for name, arr in comps.items():
                new_state[name] = new_state[name].at[idx].set(
                    jnp.asarray(np.ascontiguousarray(arr)))
            self.device_state = new_state
            for s_ in slots:
                self._slot_flushed[int(s_)] = 1


class PerKeySpillBackend(TpuKeyedStateBackend):
    def create_aggregating_state(self, d):
        assert isinstance(d.aggregate_function, DeviceAggregateFunction)
        st = PerKeySpillState(self, d, self.initial_capacity,
                              self.microbatch,
                              max_device_slots=self.max_device_slots)
        self._device_states[d.name] = st
        return st

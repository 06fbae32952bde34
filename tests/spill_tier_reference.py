"""The `tpu` backend's spill tier as it was before the bulk tier: one
dict of numpy rows per spilled key, one `state.upload` dispatch per
promotion, one Python walk of every slot per eviction.  Kept as the
reference `tests/test_spill_tier.py` holds the bulk paths to, bit for
bit: the method bodies are the old ones, word for word.
"""

from collections import defaultdict
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.core.keygroups import assign_to_key_group
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
        #: (key, namespace) → {component: numpy row}
        self.host_tier: Dict[Tuple[Any, Any], Dict[str, np.ndarray]] = {}
        self._spilled = self.host_tier

    def _promote_spilled(self, keys, namespace, namespaces) -> None:
        """No batch pre-pass: `_slot_for` promotes key by key."""

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

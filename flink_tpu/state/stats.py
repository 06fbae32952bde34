"""Process-wide state-pressure statistics.

One mutable singleton (`STATE_STATS`) counts batched-vs-row state
ingest and device flush traffic, plus a weak registry of the live
device-resident aggregation states so gauges can report slots in use,
spill-tier size, evictions and pending-ring depth without the backend
holding a reference to the metrics plane (mirrors NET_STATS in
runtime/netchannel.py).
"""

from __future__ import annotations

import threading
import weakref
from collections import deque


class StateStats:
    """Counters for the keyed-state ingest/flush hot path."""

    __slots__ = (
        "batch_rows", "row_fallback_rows", "batch_calls",
        "row_fallback_calls", "flush_batches", "flush_rows",
        "flush_row_form_batches", "flush_sizes", "result_rows",
        "result_padded_rows", "snapshot_columns", "snapshot_rows",
        "snapshot_captures", "snapshot_tiles", "snapshot_bytes_device",
        "snapshot_bytes_written",
        "evicted_rows", "promoted_rows", "spill_fired_rows",
        "budget_overruns", "bulk_probe_rows", "per_key_probe_rows",
        "int_table_rows", "int_table_demotions",
        "merged_rows",
        "hash_column_rows", "hash_per_value_rows",
        "per_state_batch_rows", "per_state_batch_calls",
        "per_state_fallback_rows", "per_state_fallback_calls",
    )

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        #: rows ingested through a backend-native add_batch path
        self.batch_rows = 0
        #: rows that fell back to per-row state.add inside add_batch
        self.row_fallback_rows = 0
        self.batch_calls = 0
        self.row_fallback_calls = 0
        #: device micro-batch flushes and the rows they carried
        self.flush_batches = 0
        self.flush_rows = 0
        #: flushes whose `state.update` ran in place (the quantile
        #: sketch's tile kernel, on a TPU) where the others scatter cells
        self.flush_row_form_batches = 0
        #: recent flush batch sizes (for mean/max gauges)
        self.flush_sizes = deque(maxlen=512)
        #: rows batched fire reads asked `state.result` for, and rows it
        #: was dispatched at (each read padded to a shape bucket)
        self.result_rows = 0
        self.result_padded_rows = 0
        #: snapshot rows serialized as columns vs boxed per-row
        self.snapshot_columns = 0
        self.snapshot_rows = 0
        #: the tpu backend's captures at a barrier: how many, the tiles
        #: of rows their device programs wrote into buffers of their
        #: own, those buffers' bytes, and the bytes of the key-group
        #: chunks the captures were encoded into (a writer resolves a
        #: capture later: the last grows then).  All stay 0 while
        #: nothing snapshots
        self.snapshot_captures = 0
        self.snapshot_tiles = 0
        self.snapshot_bytes_device = 0
        self.snapshot_bytes_written = 0
        #: the tpu backend's spill tier: rows evicted to host RAM, rows
        #: promoted back, rows a batched fire finalised from there, and
        #: times the device budget was overrun (nothing cold to evict)
        self.evicted_rows = 0
        self.promoted_rows = 0
        self.spill_fired_rows = 0
        self.budget_overruns = 0
        #: rows whose slot the tpu backend resolved by a bulk probe of
        #: a namespace's table (add_batch / get_batch / clear_batch) /
        #: by the per-key door (`_slot_for`, scalar clear)
        self.bulk_probe_rows = 0
        self.per_key_probe_rows = 0
        #: of the bulk-probed rows, those the slot index took as one
        #: int64 column (`state/slot_index.py`: one C call on the
        #: namespace's integer table, or no table there at all; the
        #: rest went through a dict, a call per key), and integer
        #: tables that met a key they could not hold and became dicts
        self.int_table_rows = 0
        self.int_table_demotions = 0
        #: source slots the tpu backend's batched session merge
        #: (`merge_namespaces_batch`) folded into their targets
        self.merged_rows = 0
        #: value hashes the tpu backend took over a whole integer
        #: column of `add_batch` in one pass / from `stable_hash64` a
        #: value at a time (any other column, and the scalar `add`)
        self.hash_column_rows = 0
        self.hash_per_value_rows = 0
        #: the same batch/fallback split ATTRIBUTED by state name, so a
        #: fallback is traceable to the state that caused it; the
        #: aggregate counters above stay authoritative for the
        #: established gauge names
        self.per_state_batch_rows = {}
        self.per_state_batch_calls = {}
        self.per_state_fallback_rows = {}
        self.per_state_fallback_calls = {}

    def note_batch(self, name: str, n: int) -> None:
        """One backend-native add_batch/get_batch call of `n` rows on
        state `name` (aggregates + the per-state split in one call)."""
        self.batch_calls += 1
        self.batch_rows += n
        self.per_state_batch_calls[name] = \
            self.per_state_batch_calls.get(name, 0) + 1
        self.per_state_batch_rows[name] = \
            self.per_state_batch_rows.get(name, 0) + n

    def note_fallback(self, name: str, n: int) -> None:
        """One per-row fallback pass of `n` rows on state `name`."""
        self.row_fallback_calls += 1
        self.row_fallback_rows += n
        self.per_state_fallback_calls[name] = \
            self.per_state_fallback_calls.get(name, 0) + 1
        self.per_state_fallback_rows[name] = \
            self.per_state_fallback_rows.get(name, 0) + n

    def note_flush(self, n: int, row_form: bool = False) -> None:
        self.flush_batches += 1
        self.flush_rows += n
        self.flush_row_form_batches += row_form
        self.flush_sizes.append(n)

    def note_result(self, n: int, padded: int) -> None:
        self.result_rows += n
        self.result_padded_rows += padded

    def flush_size_mean(self) -> float:
        sizes = self.flush_sizes
        return (sum(sizes) / len(sizes)) if sizes else 0.0

    def flush_size_max(self) -> int:
        sizes = self.flush_sizes
        return max(sizes) if sizes else 0


STATE_STATS = StateStats()

# Live device-resident states (DeviceAggregatingState instances).  A
# WeakSet so disposed backends drop out without an unregister call.
_LIVE_DEVICE_STATES: "weakref.WeakSet" = weakref.WeakSet()
_LIVE_LOCK = threading.Lock()


def register_device_state(state) -> None:
    with _LIVE_LOCK:
        _LIVE_DEVICE_STATES.add(state)


def device_state_summary() -> dict:
    """Aggregate live device-state pressure: slots in use, capacity,
    host-spill entries, evictions, host→device promotions, overruns of
    the device budget, pending-ring depth.  Safe to call from a gauge
    thread."""
    slots = capacity = spilled = evictions = promotions = pending = 0
    states = overruns = 0
    with _LIVE_LOCK:
        live = list(_LIVE_DEVICE_STATES)
    for st in live:
        try:
            states += 1
            slots += len(st.slot_index)
            capacity += st.capacity
            spilled += len(st.host_tier)
            evictions += st.evictions
            promotions += st.promotions
            overruns += st.budget_overruns
            pending += len(st._pending_slots)
        except Exception:  # noqa: BLE001 — racing dispose
            continue
    return {
        "states": states,
        "slots_in_use": slots,
        "capacity": capacity,
        "spilled_entries": spilled,
        "evictions": evictions,
        "promotions": promotions,
        "budget_overruns": overruns,
        "pending_depth": pending,
    }

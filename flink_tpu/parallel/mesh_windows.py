"""Mesh-sharded multi-window tumbling aggregation.

The engine the JobGraph drives: it speaks the same host
interface as the single-chip vectorized engines
(process_batch / advance_watermark / emitted / snapshot / restore, see
flink_tpu.streaming.vectorized), so DeviceWindowOperator can host it
and `keyBy().window(Tumbling...).aggregate(device_agg)` runs SPMD over
a jax.sharding.Mesh with several live windows, watermark-driven fires,
and late-record dropping.

Design (one jitted shard_map step per micro-batch):

  host    : vectorized key hashing + window assignment; late records
            dropped against the current watermark (lateness 0 — the
            WindowOperator.processElement:576-589 drop, done in bulk);
            each record gets a RING INDEX = (start // size) % R.
  device  : data-parallel input slices → bucketize by target shard
            (key hash → key group → shard, the same range-partition
            arithmetic as KeyGroupRangeAssignment.java:115) →
            lax.all_to_all over the mesh axis (the keyBy exchange as an
            ICI collective, replacing the reference's Netty shuffle,
            SURVEY.md §2.8) → REGIONAL insert into the shard's HBM hash
            table (one region per ring slot, so multiple live windows
            share one static-shape table) → scatter aggregation.
  fire    : when the watermark passes a window end, one jitted gather
            returns that ring region's (key lanes, occupancy, results)
            across all shards; the host resolves hashes back to
            original keys through its key directory and emits with the
            window's [start, end); the region is cleared on device for
            the ring slot's next occupant.

The ring bounds simultaneously-live windows on device (R regions).
Records for windows beyond the ring horizon — more than R windows
ahead of the oldest live window — park in a host-side pending buffer
and ingest when their ring slot frees (rare under bounded
out-of-orderness; unbounded future timestamps are the pathological
case the reference handles by unbounded heap state).

Overflow is grow-or-fail per region: a record that cannot claim a slot
within max_probes raises immediately instead of dropping data
(VERDICT r1 "weak #6": a silent overflow counter is data loss).

:class:`MeshSlidingWindows` composes sliding windows from slide-
granularity pane regions in the same ring: keys stay shard-local
across panes (hash routing is pane-independent), so a window fire is
a SHARD-LOCAL jitted merge — each pane region's occupied keys insert
into a scratch region and their accumulators fold in via
agg.merge_slots, then the scratch region fires like a tumbling window.
No cross-shard exchange happens at fire; the keyBy all_to_all runs
only at ingest, once per record regardless of the overlap factor.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from flink_tpu.ops.device_agg import DeviceAggregateFunction
from flink_tpu.ops.device_table import (
    DeviceHashTable,
    insert_or_lookup_regions_impl,
    make_table,
)
from flink_tpu.ops.hashing import fmix32, split_hash64_np
from flink_tpu.streaming.vectorized import hash_keys_np


def _target_shard(h_lo: jnp.ndarray, max_parallelism: int, n_shards: int) -> jnp.ndarray:
    """key hash → key group → shard (device twin of
    assign_key_groups_np + computeOperatorIndexForKeyGroup)."""
    kg = fmix32(h_lo) % jnp.uint32(max_parallelism)
    return ((kg.astype(jnp.int32) * n_shards) // max_parallelism).astype(jnp.int32)


def _bucketize(tgt: jnp.ndarray, n_shards: int, payload: Tuple[jnp.ndarray, ...],
               mask: jnp.ndarray):
    """Scatter records into [n_shards, M] buckets by target shard
    (M = local batch size, the static worst case)."""
    n = tgt.shape[0]
    # push padding records to a virtual shard so they never exchange
    tgt_eff = jnp.where(mask, tgt, n_shards)
    order = jnp.argsort(tgt_eff, stable=True)
    tgt_sorted = tgt_eff[order]
    counts = jnp.bincount(tgt_sorted, length=n_shards + 1)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(n) - offsets[tgt_sorted]
    # padding rows target the virtual shard n_shards, which is out of
    # bounds for the (n_shards, n) bucket array; mode="drop" discards
    # those writes instead of letting them collide with real shard-0
    # entries at [0, rank]
    valid = tgt_sorted < n_shards
    out_mask = jnp.zeros((n_shards, n), bool)
    out_mask = out_mask.at[tgt_sorted, rank].set(valid, mode="drop")
    outs = []
    for arr in payload:
        sorted_arr = arr[order]
        buck = jnp.zeros((n_shards, n), sorted_arr.dtype)
        buck = buck.at[tgt_sorted, rank].set(sorted_arr, mode="drop")
        outs.append(buck)
    return outs, out_mask


class MeshWindowOverflowError(RuntimeError):
    """A shard's window region ran out of slots (keys-per-window-per-
    shard exceeded capacity_per_shard).  Raised, not counted: dropping
    records silently would violate the aggregation's correctness."""


def _build_programs(mesh: Mesh, axis: str, agg: DeviceAggregateFunction,
                    max_parallelism: int, ring: int, region_size: int,
                    max_probes: int):
    """(init, step, fire) jitted shard_map programs.  Local table/state
    capacity = ring * region_size; region r holds ring slot r."""
    n_shards = mesh.shape[axis]
    local_cap = ring * region_size

    def local_init():
        return (make_table(local_cap), agg.init_state(local_cap))

    @jax.jit
    def init_sharded():
        def f():
            t, s = local_init()
            return jax.tree_util.tree_map(lambda a: a[None], (t, s))
        return shard_map(f, mesh=mesh, in_specs=(), out_specs=P(axis))()

    def local_step(table, state, h_hi, h_lo, ring_idx, values, vh_hi, vh_lo,
                   mask):
        table = jax.tree_util.tree_map(lambda a: a[0], table)
        state = jax.tree_util.tree_map(lambda a: a[0], state)
        tgt = _target_shard(h_lo, max_parallelism, n_shards)
        (b_hhi, b_hlo, b_ring, b_val, b_vhi, b_vlo), b_mask = _bucketize(
            tgt, n_shards, (h_hi, h_lo, ring_idx, values, vh_hi, vh_lo), mask)
        ex = lambda x: jax.lax.all_to_all(  # noqa: E731
            x[None], axis, split_axis=1, concat_axis=1)[0]
        flat = lambda x: ex(x).reshape(-1)  # noqa: E731
        f_hhi, f_hlo, f_ring = flat(b_hhi), flat(b_hlo), flat(b_ring)
        f_val, f_vhi, f_vlo = flat(b_val), flat(b_vhi), flat(b_vlo)
        f_mask = flat(b_mask)
        table, slots, ok = insert_or_lookup_regions_impl(
            table, f_hhi, f_hlo, f_ring, f_mask,
            region_size=region_size, max_probes=max_probes)
        eff = f_mask & ok & (slots >= 0)
        safe = jnp.where(slots >= 0, slots, 0)
        state = agg.update(state, safe, f_val, f_vhi, f_vlo, eff)
        overflow = (f_mask & ~ok).sum()
        return (jax.tree_util.tree_map(lambda a: a[None], (table, state)),
                overflow[None])

    step = jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=(P(axis),) * 9,
        out_specs=(P(axis), P(axis)),
    ), donate_argnums=(0, 1))

    def local_fire(table, state, r):
        table = jax.tree_util.tree_map(lambda a: a[0], table)
        state = jax.tree_util.tree_map(lambda a: a[0], state)
        r = r[0]  # [1] int32 per shard (replicated operand)
        slots = r * jnp.int32(region_size) + jnp.arange(
            region_size, dtype=jnp.int32)
        out = (table.key_hi[slots][None], table.key_lo[slots][None],
               table.occupied[slots][None],
               agg.result(state, slots)[None])
        table = DeviceHashTable(
            key_hi=table.key_hi,
            key_lo=table.key_lo,
            occupied=table.occupied.at[slots].set(False),
        )
        state = agg.clear_slots(state, slots)
        return jax.tree_util.tree_map(lambda a: a[None], (table, state)), out

    fire = jax.jit(shard_map(
        local_fire, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=((P(axis), P(axis)),
                   (P(axis), P(axis), P(axis), P(axis))),
    ), donate_argnums=(0, 1))

    return init_sharded, step, fire


def _build_clear_program(mesh: Mesh, axis: str,
                         agg: DeviceAggregateFunction, region_size: int):
    """Clear one region (occupancy + accumulators) with no outputs —
    the pane-prune path needs no gather."""

    def local_clear(table, state, r):
        table = jax.tree_util.tree_map(lambda a: a[0], table)
        state = jax.tree_util.tree_map(lambda a: a[0], state)
        r = r[0]
        slots = r * jnp.int32(region_size) + jnp.arange(
            region_size, dtype=jnp.int32)
        table = DeviceHashTable(
            key_hi=table.key_hi,
            key_lo=table.key_lo,
            occupied=table.occupied.at[slots].set(False),
        )
        state = agg.clear_slots(state, slots)
        return jax.tree_util.tree_map(lambda a: a[None], (table, state))

    return jax.jit(shard_map(
        local_clear, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
    ), donate_argnums=(0, 1))


def _build_merge_program(mesh: Mesh, axis: str,
                         agg: DeviceAggregateFunction, n_panes: int,
                         region_size: int, scratch_region: int,
                         junk_slot: int, max_probes: int):
    """Shard-local pane merge for sliding fires: for each of the
    window's n_panes regions (static unroll), insert the region's
    occupied keys into the scratch region and fold their accumulators
    in via agg.merge_slots.  No collectives — keys live in the same
    shard across panes.  Lanes that miss (unoccupied, or scratch
    overflow) are pointed at a sacrificial junk slot (junk ⊕= junk is
    never read; the junk region is never inserted into)."""

    def local_merge(table, state, regions):
        table = jax.tree_util.tree_map(lambda a: a[0], table)
        state = jax.tree_util.tree_map(lambda a: a[0], state)
        regions = regions[0]                      # [n_panes] int32
        lane = jnp.arange(region_size, dtype=jnp.int32)
        scratch = jnp.full(region_size, scratch_region, jnp.int32)
        overflow = jnp.int32(0)
        for i in range(n_panes):
            src_slots = regions[i] * jnp.int32(region_size) + lane
            occ = table.occupied[src_slots]
            hi = table.key_hi[src_slots]
            lo = table.key_lo[src_slots]
            table, dst, ok = insert_or_lookup_regions_impl(
                table, hi, lo, scratch, occ,
                region_size=region_size, max_probes=max_probes)
            eff = occ & ok & (dst >= 0)
            dst_safe = jnp.where(eff, dst, junk_slot)
            src_safe = jnp.where(eff, src_slots, junk_slot)
            state = agg.merge_slots(state, dst_safe, src_safe)
            overflow = overflow + (occ & ~eff).sum()
        return (jax.tree_util.tree_map(lambda a: a[None], (table, state)),
                overflow[None])

    return jax.jit(shard_map(
        local_merge, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
    ), donate_argnums=(0, 1))


class MeshTumblingWindows:
    """Multi-window mesh-sharded tumbling engine with the vectorized-
    engine host interface (DeviceWindowOperator-compatible).

    emitted   : list of (key, result, window_start, window_end)
    fired     : batch form when emit_arrays (keys, results_np, s, e)
    """

    def __init__(self, aggregate: DeviceAggregateFunction,
                 window_size_ms: int, mesh: Mesh, axis: str = "kg",
                 max_parallelism: int = 128,
                 capacity_per_window_shard: int = 1 << 12,
                 ring: int = 8, step_batch: int = 1 << 12,
                 max_probes: int = 64):
        self.agg = aggregate
        self.size = window_size_ms
        #: how far past a (pane) start a record stays live — the
        #: sliding subclass widens this to the full window size
        self.lateness_horizon = window_size_ms
        self.mesh = mesh
        self.axis = axis
        self.n_shards = mesh.shape[axis]
        self.max_parallelism = max_parallelism
        self.ring = ring
        #: ring slots handed to windows; subclasses may reserve a
        #: suffix of the ring for scratch regions
        self.usable_ring = ring
        self.region_size = capacity_per_window_shard
        if step_batch % self.n_shards:
            step_batch += self.n_shards - step_batch % self.n_shards
        self.step_batch = step_batch
        init, self._step, self._fire = _build_programs(
            mesh, axis, aggregate, max_parallelism, ring,
            capacity_per_window_shard, max_probes)
        self.table, self.state = init()
        self.watermark = -(2 ** 63)
        self.num_late_dropped = 0
        self.emitted: List[Tuple[Any, Any, int, int]] = []
        self.emit_arrays = False
        self.fired: List[Tuple[list, np.ndarray, int, int]] = []
        #: ring slot r -> window start currently resident (or None)
        self.ring_window: List[Optional[int]] = [None] * ring
        #: windows with device-resident data, start -> ring slot
        self.live: Dict[int, int] = {}
        #: per-window key directory: window start -> {key_hash: key};
        #: deleted when the window fires, so host memory is bounded by
        #: the LIVE windows' keys (not every key ever seen)
        self.key_directory: Dict[int, Dict[int, Any]] = {}
        #: far-future records parked until their ring slot frees:
        #: start -> list of (kh, values, vh) tuples
        self.pending: Dict[int, List[Tuple[np.ndarray, Optional[np.ndarray],
                                           Optional[np.ndarray]]]] = {}
        # step-batch staging buffers
        self._b_kh: List[np.ndarray] = []
        self._b_ring: List[np.ndarray] = []
        self._b_val: List[np.ndarray] = []
        self._b_vh: List[np.ndarray] = []
        self._b_count = 0

    # ---- ingestion ---------------------------------------------------
    def process_batch(self, keys, timestamps, values=None,
                      key_hashes=None, value_hashes=None) -> None:
        ts = np.asarray(timestamps, np.int64)
        kh = key_hashes if key_hashes is not None else hash_keys_np(keys)
        starts = ts - np.mod(ts, self.size)
        live = starts + self.lateness_horizon - 1 > self.watermark
        if not live.all():
            self.num_late_dropped += int((~live).sum())
            if not live.any():
                return
            ts, kh, starts = ts[live], kh[live], starts[live]
            keys = (keys[live] if isinstance(keys, np.ndarray)
                    else np.asarray(keys, dtype=object)[live])
            if values is not None:
                values = np.asarray(values)[live]
            if value_hashes is not None:
                value_hashes = np.asarray(value_hashes)[live]
        if self.agg.needs_value_hash and value_hashes is None:
            value_hashes = hash_keys_np(np.asarray(values))

        keys_arr = keys if isinstance(keys, np.ndarray) else np.asarray(
            keys, dtype=object)
        vals = (np.asarray(values, self.agg.value_dtype)
                if self.agg.needs_value else None)
        for start in np.unique(starts).tolist():
            m = starts == start
            w_kh = kh[m]
            # the host owns hash -> original key per window (emission
            # needs it back); dict work on batch-UNIQUE hashes only —
            # no per-record host loop on the hot path
            wdir = self.key_directory.setdefault(int(start), {})
            uniq, first = np.unique(w_kh, return_index=True)
            w_keys = keys_arr[m]
            for h, i in zip(uniq.tolist(), first.tolist()):
                if h not in wdir:
                    wdir[h] = w_keys[i]
            self._ingest_window(
                int(start), w_kh,
                None if vals is None else vals[m],
                None if value_hashes is None else value_hashes[m])

    def _ingest_window(self, start: int, kh, vals, vhs) -> None:
        r = self._acquire_ring_slot(start)
        if r is None:
            self.pending.setdefault(start, []).append((kh, vals, vhs))
            return
        self._b_kh.append(kh)
        self._b_ring.append(np.full(len(kh), r, np.int32))
        if vals is not None:
            self._b_val.append(vals)
        if vhs is not None:
            self._b_vh.append(vhs)
        self._b_count += len(kh)
        if self._b_count >= self.step_batch:
            self.flush()

    def _acquire_ring_slot(self, start: int) -> Optional[int]:
        got = self.live.get(start)
        if got is not None:
            return got
        r = (start // self.size) % self.usable_ring
        if self.ring_window[r] is not None:
            return None  # occupied by another live window — park
        self.ring_window[r] = start
        self.live[start] = r
        return r

    # ---- device step -------------------------------------------------
    def flush(self) -> None:
        if self._b_count == 0:
            return
        kh = (np.concatenate(self._b_kh) if len(self._b_kh) > 1
              else self._b_kh[0])
        ring = (np.concatenate(self._b_ring) if len(self._b_ring) > 1
                else self._b_ring[0])
        vals = (np.concatenate(self._b_val) if self._b_val else None)
        vhs = (np.concatenate(self._b_vh) if self._b_vh else None)
        self._b_kh.clear()
        self._b_ring.clear()
        self._b_val.clear()
        self._b_vh.clear()
        self._b_count = 0
        B = self.step_batch
        for i in range(0, len(kh), B):
            self._run_step(kh[i:i + B], ring[i:i + B],
                           None if vals is None else vals[i:i + B],
                           None if vhs is None else vhs[i:i + B])

    def _run_step(self, kh, ring, vals, vhs) -> None:
        n = len(kh)
        B = self.step_batch
        hi, lo = split_hash64_np(kh)

        def pad(a, dtype):
            out = np.zeros(B, dtype)
            out[:n] = a
            return out

        mask = np.zeros(B, bool)
        mask[:n] = True
        p_hi = pad(hi, np.uint32)
        p_lo = pad(lo, np.uint32)
        p_ring = pad(ring, np.int32)
        p_val = (pad(vals, self.agg.value_dtype) if vals is not None
                 else np.zeros(B, self.agg.value_dtype))
        if vhs is not None:
            vhi, vlo = split_hash64_np(vhs)
            p_vhi, p_vlo = pad(vhi, np.uint32), pad(vlo, np.uint32)
        else:
            p_vhi = np.zeros(B, np.uint32)
            p_vlo = np.zeros(B, np.uint32)
        (self.table, self.state), overflow = self._step(
            self.table, self.state, p_hi, p_lo, p_ring, p_val, p_vhi, p_vlo,
            mask)
        ov = int(np.asarray(overflow).sum())
        if ov:
            raise MeshWindowOverflowError(
                f"{ov} records overflowed a window region "
                f"(capacity_per_window_shard={self.region_size}, "
                f"shards={self.n_shards}); raise capacity_per_window_shard")

    # ---- firing ------------------------------------------------------
    def advance_watermark(self, watermark: int) -> int:
        """Fire due windows, interleaved with un-parking: a fire frees
        its ring slot, which may admit a parked window — which may
        itself be due (the end-of-input MAX_WATERMARK fires EVERY
        window in one call), so alternate ingest/fire until stable.
        Parked records were on time when they arrived; they are never
        dropped as late here."""
        self.watermark = watermark
        fired = 0
        while True:
            progress = False
            for start in sorted(self.pending):
                if self._acquire_ring_slot(start) is not None:
                    for kh, vals, vhs in self.pending.pop(start):
                        self._ingest_window(start, kh, vals, vhs)
                    progress = True
            self.flush()
            for start in sorted(self.live):
                if start + self.size - 1 > watermark:
                    break
                fired += self._fire_window(start)
                progress = True
            if not progress:
                break
        return fired

    def _fire_region(self, r: int):
        """Fire-and-clear one device region; returns (key hash64s,
        results) for its occupied lanes across all shards."""
        r_arr = np.full(self.n_shards, r, np.int32)
        (self.table, self.state), (hi, lo, occ, res) = self._fire(
            self.table, self.state, r_arr)
        hi = np.asarray(hi).reshape(-1)
        lo = np.asarray(lo).reshape(-1)
        occ = np.asarray(occ).reshape(-1)
        res = np.asarray(res)
        res = res.reshape(res.shape[0] * res.shape[1], *res.shape[2:])
        sel = np.nonzero(occ)[0]
        h64 = (hi[sel].astype(np.uint64) << np.uint64(32)) | lo[sel].astype(
            np.uint64)
        return h64, res[sel]

    def _fire_window(self, start: int) -> int:
        r = self.live.pop(start)
        self.ring_window[r] = None
        h64, res = self._fire_region(r)
        wdir = self.key_directory.pop(start, {})
        if not len(h64):
            return 0
        end = start + self.size
        keys = [wdir[h] for h in h64.tolist()]
        if self.emit_arrays:
            self.fired.append((keys, res, start, end))
        else:
            for k, v in zip(keys, res):
                out = v.item() if np.ndim(v) == 0 else v
                self.emitted.append((k, out, start, end))
        return len(keys)

    def block_until_ready(self) -> None:
        jax.tree_util.tree_map(lambda a: a.block_until_ready(), self.state)

    # ---- checkpoint --------------------------------------------------
    def snapshot(self) -> dict:
        self.flush()
        return {
            "table": jax.tree_util.tree_map(np.asarray, self.table),
            "state": {k: np.asarray(v) for k, v in self.state.items()},
            "max_parallelism": self.max_parallelism,
            "watermark": self.watermark,
            "num_late_dropped": self.num_late_dropped,
            "ring_window": list(self.ring_window),
            "live": dict(self.live),
            "key_directory": {s: dict(d)
                              for s, d in self.key_directory.items()},
            "pending": {s: [(np.array(kh), None if v is None else np.array(v),
                             None if h is None else np.array(h))
                            for kh, v, h in lst]
                        for s, lst in self.pending.items()},
            "fired_horizon": getattr(self, "_fired_horizon", None),
            "blocked": (sorted(self._blocked)
                        if hasattr(self, "_blocked") else None),
        }

    def restore(self, snap: dict) -> None:
        # key→shard routing derives from max_parallelism: a mismatch
        # would silently route keys away from their restored state
        snap_mp = snap.get("max_parallelism", 128)  # pre-r5 snapshots
        # were necessarily taken at the old hard-wired default of 128
        if snap_mp != self.max_parallelism:
            raise ValueError(
                f"mesh window checkpoint was taken at max_parallelism="
                f"{snap_mp}; this operator is configured "
                f"{self.max_parallelism}")
        self.table = DeviceHashTable(*[jnp.asarray(a) for a in snap["table"]])
        self.state = {k: jnp.asarray(v) for k, v in snap["state"].items()}
        self.watermark = snap["watermark"]
        self.num_late_dropped = snap["num_late_dropped"]
        self.ring_window = list(snap["ring_window"])
        self.live = dict(snap["live"])
        kd = snap["key_directory"]
        if kd and not isinstance(next(iter(kd.values())), dict):
            # legacy flat {key_hash: key} snapshot (pre per-window
            # directories): every live window may draw on the full map
            self.key_directory = {s: dict(kd) for s in snap["live"]}
        else:
            self.key_directory = {s: dict(d) for s, d in kd.items()}
        if snap.get("fired_horizon") is not None:
            self._fired_horizon = snap["fired_horizon"]
        if hasattr(self, "_blocked"):
            self._blocked = set(snap.get("blocked") or ())
        self.pending = {s: list(lst) for s, lst in snap["pending"].items()}
        self._b_kh.clear()
        self._b_ring.clear()
        self._b_val.clear()
        self._b_vh.clear()
        self._b_count = 0


class MeshSlidingWindows(MeshTumblingWindows):
    """Mesh-sharded sliding windows by pane composition.

    Ingest runs the tumbling engine at slide granularity (one region
    per pane, one all_to_all-routed insert per record); a window fire
    merges its size/slide pane regions SHARD-LOCALLY into a reserved
    scratch region (agg.merge_slots — mergeability is the sketch
    kernels' design property) and fires the scratch like a tumbling
    window.  Pane regions stay live until no future window needs them
    (same fire/prune rules as VectorizedSlidingWindows /
    LogStructuredSlidingWindows, lateness 0)."""

    def __init__(self, aggregate: DeviceAggregateFunction,
                 window_size_ms: int, slide_ms: int, mesh: Mesh,
                 axis: str = "kg", max_parallelism: int = 128,
                 capacity_per_window_shard: int = 1 << 12,
                 extra_ring: int = 4, step_batch: int = 1 << 12,
                 max_probes: int = 64):
        if window_size_ms % slide_ms != 0:
            raise ValueError("window size must be a multiple of the slide "
                             "(pane composition)")
        n_panes = window_size_ms // slide_ms
        if n_panes > 32:
            # the merge program statically unrolls n_panes probe
            # passes and the ring allocates n_panes regions per shard
            # — compile time and HBM scale with the overlap factor
            raise ValueError(
                f"mesh sliding supports size/slide <= 32 (got {n_panes}); "
                "use the single-device sliding engines for higher overlap")
        # pane slots + slack for in-flight panes + scratch + junk
        ring = n_panes + extra_ring + 2
        super().__init__(aggregate, slide_ms, mesh, axis, max_parallelism,
                         capacity_per_window_shard, ring, step_batch,
                         max_probes)
        self.window_size = window_size_ms
        self.slide = slide_ms
        self.n_panes = n_panes
        self.lateness_horizon = window_size_ms
        # reserve the ring's last two regions: scratch (window merges
        # fire from it) and junk (sacrificial no-op lanes; never
        # inserted into, so its occupancy stays empty)
        self.usable_ring = ring - 2
        self.scratch_region = ring - 2
        self.junk_region = ring - 1
        self.ring_window[self.scratch_region] = -1
        self.ring_window[self.junk_region] = -1
        self._fired_horizon = -(2 ** 63)
        #: due windows skipped because one of their panes was parked
        #: (pending) — carried across advance_watermark calls so they
        #: fire once the pane unparks, instead of being silently lost
        #: behind the fired horizon (round-2 advisor finding)
        self._blocked: set = set()
        self._merge = _build_merge_program(
            mesh, axis, aggregate, n_panes, self.region_size,
            self.scratch_region, self.junk_region * self.region_size,
            max_probes)
        self._clear = _build_clear_program(mesh, axis, aggregate,
                                           self.region_size)

    # ---- firing ------------------------------------------------------
    def advance_watermark(self, watermark: int) -> int:
        prev = self._fired_horizon
        self._fired_horizon = watermark
        self.watermark = watermark
        # windows due on an earlier call but skipped on a parked pane:
        # retry them past the fired horizon (they never fired)
        retry = self._blocked
        blocked = set(retry)
        fired = 0
        done = set()
        while True:
            progress = False
            for start in sorted(self.pending):
                if self._acquire_ring_slot(start) is not None:
                    for kh, vals, vhs in self.pending.pop(start):
                        self._ingest_window(start, kh, vals, vhs)
                    progress = True
            self.flush()
            # scan windows over live AND pending panes — a due window
            # whose every pane is parked has no live pane to anchor the
            # scan, yet must be recorded as blocked so it fires later
            panes_known = set(self.live) | set(self.pending)
            if panes_known:
                min_pane = min(panes_known)
                max_pane = max(panes_known)
                hi = min(watermark - self.window_size + 1, max_pane)
                start_from = min_pane - self.window_size + self.slide
                first = -(-start_from // self.slide) * self.slide
                for W in range(first, hi + 1, self.slide):
                    if W in done or (W + self.window_size - 1 <= prev
                                     and W not in retry):
                        continue
                    # a parked pane's records are on time — firing
                    # without them would silently lose data.  Park the
                    # WINDOW too (blocked set): pruning frees slots,
                    # the pane unparks, and this loop — or a later
                    # advance_watermark call — fires it
                    if any(p in self.pending
                           for p in range(W, W + self.window_size,
                                          self.slide)):
                        blocked.add(W)
                        continue
                    panes = [p for p in range(W, W + self.window_size,
                                              self.slide) if p in self.live]
                    if not panes:
                        continue
                    fired += self._fire_sliding_window(W, panes)
                    done.add(W)
                    progress = True
            if self._prune_panes(watermark, done, prev, retry):
                progress = True
            if not progress:
                break
        self._blocked = blocked - done
        return fired

    def _fire_sliding_window(self, W: int, pane_starts) -> int:
        regions = np.full(self.n_panes, self.junk_region, np.int32)
        for i, p in enumerate(pane_starts):
            regions[i] = self.live[p]
        reg_arr = np.tile(regions, (self.n_shards, 1))
        (self.table, self.state), overflow = self._merge(
            self.table, self.state, reg_arr)
        ov = int(np.asarray(overflow).sum())
        if ov:
            raise MeshWindowOverflowError(
                f"{ov} keys overflowed the sliding scratch region "
                f"(capacity_per_window_shard={self.region_size}); a "
                f"window's distinct keys per shard must fit one region")
        h64, res = self._fire_region(self.scratch_region)
        if not len(h64):
            return 0
        dirs = [self.key_directory[p] for p in pane_starts
                if p in self.key_directory]
        keys = []
        for h in h64.tolist():
            for d in dirs:
                if h in d:
                    keys.append(d[h])
                    break
            else:  # pragma: no cover — directory invariant violated
                raise KeyError(f"fired key hash {h} not in any pane "
                               "directory")
        end = W + self.window_size
        if self.emit_arrays:
            self.fired.append((keys, res, W, end))
        else:
            for k, v in zip(keys, res):
                out = v.item() if np.ndim(v) == 0 else v
                self.emitted.append((k, out, W, end))
        return len(keys)

    def _prune_panes(self, watermark: int, done, prev: int,
                     retry=frozenset()) -> bool:
        """Pane [P, P+slide) dies once every window containing it has
        FIRED (not merely become due — a due window blocked on a
        parked pane still needs this pane's data): clear its device
        region and free its ring slot + key directory.  Windows in
        ``retry`` sit behind the fired horizon but never fired (they
        were blocked on a parked pane) — they count as unfired here."""
        pruned = False
        for P in sorted(self.live):
            if P + self.window_size - 1 > watermark:
                break
            blocked = False
            for W in range(P - self.window_size + self.slide,
                           P + self.slide, self.slide):
                if (W + self.window_size - 1 <= watermark
                        and (W + self.window_size - 1 > prev or W in retry)
                        and W not in done
                        and any(q in self.pending or q in self.live
                                for q in range(W, W + self.window_size,
                                               self.slide))):
                    blocked = True
                    break
            if blocked:
                continue
            r = self.live.pop(P)
            self.ring_window[r] = None
            (self.table, self.state) = self._clear(
                self.table, self.state,
                np.full(self.n_shards, r, np.int32))
            self.key_directory.pop(P, None)
            pruned = True
        return pruned

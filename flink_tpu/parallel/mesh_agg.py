"""Sharded windowed aggregation: the keyBy exchange as XLA collectives.

One jitted SPMD step (shard_map over mesh axis "kg") replaces the
reference's record shuffle + keyed-state update pipeline
(KeyGroupStreamPartitioner → Netty exchange → per-record state mutation,
SURVEY.md §3.2):

  1. each device holds a data-parallel slice of the incoming batch
     (hashed keys + values),
  2. records are bucketed by target shard (key group → shard, same
     range-partition arithmetic as KeyGroupRangeAssignment.java:115)
     with a sort + scatter,
  3. `lax.all_to_all` exchanges the buckets over ICI,
  4. the receiving device resolves keys to slots in its HBM hash table
     (flink_tpu.ops.device_table) and scatter-updates its state shard.

No host participation per batch: the exchange, table insert, and
aggregation compile into one XLA program.  Window firing gathers each
shard's occupied slots and hands (key_hash → result) back to the host,
which owns the hash → original-key mapping for its shard.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from flink_tpu.ops.device_agg import DeviceAggregateFunction
from flink_tpu.ops.device_table import (
    DeviceHashTable,
    insert_or_lookup,
    make_table,
)
from flink_tpu.ops.hashing import fmix32
from flink_tpu.runtime.device_stats import TELEMETRY

_perf_ns = time.perf_counter_ns


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


class ShardState(NamedTuple):
    """Per-shard device state (under shard_map: the local block)."""
    table: DeviceHashTable
    agg_state: Dict[str, jnp.ndarray]


def make_sharded_step(mesh: Mesh, axis: str, agg: DeviceAggregateFunction,
                      max_parallelism: int, capacity_per_shard: int,
                      max_probes: int = 64):
    """Build (init_fn, step_fn, fire_fn) for mesh-sharded windowed
    aggregation.  All three are jit-compiled with shardings over
    `mesh[axis]`; step_fn is the full exchange+update program."""
    n_shards = mesh.shape[axis]

    def local_init():
        return ShardState(
            table=make_table(capacity_per_shard),
            agg_state=agg.init_state(capacity_per_shard),
        )

    @partial(shard_map, mesh=mesh, in_specs=(), out_specs=P(axis))
    def init_sharded():
        s = local_init()
        # add a leading shard axis of size 1 for the named axis
        return jax.tree_util.tree_map(lambda a: a[None], s)

    def local_step(state: ShardState, h_hi, h_lo, values, vh_hi, vh_lo, mask):
        state = jax.tree_util.tree_map(lambda a: a[0], state)
        tgt = _target_shard(h_lo, max_parallelism, n_shards)
        (b_hhi, b_hlo, b_val, b_vhi, b_vlo), b_mask = _bucketize(
            tgt, n_shards, (h_hi, h_lo, values, vh_hi, vh_lo), mask)
        # exchange: row j of my buckets goes to device j
        ex = lambda x: jax.lax.all_to_all(  # noqa: E731
            x[None], axis, split_axis=1, concat_axis=1)[0]
        r_hhi, r_hlo, r_val = ex(b_hhi), ex(b_hlo), ex(b_val)
        r_vhi, r_vlo, r_mask = ex(b_vhi), ex(b_vlo), ex(b_mask)
        flat = lambda x: x.reshape(-1)  # noqa: E731
        f_hhi, f_hlo, f_val = flat(r_hhi), flat(r_hlo), flat(r_val)
        f_vhi, f_vlo, f_mask = flat(r_vhi), flat(r_vlo), flat(r_mask)
        table, slots, ok = insert_or_lookup(
            state.table, f_hhi, f_hlo, f_mask, max_probes=max_probes)
        eff_mask = f_mask & ok & (slots >= 0)
        safe_slots = jnp.where(slots >= 0, slots, 0)
        new_agg = agg.update(state.agg_state, safe_slots, f_val,
                             f_vhi, f_vlo, eff_mask)
        overflow = (f_mask & ~ok).sum()
        new_state = ShardState(table=table, agg_state=new_agg)
        return (jax.tree_util.tree_map(lambda a: a[None], new_state),
                overflow[None])

    step = jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
    ))

    def local_fire(state: ShardState):
        state = jax.tree_util.tree_map(lambda a: a[0], state)
        slots = jnp.arange(capacity_per_shard, dtype=jnp.int32)
        results = agg.result(state.agg_state, slots)
        out = (state.table.key_hi[None], state.table.key_lo[None],
               results[None], state.table.occupied[None])
        # reset shard for the next window
        fresh = local_init()
        return jax.tree_util.tree_map(lambda a: a[None], fresh), out

    fire = jax.jit(shard_map(
        local_fire, mesh=mesh,
        in_specs=(P(axis),),
        out_specs=(P(axis), (P(axis), P(axis), P(axis), P(axis))),
    ))

    return jax.jit(init_sharded), step, fire


class MeshWindowAggregation:
    """Host-facing wrapper: one tumbling window at a time, sharded over
    the mesh.  Each host shard keeps hash → original key for emission."""

    def __init__(self, mesh: Mesh, axis: str, agg: DeviceAggregateFunction,
                 max_parallelism: int = 128, capacity_per_shard: int = 4096,
                 allow_overflow: bool = False):
        self.mesh = mesh
        self.axis = axis
        self.agg = agg
        self.n_shards = mesh.shape[axis]
        init, self._step, self._fire = make_sharded_step(
            mesh, axis, agg, max_parallelism, capacity_per_shard)
        self.state = init()
        self.capacity_per_shard = capacity_per_shard
        #: overflow policy: by default a full shard table is a hard
        #: failure (silently counting dropped records is data loss);
        #: allow_overflow=True restores the count-and-continue behavior
        #: for capacity experiments.
        self.allow_overflow = allow_overflow
        self.overflowed = 0

    def step(self, h_hi, h_lo, values, vh_hi, vh_lo, mask) -> None:
        """Process one global batch (length divisible by n_shards)."""
        if TELEMETRY.enabled:
            # the exchange here is fused into one XLA program, so the
            # pack/H2D legs are not separable: the dispatch is billed
            # as the collective phase, the overflow readback as D2H
            sent = sum(int(getattr(a, "nbytes", 0))
                       for a in (h_hi, h_lo, values, vh_hi, vh_lo, mask))
            t0 = _perf_ns()
            self.state, overflow = self._step(
                self.state, h_hi, h_lo, values, vh_hi, vh_lo, mask)
            t1 = _perf_ns()
            overflow_np = np.asarray(overflow)
            t2 = _perf_ns()
            TELEMETRY.record_transfer("h2d", sent, t0, t1,
                                      tag="mesh.step")
            TELEMETRY.record_transfer("d2h", overflow_np.nbytes, t1, t2,
                                      tag="mesh.step")
            TELEMETRY.record_exchange_round(
                "mesh.agg", 0.0, 0.0, (t1 - t0) / 1e6,
                (t2 - t1) / 1e6, sent)
            ov = int(overflow_np.sum())
        else:
            self.state, overflow = self._step(
                self.state, h_hi, h_lo, values, vh_hi, vh_lo, mask)
            ov = int(np.asarray(overflow).sum())
        if ov:
            self.overflowed += ov
            if not self.allow_overflow:
                raise RuntimeError(
                    f"{ov} records overflowed a shard hash table "
                    f"(capacity_per_shard={self.capacity_per_shard}); "
                    f"raise capacity_per_shard or shard wider")

    def fire(self):
        """Close the window: returns (key_hi, key_lo, results, occupied)
        host arrays concatenated over shards, and resets state."""
        if TELEMETRY.enabled:
            t0 = _perf_ns()
            self.state, (hi, lo, res, occ) = self._fire(self.state)
            hi_np, lo_np = np.asarray(hi), np.asarray(lo)
            res_np, occ_np = np.asarray(res), np.asarray(occ)
            t1 = _perf_ns()
            TELEMETRY.record_transfer(
                "d2h",
                hi_np.nbytes + lo_np.nbytes + res_np.nbytes
                + occ_np.nbytes,
                t0, t1, tag="mesh.fire")
            TELEMETRY.note_fire_read()
            return (hi_np.reshape(-1), lo_np.reshape(-1),
                    res_np.reshape(res_np.shape[0] * res_np.shape[1],
                                   *res_np.shape[2:]),
                    occ_np.reshape(-1))
        self.state, (hi, lo, res, occ) = self._fire(self.state)
        return (np.asarray(hi).reshape(-1), np.asarray(lo).reshape(-1),
                np.asarray(res).reshape(res.shape[0] * res.shape[1], *res.shape[2:]),
                np.asarray(occ).reshape(-1))

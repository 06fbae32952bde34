"""Mesh-sharded aggregation on the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from flink_tpu.core.keygroups import assign_key_groups_np, splitmix64_np
from flink_tpu.ops.device_agg import CountAggregate
from flink_tpu.ops.device_table import (
    insert_or_lookup,
    lookup_np,
    make_table,
)
from flink_tpu.ops.sketches import HyperLogLogAggregate
from flink_tpu.parallel import MeshTumblingWindows
from flink_tpu.streaming.vectorized import hash_keys_np


# ---------------------------------------------------------------------
# device hash table
# ---------------------------------------------------------------------

def _lanes(h64):
    h64 = np.asarray(h64, np.uint64)
    return ((h64 >> np.uint64(32)).astype(np.uint32),
            (h64 & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def test_device_table_insert_and_dedup():
    table = make_table(64)
    h = splitmix64_np(np.arange(10, dtype=np.uint64))
    hi, lo = _lanes(h)
    mask = np.ones(10, bool)
    table, slots, ok = insert_or_lookup(table, jnp.asarray(hi), jnp.asarray(lo),
                                        jnp.asarray(mask))
    slots = np.asarray(slots)
    assert np.asarray(ok).all()
    assert len(set(slots.tolist())) == 10  # distinct keys → distinct slots
    # same keys again → same slots
    table2, slots2, ok2 = insert_or_lookup(
        table, jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(slots2), slots)
    # duplicates within one batch → one slot
    dup_hi = jnp.asarray(np.repeat(hi[:1], 5))
    dup_lo = jnp.asarray(np.repeat(lo[:1], 5))
    _, dslots, _ = insert_or_lookup(table2, dup_hi, dup_lo,
                                    jnp.ones(5, bool))
    assert len(set(np.asarray(dslots).tolist())) == 1
    assert np.asarray(dslots)[0] == slots[0]


def test_device_table_host_lookup_agrees():
    table = make_table(128)
    h = splitmix64_np(np.arange(40, dtype=np.uint64))
    hi, lo = _lanes(h)
    table, slots, ok = insert_or_lookup(
        table, jnp.asarray(hi), jnp.asarray(lo), jnp.ones(40, bool))
    host_slots = lookup_np(table, h)
    np.testing.assert_array_equal(host_slots, np.asarray(slots))


def test_device_table_overflow_signals():
    table = make_table(8)
    h = splitmix64_np(np.arange(32, dtype=np.uint64))
    hi, lo = _lanes(h)
    table, slots, ok = insert_or_lookup(
        table, jnp.asarray(hi), jnp.asarray(lo), jnp.ones(32, bool),
        max_probes=8)
    ok = np.asarray(ok)
    assert ok.sum() <= 8  # at most capacity resolve
    assert (~ok).any()    # and overflow is reported, not silent


def test_padding_not_inserted():
    table = make_table(32)
    h = splitmix64_np(np.arange(4, dtype=np.uint64))
    hi, lo = _lanes(h)
    mask = np.array([True, True, False, False])
    table, slots, ok = insert_or_lookup(
        table, jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(mask))
    assert int(np.asarray(table.occupied).sum()) == 2


# ---------------------------------------------------------------------
# mesh-sharded aggregation (8 virtual devices)
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh():
    devices = np.array(jax.devices()[:8])
    assert len(devices) == 8, "conftest must provide 8 virtual devices"
    return Mesh(devices, ("kg",))


def _owner_shard(h64, n_shards):
    """key hash → key group → shard, on the host."""
    return (assign_key_groups_np(h64, 128).astype(np.int64)
            * n_shards) // 128


def _table_lanes(eng):
    """[(shard, key hash64)] of every occupied slot of the engine's
    sharded table."""
    t = eng.table
    hi, lo, occ = (np.asarray(a) for a in (t.key_hi, t.key_lo, t.occupied))
    shard, slot = np.nonzero(occ)
    h64 = (hi[shard, slot].astype(np.uint64) << np.uint64(32)) \
        | lo[shard, slot].astype(np.uint64)
    return shard, h64


def test_mesh_keys_land_on_owner_shard(mesh):
    """Each key's state must live on the shard its key group maps to
    (the device twin `_target_shard` against the host arithmetic)."""
    n_shards = mesh.shape["kg"]
    eng = MeshTumblingWindows(CountAggregate(), 1000, mesh,
                              capacity_per_window_shard=128, step_batch=64)
    keys = np.arange(200)
    eng.process_batch(keys, np.full(200, 100))
    eng.flush()
    shard, h64 = _table_lanes(eng)
    assert sorted(h64.tolist()) == sorted(hash_keys_np(keys).tolist())
    np.testing.assert_array_equal(shard, _owner_shard(h64, n_shards))
    eng.advance_watermark(999)
    assert sorted(k for k, _, _, _ in eng.emitted) == keys.tolist()


def test_mesh_hll(mesh):
    """A sketch with value hashes through the sharded scatter step."""
    eng = MeshTumblingWindows(HyperLogLogAggregate(precision=9), 1000, mesh,
                              capacity_per_window_shard=64, step_batch=512)
    n = 4000
    keys = np.repeat(np.arange(4), n // 4)
    users = np.arange(n)  # 1000 distinct per key
    eng.process_batch(keys, np.full(n, 100), users,
                      value_hashes=splitmix64_np(users.astype(np.uint64)))
    eng.advance_watermark(999)
    assert sorted(k for k, _, _, _ in eng.emitted) == [0, 1, 2, 3]
    for _, est, start, end in eng.emitted:
        assert (start, end) == (0, 1000)
        assert abs(est - 1000) / 1000 < 0.10


def test_mesh_padding_does_not_clobber_shard0(mesh):
    """Regression: padded (mask=False) records used to scatter to bucket
    row 0 during _bucketize, colliding with real shard-0 records at the
    same [0, rank] positions and silently dropping them."""
    n_shards = mesh.shape["kg"]
    per = 8  # slice length per device
    total = per * n_shards
    eng = MeshTumblingWindows(CountAggregate(), 1000, mesh,
                              capacity_per_window_shard=128,
                              step_batch=total)
    # pick n_shards keys that all target shard 0, and place exactly one
    # at the FRONT of each device's slice so every device holds a real
    # shard-0 record followed by padding — the layout where padding's
    # bucket-row-0 writes used to collide with the real entry.  The
    # engine pads only the tail of a batch, so the step program is
    # called as `_run_step` calls it, with this mask.
    keys = []
    k = 0
    while len(keys) < n_shards:
        if _owner_shard(splitmix64_np(np.array([k], np.uint64)),
                        n_shards)[0] == 0:
            keys.append(k)
        k += 1
    h64 = splitmix64_np(np.array(keys, np.uint64))
    hi = np.zeros(total, np.uint32)
    lo = np.zeros(total, np.uint32)
    mask = np.zeros(total, bool)
    idx = np.arange(n_shards) * per  # index 0 of each device slice
    hi[idx] = (h64 >> np.uint64(32)).astype(np.uint32)
    lo[idx] = (h64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    mask[idx] = True
    zeros = np.zeros(total, np.uint32)
    (eng.table, eng.state), overflow = eng._step(
        eng.table, eng.state, hi, lo, np.zeros(total, np.int32),
        np.zeros(total, np.float32), zeros, zeros, mask)
    assert int(np.asarray(overflow).sum()) == 0
    got, res = eng._fire_region(0)
    # every key survives, including shard-0 ones
    assert sorted(got.tolist()) == sorted(h64.tolist())
    assert (res == 1).all()

"""``QuantileSketchAggregate.update`` has two forms (a scatter of
cells; on the TPU a kernel that adds to the layout's tiles) and one
rule that picks between them from the static shapes.  Here: both
against ``np.add.at`` bit for bit, the rule at the shapes that matter,
what the flush counter may say off the TPU, the sharded engine's step,
the scalar twin, and the tile form compiled for the chip at the
benchmark cell's size and for four chips inside the mesh engine's step
(no chip needed: the TPU's compiler is installed here and compiles for
a described one)."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_tpu.ops import sketches
from flink_tpu.ops.sketches import (
    HyperLogLogAggregate,
    QuantileSketchAggregate,
    quantile_update_form,
)

CELL_ROWS, CELL_CAPACITY, CELL_BUCKETS = 16384, 524288, 2075


class LaneBuckets(QuantileSketchAggregate):
    """A sketch whose bucket count is a multiple of the 128 lanes
    (2,176 = 17 x 128): the constructor's range, a wider table, which
    the TPU holds slot-major: no tiles of slots, cells only."""

    def __init__(self):
        super().__init__()
        self.buckets = 2176


AGGS = {"2075": QuantileSketchAggregate, "2176": LaneBuckets}


def make_batch(seed, rows, capacity, masking):
    """Slots with one holding > 5% of the batch, values at and beyond
    the sketch's range, and a mask."""
    rng = np.random.default_rng(seed)
    slots = rng.integers(0, capacity, rows).astype(np.int32)
    slots[rng.random(rows) < 0.08] = capacity // 3
    # the middle of a bucket, so that float32 rounds no value across an
    # edge and a fused and an unfused log agree on every bucket
    log_gamma = np.log(1.01 / 0.99)
    values = np.exp((np.floor(rng.normal(3.0, 1.0, rows) / log_gamma) + 0.5)
                    * log_gamma).astype(np.float32)
    edge = np.array([0.0, -1.0, 1e-9, 0.5e-9, 1e9, 3e9, np.float32(1e-9),
                     np.nextafter(np.float32(1e9), np.float32(0))],
                    np.float32)
    values[:min(rows, len(edge))] = edge[:rows]
    mask = {"none": np.ones(rows, bool),
            "part": rng.random(rows) < 0.7,
            "all": np.zeros(rows, bool)}[masking]
    return slots, values, mask


def reference(agg, capacity, start, slots, values, mask):
    b = np.asarray(agg._bucket_of(jnp.asarray(values)))
    want = start.copy()
    np.add.at(want, (slots[mask], b[mask]), 1)
    return b, want


def run_form(name, hist, slots, b, inc):
    if name == "tiles":
        # the kernel itself, interpreted: what the TPU branch runs
        return sketches._add_tiles_tpu(hist, slots, b, inc, interpret=True)
    return sketches._add_cells(hist, slots, b, inc)


@pytest.mark.parametrize("masking", ["none", "part", "all"])
@pytest.mark.parametrize("rows, capacity", [
    (1, 1024), (256, 1024), (256, 4096), (256, 32768), (16384, 32768)])
@pytest.mark.parametrize("buckets", sorted(AGGS))
def test_every_form_is_np_add_at_bit_for_bit(buckets, rows, capacity,
                                              masking):
    agg = AGGS[buckets]()
    slots, values, mask = make_batch(rows + capacity, rows, capacity,
                                     masking)
    rng = np.random.default_rng(7)
    start = rng.integers(0, 3, (capacity, agg.buckets)).astype(np.int32)
    b, want = reference(agg, capacity, start, slots, values, mask)
    if masking != "all" and rows > 16:
        assert np.bincount(slots[mask]).max() > 0.05 * mask.sum()
    forms = ["cells"]
    if buckets == "2075" and rows <= 256:
        forms.append("tiles")  # interpreted a grid step at a time: slow
    for name in forms:
        got = run_form(name, jnp.asarray(start), jnp.asarray(slots),
                       jnp.asarray(b), jnp.asarray(mask.astype(np.int32)))
        assert got.dtype == jnp.int32
        assert np.array_equal(np.asarray(got), want), name
    # and the door every caller uses, whichever form the rule picks
    zeros = np.zeros(rows, np.uint32)
    got = jax.jit(agg.update)({"hist": jnp.asarray(start)}, slots, values,
                              zeros, zeros, mask)["hist"]
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("rows, capacity, buckets, form", [
    # the benchmark cell: a 16,384-row flush against 2^19 slots
    (CELL_ROWS, CELL_CAPACITY, CELL_BUCKETS, "tiles"),
    (32768, CELL_CAPACITY, CELL_BUCKETS, "tiles"),
    # buckets a multiple of 128: the TPU holds the table slot-major
    (CELL_ROWS, CELL_CAPACITY, 2176, "cells"),
    (8, 1024, 2048, "cells"),
    # while the table is still growing a flush is not small against it
    (CELL_ROWS, 16384, CELL_BUCKETS, "cells"),
    (CELL_ROWS, 32768, CELL_BUCKETS, "tiles"),
    # the vectorized and mesh engines: 2^19-row batches, small tables
    (1 << 19, 1 << 12, CELL_BUCKETS, "cells"),
    (1 << 19, 1 << 22, CELL_BUCKETS, "cells"),
    # ... and the mesh engine's default step: 4,096 rows, 8 x 4,096 slots
    (4096, 32768, CELL_BUCKETS, "tiles"),
    # the scalar twin
    (1, CELL_CAPACITY, CELL_BUCKETS, "cells"),
    (1, 1, CELL_BUCKETS, "cells"),
    # more rows than the tile form's scalars have room for
    (1 << 16, 1 << 22, CELL_BUCKETS, "cells"),
    # a capacity that is no multiple of 128: no tiles of slots
    (8, 64, CELL_BUCKETS, "cells"),
    (8, 1000, CELL_BUCKETS, "cells"),
])
def test_the_rule_follows_the_shapes(rows, capacity, buckets, form,
                                     monkeypatch):
    assert quantile_update_form(rows, capacity, buckets) == form
    agg = QuantileSketchAggregate()
    agg.buckets = buckets
    # the kernel is the TPU's: here every update scatters cells, and
    # the answer the state backend counts by says so
    assert jax.default_backend() != "tpu"
    assert agg.update_runs_in_place(rows, capacity) is False
    monkeypatch.setattr(sketches, "_tile_form_runs", lambda: True)
    assert agg.update_runs_in_place(rows, capacity) is (form == "tiles")


def test_only_the_quantile_sketch_has_an_in_place_form(monkeypatch):
    monkeypatch.setattr(sketches, "_tile_form_runs", lambda: True)
    assert not HyperLogLogAggregate(12).update_runs_in_place(
        CELL_ROWS, CELL_CAPACITY)


def test_off_the_tpu_the_tile_form_lowers_to_the_cell_scatter():
    """`lax.platform_dependent`: the program built here holds the
    scatter and no kernel call, at shapes the rule gives to tiles."""
    rows, capacity = 256, 4096
    agg = QuantileSketchAggregate()
    assert quantile_update_form(rows, capacity, agg.buckets) == "tiles"
    zeros = np.zeros(rows, np.uint32)
    text = jax.jit(agg.update).lower(
        {"hist": jnp.zeros((capacity, agg.buckets), jnp.int32)},
        np.zeros(rows, np.int32), np.ones(rows, np.float32), zeros, zeros,
        np.ones(rows, bool)).compile().as_text()
    assert "scatter" in text
    assert "custom-call" not in text and "quantile_add_tiles" not in text


def test_the_mesh_engine_steps_a_quantile_sketch_at_tile_shapes():
    """`parallel/mesh_windows.py` calls `update` inside a `shard_map`,
    where the kernel's output has to say over which mesh axes it
    varies, or the step does not trace on any platform.  Its default
    step (4,096 rows against 8 x 4,096 slots a shard) is one the rule
    gives to tiles."""
    from jax.sharding import Mesh

    from flink_tpu.parallel import MeshTumblingWindows
    mesh = Mesh(np.array(jax.devices()[:4]), ("kg",))
    agg = QuantileSketchAggregate()
    eng = MeshTumblingWindows(agg, 1000, mesh,
                              capacity_per_window_shard=128, step_batch=256)
    assert quantile_update_form(eng.step_batch, eng.ring * eng.region_size,
                                agg.buckets) == "tiles"
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 40, 1000)
    values = np.exp(rng.normal(3.0, 1.0, 1000)).astype(np.float32)
    eng.process_batch(keys, np.full(1000, 100), values)
    eng.advance_watermark(999)
    assert sorted(k for k, _, _, _ in eng.emitted) == sorted(set(keys))
    for key, (p50, p99), _, _ in eng.emitted:
        own = np.sort(values[keys == key])
        exact = own[int(np.ceil(0.5 * len(own))) - 1]
        assert abs(p50 - exact) <= 0.0102 * exact
        assert p99 <= own[-1] * 1.0102


@pytest.mark.parametrize("buckets", sorted(AGGS))
def test_the_scalar_twin_is_unchanged(buckets):
    agg = AGGS[buckets]()
    values = [20.0, 1e-9, 0.0, 3e9, 55.5, 20.0]
    acc = agg.create_accumulator()
    for v in values:
        acc = agg.add(v, acc)
    b = np.asarray(agg._bucket_of(jnp.asarray(values, jnp.float32)))
    want = np.bincount(b, minlength=agg.buckets).astype(np.int32)
    assert acc["hist"].shape == (agg.buckets,)
    assert np.array_equal(acc["hist"], want)
    p50, p99 = agg.get_result(acc)
    assert abs(p50 - 20.0) <= 0.0102 * 20.0 and p99 > 1e8


# ---- compiled for the chip, at the benchmark cell's size ----------------

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def cache_off():
    """A compile for a described chip can be written to the persistent
    cache but not read back, so the cache is kept out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def compile_for_chip(fn, *args):
    """`fn` with its first argument donated, compiled for the described
    chip."""
    with cache_off():
        return jax.jit(fn, donate_argnums=0).lower(*args).compile()


def cell_shapes(one_chip, rows=CELL_ROWS):
    """The benchmark cell's flush: (table, a column of the batch)."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return (lambda: arg((CELL_CAPACITY, CELL_BUCKETS), jnp.int32),
            lambda dtype: arg((rows,), dtype))


def whole_table_ops(compiled, capacity, buckets):
    """Instructions that produce an array of the whole table's size by
    anything but a bitcast, a parameter or the in-place update."""
    shapes = (rf"\[{capacity * buckets}\]", rf"\[{capacity},{buckets}\]",
              rf"\[{buckets},{capacity}\]", rf"\[1,{capacity},{buckets}\]")
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = s32(" + "|".join(shapes)
                     + r")\S* (\w[\w-]*)\(", line)
        if m and m.group(3) not in ("parameter", "bitcast",
                                    "get-tuple-element"):
            found.append((m.group(1), m.group(3)))
    return found


# the cell's flush, and chip_smoke leg 2c's (two batches in one window)
@pytest.mark.parametrize("rows", [CELL_ROWS, 2 * CELL_ROWS])
def test_on_the_chip_the_table_stays_in_place(one_chip, rows):
    agg = QuantileSketchAggregate()
    table, column = cell_shapes(one_chip, rows)
    compiled = compile_for_chip(
        agg.update, {"hist": table()}, column(jnp.int32),
        column(jnp.float32), column(jnp.uint32), column(jnp.uint32),
        column(jnp.bool_))
    table_bytes = CELL_CAPACITY * agg.buckets * 4
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= table_bytes      # donated, reused
    assert memory.temp_size_in_bytes < table_bytes // 16  # no second copy
    # the kernel alone: no copy, reshape or transpose of the table
    ops = whole_table_ops(compiled, CELL_CAPACITY, agg.buckets)
    assert [kind for _, kind in ops] == ["custom-call"], ops
    assert "quantile_add_tiles" in compiled.as_text()


def test_on_the_chip_the_cell_form_rewrites_the_table(one_chip):
    """The finding the tile form exists for, kept as a test: if XLA
    one day scatters cells in place, the kernel and the rule can go."""
    agg = QuantileSketchAggregate()
    table, column = cell_shapes(one_chip)
    compiled = compile_for_chip(sketches._add_cells, table(),
                                *[column(jnp.int32) for _ in range(3)])
    assert compiled.memory_analysis().temp_size_in_bytes \
        >= CELL_CAPACITY * agg.buckets * 4
    kinds = [kind for _, kind in whole_table_ops(
        compiled, CELL_CAPACITY, agg.buckets)]
    assert kinds.count("reshape") == 2


def mesh_step_for_four_chips(topo, agg, ring, region, rows_a_chip):
    """`parallel/mesh_windows.py`'s step over the four described chips,
    compiled: the program a sharded quantile job runs per batch."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from flink_tpu.parallel import mesh_windows
    mesh = Mesh(np.array(topo.devices), ("kg",))
    sharded = NamedSharding(mesh, PartitionSpec("kg"))
    init, step, _ = mesh_windows._build_programs(
        mesh, "kg", agg, 128, ring, region, 8)
    table, state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharded),
        jax.eval_shape(init))

    def column(dtype):
        return jax.ShapeDtypeStruct((4 * rows_a_chip,), dtype,
                                    sharding=sharded)
    with cache_off():
        return step.lower(
            table, state, column(jnp.uint32), column(jnp.uint32),
            column(jnp.int32), column(jnp.float32), column(jnp.uint32),
            column(jnp.uint32), column(jnp.bool_)).compile()


def test_on_four_chips_the_mesh_step_holds_the_kernel(topo, monkeypatch):
    """Inside the sharded step the kernel compiles, a shard's table
    `[1, slots, buckets]` is copied into the bucket-major view and out
    again (2 passes over it), and the cell form there costs those and
    its two flat views besides: the rule does no harm on this path."""
    agg = QuantileSketchAggregate()
    ring, region, rows = 4, 16384, 2048
    assert quantile_update_form(4 * rows, ring * region,
                                agg.buckets) == "tiles"
    tiled = mesh_step_for_four_chips(topo, agg, ring, region, rows)
    assert tiled.as_text().count("quantile_add_tiles") >= 1
    kinds = [kind for _, kind in whole_table_ops(
        tiled, ring * region, agg.buckets)]
    assert "reshape" not in kinds and kinds.count("copy") <= 2, kinds
    monkeypatch.setattr(sketches, "_TILE_FORM_MAX_ROWS", 1)
    cells = mesh_step_for_four_chips(topo, agg, ring, region, rows)
    assert "quantile_add_tiles" not in cells.as_text()
    kinds = [kind for _, kind in whole_table_ops(
        cells, ring * region, agg.buckets)]
    assert kinds.count("reshape") >= 2, kinds

"""Mergeable sketch aggregates: HyperLogLog, Count-Min, quantiles.

These are the north-star kernels (BASELINE.md configs 2-4).  None exist
in the reference (SURVEY.md §6: "HLL itself is not in the reference");
they plug into the windowed-aggregation boundary the reference defines
(AggregateFunction.java:127-160) and run either per-record on the heap
backend (scalar twin, see DeviceAggregateFunction) or micro-batched on
TPU where the whole key-group's sketches update in one scatter.

Design notes (TPU-first):
- HLL registers are uint8 `[slots, m]`; a batch update is one
  scatter-max into the flattened `[slots*m]` view.  Rank/register come
  from exact uint32 bit ops (flink_tpu/ops/hashing.py), never float log.
- Count-Min is `[slots, depth, width]` int32 with Kirsch–Mitzenmacher
  row hashing; a batch is one scatter-add of depth*N entries.
- Quantiles use a DDSketch-style log-bucketed histogram (relative-error
  guarantee, fixed shape, trivially mergeable) rather than a literal
  t-digest: centroid lists are pointer-chasing and dynamically sized —
  hostile to XLA — while the log-histogram is a scatter-add, and serves
  the same p50/p99 queries (BASELINE.md config 3).  On the TPU a batch
  that is small against the table adds tiles of it, never cells: XLA's
  TPU lowering flattens the table around a scatter of single cells (a
  rewrite of all of it, twice, for 16,384 increments).  The TPU holds
  `int32[slots, 2075]` bucket-major (of the two dimensions only the
  slots are a multiple of the 128 lanes), so what lies together is an
  (8 buckets x 128 slots) tile, and a kernel adds each increment into
  its tile with the table left where it is (`quantile_update_form`).
"""

from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.core.keygroups import hash_int_column_np
from flink_tpu.ops.device_agg import DeviceAggregateFunction, StateSpec
from flink_tpu.ops.hashing import (
    countmin_rows,
    hll_register_and_rank,
    split_hash64_np,
)


class HyperLogLogAggregate(DeviceAggregateFunction):
    """Approximate COUNT DISTINCT.

    Standard HLL with 2^precision uint8 registers per slot; estimator
    uses the alpha_m bias correction plus linear counting for the small
    range.  Relative error ≈ 1.04/sqrt(m) (precision 12 → ~1.6%).
    """

    needs_value_hash = True

    def __init__(self, precision: int = 12):
        if not 4 <= precision <= 18:
            raise ValueError("precision must be in [4, 18]")
        self.precision = precision
        self.m = 1 << precision
        if self.m == 16:
            self.alpha = 0.673
        elif self.m == 32:
            self.alpha = 0.697
        elif self.m == 64:
            self.alpha = 0.709
        else:
            self.alpha = 0.7213 / (1.0 + 1.079 / self.m)

    def state_specs(self) -> Dict[str, StateSpec]:
        return {"regs": StateSpec((self.m,), np.dtype(np.uint8), 0)}

    def compress_value_hash(self, vh_hi, vh_lo):
        """Host-side precompute: ship (rank uint8, register uint16)
        instead of the 8-byte hash — 2.7x less ingest bandwidth.
        floor(log2) on float64 is exact for uint32 inputs."""
        hi = np.asarray(vh_hi, np.uint32)
        lo = np.asarray(vh_lo, np.uint32)
        x = hi.astype(np.float64)
        clz = np.where(hi == 0, 32,
                       31 - np.floor(np.log2(np.maximum(x, 1.0))).astype(np.int64))
        rank = (clz + 1).astype(np.uint8)
        # uint16 covers precision <= 16; larger register files need the
        # full 32-bit index
        reg_dtype = np.uint16 if self.precision <= 16 else np.uint32
        reg = (lo & np.uint32(self.m - 1)).astype(reg_dtype)
        return rank, reg

    def update(self, state, slots, values, vh_hi, vh_lo, mask):
        if vh_hi.dtype == jnp.uint8:
            # pre-compressed on host: vh_hi = rank, vh_lo = register
            rank = vh_hi.astype(jnp.int32)
            reg = vh_lo.astype(jnp.int32)
        else:
            reg, rank = hll_register_and_rank(vh_hi, vh_lo, self.precision)
        rank = jnp.where(mask, rank, 0).astype(jnp.uint8)
        # 2-d scatter-max: no flattened index, so capacity*m may exceed
        # int32 range (TPU indices are per-dimension 32-bit)
        return {**state,
                "regs": state["regs"].at[slots.astype(jnp.int32), reg].max(rank)}

    def result(self, state, slots):
        return self._estimate(state["regs"][slots])

    def result_dense(self, state):
        # gather-free fire for contiguous slot ranges: the estimate is
        # one dense [S, m] reduction at memory bandwidth
        return self._estimate(state["regs"])

    def _estimate(self, regs_u8):                              # [S, m]
        # 2^-r built directly in the float32 exponent field
        # ((127 - r) << 23 bitcast to f32 — exact for integer ranks
        # 0..~60, no denormals) — integer ops fuse into the reduction
        # where a transcendental exp2 dominates the fire
        bits = (jnp.uint32(127) - regs_u8.astype(jnp.uint32)) << 23
        inv = jax.lax.bitcast_convert_type(bits, jnp.float32)
        m = jnp.float32(self.m)
        est = self.alpha * m * m / jnp.sum(inv, axis=-1)
        zeros = jnp.sum(regs_u8 == 0, axis=-1).astype(jnp.float32)
        linear = m * (jnp.log(m) - jnp.log(jnp.maximum(zeros, 1.0)))
        use_linear = (est <= 2.5 * m) & (zeros > 0)
        return jnp.where(use_linear, linear, est)

    def merge_slots(self, state, dst, src):
        return {**state,
                "regs": state["regs"].at[dst].max(state["regs"][src])}


class CountMinSketchAggregate(DeviceAggregateFunction):
    """Count-Min sketch: approximate per-item frequencies.

    ``result`` returns the per-slot total weight (exact L1 mass, kept
    in a side counter); per-item frequency estimates are served by
    :meth:`point_query` (a queryable-state style read).
    Guarantee: est ≤ true + eps*L1 with prob 1-delta, eps=e/width,
    delta=e^-depth.

    As constructed by default, the weight and the item are the SAME
    extracted value (a stream of weights that are their own identity).
    ``unit_weights=True`` is the heavy-hitter deployment: every event
    adds 1 and the extracted value is the item alone, hashed as a
    column and never shipped as a value.  ``queries=(item, ...)``
    makes ``result`` read the table: ``int32[N, 1 + W]``, the total,
    then ``point_query`` of each of the W items in that slot (their
    hashes taken once, here, by the function that hashes a column of
    ``add_batch``).
    """

    needs_value = True        # weight (usually 1.0)
    needs_value_hash = True   # item identity

    def __init__(self, depth: int = 4, width: int = 2048, *,
                 unit_weights: bool = False,
                 queries: Sequence[int] | None = None):
        self.depth = depth
        self.width = width
        self.unit_weights = unit_weights
        if unit_weights:
            self.needs_value = False
        self.queries = None if queries is None else tuple(queries)
        if self.queries is not None:
            hashes = hash_int_column_np(np.asarray(self.queries, np.int64))
            self._query_hi, self._query_lo = split_hash64_np(hashes)

    def state_specs(self) -> Dict[str, StateSpec]:
        return {"table": StateSpec((self.depth, self.width), np.dtype(np.int32), 0),
                "total": StateSpec((), np.dtype(np.int32), 0)}

    def update(self, state, slots, values, vh_hi, vh_lo, mask):
        if self.unit_weights:
            w = mask.astype(jnp.int32)                              # [N]
        else:
            w = jnp.where(mask, values.astype(jnp.int32), 0)
        cols = countmin_rows(vh_hi, vh_lo, self.depth, self.width)  # [d, N]
        slots_b = jnp.broadcast_to(slots.astype(jnp.int32)[None, :], cols.shape)
        rows_b = jnp.broadcast_to(
            jnp.arange(self.depth, dtype=jnp.int32)[:, None], cols.shape)
        w_b = jnp.broadcast_to(w[None, :], cols.shape)
        return {**state,
                "table": state["table"].at[slots_b, rows_b, cols].add(w_b),
                "total": state["total"].at[slots].add(w)}

    def result(self, state, slots):
        total = state["total"][slots]
        if self.queries is None:
            return total
        # every (slot, tracked item) pair as one point query
        n, w = slots.shape[0], len(self.queries)
        est = self.point_query(
            state, jnp.repeat(slots, w),
            jnp.tile(jnp.asarray(self._query_hi), n),
            jnp.tile(jnp.asarray(self._query_lo), n))
        return jnp.concatenate([total[:, None], est.reshape(n, w)], axis=1)

    def point_query(self, state, slots, qh_hi, qh_lo):
        """Estimate frequency of items (qh_hi, qh_lo) in slot `slots[i]`."""
        cols = countmin_rows(qh_hi, qh_lo, self.depth, self.width)  # [d, N]
        rows = jnp.arange(self.depth, dtype=jnp.int32)[:, None]
        vals = state["table"][slots.astype(jnp.int32)[None, :], rows, cols]  # [d, N]
        return jnp.min(vals, axis=0)

    def merge_slots(self, state, dst, src):
        return {**state,
                "table": state["table"].at[dst].add(state["table"][src]),
                "total": state["total"].at[dst].add(state["total"][src])}


# ---------------------------------------------------------------------
# The two forms of the quantile sketch's batch update.  Both give the
# same table bit for bit (integer addition in any order; a padding row
# carries an increment of 0 and changes nothing).
# ---------------------------------------------------------------------

#: the TPU's vector registers: 8 sublanes of 128 lanes, and the tile
#: its 2-d layouts keep contiguous in HBM
_SUBLANES, _LANES = 8, 128
#: a batch adds tiles while the table has this many slots to each of
#: its rows.  On a v5e the tile form costs 0.25 us a batch row whatever
#: the table (4.1 ms for 16,384), the cell form 0.14 us a slot of the
#: table whatever the batch (72 ms for 2^19 slots of 2,075 buckets: the
#: rewrite, twice): they cross at 1.8 slots a row
_TILE_FORM_TABLE_ROWS_PER_BATCH_ROW = 2
#: the tile form keeps two scalars a batch row in the TPU's scalar
#: memory (65,536 rows compile on a v5e, 131,072 do not)
_TILE_FORM_MAX_ROWS = 1 << 15


def quantile_update_form(rows: int, capacity: int, buckets: int) -> str:
    """How a batch of `rows` increments reaches `int32[capacity,
    buckets]` on a TPU, from the static shapes alone: "tiles" (of the
    table, which stays in place) for a batch small against the table,
    "cells" otherwise.  There are tiles to add where the TPU holds the
    table bucket-major, (8 buckets x 128 slots) to a tile: of two
    dimensions the one that is a multiple of the 128 lanes goes minor,
    so the capacity has to be one and the buckets must not (a table of
    such buckets is slot-major and scatters cells, as it always has)."""
    if rows < 2 or rows * _TILE_FORM_TABLE_ROWS_PER_BATCH_ROW > capacity \
            or rows > _TILE_FORM_MAX_ROWS:
        return "cells"
    if capacity % _LANES == 0 and buckets % _LANES != 0:
        return "tiles"
    return "cells"


def _add_cells(hist, slots, b, inc):
    # 2-d scatter: no flattened index, so capacity*buckets may exceed
    # int32 range (same rationale as the HLL kernel)
    return hist.at[slots, b].add(inc)


def _add_tiles_tpu(hist, slots, b, inc, interpret=False):
    """Each increment into the (8 buckets x 128 slots) tile that holds
    its cell, in place: the TPU keeps `int32[capacity, buckets]`
    bucket-major when only the capacity is a multiple of 128, so the
    transposed view is the buffer as it lies and the tiles are its
    4 KiB units."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tile_cells = _SUBLANES * _LANES

    def kernel(tile_ref, cell_ref, table_in, table_out):
        # one grid step = one increment; the steps of one tile follow
        # each other (the batch is sorted by tile), so the tile stays
        # in VMEM from its first step to its last, written back once
        i = pl.program_id(0)
        tile = tile_ref[i]

        @pl.when((i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != tile))
        def _():
            table_out[...] = table_in[...]

        at = cell_ref[i] % tile_cells
        shape = (_SUBLANES, _LANES)
        hit = ((jax.lax.broadcasted_iota(jnp.int32, shape, 0) == at // _LANES)
               & (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                  == at % _LANES))
        table_out[...] += jnp.where(hit, cell_ref[i] // tile_cells, 0)

    table = hist.T                                   # [buckets, capacity]
    tiles_a_row = hist.shape[0] // _LANES
    tile = (b // _SUBLANES) * tiles_a_row + slots // _LANES
    # one scalar a row beside its tile: where in the tile, and above
    # that the increment (0 for a padding row, else 1)
    cell = (b % _SUBLANES) * _LANES + slots % _LANES + inc * tile_cells
    order = jnp.argsort(tile)
    block = pl.BlockSpec(
        (_SUBLANES, _LANES),
        lambda i, tile, cell: (tile[i] // tiles_a_row, tile[i] % tiles_a_row))
    out = pl.pallas_call(
        kernel,
        # (inside a `shard_map` the table varies over the mesh axes)
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype,
                                       vma=jax.typeof(table).vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(slots.shape[0],),
            in_specs=[block], out_specs=block),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="quantile_add_tiles",
    )(tile[order], cell[order], table)
    return out.T


def _add_tiles(hist, slots, b, inc):
    # off the TPU a table has no tiles to keep in place, and a scatter
    # of cells is the loop over increments it looks like
    return jax.lax.platform_dependent(hist, slots, b, inc,
                                      tpu=_add_tiles_tpu, default=_add_cells)


def _tile_form_runs() -> bool:
    # what `_add_tiles` resolves to where the backend's programs run
    return jax.default_backend() == "tpu"


class QuantileSketchAggregate(DeviceAggregateFunction):
    """DDSketch-style log-bucketed quantile sketch (t-digest role).

    Buckets: value v>0 → bucket 1 + floor(log(v)/log(gamma)) - offset,
    clamped to [1, buckets-1]; v<=min_value → bucket 0.  Relative error
    of quantile answers ≤ (gamma-1)/2 within [min_value, max_value].
    ``result`` returns the requested quantiles per slot, shape [S, Q].
    """

    needs_value = True

    def __init__(
        self,
        quantiles: Sequence[float] = (0.5, 0.99),
        relative_accuracy: float = 0.01,
        min_value: float = 1e-9,
        max_value: float = 1e9,
    ):
        self.quantiles = tuple(quantiles)
        self.gamma = (1 + relative_accuracy) / (1 - relative_accuracy)
        self.log_gamma = math.log(self.gamma)
        self.min_value = min_value
        self.offset = math.floor(math.log(min_value) / self.log_gamma)
        self.buckets = 2 + int(math.ceil(
            (math.log(max_value) - math.log(min_value)) / self.log_gamma))

    def state_specs(self) -> Dict[str, StateSpec]:
        return {"hist": StateSpec((self.buckets,), np.dtype(np.int32), 0)}

    def _bucket_of(self, values):
        v = values.astype(jnp.float32)
        logs = jnp.log(jnp.maximum(v, self.min_value)) / self.log_gamma
        b = 1 + jnp.floor(logs).astype(jnp.int32) - self.offset
        b = jnp.clip(b, 1, self.buckets - 1)
        return jnp.where(v <= self.min_value, 0, b)

    def update(self, state, slots, values, vh_hi, vh_lo, mask):
        hist = state["hist"]
        add = _add_tiles if quantile_update_form(
            slots.shape[0], hist.shape[0], self.buckets) == "tiles" \
            else _add_cells
        return {**state,
                "hist": add(hist, slots.astype(jnp.int32),
                            self._bucket_of(values), mask.astype(jnp.int32))}

    def update_runs_in_place(self, rows: int, capacity: int) -> bool:
        return _tile_form_runs() and quantile_update_form(
            rows, capacity, self.buckets) == "tiles"

    def result(self, state, slots):
        hist = state["hist"][slots].astype(jnp.float32)          # [S, B]
        cum = jnp.cumsum(hist, axis=-1)
        total = cum[..., -1:]
        # canonical DDSketch bucket estimate 2*gamma^b/(gamma+1):
        # symmetric +-alpha relative error over the bucket's value
        # range (the earlier sqrt-midpoint x 2g/(g+1) form was biased
        # sqrt(gamma) high — worst case 2*alpha at the lower edge,
        # violating the documented (gamma-1)/2 bound)
        b = jnp.arange(self.buckets, dtype=jnp.float32)
        bucket_val = jnp.exp((b + self.offset) * self.log_gamma) * \
            (2.0 / (1.0 + self.gamma))
        bucket_val = bucket_val.at[0].set(0.0)
        outs = []
        for q in self.quantiles:
            target = jnp.maximum(q * total, 1.0)
            # first bucket where cum >= target
            sel = jnp.argmax(cum >= target, axis=-1)             # [S]
            outs.append(bucket_val[sel])
        return jnp.stack(outs, axis=-1)                          # [S, Q]

    def merge_slots(self, state, dst, src):
        return {**state, "hist": state["hist"].at[dst].add(state["hist"][src])}

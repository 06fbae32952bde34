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
  the same p50/p99 queries (BASELINE.md config 3).
"""

from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.ops.device_agg import DeviceAggregateFunction, StateSpec
from flink_tpu.ops.hashing import countmin_rows, hll_register_and_rank


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
    """

    needs_value = True        # weight (usually 1.0)
    needs_value_hash = True   # item identity

    def __init__(self, depth: int = 4, width: int = 2048):
        self.depth = depth
        self.width = width

    def state_specs(self) -> Dict[str, StateSpec]:
        return {"table": StateSpec((self.depth, self.width), np.dtype(np.int32), 0),
                "total": StateSpec((), np.dtype(np.int32), 0)}

    def update(self, state, slots, values, vh_hi, vh_lo, mask):
        w = jnp.where(mask, values.astype(jnp.int32), 0)           # [N]
        cols = countmin_rows(vh_hi, vh_lo, self.depth, self.width)  # [d, N]
        slots_b = jnp.broadcast_to(slots.astype(jnp.int32)[None, :], cols.shape)
        rows_b = jnp.broadcast_to(
            jnp.arange(self.depth, dtype=jnp.int32)[:, None], cols.shape)
        w_b = jnp.broadcast_to(w[None, :], cols.shape)
        return {**state,
                "table": state["table"].at[slots_b, rows_b, cols].add(w_b),
                "total": state["total"].at[slots].add(w)}

    def result(self, state, slots):
        return state["total"][slots]

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
        b = self._bucket_of(values)
        # 2-d scatter: no flattened index, so capacity*buckets may
        # exceed int32 range (same rationale as the HLL kernel)
        return {**state,
                "hist": state["hist"].at[slots.astype(jnp.int32), b].add(
                    mask.astype(jnp.int32))}

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

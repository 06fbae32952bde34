"""DeviceAggregateFunction: the vectorized aggregation contract.

The reference funnels every windowed aggregation through
``AggregateFunction.createAccumulator/add/getResult/merge``
(flink-core/.../functions/AggregateFunction.java:127-160) invoked once
per record (heap: HeapAggregatingState.java:80-89; RocksDB:
RocksDBAggregatingState.java:108-131 — two JNI hops per record).

Here the same contract is re-shaped for TPU execution: accumulators for
ALL keys of a key-group range live as struct-of-arrays in HBM
(``state[name][slot, ...]``), and ``add`` is replaced by a batched
``update(state, slots, values, vh_hi, vh_lo)`` that scatters a whole
micro-batch in one jit-compiled device dispatch.  Each device aggregate
is *also* a plain AggregateFunction (scalar numpy accumulators =
single-slot arrays), so the identical aggregate runs on the heap
backend for differential testing and on the TPU backend for speed.

Slots are dense indices handed out by the backend's per-window key
index (flink_tpu/state/tpu_backend.py); duplicate slots within a batch
are legal and resolved by the scatter combinator (add/max/min).
"""

from __future__ import annotations

import abc
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.core.functions import AggregateFunction
from flink_tpu.runtime.tracing import traced_jit


class StateSpec(NamedTuple):
    """Per-slot layout of one state component."""
    shape: Tuple[int, ...]   # trailing shape per slot (() for scalar)
    dtype: np.dtype
    fill: float              # initial/cleared value


class DeviceAggregateFunction(AggregateFunction):
    """Batched aggregation over slot-indexed HBM state.

    Subclasses define per-slot state layout and jnp-traceable
    update/result/merge; the base class derives the scalar
    AggregateFunction contract (accumulator = dict of single-slot numpy
    arrays) so the heap backend runs the same logic per-record.
    """

    #: update() consumes the `values` array
    needs_value: bool = False
    #: update() consumes value-hash lanes (distinct-count style sketches)
    needs_value_hash: bool = False
    #: dtype the batcher should coerce values to
    value_dtype: np.dtype = np.float32

    # ---- device contract -------------------------------------------
    def extract_value(self, value):
        """Project the aggregated quantity out of a record (e.g. a
        tuple field) before it is buffered/hashed for the device; the
        IN-side of the reference's AggregateFunction.add happens here
        so the device batch carries plain numerics."""
        return value

    def extract_column(self, values):
        """Vectorized twin of extract_value over a whole value column
        (ndarray, or tuple of ndarrays for multi-column records).
        Returns the numeric column to aggregate, or None when this
        aggregate needs per-row extraction (the caller then boxes).
        Default: the identity — valid exactly when extract_value is
        still the base identity."""
        if type(self).extract_value is DeviceAggregateFunction.extract_value:
            return values
        return None

    def compress_value_hash(self, vh_hi: np.ndarray, vh_lo: np.ndarray):
        """Optionally shrink the per-record value-hash lanes on the
        host before transfer (e.g. HLL needs only register + rank, 3
        bytes instead of 8).  Whatever this returns is what update()
        receives as (vh_hi, vh_lo); default is identity."""
        return vh_hi, vh_lo

    @abc.abstractmethod
    def state_specs(self) -> Dict[str, StateSpec]:
        ...

    def init_state(self, capacity: int) -> Dict[str, jnp.ndarray]:
        return {
            name: jnp.full((capacity, *spec.shape), spec.fill, dtype=spec.dtype)
            for name, spec in self.state_specs().items()
        }

    def grow_state(self, state: Dict[str, jnp.ndarray], new_capacity: int) -> Dict[str, jnp.ndarray]:
        out = {}
        for name, spec in self.state_specs().items():
            old = state[name]
            pad = jnp.full((new_capacity - old.shape[0], *spec.shape), spec.fill, dtype=spec.dtype)
            out[name] = jnp.concatenate([old, pad], axis=0)
        return out

    @abc.abstractmethod
    def update(
        self,
        state: Dict[str, jnp.ndarray],
        slots: jnp.ndarray,          # [N] int32 slot per record
        values: jnp.ndarray,         # [N] value_dtype (dummy if !needs_value)
        vh_hi: jnp.ndarray,          # [N] uint32 (dummy if !needs_value_hash)
        vh_lo: jnp.ndarray,          # [N] uint32
        mask: jnp.ndarray,           # [N] bool — False entries are padding
    ) -> Dict[str, jnp.ndarray]:
        ...

    def update_runs_in_place(self, rows: int, capacity: int) -> bool:
        """Whether `update` of a `rows`-row batch against `capacity`
        slots, on the platform the backend's programs run on, adds to
        the table where it lies and not by a scatter of single cells:
        what the state backend notes per flush
        (`STATE_STATS.flush_row_form_batches`).  Only an aggregate
        whose update has such a form says yes."""
        return False

    @abc.abstractmethod
    def result(self, state: Dict[str, jnp.ndarray], slots: jnp.ndarray) -> jnp.ndarray:
        """Finalize: gather `slots` and compute per-slot results
        (device twin of AggregateFunction.getResult)."""
        ...

    def result_dense(self, state: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Finalize EVERY row of an already-sliced state block —
        the gather-free fire path for contiguous slot ranges (XLA
        gathers run ~2.5M rows/s on this hardware; a dynamic_slice +
        dense reduction runs at memory bandwidth).  Default falls back
        through `result` with iota slots; subclasses override to skip
        the indexing entirely."""
        first = next(iter(state.values()))
        return self.result(state, jnp.arange(first.shape[0],
                                             dtype=jnp.int32))

    def merge_slots(
        self, state: Dict[str, jnp.ndarray], dst: jnp.ndarray, src: jnp.ndarray
    ) -> Dict[str, jnp.ndarray]:
        """state[dst] ⊕= state[src] — session-window namespace merging
        (device twin of AggregateFunction.merge)."""
        raise NotImplementedError(f"{type(self).__name__} does not support merging")

    def merge_rows(
        self, state: Dict[str, jnp.ndarray], dst: jnp.ndarray, src: jnp.ndarray
    ) -> Dict[str, jnp.ndarray]:
        """state[dst] ⊕= state[src] for pairwise (dst, src) rows with
        UNIQUE dst — the ``jit(vmap(merge))`` batch-merge kernel: gather
        both row sets, vmap a single-pair merge (merge_slots over a
        2-row stacked state) across them, scatter back with one
        .at[dst].set.  Repeated dst entries would race under .set; the
        backend's batch-merge driver rounds multi-source merges so each
        dispatch is repeat-free (merge_slots stays the repeat-tolerant
        scalar path).  A pair whose dst lies past the table is
        padding: it reads a clamped row and its write is dropped."""
        specs = self.state_specs()

        def pair_merge(rows_a, rows_b):
            stacked = {k: jnp.stack([rows_a[k], rows_b[k]]) for k in rows_a}
            merged = self.merge_slots(stacked,
                                      jnp.zeros(1, jnp.int32),
                                      jnp.ones(1, jnp.int32))
            return {k: v[0] for k, v in merged.items()}

        rows_a = {k: state[k][dst] for k in specs}
        rows_b = {k: state[k][src] for k in specs}
        merged = jax.vmap(pair_merge)(rows_a, rows_b)
        out = dict(state)
        for k in specs:
            out[k] = out[k].at[dst].set(merged[k], mode="drop")
        return out

    def clear_slots(self, state: Dict[str, jnp.ndarray], slots: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        out = dict(state)
        for name, spec in self.state_specs().items():
            fill = jnp.full((slots.shape[0], *spec.shape), spec.fill, dtype=spec.dtype)
            out[name] = out[name].at[slots].set(fill)
        return out

    # ---- scalar AggregateFunction contract (heap-backend twin) ------
    # single-record programs are jit-cached: the scalar path runs once
    # per record (heap backend / composite SQL aggregates), so eager
    # dispatch per op would dominate
    def _scalar_jits(self):
        jits = getattr(self, "_scalar_jit_cache", None)
        if jits is None:
            # pinned to the CPU backend: single-record accumulators are
            # tiny, and dispatching them to the TPU per record costs a
            # device round trip each — the scalar path exists exactly
            # where per-record semantics are required, so it must stay
            # a microsecond-scale host call
            agg_name = type(self).__name__
            jits = {
                "add": traced_jit(lambda st, v, hi, lo: self.update(
                    st, jnp.zeros(1, jnp.int32), v, hi, lo,
                    jnp.ones(1, bool)),
                    name=f"agg.{agg_name}.add", backend="cpu"),
                "result": traced_jit(lambda st: self.result(
                    st, jnp.zeros(1, jnp.int32)),
                    name=f"agg.{agg_name}.result", backend="cpu"),
                "merge": traced_jit(lambda st: self.merge_slots(
                    st, jnp.array([0], jnp.int32),
                    jnp.array([1], jnp.int32)),
                    name=f"agg.{agg_name}.merge", backend="cpu"),
            }
            self._scalar_jit_cache = jits
        return jits

    def create_accumulator(self):
        return {name: np.full(spec.shape if spec.shape else (1,), spec.fill, dtype=spec.dtype)
                for name, spec in self.state_specs().items()}

    def add(self, value, accumulator):
        state = {k: np.asarray(v)[None] if np.asarray(v).shape == ()
                 else np.asarray(v).reshape(1, *self.state_specs()[k].shape)
                 for k, v in accumulator.items()}
        vals, hi, lo = self._host_record(value)
        new = jax.tree_util.tree_map(
            np.asarray, self._scalar_jits()["add"](state, vals, hi, lo))
        return {k: np.asarray(v)[0] if self.state_specs()[k].shape == ()
                else np.asarray(v)[0] for k, v in new.items()}

    def get_result(self, accumulator):
        state = {k: np.asarray(v).reshape(1, *self.state_specs()[k].shape)
                 for k, v in accumulator.items()}
        out = np.asarray(self._scalar_jits()["result"](state))[0]
        return out.item() if np.ndim(out) == 0 else out

    def merge(self, a, b):
        specs = self.state_specs()
        stacked = {k: np.stack([np.asarray(a[k]).reshape(specs[k].shape),
                                np.asarray(b[k]).reshape(specs[k].shape)])
                   for k in specs}
        merged = self._scalar_jits()["merge"](stacked)
        return {k: np.asarray(v)[0] for k, v in merged.items()}

    def _host_record(self, value):
        """Turn one scalar value into (values[1], vh_hi[1], vh_lo[1])."""
        from flink_tpu.core.keygroups import stable_hash64
        value = self.extract_value(value)
        if self.needs_value_hash:
            h = stable_hash64(value)
            hi = np.array([h >> 32], np.uint32)
            lo = np.array([h & 0xFFFFFFFF], np.uint32)
        else:
            hi = np.zeros(1, np.uint32)
            lo = np.zeros(1, np.uint32)
        if self.needs_value:
            vals = np.array([value], self.value_dtype)
        else:
            vals = np.zeros(1, self.value_dtype)
        return vals, hi, lo


# ---------------------------------------------------------------------
# Plain arithmetic aggregates (sum/count/min/max/avg) — the TPU twins of
# the reference's SumAggregator / rolling reduce on numeric fields
# (flink-streaming-java/.../api/functions/aggregation/).
# ---------------------------------------------------------------------

class SumAggregate(DeviceAggregateFunction):
    needs_value = True

    def __init__(self, dtype=np.float32):
        self._dtype = np.dtype(dtype)
        self.value_dtype = self._dtype

    def state_specs(self):
        return {"sum": StateSpec((), self._dtype, 0)}

    def update(self, state, slots, values, vh_hi, vh_lo, mask):
        vals = jnp.where(mask, values, jnp.zeros((), values.dtype))
        return {**state, "sum": state["sum"].at[slots].add(vals)}

    def result(self, state, slots):
        return state["sum"][slots]

    def result_dense(self, state):
        return state["sum"]

    def merge_slots(self, state, dst, src):
        return {**state, "sum": state["sum"].at[dst].add(state["sum"][src])}


class CountAggregate(DeviceAggregateFunction):
    def state_specs(self):
        return {"count": StateSpec((), np.dtype(np.int32), 0)}

    def update(self, state, slots, values, vh_hi, vh_lo, mask):
        return {**state, "count": state["count"].at[slots].add(mask.astype(jnp.int32))}

    def result(self, state, slots):
        return state["count"][slots]

    def result_dense(self, state):
        return state["count"]

    def merge_slots(self, state, dst, src):
        return {**state, "count": state["count"].at[dst].add(state["count"][src])}


class MinAggregate(DeviceAggregateFunction):
    needs_value = True

    def __init__(self, dtype=np.float32):
        self._dtype = np.dtype(dtype)
        self.value_dtype = self._dtype

    def state_specs(self):
        big = np.finfo(self._dtype).max if np.issubdtype(self._dtype, np.floating) \
            else np.iinfo(self._dtype).max
        return {"min": StateSpec((), self._dtype, big)}

    def update(self, state, slots, values, vh_hi, vh_lo, mask):
        fill = self.state_specs()["min"].fill
        vals = jnp.where(mask, values, jnp.full((), fill, values.dtype))
        return {**state, "min": state["min"].at[slots].min(vals)}

    def result(self, state, slots):
        return state["min"][slots]

    def merge_slots(self, state, dst, src):
        return {**state, "min": state["min"].at[dst].min(state["min"][src])}


class MaxAggregate(DeviceAggregateFunction):
    needs_value = True

    def __init__(self, dtype=np.float32):
        self._dtype = np.dtype(dtype)
        self.value_dtype = self._dtype

    def state_specs(self):
        small = np.finfo(self._dtype).min if np.issubdtype(self._dtype, np.floating) \
            else np.iinfo(self._dtype).min
        return {"max": StateSpec((), self._dtype, small)}

    def update(self, state, slots, values, vh_hi, vh_lo, mask):
        fill = self.state_specs()["max"].fill
        vals = jnp.where(mask, values, jnp.full((), fill, values.dtype))
        return {**state, "max": state["max"].at[slots].max(vals)}

    def result(self, state, slots):
        return state["max"][slots]

    def merge_slots(self, state, dst, src):
        return {**state, "max": state["max"].at[dst].max(state["max"][src])}


class AvgAggregate(DeviceAggregateFunction):
    needs_value = True

    def state_specs(self):
        return {"sum": StateSpec((), np.dtype(np.float32), 0),
                "count": StateSpec((), np.dtype(np.int32), 0)}

    def update(self, state, slots, values, vh_hi, vh_lo, mask):
        vals = jnp.where(mask, values, jnp.zeros((), values.dtype))
        return {**state,
                "sum": state["sum"].at[slots].add(vals),
                "count": state["count"].at[slots].add(mask.astype(jnp.int32))}

    def result(self, state, slots):
        cnt = state["count"][slots]
        return state["sum"][slots] / jnp.maximum(cnt, 1).astype(jnp.float32)

    def merge_slots(self, state, dst, src):
        return {**state,
                "sum": state["sum"].at[dst].add(state["sum"][src]),
                "count": state["count"].at[dst].add(state["count"][src])}

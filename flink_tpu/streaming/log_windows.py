"""Log-structured window engines — the combiner tier.

The reference's windowed-aggregation hot path is one random
read-modify-write of keyed state per record (heap:
``HeapAggregatingState.add`` → ``stateTable.transform``,
HeapAggregatingState.java:80-89; RocksDB: a get/deserialize/add/put
round trip, RocksDBAggregatingState.java:108-131).  At multi-GB state
that mechanism is memory-latency-bound on every substrate: one cache
or HBM line touched per update, on the host and in an XLA scatter
alike.

These engines restructure the work the TPU-first way (SURVEY.md §7
"per-record semantics vs batched execution"): **ingest appends** the
record's aggregate *cells* to a per-window log at memcpy speed, and
the **fire sorts the log and reduces each key's run densely** —
adaptive LSD radix sort + segmented reduction (native/host_runtime.cpp
``ft_*_log_fire``), with an optional on-device finish
(``finish_tier="device"``) that runs the transcendental estimate phase
as one jitted scan over the compacted cells.  It is the same
pre-aggregation seam the reference exposes as chained combiners
(AggregateUtil.scala:1028): state per window is bounded by
min(events, keys x m) via periodic log compaction, and a window's
state snapshot is its (compacted) log — smaller than a dense register
file whenever events/window < keys x m.

Engines:
- :class:`LogStructuredTumblingWindows` — config #1/#2 shapes.
- :class:`LogStructuredSlidingWindows` — pane logs at slide
  granularity; a window fire concatenates its panes' logs (the merge
  is free — the sort regroups across panes).  One log append per
  record regardless of the overlap factor, where the reference writes
  every record into size/slide window states
  (SlidingEventTimeWindows.assignWindows).
- :class:`LogStructuredSessionWindows` — sort by (key, ts), split
  runs at gaps (TimeWindow.intersects is inclusive: abutting windows
  merge), close sessions behind the watermark; each closed session's
  Count-Min sketch builds in an L1-resident scratch — the sort makes
  the working set session-local instead of all-keys-live.

Scope: integer-keyed streams (the key rides in the log; grouping is
exact) and mergeable aggregates with a cell decomposition —
HyperLogLog (cell = (register, rank), combine = max), Sum
(cell = value, combine = add), DDSketch quantiles (cell = bucket,
combine = add), Count-Min (sessions).  Other aggregates use the
device-resident scatter engines (vectorized.py), which also remain
the multi-chip path (parallel/mesh_windows.py).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import flink_tpu.native as nat
from flink_tpu.runtime.device_stats import TELEMETRY
from flink_tpu.runtime.tracing import get_tracer, traced_jit

_perf_ns = time.perf_counter_ns
from flink_tpu.ops.device_agg import DeviceAggregateFunction, SumAggregate
from flink_tpu.ops.hashing import split_hash64_np
from flink_tpu.ops.sketches import (
    CountMinSketchAggregate,
    HyperLogLogAggregate,
    QuantileSketchAggregate,
)


def _is_single_window(starts: np.ndarray) -> bool:
    """One vectorized pass deciding the common replayed-log shape
    (every record in one window) without np.unique's sort — shared by
    the generic and string tumbling engines."""
    return bool(len(starts)) and starts[0] == starts[-1] \
        and bool((starts == starts[0]).all())


class _WindowLog:
    """Columnar append log for one window (or pane).  ``version``
    counts mutations — an unchanged version means the snapshot chunk
    hash can be reused (incremental-checkpoint seam)."""

    __slots__ = ("keys", "cols", "count", "version", "compacted_size")

    def __init__(self):
        self.keys: List[np.ndarray] = []
        self.cols: List[Tuple[np.ndarray, ...]] = []
        self.count = 0
        self.version = 0
        #: cell count right after the last compaction — compaction
        #: re-arms only once the log has grown well past it, so a log
        #: whose compacted floor sits above the threshold (many keys x
        #: buckets) cannot re-sort itself on every ingest batch
        self.compacted_size = 0

    def append(self, keys: np.ndarray, *cols: np.ndarray) -> None:
        self.keys.append(keys)
        self.cols.append(cols)
        self.count += len(keys)
        self.version += 1

    def concat(self) -> Tuple[np.ndarray, Tuple[np.ndarray, ...]]:
        keys = (self.keys[0] if len(self.keys) == 1
                else np.concatenate(self.keys))
        n_cols = len(self.cols[0])
        cols = tuple(
            (self.cols[0][j] if len(self.cols) == 1
             else np.concatenate([c[j] for c in self.cols]))
            for j in range(n_cols))
        self.keys = [keys]
        self.cols = [cols]
        return keys, cols

    def compact(self, mode) -> None:
        keys, cols = self.concat()
        ck, ccols = mode.compact(keys, cols)
        self.keys = [ck]
        self.cols = [ccols]
        self.count = len(ck)
        self.compacted_size = self.count

    def should_compact(self, threshold: int) -> bool:
        return (self.count > threshold
                and self.count >= 2 * self.compacted_size)


class _SumTabLog:
    """Adaptive sum window state (the hash-combiner tier): a dense
    C++ key->sum table while the distinct-key count stays
    cache-resident (the per-record probe+add is then L1/L2-local —
    the word-count shape), spilling to the ordinary cell log when
    cardinality outgrows it (the sort+reduce fire then wins).  Same
    interface as _WindowLog."""

    __slots__ = ("tab", "log", "max_distinct", "version")

    def __init__(self, max_distinct: int = 1 << 19):
        self.tab = nat.NativeSumTable()  # starts small, grows
        self.log: Optional[_WindowLog] = None
        self.max_distinct = max_distinct
        self.version = 0

    @property
    def count(self) -> int:
        return self.tab.n if self.log is None else self.log.count

    def append(self, keys: np.ndarray, values: np.ndarray) -> None:
        self.version += 1
        if self.log is None:
            values = np.asarray(values, np.float64)
            consumed = self.tab.ingest(keys, values, self.max_distinct)
            if consumed == len(keys):
                return
            # cardinality outgrew the table: spill to log form and
            # free the native table (it is never consulted again)
            self.log = _WindowLog()
            tk, tsums = self.tab.export()
            self.log.append(tk, tsums)
            self.tab = None
            keys, values = keys[consumed:], values[consumed:]
        self.log.append(keys, np.asarray(values, np.float64))

    def concat(self):
        if self.log is None:
            tk, tsums = self.tab.export()
            return tk, (tsums,)
        return self.log.concat()

    def compact(self, mode) -> None:
        if self.log is not None:
            self.log.compact(mode)

    def should_compact(self, threshold: int) -> bool:
        return self.log is not None \
            and self.log.should_compact(threshold)


# ---------------------------------------------------------------------
# per-aggregate cell decompositions
# ---------------------------------------------------------------------

class _HllMode:
    name = "hll"
    can_compact = True

    @staticmethod
    def upgrade_cols(cols):
        return cols

    def new_log(self):
        return _WindowLog()

    def __init__(self, agg: HyperLogLogAggregate, finish_tier: str):
        if agg.precision > 16:
            raise ValueError("log engine supports precision <= 16 "
                             "(u16 register cells)")
        self.agg = agg
        if finish_tier == "auto":
            # startup link micro-probe, not a hardcoded host default
            from flink_tpu.ops.link_probe import recommended_finish_tier
            finish_tier = recommended_finish_tier()
        self.finish_tier = finish_tier
        self._jit_finish = None

    def make_cols(self, values, value_hashes):
        if value_hashes is None:
            from flink_tpu.streaming.vectorized import hash_keys_np
            value_hashes = hash_keys_np(values)
        vh = np.asarray(value_hashes)
        if nat.available() and vh.dtype == np.uint64:
            # one fused C++ pass (clz rank + masked register) — the
            # numpy path below costs ~8 passes incl. a float log2
            return nat.hll_make_cells(vh, self.agg.precision)
        hi, lo = split_hash64_np(vh)
        ranks, regs = self.agg.compress_value_hash(hi, lo)
        return (np.ascontiguousarray(regs, np.uint16),
                np.ascontiguousarray(ranks, np.uint8))

    def compact(self, keys, cols):
        ck, cr, crk, _ = nat.hll_log_compact(keys, cols[0], cols[1],
                                             self.agg.precision)
        return ck, (cr, crk)

    def fire(self, keys, cols):
        if self.finish_tier == "device":
            ck, cr, crk, ends = nat.hll_log_compact(
                keys, cols[0], cols[1], self.agg.precision)
            return ck[ends - 1], self._device_finish(crk, ends)
        return nat.hll_log_fire(keys, cols[0], cols[1], self.agg.precision)

    def _device_finish(self, ranks: np.ndarray, ends: np.ndarray):
        """One jitted pass over the compacted cells: exp2
        contributions, cumsum, per-key diff at run ends, estimate —
        the dense phase of the fire on the device (power-of-two jit
        shapes)."""
        import jax
        import jax.numpy as jnp

        if self._jit_finish is None:
            m = float(self.agg.m)
            alpha = self.agg.alpha

            def finish(ranks_p, ends_p, n_cells, n_keys):
                cell_live = jnp.arange(ranks_p.shape[0]) < n_cells
                inv = jnp.where(
                    cell_live,
                    jnp.exp2(-ranks_p.astype(jnp.float32)) - 1.0, 0.0)
                cs = jnp.cumsum(inv)
                key_live = jnp.arange(ends_p.shape[0]) < n_keys
                e = jnp.where(key_live, ends_p, 1)
                cum_at_end = cs[e - 1]
                prev = jnp.concatenate([jnp.zeros(1), cum_at_end[:-1]])
                seg = cum_at_end - prev
                prev_e = jnp.concatenate([jnp.zeros(1, e.dtype), e[:-1]])
                n_present = (e - prev_e).astype(jnp.float32)
                sum_inv = m + seg
                est = alpha * m * m / sum_inv
                zeros = m - n_present
                linear = m * (jnp.log(m) - jnp.log(jnp.maximum(zeros, 1.0)))
                return jnp.where((est <= 2.5 * m) & (zeros > 0),
                                 linear, est)

            self._jit_finish = traced_jit(finish, name="log.hll.finish")
        tracer = get_tracer()
        n_cells, n_keys = len(ranks), len(ends)
        with tracer.phase("log.finish.pad"):
            pc = 1 << max(0, (n_cells - 1)).bit_length()
            pk = 1 << max(0, (n_keys - 1)).bit_length()
            ranks_p = np.zeros(pc, np.uint8)
            ranks_p[:n_cells] = ranks
            ends_p = np.ones(pk, np.int32)
            ends_p[:n_keys] = ends
        with tracer.phase("log.finish.device"):
            # explicit device_put: the H2D starts before the dispatch
            dev = jax.devices()[0]
            if TELEMETRY.enabled:
                t0 = _perf_ns()
                d_ranks = jax.device_put(ranks_p, dev)
                d_ends = jax.device_put(ends_p, dev)
                TELEMETRY.record_transfer(
                    "h2d", ranks_p.nbytes + ends_p.nbytes, t0, _perf_ns(),
                    "log.finish")
                t1 = _perf_ns()
                out = np.asarray(self._jit_finish(d_ranks, d_ends,
                                                  np.int32(n_cells),
                                                  np.int32(n_keys)))
                TELEMETRY.record_transfer("d2h", out.nbytes, t1, _perf_ns(),
                                          "log.finish")
                TELEMETRY.note_fire_read()
            else:
                out = np.asarray(self._jit_finish(
                    jax.device_put(ranks_p, dev),
                    jax.device_put(ends_p, dev),
                    np.int32(n_cells), np.int32(n_keys)))
            return out[:n_keys].astype(np.float64)


class _SumMode:
    name = "sum"
    can_compact = True

    @staticmethod
    def upgrade_cols(cols):
        return cols

    def __init__(self, agg: SumAggregate, finish_tier: str):
        self.agg = agg

    def new_log(self):
        return _SumTabLog()

    def make_cols(self, values, value_hashes):
        return (np.asarray(values, np.float64),)

    def compact(self, keys, cols):
        ks, sums = nat.sum_log_fire(keys, cols[0])
        return ks, (sums,)

    def fire(self, keys, cols):
        ks, sums = nat.sum_log_fire(keys, cols[0])
        return ks, sums.astype(self.agg.value_dtype)


class _QuantileMode:
    name = "quantile"
    #: count-combining compaction: (key, bucket) duplicates collapse
    #: into count cells, bounding a window's log at keys x buckets
    #: cells regardless of event volume (the round-2 gap).  Cells are
    #: (bucket u16, count u32); raw appends carry count 1.
    can_compact = True

    def new_log(self):
        return _WindowLog()

    def __init__(self, agg: QuantileSketchAggregate, finish_tier: str):
        if agg.buckets > (1 << 16):
            raise ValueError("log engine supports <= 65536 buckets")
        self.agg = agg

    @staticmethod
    def upgrade_cols(cols):
        """Pre-count-cell checkpoints logged (bucket,) only — raw
        cells, weight 1."""
        if len(cols) == 1:
            return [cols[0], np.ones(len(cols[0]), np.uint32)]
        return cols

    def make_cols(self, values, value_hashes):
        # numpy twin of QuantileSketchAggregate._bucket_of (f32 math to
        # match the device kernel's bucketing)
        agg = self.agg
        v = np.asarray(values, np.float32)
        logs = np.log(np.maximum(v, np.float32(agg.min_value)),
                      dtype=np.float32) / np.float32(agg.log_gamma)
        b = 1 + np.floor(logs).astype(np.int32) - agg.offset
        b = np.clip(b, 1, agg.buckets - 1)
        b = np.where(v <= agg.min_value, 0, b)
        return (b.astype(np.uint16), np.ones(len(v), np.uint32))

    def compact(self, keys, cols):
        ck, cb, cc = nat.qsketch_log_compact(keys, cols[0], cols[1],
                                             self.agg.buckets)
        return ck, (cb, cc)

    def fire(self, keys, cols):
        agg = self.agg
        # the kernel computes gamma^(b-0.5) * mid_corr; folding
        # sqrt(gamma) into the correction yields the canonical
        # DDSketch estimate 2*gamma^b/(gamma+1) (symmetric +-alpha —
        # see QuantileSketchAggregate.result)
        mid_corr = 2.0 * float(np.sqrt(agg.gamma)) / (1.0 + agg.gamma)
        # never-compacted logs are all count-1 cells: the unweighted
        # kernel path carries the bucket inside the sorted record
        # (sequential walk, no per-cell gather) — one vectorized scan
        # decides, which is noise next to the sort it saves on
        counts = cols[1]
        if (counts == 1).all():
            counts = None
        ks, q = nat.qsketch_log_fire(keys, cols[0], agg.buckets,
                                     agg.quantiles, agg.log_gamma,
                                     agg.offset, mid_corr,
                                     counts=counts)
        return ks, q


def _as_u64_keys(engine, keys) -> np.ndarray:
    """Normalize integer keys to their uint64 bit pattern (exact
    grouping for signed and unsigned alike); the signedness is locked
    on the first batch — a later flip would silently reinterpret keys
    >= 2^63 emitted from earlier batches, so it is rejected."""
    keys = np.asarray(keys)
    if not np.issubdtype(keys.dtype, np.integer):
        raise TypeError("log engine requires integer keys "
                        "(the key rides in the log)")
    signed = bool(np.issubdtype(keys.dtype, np.signedinteger))
    if engine._keys_signed is None:
        engine._keys_signed = signed
    elif engine._keys_signed != signed:
        raise TypeError(
            "key dtype signedness changed mid-stream "
            f"(was {'signed' if engine._keys_signed else 'unsigned'}, "
            f"got {keys.dtype}); keep the key dtype stable")
    if signed:
        return keys.astype(np.int64, copy=False).view(np.uint64)
    return keys.astype(np.uint64, copy=False)


def _keys_out(engine, keys_u64: np.ndarray) -> np.ndarray:
    return (keys_u64.view(np.int64) if engine._keys_signed
            else keys_u64)


def _mode_for(agg: DeviceAggregateFunction, finish_tier: str):
    if isinstance(agg, HyperLogLogAggregate):
        return _HllMode(agg, finish_tier)
    if isinstance(agg, SumAggregate):
        return _SumMode(agg, finish_tier)
    if isinstance(agg, QuantileSketchAggregate):
        return _QuantileMode(agg, finish_tier)
    raise TypeError(
        "log-structured engines support HyperLogLog / Sum / "
        "QuantileSketch cell decompositions; use the vectorized "
        f"engines for {type(agg).__name__}")


# ---------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------

class LogStructuredTumblingWindows:
    """Batched keyBy().window(Tumbling...).aggregate(agg), combiner
    tier.  Same engine interface as VectorizedTumblingWindows.

    finish_tier: "host" (C++ fused sort+reduce), "device" (C++
    sort/compact, then one jitted finish on TPU — HLL only), or
    "auto" (resolved by the one-shot H2D link micro-probe in
    flink_tpu/ops/link_probe.py: a slow link keeps the finish on the
    host, a fast one moves it to the device).
    """

    def __init__(self, aggregate: DeviceAggregateFunction,
                 window_size_ms: int,
                 compact_threshold: int = 64 << 20,
                 finish_tier: str = "auto",
                 emit=None):
        if not nat.available():
            raise RuntimeError(f"native runtime required: {nat.load_error()}")
        self.agg = aggregate
        self.mode = _mode_for(aggregate, finish_tier)
        self.size = window_size_ms
        #: how far past a (pane) start a record stays live — the
        #: sliding subclass widens this to the full window size
        self.lateness_horizon = window_size_ms
        self.compact_threshold = compact_threshold
        self.windows: Dict[int, _WindowLog] = {}
        self.watermark = -(2 ** 63)
        self.emit = emit
        self.emitted: List[Tuple[Any, Any, int, int]] = []
        self.emit_arrays = False
        self.fired: List[Tuple[np.ndarray, np.ndarray, int, int]] = []
        self.num_late_dropped = 0
        #: signed input keys ride as their uint64 bit pattern and view
        #: back at fire (locked on the first batch)
        self._keys_signed = None
        #: window start -> (log version, chunk hash) — skips
        #: re-hashing unchanged windows at snapshot time
        self._chunk_cache: Dict[int, Tuple[int, str]] = {}

    # ---- ingestion --------------------------------------------------
    def process_batch(self, keys, timestamps, values=None,
                      key_hashes=None, value_hashes=None) -> None:
        with get_tracer().phase("log.append"):
            self._append_batch(keys, timestamps, values, value_hashes)

    def _append_batch(self, keys, timestamps, values, value_hashes) -> None:
        ts = np.asarray(timestamps, np.int64)
        keys = _as_u64_keys(self, keys)
        starts = ts - np.mod(ts, self.size)
        live = starts + self.lateness_horizon - 1 > self.watermark
        if not live.all():
            self.num_late_dropped += int((~live).sum())
            if not live.any():
                return
            keys, ts, starts = keys[live], ts[live], starts[live]
            if values is not None:
                values = np.asarray(values)[live]
            if value_hashes is not None:
                value_hashes = np.asarray(value_hashes)[live]

        cols = self.mode.make_cols(values, value_hashes)
        # skip np.unique's sort for the common single-window batch
        uniq_starts = (starts[:1] if _is_single_window(starts)
                       else np.unique(starts))
        for start in uniq_starts:
            log = self.windows.get(int(start))
            if log is None:
                log = self.windows[int(start)] = self.mode.new_log()
            if len(uniq_starts) == 1:
                log.append(keys, *cols)
            else:
                mask = starts == start
                log.append(keys[mask], *(c[mask] for c in cols))
            if self.mode.can_compact \
                    and log.should_compact(self.compact_threshold):
                log.compact(self.mode)

    def flush(self, grow_to: Optional[int] = None) -> None:
        """No device micro-batch to flush — kept for interface parity."""

    # ---- firing -----------------------------------------------------
    def advance_watermark(self, watermark: int) -> int:
        self.watermark = watermark
        fired = 0
        for start in sorted(self.windows):
            if start + self.size - 1 > watermark:
                continue
            log = self.windows.pop(start)
            if log.count == 0:
                continue
            with get_tracer().phase("log.concat"):
                keys, cols = log.concat()
            fired += self._fire_window(keys, cols, start, start + self.size)
        if TELEMETRY.enabled:
            TELEMETRY.note_windows_fired(fired)
        return fired

    def _fire_window(self, keys, cols, start: int, end: int) -> int:
        out_keys, results = self.mode.fire(keys, cols)
        self._emit(_keys_out(self, out_keys), results, start, end)
        return len(out_keys)

    def _emit(self, out_keys, results, start: int, end: int) -> None:
        if self.emit_arrays:
            self.fired.append((out_keys, results, start, end))
        elif self.emit is not None:
            for k, r in zip(out_keys, results):
                self.emit(k, r, start, end)
        else:
            self.emitted.extend(zip(out_keys, results,
                                    [start] * len(out_keys),
                                    [end] * len(out_keys)))

    # ---- checkpoint integration ------------------------------------
    def snapshot(self) -> dict:
        """Per-window compacted logs as content-addressed SharedChunks
        — the storage stores each distinct chunk once across retained
        checkpoints, so a window that received no records since the
        last checkpoint re-uploads ~0 bytes (round-2 verdict item 4;
        ref role: the RocksDB backend's per-SST incremental upload).
        A version cache skips re-hashing untouched windows; payloads
        stay attached so local-recovery restores never need the
        storage registry."""
        from flink_tpu.state.shared_registry import SharedChunk
        wins = {}
        live_starts = set()
        for start, log in self.windows.items():
            start = int(start)
            live_starts.add(start)
            cached = self._chunk_cache.get(start)
            keys, cols = log.concat()
            # ALWAYS copy: the payload may be stored by any retained
            # checkpoint (even one whose predecessor aborted before
            # registering), so it must never alias live arrays.  The
            # version cache only skips the re-HASH.
            payload = {"keys": keys.copy(),
                       "cols": [c.copy() for c in cols]}
            if cached is not None and cached[0] == log.version:
                wins[start] = SharedChunk(payload, chunk_hash=cached[1])
                continue
            chunk = SharedChunk(payload)
            self._chunk_cache[start] = (log.version, chunk.hash)
            wins[start] = chunk
        for start in list(self._chunk_cache):
            if start not in live_starts:
                del self._chunk_cache[start]
        return {"mode": self.mode.name, "size": self.size,
                "watermark": self.watermark,
                "num_late_dropped": self.num_late_dropped,
                "windows": wins,
                "keys_signed": self._keys_signed,
                # sliding subclass: without it a restored engine would
                # re-fire already-fired windows from pruned panes
                "fired_horizon": getattr(self, "_fired_horizon", None)}

    def restore(self, snap: dict) -> None:
        self.restore_many([snap])

    def restore_many(self, snaps, keep_fn=None) -> None:
        """Restore from one snapshot — or MERGE several after a
        parallelism change, keeping only the rows this subtask owns
        (`keep_fn`: uint64 key bit-patterns → bool mask, the
        key-group-range filter; ref StateAssignmentOperation.java's
        key-group re-split).  Merging is exact because a window's
        state IS its log: concatenation then fire-time sort/reduce
        equals any other grouping of the same rows."""
        from flink_tpu.state.shared_registry import SharedChunk
        self.watermark = max(s["watermark"] for s in snaps)
        self.num_late_dropped = sum(s["num_late_dropped"] for s in snaps)
        signed = {s["keys_signed"] for s in snaps
                  if s.get("keys_signed") is not None}
        if len(signed) > 1:
            raise ValueError("snapshots disagree on key signedness")
        self._keys_signed = signed.pop() if signed else None
        horizons = [s["fired_horizon"] for s in snaps
                    if s.get("fired_horizon") is not None]
        if horizons:
            self._fired_horizon = max(horizons)
        self.windows = {}
        self._chunk_cache = {}
        for snap in snaps:
            for start, w in snap["windows"].items():
                if isinstance(w, SharedChunk):  # un-resolved (local)
                    w = w.payload
                keys = np.asarray(w["keys"], np.uint64)
                cols = self.mode.upgrade_cols(
                    [np.asarray(c) for c in w["cols"]])
                if keep_fn is not None:
                    m = keep_fn(keys)
                    if not m.all():
                        keys = keys[m]
                        cols = [c[m] for c in cols]
                if not len(keys):
                    continue
                log = self.windows.get(int(start))
                if log is None:
                    log = self.windows[int(start)] = self.mode.new_log()
                log.append(keys, *cols)

    def block_until_ready(self) -> None:
        """Host-tier state is always materialized."""


class StringSumTumblingWindows:
    """Fused wordcount engine for STRING keys: one C++ pass per batch
    interns each word and accumulates its weight into a dense
    id-indexed per-window sum array (native ``ft_intern_sum``:
    phase-split hashing, first-probe and verify loops run with full
    instruction-level parallelism — the structural edge the batch
    interface has over the reference's per-record
    HeapAggregatingState.add, which serializes hash → probe → verify →
    add per record).  keyBy("word") .window(Tumbling) .aggregate(Sum)
    lands here (ref shape: SocketWindowWordCount.java:70-84).  Same
    engine interface as the other tiers; emits original word strings.
    """

    def __init__(self, aggregate, window_size_ms: int, emit=None):
        if not nat.available():
            raise RuntimeError(f"native runtime required: {nat.load_error()}")
        self.agg = aggregate
        self.size = window_size_ms
        self.lateness_horizon = window_size_ms
        self.interner = nat.NativeStringInterner()
        self.directory: List[str] = []          # id -> word
        self._dir_arr = None                    # cached np view
        self.windows: Dict[int, Any] = {}       # start -> NativeWordSums
        self.watermark = -(2 ** 63)
        self.emit = emit
        self.emitted: List[Tuple[Any, Any, int, int]] = []
        self.emit_arrays = False
        self.fired: List[Tuple[np.ndarray, np.ndarray, int, int]] = []
        self.num_late_dropped = 0

    def process_batch(self, keys, timestamps, values=None,
                      key_hashes=None, value_hashes=None) -> None:
        keys = np.asarray(keys)
        if keys.dtype.kind not in "US":
            keys = keys.astype(np.str_)
        ts = np.asarray(timestamps, np.int64)
        starts = ts - np.mod(ts, self.size)
        # single-window batch (the replayed-log shape): skip the
        # unique sort and the masks — they cost more than the fused
        # kernel saves
        if _is_single_window(starts) \
                and int(starts[0]) + self.lateness_horizon - 1 \
                > self.watermark:
            self._ingest(int(starts[0]), keys, values)
            return
        live = starts + self.lateness_horizon - 1 > self.watermark
        if not live.all():
            self.num_late_dropped += int((~live).sum())
            if not live.any():
                return
            keys, starts = keys[live], starts[live]
            if values is not None:
                values = np.asarray(values)[live]
        for start in np.unique(starts).tolist():
            m = starts == start
            self._ingest(int(start),
                         keys if m.all() else keys[m],
                         None if values is None
                         else (values if m.all()
                               else np.asarray(values)[m]))

    def _ingest(self, start: int, w_keys, w_vals) -> None:
        ws = self.windows.get(start)
        if ws is None:
            ws = self.windows[start] = nat.NativeWordSums()
        first_idx = ws.add(self.interner, w_keys, w_vals)
        if len(first_idx):
            self.directory.extend(w_keys[first_idx].tolist())
            self._dir_arr = None

    def flush(self, grow_to=None) -> None:
        """Interface parity."""

    def advance_watermark(self, watermark: int) -> int:
        self.watermark = watermark
        fired = 0
        for start in sorted(self.windows):
            if start + self.size - 1 > watermark:
                continue
            ws = self.windows.pop(start)
            ids, sums = ws.fire()
            if not len(ids):
                continue
            if self._dir_arr is None:
                self._dir_arr = np.asarray(self.directory, dtype=object)
            words = self._dir_arr[ids]
            results = sums.astype(self.agg.value_dtype, copy=False)
            end = start + self.size
            if self.emit_arrays:
                self.fired.append((words, results, start, end))
            elif self.emit is not None:
                for k, r in zip(words, results):
                    self.emit(k, r, start, end)
            else:
                self.emitted.extend(zip(words, results,
                                        [start] * len(ids),
                                        [end] * len(ids)))
            fired += len(ids)
        return fired

    def snapshot(self) -> dict:
        wins = {}
        for start, ws in self.windows.items():
            ids, sums = ws.fire()       # export...
            ws.load(ids, sums)          # ...and restore in place
            wins[int(start)] = {"ids": ids, "sums": sums}
        return {"mode": "string_sum", "size": self.size,
                "watermark": self.watermark,
                "num_late_dropped": self.num_late_dropped,
                "directory": list(self.directory),
                "windows": wins}

    def restore(self, snap: dict) -> None:
        self.watermark = snap["watermark"]
        self.num_late_dropped = snap["num_late_dropped"]
        self.directory = list(snap["directory"])
        self._dir_arr = None
        self.interner = nat.NativeStringInterner(
            max(16, 2 * len(self.directory)))
        if self.directory:
            # dense first-seen ids: re-interning the directory in
            # order reproduces every id
            self.interner.intern(np.asarray(self.directory))
        self.windows = {}
        for start, w in snap["windows"].items():
            ws = nat.NativeWordSums()
            ws.load(np.asarray(w["ids"], np.int64),
                    np.asarray(w["sums"], np.float64))
            self.windows[int(start)] = ws

    def restore_many(self, snaps, keep_fn=None) -> None:
        """Merge snapshots after a parallelism change: ids are dense
        PER-SUBTASK, so each snapshot's ids translate back to words
        through its own directory and re-intern here; sums are
        additive, so re-adding merges exactly.  keep_fn filters WORD
        arrays to this subtask's key groups."""
        if len(snaps) == 1 and keep_fn is None:
            self.restore(snaps[0])
            return
        self.watermark = max(s["watermark"] for s in snaps)
        self.num_late_dropped = sum(s["num_late_dropped"] for s in snaps)
        self.directory = []
        self._dir_arr = None
        self.interner = nat.NativeStringInterner()
        self.windows = {}
        for snap in snaps:
            directory = np.asarray(snap["directory"], dtype=object)
            for start, w in snap["windows"].items():
                ids = np.asarray(w["ids"], np.int64)
                if not len(ids):
                    continue
                words = directory[ids].astype(np.str_)
                sums = np.asarray(w["sums"], np.float64)
                if keep_fn is not None:
                    m = keep_fn(words)
                    if not m.any():
                        continue
                    if not m.all():
                        words, sums = words[m], sums[m]
                self._ingest(int(start), words, sums)

    def block_until_ready(self) -> None:
        """Host-tier state is always materialized."""


class LogStructuredSlidingWindows(LogStructuredTumblingWindows):
    """Sliding windows composed from slide-granularity pane logs.

    Ingest appends each record ONCE to its pane's log; a window's fire
    concatenates the size/slide pane logs — the sort+reduce regroups
    keys across panes, so pane merging costs nothing beyond the fire
    itself.  Semantics match WindowOperator + SlidingEventTimeWindows
    with lateness 0 (same fire/prune rules as
    VectorizedSlidingWindows)."""

    def __init__(self, aggregate: DeviceAggregateFunction,
                 window_size_ms: int, slide_ms: int,
                 compact_threshold: int = 64 << 20,
                 finish_tier: str = "auto", emit=None):
        if window_size_ms % slide_ms != 0:
            raise ValueError("window size must be a multiple of the slide")
        super().__init__(aggregate, slide_ms, compact_threshold,
                         finish_tier, emit)
        self.window_size = window_size_ms
        self.slide = slide_ms
        self.lateness_horizon = window_size_ms
        self._fired_horizon = -(2 ** 63)

    def advance_watermark(self, watermark: int) -> int:
        prev = self._fired_horizon
        self._fired_horizon = watermark
        self.watermark = watermark
        fired = 0
        if not self.windows:
            return 0
        min_pane = min(self.windows)
        max_pane = max(self.windows)
        hi = min(watermark - self.window_size + 1, max_pane)
        start_from = max(min_pane - self.window_size + self.slide,
                         prev - self.window_size + 2)
        first = -(-start_from // self.slide) * self.slide
        if first <= hi:
            for W in range(first, hi + 1, self.slide):
                logs = [self.windows[p]
                        for p in range(W, W + self.window_size, self.slide)
                        if p in self.windows and self.windows[p].count]
                if not logs:
                    continue
                parts = [lg.concat() for lg in logs]
                keys = (parts[0][0] if len(parts) == 1 else
                        np.concatenate([p[0] for p in parts]))
                n_cols = len(parts[0][1])
                cols = tuple(
                    (parts[0][1][j] if len(parts) == 1 else
                     np.concatenate([p[1][j] for p in parts]))
                    for j in range(n_cols))
                fired += self._fire_window(keys, cols, W,
                                           W + self.window_size)
        # prune panes no future window needs
        for P in sorted(self.windows):
            if P + self.window_size - 1 > watermark:
                break
            del self.windows[P]
        if TELEMETRY.enabled:
            TELEMETRY.note_windows_fired(fired)
        return fired


class LogStructuredSessionWindows:
    """Session windows (gap-merged, EventTimeSessionWindows /
    MergingWindowSet.java:156 semantics) + Count-Min totals over an
    event log.

    Ingest appends (key, ts, weight, value-hash); the watermark fire
    sorts by (key, ts), splits runs at gaps (inclusive — abutting
    windows merge, TimeWindow.intersects), closes sessions with
    end-1 <= watermark (each closed session's Count-Min builds in an
    L1-resident scratch) and retains open sessions' events.
    """

    def __init__(self, aggregate: CountMinSketchAggregate, gap_ms: int,
                 emit=None):
        if not isinstance(aggregate, CountMinSketchAggregate):
            raise TypeError("session log engine aggregates Count-Min")
        if not nat.available():
            raise RuntimeError(f"native runtime required: {nat.load_error()}")
        self.agg = aggregate
        self.gap = gap_ms
        self.watermark = -(2 ** 63)
        self.emit = emit
        self.emitted: List[Tuple[Any, Any, int, int]] = []
        self.emit_arrays = False
        self.fired: List[Tuple[np.ndarray, np.ndarray, int, int]] = []
        self.num_late_dropped = 0
        self._keys_signed = None
        self._log_keys: List[np.ndarray] = []
        self._log_ts: List[np.ndarray] = []
        self._log_w: List[np.ndarray] = []
        self._log_vh: List[np.ndarray] = []
        #: open-session rows carried from the last fire, in (key, ts)
        #: order exactly as the kernel returned them — passed back
        #: verbatim (the kernel merges them as a key-major stream;
        #: re-sorting here would corrupt the merge)
        self._ret: Optional[Tuple[np.ndarray, ...]] = None

    def process_batch(self, keys, timestamps, values=None,
                      key_hashes=None, value_hashes=None) -> None:
        ts = np.asarray(timestamps, np.int64)
        keys = _as_u64_keys(self, keys)
        # lateness 0: an event whose own window [ts, ts+gap) has
        # end-1 <= watermark is late.  (A post-merge refinement — the
        # event might still touch a LIVE session — cannot apply here:
        # the kernel keeps no host-visible open-session rows to test
        # against, and closed sessions already fired, so accepting it
        # could change an emitted result.  The vectorized engine DOES
        # apply it: GenericLogSessionWindows._revive_late keeps a
        # merge-chained straggler exactly as the reference's
        # merge-then-isWindowLate order does, WindowOperator.java:
        # 308-343.  This engine's stricter drop remains within the
        # reference's lateness-0 contract for events that merge into
        # nothing open.)
        live = ts + self.gap - 1 > self.watermark
        if not live.all():
            self.num_late_dropped += int((~live).sum())
            if not live.any():
                return
            keys, ts = keys[live], ts[live]
            if values is not None:
                values = np.asarray(values)[live]
            if value_hashes is not None:
                value_hashes = np.asarray(value_hashes)[live]
        if value_hashes is None:
            from flink_tpu.streaming.vectorized import hash_keys_np
            value_hashes = hash_keys_np(values)
        # per-event int truncation, matching the device tier
        # (CountMinSketchAggregate.update casts each weight to int32)
        # so both engines implement one semantics for fractional
        # weights (round-2 advisor finding)
        w = (np.ones(len(keys), np.float32) if values is None
             else np.asarray(values).astype(np.int32).astype(np.float32))
        self._log_keys.append(keys)
        self._log_ts.append(ts)
        self._log_w.append(w)
        self._log_vh.append(np.asarray(value_hashes, np.uint64))

    def flush(self, grow_to=None) -> None:
        """Interface parity."""

    def advance_watermark(self, watermark: int) -> int:
        self.watermark = watermark
        if not self._log_keys and self._ret is None:
            return 0
        cat = (lambda xs, dt: xs[0] if len(xs) == 1
               else (np.concatenate(xs) if xs
                     else np.empty(0, dt)))
        keys = cat(self._log_keys, np.uint64)
        ts = cat(self._log_ts, np.int64)
        w = cat(self._log_w, np.float32)
        vh = cat(self._log_vh, np.uint64)
        # the kernel merges the retained set (key-major, verbatim from
        # the last fire) with the ts-sorted feed itself — no host-side
        # merge/sort pass exists on this path, and retained rows are
        # never re-sorted across fires
        ok, os_, oe, ot, retained = nat.session_log_fire(
            keys, ts, w, vh, self.gap, watermark,
            self.agg.depth, self.agg.width, retained=self._ret)
        self._ret = retained if len(retained[0]) else None
        self._log_keys, self._log_ts = [], []
        self._log_w, self._log_vh = [], []
        totals = ot.astype(np.int64)
        ok = _keys_out(self, ok)
        if self.emit_arrays:
            if len(ok):
                self.fired.append((ok, totals, os_, oe))
        elif self.emit is not None:
            for k, t, s, e in zip(ok, totals, os_, oe):
                self.emit(k, t, int(s), int(e))
        else:
            self.emitted.extend(
                (k, t, int(s), int(e))
                for k, t, s, e in zip(ok, totals, os_, oe))
        return len(ok)

    def snapshot(self) -> dict:
        ret = self._ret or (np.empty(0, np.uint64),
                            np.empty(0, np.int64),
                            np.empty(0, np.float32),
                            np.empty(0, np.uint64))
        cat = (lambda xs, extra: np.concatenate([extra, *xs])
               if xs else extra.copy())
        return {"watermark": self.watermark,
                "num_late_dropped": self.num_late_dropped,
                "keys_signed": self._keys_signed,
                "keys": cat(self._log_keys, ret[0]),
                "ts": cat(self._log_ts, ret[1]),
                "w": cat(self._log_w, ret[2]),
                "vh": cat(self._log_vh, ret[3])}

    def restore(self, snap: dict) -> None:
        self.restore_many([snap])

    def restore_many(self, snaps, keep_fn=None) -> None:
        """Restore/merge retained open-session events, filtered to
        this subtask's key groups on rescale (sessions are per-key, so
        a key-partitioned split of the event log is exact)."""
        self.watermark = max(s["watermark"] for s in snaps)
        self.num_late_dropped = sum(s["num_late_dropped"] for s in snaps)
        signed = {s["keys_signed"] for s in snaps
                  if s.get("keys_signed") is not None}
        if len(signed) > 1:
            raise ValueError("snapshots disagree on key signedness")
        self._keys_signed = signed.pop() if signed else None
        self._log_keys, self._log_ts = [], []
        self._log_w, self._log_vh = [], []
        self._ret = None
        for snap in snaps:
            keys = np.asarray(snap["keys"], np.uint64)
            if not len(keys):
                continue
            m = keep_fn(keys) if keep_fn is not None else None
            if m is not None and not m.any():
                continue
            sel = (lambda a: a) if m is None or m.all() \
                else (lambda a, m=m: np.asarray(a)[m])
            self._log_keys.append(sel(keys))
            self._log_ts.append(sel(snap["ts"]))
            self._log_w.append(sel(snap["w"]))
            self._log_vh.append(sel(snap["vh"]))

    def block_until_ready(self) -> None:
        """Host-tier state is always materialized."""

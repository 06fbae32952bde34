"""Mesh-sharded log-structured window engines: the winning combiner
tier over the device mesh.

The log-structured engines (streaming/log_windows.py) are the
framework's fastest windowed-aggregation tier, but each instance is a
single-host engine.  This module scales them the same way the
reference scales ALL keyed state — a keyBy exchange that routes every
record to the subtask owning its key group (KeyGroupStreamPartitioner
→ Netty, ref flink-runtime/.../io/network/partition/consumer/
SingleInputGate.java; range arithmetic KeyGroupRangeAssignment.java:115)
— except the exchange here is ONE jitted SPMD program over the mesh
axis: records pack into opaque uint32 lanes, a shard_map step buckets
them by key-group-derived target shard and `lax.all_to_all`s the
buckets over ICI, and each host shard appends its received records to
its OWN log engine.  Fires are embarrassingly parallel per-shard C++
log fires (radix sort + segmented reduce); key groups partition keys
disjointly, so per-shard results are exactly the single-host results.

Design notes:
- The exchange payload is *bit-pattern* lanes (u64 key, i64 ts, f64
  value, u64 value-hash, each as two uint32 lanes).  The device step
  does no arithmetic on the payload — only the bucketize/sort by
  target — so no precision is lost to the TPU's 32-bit default, and
  one compiled program serves every aggregate mode.
- Targets are computed on the host with the SAME key-group arithmetic
  the row runtime uses (native ft_key_groups / keygroups numpy twin),
  so a mesh job and a MiniCluster job agree on key placement.
- The static worst case of the exchange is every record targeting one
  shard, so the received buffer is [n_shards, G] for a G-row step —
  the all_to_all padding tax.
- On a multi-host pod each host would consume only its addressable
  shards' outputs; this process consumes all shards (single-host
  runtime).
"""

from __future__ import annotations

import time
from typing import Any, List, Optional

import numpy as np

from flink_tpu.ops.device_agg import DeviceAggregateFunction
from flink_tpu.ops.sketches import CountMinSketchAggregate
from flink_tpu.runtime.device_stats import TELEMETRY

_perf_ns = time.perf_counter_ns


def _split_u64(a: np.ndarray):
    a = np.ascontiguousarray(a, np.uint64)
    return ((a >> np.uint64(32)).astype(np.uint32),
            (a & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _join_u64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


def _make_lane_exchange(mesh, axis: str):
    """The ICI leg of the keyBy exchange: ONE jitted shard_map program
    that is a pure `lax.all_to_all` over pre-bucketed lanes.

    Division of labor: the HOST packs each source shard's rows into
    per-target buckets (a counting partition — cheap, and the logs are
    host-resident anyway), the DEVICE program moves the buckets over
    the mesh axis.  The collective is the only thing that must ride
    ICI, so the compiled step contains nothing else — no sort, no
    scatter — which keeps the exchange at fabric bandwidth instead of
    device-sort speed.

    Buckets are CAPPED at `bucket_cap` rows per (source, target) pair
    instead of the static worst case m = G // S — with balanced key
    groups each bucket holds ~m/S rows, so a cap of a few times the
    mean cuts the exchanged volume from S×m to S×cap per device.  Rows
    that overflow a bucket take the out-of-band path (see _run_step).

    fn(bucks [S, S, cap, K] u32, counts [S, S] i32) →
      (recv [S, S, cap, K], recv_counts [S, S]) where recv[j][s] is
    the bucket source s sent to shard j (count rows valid)."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(bucks_blk, counts_blk):
        # bucks_blk: [1, S, cap, K] (this source's buckets, one per
        # target); all_to_all sends bucket t to device t and stacks
        # the received buckets on the same dim, now indexed by source
        ex = lambda x: jax.lax.all_to_all(  # noqa: E731
            x, axis, split_axis=1, concat_axis=1)
        return ex(bucks_blk), ex(counts_blk)

    return jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis))))


def _make_packed_exchange(mesh, axis: str, cap: int):
    """The counting-partition pack FUSED into the exchange program —
    the `split_batch`-style fan-out run on device instead of the host
    python loop in the legacy pack.

    Each source shard's block arrives RAW (``lanes [1, m, K]`` plus a
    per-row effective target ``tgt [1, m]``, masked rows = S): one
    stable sort groups rows by target, a searchsorted rank caps each
    bucket, a single scatter builds the ``[S, cap, K]`` send buckets
    (slot ``S*cap`` is the garbage bin for overflow/masked rows), and
    `lax.all_to_all` moves them — pack and collective in ONE compiled
    step, and the H2D leg ships ``m*K`` lanes instead of the legacy
    ``S*cap*K`` pre-padded buckets.

    Overflow discipline: the host pre-checks bucket counts with one
    vectorized bincount and only takes this path when NO (source,
    target) bucket overflows ``cap`` — the device program itself would
    silently truncate (rows past ``cap`` land in the garbage bin), so
    the guard keeps the fallback exact rather than best-effort."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    import jax.numpy as jnp

    S = mesh.shape[axis]

    def local(lanes_blk, tgt_blk):
        lanes, tgt = lanes_blk[0], tgt_blk[0]
        m, k = lanes.shape
        order = jnp.argsort(tgt, stable=True)
        st = tgt[order]
        rows = lanes[order]
        first = jnp.searchsorted(st, st, side="left").astype(jnp.int32)
        rank = jnp.arange(m, dtype=jnp.int32) - first
        valid = (st < S) & (rank < cap)
        slot = jnp.where(valid, st * cap + rank, S * cap)
        bucks = jnp.zeros((S * cap + 1, k), jnp.uint32).at[slot].set(rows)
        counts = jnp.minimum(
            jnp.bincount(jnp.clip(st, 0, S), length=S + 1)[:S],
            cap).astype(jnp.int32)
        bucks = bucks[:S * cap].reshape(1, S, cap, k)
        counts = counts.reshape(1, S)
        ex = lambda x: jax.lax.all_to_all(  # noqa: E731
            x, axis, split_axis=1, concat_axis=1)
        return ex(bucks), ex(counts)

    return jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis))))


class _MeshShardedLogEngine:
    """Generic wrapper: N per-shard log engines behind the all_to_all
    lane exchange.  Presents the standard engine interface
    (process_batch / flush / advance_watermark / emitted / fired /
    snapshot / restore) so DeviceWindowOperator and
    ColumnarWindowOperator route to it unchanged."""

    def __init__(self, mesh, axis: str, shard_factory,
                 agg: DeviceAggregateFunction,
                 max_parallelism: int = 128, step_batch: int = 8192,
                 bucket_factor: float = 4.0):
        self.mesh = mesh
        self.axis = axis
        self.agg = agg
        self.n_shards = mesh.shape[axis]
        self.max_parallelism = max_parallelism
        if max_parallelism < self.n_shards:
            raise ValueError("max_parallelism < mesh shards")
        # G must be divisible by the shard count (data-parallel slices)
        self.step_batch = -(-step_batch // self.n_shards) * self.n_shards
        self.shards = [shard_factory() for _ in range(self.n_shards)]
        self.needs_value = bool(agg.needs_value)
        self.needs_value_hash = bool(agg.needs_value_hash)
        self.n_lanes = 4 + (2 if self.needs_value else 0) \
            + (2 if self.needs_value_hash else 0)
        m = self.step_batch // self.n_shards
        # per-(source, target) bucket capacity: balanced traffic puts
        # ~m/S rows in each bucket; cap at bucket_factor× the mean
        # (never above the worst case m) and route the rare overflow
        # out of band (see _run_step)
        self.bucket_cap = min(
            m, max(1, int(bucket_factor * m / self.n_shards)))
        self._exchange = _make_lane_exchange(mesh, axis)
        self._packed_exchange = _make_packed_exchange(
            mesh, axis, self.bucket_cap)
        # reusable send buffer for the host-pack fallback; rows beyond
        # counts[s, t] are stale garbage that travels but is never
        # read on the receive side
        self._buck_buf = np.zeros(
            (self.n_shards, self.n_shards, self.bucket_cap,
             self.n_lanes), np.uint32)
        # row offsets for the one-bincount overflow precheck: source s
        # contributes ids s*(S+1) + target, so one flat bincount yields
        # the full [S, S+1] (source, target) count matrix
        self._src_base = (np.arange(self.n_shards, dtype=np.int64)
                          [:, None] * (self.n_shards + 1))
        # in-flight (recv, rcounts) device arrays from the previous
        # step on the overlapped (non-telemetry) path; delivered at the
        # next step or at any drain point (flush / snapshot / fires)
        self._inflight = None
        #: rows that overflowed a bucket and took the out-of-band path
        self.num_overflow_routed = 0
        self._keys_signed: Optional[bool] = None
        # pending rows not yet exchanged (lists of per-batch arrays)
        self._p_lanes: List[np.ndarray] = []
        self._p_tgt: List[np.ndarray] = []
        self._p_n = 0
        self.emit = None
        self.emitted: List[Any] = []
        self.emit_arrays = False
        self.fired: List[Any] = []

    # ---- ingestion --------------------------------------------------
    def process_batch(self, keys, timestamps, values=None,
                      key_hashes=None, value_hashes=None) -> None:
        keys = np.asarray(keys)
        if not np.issubdtype(keys.dtype, np.integer):
            raise TypeError("mesh log engine requires integer keys")
        signed = bool(np.issubdtype(keys.dtype, np.signedinteger))
        if self._keys_signed is None:
            self._keys_signed = signed
        elif self._keys_signed != signed:
            raise TypeError("key dtype signedness changed mid-stream")
        keys_u64 = (keys.astype(np.int64, copy=False).view(np.uint64)
                    if signed else keys.astype(np.uint64, copy=False))
        ts = np.asarray(timestamps, np.int64)
        if key_hashes is None:
            from flink_tpu.streaming.vectorized import hash_keys_np
            key_hashes = hash_keys_np(keys)
        tgt = self._targets(np.asarray(key_hashes, np.uint64))
        lanes = [*_split_u64(keys_u64), *_split_u64(ts.view(np.uint64))]
        if self.needs_value:
            vals = (np.ones(len(keys), np.float64) if values is None
                    else np.asarray(values, np.float64))
            lanes.extend(_split_u64(vals.view(np.uint64)))
        if self.needs_value_hash:
            if value_hashes is None:
                from flink_tpu.streaming.vectorized import hash_keys_np
                value_hashes = hash_keys_np(np.asarray(values))
            lanes.extend(_split_u64(np.asarray(value_hashes, np.uint64)))
        self._p_lanes.append(np.stack(lanes, axis=-1))
        self._p_tgt.append(tgt.astype(np.int32, copy=False))
        self._p_n += len(keys)
        while self._p_n >= self.step_batch:
            self._drain_one_step()

    def _targets(self, hashes64: np.ndarray) -> np.ndarray:
        try:
            import flink_tpu.native as nat
            return nat.key_groups(hashes64, self.max_parallelism,
                                  self.n_shards)
        except Exception:  # noqa: BLE001 — numpy twin
            from flink_tpu.core.keygroups import (
                assign_operator_indexes_np,
            )
            return assign_operator_indexes_np(
                hashes64, self.max_parallelism, self.n_shards)

    def _concat_pending(self):
        lanes = (self._p_lanes[0] if len(self._p_lanes) == 1
                 else np.concatenate(self._p_lanes))
        tgt = (self._p_tgt[0] if len(self._p_tgt) == 1
               else np.concatenate(self._p_tgt))
        return lanes, tgt

    def _drain_one_step(self) -> None:
        lanes, tgt = self._concat_pending()
        G = self.step_batch
        self._run_step(lanes[:G], tgt[:G],
                       np.ones(G, bool))
        rest_lanes, rest_tgt = lanes[G:], tgt[G:]
        self._p_lanes = [rest_lanes] if len(rest_lanes) else []
        self._p_tgt = [rest_tgt] if len(rest_tgt) else []
        self._p_n = len(rest_lanes)

    def flush(self, grow_to: Optional[int] = None) -> None:
        """Exchange every pending row (the final partial step pads to
        the compiled G with masked rows) and land any overlapped step
        still in flight."""
        if self._p_n:
            lanes, tgt = self._concat_pending()
            self._p_lanes, self._p_tgt, self._p_n = [], [], 0
            G = self.step_batch
            for off in range(0, len(lanes), G):
                chunk_l, chunk_t = lanes[off:off + G], tgt[off:off + G]
                n = len(chunk_l)
                if n < G:
                    pad_l = np.zeros((G - n, self.n_lanes), np.uint32)
                    chunk_l = np.concatenate([chunk_l, pad_l])
                    chunk_t = np.concatenate(
                        [chunk_t, np.zeros(G - n, np.int32)])
                mask = np.zeros(G, bool)
                mask[:n] = True
                self._run_step(chunk_l, chunk_t, mask)
        self._drain_inflight()

    def _run_step(self, lanes: np.ndarray, tgt: np.ndarray,
                  mask: np.ndarray) -> None:
        """One G-row exchange step.  Each source slice models one
        ingest host's rows (data-parallel split of the batch).

        Fast path (no bucket overflow, the common case by bucket_cap
        construction): ship RAW lanes + targets and let the fused
        device program pack AND exchange in one compiled step — the
        host's only work is a single bincount precheck, and the H2D
        payload is the m×K rows themselves rather than the padded
        S×cap×K bucket buffer.  Overflowing steps fall back to the
        host counting-partition pack (_run_step_hostpack), which
        routes the beyond-cap tail out of band.

        Without telemetry the fast path is double-buffered: the step's
        device work is dispatched asynchronously and the PREVIOUS
        step's results are converted/delivered while the fabric moves
        this one, so collective time overlaps host delivery instead of
        serializing with it.  Rows still reach shard engines in step order
        — every consumer of shard state drains the in-flight step
        first (flush / advance_watermark / snapshot)."""
        S, cap = self.n_shards, self.bucket_cap
        m = len(lanes) // S
        telem = TELEMETRY.enabled
        t0 = _perf_ns() if telem else 0
        tgt_eff = np.where(mask, tgt, S).astype(np.int32, copy=False)
        te = tgt_eff.reshape(S, m)
        counts_st = np.bincount(
            (self._src_base + te).ravel(),
            minlength=S * (S + 1)).reshape(S, S + 1)[:, :S]
        if (counts_st > cap).any():
            self._drain_inflight()
            self._run_step_hostpack(lanes, te, t0)
            return
        lanes3 = np.ascontiguousarray(
            lanes.reshape(S, m, self.n_lanes))
        if telem:
            # phase-split round: an explicit sharded device_put
            # separates the H2D leg from the collective so the ledger
            # attributes fabric time and staging time independently.
            # pack_ms here is the host-side precheck/staging only —
            # the pack itself rides inside the collective phase.
            self._drain_inflight()
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            t1 = _perf_ns()
            sharding = NamedSharding(self.mesh, PartitionSpec(self.axis))
            d_lanes = jax.device_put(lanes3, sharding)
            d_tgt = jax.device_put(te, sharding)
            jax.block_until_ready((d_lanes, d_tgt))
            t2 = _perf_ns()
            recv, rcounts = self._packed_exchange(d_lanes, d_tgt)
            jax.block_until_ready((recv, rcounts))
            t3 = _perf_ns()
            recv = np.asarray(recv)
            rcounts = np.asarray(rcounts)
            t4 = _perf_ns()
            sent = lanes3.nbytes + te.nbytes
            TELEMETRY.record_transfer("h2d", sent, t1, t2,
                                      tag="mesh.exchange")
            TELEMETRY.record_transfer(
                "d2h", recv.nbytes + rcounts.nbytes, t3, t4,
                tag="mesh.exchange")
            TELEMETRY.record_exchange_round(
                "mesh.log", (t1 - t0) / 1e6, (t2 - t1) / 1e6,
                (t3 - t2) / 1e6, (t4 - t3) / 1e6, sent)
            self._deliver_recv(recv, rcounts)
        else:
            # launch this step before touching the previous one: the
            # np.asarray below blocks on step k-1 while step k is
            # already moving on the fabric
            prev = self._inflight
            self._inflight = self._packed_exchange(lanes3, te)
            if prev is not None:
                self._deliver_recv(np.asarray(prev[0]),
                                   np.asarray(prev[1]))

    def _run_step_hostpack(self, lanes: np.ndarray, te: np.ndarray,
                           t0: int) -> None:
        """Legacy host counting-partition pack for steps where some
        (source, target) bucket overflows the cap: per-slice stable
        sort, explicit bucket fill, pure all_to_all, with the
        beyond-cap tail routed out of band."""
        S, cap = self.n_shards, self.bucket_cap
        m = te.shape[1]
        telem = TELEMETRY.enabled
        bucks = self._buck_buf
        counts = np.zeros((S, S), np.int32)
        overflow = []           # (target, rows) beyond the bucket cap
        for s in range(S):
            sl = slice(s * m, (s + 1) * m)
            tgt_eff = te[s]
            # one stable sort per slice groups rows by target (O(m log
            # m) independent of S; masked padding rows sort last as
            # virtual target S and never ship)
            order = np.argsort(tgt_eff, kind="stable")
            sl_sorted = lanes[sl][order]
            run_counts = np.bincount(tgt_eff, minlength=S + 1)
            off = 0
            for t in range(S):
                n_t = int(run_counts[t])
                rows = sl_sorted[off:off + n_t]
                off += n_t
                c = min(n_t, cap)
                bucks[s, t, :c] = rows[:c]
                counts[s, t] = c
                if n_t > c:
                    overflow.append((t, rows[c:]))
        if telem:
            # phase-split round: an explicit sharded device_put
            # separates the H2D leg from the collective so the ledger
            # attributes fabric time and staging time independently
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            t1 = _perf_ns()
            sharding = NamedSharding(self.mesh, PartitionSpec(self.axis))
            d_bucks = jax.device_put(bucks, sharding)
            d_counts = jax.device_put(counts, sharding)
            jax.block_until_ready((d_bucks, d_counts))
            t2 = _perf_ns()
            recv, rcounts = self._exchange(d_bucks, d_counts)
            jax.block_until_ready((recv, rcounts))
            t3 = _perf_ns()
            recv = np.asarray(recv)
            rcounts = np.asarray(rcounts)
            t4 = _perf_ns()
            sent = bucks.nbytes + counts.nbytes
            TELEMETRY.record_transfer("h2d", sent, t1, t2,
                                      tag="mesh.exchange")
            TELEMETRY.record_transfer(
                "d2h", recv.nbytes + rcounts.nbytes, t3, t4,
                tag="mesh.exchange")
            TELEMETRY.record_exchange_round(
                "mesh.log", (t1 - t0) / 1e6, (t2 - t1) / 1e6,
                (t3 - t2) / 1e6, (t4 - t3) / 1e6, sent)
        else:
            recv, rcounts = self._exchange(bucks, counts)
            recv = np.asarray(recv)
            rcounts = np.asarray(rcounts)
        self._deliver_recv(recv, rcounts)
        # bucket-cap overflow: live rows the exchange could not fit.
        # This single-host runtime owns every shard engine, so they
        # route host-side; a multi-host runtime would re-send them on
        # the next step (a bounded tail by construction).
        for t, rows in overflow:
            self.num_overflow_routed += len(rows)
            self._deliver(int(t), rows)

    def _deliver_recv(self, recv: np.ndarray,
                      rcounts: np.ndarray) -> None:
        S = self.n_shards
        for j in range(S):
            parts = [recv[j, s, :rcounts[j, s]]
                     for s in range(S) if rcounts[j, s]]
            if parts:
                self._deliver(j, parts[0] if len(parts) == 1
                              else np.concatenate(parts))

    def _drain_inflight(self) -> None:
        """Deliver the overlapped previous step, if any.  Called at
        every point that observes shard-engine state (flush → fires,
        snapshot) and before any out-of-order delivery path."""
        inflight = self._inflight
        if inflight is None:
            return
        self._inflight = None
        self._deliver_recv(np.asarray(inflight[0]),
                           np.asarray(inflight[1]))

    def _deliver(self, shard: int, rows: np.ndarray) -> None:
        keys_u64 = _join_u64(rows[:, 0], rows[:, 1])
        keys = (keys_u64.view(np.int64) if self._keys_signed
                else keys_u64)
        ts = _join_u64(rows[:, 2], rows[:, 3]).view(np.int64)
        lane = 4
        values = None
        if self.needs_value:
            values = _join_u64(rows[:, lane],
                               rows[:, lane + 1]).view(np.float64)
            lane += 2
        vh = None
        if self.needs_value_hash:
            vh = _join_u64(rows[:, lane], rows[:, lane + 1])
        self.shards[shard].process_batch(keys, ts, values,
                                         value_hashes=vh)

    # ---- firing -----------------------------------------------------
    def advance_watermark(self, watermark: int) -> int:
        self.flush()
        fired = 0
        for sh in self.shards:
            sh.emit_arrays = self.emit_arrays
            sh.emit = None
            fired += sh.advance_watermark(watermark)
            if self.emit_arrays:
                self.fired.extend(sh.fired)
                del sh.fired[:]
            else:
                if self.emit is not None:
                    for k, r, s, e in sh.emitted:
                        self.emit(k, r, s, e)
                else:
                    self.emitted.extend(sh.emitted)
                del sh.emitted[:]
        return fired

    @property
    def num_late_dropped(self) -> int:
        # all late drops happen inside the shard engines (the wrapper
        # never inspects timestamps)
        return sum(sh.num_late_dropped for sh in self.shards)

    @property
    def watermark(self) -> int:
        return max(sh.watermark for sh in self.shards)

    # ---- checkpoint -------------------------------------------------
    def snapshot(self) -> dict:
        # an overlapped step's rows are neither pending nor in any
        # shard yet — land them first or the snapshot would lose them
        self._drain_inflight()
        lanes, tgt = (self._concat_pending() if self._p_n
                      else (np.zeros((0, self.n_lanes), np.uint32),
                            np.zeros(0, np.int32)))
        return {"mesh_log": True,
                "n_shards": self.n_shards,
                "max_parallelism": self.max_parallelism,
                "keys_signed": self._keys_signed,
                "pending_lanes": lanes.copy(),
                "pending_tgt": tgt.copy(),
                "shards": [sh.snapshot() for sh in self.shards]}

    def restore(self, snap: dict) -> None:
        if snap["n_shards"] != self.n_shards:
            raise ValueError(
                f"mesh log checkpoint was taken at {snap['n_shards']} "
                f"shards; this mesh has {self.n_shards} (re-shard the "
                "mesh or restore on a matching one)")
        # key→shard routing is hash % max_parallelism-derived: a
        # mismatch would silently split each key's state across shards
        snap_mp = snap.get("max_parallelism", 128)  # pre-r5 snapshots
        # were necessarily taken at the old hard-wired default of 128
        if snap_mp != self.max_parallelism:
            raise ValueError(
                f"mesh log checkpoint was taken at max_parallelism="
                f"{snap_mp}; this operator is configured "
                f"{self.max_parallelism} — keys would route to "
                "different shards than the ones holding their state")
        # in-flight rows belong to the pre-restore stream: drop them
        self._inflight = None
        self._keys_signed = snap["keys_signed"]
        self._p_lanes = ([snap["pending_lanes"]]
                         if len(snap["pending_lanes"]) else [])
        self._p_tgt = ([snap["pending_tgt"]]
                       if len(snap["pending_tgt"]) else [])
        self._p_n = len(snap["pending_lanes"])
        for sh, s in zip(self.shards, snap["shards"]):
            sh.restore(s)

    def block_until_ready(self) -> None:
        """Land any overlapped exchange step; shard state itself is
        host-resident and always materialized."""
        self._drain_inflight()


class MeshLogTumblingWindows(_MeshShardedLogEngine):
    """keyBy().window(Tumbling).aggregate over the mesh: all_to_all
    keyBy exchange + per-shard log-structured fires."""

    def __init__(self, aggregate: DeviceAggregateFunction,
                 window_size_ms: int, mesh, axis: str = "kg",
                 max_parallelism: int = 128, step_batch: int = 8192,
                 finish_tier: str = "auto"):
        from flink_tpu.streaming.log_windows import (
            LogStructuredTumblingWindows,
        )
        super().__init__(
            mesh, axis,
            lambda: LogStructuredTumblingWindows(
                aggregate, window_size_ms, finish_tier=finish_tier),
            aggregate, max_parallelism, step_batch)
        self.size = window_size_ms


class MeshLogSlidingWindows(_MeshShardedLogEngine):
    """Sliding windows over the mesh: per-shard pane logs (one append
    per record regardless of overlap), exchange as above."""

    def __init__(self, aggregate: DeviceAggregateFunction,
                 window_size_ms: int, slide_ms: int, mesh,
                 axis: str = "kg", max_parallelism: int = 128,
                 step_batch: int = 8192, finish_tier: str = "auto"):
        from flink_tpu.streaming.log_windows import (
            LogStructuredSlidingWindows,
        )
        super().__init__(
            mesh, axis,
            lambda: LogStructuredSlidingWindows(
                aggregate, window_size_ms, slide_ms,
                finish_tier=finish_tier),
            aggregate, max_parallelism, step_batch)
        self.size = window_size_ms
        self.slide = slide_ms


class MeshLogSessionWindows(_MeshShardedLogEngine):
    """Session windows over the mesh.  Sessions are per-key and key
    groups partition keys disjointly, so per-shard gap merging is
    exactly the single-host semantics (MergingWindowSet.java:156)."""

    def __init__(self, aggregate: CountMinSketchAggregate, gap_ms: int,
                 mesh, axis: str = "kg", max_parallelism: int = 128,
                 step_batch: int = 8192):
        from flink_tpu.streaming.log_windows import (
            LogStructuredSessionWindows,
        )
        super().__init__(
            mesh, axis,
            lambda: LogStructuredSessionWindows(aggregate, gap_ms),
            aggregate, max_parallelism, step_batch)
        self.gap = gap_ms


def mesh_log_engine_for_assigner(assigner, agg: DeviceAggregateFunction,
                                 mesh, axis: str = "kg",
                                 max_parallelism: int = 128):
    """Mesh-sharded log tier for this assigner+aggregate, or None when
    the cell decomposition / assigner shape doesn't fit (same scope as the
    single-device log tier: integer keys, HLL/Sum/Quantile cells,
    Count-Min sessions)."""
    from flink_tpu.streaming.window_engines import aligned_shape
    shape = aligned_shape(assigner)
    try:
        return shape and shape.build(
            MeshLogTumblingWindows, MeshLogSlidingWindows,
            MeshLogSessionWindows, agg, mesh, axis=axis,
            max_parallelism=max_parallelism)
    except (TypeError, ValueError, RuntimeError):
        return None  # unsupported cell decomposition / params / no native lib

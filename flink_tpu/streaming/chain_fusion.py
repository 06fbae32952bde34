"""Chain fusion: one jitted columnar program per typeflow-proven run.

A conclusively-proven operator chain still pays one Python dispatch —
and one host-materialized intermediate column set — per operator per
batch.  This module lowers the maximal fusable RUN of a chain (map
arithmetic, filter mask + compaction, the splitmix64 keyBy hash, and
tumbling/sliding first-pane assignment) into ONE ``traced_jit``
program: columns cross the host↔device boundary exactly twice per
batch (in once, out once) and every intermediate lives on device.

Pipeline position
-----------------
``try_fuse_subtask`` runs at the END of ``SubtaskInstance.open()`` —
after executor wiring, so the router's routes (and therefore the
downstream channel count) are compile-time constants.  It anchors a
:class:`FusedChainProgram` on the first operator of the run; the task
layer's batch dispatch (``process_batch_element`` for chain heads,
``_ChainedOutput.collect_batch`` mid-chain) checks that anchor and
hands the whole batch to the program instead of the per-operator
kernels.

What fuses
----------
* ``StreamMap`` / ``StreamFilter`` whose UDF the AOT liftability
  analyzer proved LIFTABLE (or the type-flow prover stamped
  ``_static_kernel``) and whose per-operator state machine hasn't
  locked boxed.
* When the run reaches the chain tail and the only out-route is a
  ``KeyGroupStreamPartitioner`` over a positional int key field, the
  keyBy exchange itself: splitmix64 + the 32-bit key-group avalanche
  run on device, and compaction + channel routing fold into a single
  stable sort.  The host then emits zero-copy per-channel slices.
* A tumbling/sliding ``WindowOperator`` directly after the kernel run
  in the same chain: the first-pane-start column is computed on
  device and injected via ``process_batch_fused``.

Safety contract
---------------
The per-operator ``_ColumnKernelMixin`` boxed fallback stays fully
intact.  The first batch of every new dtype signature is verified against
a full numpy twin (values, timestamps, validity masks, routing hashes,
channel bounds, pane starts — exact equality, NaN-aware) BEFORE
anything is emitted.  Demotion is for what the DATA or the UDF can
cause — a column dtype the device cannot represent, a kernel output
that is not a column, a UDF jax cannot trace, a twin mismatch: the
WHOLE chain goes back to per-operator dispatch with a recorded reason,
and the failing batch is replayed through the untouched per-operator
path, so demotion can never produce wrong output.  An exception from
this module's own code is a bug and propagates: it fails the job.

Mesh sharding
-------------
With >1 device and a large enough bucket the same program runs under
``shard_map`` on a named mesh (batch axis): each shard compacts its
row block locally and the host reassembles shard-order prefixes —
bit-identical to the single-device program.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64, shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from flink_tpu.runtime.device_stats import TELEMETRY, tree_nbytes
from flink_tpu.runtime.tracing import traced_jit

log = logging.getLogger(__name__)

#: master switch (env FLINK_TPU_CHAIN_FUSION=0 disables)
FUSION_ENABLED = os.environ.get(
    "FLINK_TPU_CHAIN_FUSION", "1").lower() not in ("0", "false", "off")

#: batches below this row count take the per-operator path — a jit
#: dispatch costs more than a few small numpy passes (tests patch this)
MIN_FUSED_ROWS = 512

#: per-shard row floor before the mesh variant beats one device
MESH_MIN_ROWS_PER_SHARD = 2048


class _FusionStats:
    """Process-wide counters for the fused-chain plane."""

    def __init__(self) -> None:
        self.programs = 0        # compiled FusedChainPrograms
        self.fused_batches = 0
        self.fused_rows = 0
        self.probes = 0          # numpy-twin verifications run
        self.demotions = 0
        self.small_batches = 0   # wanted but under MIN_FUSED_ROWS
        self.last_demotion: Optional[Tuple[str, str]] = None

    def reset(self) -> None:
        self.__init__()


FUSION_STATS = _FusionStats()


class _Demoted(Exception):
    """Internal: raised inside _execute after demote() already ran."""


class _NotFusable(TypeError):
    """Raised while TRACING the fused body when a UDF kernel's output
    cannot ride the device program (not a column, not a bool mask, a
    key column that is not int64) — a property of the UDF and the
    batch dtypes, so it demotes."""


# ---------------------------------------------------------------------
# AOT eligibility
# ---------------------------------------------------------------------

def _kernel_stage(op) -> Optional[Tuple[str, Callable, str]]:
    """(kind, fn, "") when ``op`` is a fusable map/filter stage, else
    (None, None, reason)."""
    from flink_tpu.streaming.operators import (
        StreamFilter,
        StreamMap,
        _kernel_fn,
        _udf_liftable,
    )
    if isinstance(op, StreamMap):
        kind = "map"
    elif isinstance(op, StreamFilter):
        kind = "filter"
    else:
        return None
    if op._batch_kernel is False:
        return None
    if not op._static_kernel:
        ok, _reason = _udf_liftable(op.user_function, op._KERNEL_ATTR)
        if not ok:
            return None
    return kind, _kernel_fn(op.user_function, op._KERNEL_ATTR), ""


def _window_stage_reason(op) -> Optional[str]:
    """None when ``op`` can take a fused pane column, else the reason
    it can't."""
    from flink_tpu.streaming.window_operator import (
        EvictingWindowOperator,
        WindowOperator,
    )
    if not isinstance(op, WindowOperator):
        return "not a window operator"
    if isinstance(op, EvictingWindowOperator):
        return "evicting window operator is per-row"
    if op.assigner.is_merging():
        return "session windows have no pane column to fuse"
    reason = op._batch_eligibility()
    if reason is not None:
        return reason
    return None


def _blocker_reason(op) -> str:
    """Why ``op`` blocks fusion (for reports)."""
    from flink_tpu.streaming.operators import (
        StreamFilter,
        StreamMap,
        _udf_liftable,
    )
    if isinstance(op, (StreamMap, StreamFilter)):
        if op._batch_kernel is False:
            return (op.columnar_fallback_reason
                    or "operator locked onto the boxed path")
        if not op._static_kernel:
            ok, reason = _udf_liftable(op.user_function, op._KERNEL_ATTR)
            if not ok:
                return reason
        return "fusable"  # shouldn't be reported as a blocker
    wreason = _window_stage_reason(op)
    if wreason != "not a window operator":
        return wreason or "fusable"
    return f"{type(op).__name__} has no columnar kernel"


def select_run(operators) -> Tuple[int, int, Optional[int]]:
    """The maximal fusable run of an operator chain.

    Returns ``(start, n_kernel, window_index)``: the run covers
    ``operators[start : start + n_kernel]`` kernel stages plus, when
    ``window_index`` is not None, the window operator directly after.
    ``n_kernel == 0`` means no fusable run exists.
    """
    n = len(operators)
    start = 0
    while start < n and _kernel_stage(operators[start]) is None:
        start += 1
    k = 0
    while start + k < n and _kernel_stage(operators[start + k]) is not None:
        k += 1
    if k == 0:
        return 0, 0, None
    widx = None
    nxt = start + k
    if nxt < n and _window_stage_reason(operators[nxt]) is None:
        widx = nxt
    return start, k, widx


def fusion_report(operators) -> dict:
    """AOT fusion summary for one chain — feeds ``chain_report``,
    FT184 and ``flink_tpu lint --types``."""
    start, k, widx = select_run(operators)
    names = [getattr(op, "operator_id", "") or type(op).__name__
             for op in operators]
    if k == 0:
        blocker = None
        reason = None
        for i, op in enumerate(operators):
            stage = _kernel_stage(op)
            if stage is None and _window_stage_reason(op) is not None:
                blocker = names[i]
                reason = _blocker_reason(op)
                break
        return {"fusable": False, "fused_ops": [],
                "first_blocker": blocker, "blocker_reason": reason}
    end = (widx + 1) if widx is not None else (start + k)
    fused = names[start:end]
    blocker = None
    reason = None
    if end < len(operators):
        blocker = names[end]
        reason = _blocker_reason(operators[end])
    elif start > 0:
        # the run exists but a non-fusable prefix (usually the source)
        # keeps it from covering the whole chain — name the LAST
        # prefix op so the report explains the gap
        blocker = names[start - 1]
        reason = _blocker_reason(operators[start - 1])
    return {"fusable": True, "fused_ops": fused,
            "first_blocker": blocker, "blocker_reason": reason}


# ---------------------------------------------------------------------
# compile
# ---------------------------------------------------------------------

def try_fuse_subtask(subtask) -> None:
    """Compile and anchor a fused program for one SubtaskInstance —
    called at the end of ``SubtaskInstance.open()`` (routes wired,
    operators opened)."""
    if not FUSION_ENABLED:
        return
    from flink_tpu.streaming import columnar
    if not columnar.PIPELINE_ENABLED:
        return
    ops = getattr(subtask, "operators", None)
    if not ops:
        return
    # idempotent: open() can run again after a restore
    for op in ops:
        if "_fused_chain" in op.__dict__ and op._fused_chain is not None:
            return
    program = compile_chain(ops, router=getattr(subtask, "router", None))
    if program is not None:
        program.anchor._fused_chain = program
        FUSION_STATS.programs += 1


def compile_chain(operators, router=None) -> Optional["FusedChainProgram"]:
    """Lower the maximal fusable run of ``operators`` into a
    :class:`FusedChainProgram`, or None when nothing fuses (no proven
    run, run of a single stage with no routing/window leg to amortize
    it)."""
    start, k, widx = select_run(operators)
    if k == 0:
        return None
    stages = []
    for op in operators[start:start + k]:
        kind, fn, _ = _kernel_stage(op)
        stages.append((kind, fn))
    window_op = operators[widx] if widx is not None else None
    kernel_ops = list(operators[start:start + k])
    tail_op = operators[widx] if widx is not None else operators[start + k - 1]

    # routing leg: only when the run ends at the chain tail and the
    # single non-side route is a key-group exchange over a positional
    # int field of the POST-map row tuple
    route_field = None
    route_channels = None
    route_part = None
    if window_op is None and start + k == len(operators) and router is not None:
        from flink_tpu.core.functions import _FieldKeySelector
        from flink_tpu.streaming.partitioners import KeyGroupStreamPartitioner
        data_routes = [r for r in getattr(router, "routes", [])
                       if r[2] is None]
        if len(data_routes) == 1:
            part, channels, _tag = data_routes[0]
            sel = getattr(part, "key_selector", None)
            if (isinstance(part, KeyGroupStreamPartitioner)
                    and not getattr(part, "broadcast_all", False)
                    and len(channels) > 1
                    and isinstance(sel, _FieldKeySelector)
                    and type(sel._field) is int):
                route_field = sel._field
                route_channels = channels
                route_part = part
    if k == 1 and window_op is None and route_field is None:
        # one kernel stage and nothing else fused: the per-operator
        # kernel is already a single vectorized pass — no win
        return None
    return FusedChainProgram(
        operators=operators, start=start, kernel_ops=kernel_ops,
        stages=stages, window_op=window_op, router=router,
        route_field=route_field, route_channels=route_channels,
        route_part=route_part, tail_op=tail_op)


# ---------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------

class FusedChainProgram:
    """One compiled chain run: dtype-signature-probed jitted columnar
    program + host emission glue.  Anchored on the run's first
    operator; the task layer calls :meth:`wants` / :meth:`run`."""

    def __init__(self, operators, start, kernel_ops, stages, window_op,
                 router, route_field, route_channels, route_part, tail_op):
        self.operators = operators
        self.start = start
        self.anchor = operators[start]
        self.kernel_ops = kernel_ops
        self.stages = stages
        self.window_op = window_op
        self.router = router
        self.route_field = route_field
        self.route_channels = route_channels
        self.route_part = route_part
        self.renames = any(kind == "map" for kind, _ in stages)
        self.members = list(kernel_ops) + ([window_op] if window_op else [])
        head_id = getattr(self.anchor, "operator_id", "") \
            or type(self.anchor).__name__
        tail_id = getattr(tail_op, "operator_id", "") \
            or type(tail_op).__name__
        self.label = f"chain.{head_id}→{tail_id}"
        self.active = True
        self.demoted_reason: Optional[str] = None
        self._verified_sigs: set = set()
        self._fns: dict = {}
        #: (mode, scalar, use_mesh) → did the traced program produce
        #: tuple rows?  Written at trace time (the python body only
        #: runs then), read by the emission glue for output naming.
        self._tuple_out: dict = {}
        # mesh: largest power-of-two device prefix, batch ("rows") axis
        self.mesh = None
        self.mesh_shards = 1
        devs = jax.devices()
        if len(devs) >= 2:
            s = 1 << (len(devs).bit_length() - 1)
            self.mesh = Mesh(np.array(devs[:s]), ("rows",))
            self.mesh_shards = s
        for op in self.members:
            op._fused_member = self
        if self.window_op is not None:
            wassigner = self.window_op.assigner
            self._w_size = int(wassigner.size)
            self._w_slide = int(getattr(wassigner, "slide", wassigner.size))
            self._w_offset = int(wassigner.offset)
        if self.route_part is not None:
            self._r_maxpar = int(self.route_part.max_parallelism)
            self._r_nch = len(self.route_channels)

    # ---- dispatch predicate -----------------------------------------
    def wants(self, batch) -> bool:
        if not self.active:
            return False
        n = len(batch)
        if n < MIN_FUSED_ROWS:
            FUSION_STATS.small_batches += 1
            return False
        if batch.routing is not None:
            return False  # upstream already routed: shape unknown here
        if self.window_op is not None:
            # the fused pane column needs every row timestamped; the
            # per-op path handles the (rare) partially-stamped batch
            if batch.ts is None:
                return False
            m = batch.ts_mask
            if m is not None and not m.all():
                return False
        return True

    # ---- demotion ----------------------------------------------------
    def demote(self, reason: str) -> None:
        if not self.active:
            return
        self.active = False
        self.demoted_reason = reason
        FUSION_STATS.demotions += 1
        FUSION_STATS.last_demotion = (self.label, reason)
        for op in self.members:
            if op.columnar_decided_by == "fused":
                op.columnar_decided_by = None
            op._fused_member = None
        log.warning("fused chain %s demoted to per-operator dispatch: %s",
                    self.label, reason)

    # ---- run ---------------------------------------------------------
    def run(self, batch) -> None:
        """Execute the fused program on ``batch``.  When the batch or
        the UDFs demote the chain, the batch replays through the
        untouched per-operator path (nothing was emitted yet —
        compute-all-then-emit); any other exception propagates."""
        try:
            emit = self._execute(batch)
        except _Demoted:
            self.anchor.process_batch(batch)
            return
        emit()

    # ---- internals ---------------------------------------------------
    def _execute(self, batch):
        n = len(batch)
        col_arrays = tuple(batch.cols.values())
        for name, a in batch.cols.items():
            if a.dtype.kind not in "biuf":
                self.demote(f"column {name!r} dtype {a.dtype} is not "
                            f"device-representable")
                raise _Demoted
        scalar = batch.is_scalar
        ts, tsm = batch.ts, batch.ts_mask
        use_window = self.window_op is not None and ts is not None
        use_route = self.route_field is not None

        bucket = max(MIN_FUSED_ROWS, 1 << (n - 1).bit_length())
        use_mesh = (self.mesh is not None
                    and bucket >= self.mesh_shards * MESH_MIN_ROWS_PER_SHARD)
        # routing folds into the program's sort on one device AND on
        # the mesh: per-shard partitions merge channel-major on the
        # host, which IS the global stable order (shards are position
        # ranges)
        mode = ("window" if use_window
                else ("route" if use_route else "plain"))

        valid = np.zeros(bucket, bool)
        valid[:n] = True

        def pad(a, fill=0):
            if a is None or bucket == n:
                return a
            out = np.empty(bucket, a.dtype)
            out[:n] = a
            out[n:] = fill
            return out

        p_cols = tuple(pad(a) for a in col_arrays)
        p_ts = pad(ts)
        p_tsm = pad(tsm, fill=False)

        fn = self._device_fn(mode, scalar, use_mesh)
        tel = TELEMETRY
        with enable_x64():
            args = (p_cols, p_ts, p_tsm, valid)
            if tel.enabled:
                # explicit boundary copies so the ledger shows the fused
                # region's ONLY host↔device traffic: one h2d, one d2h
                sharding = (NamedSharding(self.mesh, P("rows"))
                            if use_mesh else None)
                t0 = time.perf_counter_ns()
                args = jax.device_put(args, sharding)
                jax.block_until_ready(args)
                tel.record_transfer("h2d", tree_nbytes(args), t0,
                                    time.perf_counter_ns(),
                                    "chain.boundary")
            try:
                outs = fn(*args)
            except (_NotFusable, jax.errors.JAXTypeError) as e:
                # the UDF's kernel does not trace into a column
                # program (JAXTypeError: it concretizes a tracer or
                # hands one to numpy) — the per-operator kernels cope
                self.demote(f"UDF kernel is not device-traceable: {e!r}")
                raise _Demoted from e
            if tel.enabled:
                jax.block_until_ready(outs)
                t2 = time.perf_counter_ns()
                host = jax.tree_util.tree_map(np.asarray, outs)
                tel.record_transfer("d2h", tree_nbytes(outs), t2,
                                    time.perf_counter_ns(),
                                    "chain.boundary")
            else:
                host = jax.tree_util.tree_map(np.asarray, outs)
        out_cols, out_ts, out_tsm, stage_rows, count_out, bounds, hashes, \
            pane = host
        if use_mesh:
            # per-shard kept prefixes → global arrays, shard order
            counts = np.asarray(count_out).ravel()
            count = int(counts.sum())
            m = bucket // self.mesh_shards
            if bounds is not None:
                # route: per-shard partitions [S, nch+1] gathered
                # channel-major, shard-minor — shards are position
                # ranges, so this IS the global stable route order
                b = np.asarray(bounds, np.int64)
                sel = np.concatenate(
                    [np.arange(i * m + b[i, c], i * m + b[i, c + 1])
                     for c in range(self._r_nch)
                     for i in range(self.mesh_shards)]) if count else \
                    np.zeros(0, np.int64)
                per_ch = (b[:, 1:] - b[:, :-1]).sum(axis=0)
                bounds = np.concatenate(([0], np.cumsum(per_ch)))
            else:
                sel = np.concatenate(
                    [np.arange(i * m, i * m + int(c)) for i, c
                     in enumerate(counts.tolist())]) if count else \
                    np.zeros(0, np.int64)
            gather = lambda a: a[sel] if a is not None else None  # noqa: E731
            out_cols = tuple(gather(a) for a in out_cols)
            out_ts, out_tsm = gather(out_ts), gather(out_tsm)
            hashes, pane = gather(hashes), gather(pane)
            stage_rows = np.asarray(stage_rows).reshape(
                self.mesh_shards, -1).sum(axis=0)
        else:
            count = int(count_out)
            sl = lambda a: a[:count] if a is not None else None  # noqa: E731
            out_cols = tuple(sl(a) for a in out_cols)
            out_ts, out_tsm = sl(out_ts), sl(out_tsm)
            hashes, pane = sl(hashes), sl(pane)
            stage_rows = np.asarray(stage_rows)
        if bounds is not None:
            bounds = np.asarray(bounds, np.int64)
        tuple_out = self._tuple_out[(mode, scalar, use_mesh)]

        sig = (mode, scalar, use_mesh,
               tuple(a.dtype.str for a in col_arrays),
               ts is None, tsm is None)
        if sig not in self._verified_sigs:
            self._verify(batch, n, mode, out_cols, out_ts, out_tsm,
                         count, bounds, hashes, pane)
            self._verified_sigs.add(sig)

        return self._make_emit(batch, n, mode, tuple_out, out_cols, out_ts,
                               out_tsm, stage_rows, count, bounds, hashes,
                               pane)

    # .................................................................
    def _numpy_twin(self, batch, n, mode):
        """The per-operator reference: every fused stage replayed in
        numpy on the UNPADDED batch.  Returns (cols, ts, tsm, count,
        bounds, hashes, pane) in emission order."""
        from flink_tpu.core.keygroups import (
            assign_operator_indexes_np,
            splitmix64_np,
        )
        from flink_tpu.streaming.operators import _normalize_kernel_output
        vals = batch.value_arrays()
        keep = np.ones(n, bool)
        for kind, fn in self.stages:
            out = fn(vals)
            if kind == "map":
                arrays = _normalize_kernel_output(out, n)
                if arrays is None:
                    return None
                vals = arrays
            else:
                if not (isinstance(out, np.ndarray) and out.shape == (n,)
                        and out.dtype == np.bool_):
                    return None
                keep = keep & out
        cols = vals if type(vals) is tuple else (vals,)
        eff = None
        hashes = bounds = None
        if mode in ("route", "attach"):
            if type(vals) is not tuple or self.route_field >= len(cols):
                return None  # routing leg needs tuple rows
            key = cols[self.route_field]
            if key.dtype != np.int64:
                return None
            hashes = splitmix64_np(key)
            if mode == "route":
                idx = assign_operator_indexes_np(
                    hashes, self._r_maxpar, self._r_nch)
                eff = np.where(keep, idx, self._r_nch)
        if eff is None:
            eff = np.where(keep, 0, 1)
        order = np.argsort(eff, kind="stable")
        cnt = int(keep.sum())
        kord = order[:cnt]
        if mode == "route":
            bounds = np.searchsorted(eff[order],
                                     np.arange(self._r_nch + 1))
        ref_cols = tuple(a[kord] for a in cols)
        ref_ts = batch.ts[kord] if batch.ts is not None else None
        ref_tsm = batch.ts_mask[kord] if batch.ts_mask is not None else None
        # route mode drops the hash column on device (consumed by the
        # partition) — mirror that, the bounds carry the verification
        ref_h = (hashes[kord] if hashes is not None and mode != "route"
                 else None)
        ref_pane = None
        if mode == "window" and ref_ts is not None:
            t = ref_ts.astype(np.int64)
            ref_pane = t - ((t - self._w_offset) % self._w_slide)
        return ref_cols, ref_ts, ref_tsm, cnt, bounds, ref_h, ref_pane

    @staticmethod
    def _arr_eq(a, b) -> bool:
        if a is None or b is None:
            return a is None and b is None
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype != b.dtype or a.shape != b.shape:
            return False
        if a.dtype.kind == "f":
            return bool(np.array_equal(a, b, equal_nan=True))
        return bool(np.array_equal(a, b))

    def _verify(self, batch, n, mode, out_cols, out_ts, out_tsm, count,
                bounds, hashes, pane) -> None:
        """First batch per dtype signature: exact comparison against
        the numpy twin BEFORE anything is emitted.  Mismatch demotes
        the whole chain."""
        FUSION_STATS.probes += 1
        ref = self._numpy_twin(batch, n, mode)
        if ref is None:
            self.demote("probe: numpy reference not columnar "
                        "(kernel output shape or key dtype)")
            raise _Demoted
        ref_cols, ref_ts, ref_tsm, cnt, ref_bounds, ref_h, ref_pane = ref
        ok = (cnt == count
              and len(ref_cols) == len(out_cols)
              and all(self._arr_eq(a, b)
                      for a, b in zip(out_cols, ref_cols))
              and self._arr_eq(out_ts, ref_ts)
              and self._arr_eq(out_tsm, ref_tsm)
              and self._arr_eq(bounds, ref_bounds)
              and self._arr_eq(hashes, ref_h)
              and self._arr_eq(pane, ref_pane))
        if not ok:
            self.demote("probe mismatch (fused != per-operator result)")
            raise _Demoted

    # .................................................................
    def _make_emit(self, batch, n, mode, tuple_out, out_cols, out_ts,
                   out_tsm, stage_rows, count, bounds, hashes, pane):
        """Emission closure — runs OUTSIDE the demotion try/except:
        from here on the fused result is committed (it is verified or
        its signature was)."""
        from flink_tpu.streaming.elements import RecordBatch
        if self.renames:
            # map stages rename machine-style, exactly like the
            # per-operator _kernel_output_batch
            if tuple_out:
                cols = {f"f{i}": a for i, a in enumerate(out_cols)}
            else:
                cols = {"v": out_cols[0]}
        else:
            cols = dict(zip(batch.cols.keys(), out_cols))

        def emit():
            rows = stage_rows.tolist()
            for op, r in zip(self.kernel_ops, rows):
                op._note_fused(int(r))
            FUSION_STATS.fused_batches += 1
            FUSION_STATS.fused_rows += n
            if count == 0:
                return
            out = RecordBatch(cols, out_ts, out_tsm)
            if mode == "window":
                self.window_op.process_batch_fused(out, pane)
                return
            if mode == "route":
                router = self.router
                if router.records_out_counter is not None:
                    router.records_out_counter.count += count
                router.flush_records()
                channels = self.route_channels
                bl = bounds.tolist()
                for c in range(self._r_nch):
                    lo, hi = int(bl[c]), int(bl[c + 1])
                    if lo < hi:
                        channels[c].push(RecordBatch(
                            {k: a[lo:hi] for k, a in cols.items()},
                            out_ts[lo:hi] if out_ts is not None else None,
                            out_tsm[lo:hi] if out_tsm is not None else None))
                return
            if mode == "attach" and hashes is not None:
                out.routing = hashes
            self._after_output().collect_batch(out)

        return emit

    def _after_output(self):
        """Where the fused run's output goes when it doesn't terminate
        in a window/routing leg: the last fused op's own output (the
        next _ChainedOutput, or the router at chain tail)."""
        return self.kernel_ops[-1].output

    # .................................................................
    def _device_fn(self, mode, scalar, use_mesh):
        key = (mode, scalar, use_mesh)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._build_fn(mode, scalar, use_mesh)
            self._fns[key] = fn
        return fn

    def _build_fn(self, mode, scalar, use_mesh):
        stages = self.stages
        route_field = self.route_field
        maxpar = getattr(self, "_r_maxpar", 0)
        nch = getattr(self, "_r_nch", 0)
        w_offset = getattr(self, "_w_offset", 0)
        w_slide = getattr(self, "_w_slide", 1)
        program = self

        def norm_map(out, nrows):
            if type(out) is tuple:
                if not out:
                    raise _trace_err("map kernel returned an empty tuple")
                cols = []
                for item in out:
                    if hasattr(item, "dtype") and hasattr(item, "shape"):
                        if tuple(item.shape) != (nrows,):
                            raise _trace_err(
                                "kernel output is not a column shape")
                        cols.append(item)
                    elif isinstance(item, (bool, int, float, np.generic)):
                        cols.append(jnp.full(nrows, item))
                    else:
                        raise _trace_err(
                            f"map output field of type "
                            f"{type(item).__name__} is not "
                            f"device-representable")
                return tuple(cols)
            if hasattr(out, "dtype") and hasattr(out, "shape"):
                if tuple(out.shape) != (nrows,):
                    raise _trace_err("kernel output is not a column shape")
                return out
            raise _trace_err("kernel output is not a column shape")

        def stable_order(eff, nrows, nclass):
            # Stable partition permutation WITHOUT argsort: sort the
            # combined key ``class * n + position`` (all values unique,
            # ties impossible) and decode with divmod.  A value sort is
            # ~5x cheaper than argsort on the XLA CPU backend and the
            # result is bit-identical to np.argsort(eff, kind="stable").
            if nclass * nrows < 2 ** 31:
                comb = eff.astype(jnp.int32) * jnp.int32(nrows) \
                    + jnp.arange(nrows, dtype=jnp.int32)
            else:
                comb = eff.astype(jnp.int64) * jnp.int64(nrows) \
                    + jnp.arange(nrows, dtype=jnp.int64)
            s = jnp.sort(comb)
            return s % nrows, s // nrows

        def body(cols, ts, tsm, valid):
            nrows = valid.shape[0]
            vals = cols[0] if scalar else cols
            keep = valid
            stage_rows = []
            for kind, fn in stages:
                stage_rows.append(keep.sum())
                out = fn(vals)
                if kind == "map":
                    vals = norm_map(out, nrows)
                else:
                    if not (hasattr(out, "dtype")
                            and out.dtype == jnp.bool_
                            and tuple(out.shape) == (nrows,)):
                        raise _trace_err(
                            "filter kernel did not produce a bool mask")
                    keep = keep & out
            out_cols = vals if type(vals) is tuple else (vals,)
            program._tuple_out[(mode, scalar, use_mesh)] = \
                type(vals) is tuple
            hashes = bounds = pane = None
            if mode in ("route", "attach"):
                if type(vals) is not tuple or route_field >= len(out_cols):
                    raise _trace_err(
                        "routing leg needs tuple rows with the key field")
                key_col = out_cols[route_field]
                if key_col.dtype != jnp.int64:
                    raise _trace_err(
                        f"key column dtype {key_col.dtype} is not int64 "
                        f"(routing parity needs the int fast path)")
                hashes = _jnp_splitmix64(key_col)
            if mode == "route":
                idx = _jnp_operator_indexes(hashes, maxpar, nch)
                # the partition consumes the hashes; rows leave already
                # grouped per channel, so nothing downstream reads them
                # — dropping the column saves a gather and a d2h copy
                hashes = None
                eff = jnp.where(keep, idx, jnp.int32(nch))
                order, cls = stable_order(eff, nrows, nch + 1)
                bounds = jnp.searchsorted(
                    cls, jnp.arange(nch + 1, dtype=cls.dtype))
            else:
                order, _ = stable_order(
                    (~keep).astype(jnp.int32), nrows, 2)
            count = keep.sum()
            g = lambda a: None if a is None else a[order]  # noqa: E731
            out_cols = tuple(g(a) for a in out_cols)
            out_ts, out_tsm = g(ts), g(tsm)
            hashes = g(hashes)
            if mode == "window" and out_ts is not None:
                t = out_ts.astype(jnp.int64)
                pane = t - ((t - w_offset) % w_slide)
            srows = (jnp.stack(stage_rows) if stage_rows
                     else jnp.zeros(0, jnp.int64))
            return out_cols, out_ts, out_tsm, srows, count, bounds, \
                hashes, pane

        if not use_mesh:
            return traced_jit(body, self.label)

        def shard_body(cols, ts, tsm, valid):
            out_cols, out_ts, out_tsm, srows, count, b, hashes, pane = \
                body(cols, ts, tsm, valid)
            # leading shard axis for the scalars so out_specs P("rows")
            # concatenates them into [n_shards] / [n_shards, n_stages]
            # (and [n_shards, nch+1] for the per-shard route bounds)
            return (out_cols, out_ts, out_tsm, srows[None, :],
                    count[None], None if b is None else b[None, :],
                    hashes, pane)

        spec = P("rows")
        bspec = spec if mode == "route" else None
        sharded = shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec, spec, spec, spec, bspec, spec, spec),
            check_vma=False)
        return traced_jit(sharded, self.label)


def _trace_err(msg: str) -> Exception:
    return _NotFusable(f"chain fusion: {msg}")


# ---------------------------------------------------------------------
# jnp twins of the routing arithmetic (keygroups.py)
# ---------------------------------------------------------------------

def _jnp_splitmix64(x):
    """splitmix64 on an int64 column — bit-identical to
    ``keygroups.splitmix64_np`` / ``_routing_hashes`` int keys."""
    z = x.astype(jnp.uint64) + jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> jnp.uint64(31))


def _jnp_operator_indexes(hashes, max_parallelism, num_channels):
    """hash → key group (32-bit murmur avalanche) → operator index —
    bit-identical to ``keygroups.assign_operator_indexes_np``."""
    m32 = jnp.uint64(0xFFFFFFFF)
    h = hashes & m32
    h = h ^ (h >> jnp.uint64(16))
    h = (h * jnp.uint64(0x85EBCA6B)) & m32
    h = h ^ (h >> jnp.uint64(13))
    h = (h * jnp.uint64(0xC2B2AE35)) & m32
    h = h ^ (h >> jnp.uint64(16))
    kg = h % jnp.uint64(max_parallelism)
    idx = (kg * jnp.uint64(num_channels)) // jnp.uint64(max_parallelism)
    return idx.astype(jnp.int32)

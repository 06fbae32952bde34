"""Which engine runs a windowed aggregate, what its tier is called in a
checkpoint, and what it means for an operator to host one.

The one place that knows the shape test (`aligned_shape`), the graph
builder's gate (`batched_operator_kind`), the tiers (`TIERS`: the name
an engine was built under is the name its snapshot carries and the
constructor that reads it back), the ladder (`select_engine`) and
hosting (`WindowEngineHost`, the base of DeviceWindowOperator, the
DataStream door, and ColumnarWindowOperator, the SQL door).
GenericWindowOperator (arbitrary Python aggregates) has one tier and
keeps its own snapshot code; it shares only the shape test.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from flink_tpu.ops.device_agg import DeviceAggregateFunction, SumAggregate
from flink_tpu.streaming.operators import StreamOperator
from flink_tpu.streaming.windowing import (
    EventTimeSessionWindows,
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)


class WindowShape(NamedTuple):
    """An assigner the batched engines cover.  Every engine family has
    one class per kind whose constructor starts (aggregate, *params)."""
    kind: str      # "tumbling" | "sliding" | "session"
    params: tuple  # (size,) | (size, slide) | (gap,)

    @property
    def grid(self) -> Optional[int]:
        """Window ends fall on multiples of this; None for sessions,
        which can fire at any time."""
        return None if self.kind == "session" else self.params[-1]

    def build(self, tumbling, sliding, session, agg, *args, **kwargs):
        cls = {"tumbling": tumbling, "sliding": sliding,
               "session": session}[self.kind]
        return cls(agg, *self.params, *args, **kwargs)


def aligned_shape(assigner) -> Optional[WindowShape]:
    """The shape test: event-time tumbling or sliding windows on the
    epoch grid (no offset, the slide dividing the size) and event-time
    sessions; anything else stays on the scalar WindowOperator."""
    if isinstance(assigner, EventTimeSessionWindows):
        return WindowShape("session", (assigner.gap,))
    if isinstance(assigner, (TumblingEventTimeWindows,
                             SlidingEventTimeWindows)) \
            and assigner.offset == 0:
        if isinstance(assigner, TumblingEventTimeWindows):
            return WindowShape("tumbling", (assigner.size,))
        if assigner.size % assigner.slide == 0:
            return WindowShape("sliding", (assigner.size, assigner.slide))
    return None


def batched_operator_kind(assigner, aggregate_function, trigger, evictor,
                          allowed_lateness, late_tag,
                          window_function) -> Optional[str]:
    """The graph builder's gate: "device" (DeviceWindowOperator) for a
    DeviceAggregateFunction, "generic" (GenericWindowOperator) for any
    other aggregate, None (the scalar WindowOperator) unless the window
    has an aligned shape, the default trigger, no evictor, no lateness
    and at most a plain callable as window function (ref: the
    one-operator-serves-all contract of WindowOperator.java:291-421)."""
    if (trigger is not None or evictor is not None
            or allowed_lateness != 0 or late_tag is not None
            or (window_function is not None
                and not callable(window_function))
            or aligned_shape(assigner) is None):
        return None
    return ("device" if isinstance(aggregate_function,
                                   DeviceAggregateFunction) else "generic")


def is_mesh_factory(mesh) -> bool:
    """True for a callable that BUILDS a mesh (the pod-topology
    per-process factory) as opposed to a Mesh instance — jax's Mesh is
    itself callable (a context decorator), so `callable` alone cannot
    discriminate; factories have no device grid `.shape`."""
    return callable(mesh) and not hasattr(mesh, "shape")


def resolve_mesh(mesh):
    """Mesh | mesh-factory | None → Mesh | None (factories resolve in
    the CURRENT process; device handles cannot ride a pickled graph)."""
    return mesh() if is_mesh_factory(mesh) else mesh


# ---- the tiers: host → engine, or None where the tier does not fit

def string_sum_fits(shape: WindowShape, agg) -> bool:
    """The fused intern+sum engine takes string-keyed tumbling sums.
    Floating accumulation only: the C++ kernel sums in double, so
    integer value dtypes (exact beyond 2^53) stay on the exact tiers."""
    return (shape.kind == "tumbling" and isinstance(agg, SumAggregate)
            and np.issubdtype(agg.value_dtype, np.floating))


def _string_sum(host):
    from flink_tpu.streaming.log_windows import StringSumTumblingWindows
    if string_sum_fits(host.shape, host.agg):
        return StringSumTumblingWindows(host.agg, *host.shape.params)
    return None


def _mesh_log(host):
    """all_to_all keyBy exchange + per-shard log fires
    (parallel/mesh_log.py); a mesh factory resolves here, so a job that
    never asks for this tier pays no device/client init."""
    from flink_tpu.parallel.mesh_log import mesh_log_engine_for_assigner
    host.mesh = resolve_mesh(host.mesh)
    if host.mesh is None:
        raise RuntimeError(
            "the mesh log tier needs a mesh (env.set_mesh)")
    return mesh_log_engine_for_assigner(
        host.assigner, host.agg, host.mesh, axis=host.mesh_axis,
        max_parallelism=host.max_parallelism)


def _log(host):
    """Log-structured combiner tier (streaming/log_windows.py scope:
    integer keys, HLL/Sum/Quantile cells, Count-Min sessions).  A
    missing native runtime is an error (the engines raise
    RuntimeError), never a reason to hand the job to another engine."""
    from flink_tpu.streaming import log_windows as lw
    try:
        return host.shape.build(lw.LogStructuredTumblingWindows,
                                lw.LogStructuredSlidingWindows,
                                lw.LogStructuredSessionWindows, host.agg)
    except (TypeError, ValueError):
        return None  # unsupported cell decomposition / params


def _vectorized(host):
    """The device-resident scatter engines: every aggregate, every key
    dtype.  Where the host allows it (difference (i) below) and the
    shape has one, the sharded twin (SPMD over the mesh axis,
    parallel/mesh_windows.py)."""
    shape = host.shape
    if host.mesh_scatter and host.mesh is not None \
            and shape.kind != "session":
        from flink_tpu.parallel import mesh_windows as mw
        mesh = host.mesh = resolve_mesh(host.mesh)
        return shape.build(
            mw.MeshTumblingWindows, mw.MeshSlidingWindows, None,
            host.agg, mesh, axis=host.mesh_axis,
            max_parallelism=host.max_parallelism,
            capacity_per_window_shard=max(
                1 << 8,
                host.initial_capacity // mesh.shape[host.mesh_axis]))
    from flink_tpu.streaming import vectorized as vz
    from flink_tpu.streaming.vectorized_sessions import (
        VectorizedSessionWindows,
    )
    return shape.build(vz.VectorizedTumblingWindows,
                       vz.VectorizedSlidingWindows,
                       VectorizedSessionWindows, host.agg,
                       initial_capacity=host.initial_capacity)


#: checkpoint tier name → constructor, in ladder order.  The names are
#: on disk (`device_tier` / `columnar_tier`): never rename one.
TIERS = {"mesh_log": _mesh_log, "string_sum": _string_sum, "log": _log,
         "vectorized": _vectorized}


def select_engine(host, key_dtype: np.dtype):
    """The ladder, run once in an operator's life, on its first batch:
    mesh log → (mesh scatter) → fused string sum → log → vectorized.
    → (engine, tier name).

    `key_dtype` is what the host observed after its own interning, and
    the two doors observe differently — on purpose or not, these are
    today's answers and the table in tests/test_window_engines.py holds
    them:

    (i)   under a mesh, keys or aggregates outside the mesh log tier
          run the sharded scatter engines on the DataStream door
          (`host.mesh_scatter`) and the single-device ladder on the SQL
          door, which so resolves a mesh factory only for integer keys;
    (ii)  the DataStream door interns string keys before it asks, so it
          asks with integer ids (and reaches the log tiers) wherever
          the fused string sum does not fit; the SQL door asks with the
          string dtype and falls to the vectorized tier there;
    (iii) a missing native runtime stays an error raised by the log
          engines, never a reason to choose another engine (PR 21)."""
    if host.shape is None:
        raise ValueError(f"no device engine for assigner {host.assigner!r}")
    integer_keys = np.issubdtype(key_dtype, np.integer)
    ladder = ["mesh_log"] if host.mesh is not None and integer_keys else []
    if host.mesh is None or not host.mesh_scatter:
        if key_dtype.kind in "US":
            ladder.append("string_sum")
        if integer_keys:
            ladder.append("log")
    for tier in ladder:
        engine = TIERS[tier](host)
        if engine is not None:
            return engine, tier
    return _vectorized(host), "vectorized"


def engine_for_tier(host, tier):
    """The constructor that reads a checkpoint of `tier`.  A snapshot
    from before tiers had names (None) is a vectorized one."""
    engine = None if host.shape is None \
        else TIERS.get(tier, _vectorized)(host)
    if engine is None:
        raise RuntimeError(
            f"checkpoint was taken on the {tier!r} engine tier, which "
            f"does not cover {host.agg!r} over {host.assigner!r} here")
    return engine


class WindowEngineHost(StreamOperator):
    """An operator that IS its keyed state: one window engine, chosen
    on the first batch, snapshotted under the door's own checkpoint
    keys."""

    #: checkpoint keys of this door (on disk: never rename one)
    engine_key: str
    tier_key: str
    #: difference (i) of `select_engine`
    mesh_scatter = False

    def __init__(self, assigner, agg: DeviceAggregateFunction,
                 initial_capacity: int, mesh, mesh_axis: str):
        super().__init__()
        self.assigner = assigner
        self.shape = aligned_shape(assigner)
        self.agg = agg
        self.initial_capacity = initial_capacity
        #: with a mesh, the keyBy exchange is lax.all_to_all over the
        #: mesh axis and the aggregation shards over it — the plan
        #: stays at parallelism 1 and the mesh provides the scale axis
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.engine = None
        self.tier = None
        self.num_late_records_dropped = 0  # metric parity

    def set_key_context(self, record):
        pass  # no keyed backend; keys resolve vectorized in the engine

    def _adopt(self, engine, tier) -> None:
        self.engine, self.tier = engine, tier
        # engines that can hand a fire over as arrays do; the rest
        # (VectorizedSessionWindows) deliver one tuple per result
        if hasattr(engine, "fired"):
            engine.emit_arrays = True

    def _build_engine(self, key_dtype: np.dtype) -> None:
        self._adopt(*select_engine(self, key_dtype))
        # fast-forward a lazily created engine to the operator's
        # watermark — records behind it must count as LATE, not be
        # aggregated into windows that already passed downstream
        wm = getattr(self, "current_watermark", None)
        if wm is not None and wm > -(2 ** 63):
            self.engine.advance_watermark(wm)

    # ---- checkpoint -------------------------------------------------
    def snapshot_state(self, checkpoint_id: Optional[int] = None) -> dict:
        snap = super().snapshot_state(checkpoint_id)
        if self.engine is not None:
            snap[self.engine_key] = self.engine.snapshot()
            snap[self.tier_key] = self.tier
        return snap

    def _resplits(self, snapshots) -> bool:
        """Parallelism changed, or several old subtasks' states land
        here: the engine states merge and re-split by key group."""
        return (sum(self.engine_key in s for s in snapshots) > 1
                or any(s.get("restore_old_parallelism", self.num_subtasks)
                       != self.num_subtasks for s in snapshots))

    def restore_state(self, snapshots) -> None:
        super().restore_state(snapshots)
        snaps = [s for s in snapshots if self.engine_key in s]
        if not snaps:
            return
        tiers = {s.get(self.tier_key) for s in snaps}
        if len(tiers) > 1:
            raise ValueError(
                f"snapshots span engine tiers {sorted(map(str, tiers))}; "
                "cannot merge across tiers")
        tier = tiers.pop()
        if self.engine is None:
            self._adopt(engine_for_tier(self, tier), tier)
        states = [s[self.engine_key] for s in snaps]
        if not self._resplits(snapshots):
            self.engine.restore(states[0])
            return
        # keep only this subtask's key groups (ref:
        # StateAssignmentOperation key-group re-split), by the shared
        # definition, so re-split state lands where the runtime's keyBy
        # partitioner routes live records
        if not hasattr(self.engine, "restore_many"):
            raise ValueError(
                f"the {tier!r} engine tier cannot re-split its state "
                "across a parallelism change; restore at the "
                "checkpointed parallelism")
        from flink_tpu.core.keygroups import make_key_group_keep_fn
        self.engine.restore_many(
            states, keep_fn=make_key_group_keep_fn(
                self.max_parallelism, self.num_subtasks,
                self.subtask_index))

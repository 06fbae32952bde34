"""Keyed-state backend contract.

Re-designs flink-runtime/.../state/AbstractKeyedStateBackend.java:64-453:
per-state-name factories (createValueState :159 … createMapState :229),
`setCurrentKey` :237 (computes the key group), `getOrCreateKeyedState`
:319 (binds a descriptor once and caches), namespace addressing
(window = namespace, WindowOperator.java:387) and snapshot/restore.

Differences from the reference, on purpose:
- No per-state serializer plumbing on the hot path; Python values go
  straight into the tables, serialization happens only at snapshot
  time (and for the TPU backend the hot path is numeric arrays).
- `snapshot()` returns a `KeyedStateSnapshot` of per-key-group chunks
  so restore can re-split ranges on rescale
  (ref: KeyGroupsStateHandle.java, StateAssignmentOperation.java).
"""

from __future__ import annotations

import abc
from typing import Any, Dict, Iterable, Optional

import numpy as np

from flink_tpu.core.key_columns import as_list
from flink_tpu.core.keygroups import (
    KeyGroupRange,
    assign_key_groups_np,
    assign_to_key_group,
    stable_hashes_np,
)
from flink_tpu.core.state import (
    AggregatingStateDescriptor,
    FoldingStateDescriptor,
    ListStateDescriptor,
    MapStateDescriptor,
    ReducingStateDescriptor,
    StateDescriptor,
    ValueStateDescriptor,
)


#: default namespace used for non-windowed keyed state
#: (ref: VoidNamespace.java — a singleton namespace)
VOID_NAMESPACE = ()


class KeyedStateSnapshot:
    """Serialized keyed state, chunked per key group.

    `key_group_bytes[kg]` is an opaque blob for key group `kg`;
    restore feeds each chunk whose key group falls in the new backend's
    range (ref: KeyGroupsStateHandle.java + KeyGroupRangeOffsets.java —
    here chunks are explicit instead of offsets into one stream).

    Each key group's blob is wrapped as a content-addressed
    SharedChunk: checkpoint storage stores every distinct chunk ONCE
    across retained checkpoints, so an untouched key group contributes
    ~0 bytes to the next checkpoint (the incremental-checkpoint seam,
    ref: RocksDBKeyedStateBackend incremental snapshots +
    SharedStateRegistry.java).  Consumers read via ``blobs()``, which
    hands back raw bytes whether the snapshot is freshly taken
    (wrapped), storage-resolved (raw), or mixed (after intersect).
    """

    __slots__ = ("key_group_bytes", "meta")

    def __init__(self, key_group_bytes: Dict[int, bytes],
                 meta: Optional[dict] = None, wrap: bool = True):
        if wrap:
            from flink_tpu.state.shared_registry import SharedChunk
            key_group_bytes = {
                kg: b if isinstance(b, SharedChunk) else SharedChunk(b)
                for kg, b in key_group_bytes.items()}
        self.key_group_bytes = key_group_bytes
        self.meta = meta or {}

    def blobs(self):
        """Yields (key_group, raw_bytes)."""
        from flink_tpu.state.shared_registry import SharedChunk
        for kg, b in self.key_group_bytes.items():
            yield kg, (b.payload if isinstance(b, SharedChunk) else b)

    @property
    def total_bytes(self) -> int:
        return sum(len(b) for _, b in self.blobs() if b is not None)

    def intersect(self, key_group_range: KeyGroupRange) -> "KeyedStateSnapshot":
        return KeyedStateSnapshot(
            {kg: b for kg, b in self.key_group_bytes.items()
             if key_group_range.contains(kg)},
            dict(self.meta),
            wrap=False,
        )

    def _map_chunks_(self, fn):
        """shared_registry.map_chunks protocol: rebuild with every
        chunk node replaced (registration / resolution)."""
        from flink_tpu.state.shared_registry import ChunkRef, SharedChunk
        mapped = {}
        changed = False
        for kg, b in self.key_group_bytes.items():
            nb = fn(b) if isinstance(b, (SharedChunk, ChunkRef)) else b
            changed = changed or nb is not b
            mapped[kg] = nb
        if not changed:
            return self
        return KeyedStateSnapshot(mapped, dict(self.meta), wrap=False)


class DeferredSnapshot:
    """A part of a task's snapshot that is finished after the barrier:
    what a backend with an asynchronous part puts into the ack
    (its `capture_snapshot`; the timer service's).  `resolve()` (any thread;
    the work is done once) gives the finished part.  A checkpoint's
    coordinator resolves the handles of its acks on its writer and
    puts what they give in their place before anything else reads
    the acks (`CheckpointCoordinator._do_persist`)."""

    __slots__ = ("_make", "_value", "_lock")

    def __init__(self, make):
        import threading
        self._make = make
        self._value = None
        self._lock = threading.Lock()

    def resolve(self):
        with self._lock:
            if self._make is not None:
                self._value = self._make()
                self._make = None
            return self._value


class KeyedStateBackend(abc.ABC):
    """The contract every keyed backend implements
    (ref: AbstractKeyedStateBackend.java:64)."""

    def __init__(self, key_group_range: KeyGroupRange, max_parallelism: int):
        self.key_group_range = key_group_range
        self.max_parallelism = max_parallelism
        self._current_key: Any = None
        self._current_key_group: int = -1
        # introspection registry (WeakSet — unconditional and free;
        # the plane only walks registered backends while enabled)
        from flink_tpu.state.introspect import INTROSPECTION
        INTROSPECTION.register_backend(self)
        #: name → bound state object (ref: keyValueStatesByName, :319)
        self._states: Dict[str, Any] = {}
        #: name → descriptor it was bound with (compatibility checks)
        self._descriptors: Dict[str, StateDescriptor] = {}
        #: serializer configs recorded by restored snapshots — checked
        #: at bind time for states registered after restore
        self._restored_serializer_cfgs: Dict[str, Any] = {}
        #: queryable-state registrations (ref: :382-389)
        self.queryable_states: Dict[str, Any] = {}

    # ---- key context (ref: setCurrentKey :237) ----------------------
    def set_current_key(self, key: Any) -> None:
        self._current_key = key
        self._current_key_group = assign_to_key_group(key, self.max_parallelism)

    @property
    def current_key(self) -> Any:
        return self._current_key

    @property
    def current_key_group(self) -> int:
        return self._current_key_group

    # ---- state binding (ref: getOrCreateKeyedState :319) ------------
    def get_or_create_keyed_state(self, descriptor: StateDescriptor):
        state = self._states.get(descriptor.name)
        if state is None:
            self._check_serializer_against_restored(descriptor)
            state = self._create_state(descriptor)
            self._states[descriptor.name] = state
            self._descriptors[descriptor.name] = descriptor
            if descriptor.is_queryable:
                self.queryable_states[descriptor.queryable_state_name] = state
        else:
            bound = self._descriptors[descriptor.name]
            if bound.TYPE != descriptor.TYPE:
                # (ref: StateDescriptor compatibility check in
                # AbstractKeyedStateBackend — same name, different kind
                # of state is a program error, not a cache hit)
                raise ValueError(
                    f"state {descriptor.name!r} already registered as "
                    f"{bound.TYPE!r}, cannot rebind as {descriptor.TYPE!r}")
        return state

    def get_partitioned_state(self, namespace, descriptor: StateDescriptor):
        """Bind + switch namespace in one call
        (ref: getPartitionedState :403)."""
        state = self.get_or_create_keyed_state(descriptor)
        state.set_current_namespace(namespace)
        return state

    def _create_state(self, descriptor: StateDescriptor):
        # ordered most-specific-first; isinstance covers subclasses
        for dtype, factory in [
            (MapStateDescriptor, self.create_map_state),
            (AggregatingStateDescriptor, self.create_aggregating_state),
            (ReducingStateDescriptor, self.create_reducing_state),
            (FoldingStateDescriptor, self.create_folding_state),
            (ListStateDescriptor, self.create_list_state),
            (ValueStateDescriptor, self.create_value_state),
        ]:
            if isinstance(descriptor, dtype):
                return factory(descriptor)
        raise TypeError(f"unsupported state descriptor {descriptor!r}")

    # ---- factories (ref: createValueState :159 … createMapState :229)
    @abc.abstractmethod
    def create_value_state(self, descriptor: ValueStateDescriptor):
        ...

    @abc.abstractmethod
    def create_list_state(self, descriptor: ListStateDescriptor):
        ...

    @abc.abstractmethod
    def create_reducing_state(self, descriptor: ReducingStateDescriptor):
        ...

    @abc.abstractmethod
    def create_aggregating_state(self, descriptor: AggregatingStateDescriptor):
        ...

    @abc.abstractmethod
    def create_folding_state(self, descriptor: FoldingStateDescriptor):
        ...

    @abc.abstractmethod
    def create_map_state(self, descriptor: MapStateDescriptor):
        ...

    # ---- batched ingest (the paper's core thesis: whole sub-batches
    # of (key, namespace, value) rows enter keyed state in one call,
    # key-group assignment done in ONE vectorized hash pass instead of
    # per-row setCurrentKey) ------------------------------------------
    def assign_key_groups_batch(self, keys) -> np.ndarray:
        """Vectorized key → key-group for a whole column of keys.
        Bit-identical to per-row ``assign_to_key_group`` (the splitmix64
        parity path shared with the batched router's split_batch)."""
        return assign_key_groups_np(stable_hashes_np(keys),
                                    self.max_parallelism)

    def add_batch(self, state, keys, namespace, values,
                  namespaces=None, pre_extracted: bool = False) -> str:
        """Append a whole column of values into `state`, one row per
        (keys[i], namespace-or-namespaces[i], values[i]).

        Dispatches to the state object's native ``add_batch`` when it
        has one (device SoA scatter on the TPU backend, grouped
        in-order fold on the heap column table); otherwise falls back
        to the exact per-row path (set_current_key +
        set_current_namespace + state.add) so opaque-object states keep
        bit-identical semantics.  Returns the path taken ("batch" or
        "rows") so callers/benches can assert zero boxed fallbacks.

        Leaves the backend's current key/namespace context undefined —
        callers in a row context must re-establish it.
        """
        from flink_tpu.state.introspect import INTROSPECTION
        from flink_tpu.state.stats import STATE_STATS
        keys = _keys_for(state, keys)
        n = len(keys)
        name = _state_name(state)
        if INTROSPECTION.enabled:
            INTROSPECTION.note_ingest(name, as_list(keys),
                                      self.max_parallelism)
        native = getattr(state, "add_batch", None)
        if native is not None:
            if pre_extracted:
                # caller already ran the aggregate's extract_value over
                # the whole column (device states only — heap states
                # don't take the kwarg)
                native(keys, namespace, values, namespaces=namespaces,
                       pre_extracted=True)
            else:
                native(keys, namespace, values, namespaces=namespaces)
            STATE_STATS.note_batch(name, n)
            return "batch"
        if namespaces is None:
            state.set_current_namespace(namespace)
            for i in range(n):
                self.set_current_key(keys[i])
                state.add(values[i])
        else:
            for i in range(n):
                self.set_current_key(keys[i])
                state.set_current_namespace(namespaces[i])
                state.add(values[i])
        STATE_STATS.note_fallback(name, n)
        return "rows"

    def get_batch(self, state, keys, namespace, namespaces=None):
        """Read a whole column of (keys[i], namespace-or-namespaces[i])
        contents out of `state` — the batched twin of ``state.get()``,
        the window FIRE path's one-gather read.

        Returns ``(results, found, path)``: `results` indexes per row
        (an ndarray for device states, a list for host states), `found`
        is a bool mask (False rows have no state — the scalar get()'s
        None), and `path` is ``"batch"`` or ``"rows"``.

        Dispatches to the state object's native ``get_batch`` when it
        has one (ONE flush + ONE fused gather + ONE D2H per component
        on the TPU backend, direct column reads on the heap tables);
        otherwise falls back to the exact per-row loop
        (set_current_key + set_current_namespace + state.get) so
        opaque-object states keep bit-identical semantics.

        Leaves the backend's current key/namespace context undefined —
        callers in a row context must re-establish it.
        """
        from flink_tpu.state.stats import STATE_STATS
        keys = _keys_for(state, keys)
        n = len(keys)
        name = _state_name(state)
        native = getattr(state, "get_batch", None)
        if native is not None:
            results, found = native(keys, namespace, namespaces=namespaces)
            STATE_STATS.note_batch(name, n)
            return results, found, "batch"
        results = []
        found = np.empty(n, bool)
        if namespaces is None:
            state.set_current_namespace(namespace)
        for i in range(n):
            self.set_current_key(keys[i])
            if namespaces is not None:
                state.set_current_namespace(namespaces[i])
            v = state.get()
            results.append(v)
            found[i] = v is not None
        STATE_STATS.note_fallback(name, n)
        return results, found, "rows"

    def clear_batch(self, state, keys, namespace, namespaces=None) -> str:
        """Drop a whole column of (keys[i], namespace-or-namespaces[i])
        slots from `state` — the batched twin of ``state.clear()``, the
        fire path's one-call cleanup.  Returns the path taken ("batch"
        or "rows"); fallback semantics per row are exactly
        set_current_key + set_current_namespace + state.clear().

        Leaves the backend's current key/namespace context undefined.
        """
        keys = _keys_for(state, keys)
        native = getattr(state, "clear_batch", None)
        if native is not None:
            native(keys, namespace, namespaces=namespaces)
            return "batch"
        if namespaces is None:
            state.set_current_namespace(namespace)
            for k in keys:
                self.set_current_key(k)
                state.clear()
        else:
            for i, k in enumerate(keys):
                self.set_current_key(k)
                state.set_current_namespace(namespaces[i])
                state.clear()
        return "rows"

    def merge_namespaces_batch(self, state, merges) -> str:
        """Fold, for every ``(key, target, [sources])`` of `merges`,
        the sources' namespaces of that key into its target — the
        batched twin of ``state.merge_namespaces`` under each key, the
        session windows' merge of state windows.  Dispatches to the
        state object's native ``merge_namespaces_batch`` (the TPU
        backend: one flush, the merges in rounds through one pairwise
        kernel, one clear); otherwise key by key.  Returns the path
        taken.  Leaves the backend's current key undefined."""
        native = getattr(state, "merge_namespaces_batch", None)
        if native is not None:
            native(merges)
            return "batch"
        for key, target, sources in merges:
            self.set_current_key(key)
            state.merge_namespaces(target, sources)
        return "rows"

    # ---- introspection ----------------------------------------------
    @abc.abstractmethod
    def get_keys(self, state_name: str, namespace) -> Iterable[Any]:
        """All keys having state under (state_name, namespace)
        (ref: KeyedStateBackend#getKeys)."""

    def num_registered_states(self) -> int:
        return len(self._states)

    # ---- serializer compatibility (ref: the
    # TypeSerializerConfigSnapshot contract — a snapshot records the
    # serializer configuration per state, and restore refuses a
    # serializer that cannot read it, StateMigrationException) --------
    def serializer_config_snapshots(self) -> dict:
        out = {}
        for name, d in self._descriptors.items():
            ser = getattr(d, "serializer", None)
            if ser is not None:
                out[name] = ser.snapshot_configuration()
        return out

    def check_serializer_compatibility(self, snapshots) -> None:
        for snap in snapshots:
            recorded = (snap.meta or {}).get("serializers", {})
            for name, cfg in recorded.items():
                # remembered for states bound AFTER restore (the
                # late-bind path restore-before-bind supports)
                self._restored_serializer_cfgs[name] = cfg
                d = self._descriptors.get(name)
                if d is not None:
                    # check only — values have not loaded yet; the
                    # restore's tail runs _apply_restored_migrations
                    self._check_serializer_against_restored(
                        d, migrate=False)

    def _check_serializer_against_restored(self,
                                           descriptor: StateDescriptor,
                                           migrate: bool = True
                                           ) -> None:
        from flink_tpu.core.serialization import StateMigrationException
        cfg = self._restored_serializer_cfgs.get(descriptor.name)
        ser = getattr(descriptor, "serializer", None)
        if cfg is not None and ser is not None \
                and not ser.ensure_compatibility(cfg):
            raise StateMigrationException(
                f"state '{descriptor.name}' was written with serializer "
                f"{cfg.serializer_name!r}; the registered serializer "
                f"{type(ser).__name__!r} cannot read it (ref: "
                f"TypeSerializerConfigSnapshot compatibility)")
        # COMPATIBLE_AFTER_MIGRATION: a changed-but-readable config
        # (e.g. an evolved record schema) migrates the state's values
        # once, at whichever comes later — bind or restore.  The
        # recorded config is then replaced so a re-bind can never
        # migrate twice (double resolution would overwrite real
        # values with defaults).
        if migrate and cfg is not None and ser is not None \
                and cfg != ser.snapshot_configuration():
            self._migrate_state_values(descriptor, ser, cfg)
            self._restored_serializer_cfgs[descriptor.name] = \
                ser.snapshot_configuration()

    def _migrate_state_values(self, descriptor: StateDescriptor,
                              serializer, restored_cfg) -> None:
        """Backend hook: rewrite the descriptor's restored values via
        serializer.migrate_value.  Backends that materialize restored
        values as live objects (the heap/tpu host tables) override;
        byte-oriented stores resolve lazily through the serializer
        itself and need nothing here.  (Takes the DESCRIPTOR, not the
        name: at bind time the registry entry does not exist yet.)"""

    def _apply_restored_migrations(self) -> None:
        """Called by restore() AFTER values load: migrate every
        already-bound state whose recorded config differs (the
        bind-before-restore order; restore-before-bind migrates at
        bind via _check_serializer_against_restored)."""
        for name, d in self._descriptors.items():
            cfg = self._restored_serializer_cfgs.get(name)
            ser = getattr(d, "serializer", None)
            if cfg is not None and ser is not None \
                    and cfg != ser.snapshot_configuration():
                self._migrate_state_values(d, ser, cfg)
                self._restored_serializer_cfgs[name] = \
                    ser.snapshot_configuration()

    # ---- snapshot / restore (ref: Snapshotable) ---------------------
    @abc.abstractmethod
    def snapshot(self) -> KeyedStateSnapshot:
        ...

    @abc.abstractmethod
    def restore(self, snapshots: Iterable[KeyedStateSnapshot]) -> None:
        """Restore from one or more snapshots' chunks that intersect
        this backend's key-group range (rescale = pass the snapshots of
        all old subtasks; chunks outside the range are skipped).
        Implementations call `check_serializer_compatibility` first."""

    # ---- keyed-state introspection ----------------------------------
    def accounting_breakdown(self) -> Dict[str, Dict[int, dict]]:
        """Per-(state, key-group) accounting:
        ``{state_name: {key_group: {"rows", "bytes", "namespaces"}}}``.
        Bytes follow the snapshot's serialization exactly — component
        ndarray nbytes for columnar rows, pickled length for boxed
        rows — so live accounting, the archive payload and the offline
        inspector always agree.  Backends with tables override."""
        return {}

    def dispose(self) -> None:
        # freeze accounting BEFORE subclasses clear their tables
        # (subclass disposes call super().dispose() first), so a
        # finished job's numbers survive into the archive payload
        from flink_tpu.state.introspect import INTROSPECTION
        if INTROSPECTION.enabled:
            INTROSPECTION.note_dispose(self)
        self._states.clear()


def _state_name(state) -> str:
    d = getattr(state, "_descriptor", None)
    return getattr(d, "name", "?") if d is not None else "?"


def _keys_for(state, keys):
    """`keys` as `state` takes them: an int64 column as it is where the
    state says `takes_key_column`, else the list of Python objects every
    other state has always been handed."""
    if isinstance(keys, np.ndarray) \
            and not getattr(state, "takes_key_column", False):
        return keys.tolist()
    return keys


def encode_obj_column(values) -> tuple:
    """Encode a python value column through the wire codec's "col" tier
    (int64/float64/str/tuple columns, PR 5) — ``("pickle", list)`` when
    the column is not strictly typed.  Snapshot chunks carry these so
    key columns and namespace columns serialize without boxing."""
    values = list(values)
    if values:
        try:
            from flink_tpu.runtime.netchannel import _encode_value_column
            col = _encode_value_column(values)
        except (OverflowError, ValueError):
            col = None
        if col is not None:
            return col
    return ("pickle", values)


def decode_obj_column(col, n: int) -> list:
    """Inverse of encode_obj_column."""
    if col[0] == "pickle":
        return list(col[1])
    from flink_tpu.runtime.netchannel import _decode_value_column
    return _decode_value_column(col, n)


def migrate_table_values(table, descriptor, serializer,
                         restored_cfg) -> None:
    """Shared value-migration pass over a live StateTable: the
    descriptor's TYPE decides the stored shape — a LIST state stores a
    Python list of elements and a MAP state a dict of entries, so the
    ELEMENT serializer's migrate_value maps over them; everything else
    stores one value (the reference's per-element migration in
    StateTableByKeyGroupReaders)."""
    kind = getattr(descriptor, "TYPE", "value")
    if kind == "list":
        def mig(v):
            return [serializer.migrate_value(x, restored_cfg) for x in v]
    elif kind == "map":
        def mig(v):
            return {k: serializer.migrate_value(x, restored_cfg)
                    for k, x in v.items()}
    else:
        def mig(v):
            return serializer.migrate_value(v, restored_cfg)
    for namespace, key, value in list(table.entries()):
        table.put(key, namespace, mig(value))

"""State-backend selection from configuration.

Mirrors flink-runtime/.../state/StateBackendLoader.java:92-109, where
the `state.backend` config key resolves shortcut names to factories —
the north-star requirement is that ONLY this switch changes between the
heap and TPU deployments.  Shortcuts accepted:

  heap | jobmanager | filesystem  → HeapKeyedStateBackend
  tpu  | rocksdb                  → TpuKeyedStateBackend
                                    (`rocksdb` maps to the TPU backend
                                    because it occupies the same role:
                                    the scalable keyed backend)
"""

from __future__ import annotations

from flink_tpu.core.config import Configuration
from flink_tpu.core.keygroups import KeyGroupRange
from flink_tpu.state.backend import KeyedStateBackend
from flink_tpu.state.heap_backend import HeapKeyedStateBackend
from flink_tpu.state.tpu_backend import TpuKeyedStateBackend

#: config key (ref: CheckpointingOptions.java:33 `state.backend`)
STATE_BACKEND_KEY = "state.backend"

_HEAP_NAMES = {"heap", "jobmanager", "filesystem", "memory", "hashmap"}
_TPU_NAMES = {"tpu", "rocksdb", "device", "hbm"}


def load_state_backend(
    config_or_name,
    key_group_range: KeyGroupRange,
    max_parallelism: int,
    name: str | None = None,
    **kwargs,
) -> KeyedStateBackend:
    """`config_or_name` is a `Configuration` (the backend's name under
    `state.backend`, its tuning keys beside it) or a bare name, which
    carries no tuning.  `name` overrides the configured name (a graph
    node that pins its own backend) and leaves the tuning keys be."""
    if isinstance(config_or_name, Configuration):
        if name is None:
            name = config_or_name.get_string(STATE_BACKEND_KEY, "heap")
        # HBM budget: beyond it, cold device slots spill to host RAM
        if config_or_name.contains("state.backend.tpu.max-device-slots"):
            cap = config_or_name.get_integer(
                "state.backend.tpu.max-device-slots")
            if cap is None or cap <= 0:
                raise ValueError(
                    "state.backend.tpu.max-device-slots must be > 0 "
                    f"(got {cap}); omit it for an uncapped device tier")
            kwargs.setdefault("max_device_slots", cap)
        # device scatter/gather micro-batch (pending-ring flush size)
        if config_or_name.contains("state.backend.tpu.microbatch-size"):
            mb = config_or_name.get_integer(
                "state.backend.tpu.microbatch-size")
            if mb is None or mb <= 0:
                raise ValueError(
                    "state.backend.tpu.microbatch-size must be > 0 "
                    f"(got {mb}); omit it for the built-in default")
            kwargs.setdefault("microbatch", mb)
    elif name is None:
        name = "heap" if config_or_name is None else str(config_or_name)
    name = name.lower()
    if name in _HEAP_NAMES:
        return HeapKeyedStateBackend(key_group_range, max_parallelism)
    if name in _TPU_NAMES:
        return TpuKeyedStateBackend(key_group_range, max_parallelism, **kwargs)
    raise ValueError(
        f"unknown state backend {name!r}; expected one of "
        f"{sorted(_HEAP_NAMES | _TPU_NAMES)}")

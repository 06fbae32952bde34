"""Multi-chip parallelism: key-group sharding over a jax Mesh.

The reference distributes keyed state by assigning key-group ranges to
parallel subtasks and shuffling records over Netty TCP with
credit-based flow control (SURVEY.md §2.2 network stack, §2.8).  Here
the same key-group contract maps onto a device mesh: state shards live
per-device, and the keyBy exchange is a device-side bucketed
all_to_all inside one jitted SPMD program — collectives ride ICI, not
a host network stack.
"""

from flink_tpu.parallel.mesh_log import (
    MeshLogSessionWindows,
    MeshLogSlidingWindows,
    MeshLogTumblingWindows,
    mesh_log_engine_for_assigner,
)
from flink_tpu.parallel.mesh_windows import (
    MeshSlidingWindows,
    MeshTumblingWindows,
)

__all__ = ["MeshTumblingWindows", "MeshSlidingWindows",
           "MeshLogTumblingWindows", "MeshLogSlidingWindows",
           "MeshLogSessionWindows", "mesh_log_engine_for_assigner"]

"""Sliding-window aggregation SPMD over a device mesh.

Runs on any JAX device set — on a TPU pod slice the mesh axis rides
ICI; here it works identically over virtual CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/mesh_sliding_windows.py

Each record is routed to its key's shard once (a bucketed all_to_all
inside the jitted ingest step — the keyBy exchange as an ICI
collective); window fires merge the slide-granularity pane regions
shard-locally and gather only the fired results.
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))  # run from anywhere


import jax
import numpy as np
from jax.sharding import Mesh

from flink_tpu.ops.sketches import HyperLogLogAggregate
from flink_tpu.parallel import MeshSlidingWindows


def main():
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("kg",))
    print(f"mesh: {len(devices)} x {devices[0].platform}")

    rng = np.random.default_rng(7)
    n = 50_000
    pages = rng.integers(0, 100, n).astype(np.uint64)
    ts = np.sort(rng.integers(0, 30_000, n))
    users = rng.integers(0, 5_000, n).astype(np.uint64)

    eng = MeshSlidingWindows(
        HyperLogLogAggregate(precision=10),
        window_size_ms=10_000, slide_ms=2_000, mesh=mesh,
        capacity_per_window_shard=1 << 10)
    CH = 10_000
    for i in range(0, n, CH):
        sl = slice(i, i + CH)
        eng.process_batch(pages[sl], ts[sl],
                          value_hashes=np.asarray(
                              [hash((int(u), 7)) & (2**63 - 1)
                               for u in users[sl]], np.uint64))
        eng.advance_watermark(int(ts[sl][-1]) - 1)
    eng.advance_watermark(10**9)

    print(f"{len(eng.emitted)} (page, window) unique-visitor estimates; "
          "first five:")
    for page, uv, s, e in eng.emitted[:5]:
        print(f"  page={page} uv~{float(uv):.0f} window=[{s}, {e})")


if __name__ == "__main__":
    main()

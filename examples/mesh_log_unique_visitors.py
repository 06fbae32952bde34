"""The flagship log-structured window engine over a device mesh.

The log tier (streaming/log_windows.py) is the framework's fastest
windowed-aggregation engine; this example runs it SHARDED over a mesh
(parallel/mesh_log.py): the keyBy exchange is one jitted
`lax.all_to_all` over pre-bucketed lanes — on a TPU pod slice it
rides ICI — and each shard fires its own C++ log. Works identically
over virtual CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/mesh_log_unique_visitors.py

The same query also runs through SQL: set env.set_mesh and the
columnar TUMBLE plan routes onto the mesh log tier (see
tests/test_mesh_log.py::test_sql_tumble_rides_mesh_and_matches_host).
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))  # run from anywhere


import jax
import numpy as np
from jax.sharding import Mesh

from flink_tpu.ops.sketches import HyperLogLogAggregate
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.sources import (
    BoundedOutOfOrdernessTimestampExtractor,
    CollectSink,
)
from flink_tpu.streaming.windowing import TumblingEventTimeWindows


def main():
    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("kg",))
    print(f"mesh: {len(devices)} device(s) on axis 'kg'")

    rng = np.random.default_rng(0)
    n = 200_000
    events = sorted(
        ((int(p), int(u), int(t)) for p, u, t in zip(
            rng.integers(0, 500, n),        # page id (the key)
            rng.zipf(1.3, n) % 50_000,       # user id (skewed)
            rng.integers(0, 10_000, n))),    # event-time ms
        key=lambda e: e[2])

    env = StreamExecutionEnvironment()
    env.set_mesh(mesh)   # window aggregation shards over the mesh

    agg = HyperLogLogAggregate(precision=12)
    agg.extract_value = lambda rec: rec[1]   # distinct users
    sink = CollectSink()
    stream = env.from_collection(events)
    stream = stream.assign_timestamps_and_watermarks(
        BoundedOutOfOrdernessTimestampExtractor(0, lambda e: e[2]))
    (stream.key_by(lambda e: e[0])
        .window(TumblingEventTimeWindows.of(1000))
        .aggregate(agg, window_function=(
            lambda key, w, vals: [(key, w.start, round(float(vals[0])))]))
        .add_sink(sink))
    env.execute("mesh-log-unique-visitors")

    by_window = {}
    for page, start, uniq in sink.values:
        by_window.setdefault(start, []).append((page, uniq))
    for start in sorted(by_window)[:3]:
        top = sorted(by_window[start], key=lambda kv: -kv[1])[:3]
        print(f"window [{start}, {start + 1000}): "
              + ", ".join(f"page {p}: ~{u} users" for p, u in top))
    print(f"{len(sink.values)} (page, window) results over "
          f"{len(by_window)} windows")


if __name__ == "__main__":
    main()

"""Per-page unique visitors (BASELINE.md config #2 shape): keyBy(page)
→ tumbling window → APPROX COUNT DISTINCT(user).  Integer keys and
HLL put the default `aggregate()` on the log-structured tier
(streaming/log_windows.py): C++ host ingest and sort, and a fire
finish that runs on the host or the device as the link probe says."""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))  # run from anywhere


import numpy as np

from flink_tpu.ops.sketches import HyperLogLogAggregate
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.sources import (
    BoundedOutOfOrdernessTimestampExtractor,
)
from flink_tpu.streaming.windowing import TumblingEventTimeWindows


def main():
    rng = np.random.default_rng(0)
    n = 50_000
    events = sorted(
        zip(rng.integers(0, 20, n).tolist(),        # page
            rng.integers(0, 5_000, n).tolist(),     # user
            rng.integers(0, 10_000, n).tolist()),   # ts (ms)
        key=lambda e: e[2])

    env = StreamExecutionEnvironment.get_execution_environment()
    agg = HyperLogLogAggregate(precision=12)
    agg.extract_value = lambda e: e[1]

    stream = env.from_collection(events)
    stream = stream.assign_timestamps_and_watermarks(
        BoundedOutOfOrdernessTimestampExtractor(0, lambda e: e[2]))
    (stream.key_by(lambda e: e[0])
        .window(TumblingEventTimeWindows.of(1000))
        .aggregate(agg, window_function=lambda page, w, vals: [
            (page, w.start, round(vals[0]))])
        .print_("uniques"))
    env.execute("windowed-hll-unique-visitors")


if __name__ == "__main__":
    main()

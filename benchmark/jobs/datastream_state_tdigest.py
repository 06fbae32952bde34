"""Config #3 with the sketch the source names: ``keyBy().window(sliding
10 s / 1 s).aggregate(t-digest)`` with every live (key, window) digest
in the ``tpu`` keyed-state backend — ``WindowOperator.process_batch``
-> ``TpuKeyedStateBackend.add_batch`` -> ``DeviceAggregatingState`` —
under the device-slot budget the configuration lists, set in the
environment's ``Configuration`` as ``datastream_state_sliding`` sets
it.  Everything but the sketch is that job's.

The window column of a result row is the start of the window's LAST
pane (``window.end - slide``), as in ``datastream_state_sliding``.
"""

import sliding
import tdigest
from flink_tpu.core.config import Configuration
from flink_tpu.ops.tdigest import TDigestAggregate
from flink_tpu.streaming.windowing import SlidingEventTimeWindows

BUDGET_KEY = "state.backend.tpu.max-device-slots"


class ValueDigest(TDigestAggregate):
    """A t-digest over field 1 (the value) of a (key, value) row."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def build(env, source, sink, config):
    for key, value in config["state_backend_config"].items():
        env.config.set(key, value)
    agg = ValueDigest(tuple(config["quantiles"]))
    # the digest is the constructor's own: a configuration that states
    # another is not the one that runs
    stated = (config["delta"], config["buffer"], config["centroids"])
    built = (agg.delta, agg.buffer, agg.centroids)
    if stated != built:
        raise SystemExit(
            f"benchmark: {config['name']} states a t-digest of (delta, "
            f"buffer, centroids) {stated}; TDigestAggregate's defaults "
            f"build {built}")
    slide_ms = config["slide_ms"]

    def emit_row(key, window, vals):
        p50, p99 = vals[0]
        return [(key, window.end - slide_ms, float(p50), float(p99))]

    source.configure(("f0", "f1", "f2"), as_elements=True)
    env.set_state_backend(config["state_backend"])
    windowed = (env.add_source(source, name="events")
                .key_by(0)
                .window(SlidingEventTimeWindows.of(
                    config["window_size_ms"], slide_ms)))
    # pin the route: the scalar WindowOperator over the state backend
    windowed.disable_device_operator()
    windowed.aggregate(agg, window_function=emit_row).add_sink(sink)
    handed = env._make_executor().state_backend
    if not isinstance(handed, Configuration) or \
            handed.get_integer(BUDGET_KEY) != \
            config["state_backend_config"][BUDGET_KEY]:
        raise SystemExit(
            f"benchmark: {config['name']} needs {BUDGET_KEY} to reach the "
            f"state backend through env.execute(); this tree's executor "
            f"is handed {handed!r}, so the backend would run uncapped")
    sliding.mark_counters(source.timeline, config)
    tdigest.mark_counters(source.timeline)


def describe(op):
    """Facts about the route that ran, for an earlier line."""
    state = op.window_state
    table = sum(int(a.size) * a.dtype.itemsize
                for a in state.device_state.values())
    return {"route": "WindowOperator.process_batch -> "
                     f"{type(op.keyed_backend).__name__}.add_batch -> "
                     f"{type(state).__name__}",
            "slots": state.capacity,
            "table_bytes": table,
            "live_slots_after_a_fire": [sliding.noted("t0", "live_slots"),
                                        sliding.noted("end", "live_slots")],
            "budget": state.max_device_slots,
            "evictions": state.evictions,
            "promotions": state.promotions,
            "budget_overruns": state.budget_overruns,
            "in_measured_windows": {
                "rows_per_event": sliding.rows_per_event(),
                **{name: sliding.counted(name)
                   for name in sliding.COUNTERS},
                **{name: tdigest.counted(name)
                   for name in tdigest.COUNTERS},
                "flush_batches": tdigest.counted("flush_batches")}}

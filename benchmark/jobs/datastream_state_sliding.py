"""Config #3 on the north-star route: ``keyBy().window(sliding 10 s /
1 s).aggregate(quantile sketch)`` with every live (key, window) sketch
in the ``tpu`` keyed-state backend — ``WindowOperator.process_batch``
-> ``TpuKeyedStateBackend.add_batch`` -> ``DeviceAggregatingState`` —
under the device-slot budget the configuration lists under
``state_backend_config``, set in the environment's ``Configuration``
as ``datastream_state_spill`` sets it.  Nothing here constructs a
backend.

The window column of a result row is the start of the window's LAST
pane (``window.end - slide``): the harness indexes a result by the 1 s
source period whose watermark fires it.
"""

import sliding
from flink_tpu.core.config import Configuration
from flink_tpu.ops.sketches import QuantileSketchAggregate
from flink_tpu.streaming.windowing import SlidingEventTimeWindows

BUDGET_KEY = "state.backend.tpu.max-device-slots"


class ValueQuantiles(QuantileSketchAggregate):
    """Quantiles over field 1 (the value) of a (key, value) row."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def build(env, source, sink, config):
    for key, value in config["state_backend_config"].items():
        env.config.set(key, value)
    agg = ValueQuantiles(tuple(config["quantiles"]))
    # the sketch is the constructor's own operating point: a
    # configuration that states another is not the one that runs
    stated = (config["relative_accuracy"], config["buckets"])
    built = (round((agg.gamma - 1) / (agg.gamma + 1), 12), agg.buckets)
    if stated != built:
        raise SystemExit(
            f"benchmark: {config['name']} states a sketch of (accuracy, "
            f"buckets) {stated}; QuantileSketchAggregate's defaults "
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
    # pin the route: the scalar WindowOperator over the state backend,
    # not the default aggregate() door (DeviceWindowOperator over the
    # log tier, whose quantile mode runs no device program)
    windowed.disable_device_operator()
    windowed.aggregate(agg, window_function=emit_row).add_sink(sink)
    # a tree whose executors are handed the backend's name alone would
    # run this deployment uncapped: refuse it here, before data moves
    handed = env._make_executor().state_backend
    if not isinstance(handed, Configuration) or \
            handed.get_integer(BUDGET_KEY) != \
            config["state_backend_config"][BUDGET_KEY]:
        raise SystemExit(
            f"benchmark: {config['name']} needs {BUDGET_KEY} to reach the "
            f"state backend through env.execute(); this tree's executor "
            f"is handed {handed!r}, so the backend would run uncapped")
    sliding.mark_counters(source.timeline, config)


def describe(op):
    """Facts about the route that ran, for an earlier line."""
    state = op.window_state
    hist = state.device_state["hist"]
    return {"route": "WindowOperator.process_batch -> "
                     f"{type(op.keyed_backend).__name__}.add_batch -> "
                     f"{type(state).__name__}",
            "slots": state.capacity,
            "table_bytes": int(hist.size) * hist.dtype.itemsize,
            "live_slots_after_a_fire": [sliding.noted("t0", "live_slots"),
                                        sliding.noted("end", "live_slots")],
            "budget": state.max_device_slots,
            "evictions": state.evictions,
            "promotions": state.promotions,
            "budget_overruns": state.budget_overruns,
            # the last fires of a run are its trailing partial
            # windows: what the measured periods did is in the counters
            "in_measured_windows": {
                "rows_per_event": sliding.rows_per_event(),
                **{name: sliding.counted(name)
                   for name in sliding.COUNTERS}}}

"""Config #2 on the north-star route: ``keyBy().window().aggregate()``
with the window state in the keyed-state backend the configuration
names — ``WindowOperator.process_batch`` ->
``TpuKeyedStateBackend.add_batch`` -> ``DeviceAggregatingState`` —
exactly as ``chip_smoke.py`` leg 2 builds it.
"""

from flink_tpu.ops.sketches import HyperLogLogAggregate
from flink_tpu.streaming.windowing import TumblingEventTimeWindows


class UserHll(HyperLogLogAggregate):
    """COUNT DISTINCT over field 1 (the user) of a (key, user) row."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def emit_row(key, window, vals):
    return [(key, window.start, float(vals[0]))]


def build(env, source, sink, config):
    source.configure(("f0", "f1", "f2"), as_elements=True)
    env.set_state_backend(config["state_backend"])
    windowed = (env.add_source(source, name="events")
                .key_by(0)
                .window(TumblingEventTimeWindows.of(config["window_ms"])))
    # pin the route: the scalar WindowOperator over the state backend,
    # not the default aggregate() door (DeviceWindowOperator)
    windowed.disable_device_operator()
    windowed.aggregate(UserHll(config["hll_precision"]),
                       window_function=emit_row).add_sink(sink)


def describe(op):
    """Facts about the route that ran, for an earlier line."""
    state = op.window_state
    regs = state.device_state["regs"]
    return {"route": "WindowOperator.process_batch -> "
                     f"{type(op.keyed_backend).__name__}.add_batch -> "
                     f"{type(state).__name__}",
            "slots": state.capacity,
            "register_bytes": int(regs.size) * regs.dtype.itemsize,
            "evictions": state.evictions}

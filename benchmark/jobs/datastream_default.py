"""Config #2 through the door a user writes:
``key_by(0).window(Tumbling 1 s).aggregate(UserHll, emit_row)`` with no
state backend set and no route pinned, so ``WindowedStream.aggregate``
picks the operator (``DeviceWindowOperator``) and the operator its
engine — ``chip_smoke.py`` leg 3b.
"""

import numpy as np

import loader
from flink_tpu.ops import link_probe
from flink_tpu.streaming.windowing import TumblingEventTimeWindows

# the aggregate and the window function are state_hll_1m's: the two
# configurations differ in the route alone
_pinned = loader.load_module("jobs", "datastream_state")
UserHll, emit_row = _pinned.UserHll, _pinned.emit_row


def build(env, source, sink, config):
    source.configure(("f0", "f1", "f2"), as_elements=True)
    (env.add_source(source, name="events")
     .key_by(0)
     .window(TumblingEventTimeWindows.of(config["window_ms"]))
     .aggregate(UserHll(config["hll_precision"]), window_function=emit_row)
     .add_sink(sink))


def describe(op):
    """Facts about the route that ran, for an earlier line."""
    engine = op.engine
    h2d = link_probe.measure()["h2d_gbps"]
    return {"route": "aggregate() -> "
                     f"{type(op).__name__}.process_batch -> "
                     f"{type(engine).__name__}",
            "finish_tier": getattr(getattr(engine, "mode", None),
                                   "finish_tier", None),
            "h2d_gbps": float(h2d) if np.isfinite(h2d) else str(h2d),
            "columnar_rows": op.columnar_rows,
            "boxed_fallbacks": op.boxed_fallbacks}

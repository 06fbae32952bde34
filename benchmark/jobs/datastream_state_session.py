"""Config #4 on the north-star route: ``keyBy().window(event-time
sessions, gap 10 s).aggregate(Count-Min sketch)`` over a replayable
log, every open session's sketch in the ``tpu`` keyed-state backend —
``WindowOperator.process_batch`` (the batched MergingWindowSet) ->
``TpuKeyedStateBackend.add_batch`` under each row's state window ->
``DeviceAggregatingState`` — under the device-slot budget the
configuration lists under ``state_backend_config``, set in the
environment's ``Configuration`` as ``datastream_state_sliding`` sets
it.  Nothing here constructs a backend.

The window column of a result row (the configuration's
``window_start``) is the start of the 1 s source PERIOD the session's
last millisecond falls into, ``(window.end - 1) // window_ms *
window_ms``: the period whose closing watermark fires the session, by
which the harness indexes a result.  The session's own bounds are the
two columns after it.
"""

import session
from flink_tpu.core.config import Configuration
from flink_tpu.ops.sketches import CountMinSketchAggregate
from flink_tpu.streaming.windowing import EventTimeSessionWindows

BUDGET_KEY = "state.backend.tpu.max-device-slots"


class ItemCounts(CountMinSketchAggregate):
    """A count of one per event over field 1 (the item) of a
    (key, item) row."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def build(env, source, sink, config):
    for key, value in config["state_backend_config"].items():
        env.config.set(key, value)
    agg = ItemCounts(config["depth"], config["width"], unit_weights=True,
                     queries=source.watch_items)
    if len(source.watch_items) != config["watch_count"]:
        raise SystemExit(
            f"benchmark: {config['name']} tracks {config['watch_count']} "
            f"items; the source names {len(source.watch_items)}")
    window_ms = config["window_ms"]

    def emit_row(key, window, vals):
        row = vals[0].tolist()  # the total, then the tracked items'
        return [(key, (window.end - 1) // window_ms * window_ms,
                 window.start, window.end, *row)]

    env.set_state_backend(config["state_backend"])
    windowed = (env.add_source(source, name="events")
                .key_by(0)
                .window(EventTimeSessionWindows.with_gap(config["gap_ms"])))
    # pin the route: the scalar WindowOperator over the state backend,
    # not the default aggregate() door (a log-structured session engine
    # on the host, which runs no device program and keeps no sketch)
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
    session.note_operators(env)
    session.mark_counters(source.timeline)


def describe(op):
    """Facts about the route that ran, for an earlier line."""
    state = op.window_state
    table = state.device_state["table"]
    return {"route": "WindowOperator.process_batch -> "
                     f"{type(op.keyed_backend).__name__}.add_batch -> "
                     f"{type(state).__name__}",
            "slots": state.capacity,
            "table_bytes": int(table.size) * table.dtype.itemsize,
            "live_sessions_after_a_fire": [
                session.noted("t0", "live_slots"),
                session.noted("end", "live_slots")],
            "budget": state.max_device_slots,
            "evictions": state.evictions,
            "promotions": state.promotions,
            "budget_overruns": state.budget_overruns,
            "boxed_fallbacks": op.boxed_fallbacks,
            # the last fire of a run closes every session still open:
            # what the measured periods did is in the counters
            "in_measured_windows": {
                name: session.counted(name)
                for name in (*session.OPERATOR_COUNTERS,
                             *session.COUNTERS, "ingest_batches")}}

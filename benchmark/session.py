"""What the session Count-Min cell's metrics need besides the phases:
the program's own counters at ``t0`` and at the end of the measured
window (the state backend's, and the window operator's session
counters), the bytes its two device programs must move, and their time
on the device from the traced slice (``sliding``'s reading of the
trace's "XLA Modules" line).

A program without a counter or a program named here gives ``None``
from every function, and nothing raises.
"""

from __future__ import annotations

import sliding

#: ``STATE_STATS`` fields noted on the timeline: rows through the
#: backend's batch doors (``add_batch`` AND the fires' ``get_batch``),
#: rows the fires asked ``state.result`` for and rows dispatched, rows
#: a per-key probe resolved (on the batched session path a merge's
#: target, once, and no other), source slots the batched merge folded,
#: and what the spill tier did
#: (nothing, in this cell)
COUNTERS = ("batch_rows", "batch_calls", "result_rows",
            "result_padded_rows", "per_key_probe_rows", "bulk_probe_rows",
            "merged_rows", "hash_column_rows", "hash_per_value_rows",
            "evicted_rows", "promoted_rows", "budget_overruns")
#: the window operator's own: rows that opened a session, rows that
#: joined one, windows a merge swallowed, rows dropped as late
OPERATOR_COUNTERS = ("sessions_opened", "sessions_extended",
                     "session_windows_merged", "num_late_records_dropped",
                     "timers_swept", "timer_runs")
UPDATE_PROGRAM = "jit_state_update"
RESULT_PROGRAM = "jit_state_result"

_marks = {}
#: the window operators the job built (the linter dry-builds one too)
_operators = []


def note_operators(env, class_name="WindowOperator"):
    """Keep every operator of the named class the executor builds for
    this job, as ``meters.capture_operators`` does for the harness."""
    for node in env.get_stream_graph().nodes.values():
        def factory(inner=node.operator_factory):
            op = inner()
            if type(op).__name__ == class_name:
                _operators.append(op)
            return op
        node.operator_factory = factory


def counters():
    """The ``COUNTERS``, the working operator's ``OPERATOR_COUNTERS``,
    ``ingest_batches`` (entries of the ``window.ingest`` phase) and
    ``live_slots`` (a gauge: the sessions live on the device, just
    after a fire)."""
    from flink_tpu.runtime.tracing import get_tracer
    from flink_tpu.state import stats
    noted = {name: getattr(stats.STATE_STATS, name) for name in COUNTERS
             if hasattr(stats.STATE_STATS, name)}
    for op in _operators:
        if op.columnar_rows or op.boxed_rows:
            noted.update({name: getattr(op, name)
                          for name in OPERATOR_COUNTERS
                          if hasattr(op, name)})
    ingest = get_tracer().stats().get("window.ingest")
    if ingest is not None:
        noted["ingest_batches"] = ingest["count"]
    noted["live_slots"] = stats.device_state_summary()["slots_in_use"]
    return noted


def mark_counters(timeline):
    """Note the counters when the timeline reaches ``t0`` and when the
    measured window ends."""
    timeline.on_t0.append(lambda: _marks.__setitem__("t0", counters()))
    timeline.on_end.append(lambda: _marks.__setitem__("end", counters()))


def noted(mark, name):
    """Counter ``name`` as noted at ``mark`` ("t0" or "end")."""
    return (_marks.get(mark) or {}).get(name)


def counted(name):
    """Growth of counter ``name`` over the measured window."""
    t0, end = _marks.get("t0"), _marks.get("end")
    if not t0 or not end or name not in t0 or name not in end:
        return None
    return end[name] - t0[name]


# ---- bytes the device programs must move through HBM ------------------

def update_row_bytes(config):
    """One flushed row of ``state.update``: ``depth`` int32 cells of
    the table read and written, the slot's int32 total read and
    written, and the row's own int32 slot and two uint32 hash lanes
    read."""
    return config["depth"] * (4 + 4) + 8 + 12


def result_row_bytes(config):
    """One fired slot of ``state.result``: ``depth`` int32 cells
    gathered for each of the ``watch_count`` tracked items, the int32
    total gathered, and 1 + ``watch_count`` int32 results written."""
    watch = config["watch_count"]
    return config["depth"] * watch * 4 + 4 + 4 * (1 + watch)


def slot_bytes(config):
    """One slot's accumulator: the ``depth`` x ``width`` int32 table
    and the int32 total."""
    return 4 * config["depth"] * config["width"] + 4


def result_rows(run):
    """Rows of one ``state.result`` dispatch, the mean over the
    measured window: ``get_batch`` gathers a fire's slots in tiles of
    the power of two whose rows fit the program's scratch (a smaller
    fire in one dispatch of the power of two above it) and counts the
    rows it dispatched, so the fires' rows ÷ (rows dispatched ÷ rows
    of a dispatch).  The padding is not work the fire needs."""
    from flink_tpu.state import tpu_backend
    scratch = getattr(tpu_backend, "RESULT_SCRATCH_BYTES", None)
    rows, padded = counted("result_rows"), counted("result_padded_rows")
    fires = run["events"] // run["config"]["events_per_window"]
    if scratch is None or not rows or not padded or not fires:
        return None
    tile = 1 << ((scratch // slot_bytes(run["config"])).bit_length() - 1)
    width = min(1 << (-(-rows // fires) - 1).bit_length(), tile)
    return rows * width / padded


# rows of one ``state.update`` dispatch and a program's share of the
# HBM roofline are the sliding cell's, one backend and one trace format
update_rows = sliding.update_rows
roofline_share = sliding.roofline_share

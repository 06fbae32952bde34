"""What the sliding quantile cell's metrics need besides the phases:
the program's own state counters at ``t0`` and at the end of the
measured window, the bytes its two device programs must move, and
their time on the device from the traced slice (``spill``'s reading
of the trace's "XLA Modules" line).

A program without a counter or a program named here gives ``None``
from every function, and nothing raises.
"""

from __future__ import annotations

import spill

#: ``STATE_STATS`` fields noted on the timeline: rows through the
#: backend's batch doors (``add_batch`` AND the fires' ``get_batch``),
#: rows the fires asked ``state.result`` for and rows dispatched, and
#: what the spill tier did (nothing, in this cell)
COUNTERS = ("batch_rows", "result_rows", "result_padded_rows",
            "evicted_rows", "promoted_rows", "budget_overruns")
UPDATE_PROGRAM = "jit_state_update"
RESULT_PROGRAM = "jit_state_result"
#: a row of ``state.update``'s input: int32 slot, float32 value, bool
#: mask, read; one int32 cell of the table read and written
UPDATE_ROW_BYTES = 4 + 4 + 1 + 2 * 4

_marks = {}


def counters():
    """The ``COUNTERS`` and, beside them, ``ingest_batches`` (batches
    the window operator took in: entries of the ``window.ingest``
    phase) and ``live_slots`` (a gauge: the (key, window) slots live
    on the device just after a fire)."""
    from flink_tpu.runtime.tracing import get_tracer
    from flink_tpu.state import stats
    noted = {name: getattr(stats.STATE_STATS, name) for name in COUNTERS
             if hasattr(stats.STATE_STATS, name)}
    ingest = get_tracer().stats().get("window.ingest")
    if ingest is not None:
        noted["ingest_batches"] = ingest["count"]
    noted["live_slots"] = stats.device_state_summary()["slots_in_use"]
    return noted


def mark_counters(timeline, config):
    """Note the counters when the timeline reaches ``t0`` and when the
    measured window ends."""
    _marks["batch_rows"] = config["batch_rows"]
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


def rows_per_event():
    """State rows written per event taken in over the measured window:
    rows through ``add_batch`` (the batch doors' rows less the fires'
    reads) ÷ events (batches taken in x the batch's rows: the marks
    fall between two batches, not on a period's edge)."""
    doors, reads = counted("batch_rows"), counted("result_rows")
    batches = counted("ingest_batches")
    if doors is None or reads is None or not batches:
        return None
    return (doors - reads) / (batches * _marks["batch_rows"])


# ---- bytes the device programs must move through HBM ------------------

def slot_bytes(config):
    """One slot's accumulator: ``buckets`` int32 counts."""
    return 4 * config["buckets"]


def update_rows(run):
    """Rows of one ``state.update`` dispatch: the mean of the flushes
    of the measured window, by the program's own counters.  Padding
    rows are not work the update needs, so they are not counted."""
    rows = run["end"]["flush_rows"] - run["t0"]["flush_rows"]
    batches = run["end"]["flush_batches"] - run["t0"]["flush_batches"]
    return rows / batches if batches else None


def result_rows(run):
    """Rows of one ``state.result`` dispatch: ``get_batch`` gathers a
    fire's slots in tiles of the power of two whose rows fit the
    program's scratch, a smaller fire in one dispatch of the power of
    two above it."""
    from flink_tpu.state import tpu_backend
    scratch = getattr(tpu_backend, "RESULT_SCRATCH_BYTES", None)
    rows = counted("result_rows")
    fires = run["events"] // run["config"]["events_per_window"]
    if scratch is None or not rows or not fires:
        return None
    tile = 1 << ((scratch // slot_bytes(run["config"])).bit_length() - 1)
    return min(1 << (-(-rows // fires) - 1).bit_length(), tile)


def result_bytes(rows, config):
    """A gather of whole slots and two float32 quantiles out."""
    return rows * (slot_bytes(config) + 4 * len(config["quantiles"]))


def roofline_share(run, program, bytes_per_dispatch):
    """Bytes the program's dispatches of the slice must move ÷ their
    device time ÷ the device's HBM bandwidth, in %."""
    import jax

    import peaks
    if bytes_per_dispatch is None:
        return None
    timed = spill.program_seconds(run, program)
    if timed is None:
        return None
    dispatches, seconds = timed
    peak = peaks.for_device(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * dispatches * bytes_per_dispatch / seconds / peak

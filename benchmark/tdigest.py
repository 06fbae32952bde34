"""What the t-digest cell's metrics need besides ``sliding``'s: the
aggregate's own counts (``flink_tpu/state/stats.py`` ``agg_counters``)
at ``t0`` and at the end of the measured window, and the bytes its two
device programs must move.  The counts are read from the device at the
two marks, outside the timed window.

A program without the t-digest or its counts gives ``None`` from every
function, and nothing raises.
"""

from __future__ import annotations

import sliding

#: the aggregate's counts: digests compressed in ingest (one per
#: compress of one digest), rounds of compress dispatched, and the
#: entries (centroids and buffered values) of the digests finished at
#: fires (those digests are ``sliding``'s ``result_rows``)
COUNTERS = ("tdigest_ingest_compresses", "tdigest_compress_rounds",
            "tdigest_fired_entries")
#: a row of ``state.update``'s input: int32 slot and float32 value
#: read, one float32 buffer cell written
UPDATE_ROW_BYTES = 4 + 4 + 4
#: a fired row's output: two float32 quantiles
RESULT_OUT_BYTES = 8

_marks = {}


def counters():
    """The aggregate's counts and the flushes, or None where the
    program keeps no such counts."""
    from flink_tpu.state import stats
    if not hasattr(stats, "agg_counters"):
        return None
    noted = stats.agg_counters()
    noted["flush_batches"] = stats.STATE_STATS.flush_batches
    noted["flush_rows"] = stats.STATE_STATS.flush_rows
    return noted


def mark_counters(timeline):
    timeline.on_t0.append(lambda: _marks.__setitem__("t0", counters()))
    timeline.on_end.append(lambda: _marks.__setitem__("end", counters()))


def counted(name):
    """Growth of count ``name`` over the measured window."""
    t0, end = _marks.get("t0"), _marks.get("end")
    if not t0 or not end:
        return None
    return end.get(name, 0) - t0.get(name, 0)


def slot_bytes(config):
    """One digest: B float32 buffer cells, C (float32 mean, int32
    weight) centroids, and int32 fill and count, float32 min and max."""
    return 4 * config["buffer"] + 8 * config["centroids"] + 16


def compress_write_bytes(config):
    """What a compress in ``state.update`` writes of a digest: its
    centroids (float32 mean, int32 weight) and the 16 B of fill, count,
    min and max."""
    return 8 * config["centroids"] + 16


def update_bytes(config):
    """Least bytes of one ``state.update`` dispatch, the mean of the
    measured window's flushes: its rows, and each digest it compressed
    read whole and its centroids and scalars written."""
    batches = counted("flush_batches")
    rows = counted("flush_rows")
    compressed = counted("tdigest_ingest_compresses")
    if not batches or rows is None or compressed is None:
        return None
    return (rows * UPDATE_ROW_BYTES + compressed * (
        slot_bytes(config) + compress_write_bytes(config))) / batches


def result_bytes(run):
    """Least bytes of one ``state.result`` dispatch: the fired digests
    it finishes, each read whole, two quantiles out.  ``get_batch``
    dispatches a fire in tiles of the power of two whose rows fit the
    program's scratch."""
    from flink_tpu.state import tpu_backend
    scratch = getattr(tpu_backend, "RESULT_SCRATCH_BYTES", None)
    rows = sliding.counted("result_rows")
    fires = run["events"] // run["config"]["events_per_window"]
    if scratch is None or not rows or not fires:
        return None
    tile = 1 << ((scratch // slot_bytes(run["config"])).bit_length() - 1)
    per_fire = rows / fires
    dispatches = -(-per_fire // tile)
    return per_fire / dispatches * (slot_bytes(run["config"])
                                    + RESULT_OUT_BYTES)


def compress_per_event():
    """Digests compressed in ingest ÷ events taken in, over the
    measured window (events: ``sliding``'s batches x their rows)."""
    compressed = counted("tdigest_ingest_compresses")
    batches = sliding.counted("ingest_batches")
    if compressed is None or not batches:
        return None
    return compressed / (batches * sliding._marks["batch_rows"])

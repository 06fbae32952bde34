"""Closed-loop replay: the source hands over the next batch as soon as
the executor's loop asks for one, so under backpressure the job runs
at the highest rate it sustains.

``make(config, traffic, seed, seconds)`` draws the run's events from
the seed and returns the source; the harness finds this file by the
traffic mix's ``source`` name.  What a source owes the harness:

    source.timeline       the run's :class:`timeline.Timeline`
    source.events_emitted how many events it handed over, in all
    source.emitted()      one :class:`Emitted` per event-time window
                          it emitted into, for the reference
"""

from __future__ import annotations

import time
from typing import Callable, NamedTuple

import numpy as np

import loader
from flink_tpu.streaming.columnar import ColumnarSource
from flink_tpu.streaming.elements import MAX_WATERMARK, RecordBatch, Watermark
from timeline import Timeline

#: windows emitted before ``t0``.  They warm SHAPES, not contents: the
#: first grows the state to its working capacity and fires once, the
#: second runs at that capacity.  A program whose shapes follow the
#: data (one per fired-key count, say) compiles again in the measured
#: window, as it does for every user.
WARMUP_WINDOWS = 2
#: a traced run profiles the period of this window
PROFILE_WINDOW = WARMUP_WINDOWS + 2
#: events drawn from the seed, and the most windows they are cut into.
#: A route emits fresh windows until it has taken in this many events:
#: 128 windows of state_hll_1m (six times what a run holds today),
#: 8 of sql_acd_1m (about a tenth: a window is 64 MiB of columns)
POOL_EVENTS = 1 << 25
POOL_WINDOWS_AT_MOST = 128


class Emitted(NamedTuple):
    #: the window covers event time [window * window_ms, + window_ms)
    window: int
    #: windows with the same id carried the same rows
    data_id: tuple
    #: () -> the input columns (keys, users) the window carried
    columns: Callable[[], tuple]


def make(config, traffic, seed, seconds, clock=time.perf_counter):
    """The source of one run.  ``POOL_EVENTS`` events are drawn from
    the seed, keys by the mix's distribution and users uniform below
    ``2 ** user_bits``, and cut into windows of ``events_per_window``;
    window ``w`` carries pool entry ``w mod P``."""
    epw, batch = config["events_per_window"], config["batch_rows"]
    if epw % batch:
        raise loader.CellError(
            f"events_per_window {epw} is not a whole number of "
            f"{batch}-row batches")
    pool = min(max(1, POOL_EVENTS // epw), POOL_WINDOWS_AT_MOST)
    rng = np.random.default_rng(seed)
    generator = loader.load_module("generators", traffic["key_distribution"])
    keys = generator.draw(rng, pool * epw, config["key_space"],
                          traffic["params"])
    keys = np.ascontiguousarray(keys, np.int64).reshape(pool, epw)
    users = rng.integers(0, 1 << config["user_bits"], (pool, epw),
                         dtype=np.int64)
    timeline = Timeline(WARMUP_WINDOWS, seconds, PROFILE_WINDOW, clock)
    return ReplaySource(keys, users, epw, batch, config["window_ms"],
                        timeline)


class ReplaySource(ColumnarSource):
    """Window ``w`` carries entry ``w mod P`` of a pool of P windows
    drawn from the seed; a run that outlasts its pool replays it, and
    says so (``replayed_windows``).  Every event-time window holds
    exactly ``events_per_window`` events, time-sorted, in
    ``batch_rows``-row batches with a watermark after each, as
    ``ColumnarSource`` emits them.

    It emits the warm-up windows, lets the timeline note
    ``t0`` when the last of them has fired, emits whole windows until
    ``seconds`` have passed, finishes the window it is in, emits the
    first batch of the next one (whose watermark closes
    the last measured window the way every other window was closed),
    waits until that window's results are at the sink and ends the
    stream."""

    def __init__(self, pool_keys, pool_users, events_per_window,
                 batch_rows, window_ms, timeline):
        self.pool_keys = pool_keys
        self.pool_users = pool_users
        self.events_per_window = events_per_window
        self.batch_rows = batch_rows
        self.window_ms = window_ms
        self.timeline = timeline
        #: event-time offset of every position in a window
        self.offsets = (np.arange(events_per_window, dtype=np.int64)
                        * window_ms) // events_per_window
        self._w = 0
        self._lo = 0
        self._closing = False
        self._ended = False
        #: window -> rows emitted into it (one dict shared with every
        #: clone: ``__deepcopy__`` copies ``__dict__`` by reference)
        self._rows_by_window = {}
        # what the system may look at on a ColumnarSource
        super().__init__({"f0": pool_keys[0], "f1": pool_users[0],
                          "f2": self.offsets},
                         rowtime="f2", chunk=batch_rows)
        self.configure(("f0", "f1", "f2"), as_elements=True)

    def configure(self, names, as_elements):
        """The job's conventions: column names (key, user, rowtime),
        and whether a batch travels as a stream ELEMENT (DataStream
        pipeline) or as one record's VALUE (the SQL tier)."""
        self.names = tuple(names)
        self.as_elements = as_elements
        self.cols = {names[0]: self.pool_keys[0],
                     names[1]: self.pool_users[0], names[2]: self.offsets}
        self.rowtime = names[2]

    def __deepcopy__(self, memo):
        clone = super().__deepcopy__(memo)
        clone._w, clone._lo = 0, 0
        clone._closing = clone._ended = False
        self._rows_by_window.clear()
        return clone

    @property
    def events_emitted(self):
        return sum(self._rows_by_window.values())

    def emitted(self):
        pool = len(self.pool_keys)
        out = []
        for w, rows in sorted(self._rows_by_window.items()):
            entry = w % pool
            out.append(Emitted(
                w, (entry, rows),
                lambda entry=entry, rows=rows: (
                    self.pool_keys[entry][:rows],
                    self.pool_users[entry][:rows])))
        return out

    def replayed_windows(self):
        """Windows that carried rows an earlier window had carried."""
        return max(0, len(self._rows_by_window) - len(self.pool_keys))

    def emit_step(self, ctx, max_records):
        tl = self.timeline
        if self._ended or not self._running:
            return False
        if self._closing:
            return self._try_end(ctx)
        tl.step_begins()
        w, lo = self._w, self._lo
        if lo == 0:
            tl.window_starts(w)
        t_in, nested = tl.clock(), tl.nested_s()
        hi = lo + self.batch_rows
        entry = w % len(self.pool_keys)
        ts = self.offsets[lo:hi] + w * self.window_ms
        kn, un, tn = self.names
        batch = RecordBatch({kn: self.pool_keys[entry][lo:hi],
                             un: self.pool_users[entry][lo:hi],
                             tn: ts}, ts)
        self._rows_by_window[w] = hi
        if self.as_elements:
            ctx.collect_batch(batch)
        else:
            ctx.collect(batch)
        if lo == 0 and w > 0:
            tl.closes[w - 1] = tl.clock()
        ctx.emit_watermark(Watermark(int(ts[-1]) - 1))
        if tl.t0 is not None:
            tl.source_s += (tl.clock() - t_in) - (tl.nested_s() - nested)
        if lo == 0 and tl.last_measured is not None:
            self._closing = True
            return self._try_end(ctx)
        if hi == self.events_per_window:
            self._w, self._lo = w + 1, 0
        else:
            self._lo = hi
        return True

    def _try_end(self, ctx):
        """The closing batch is out.  The loop is cooperative, so once
        the sink is receiving the last measured window, that window's
        fire has run to its end: the measured window is over, and the
        stream ends (which fires the one-batch window after it)."""
        tl = self.timeline
        if tl.current_window != tl.last_measured:
            return True
        tl.end_measured()
        ctx.emit_watermark(MAX_WATERMARK)
        self._ended = True
        return False

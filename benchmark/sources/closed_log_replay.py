"""Closed-loop replay through the program's own log connector: the
source of a run IS a ``ReplayableLogSource`` over a
``ColumnarPartitionedLog``, and what this file adds around it is the
producer and the harness's clocks.

One step of the source is one PERIOD (the configuration's
``window_ms``) of event time: the producer appends the period's
``events_per_window`` rows to the log, row ``i`` to partition
``i mod partitions`` (so the broker never runs ahead of the consumer),
then the connector's own ``emit_step`` reads one chunk from every
partition, hands each over as one ``RecordBatch`` through
``collect_batch`` and emits ONE watermark, ``watermark_lag_ms`` behind
the newest timestamp.  With a lag of one period that watermark closes
every session whose last event plus the gap fell into the period
BEFORE the one just read: the job emits that period's start as its
window column, so the harness's clocks index fires by period as they
do for a tumbling job.  The watermark travels in a step of its own
(:class:`_Clocks`): the executor's exchange queues what one step
emits, so a watermark that left with its period's chunks would wait
behind all four of them; kept back until the loop asks the source
again, it reaches the window operator at once, ``closes[w - 1]`` is
stamped as it goes, and ``fire_p50_ms`` holds no ingest in this cell.

Timestamps are spaced evenly over ``[1, window_ms)`` of a period:
never on its edge, so that no session's end is a multiple of
``window_ms`` and the program's fire periods
(``period_history``: ``newest_window_end // window_ms``) number every
fire alike.

``make(config, traffic, seed, seconds)`` draws the run's events from
the seed and returns the source; what a source owes the harness:

    source.timeline       the run's :class:`timeline.Timeline`
    source.events_emitted how many events it handed over, in all
    source.emitted()      one :class:`Emitted` per period, for the
                          reference: ``columns()`` gives (keys, items,
                          timestamps, tracked items), rows in the
                          order the connector handed them over
    source.watch_items    the tracked items, for the job
"""

from __future__ import annotations

import time
from typing import Callable, NamedTuple

import numpy as np

import loader
from flink_tpu.connectors.log_connector import ReplayableLogSource
from flink_tpu.connectors.partitioned_log import ColumnarPartitionedLog
from flink_tpu.streaming.elements import MAX_WATERMARK
from timeline import Timeline

#: periods emitted before ``t0``, beyond the gap.  A session closes
#: one gap after its last event, so the first fires come after
#: ``gap_ms / window_ms`` periods; two periods later the state table
#: is at its working capacity and the update, result, clear and merge
#: programs are at their last shapes, so nothing compiles after ``t0``
WARMUP_PERIODS_BEYOND_THE_GAP = 2
#: a traced run profiles this period after the warm-up's last, as
#: ``closed_replay`` does
PROFILE_PERIOD_AFTER_WARMUP = 2
#: periods drawn from the seed: more than a run holds
POOL_PERIODS = 256


class Emitted(NamedTuple):
    #: the period covers event time [window * window_ms, + window_ms)
    window: int
    #: periods with the same id carried the same rows
    data_id: tuple
    #: () -> (keys, items, timestamps, tracked items) of the period
    columns: Callable[[], tuple]


def make(config, traffic, seed, seconds, clock=time.perf_counter):
    """The source of one run: ``POOL_PERIODS`` periods of
    ``events_per_window`` events drawn from the seed, keys and items
    by the mix's distribution, two draws, two permutations."""
    epw, parts = config["events_per_window"], config["partitions"]
    if epw % parts or config["batch_rows"] != epw // parts:
        raise loader.CellError(
            f"events_per_window {epw} is not {parts} partition chunks "
            f"of batch_rows {config['batch_rows']}")
    if config["watermark_lag_ms"] != config["window_ms"]:
        raise loader.CellError(
            f"the harness's clocks index a fire by window_ms "
            f"{config['window_ms']}: the watermark has to lag by one "
            f"period, not by {config['watermark_lag_ms']} ms")
    params = traffic["params"]
    rng = np.random.default_rng(seed)
    generator = loader.load_module("generators", traffic["key_distribution"])
    periods = min(POOL_PERIODS, config.get("pool_periods", POOL_PERIODS))
    keys = generator.draw(rng, periods * epw, config["key_space"],
                          {"exponent": params["exponent"]})
    items = generator.draw(rng, periods * epw, config["item_space"],
                           {"exponent": params["item_exponent"]})
    # the tracked items: the most frequent of the pool, ties to the
    # lower id
    counts = np.bincount(items, minlength=config["item_space"])
    watch = np.lexsort((np.arange(len(counts)), -counts))[
        :config["watch_count"]]
    # row i of a period goes to partition i mod parts: [period,
    # partition, row of the chunk], each chunk contiguous
    def by_partition(column):
        return np.ascontiguousarray(
            column.astype(np.int64).reshape(periods, epw // parts, parts)
            .transpose(0, 2, 1))
    warmup = config["gap_ms"] // config["window_ms"] \
        + WARMUP_PERIODS_BEYOND_THE_GAP
    timeline = Timeline(warmup, seconds,
                        warmup + PROFILE_PERIOD_AFTER_WARMUP, clock)
    return LogReplaySource(by_partition(keys), by_partition(items),
                           tuple(int(i) for i in watch), config, timeline)


class _Clocks:
    """The connector's context, with the harness's clock on the one
    watermark of a step.  It keeps that watermark back until the
    source's next step, when the exchange has handed the period's
    chunks to the window operator (a chunk is larger than the
    channel's capacity, so the loop asks the source again only once
    the last of them is taken in): :meth:`release` then notes
    ``closes[w - 1]`` and hands the watermark on, so a fire's clock
    starts at the watermark and holds none of the period's ingest.
    Everything else passes through."""

    def __init__(self, ctx, timeline, period):
        self._ctx = ctx
        self._timeline = timeline
        self._period = period
        self.held = None

    def emit_watermark(self, watermark):
        self.held = watermark

    def release(self):
        if self.held is not None:
            if self._period > 0:
                self._timeline.closes.setdefault(self._period - 1,
                                                 self._timeline.clock())
            self._ctx.emit_watermark(self.held)
            self.held = None

    def __getattr__(self, name):
        return getattr(self._ctx, name)


class LogReplaySource(ReplayableLogSource):
    """Period ``w`` carries entry ``w mod P`` of a pool of P periods
    drawn from the seed (a run that outlasts its pool replays it).

    It emits the warm-up periods, lets the timeline note ``t0`` when
    the last of them has fired, emits whole periods until ``seconds``
    have passed, emits one more (whose watermark closes the last
    measured period the way every other was closed), waits until that
    period's results are at the sink and ends the stream, as
    ``closed_replay.ReplaySource`` does."""

    #: eligibility marker read by analysis.columnar_eligibility
    emits_batches = True

    def __init__(self, pool_keys, pool_items, watch_items, config, timeline):
        super().__init__(ColumnarPartitionedLog(config["partitions"]),
                         watermark_lag_ms=config["watermark_lag_ms"],
                         batch_per_partition=config["batch_rows"])
        self.pool_keys = pool_keys
        self.pool_items = pool_items
        self.watch_items = watch_items
        self.events_per_window = config["events_per_window"]
        self.window_ms = config["window_ms"]
        self.timeline = timeline
        epw, parts = self.events_per_window, config["partitions"]
        #: event-time offset of every row of a period, by partition:
        #: evenly over [1, window_ms)
        spaced = 1 + (np.arange(epw, dtype=np.int64)
                      * (self.window_ms - 1)) // epw
        self.time_offsets = np.ascontiguousarray(
            spaced.reshape(epw // parts, parts).T)
        self._w = 0
        #: the context of a period whose watermark is still to go out
        self._clocks = None
        self._closing = False
        self._ended = False
        #: period -> rows emitted into it (one dict shared with every
        #: clone, as ``closed_replay``'s)
        self._rows_by_window = {}

    def __deepcopy__(self, memo):
        clone = type(self).__new__(type(self))
        clone.__dict__.update(self.__dict__)
        clone.offsets = {}
        clone._w = 0
        clone._clocks = None
        clone._closing = clone._ended = False
        clone._max_ts = clone._last_wm = None
        clone._my_partitions = None
        clone._pending_offset_commits = []
        self._rows_by_window.clear()
        return clone

    def open(self, configuration):
        super().open(configuration)
        # a consumer reads what its own producer appends: from the
        # head of the log, wherever an earlier attempt left it
        self.offsets = {p: self.log.end_offset(p)
                        for p in self._my_partitions}

    @property
    def events_emitted(self):
        return sum(self._rows_by_window.values())

    def emitted(self):
        pool = len(self.pool_keys)
        out = []
        for w in sorted(self._rows_by_window):
            entry = w % pool
            out.append(Emitted(
                w, (entry, self._rows_by_window[w]),
                lambda entry=entry, w=w: (
                    self.pool_keys[entry].reshape(-1),
                    self.pool_items[entry].reshape(-1),
                    (self.time_offsets + w * self.window_ms).reshape(-1),
                    self.watch_items)))
        return out

    def _produce(self, w):
        """The producer: period ``w``'s rows, row ``i`` to partition
        ``i mod partitions``."""
        entry = w % len(self.pool_keys)
        for p in range(self.log.num_partitions):
            self.log.append_columns(
                p, {"f0": self.pool_keys[entry][p],
                    "f1": self.pool_items[entry][p]},
                self.time_offsets[p] + w * self.window_ms)

    def emit_step(self, ctx, max_records):
        """A period takes two steps: the producer's append and the
        connector's own ``emit_step`` (its chunks go out, its
        watermark is kept back), then the watermark."""
        tl = self.timeline
        if self._ended:
            return False
        if self._closing:
            return self._try_end(ctx)
        if self._clocks is not None:
            t_in, nested = tl.clock(), tl.nested_s()
            self._clocks.release()
            self._clocks = None
            self._w += 1
            if tl.last_measured is not None:
                self._closing = True
        else:
            tl.step_begins()
            w = self._w
            tl.window_starts(w)
            # (t0's hooks reset the nested timers in step_begins, the
            # profiler starts in window_starts)
            t_in, nested = tl.clock(), tl.nested_s()
            self._produce(w)
            self._clocks = _Clocks(ctx, tl, w)
            if not super().emit_step(self._clocks, self.events_per_window):
                self._ended = True
            self._rows_by_window[w] = self.events_per_window
        if tl.t0 is not None:
            tl.source_s += (tl.clock() - t_in) - (tl.nested_s() - nested)
        if self._closing:
            return self._try_end(ctx)
        return not self._ended

    def _try_end(self, ctx):
        """The closing period is out.  The loop is cooperative, so once
        the sink is receiving the last measured period, that period's
        fire has run to its end: the measured window is over, and the
        stream ends (which fires the sessions still open)."""
        tl = self.timeline
        if tl.current_window != tl.last_measured:
            return True
        tl.end_measured()
        ctx.emit_watermark(MAX_WATERMARK)
        self._ended = True
        return False

"""The benchmark's clocks: the host-clock notes of one run, and the
sink that notes when results arrive.

A source (``sources/<name>.py``) and this sink run on the
LocalExecutor's one cooperative loop, in the process that holds the
chip.  What they note goes into one :class:`Timeline`, shared by
reference (``ColumnarSource.__deepcopy__`` copies ``__dict__`` by
reference, and sinks are not cloned).

A job emits its results as tuples one at a time, or as ``RecordBatch``
chunks with the columns in the same order; column ``window_column``
holds the start of the window a row belongs to.
"""

from __future__ import annotations

import time

import numpy as np

from flink_tpu.streaming.elements import RecordBatch
from flink_tpu.streaming.sources import SinkFunction


class Timeline:
    """Host-clock notes of one run.  Window ``w`` covers event time
    ``[w * window_ms, (w + 1) * window_ms)``; windows
    ``warmup_windows .. last_measured`` are the measured ones.  The
    measured interval runs from the last result row of the last
    warm-up window to the last result row of the last measured
    window: n whole periods, each with one window's worth of events
    taken in and one fire."""

    def __init__(self, warmup_windows, seconds, profile_window=None,
                 clock=time.perf_counter):
        self.clock = clock
        self.warmup_windows = warmup_windows
        self.seconds = seconds
        #: host time at which the last warm-up window's last result
        #: row reached the sink
        self.t0 = None
        #: the sink's newest arrival (the sink puts its own reader here)
        self.newest_arrival = clock
        self.last_measured = None
        #: window -> emission of the first watermark that closes it
        self.closes = {}
        #: window -> arrival of its newest result row at the sink
        self.arrivals = {}
        #: seconds inside the source's emit_step since t0, without what
        #: ran nested in it (``nested_s``: where the source is chained
        #: to the window operator, collect() runs the operator)
        self.source_s = 0.0
        self.nested_s = lambda: 0.0
        #: seconds since t0 spent starting and stopping the profiler
        self.excluded_s = 0.0
        #: called once each: at t0, and when the sink has seen the last
        #: row of the last measured window
        self.on_t0 = []
        self.on_end = []
        self.ended = False
        #: the window whose results the sink is receiving
        self.current_window = None
        #: the traced slice, when the harness sets a profiler (an
        #: object with start() and stop()): the period of window
        #: ``profile_window``, and its host times
        self.profiler = None
        self.profile_window = profile_window
        self.slice_start = None
        self.slice_stop = None

    # ---- called by the source at the first batch of window w --------
    def step_begins(self):
        """The loop is cooperative: once the sink is receiving the last
        warm-up window, that window's fire has run to its end."""
        if self.t0 is None \
                and self.current_window == self.warmup_windows - 1:
            for hook in self.on_t0:
                hook()
            self.t0 = self.newest_arrival()

    def window_starts(self, w):
        if (self.t0 is not None and self.last_measured is None
                and w > self.warmup_windows
                and self.elapsed() >= self.seconds):
            self.last_measured = w - 1
        if self.profiler is not None:
            if w == self.profile_window:
                self._excluded(self.profiler.start)
                self.slice_start = self.clock()
            elif w == self.profile_window + 1:
                self.stop_slice()

    def stop_slice(self):
        if self.slice_start is not None and self.slice_stop is None:
            self.slice_stop = self.clock()
            self._excluded(self.profiler.stop)

    def _excluded(self, fn):
        t = self.clock()
        fn()
        self.excluded_s += self.clock() - t

    def elapsed(self):
        return self.clock() - self.t0 - self.excluded_s

    # ---- called by the source once the sink holds every row of the
    # last measured window --------------------------------------------
    def end_measured(self):
        if not self.ended:
            self.ended = True
            for hook in self.on_end:
                hook()

    # ---- read by the harness ----------------------------------------
    def measured_windows(self):
        return list(range(self.warmup_windows, self.last_measured + 1))

    def window_s(self):
        """t0 to the last row of the last measured window, without the
        profiler's own stalls."""
        return (self.arrivals[self.last_measured] - self.t0
                - self.excluded_s)

    def periods_s(self):
        """Last row of one window to last row of the next, for every
        measured window: each holds one window's worth of events taken
        in and one fire."""
        return [self.arrivals[w] - (self.arrivals[w - 1]
                                    if w > self.warmup_windows else self.t0)
                for w in self.measured_windows()]

    def fire_latencies_s(self):
        return [self.arrivals[w] - self.closes[w]
                for w in self.measured_windows()]


class ArrivalSink(SinkFunction):
    """Keeps every result with the host time it arrived.  Chunks are
    kept as their columns; rows that arrive one at a time are kept per
    window with the arrival time of the newest."""

    def __init__(self, timeline, window_ms, window_column=1):
        self.timeline = timeline
        timeline.newest_arrival = self.newest_arrival
        self.window_ms = window_ms
        self.window_column = window_column
        self._clock = timeline.clock
        #: (window start, list of row tuples | tuple of columns)
        self.windows = []
        self._cur_ws = None
        self._cur_rows = None
        self._t_last = 0.0

    def invoke(self, value, context=None):
        if isinstance(value, RecordBatch):
            self.invoke_batch(value)
            return
        ws = value[self.window_column]
        if ws != self._cur_ws or self._cur_rows is None:
            self._roll(ws)
            self._cur_rows = []
            self.windows.append((ws, self._cur_rows))
        self._cur_rows.append(value)
        self._t_last = self._clock()

    def newest_arrival(self):
        return self._t_last

    def invoke_batch(self, batch):
        if len(batch) == 0:
            return
        cols = tuple(np.asarray(c) for c in batch.cols.values())
        starts = cols[self.window_column]
        if starts[0] != starts[-1] or (starts != starts[0]).any():
            for ws in np.unique(starts).tolist():
                sel = starts == ws
                self._chunk(ws, tuple(c[sel] for c in cols))
        else:
            self._chunk(int(starts[0]), cols)

    def _chunk(self, ws, cols):
        if ws != self._cur_ws:
            self._roll(ws)
        self.windows.append((ws, cols))
        self._t_last = self._clock()

    def _roll(self, ws):
        """Results of another window begin: the window before it has
        its last row."""
        prev = self._cur_ws
        self._cur_ws, self._cur_rows = ws, None
        tl = self.timeline
        if prev is not None:
            w = prev // self.window_ms
            tl.arrivals[w] = max(tl.arrivals.get(w, 0.0), self._t_last)
        tl.current_window = None if ws is None else ws // self.window_ms

    def finish(self):
        """After the job: the newest window has its last row too."""
        self._roll(None)

    def by_window(self):
        """{window start: the result columns, as the job ordered them}
        with every chunk and row that arrived for it, in arrival
        order."""
        parts = {}
        for ws, payload in self.windows:
            if isinstance(payload, list):
                payload = tuple(np.asarray(c) for c in zip(*payload))
            parts.setdefault(int(ws), []).append(payload)
        return {ws: tuple(np.concatenate(c) for c in zip(*chunks))
                for ws, chunks in parts.items()}

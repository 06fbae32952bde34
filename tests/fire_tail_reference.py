"""The fire's emit tail as it was before the fire buffer held rows:
one ``StreamRecord`` per emitted row, then ``batch_from_records`` with
a Python-level pass per check.  Kept as the plain reference the
differential tests of the batched fires compare against
(tests/test_fire_batch.py, tests/test_device_window_batch_door.py):
what leaves a fire must equal this cell for cell, dtype for dtype,
column names and timestamps included."""

import numpy as np

from flink_tpu.streaming.elements import RecordBatch, StreamRecord
from flink_tpu.streaming.operators import Output


def reference_columns(values):
    """``columns_from_values`` in its loop form."""
    if not values:
        return None
    v0 = values[0]
    if type(v0) is tuple:
        arity = len(v0)
        if arity == 0 or any(type(v) is not tuple or len(v) != arity
                             for v in values):
            return None
        cols = {}
        for i in range(arity):
            col = _reference_column([v[i] for v in values])
            if col is None:
                return None
            cols[f"f{i}"] = col
        return cols
    col = _reference_column(values)
    return None if col is None else {"v": col}


def _reference_column(cells):
    t = type(cells[0])
    if any(type(c) is not t for c in cells):
        return None
    if t is int:
        try:
            return np.array(cells, np.int64)
        except OverflowError:
            return None
    if t is float:
        return np.array(cells, np.float64)
    if t is str:
        arr = np.empty(len(cells), object)
        arr[:] = cells
        return arr
    return None


def reference_batch(rows):
    """``rows``: the (value, timestamp) pairs of one fire, in order.
    The RecordBatch the old tail made of them, or None where it sent
    them on as per-row records (one row, or rows that do not fit)."""
    if len(rows) < 2:
        return None
    cols = reference_columns([v for v, _ in rows])
    if cols is None:
        return None
    return RecordBatch(cols, np.array([t for _, t in rows], np.int64))


class CountedRecord(StreamRecord):
    """Patched over a module's ``StreamRecord``, counts the records
    that module builds."""

    made = 0

    def __init__(self, value, timestamp=None):
        type(self).made += 1
        super().__init__(value, timestamp)


class FireSpy(Output):
    """Keeps what a fire hands on, as it arrives and unboxed: ("batch",
    RecordBatch) and ("record", (value, timestamp)) in order."""

    def __init__(self):
        self.events = []
        self.watermarks = []

    def collect(self, record):
        self.events.append(("record", (record.value, record.timestamp)))

    def collect_batch(self, batch):
        self.events.append(("batch", batch))

    def emit_watermark(self, watermark):
        self.watermarks.append(watermark.timestamp)

    def collect_side(self, tag, record):
        pass

    def emit_latency_marker(self, marker):
        pass

    def take(self):
        events, self.events = self.events, []
        return events

    def rows(self):
        """Every row seen, boxed: (value, timestamp) in order."""
        out = []
        for kind, what in self.events:
            if kind == "record":
                out.append(what)
            else:
                out.extend(zip(what.row_values(), what.ts.tolist()))
        return out


def assert_same_batch(got, want):
    assert list(got.cols) == list(want.cols)
    for name, col in want.cols.items():
        assert got.cols[name].dtype == col.dtype, name
        assert got.cols[name].tolist() == col.tolist(), name
        if col.dtype == object:
            assert [type(c) for c in got.cols[name]] \
                == [type(c) for c in col], name
    assert got.ts.dtype == want.ts.dtype == np.int64
    assert got.ts.tolist() == want.ts.tolist()
    assert got.ts_mask is None and want.ts_mask is None


def assert_fire_left_as(events, rows):
    """``events``: what one fire handed on (``FireSpy.take``);
    ``rows``: the (value, timestamp) pairs the old tail was given."""
    want = reference_batch(rows)
    if want is None:
        assert events == [("record", row) for row in rows]
        # equal is not enough for a cell: 1 == 1.0 == True
        assert [repr(v) for _, (v, _) in events] \
            == [repr(v) for v, _ in rows]
        return
    assert [kind for kind, _ in events] == ["batch"]
    assert_same_batch(events[0][1], want)

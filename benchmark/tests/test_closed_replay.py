"""The closed-loop replay source: exactly ``events_per_window`` events
in every event-time window, time-sorted, a watermark after each batch,
the closing watermark noted once per window, fresh rows in every
window until the pool is used up; and the sink's arrival notes."""

import copy

import numpy as np
import pytest

import loader
import timeline as clocks
from flink_tpu.streaming.elements import MAX_WATERMARK, RecordBatch

closed_replay = loader.load_module("sources", "closed_replay")


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 0.25
        return self.now


class Collected:
    """A SourceContext that keeps what it is given, and stands in for
    a job chained behind it: a watermark that closes a window puts
    that window's results at the sink at once."""

    def __init__(self, timeline):
        self.elements = []
        self.timeline = timeline

    def collect(self, batch):
        self.elements.append(("value", batch))

    def collect_batch(self, batch):
        self.elements.append(("element", batch))

    def emit_watermark(self, watermark):
        self.elements.append(("watermark", watermark.timestamp))
        closed = (watermark.timestamp + 1) // 1000 - 1
        if 0 <= closed < 10 ** 6:
            self.timeline.current_window = closed


def run_source(epw=64, batch=16, warmup=1, seconds=10.0, pool=2):
    clock = FakeClock()
    timeline = clocks.Timeline(warmup, seconds, clock=clock)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 50, (pool, epw), dtype=np.int64)
    users = rng.integers(0, 1 << 40, (pool, epw), dtype=np.int64)
    source = closed_replay.ReplaySource(keys, users, epw, batch, 1000, timeline)
    drain(source, Collected(timeline))
    return timeline, source.ctx, keys, users


def drain(source, ctx):
    source.ctx = ctx
    clone = copy.deepcopy(source)      # as the source factory does
    steps = 0
    while clone.emit_step(ctx, 128):
        steps += 1
        assert steps < 10_000


def test_every_window_holds_exactly_events_per_window_in_time_order():
    timeline, ctx, keys, users = run_source()
    batches = [b for kind, b in ctx.elements if kind == "element"]
    ts = np.concatenate([b.ts for b in batches])
    assert (np.diff(ts) >= 0).all()
    last = timeline.last_measured
    per_window = np.bincount(ts // 1000)
    # whole windows, then the one batch that closes the last of them
    assert per_window.tolist() == [64] * (last + 1) + [16]
    # window w replays pool entry w mod P, in order
    for w in range(last + 1):
        got = np.concatenate([b.cols["f0"] for b in batches
                              if b.ts[0] // 1000 == w])
        assert np.array_equal(got, keys[w % 2])


def test_a_watermark_follows_every_batch_and_closes_each_window_once():
    timeline, ctx, _, _ = run_source()
    kinds = [kind for kind, _ in ctx.elements]
    assert kinds[-1] == "watermark" \
        and ctx.elements[-1][1] == MAX_WATERMARK.timestamp
    body = ctx.elements[:-1]
    assert [k for k, _ in body] == ["element", "watermark"] * (len(body) // 2)
    marks = []
    for (_, batch), (_, mark) in zip(body[0::2], body[1::2]):
        assert mark == int(batch.ts[-1]) - 1
        marks.append(mark)
    assert marks == sorted(marks)
    last = timeline.last_measured
    # the first watermark >= the window's last millisecond closes it
    for w in range(last + 1):
        assert sum(m >= (w + 1) * 1000 - 1 for m in marks) >= 1
        assert w in timeline.closes
    assert sorted(timeline.closes) == list(range(last + 1))


def test_the_window_ends_after_the_asked_seconds_on_a_whole_window():
    # the fake clock moves 0.25 s per reading
    timeline, _, _, _ = run_source(seconds=10.0)
    assert timeline.t0 is not None
    assert timeline.last_measured >= timeline.warmup_windows
    short, _, _, _ = run_source(seconds=0.1)
    assert short.last_measured == short.warmup_windows


def test_t0_is_the_last_row_of_the_last_warmup_window():
    """The measured interval is n whole periods: it starts when the
    last warm-up window has fired and ends when the last measured
    window has, so it holds n fires and n windows' worth of events."""
    clock = FakeClock()
    timeline = clocks.Timeline(2, 3.0, clock=clock)
    sink = clocks.ArrivalSink(timeline, 1000)
    keys = np.zeros((1, 32), np.int64)
    source = closed_replay.ReplaySource(keys, keys, 32, 8, 1000, timeline)

    class Chained(Collected):
        def emit_watermark(self, watermark):
            closed = (watermark.timestamp + 1) // 1000 - 1
            if 0 <= closed < 10 ** 6 and closed not in timeline.arrivals \
                    and closed != timeline.current_window:
                sink.invoke((0, closed * 1000, 1.0))    # its fire

    ctx = Chained(timeline)
    for _ in range(1000):
        if not source.emit_step(ctx, 1):
            break
    else:
        raise AssertionError("the source never ended")
    sink.finish()
    assert timeline.t0 == timeline.arrivals[1]
    measured = timeline.measured_windows()
    assert measured[0] == 2 and len(timeline.fire_latencies_s()) == len(measured)
    assert timeline.window_s() == timeline.arrivals[measured[-1]] - timeline.arrivals[1]
    assert timeline.window_s() >= 3.0
    periods = timeline.periods_s()
    assert len(periods) == len(measured)
    assert sum(periods) == pytest.approx(timeline.window_s())


def test_the_stream_ends_only_once_the_last_window_is_at_the_sink():
    clock = FakeClock()
    timeline = clocks.Timeline(1, 0.1, clock=clock)
    ended = []
    timeline.on_end.append(lambda: ended.append(True))
    keys = np.zeros((1, 8), np.int64)
    source = closed_replay.ReplaySource(keys, keys, 8, 8, 1000, timeline)
    ctx = Collected(timeline)

    def queued(wm):
        # only the warm-up window's fire has reached the sink
        ctx.elements.append(("wm", wm.timestamp))
        if (wm.timestamp + 1) // 1000 - 1 == 0:
            timeline.current_window = 0

    ctx.emit_watermark = queued
    for _ in range(100):
        if timeline.last_measured is not None:
            break
        assert source.emit_step(ctx, 1)
    assert timeline.last_measured == 1
    # unchained: the closing watermark is still queued; the source polls
    n = len(ctx.elements)
    assert source.emit_step(ctx, 1) and source.emit_step(ctx, 1)
    assert len(ctx.elements) == n and not ended
    timeline.current_window = timeline.last_measured
    assert source.emit_step(ctx, 1) is False and ended == [True]
    assert ctx.elements[-1] == ("wm", MAX_WATERMARK.timestamp)


def test_sql_convention_sends_the_batch_as_a_record_value():
    clock = FakeClock()
    timeline = clocks.Timeline(1, 0.1, clock=clock)
    keys = np.zeros((1, 8), np.int64)
    source = closed_replay.ReplaySource(keys, keys, 8, 8, 1000, timeline)
    source.configure(("k", "u", "ts"), as_elements=False)
    ctx = Collected(timeline)
    source.emit_step(ctx, 1)
    kind, batch = ctx.elements[0]
    assert kind == "value" and isinstance(batch, RecordBatch)
    assert list(batch.cols) == ["k", "u", "ts"]


def test_sink_notes_the_newest_arrival_per_window_rows_and_chunks():
    clock = FakeClock()
    timeline = clocks.Timeline(0, 1.0, clock=clock)
    sink = clocks.ArrivalSink(timeline, 1000)
    sink.invoke((7, 0, 2.0))
    sink.invoke((8, 0, 3.0))
    t_window0 = clock.now
    assert timeline.current_window == 0
    sink.invoke((7, 1000, 1.0))
    assert timeline.arrivals[0] == t_window0
    assert timeline.current_window == 1
    chunk = RecordBatch({"k": np.array([1, 2]), "ws": np.array([2000, 2000]),
                         "d": np.array([1.0, 5.0])}, np.array([2999, 2999]))
    sink.invoke(chunk)          # the SQL tier: a batch as a record's value
    assert timeline.current_window == 2
    sink.finish()
    assert sorted(timeline.arrivals) == [0, 1, 2]
    got = sink.by_window()
    assert got[0][0].tolist() == [7, 8] and got[0][2].tolist() == [2.0, 3.0]
    assert got[2000][0].tolist() == [1, 2] and len(got[2000]) == 3


def test_sink_finds_the_window_start_in_the_column_it_is_told():
    timeline = clocks.Timeline(0, 1.0, clock=FakeClock())
    sink = clocks.ArrivalSink(timeline, 1000, window_column=0)
    sink.invoke((0, 7, 2.0))
    sink.invoke((1000, 7, 3.0))
    sink.finish()
    assert sorted(timeline.arrivals) == [0, 1]
    assert sink.by_window()[1000][1].tolist() == [7]


CONFIG = {"events_per_window": 64, "batch_rows": 16, "window_ms": 1000,
          "key_space": 50, "user_bits": 40}
TRAFFIC = {"key_distribution": "uniform", "params": {}}


def made(seed, seconds=3.0):
    source = closed_replay.make(CONFIG, TRAFFIC, seed, seconds,
                                clock=FakeClock())
    drain(source, Collected(source.timeline))
    return source


def test_every_window_carries_fresh_rows_drawn_from_the_seed():
    """No window of a run repeats another until the pool is used up;
    the same seed draws the same run, another seed another."""
    source = made(5)
    assert len(source.pool_keys) == closed_replay.POOL_WINDOWS_AT_MOST
    emitted = source.emitted()
    last = source.timeline.last_measured
    assert source.timeline.warmup_windows == closed_replay.WARMUP_WINDOWS == 2
    assert [e.window for e in emitted] == list(range(last + 2))
    assert len({e.data_id for e in emitted}) == len(emitted)
    assert source.replayed_windows() == 0
    sent = [b for kind, b in source.ctx.elements if kind == "element"]
    for e in emitted:
        keys, users = e.columns()
        got = np.concatenate([b.cols["f0"] for b in sent
                              if b.ts[0] // 1000 == e.window])
        assert np.array_equal(got, keys) and len(users) == len(keys)
    # whole windows, then the one batch that closes the last of them
    assert [len(e.columns()[0]) for e in emitted] == [64] * (last + 1) + [16]
    assert source.events_emitted == 64 * (last + 1) + 16
    windows = [e.columns()[0].tolist() for e in emitted[:-1]]
    assert len({tuple(w) for w in windows}) == len(windows)
    again, other = made(5), made(6)
    assert [e.columns()[0].tolist() for e in again.emitted()[:-1]] == windows
    assert other.emitted()[0].columns()[0].tolist() != windows[0]


def test_a_run_that_outlasts_its_pool_replays_it_and_says_so(monkeypatch):
    monkeypatch.setattr(closed_replay, "POOL_EVENTS", 64 * 3)
    source = made(5)
    emitted = source.emitted()
    assert len(emitted) > 3 and source.replayed_windows() == len(emitted) - 3
    assert emitted[3].data_id == emitted[0].data_id
    rows = len(emitted[4].columns()[0])
    assert np.array_equal(emitted[4].columns()[0],
                          emitted[1].columns()[0][:rows])


def test_windows_must_be_whole_batches():
    with pytest.raises(loader.CellError):
        closed_replay.make({**CONFIG, "batch_rows": 24}, TRAFFIC, 1, 1.0)

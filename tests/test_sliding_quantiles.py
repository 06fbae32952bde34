"""Config #3's job at a small size on the CPU: sliding 10 s / 1 s
p50 / p99 (``QuantileSketchAggregate``) per key on the scalar
``WindowOperator`` over the keyed-state backend, against exact order
statistics (``tests/quantile_sliding_reference.py``: numpy, float64,
nothing of the system).  Ten state rows an event, a fire every slide,
partial windows at both ends of the stream."""

import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

import quantile_sliding_reference as reference
from flink_tpu.core.config import Configuration
from flink_tpu.ops.sketches import QuantileSketchAggregate
from flink_tpu.runtime.tracing import get_tracer
from flink_tpu.state.stats import STATE_STATS
from flink_tpu.streaming.columnar import ColumnarSource
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.sources import CollectSink
from flink_tpu.streaming.window_operator import WindowOperator
from flink_tpu.streaming.windowing import SlidingEventTimeWindows

SIZE_MS, SLIDE_MS = 10_000, 1_000
PANES = SIZE_MS // SLIDE_MS
CONFIG = {"slide_ms": SLIDE_MS, "window_size_ms": SIZE_MS,
          "quantiles": [0.5, 0.99], "relative_accuracy": 0.01}
GAMMA = 1.01 / 0.99


class ValueQuantiles(QuantileSketchAggregate):
    """p50 / p99 over field 1 (the value) of a (key, value) row."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


class Bf16Buckets(ValueQuantiles):
    """The same sketch with the bucket index computed in bfloat16: the
    nearest precision below the float32 the system computes it in."""

    def _bucket_of(self, values):
        v = values.astype(jnp.bfloat16)
        logs = jnp.log(jnp.maximum(v, jnp.bfloat16(self.min_value))) \
            / jnp.bfloat16(self.log_gamma)
        b = 1 + jnp.floor(logs.astype(jnp.float32)).astype(jnp.int32) \
            - self.offset
        b = jnp.clip(b, 1, self.buckets - 1)
        return jnp.where(values <= self.min_value, 0, b)


class _BatchElements:
    """A batch travels as a stream ELEMENT, as on the DataStream
    pipeline (``ColumnarSource`` collects it as a record's value)."""

    def __init__(self, ctx):
        self._ctx = ctx

    def collect(self, batch):
        self._ctx.collect_batch(batch)

    def emit_watermark(self, watermark):
        self._ctx.emit_watermark(watermark)


class EventSource(ColumnarSource):
    """(key, value, ts) rows in ``chunk``-row batches, a watermark
    after each, MAX_WATERMARK at the end."""

    def __init__(self, keys, values, ts, chunk):
        super().__init__({"f0": keys, "f1": values, "f2": ts},
                         rowtime="f2", chunk=chunk)

    def emit_step(self, ctx, max_records):
        return super().emit_step(_BatchElements(ctx), max_records)


def make_events(seed, n_keys, per_slide, slides):
    """Zipf 0.99 keys, lognormal values, ``per_slide`` time-sorted
    events in every 1 s slide period."""
    rng = np.random.default_rng(seed)
    n = per_slide * slides
    cumulative = np.cumsum(np.arange(1, n_keys + 1, dtype=np.float64)
                           ** -0.99)
    ranks = np.searchsorted(cumulative, rng.random(n) * cumulative[-1],
                            side="right")
    keys = rng.permutation(n_keys)[np.minimum(ranks, n_keys - 1)]
    values = np.exp(rng.normal(3.0, 1.0, n))
    ts = (np.arange(n, dtype=np.int64) * SLIDE_MS) // per_slide
    return keys.astype(np.int64), values, ts


def emit_row(key, window, vals):
    p50, p99 = vals[0]
    return [(key, window.end - SLIDE_MS, float(p50), float(p99))]


def run_job(events, chunk, backend="tpu", configuration=None, agg=None):
    """The job through ``env.execute()``; returns (the working
    operator, {last pane's start: (keys, starts, p50, p99)})."""
    env = StreamExecutionEnvironment(configuration)
    env.set_state_backend(backend)
    windowed = (env.add_source(EventSource(*events, chunk), name="events")
                .key_by(0)
                .window(SlidingEventTimeWindows.of(SIZE_MS, SLIDE_MS)))
    windowed.disable_device_operator()
    sink = CollectSink()
    windowed.aggregate(agg or ValueQuantiles(),
                       window_function=emit_row).add_sink(sink)
    made = []
    for node in env.get_stream_graph().nodes.values():
        def factory(inner=node.operator_factory):
            op = inner()
            made.append(op)
            return op
        node.operator_factory = factory
    env.execute("sliding-quantiles")
    op, = [o for o in made if isinstance(o, WindowOperator)
           and (o.columnar_rows or o.boxed_rows)]
    by_window = {}
    for row in sink.values:
        by_window.setdefault(row[1], []).append(row)
    return op, {ws: tuple(np.asarray(c) for c in zip(*rows))
                for ws, rows in by_window.items()}


def emitted_panes(events, per_slide):
    keys, values, _ = events
    return [(p, None, lambda p=p: (keys[p * per_slide:(p + 1) * per_slide],
                                   values[p * per_slide:(p + 1) * per_slide]))
            for p in range(len(keys) // per_slide)]


PER_SLIDE, SLIDES = 192, 14
EVENTS = make_events(33, 300, PER_SLIDE, SLIDES)


# ---- the state route against the reference ----------------------------

@pytest.mark.parametrize("chunk", [
    64,     # three batches a slide
    80,     # every third batch straddles a slide
    192,    # one batch a slide
    500,    # a batch spans three slides
])
def test_tpu_backend_matches_exact_order_statistics(chunk):
    op, results = run_job(EVENTS, chunk)
    verdict = reference.check(CONFIG, emitted_panes(EVENTS, PER_SLIDE),
                              results)
    assert verdict["problems"] == [] and verdict["failed"] == 0
    assert verdict["attempted"] == 2 * sum(len(r[0])
                                           for r in results.values())
    assert verdict["facts"]["max_rel_err"] <= reference.bound(CONFIG)
    # the partial windows at the head of the stream and those the final
    # watermark fires are results too
    assert sorted(results) == [w * SLIDE_MS
                               for w in range(SLIDES + PANES - 1)]
    assert op.boxed_fallbacks == 0
    assert op.columnar_rows == len(EVENTS[0])
    assert op.fire_rows_via_records == 0


@pytest.mark.parametrize("tile_form_runs", [False, True])
def test_the_job_notes_the_flushes_that_ran_in_place(tile_form_runs,
                                                     monkeypatch):
    """Flushes of 64 rows against a table of 4,096 slots and more: the
    shapes give every one to the tile kernel.  That kernel is the
    TPU's, so here each flush scatters cells and none is noted; where
    the kernel runs (stood in for: the answer, not the program) every
    flush of the job is."""
    from flink_tpu.ops import sketches
    if tile_form_runs:
        monkeypatch.setattr(sketches, "_tile_form_runs", lambda: True)
    before = (STATE_STATS.flush_batches, STATE_STATS.flush_row_form_batches)
    op, results = run_job(EVENTS, 64, configuration=Configuration().set(
        "state.backend.tpu.microbatch-size", 64))
    flushed = STATE_STATS.flush_batches - before[0]
    assert flushed == PANES * len(EVENTS[0]) // 64
    assert STATE_STATS.flush_row_form_batches - before[1] \
        == (flushed if tile_form_runs else 0)
    assert op.window_state.capacity >= 4096
    verdict = reference.check(CONFIG, emitted_panes(EVENTS, PER_SLIDE),
                              results)
    assert verdict["problems"] == [] and verdict["failed"] == 0


@pytest.mark.parametrize("budget, microbatch", [(1024, 64), (2048, 128)])
def test_under_a_small_budget_the_tier_evicts_and_fires_from_host_ram(
        budget, microbatch):
    conf = (Configuration()
            .set("state.backend.tpu.max-device-slots", budget)
            .set("state.backend.tpu.microbatch-size", microbatch))
    fired_before = STATE_STATS.spill_fired_rows
    op, results = run_job(EVENTS, 64, configuration=conf)
    state = op.window_state
    assert state.max_device_slots == budget
    assert state.evictions > 0
    assert STATE_STATS.spill_fired_rows > fired_before
    verdict = reference.check(CONFIG, emitted_panes(EVENTS, PER_SLIDE),
                              results)
    assert verdict["problems"] == [] and verdict["failed"] == 0
    assert verdict["attempted"] > 0


def test_heap_backend_gives_the_same_rows_within_one_bucket():
    _, on_tpu = run_job(EVENTS, 64)
    _, on_heap = run_job(EVENTS, 64, backend="heap")
    assert sorted(on_tpu) == sorted(on_heap)
    compared = 0
    for ws, got in on_tpu.items():
        order_t = np.argsort(got[0])
        order_h = np.argsort(on_heap[ws][0])
        assert got[0][order_t].tolist() == on_heap[ws][0][order_h].tolist()
        for col in (2, 3):
            ratio = got[col][order_t] / on_heap[ws][col][order_h]
            assert (ratio <= GAMMA * (1 + 1e-6)).all()
            assert (ratio >= (1 - 1e-6) / GAMMA).all()
            compared += len(ratio)
    assert compared > 2000
    verdict = reference.check(CONFIG, emitted_panes(EVENTS, PER_SLIDE),
                              on_heap)
    assert verdict["problems"] == [] and verdict["failed"] == 0


# ---- what the comparison refuses --------------------------------------

def test_a_sketch_bucketed_in_bfloat16_fails_the_bound():
    _, results = run_job(EVENTS, 64, agg=Bf16Buckets())
    verdict = reference.check(CONFIG, emitted_panes(EVENTS, PER_SLIDE),
                              results)
    assert verdict["failed"] > 0.1 * verdict["attempted"]
    assert verdict["facts"]["max_rel_err"] > 1.5 * reference.bound(CONFIG)


def test_dropping_the_midpoint_correction_fails_the_bound():
    _, results = run_job(EVENTS, 64)
    upper_edges = {ws: (r[0], r[1], r[2] * (GAMMA + 1) / 2,
                        r[3] * (GAMMA + 1) / 2)
                   for ws, r in results.items()}
    verdict = reference.check(CONFIG, emitted_panes(EVENTS, PER_SLIDE),
                              upper_edges)
    assert verdict["failed"] > 0.3 * verdict["attempted"]
    assert 0.0102 < verdict["facts"]["max_rel_err"] <= 0.0203


@pytest.mark.parametrize("fault", ["missing key", "duplicated row",
                                   "stray window", "missing window",
                                   "nan"])
def test_the_comparison_counts_what_is_not_one_row_per_key_and_window(fault):
    _, results = run_job(EVENTS, 192)
    results = dict(results)
    ws = 5 * SLIDE_MS
    keys, starts, p50, p99 = results[ws]
    expected = None
    if fault == "missing key":
        results[ws] = (keys[1:], starts[1:], p50[1:], p99[1:])
        expected = 2
    elif fault == "duplicated row":
        results[ws] = tuple(np.concatenate([c, c[:1]])
                            for c in results[ws])
        expected = 2
    elif fault == "stray window":
        results[1_000_000] = tuple(c[:3] for c in results[ws])
        expected = 6
    elif fault == "missing window":
        del results[ws]
        expected = 2 * len(keys)
    else:
        p50 = p50.copy()
        p50[0] = np.nan
        results[ws] = (keys, starts, p50, p99)
        expected = 1
    verdict = reference.check(CONFIG, emitted_panes(EVENTS, PER_SLIDE),
                              results)
    assert verdict["failed"] == expected
    assert len(verdict["problems"]) == 1


# ---- counters and phases ----------------------------------------------

@pytest.mark.parametrize("chunk, groups_a_batch", [(64, 10), (192, 10)])
def test_every_event_is_ten_state_rows(chunk, groups_a_batch):
    """Batches that do not straddle a slide: ten windows touched per
    batch, ten state rows per event, on the operator and in the
    backend's own counter."""
    doors, reads = STATE_STATS.batch_rows, STATE_STATS.result_rows
    op, results = run_job(EVENTS, chunk)
    n = len(EVENTS[0])
    assert op.window_rows == PANES * n
    assert op.windows_touched == groups_a_batch * (n // chunk)
    assert op._window_rows_per_row() == PANES
    fired = sum(len(r[0]) for r in results.values())
    assert STATE_STATS.result_rows - reads == fired
    assert (STATE_STATS.batch_rows - doors) - fired == PANES * n


def test_a_batch_that_straddles_a_slide_touches_eleven_windows():
    op, _ = run_job(EVENTS, 80)
    n = len(EVENTS[0])
    batches = -(-n // 80)
    straddling = sum(1 for b in range(batches)
                     if (b * 80) // PER_SLIDE
                     != (min((b + 1) * 80, n) - 1) // PER_SLIDE)
    assert straddling > 0
    assert op.windows_touched == PANES * batches + straddling
    assert op.window_rows == PANES * n


def test_group_phase_counts_follow_batches_and_windows_never_rows():
    """The same batches, windows and fires at four times the rows and
    keys: every phase is entered the same number of times."""
    tracer = get_tracer()
    counts = []
    for per_slide in (64, 256):
        events = make_events(7, per_slide * 2, per_slide, 12)
        tracer.reset()
        op, _ = run_job(events, per_slide // 2)
        counts.append({name: s["count"]
                       for name, s in tracer.stats().items()})
        batches = 12 * 2
        assert counts[-1]["window.ingest"] == batches
        assert counts[-1]["window.ingest.assign"] == batches
        assert counts[-1]["window.ingest.group"] == batches
        assert counts[-1]["timers.register"] == op.windows_touched \
            == PANES * batches
        assert counts[-1]["state.add.slots"] == PANES * batches
    assert counts[0] == counts[1]


# ---- the reference itself ---------------------------------------------

def test_the_two_copies_of_the_reference_are_one_file():
    here = os.path.dirname(os.path.abspath(__file__))
    other = os.path.join(os.path.dirname(here), "benchmark", "references",
                         "quantile_sliding.py")
    with open(other, "rb") as a, open(reference.__file__, "rb") as b:
        assert a.read() == b.read()
    spec = importlib.util.spec_from_file_location("quantile_sliding", other)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 50, 4000)
    values = np.exp(rng.normal(3.0, 1.0, 4000))
    for mine, theirs in zip(
            reference.exact_quantiles(keys, values, [0.5, 0.99]),
            copy.exact_quantiles(keys, values, [0.5, 0.99])):
        assert np.array_equal(mine, theirs)
    assert copy.bound(CONFIG) == reference.bound(CONFIG) == 0.0102


@pytest.mark.parametrize("q, n, rank", [
    (0.5, 1, 1), (0.5, 2, 1), (0.5, 3, 2), (0.99, 1, 1), (0.99, 100, 99),
    (0.99, 101, 100), (0.99, 300, 297), (0.99, 9011, 8921), (0.5, 0, 1)])
def test_ranks_are_the_ceiling_in_whole_numbers(q, n, rank):
    assert reference.ranks(np.array([n]), q).tolist() == [rank]


def test_exact_quantiles_are_the_sorted_values_at_those_ranks():
    rng = np.random.default_rng(11)
    keys = rng.integers(0, 40, 3000)
    values = rng.random(3000)
    k, n, q = reference.exact_quantiles(keys, values, [0.5, 0.99])
    assert k.tolist() == sorted(set(keys.tolist()))
    for i, key in enumerate(k.tolist()):
        own = np.sort(values[keys == key])
        assert n[i] == len(own)
        assert q[i, 0] == own[-(-len(own) // 2) - 1]
        assert q[i, 1] == own[-(-99 * len(own) // 100) - 1]

"""The cell ``session_cm_log.zipf``, rehearsed on the CPU: config #4
on the state route, read from a 4-partition replayable log by the
program's own connector.  The run is ``correct`` against the plain
reference, takes the route it names on the batched session path (no
boxed batch, no per-key probe, no late row), and its traced run reads
the metrics a CPU trace can carry.  The source's periods, partitions,
watermarks and clocks; the reference's failures on doctored rows; the
by-name rule."""

import copy
import json
import math

import numpy as np
import pytest

import loader
from test_harness import ROOT, last_line, run_cell

CELL = "session_cm_log.zipf"
CONFIG = "session_cm_log"
BUDGET_KEY = "state.backend.tpu.max-device-slots"
NEW_METRICS = ("session_resolve_share", "session_timer_share",
               "session_state_ingest_share", "session_fire_share",
               "countmin_update_roofline", "countmin_result_roofline")


def rehearse(trace, seed="3700000019"):
    proc = run_cell(ROOT, "--workload", CELL, "--seed", seed,
                    "--seconds", "1", "--trace", trace, "--rehearse-cpu")
    lines = {tag: json.loads(line[len(tag) + 2:])
             for line in proc.stdout.splitlines()
             for tag in ("route", "check", "data", "window")
             if line.startswith(f"[{tag}]")}
    return last_line(proc), lines


def test_the_cell_rehearses_correct_on_the_batched_session_path():
    out, lines = rehearse("0")
    assert out["rehearsal"] is True and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"rehearsal_events_per_s",
                                   "rehearsal_fire_p50_ms",
                                   "rehearsal_setup_s"}
    route = lines["route"]
    assert route["operator"] == "WindowOperator"
    assert route["route"].endswith("TpuKeyedStateBackend.add_batch -> "
                                   "DeviceAggregatingState")
    config = loader.read_json(loader.BENCH_DIR / "configs"
                              / f"{CONFIG}.json")
    assert route["budget"] == config["rehearsal"][
        "state_backend_config"][BUDGET_KEY]
    assert route["table_bytes"] == route["slots"] * 4 * 4 * 2048
    assert route["evictions"] == route["promotions"] == 0
    assert route["budget_overruns"] == route["boxed_fallbacks"] == 0
    measured = route["in_measured_windows"]
    # a merge probes its target's slot through the per-key door, once
    assert measured["per_key_probe_rows"] <= measured["merged_rows"]
    assert measured["num_late_records_dropped"] == 0
    assert measured["hash_per_value_rows"] == 0
    assert measured["evicted_rows"] == 0
    events = measured["ingest_batches"] * config["rehearsal"]["batch_rows"]
    assert measured["sessions_opened"] + measured["sessions_extended"] \
        == events
    assert measured["timers_swept"] == measured["result_rows"] > 0
    # one add_batch a chunk, one get_batch a fire
    fires = lines["window"]["fires"]
    assert measured["batch_calls"] == measured["ingest_batches"] + fires
    # warm-up: the gap's periods and two more
    rehearsal = config["rehearsal"]
    assert lines["data"]["warmup_windows"] \
        == rehearsal["gap_ms"] // config["window_ms"] + 2
    check = lines["check"]
    assert check["sessions"] * 9 == out["attempted"]
    assert check["over_bound_share"] <= check["allowed_share"]


def test_the_traced_rehearsal_reads_the_session_metrics():
    out, _ = rehearse("1")
    assert out["correct"] is True and out["failed"] == 0
    value = {n[len("rehearsal_"):]: m["value"]
             for n, m in out["metrics"].items()}
    assert {"session_resolve_share", "session_timer_share",
            "session_state_ingest_share", "session_fire_share",
            "fire_emit_share", "phase_coverage_share",
            "phase_coverage_interval_share", "gc_interval_share",
            "fire_emit_interval_share", "source_host_share",
            "compiles_in_window"} <= set(value)
    for name in NEW_METRICS[:4]:
        assert 0 < value[name] < 100, name
    assert value["phase_coverage_interval_share"] >= 90
    # no device plane in a CPU trace: the rooflines stay silent, as do
    # the listed metrics of the cells this one is not
    assert not set(value) & {"countmin_update_roofline",
                             "countmin_result_roofline",
                             "state_slot_share", "spill_ingest_share",
                             "sliding_fanout_share", "native_host_share"}


# ---- the source --------------------------------------------------------

class Taken:
    """A source context that keeps what it is handed, with a clock."""

    def __init__(self, timeline):
        self.timeline = timeline
        self.elements = []

    def collect_batch(self, batch):
        self.elements.append(("batch", batch, self.timeline.clock()))
        # the sink of a real job: results of the period before
        self.timeline.current_window = None

    def emit_watermark(self, watermark):
        self.elements.append(("watermark", watermark.timestamp,
                              self.timeline.clock()))


def small_source(seconds=0.0, ticks=None):
    config = loader.read_json(loader.BENCH_DIR / "configs"
                              / f"{CONFIG}.json")
    config = {**config, **config["rehearsal"]}
    traffic = loader.read_json(loader.BENCH_DIR / "traffic"
                               / "zipf_items_log.json")
    ticks = ticks if ticks is not None else iter(range(10 ** 9))
    module = loader.load_module("sources", traffic["source"])
    source = module.make(config, traffic, 7, seconds,
                         clock=lambda: float(next(ticks)))
    source._my_partitions = list(range(config["partitions"]))
    source.offsets = {p: 0 for p in source._my_partitions}
    return source, config, module


def test_the_source_hands_a_period_over_as_partition_chunks():
    source, config, module = small_source()
    tl = source.timeline
    assert tl.warmup_windows == config["gap_ms"] // config["window_ms"] + 2
    assert tl.profile_window == tl.warmup_windows + 2
    assert len(source.watch_items) == config["watch_count"]
    ctx = Taken(tl)
    parts, epw = config["partitions"], config["events_per_window"]
    for _ in range(6):   # three periods: chunks, then the watermark
        assert source.emit_step(ctx, 1) is True
    kinds = [e[0] for e in ctx.elements]
    assert kinds == (["batch"] * parts + ["watermark"]) * 3
    for w in range(3):
        chunks = ctx.elements[w * (parts + 1):w * (parts + 1) + parts]
        wm = ctx.elements[w * (parts + 1) + parts][1]
        ts = np.concatenate([b.ts for _, b, _ in chunks])
        assert len(ts) == epw
        assert ts.min() > w * config["window_ms"]          # never on
        assert ts.max() < (w + 1) * config["window_ms"]    # an edge
        assert wm == ts.max() - config["watermark_lag_ms"]
        for p, (_, batch, _) in enumerate(chunks):
            assert list(batch.cols) == ["f0", "f1"]
            assert len(batch) == config["batch_rows"]
            assert (np.diff(batch.ts) >= 0).all()    # in order inside
            # row i of the period went to partition i mod parts
            keys, items, stamps, watch = source.emitted()[w].columns()
            lo = p * config["batch_rows"]
            np.testing.assert_array_equal(
                batch.cols["f0"], keys[lo:lo + config["batch_rows"]])
            np.testing.assert_array_equal(
                batch.ts, stamps[lo:lo + config["batch_rows"]])
        # partitions interleave in time: a later chunk starts before
        # the one before it ends
        assert chunks[1][1].ts[0] < chunks[0][1].ts[-1]
    assert source.events_emitted == 3 * epw
    assert source.offsets == {p: 3 * epw // parts for p in range(parts)}
    assert watch == source.watch_items
    # the tracked items are the pool's most frequent, ties to the lower
    counts = np.bincount(source.pool_items.reshape(-1),
                         minlength=config["item_space"])
    best = sorted(range(len(counts)), key=lambda i: (-counts[i], i))
    assert list(source.watch_items) == best[:config["watch_count"]]


def test_the_clock_of_a_fire_starts_at_its_watermark():
    source, config, _ = small_source()
    tl = source.timeline
    ctx = Taken(tl)
    for _ in range(8):
        source.emit_step(ctx, 1)
    marks = [e for e in ctx.elements if e[0] == "watermark"]
    assert sorted(tl.closes) == [0, 1, 2]   # period w's closes w - 1
    for w in (0, 1, 2):
        # stamped in the step that hands the watermark on, after the
        # period's last chunk went out
        last_chunk = [e for e in ctx.elements if e[0] == "batch"][
            (w + 2) * config["partitions"] - 1][2]
        assert last_chunk < tl.closes[w] < marks[w + 1][2]


def test_the_stream_ends_after_the_closing_period():
    ticks = iter(range(10 ** 9))
    source, config, _ = small_source(seconds=30.0, ticks=ticks)
    tl = source.timeline
    ctx = Taken(tl)
    hooks = []
    tl.on_t0.append(lambda: hooks.append("t0"))
    tl.on_end.append(lambda: hooks.append("end"))
    warm = tl.warmup_windows
    steps = 0
    while True:
        if source._w == warm + 1 and source._clocks is None:
            tl.current_window = warm - 1   # the sink has the warm-up's
        if tl.last_measured is not None and source._closing:
            tl.current_window = tl.last_measured
        more = source.emit_step(ctx, 1)
        steps += 1
        if not more:
            break
        assert steps < 400
    assert hooks == ["t0", "end"]
    assert tl.last_measured >= warm + 1
    # one whole period after the last measured one, then the end
    assert max(source._rows_by_window) == tl.last_measured + 1
    assert ctx.elements[-1][1] > 10 ** 15   # MAX_WATERMARK
    assert source.emit_step(ctx, 1) is False


# ---- the reference ------------------------------------------------------

def tiny_case():
    """Three keys over 40 s, gap 10 s, tracked items 1 and 2."""
    rows = [  # key, item, ts
        (1, 1, 1_000), (1, 2, 9_000), (1, 1, 19_000),   # one session
        (1, 9, 40_000),                                  # another
        (2, 2, 5_000), (2, 2, 15_001),                   # two: gap + 1
        (3, 1, 7_000), (3, 1, 17_000),                   # one: abuts
    ]
    keys, items, ts = (np.array(c, np.int64) for c in zip(*rows))
    config = {"gap_ms": 10_000, "window_ms": 1000, "depth": 4,
              "width": 2048}
    emitted = [(0, None, lambda: (keys, items, ts, (1, 2)))]
    good = [(1, 1_000, 29_000, 3, 2, 1), (1, 40_000, 50_000, 1, 0, 0),
            (2, 5_000, 15_000, 1, 0, 1), (2, 15_001, 25_001, 1, 0, 1),
            (3, 7_000, 27_000, 2, 2, 0)]
    return config, emitted, good


def results_of(rows):
    cols = [[r[0] for r in rows],
            [(r[2] - 1) // 1000 * 1000 for r in rows],
            *[[r[c] for r in rows] for c in range(1, 6)]]
    return {0: tuple(np.array(c, np.int64) for c in cols)}


def check(rows):
    reference = loader.load_module("references", "session_countmin")
    config, emitted, _ = tiny_case()
    return reference.check(config, emitted, results_of(rows))


def test_the_reference_accepts_the_exact_rows():
    good = tiny_case()[2]
    verdict = check(good)
    assert verdict["failed"] == 0 and verdict["problems"] == []
    assert verdict["attempted"] == 5 * 3
    assert verdict["facts"]["sessions"] == 5
    assert verdict["facts"]["events"] == 8
    # an over-count within the bound's share is sound: one pair of 10
    # over its bound of floor(e / 2048 x 3) = 0 is 10% > 1.83%, so it
    # fails; the same in a set of 100 pairs would not
    over = [(1, 1_000, 29_000, 3, 2, 2)] + good[1:]
    assert check(over)["failed"] == 1


DOCTORED = {
    "missing": lambda g: g[1:],
    "duplicated": lambda g: g + [g[2]],
    "under_counted": lambda g: [(1, 1_000, 29_000, 3, 1, 1)] + g[1:],
    "answers_with_the_total": lambda g: [
        (k, s, e, t, t, t) for k, s, e, t, _, _ in g],
    "wrong_total": lambda g: [(1, 1_000, 29_000, 4, 2, 1)] + g[1:],
    "front_growth_dropped": lambda g: [(1, 9_000, 29_000, 2, 1, 1)] + g[1:],
    "merge_dropped": lambda g: [(3, 7_000, 17_000, 1, 1, 0),
                                (3, 17_000, 27_000, 1, 1, 0)] + g[:4],
    "abutting_not_split": lambda g: g[:2] + [(2, 5_000, 25_001, 2, 0, 2)]
    + g[4:],
    "table_update_skipped": lambda g: [
        (k, s, e, t, 0, 0) for k, s, e, t, _, _ in g],
}


@pytest.mark.parametrize("name", sorted(DOCTORED))
def test_the_reference_fails_doctored_rows(name):
    verdict = check(DOCTORED[name](copy.deepcopy(tiny_case()[2])))
    assert verdict["failed"] > 0 and verdict["problems"], name
    words = " ".join(verdict["problems"])
    expect = {"missing": "1 sessions missing",
              "duplicated": "1 rows duplicated",
              "under_counted": "below the exact count",
              "answers_with_the_total": "beyond exact",
              "wrong_total": "totals differ",
              "front_growth_dropped": "1 sessions missing, 1 rows",
              "merge_dropped": "1 sessions missing, 2 rows",
              "abutting_not_split": "2 sessions missing, 1 rows",
              "table_update_skipped": "below the exact count"}[name]
    assert expect in words, (name, words)


def test_a_row_under_another_period_fails():
    config, emitted, good = tiny_case()
    reference = loader.load_module("references", "session_countmin")
    results = results_of(good)
    results[0][1][0] += 1000
    verdict = reference.check(config, emitted, results)
    assert verdict["failed"] == 1
    assert "another period" in verdict["problems"][0]


def test_the_bound_is_the_papers():
    """eps = e / width of the session's mass, delta = e^-depth."""
    config, emitted, good = tiny_case()
    reference = loader.load_module("references", "session_countmin")
    big = 100_000
    keys = np.full(big, 5, np.int64)
    items = np.arange(big, dtype=np.int64) % 1000 + 10
    ts = np.arange(big, dtype=np.int64)
    emitted = [(0, None, lambda: (keys, items, ts, (1, 2)))]
    allowed = math.floor(math.e / 2048 * big)
    assert allowed == 132
    ok = {0: tuple(np.array([c], np.int64) for c in
                   (5, (big - 1 + 10_000 - 1) // 1000 * 1000, 0,
                    big - 1 + 10_000, big, allowed, 0))}
    assert reference.check(config, emitted, ok)["failed"] == 0
    ok[0][5][0] = allowed + 1
    verdict = reference.check(config, emitted, ok)
    assert verdict["failed"] == 1    # 1 pair of 2 > e^-4
    assert verdict["facts"]["allowed_share"] == math.exp(-4)


def large_case(large, small=200, per=2000):
    """`large` keys with one session of `per` events of items no one
    tracks (a bound of floor(e / 2048 x 2000) = 2), `small` keys with
    one event each; every estimate exact."""
    keys = np.concatenate([np.repeat(np.arange(large), per),
                           large + np.arange(small)]).astype(np.int64)
    items = np.full(len(keys), 9, np.int64)
    ts = np.concatenate([np.tile(np.arange(per), large),
                         np.zeros(small)]).astype(np.int64)
    ends = [per - 1 + 10_000] * large + [10_000] * small
    totals = [per] * large + [1] * small
    rows = [np.arange(large + small), [(e - 1) // 1000 * 1000 for e in ends],
            [0] * (large + small), ends, totals,
            [0] * (large + small), [0] * (large + small)]
    return ([(0, None, lambda: (keys, items, ts, (1, 2)))],
            {0: tuple(np.array(c, np.int64) for c in rows)})


@pytest.mark.parametrize("large,over,failed", [
    (40, 1, 0),    # 1 of 80 large pairs: 1.25% < e^-4
    (40, 3, 3),    # 3 of 80: 3.75%, though 3 of 480 pairs in all
    (20, 3, 0),    # 40 large pairs: too few for a share of e^-4
])
def test_the_large_sessions_share_is_taken_alone(large, over, failed):
    """A fault that only sessions with a bound of 1 and more can show
    (one row read of the four) hides among the pairs of small
    sessions, whose estimates are exact in any table."""
    config = tiny_case()[0]
    reference = loader.load_module("references", "session_countmin")
    emitted, results = large_case(large)
    assert reference.check(config, emitted, results)["failed"] == 0
    results[0][5][:over] = 3   # one past the bound of 2
    verdict = reference.check(config, emitted, results)
    facts = verdict["facts"]
    assert facts["large_pairs_compared"] == 2 * large
    assert facts["large_estimates_over_bound"] == over
    assert facts["over_bound_share"] < facts["allowed_share"]
    assert verdict["failed"] == failed
    assert bool(failed) == ("bound is at least 1"
                            in " ".join(verdict["problems"]))


def test_the_copy_under_tests_is_the_reference_byte_for_byte():
    here = (loader.BENCH_DIR / "references" / "session_countmin.py")
    there = loader.BENCH_DIR.parent / "tests" \
        / "session_countmin_reference.py"
    assert here.read_bytes() == there.read_bytes()


# ---- by name -------------------------------------------------------------

def test_the_configuration_is_the_source_cut_in_events_alone():
    contract = loader.read_json(loader.CONTRACT)
    entry = next(c for c in contract["configs"] if c["name"] == CONFIG)
    config = loader.read_json(loader.BENCH_DIR / "configs"
                              / f"{CONFIG}.json")
    sliding = loader.read_json(loader.BENCH_DIR / "configs"
                               / "tdigest_sliding_10m.json")
    assert entry["reduced"] == config["reduced"] == ["events_per_window"]
    assert list(config["reduced_why"]) == ["events_per_window"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # the source's shapes, uncut
    assert (config["gap_ms"], config["window_ms"]) == (10000, 1000)
    assert config["key_space"] == 1_000_000
    assert config["item_space"] == 1 << 20
    assert (config["depth"], config["width"]) == (4, 2048)
    assert config["partitions"] == 4
    assert config["watermark_lag_ms"] == config["window_ms"]
    assert config["batch_rows"] == sliding["batch_rows"] == 8192
    assert config["events_per_window"] \
        == config["partitions"] * config["batch_rows"]
    # the budget alone: every other option of the backend at its default
    assert config["state_backend_config"] == {BUDGET_KEY: 1 << 17}
    assert config["expect"] == sliding["expect"]
    assert config["result_columns"] == [
        "key", "window_start", "session_start", "session_end", "total",
        *[f"est_{i}" for i in range(config["watch_count"])]]
    assert {"heavy hitters", "log"} == set(config["departures"])
    assert {"time", "late_data", "emission", "estimate", "delivery"} \
        == set(config["guarantees"])
    # the sketch the file states is the constructor's own
    from flink_tpu.ops.sketches import CountMinSketchAggregate
    sketch = CountMinSketchAggregate()
    assert (sketch.depth, sketch.width) == (config["depth"],
                                            config["width"])
    cells = [w for w in contract["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "zipf_items_log", 1)]
    assert contract["workloads"][-1]["name"] == CELL
    traffic = loader.read_json(loader.BENCH_DIR / "traffic"
                               / "zipf_items_log.json")
    assert traffic["source"] == "closed_log_replay"
    assert traffic["key_distribution"] == "zipf"
    assert traffic["params"] == {"exponent": 0.99, "item_exponent": 0.99}
    metrics = {m["name"]: m for m in contract["per_layer"]}
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL], name
        loader.load_module("layer_metrics", name)
    assert [m["name"] for m in contract["per_layer"][-6:]] \
        == list(NEW_METRICS)
    assert len(contract["configs"]) == 6 and len(contract["workloads"]) == 8
    assert len(contract["per_layer"]) == 47


def test_bytes_functions_follow_the_programs_shapes(monkeypatch):
    import session
    config = loader.read_json(loader.BENCH_DIR / "configs"
                              / f"{CONFIG}.json")
    assert session.update_row_bytes(config) == 4 * 8 + 8 + 12
    assert session.result_row_bytes(config) == 4 * 8 * 4 + 4 + 36
    assert session.slot_bytes(config) == 32772
    fires = 20

    def fired(rows, padded):
        monkeypatch.setattr(session, "_marks", {
            "t0": {"result_rows": 10, "result_padded_rows": 16},
            "end": {"result_rows": 10 + rows * fires,
                    "result_padded_rows": 16 + padded * fires}})
        return session.result_rows(run)

    run = {"config": config,
           "events": fires * config["events_per_window"],
           "t0": {"flush_rows": 5, "flush_batches": 1},
           "end": {"flush_rows": 5 + 16384 * 30, "flush_batches": 31}}
    # a slot of 32,772 B: tiles of 4,096 rows, so a fire of 7,500 is
    # two dispatches of 3,750 rows of work each, not one of 7,500
    assert fired(7500, 8192) == 3750
    assert fired(9000, 12288) == 3000
    # a fire under a tile is one dispatch of the power of two above it
    assert fired(3000, 4096) == 3000
    assert fired(100, 128) == 100
    assert session.update_rows(run) == 16384


def test_a_tree_without_the_counters_reads_nothing(monkeypatch):
    import session
    monkeypatch.setattr(session, "_marks", {})
    assert session.counted("batch_rows") is None
    assert session.noted("t0", "live_slots") is None
    run = {"config": {"depth": 4, "watch_count": 8,
                      "events_per_window": 32768},
           "events": 32768, "slice_s": None, "traffic": {},
           "t0": {"flush_rows": 0, "flush_batches": 0},
           "end": {"flush_rows": 0, "flush_batches": 0}}
    assert session.result_rows(run) is None
    assert session.update_rows(run) is None
    for name in NEW_METRICS[4:]:
        reader = loader.load_module("layer_metrics", name)
        assert reader.read(run) is None, name

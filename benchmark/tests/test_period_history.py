"""The measured periods picked out of a synthetic history of fire
periods, the cases in which nothing is read, the eight readers over
it, and a traced rehearsal's ``[periods]`` line."""

import json

import pytest

import loader
import period_history
from test_harness import ROOT, last_line, run_cell

READERS = ("gc_interval_share", "fire_gc_share", "fire_release_share",
           "state_hash_interval_share", "state_slot_interval_share",
           "state_device_wait_interval_share", "fire_emit_interval_share",
           "phase_coverage_interval_share")
TUMBLING = {"window_ms": 1000, "events_per_window": 100}
SLIDING = {"window_ms": 1000, "slide_ms": 1000, "window_size_ms": 10000,
           "events_per_window": 100}


def period(seq, start_s, length_s, window, window_ms=1000, fired=1):
    """One period as ``Tracer.periods()`` gives it: 8 batches of hash
    and slot work, one fire with its emit tail and its release, a
    collection in the hash loop and one in the emit loop."""
    ms = length_s * 1e3
    return {
        "seq": seq, "start_s": start_s, "end_s": start_s + length_s,
        "operator": "WindowOperator", "watermark": window * window_ms + 7,
        "windows": fired, "keys": 50,
        "newest_window_end": (window + 1) * window_ms,
        "phases": {
            "window.ingest": {"count": 8, "total_ms": 0.5 * ms,
                              "self_ms": 0.04 * ms, "gc_under_ms": 0.06 * ms},
            "state.add.hash": {"count": 8, "total_ms": 0.26 * ms,
                               "self_ms": 0.2 * ms, "gc_ms": 0.06 * ms,
                               "gcs": 1, "gc_under_ms": 0.06 * ms},
            "state.add.slots": {"count": 8, "total_ms": 0.2 * ms,
                                "self_ms": 0.2 * ms},
            "window.watermark": {"count": 2, "total_ms": 0.4 * ms,
                                 "self_ms": 0.02 * ms,
                                 "native_ms": 0.01 * ms,
                                 "gc_under_ms": 0.04 * ms},
            "state.get.device": {"count": 1, "total_ms": 0.1 * ms,
                                 "self_ms": 0.1 * ms},
            "window.fire.batch": {"count": 1, "total_ms": 0.24 * ms,
                                  "self_ms": 0.2 * ms, "gc_ms": 0.04 * ms,
                                  "gcs": 1, "gc_under_ms": 0.04 * ms},
            "window.fire.release": {"count": 1, "total_ms": 0.04 * ms,
                                    "self_ms": 0.04 * ms}},
        "gc": {"collections": 3, "gc_ms": 0.11 * ms,
               "by_generation": {0: {"collections": 2, "gc_ms": 0.07 * ms},
                                 2: {"collections": 1, "gc_ms": 0.04 * ms}},
               "unphased": {"collections": 1, "gc_ms": 0.01 * ms}},
        "kernels": {"splitmix64": 0.01 * ms}}


def history(warmup, n, lengths=None, closing_fires=1):
    """``warmup`` warm-up periods twice as long as the measured ones,
    ``n`` measured, the closing one."""
    out, t = [], 100.0
    lengths = lengths or [0.5] * n
    for w in range(warmup):
        out.append(period(len(out), t, 1.0, w))
        t += 1.0
    for i, length in enumerate(lengths):
        out.append(period(len(out), t, length, warmup + i))
        t += length
    out.append(period(len(out), t, 0.05, warmup + n + closing_fires - 1,
                      fired=closing_fires))
    return out


class FakeTracer:
    dropped_periods = 0

    def __init__(self, periods):
        self._periods = periods

    def periods(self):
        return self._periods


def run_of(config, traffic, n, window_s):
    return {"config": config, "traffic": {"source": traffic},
            "events": n * config["events_per_window"] + 7,
            "window_s": window_s, "slice_s": None}


@pytest.mark.parametrize("config, source, warmup", [
    (TUMBLING, "closed_replay", 2),
    (SLIDING, "closed_replay_sliding", 12)], ids=["tumbling", "sliding"])
def test_the_measured_periods_are_picked_by_their_window(config, source,
                                                         warmup):
    run = run_of(config, source, 6, 3.0)
    assert period_history.warmup_windows(config, run["traffic"]) == warmup
    # the sliding job's closing watermark fires every live window at once
    closing = 1 if source == "closed_replay" else 10
    periods = history(warmup, 6, closing_fires=closing)
    t = period_history.read_history(run, FakeTracer(periods))
    assert t["periods"] == 6 and t["first_window"] == warmup
    assert [p["seq"] for p in t["measured"]] == list(range(warmup,
                                                           warmup + 6))
    assert t["interval_s"] == pytest.approx(3.0)
    assert t["length_s"] == {"p50": pytest.approx(0.5),
                             "p90": pytest.approx(0.5),
                             "max": pytest.approx(0.5)}
    hash_ = t["phases"]["state.add.hash"]
    assert hash_["count_mean"] == 8 and hash_["gcs_mean"] == 1
    assert hash_["self_s_mean"] == pytest.approx(0.1)
    assert hash_["self_s_median"] == pytest.approx(0.1)
    assert hash_["gc_s_mean"] == pytest.approx(0.03)
    assert t["gc"]["collections_mean"] == 3
    assert t["gc"]["collections_mean_by_generation"] == {"0": 2, "2": 1}
    assert t["gc"]["gc_s_mean"] == pytest.approx(0.055)
    assert t["gc"]["unphased_s_mean"] == pytest.approx(0.005)
    assert t["kernels_s_mean"] == {"splitmix64": pytest.approx(0.005)}
    # without the warm-up count: the n periods before the closing one
    assert period_history.measured(periods, 1000, 6) == t["measured"]


def test_a_share_is_a_sum_over_the_periods_over_the_sum_of_their_lengths():
    """Periods of unlike lengths: the long one weighs more than a mean
    of the periods' own shares would give it."""
    lengths = [0.4, 0.4, 1.6]
    periods = history(2, 3, lengths)
    periods[4]["phases"]["state.add.hash"]["self_ms"] = 0.5 * 1600
    # outside the two top-level phases as long as the short periods are
    periods[4]["phases"]["window.ingest"]["total_ms"] = 1600 - 640 - 40
    run = run_of(TUMBLING, "closed_replay", 3, sum(lengths))
    t = period_history.read_history(run, FakeTracer(periods))
    assert t["stalls_s"] == pytest.approx(0.0, abs=1e-9)
    assert t["length_s"]["p50"] == pytest.approx(0.4)
    assert t["length_s"]["max"] == pytest.approx(1.6)
    got = 100 * t["phases"]["state.add.hash"]["self_s_sum"] / t["interval_s"]
    assert got == pytest.approx(100 * (0.08 + 0.08 + 0.8) / 2.4)


def test_the_profilers_stalls_are_taken_out_before_the_lengths_are_compared():
    """The harness's ``window_s`` leaves out the profiler's start and
    stop; they run between two batches, in no phase, so a period that
    holds one is longer by it with no phase the longer."""
    periods = history(2, 6)
    for i, stall in ((3, 0.05), (4, 0.7)):
        periods[i]["end_s"] += stall
        for later in periods[i + 1:]:
            later["start_s"] += stall
            later["end_s"] += stall
    run = run_of(TUMBLING, "closed_replay", 6, 3.0)
    t = period_history.read_history(run, FakeTracer(periods))
    assert t["stalls_s"] == pytest.approx(0.75)
    assert t["interval_s"] == pytest.approx(3.75)  # the shares' base
    assert t["length_s"]["max"] == pytest.approx(1.2)
    # the same 0.75 s spread over every period is no stall: refused
    periods = history(2, 6, [0.625] * 6)
    for p in periods[2:8]:
        for row in p["phases"].values():
            row["total_ms"] *= 0.8
    assert period_history.read_history(run, FakeTracer(periods)) is None


@pytest.mark.parametrize("case", ["count", "length", "no_periods",
                                  "no_closing", "gap", "dropped"])
def test_nothing_is_read_where_the_measured_periods_cannot_be_told(case):
    periods = history(2, 6)
    run = run_of(TUMBLING, "closed_replay", 6, 3.0)
    tracer = FakeTracer(periods)
    if case == "count":
        run = run_of(TUMBLING, "closed_replay", 7, 3.5)
    elif case == "length":
        run["window_s"] = 3.0 * 1.03
    elif case == "no_periods":
        tracer = object()  # the parent's tracer has no periods()
    elif case == "no_closing":
        tracer = FakeTracer(periods[:-1])
    elif case == "gap":
        tracer = FakeTracer(periods[:4] + periods[5:] + [
            period(9, 110.0, 0.05, 9)])
    elif case == "dropped":
        tracer = FakeTracer(periods[3:])  # the ring lost the first ones
    assert period_history.read_history(run, tracer) is None
    # within the tolerance the same history reads
    if case == "length":
        run["window_s"] = 3.0 * 1.015
        assert period_history.read_history(run, tracer)["periods"] == 6


@pytest.fixture
def read(monkeypatch):
    """A reader by name over the synthetic history's table."""
    run = run_of(TUMBLING, "closed_replay", 6, 3.0)
    t = period_history.read_history(run, FakeTracer(history(2, 6)))
    monkeypatch.setattr(period_history, "table", lambda run: t)
    return lambda name: loader.load_module("layer_metrics", name).read(run)


def test_the_eight_readers_over_the_synthetic_history(read):
    assert read("gc_interval_share") == pytest.approx(11.0)
    # under window.watermark 0.04 of 0.4 of every period
    assert read("fire_gc_share") == pytest.approx(10.0)
    assert read("fire_release_share") == pytest.approx(4.0)
    assert read("state_hash_interval_share") == pytest.approx(20.0)
    assert read("state_slot_interval_share") == pytest.approx(20.0)
    assert read("state_device_wait_interval_share") == pytest.approx(10.0)
    assert read("fire_emit_interval_share") == pytest.approx(20.0)
    # the two top-level phases' own 0.04 + 0.02 of their 0.5 + 0.4,
    # 0.01 of it in a native kernel called straight from the watermark
    assert read("phase_coverage_interval_share") == pytest.approx(
        100 * (1 - 0.05 / 0.9))


def test_every_reader_loads_by_name_and_reads_nothing_from_an_empty_run(
        monkeypatch):
    contract = loader.read_json(loader.CONTRACT)
    by_name = {m["name"]: m for m in contract["per_layer"]}
    assert set(READERS) <= set(by_name)
    # appended in this order (not pinned to the tail: a later PR appends)
    names = [m["name"] for m in contract["per_layer"]]
    first = names.index(READERS[0])
    assert names[first:first + 8] == list(READERS) and first >= 33
    cells = {w["name"] for w in contract["workloads"]}
    for name in READERS:
        assert by_name[name]["source"] == "program_span"
        assert set(by_name[name].get("workloads", cells)) <= cells
    # a program without periods(): the parent's
    monkeypatch.setattr(period_history, "table", lambda run: None)
    for name in READERS:
        reader = loader.load_module("layer_metrics", name)
        assert reader.read({"slice_s": None}) is None, name
    # a history in which none of a reader's phases occurred
    empty = {"interval_s": 1.0, "phases": {},
             "gc": {"gc_s_sum": 0.0}}
    monkeypatch.setattr(period_history, "table", lambda run: empty)
    for name in READERS:
        reader = loader.load_module("layer_metrics", name)
        expected = 0.0 if name == "gc_interval_share" else None
        assert reader.read({}) == expected, name


def test_a_traced_rehearsal_prints_a_periods_line_and_the_eight_metrics():
    proc = run_cell(ROOT, "--workload", "state_hll_1m.uniform", "--seed",
                    "5", "--seconds", "1", "--trace", "1", "--rehearse-cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = last_line(proc)
    [line] = [ln for ln in proc.stdout.splitlines()
              if ln.startswith("[periods] ")]
    t = json.loads(line[len("[periods] "):])
    [window] = [json.loads(ln[len("[window] "):])
                for ln in proc.stdout.splitlines()
                if ln.startswith("[window] ")]
    assert t["periods"] == window["measured_windows"]
    assert t["interval_s"] == pytest.approx(window["window_s"], rel=0.02)
    assert t["first_window"] == 2 and t["dropped_periods"] == 0
    assert t["phases"]["window.watermark"]["count_mean"] >= 1
    assert t["phases"]["window.fire.release"]["count_mean"] == 1
    assert "measured" not in t
    for name in READERS:
        assert f"rehearsal_{name}" in result["metrics"], name

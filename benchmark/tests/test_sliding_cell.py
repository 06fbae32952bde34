"""The cell ``tdigest_sliding_10m.zipf``, rehearsed on the CPU: config
#3 on the state route with its device-slot budget set in the
environment's ``Configuration``; the run is ``correct`` against exact
order statistics, takes the route it names, writes ten state rows an
event with the spill tier idle, and its traced run reads the metrics a
CPU trace can carry."""

import json

import pytest

import loader
from test_harness import ROOT, last_line, run_cell

CELL = "tdigest_sliding_10m.zipf"
CONFIG = "tdigest_sliding_10m"
BUDGET_KEY = "state.backend.tpu.max-device-slots"
NEW_METRICS = ("sliding_fanout_share", "sliding_state_ingest_share",
               "state_rows_per_event", "quantile_fire_device_share",
               "quantile_update_roofline", "quantile_result_roofline")


def rehearse(trace):
    proc = run_cell(ROOT, "--workload", CELL, "--seed", "3300000019",
                    "--seconds", "1", "--trace", trace, "--rehearse-cpu")
    lines = {tag: json.loads(line[len(tag) + 2:])
             for line in proc.stdout.splitlines()
             for tag in ("route", "check", "data")
             if line.startswith(f"[{tag}]")}
    return last_line(proc), lines


def test_the_cell_rehearses_correct_with_ten_rows_an_event():
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
    assert route["table_bytes"] == route["slots"] * 4 * config["buckets"]
    assert route["evictions"] == route["promotions"] == 0
    assert route["budget_overruns"] == 0
    measured = route["in_measured_windows"]
    assert measured["rows_per_event"] == 10.0
    assert measured["evicted_rows"] == measured["budget_overruns"] == 0
    assert lines["data"]["warmup_windows"] == 12
    assert lines["check"]["max_rel_err"] <= lines["check"]["bound"] \
        == 0.0102


def test_the_traced_rehearsal_reads_the_sliding_metrics():
    out, _ = rehearse("1")
    assert out["correct"] is True and out["failed"] == 0
    value = {n[len("rehearsal_"):]: m["value"]
             for n, m in out["metrics"].items()}
    assert {"sliding_fanout_share", "sliding_state_ingest_share",
            "state_rows_per_event", "quantile_fire_device_share",
            "fire_emit_share", "phase_coverage_share",
            "compiles_in_window"} <= set(value)
    assert value["compiles_in_window"] == 0
    assert value["state_rows_per_event"] == 10.0
    for name in ("sliding_fanout_share", "sliding_state_ingest_share",
                 "quantile_fire_device_share"):
        assert 0 < value[name] < 100, name
    assert value["phase_coverage_share"] >= 90
    # no device plane in a CPU trace: the rooflines stay silent, as do
    # the listed metrics of the cells this one is not
    assert not set(value) & {"quantile_update_roofline",
                             "quantile_result_roofline",
                             "state_slot_share", "spill_ingest_share",
                             "door_ingest_share", "native_host_share"}


def test_the_configuration_is_the_source_cut_in_events_alone():
    contract = loader.read_json(loader.CONTRACT)
    entry = next(c for c in contract["configs"] if c["name"] == CONFIG)
    config = loader.read_json(loader.BENCH_DIR / "configs"
                              / f"{CONFIG}.json")
    spill = loader.read_json(loader.BENCH_DIR / "configs" / "hll_10m.json")
    assert entry["reduced"] == config["reduced"] == ["events_per_window"]
    assert list(config["reduced_why"]) == ["events_per_window"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    # the source's shapes, uncut
    assert (config["window_size_ms"], config["slide_ms"]) == (10000, 1000)
    assert config["window_ms"] == config["slide_ms"]
    assert config["key_space"] == 10_000_000
    assert config["quantiles"] == [0.5, 0.99]
    assert config["batch_rows"] == spill["batch_rows"] == 8192
    assert config["events_per_window"] % config["batch_rows"] == 0
    assert config["state_backend_config"] == {BUDGET_KEY: 1 << 19}
    assert config["expect"] == spill["expect"]
    assert config["result_columns"] == ["key", "window_start", "p50", "p99"]
    assert "t-digest" in config["departures"]["sketch"]
    assert {"time", "emission", "estimate", "late_data"} \
        == set(config["guarantees"])
    # the sketch the file states is the constructor's own
    from flink_tpu.ops.sketches import QuantileSketchAggregate
    sketch = QuantileSketchAggregate(tuple(config["quantiles"]))
    assert sketch.buckets == config["buckets"]
    assert abs((sketch.gamma - 1) / (sketch.gamma + 1)
               - config["relative_accuracy"]) < 1e-12
    cells = [w for w in contract["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "zipf_values", 1)]
    traffic = loader.read_json(loader.BENCH_DIR / "traffic"
                               / "zipf_values.json")
    assert traffic["source"] == "closed_replay_sliding"
    assert traffic["params"] == {"exponent": 0.99, "value_mu": 3.0,
                                 "value_sigma": 1.0}
    metrics = {m["name"]: m for m in contract["per_layer"]}
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL], name
    assert [m["name"] for m in contract["per_layer"][-6:]] \
        == list(NEW_METRICS)


@pytest.mark.parametrize("fired, expect", [
    (75_300, 16384), (20_000, 16384), (5_000, 8192), (1, 1)])
def test_bytes_functions_follow_the_programs_shapes(fired, expect,
                                                    monkeypatch):
    """What the roofline metrics divide: the rows one dispatch of each
    program moves, and the bytes of a row."""
    import sliding
    from flink_tpu.core.keygroups import KeyGroupRange
    from flink_tpu.core.state import AggregatingStateDescriptor
    from flink_tpu.ops.sketches import QuantileSketchAggregate
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend
    config = loader.read_json(loader.BENCH_DIR / "configs"
                              / f"{CONFIG}.json")
    assert sliding.slot_bytes(config) == 8300
    assert sliding.UPDATE_ROW_BYTES == 17
    assert sliding.result_bytes(16384, config) == 16384 * 8308
    fires = 20
    monkeypatch.setattr(sliding, "_marks", {
        "t0": {"result_rows": 10, "result_padded_rows": 16},
        "end": {"result_rows": 10 + fired * fires,
                "result_padded_rows": 16 + 81920 * fires}})
    run = {"config": config,
           "events": fires * config["events_per_window"],
           "t0": {"flush_rows": 5, "flush_batches": 1},
           "end": {"flush_rows": 5 + 16384 * 30, "flush_batches": 31}}
    assert sliding.result_rows(run) == expect
    assert sliding.update_rows(run) == 16384
    # the program's own tile and padding, at a capacity small enough
    # to allocate
    st = TpuKeyedStateBackend(
        KeyGroupRange(0, 127), 128,
        initial_capacity=8).get_or_create_keyed_state(
        AggregatingStateDescriptor("s", QuantileSketchAggregate()))
    assert st._bytes_per_slot() == sliding.slot_bytes(config)
    assert min(1 << (fired - 1).bit_length(), st._result_tile()) == expect


def test_a_tree_without_the_counters_reads_nothing(monkeypatch):
    import sliding
    monkeypatch.setattr(sliding, "_marks", {})
    assert sliding.counted("batch_rows") is None
    assert sliding.rows_per_event() is None
    run = {"config": {"buckets": 2075, "events_per_window": 16384},
           "events": 16384, "slice_s": None,
           "t0": {"flush_rows": 0, "flush_batches": 0},
           "end": {"flush_rows": 0, "flush_batches": 0}}
    assert sliding.result_rows(run) is None
    assert sliding.update_rows(run) is None
    for name in NEW_METRICS:
        reader = loader.load_module("layer_metrics", name)
        assert reader.read(run) is None, name

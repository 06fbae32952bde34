"""The cell ``tdigest_literal.zipf_values``, rehearsed on the CPU: config
#3 with a merging t-digest on the state route; the run is ``correct``
against the plain reference (``references/tdigest_sliding.py``), takes
the route it names, compresses in ingest, writes ten state rows an
event with the spill tier idle, and its traced run reads the metrics a
CPU trace can carry."""

import json

import numpy as np

import loader
from test_harness import ROOT, last_line, run_cell

CELL = "tdigest_literal.zipf_values"
CONFIG = "tdigest_literal"
BUDGET_KEY = "state.backend.tpu.max-device-slots"
NEW_METRICS = ("tdigest_update_roofline", "tdigest_result_roofline",
               "tdigest_compress_per_event")
BORROWED = ("sliding_fanout_share", "sliding_state_ingest_share",
            "state_rows_per_event", "quantile_fire_device_share",
            "fire_release_share", "state_hash_interval_share",
            "state_slot_interval_share", "state_device_wait_interval_share",
            "state_device_wait_share", "state_flush_rows_mean")


def rehearse(trace):
    proc = run_cell(ROOT, "--workload", CELL, "--seed", "4400000019",
                    "--seconds", "1", "--trace", trace, "--rehearse-cpu")
    lines = {tag: json.loads(line[len(tag) + 2:])
             for line in proc.stdout.splitlines()
             for tag in ("route", "check", "data")
             if line.startswith(f"[{tag}]")}
    return last_line(proc), lines


def test_the_cell_rehearses_correct_and_compresses_in_ingest():
    out, lines = rehearse("0")
    assert out["rehearsal"] is True and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    route = lines["route"]
    assert route["operator"] == "WindowOperator"
    assert route["route"].endswith("TpuKeyedStateBackend.add_batch -> "
                                   "DeviceAggregatingState")
    config = loader.read_json(loader.BENCH_DIR / "configs"
                              / f"{CONFIG}.json")
    assert route["budget"] == config["rehearsal"][
        "state_backend_config"][BUDGET_KEY]
    assert route["table_bytes"] == route["slots"] * config["slot_bytes"]
    assert route["evictions"] == route["promotions"] == 0
    measured = route["in_measured_windows"]
    assert measured["rows_per_event"] == 10.0
    assert measured["tdigest_ingest_compresses"] > 0
    assert measured["tdigest_fired_entries"] >= measured["result_rows"] > 0
    check = lines["check"]
    assert check["max_rel_err"] <= check["tolerance"]
    assert check["max_rank_err_units"] <= check["rank_bound_units"]
    assert check["flagged_digests"] < check["digests"] // 100


def test_the_traced_rehearsal_reads_the_counter_and_no_device_metric():
    out, _ = rehearse("1")
    assert out["correct"] is True and out["failed"] == 0
    value = {n[len("rehearsal_"):]: m["value"]
             for n, m in out["metrics"].items()}
    assert {"tdigest_compress_per_event", "compiles_in_window",
            *BORROWED} <= set(value)
    assert value["compiles_in_window"] == 0
    assert value["state_rows_per_event"] == 10.0
    assert 0 < value["tdigest_compress_per_event"] < 0.05
    # no device plane in a CPU trace: the rooflines stay silent, as do
    # the DDSketch cell's own
    assert not set(value) & {"tdigest_update_roofline",
                             "tdigest_result_roofline",
                             "quantile_update_roofline",
                             "quantile_result_roofline"}


def test_the_configuration_is_the_sliding_deployment_with_the_sketch_swapped():
    contract = loader.read_json(loader.CONTRACT)
    entry = next(c for c in contract["configs"] if c["name"] == CONFIG)
    config = loader.read_json(loader.BENCH_DIR / "configs"
                              / f"{CONFIG}.json")
    ddsketch = loader.read_json(loader.BENCH_DIR / "configs"
                                / "tdigest_sliding_10m.json")
    assert entry["reduced"] == config["reduced"] == ["events_per_window"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    for key in ("window_ms", "window_size_ms", "slide_ms", "key_space",
                "key_dtype", "quantiles", "events_per_window", "batch_rows",
                "state_backend", "state_backend_config", "expect",
                "result_columns", "rehearsal"):
        assert config[key] == ddsketch[key], key
    from flink_tpu.ops.tdigest import TDigestAggregate
    agg = TDigestAggregate(tuple(config["quantiles"]))
    assert (agg.delta, agg.buffer, agg.centroids) == (
        config["delta"], config["buffer"], config["centroids"]) \
        == (100, 512, 128)
    assert config["slot_bytes"] == sum(
        max(1, int(np.prod(s.shape))) * s.dtype.itemsize
        for s in agg.state_specs().values()) == 3088
    cells = [w for w in contract["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "zipf_values", 1)]
    metrics = {m["name"]: m for m in contract["per_layer"]}
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL]
    for name in BORROWED:
        assert CELL in metrics[name]["workloads"]
    assert (loader.BENCH_DIR / "references" / "tdigest_sliding.py"
            ).read_bytes() == (loader.BENCH_DIR.parent / "tests"
                               / "tdigest_reference.py").read_bytes()

"""The cell ``hll_1m_default_door.uniform``, rehearsed on the CPU: the
job a user writes, nothing pinned, reaches ``DeviceWindowOperator``'s
batch door over the log engine, is ``correct`` against
``hll_tumbling``, and its traced run reads every phase metric."""

import json

import pytest

import loader
from test_harness import ROOT, last_line, run_cell

CELL = "hll_1m_default_door.uniform"


def rehearse(trace):
    proc = run_cell(ROOT, "--workload", CELL, "--seed", "3000000019",
                    "--seconds", "1", "--trace", trace, "--rehearse-cpu")
    route = next(json.loads(line[len("[route]"):])
                 for line in proc.stdout.splitlines()
                 if line.startswith("[route]"))
    return last_line(proc), route


def test_the_cell_rehearses_correct_on_the_route_it_names():
    out, route = rehearse("0")
    assert out["rehearsal"] is True and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"rehearsal_events_per_s",
                                   "rehearsal_fire_p50_ms",
                                   "rehearsal_setup_s"}
    assert route["operator"] == "DeviceWindowOperator"
    assert route["route"].endswith("DeviceWindowOperator.process_batch "
                                   "-> LogStructuredTumblingWindows")
    assert route["boxed_fallbacks"] == 0 and route["columnar_rows"] > 0


def test_the_traced_rehearsal_reads_every_phase_metric():
    out, _ = rehearse("1")
    assert out["correct"] is True and out["failed"] == 0
    names = {n[len("rehearsal_"):] for n in out["metrics"]}
    assert {"door_ingest_share", "door_engine_fire_share",
            "fire_emit_share", "fire_downstream_share",
            "phase_coverage_share", "window_op_ingest_share",
            "window_op_fire_share", "source_host_share",
            "compiles_in_window", "compile_window_share"} <= names
    value = {n: out["metrics"]["rehearsal_" + n]["value"] for n in names}
    assert value["compiles_in_window"] == 0
    assert 0 < value["door_ingest_share"] < 100
    assert 0 < value["door_engine_fire_share"] < 100
    # the metrics of the other two routes stay silent here
    assert not names & {"state_slot_share", "timers_share",
                        "log_concat_share", "native_host_share"}


def test_the_configuration_is_the_source_at_its_own_size():
    contract = loader.read_json(loader.CONTRACT)
    entry = next(c for c in contract["configs"]
                 if c["name"] == "hll_1m_default_door")
    config = loader.read_json(loader.BENCH_DIR / "configs"
                              / "hll_1m_default_door.json")
    state = loader.read_json(loader.BENCH_DIR / "configs"
                             / "state_hll_1m.json")
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    assert config["events_per_window"] == 1 << 22
    assert config["batch_rows"] == 1 << 19
    assert config["guarantees"] == state["guarantees"]
    assert config["reference"] == state["reference"] == "hll_tumbling"
    for key in ("window_ms", "key_space", "key_dtype", "user_bits",
                "hll_precision", "result_columns"):
        assert config[key] == state[key], key
    metrics = {m["name"]: m for m in contract["per_layer"]}
    for name in ("door_ingest_share", "door_engine_fire_share"):
        assert metrics[name]["workloads"] == [
            "hll_1m_default_door.uniform"]

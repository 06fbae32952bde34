"""The cell ``hll_10m.uniform``, rehearsed on the CPU: config #2 at a
key space its device-slot budget cannot hold, the budget set in the
environment's ``Configuration``; the run is ``correct`` against
``hll_tumbling``, takes the route it names, evicts, promotes and fires
from host RAM, and its traced run reads every spill metric."""

import json

import pytest

import loader
from test_harness import ROOT, last_line, run_cell

CELL = "hll_10m.uniform"
BUDGET_KEY = "state.backend.tpu.max-device-slots"


def rehearse(trace):
    proc = run_cell(ROOT, "--workload", CELL, "--seed", "3100000019",
                    "--seconds", "1", "--trace", trace, "--rehearse-cpu")
    route = next(json.loads(line[len("[route]"):])
                 for line in proc.stdout.splitlines()
                 if line.startswith("[route]"))
    return last_line(proc), route


def test_the_cell_rehearses_correct_under_its_budget():
    out, route = rehearse("0")
    assert out["rehearsal"] is True and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"rehearsal_events_per_s",
                                   "rehearsal_fire_p50_ms",
                                   "rehearsal_setup_s"}
    assert route["operator"] == "WindowOperator"
    assert route["route"].endswith("TpuKeyedStateBackend.add_batch -> "
                                   "DeviceAggregatingState")
    budget = loader.read_json(loader.BENCH_DIR / "configs" / "hll_10m.json")[
        "rehearsal"]["state_backend_config"][BUDGET_KEY]
    assert route["budget"] == route["slots"] == budget
    assert route["register_bytes"] == budget * 4096
    assert route["evictions"] > 0 and route["promotions"] > 0
    assert route["budget_overruns"] == 0
    measured = route["in_measured_windows"]
    assert measured["evicted_rows"] > 0 and measured["promoted_rows"] > 0
    assert measured["spill_fired_rows"] > 0
    assert measured["budget_overruns"] == 0


def test_the_traced_rehearsal_reads_every_spill_metric():
    out, _ = rehearse("1")
    assert out["correct"] is True and out["failed"] == 0
    value = {n[len("rehearsal_"):]: m["value"]
             for n, m in out["metrics"].items()}
    assert {"spill_ingest_share", "spill_fire_share",
            "spill_rows_per_window", "fire_emit_share",
            "phase_coverage_share", "compiles_in_window"} <= set(value)
    assert value["compiles_in_window"] == 0
    assert 0 < value["spill_ingest_share"] < 100
    assert 0 < value["spill_fire_share"] < 100
    assert value["spill_rows_per_window"] > 0
    assert value["phase_coverage_share"] >= 90
    # no device plane in a CPU trace: the rooflines stay silent, as do
    # the listed metrics of the cells this one is not
    assert not set(value) & {"state_evict_roofline",
                             "state_promote_roofline", "state_slot_share",
                             "door_ingest_share", "native_host_share"}


def test_the_configuration_is_the_source_cut_in_events_alone():
    contract = loader.read_json(loader.CONTRACT)
    entry = next(c for c in contract["configs"] if c["name"] == "hll_10m")
    config = loader.read_json(loader.BENCH_DIR / "configs" / "hll_10m.json")
    state = loader.read_json(loader.BENCH_DIR / "configs"
                             / "state_hll_1m.json")
    assert entry["reduced"] == config["reduced"] == ["events_per_window"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    assert config["key_space"] == 10_000_000
    assert config["events_per_window"] % config["batch_rows"] == 0
    assert config["state_backend_config"] == {BUDGET_KEY: 1 << 20}
    assert config["guarantees"] == state["guarantees"]
    # hll_tumbling's check, through the module that lets keys up to
    # 2^24 into its exact count
    assert state["reference"] == "hll_tumbling"
    assert config["reference"] == "hll_tumbling_24bit"
    wide = loader.load_module("references", "hll_tumbling_24bit")
    plain = loader.load_module("references", "hll_tumbling")
    assert wide.check.__code__.co_code == plain.check.__code__.co_code
    assert wide.check.__globals__["exact_distinct"] is wide.exact_distinct
    assert config["expect"] == state["expect"]
    for key in ("window_ms", "key_dtype", "user_bits", "hll_precision",
                "batch_rows", "result_columns", "state_backend"):
        assert config[key] == state[key], key
    cells = [w for w in contract["workloads"] if w["config"] == "hll_10m"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "uniform", 1)]
    metrics = {m["name"]: m for m in contract["per_layer"]}
    for name in ("spill_ingest_share", "spill_fire_share",
                 "spill_rows_per_window", "state_evict_roofline",
                 "state_promote_roofline"):
        assert metrics[name]["workloads"] == [CELL]


@pytest.mark.parametrize("rows, micro, expect", [
    (1 << 20, None, (262144, 1024)), (1024, 64, (256, 64))])
def test_bytes_functions_follow_the_programs_shapes(rows, micro, expect):
    """What the roofline metrics divide: the rows one dispatch of each
    program moves, read once and written once."""
    import spill
    from flink_tpu.core.keygroups import KeyGroupRange
    from flink_tpu.core.state import AggregatingStateDescriptor
    from flink_tpu.ops.sketches import HyperLogLogAggregate
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend
    config = {"hll_precision": 12,
              "state_backend_config": {BUDGET_KEY: rows}}
    kw = {}
    if micro is not None:
        config["state_backend_config"][
            "state.backend.tpu.microbatch-size"] = micro
        kw["microbatch"] = micro
    assert (spill.evict_rows(config), spill.promote_rows(config)) == expect
    assert spill.moved_bytes(expect[0], config) == 2 * expect[0] * 4096
    # the program's own tile, at a capacity small enough to allocate
    st = TpuKeyedStateBackend(
        KeyGroupRange(0, 127), 128, initial_capacity=8, max_device_slots=8,
        **kw).get_or_create_keyed_state(
        AggregatingStateDescriptor("s", HyperLogLogAggregate(12)))
    assert st._promote_tile() == expect[1]


def test_the_24bit_reference_counts_keys_the_plain_one_refuses():
    import numpy as np
    wide = loader.load_module("references", "hll_tumbling_24bit")
    plain = loader.load_module("references", "hll_tumbling")
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 10_000_000, 50_000)
    keys[:4] = [9_999_999, 9_999_999, 0, (1 << 23)]
    users = rng.integers(0, 1 << 40, len(keys))
    users[:2] = (1 << 40) - 1  # the same user twice: one distinct
    with pytest.raises(ValueError):
        plain.exact_distinct(keys, users)
    k, c = wide.exact_distinct(keys, users)
    want = {}
    for key, user in zip(keys.tolist(), users.tolist()):
        want.setdefault(key, set()).add(user)
    assert k.tolist() == sorted(want)
    assert c.tolist() == [len(want[key]) for key in sorted(want)]
    assert c[-1] == 1 and k[-1] == 9_999_999
    small = keys % 1000, users
    assert [a.tolist() for a in wide.exact_distinct(*small)] == \
        [a.tolist() for a in plain.exact_distinct(*small)]
    with pytest.raises(ValueError):
        wide.exact_distinct(np.array([1 << 24]), np.array([1]))

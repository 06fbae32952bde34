"""The phase reduction on the small trace beside it gives the nesting,
the self times, the compile carved out of its parent, the idle overlap
and both ratio metrics computed by hand in the trace file's comments;
a rehearsal prints the host-share metrics and a ``[phases]`` line."""

import json
import os

import pytest
from jax.profiler import ProfileData

import loader
import span_slice
from test_harness import ROOT, last_line, run_cell

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "small_trace_phases.pbtxt")
NS = 1e-9
READERS = ("ingest_box_share", "timers_share", "state_slot_share",
           "state_hash_share", "state_device_wait_share", "fire_emit_share",
           "fire_downstream_share", "log_concat_share", "log_finish_share",
           "phase_coverage_share", "idle_unexplained_share")


def load(text):
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


@pytest.fixture(scope="module")
def table():
    with open(TRACE, encoding="utf-8") as f:
        out = span_slice.reduce_phases(load(f.read()))
    out["slice_s"] = 10000 * NS
    return out


@pytest.fixture
def read(table, monkeypatch):
    """A reader by name, over the small trace's table."""
    monkeypatch.setattr(span_slice, "table", lambda run: table)
    return lambda name: loader.load_module("layer_metrics", name).read(
        {"slice_s": table["slice_s"]})


def test_nesting_is_by_containment_and_self_time_excludes_children(table):
    rows = table["phases"]
    assert {name: row["count"] for name, row in rows.items()} == {
        "window.ingest": 1, "window.ingest.box": 1, "state.flush": 1,
        "jax.compile": 2, "window.watermark": 1, "timers.sweep": 1,
        "state.get.device": 1, "window.fire.batch": 1,
        "window.fire.downstream": 1, "native.splitmix64": 1}
    assert rows["window.ingest"]["total_s"] == pytest.approx(3000 * NS)
    assert rows["window.ingest"]["self_s"] == pytest.approx(1000 * NS)
    assert rows["window.watermark"]["total_s"] == pytest.approx(4500 * NS)
    assert rows["window.watermark"]["self_s"] == pytest.approx(300 * NS)
    assert rows["timers.sweep"]["self_s"] == pytest.approx(400 * NS)


def test_a_compile_is_carved_out_of_the_phase_that_needed_it(table):
    rows = table["phases"]
    assert rows["jax.compile"]["self_s"] == pytest.approx(1800 * NS)
    assert rows["state.flush"]["total_s"] == pytest.approx(1500 * NS)
    assert rows["state.flush"]["self_s"] == pytest.approx(700 * NS)
    assert rows["state.get.device"]["self_s"] == pytest.approx(1000 * NS)


def test_idle_is_the_self_time_in_which_the_device_ran_no_op(table):
    idle = {name: row["idle_s"] / NS for name, row in table["phases"].items()}
    assert idle == pytest.approx({
        "window.ingest": 1000, "window.ingest.box": 500, "state.flush": 500,
        "jax.compile": 800, "window.watermark": 300, "timers.sweep": 400,
        "state.get.device": 700, "window.fire.batch": 1000,
        "window.fire.downstream": 800, "native.splitmix64": 300})
    assert table["idle_s"] == pytest.approx(7000 * NS)
    assert table["idle_in_leaves_s"] == pytest.approx(5000 * NS)


def test_share_readers_divide_self_time_by_the_slice(read):
    assert read("ingest_box_share") == pytest.approx(5.0)
    assert read("timers_share") == pytest.approx(4.0)
    assert read("state_device_wait_share") == pytest.approx(17.0)
    assert read("fire_emit_share") == pytest.approx(10.0)
    assert read("fire_downstream_share") == pytest.approx(8.0)
    # none of its phases occurred: left out of the line
    assert read("log_concat_share") is None
    assert read("log_finish_share") is None
    assert read("state_hash_share") is None


def test_both_ratio_metrics(read):
    assert read("phase_coverage_share") == pytest.approx(100 * 6200 / 7500)
    assert read("idle_unexplained_share") == pytest.approx(100 * 2000 / 7000)


def test_a_child_that_outlasts_its_parent_is_cut_to_it():
    got = {name: (under, end, pieces) for name, under, _start, end, pieces
           in span_slice.nest([(0, 10, "a"), (4, 12, "b"), (12, 14, "c")])}
    assert got["a"] == ("a", 10, [(0, 4)])
    assert got["b"] == ("a", 10, [(4, 10)])
    assert got["c"] == ("c", 14, [(12, 14)])


def test_a_program_without_phases_gives_every_reader_nothing(monkeypatch):
    """The parent of the PR that added the phases: jax's compile events
    are there, no ``flink/`` event is."""
    parent = span_slice.reduce_phases(load('''
      planes { id: 1 name: "/device:TPU:0"
        lines { id: 2 name: "XLA Ops" timestamp_ns: 100
                events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 } }
        event_metadata { key: 1 value { id: 1 name: "op.a" } } }
      planes { id: 2 name: "/host:CPU"
        lines { id: 7 name: "python3" timestamp_ns: 0
                events { metadata_id: 1 offset_ps: 0 duration_ps: 900000 }
                events { metadata_id: 2 offset_ps: 0 duration_ps: 50000 } }
        event_metadata { key: 1 value { id: 1 name: "bench.fire" } }
        event_metadata { key: 2 value { id: 2
                                name: "backend_compile_and_load" } } }'''))
    assert set(parent["phases"]) == {"jax.compile"}
    monkeypatch.setattr(span_slice, "table", lambda run: parent)
    contract = loader.read_json(loader.CONTRACT)
    assert set(READERS) <= {m["name"] for m in contract["per_layer"]}
    for name in READERS:
        reader = loader.load_module("layer_metrics", name)
        assert reader.read({"slice_s": 1e-6}) is None, name


def test_without_a_device_plane_the_host_shares_still_come_out():
    with open(TRACE, encoding="utf-8") as f:
        host_only = f.read().replace("/device:TPU:0", "/host:other")
    out = span_slice.reduce_phases(load(host_only))
    assert out["idle_s"] is None and out["idle_in_leaves_s"] is None
    assert out["phases"]["state.flush"]["idle_s"] is None
    assert out["phases"]["state.flush"]["self_s"] == pytest.approx(700 * NS)


@pytest.mark.parametrize("cell, expected, absent", [
    ("state_hll_1m.uniform",
     {"ingest_box_share", "timers_share", "state_slot_share",
      "state_hash_share", "state_device_wait_share", "fire_emit_share",
      "fire_downstream_share", "phase_coverage_share"},
     {"log_concat_share", "log_finish_share", "idle_unexplained_share"}),
    ("sql_acd_1m.uniform",
     {"fire_emit_share", "fire_downstream_share", "log_concat_share",
      "phase_coverage_share"},
     {"ingest_box_share", "state_hash_share", "idle_unexplained_share"})])
def test_a_traced_rehearsal_prints_the_host_shares_and_a_phases_line(
        cell, expected, absent):
    proc = run_cell(ROOT, "--workload", cell, "--seed", "5", "--seconds", "1",
                    "--trace", "1", "--rehearse-cpu")
    out = last_line(proc)
    names = set(out["metrics"])
    assert {"rehearsal_" + n for n in expected} <= names
    assert not {"rehearsal_" + n for n in absent} & names
    assert 0 < out["metrics"]["rehearsal_phase_coverage_share"]["value"] <= 100
    [line] = [ln for ln in proc.stdout.splitlines()
              if ln.startswith("[phases] ")]
    table = json.loads(line[len("[phases] "):])
    assert {"window.ingest", "window.watermark", "window.fire.batch"} \
        <= set(table["phases"])
    assert table["idle_s"] is None  # no device plane on the CPU
    with open(os.path.join(ROOT, "benchmark_out", "trace", cell,
                           "phases.json"), encoding="utf-8") as f:
        assert json.load(f) == table

"""The command itself, rehearsed on the CPU: cells are found by name
from data files with no edit to ``run.py``; the rehearsal cannot print
a device metric's name; without a chip there is no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import loader

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def run_cell(root, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def contract_names():
    contract = loader.read_json(loader.CONTRACT)
    return {m["name"] for m in contract["end_to_end"] + contract["per_layer"]}


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_no_device_metric_name(trace):
    out = last_line(run_cell(ROOT, "--workload", "sql_acd_1m.uniform",
                             "--seed", "1", "--seconds", "1",
                             "--trace", trace, "--rehearse-cpu"))
    assert out["rehearsal"] is True and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"] and out["device"]["platform"] == "cpu"
    names = set(out["metrics"])
    assert all(n.startswith("rehearsal_") for n in names)
    assert not names & contract_names()


def test_without_a_chip_there_is_no_result():
    proc = run_cell(ROOT, "--workload", "sql_acd_1m.uniform", "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "needs 1 TPU chip" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_alone_in_a_directory_there_is_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no system
    to measure."""
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(loader.CONTRACT, tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "sql_acd_1m.uniform", "--seed", "1", "--seconds", "1", "--trace", "0",
         "--rehearse-cpu"],
        cwd=tmp_path, env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "cannot import the system" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_an_unknown_cell_is_refused_by_name():
    proc = run_cell(ROOT, "--workload", "no_such.cell", "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and "no_such.cell" in proc.stderr


TOY_JOB = '''
import numpy as np
from flink_tpu.ops.device_agg import MaxAggregate
from flink_tpu.streaming.windowing import TumblingEventTimeWindows


class UserMax(MaxAggregate):
    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def build(env, source, sink, config):
    source.configure(("f0", "f1", "f2"), as_elements=True)
    env.set_state_backend(config["state_backend"])
    windowed = (env.add_source(source, name="events").key_by(0)
                .window(TumblingEventTimeWindows.of(config["window_ms"])))
    windowed.disable_device_operator()
    windowed.aggregate(
        UserMax(np.float64),
        window_function=lambda key, window, vals:
        [(window.start, key, float(vals[0]))]).add_sink(sink)
'''

TOY_REFERENCE = '''
import numpy as np


def check(config, emitted, results):
    results = dict(results)
    attempted = failed = 0
    for window, _data_id, columns in emitted:
        keys, users = columns()
        exact = np.full(config["key_space"], -1, np.int64)
        np.maximum.at(exact, keys, users)
        _starts, got_keys, got_max = results.pop(window * config["window_ms"])
        attempted += int((exact >= 0).sum())
        seen = np.bincount(got_keys, minlength=len(exact))
        failed += int(((exact >= 0) != (seen == 1)).sum())
        failed += int((got_max != exact[got_keys]).sum())
    failed += sum(len(cols[0]) for cols in results.values())
    return {"attempted": attempted, "failed": failed, "problems": [],
            "facts": {"aggregate": "max"}}
'''

TOY_SOURCE = '''
import loader


def make(config, traffic, seed, seconds):
    source = loader.load_module("sources", "closed_replay").make(
        config, traffic, seed + traffic["seed_offset"], seconds)
    print("[toy_source] made", flush=True)
    return source
'''


def test_a_new_cell_is_files_and_entries_with_run_py_untouched(tmp_path):
    """Another aggregate (MAX, exact) with its own job, its own
    reference and its own order of result columns, over a made-up
    source, traffic mix, key distribution and per-layer metric: all
    dropped in as files beside a byte-for-byte copy of the harness."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    config = loader.read_json(
        loader.BENCH_DIR / "configs" / "state_hll_1m.json")
    config.update(name="toy_max", job="toy_max_state",
                  reference="max_tumbling",
                  result_columns=["window_start", "key", "max"],
                  key_space=64, events_per_window=1024, batch_rows=256,
                  user_bits=20)
    for gone in ("rehearsal", "hll_precision"):
        config.pop(gone)
    (bench / "configs" / "toy_max.json").write_text(json.dumps(config))
    (bench / "jobs" / "toy_max_state.py").write_text(TOY_JOB)
    (bench / "references" / "max_tumbling.py").write_text(TOY_REFERENCE)
    (bench / "sources" / "toy_source.py").write_text(TOY_SOURCE)
    (bench / "traffic" / "toy_hot.json").write_text(json.dumps({
        "name": "toy_hot", "source": "toy_source", "seed_offset": 7,
        "key_distribution": "hot_one", "params": {"hot": 5}}))
    (bench / "generators" / "hot_one.py").write_text(
        "import numpy as np\n"
        "def draw(rng, n, key_space, params):\n"
        "    keys = rng.integers(0, key_space, n, dtype=np.int64)\n"
        "    keys[::2] = params['hot']\n"
        "    return keys\n")
    (bench / "layer_metrics" / "toy_events.py").write_text(
        "def read(run):\n    return run['events']\n")
    contract = loader.read_json(loader.CONTRACT)
    contract["configs"].append({"name": "toy_max", "source": "made up",
                                "file": "benchmark/configs/toy_max.json",
                                "reduced": [], "why": "a test"})
    contract["workloads"].append({"name": "toy_max.toy_hot",
                                  "config": "toy_max", "traffic": "toy_hot",
                                  "chips": 1, "why": "a test"})
    contract["per_layer"].append({
        "name": "toy_events", "unit": "events", "better": "higher",
        "source": "program_counter", "layer": "engines",
        "moves": "events_per_s", "workloads": ["toy_max.toy_hot"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(contract))
    assert all(p.read_bytes() == was for p, was in before.items())
    proc = run_cell(tmp_path, "--workload", "toy_max.toy_hot", "--seed", "3",
                    "--seconds", "1", "--trace", "1", "--rehearse-cpu")
    out = last_line(proc)
    assert "[toy_source] made" in proc.stdout
    assert '"aggregate": "max"' in proc.stdout
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    events = out["metrics"]["rehearsal_toy_events"]
    assert events["unit"] == "events" and events["value"] % 1024 == 0
    # a metric listed for other cells only is left out of this one
    assert "rehearsal_native_host_share" not in out["metrics"]
    assert "rehearsal_source_host_share" in out["metrics"]


def test_contract_names_resolve_to_files():
    contract = loader.read_json(loader.CONTRACT)
    for w in contract["workloads"]:
        cell = loader.load_cell(w["name"])
        assert hasattr(loader.load_module("jobs", cell.config["job"]),
                       "build")
        assert hasattr(loader.load_module("references",
                                          cell.config["reference"]), "check")
        assert hasattr(loader.load_module("sources", cell.traffic["source"]),
                       "make")
        loader.load_module("generators", cell.traffic["key_distribution"])
        assert {m["name"] for m in cell.end_to_end} \
            == {"events_per_s", "fire_p50_ms", "setup_s"}
        for metric in cell.per_layer:
            assert hasattr(loader.load_module("layer_metrics",
                                              metric["name"]), "read")
    for c in contract["configs"]:
        on_disk = loader.read_json(loader.BENCH_DIR.parent / c["file"])
        assert on_disk["source"] == c["source"]
        assert on_disk["reduced"] == c["reduced"]

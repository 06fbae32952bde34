"""The cell ``session_cm_log_ckpt.zipf``, rehearsed on the CPU end to
end: the session cell's job with checkpoints to a filesystem
directory, the ledger of the run's checkpoints, and one recovery from
a retained checkpoint after the timed window.  And two broken
variants the comparison must fail: a snapshot that leaves out the
pending micro-batch, and offsets one chunk ahead of the state."""

import json
import os
import subprocess
import sys

import loader
from test_harness import ROOT, last_line, run_cell

CELL = "session_cm_log_ckpt.zipf"
CONFIG = "session_cm_log_ckpt"
NEW_METRICS = ("ckpt_sync_share", "ckpt_sync_ms_max",
               "ckpt_duration_ms_mean", "ckpt_completed_in_window",
               "ckpt_written_mib_mean", "state_snapshot_roofline")
ARGS = ("--workload", CELL, "--seconds", "3", "--rehearse-cpu")


def tagged(proc, *tags):
    return {tag: json.loads(line[len(tag) + 2:])
            for line in proc.stdout.splitlines() for tag in tags
            if line.startswith(f"[{tag}]")}


def test_the_configuration_is_the_session_cells_plus_the_checkpoints():
    plain = loader.read_json(loader.BENCH_DIR / "configs"
                             / "session_cm_log.json")
    ours = loader.read_json(loader.BENCH_DIR / "configs" / f"{CONFIG}.json")
    changed = {k for k in set(plain) | set(ours)
               if plain.get(k) != ours.get(k)}
    assert changed == {"name", "source", "deployment", "job", "reference",
                       "checkpoint", "guarantees", "assumed", "rehearsal"}
    assert ours["checkpoint"] == {
        "interval_ms": 5000, "mode": "exactly_once", "max_concurrent": 1,
        "async": True, "storage": "filesystem", "retain": 1}
    assert ours["reduced"] == ["events_per_window"]
    for group in ("guarantees", "assumed", "rehearsal"):
        # nothing of the session cell's taken away or weakened
        for key, value in plain[group].items():
            if (group, key) != ("guarantees", "delivery"):
                assert ours[group][key] == value, (group, key)
    assert "exactly-once" in ours["guarantees"]["delivery"]
    contract = loader.read_json(loader.CONTRACT)
    listed = {m["name"] for m in contract["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW_METRICS) <= listed
    assert {"session_resolve_share", "countmin_update_roofline"} <= listed
    for name in NEW_METRICS:
        assert (loader.BENCH_DIR / "layer_metrics" / f"{name}.py").is_file()


def test_the_cell_rehearses_correct_with_checkpoints_and_a_recovery():
    proc = run_cell(ROOT, *ARGS, "--seed", "3900000019", "--trace", "0")
    out = last_line(proc)
    assert out["rehearsal"] is True and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"rehearsal_events_per_s",
                                   "rehearsal_fire_p50_ms",
                                   "rehearsal_setup_s"}
    lines = tagged(proc, "check", "route")
    check, route = lines["check"], lines["route"]
    assert check["problems"] == []
    assert check["checkpoints_completed"] == check["checkpoints_triggered"]
    assert check["checkpoints_completed"] >= max(
        1, check["checkpoints_required"])
    assert check["snapshot_rows"] > 0 and check["snapshot_tiles"] > 0
    # a sketch travels as its cells off the fill: far less than dense
    assert check["snapshot_bytes_encoded"] \
        < check["snapshot_rows"] * 4 * 4 * 2048 / 10
    assert check["recovery_rows"] == check["recovery_rows_expected"] > 0
    assert check["recovery_replayed_periods"] >= 0  # (a fact)
    assert check["recovery_sessions_open_across"] > 0
    assert check["recovery_late_rows"] == 0
    assert check["restore_s"] > 0 and check["catch_up_s"] > 0
    measured = route["in_measured_windows"]
    assert measured["num_late_records_dropped"] == 0
    assert route["evictions"] == route["boxed_fallbacks"] == 0
    directory = os.path.join(ROOT, "benchmark_out", "checkpoints", CONFIG)
    kept = [n for n in os.listdir(directory) if n.startswith("chk-")]
    assert len(kept) == 1  # retain 1


def test_the_traced_rehearsal_reads_the_checkpoint_metrics():
    proc = run_cell(ROOT, *ARGS, "--seed", "3900000023", "--trace", "1")
    out = last_line(proc)
    assert out["correct"] is True and out["failed"] == 0
    value = {n[len("rehearsal_"):]: m["value"]
             for n, m in out["metrics"].items()}
    assert {"ckpt_duration_ms_mean", "ckpt_completed_in_window",
            "ckpt_written_mib_mean", "fire_emit_share",
            "compiles_in_window"} <= set(value)
    assert value["ckpt_completed_in_window"] >= 1
    assert value["ckpt_duration_ms_mean"] > 0
    assert 0 < value["ckpt_written_mib_mean"] < 64
    # no device plane in a CPU trace: the roofline stays silent
    assert "state_snapshot_roofline" not in value
    # off the tracer's own books: never silent
    assert 0 < value["ckpt_sync_share"] < 100
    assert value["ckpt_sync_ms_max"] > 0
    # the traced period is one a barrier falls into
    traced = tagged(proc, "check")["check"]["traced_period"]
    assert traced["pending"] or traced["timer_left_ms"] > 0


BROKEN = {
    "a snapshot that leaves out the pending micro-batch": """
from flink_tpu.state import tpu_backend
whole = tpu_backend.DeviceAggregatingState.capture
def capture(self):
    self._flush = lambda: None      # the barrier forgets to flush
    try:
        return whole(self)
    finally:
        del self._flush
tpu_backend.DeviceAggregatingState.capture = capture
tpu_backend.TpuKeyedStateBackend.flush_all = lambda self: None
# (every barrier between a period's chunks and its watermark, when the
# chunks' rows are pending: a fire flushes them)
from flink_tpu.runtime import local
inject = local.SubtaskInstance.handle_pending_trigger
def handle_pending_trigger(self):
    fn = getattr(self.head, "user_function", None)
    if getattr(fn, "_clocks", 0) is None and not fn._closing:
        return
    inject(self)
local.SubtaskInstance.handle_pending_trigger = handle_pending_trigger
""",
    "offsets one chunk ahead of the state": """
from flink_tpu.connectors import log_connector
honest = log_connector.ReplayableLogSource.snapshot_function_state
def snapshot_function_state(self, checkpoint_id):
    state = honest(self, checkpoint_id)
    ahead = {p: off + self.batch_per_partition
             for p, off in state["offsets"].items()}
    self._pending_offset_commits[-1] = (checkpoint_id, ahead)
    return {"offsets": ahead}
log_connector.ReplayableLogSource.snapshot_function_state = \\
    snapshot_function_state
""",
}


def run_broken(patch, *args):
    program = (patch + "\nimport runpy, sys\n"
               f"sys.argv = ['benchmark/run.py', *{list(args)!r}]\n"
               "runpy.run_path('benchmark/run.py', run_name='__main__')\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}
    return subprocess.run([sys.executable, "-c", program], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


def test_a_snapshot_without_the_pending_micro_batch_fails_the_check():
    proc = run_broken(
        BROKEN["a snapshot that leaves out the pending micro-batch"],
        *ARGS, "--seed", "3900000029", "--trace", "0")
    out = last_line(proc)
    assert out["correct"] is False
    problems = tagged(proc, "check")["check"]["problems"]
    assert any("the recovery emitted" in p for p in problems), problems


def test_offsets_ahead_of_the_state_fail_the_check():
    proc = run_broken(BROKEN["offsets one chunk ahead of the state"],
                      *ARGS, "--seed", "3900000031", "--trace", "0")
    out = last_line(proc)
    assert out["correct"] is False
    problems = tagged(proc, "check")["check"]["problems"]
    assert any("the recovery emitted" in p for p in problems), problems

"""What the spill tier's metrics need besides the phases: the
program's own spill counters at ``t0`` and at the end of the measured
window, the bytes the tier's two device programs must move, and their
time on the device from the traced slice.

A program without the tier (the parent of the PR that added it) has no
such counters, constants or programs: every function here then returns
``None`` and raises nothing.
"""

from __future__ import annotations

import span_slice
import xplane

COUNTERS = ("evicted_rows", "promoted_rows", "spill_fired_rows",
            "budget_overruns")
#: the tier's device programs, as ``traced_jit`` labels them
EVICT_PROGRAM = "jit_state_evict"
PROMOTE_PROGRAM = "jit_state_promote"
BUDGET_KEY = "state.backend.tpu.max-device-slots"

_marks = {}
#: trace path -> its first device's module events, read once
_modules = {}


def counters():
    from flink_tpu.state.stats import STATE_STATS
    if not all(hasattr(STATE_STATS, name) for name in COUNTERS):
        return None
    return {name: getattr(STATE_STATS, name) for name in COUNTERS}


def mark_counters(timeline):
    """Note the counters when the timeline reaches ``t0`` and when the
    measured window ends (the harness's own marks hold a fixed set)."""
    timeline.on_t0.append(lambda: _marks.__setitem__("t0", counters()))
    timeline.on_end.append(lambda: _marks.__setitem__("end", counters()))


def counted(name):
    """Growth of counter ``name`` over the measured window."""
    if not _marks.get("t0") or not _marks.get("end"):
        return None
    return _marks["end"][name] - _marks["t0"][name]


# ---- bytes the device programs must move through HBM ------------------

def row_bytes(config):
    """One slot's accumulator: 2^p one-byte HLL registers."""
    return 1 << config["hll_precision"]


def evict_rows(config):
    """Rows one ``state.evict`` dispatch gathers: a quarter of the
    slots, whatever the number of cold ones."""
    return config["state_backend_config"][BUDGET_KEY] // 4


def promote_rows(config):
    """Rows one ``state.promote`` dispatch scatters: the tile."""
    from flink_tpu.state import tpu_backend
    tile = getattr(tpu_backend, "PROMOTE_TILE_BYTES", None)
    if tile is None:
        return None
    micro = config["state_backend_config"].get(
        "state.backend.tpu.microbatch-size", tpu_backend.DEFAULT_MICROBATCH)
    rows = 1 << ((tile // row_bytes(config)).bit_length() - 1)
    return min(rows, 1 << (micro - 1).bit_length())


def moved_bytes(rows, config):
    """A gather or a scatter of whole rows reads each once and writes
    each once."""
    return 2 * rows * row_bytes(config)


# ---- their time on the device ------------------------------------------

def program_seconds(run, program):
    """(dispatches, device seconds) of the XLA program ``program`` on
    the first device of the traced slice; ``None`` without a trace or
    without the program in it."""
    path = span_slice.newest_trace() if run.get("slice_s") else None
    if path is None:
        return None
    if path not in _modules:
        _modules[path] = first_device_modules(path)
    spans = [d for name, d in _modules[path] if name.startswith(program)]
    return (len(spans), sum(spans) * 1e-9) if spans else None


def first_device_modules(path):
    """(name, duration in ns) of every event on the "XLA Modules" line
    of the trace's first device plane."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            return [(e.name, e.duration_ns) for line in plane.lines
                    if line.name == "XLA Modules" for e in line.events]
    return []


def roofline_share(run, program, rows_per_dispatch):
    """Bytes the program's dispatches of the slice must move ÷ their
    device time ÷ the device's HBM bandwidth, in %."""
    import jax

    import peaks
    if rows_per_dispatch is None:
        return None
    timed = program_seconds(run, program)
    if timed is None:
        return None
    dispatches, seconds = timed
    peak = peaks.for_device(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * dispatches * moved_bytes(
        rows_per_dispatch, run["config"]) / seconds / peak

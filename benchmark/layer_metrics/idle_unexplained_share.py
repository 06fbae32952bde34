"""The part of the device's idle time that no phase explains: idle
seconds of the trace inside no leaf phase's self time ÷ all its idle
seconds.  Both clocks are the profiler's."""

import span_slice


def read(run):
    t = span_slice.table(run)
    if not span_slice.has_phases(t) or not t["idle_s"]:
        return None
    return 100.0 * (t["idle_s"] - t["idle_in_leaves_s"]) / t["idle_s"]

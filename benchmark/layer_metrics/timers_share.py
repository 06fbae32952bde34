"""Share of the profiler slice in timer registration at ingest and the
timer sweep at the watermark (self time of the program's phases)."""

import span_slice

PHASES = ("timers.register", "timers.sweep")


def read(run):
    return span_slice.share(run, PHASES)

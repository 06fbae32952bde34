"""Share of the profiler slice the window operator spends turning one
batch into one group per sliding window it touches: the pane
arithmetic, the grouping (lexsort, bounds, per-window key lists) and
the timers registered per (batch, window)."""

import span_slice

PHASES = ("window.ingest.assign", "window.ingest.group", "timers.register")


def read(run):
    return span_slice.share(run, PHASES)

"""Share of the profiler slice spent turning fired state into result rows:
the per-key window function loop and `batch_from_records` (state
route), building the result `RecordBatch` (SQL)."""

import span_slice

PHASES = ("window.fire.batch", "window.fire.columnarize")


def read(run):
    return span_slice.share(run, PHASES)

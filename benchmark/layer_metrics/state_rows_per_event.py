"""State rows written per event of the measured window, from the
program's exact counters: rows `add_batch` took (`STATE_STATS.batch_rows`
less the fires' reads, `result_rows`) between `t0` and the end ÷ events
taken in between the same two marks (the assigner's fan-out: 10 for a
sliding window of ten slides)."""

import sliding


def read(run):
    return sliding.rows_per_event()

"""Rows that crossed between HBM and host RAM per measured window,
from the program's exact counters: (`STATE_STATS.evicted_rows` +
`promoted_rows`) between `t0` and the end ÷ measured windows."""

import spill


def read(run):
    evicted, promoted = spill.counted("evicted_rows"), \
        spill.counted("promoted_rows")
    windows = run["events"] // run["config"]["events_per_window"]
    if evicted is None or promoted is None or not windows:
        return None
    return (evicted + promoted) / windows

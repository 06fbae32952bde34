"""Share of the profiler slice the default door's ingest takes: the
batch's key and value columns (`device_window.columns`), the record
door's flush, the hash of the value column and the append to the
window's log, with the native calls nested in them."""

import span_slice

PHASES = ("device_window.columns", "device_window.flush",
          "columnar.ingest.hash", "log.append",
          "native.splitmix64", "native.hll_make_cells")


def read(run):
    return span_slice.share(run, PHASES)

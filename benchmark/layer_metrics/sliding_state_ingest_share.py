"""Share of the profiler slice in the tpu state backend's ingest of a
value-carrying aggregate: slot claims per (key, window) row, the value
column taken into the pending ring, the flushes' scatter-add."""

import span_slice

PHASES = ("state.add.slots", "state.add.hash", "state.flush")


def read(run):
    return span_slice.share(run, PHASES)

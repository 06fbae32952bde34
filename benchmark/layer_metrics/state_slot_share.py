"""Share of the profiler slice in the tpu state backend's host-side slot
work: claiming at ingest, lookup at the fire, release at the clear."""

import span_slice

PHASES = ("state.add.slots", "state.get.lookup", "state.clear.slots")


def read(run):
    return span_slice.share(run, PHASES)

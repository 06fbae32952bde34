"""Share of the profiler slice the spill tier takes out of ingest:
bulk evictions to host RAM (one device gather each) and the batched
promotion of the spilled rows a batch touches."""

import span_slice

PHASES = ("state.evict", "state.promote")


def read(run):
    return span_slice.share(run, PHASES)

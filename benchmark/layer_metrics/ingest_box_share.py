"""Share of the profiler slice in the state route's row boxing and pane
assignment (self time of the program's phases, `span_slice`)."""

import span_slice

PHASES = ("window.ingest.box", "window.ingest.assign")


def read(run):
    return span_slice.share(run, PHASES)

"""Share of the profiler slice in `output.collect*` of a fire: everything
chained after the window operator, the sink included."""

import span_slice

PHASES = ("window.fire.downstream",)


def read(run):
    return span_slice.share(run, PHASES)

"""Share of the profiler slice in `log.concat()`: the fired window's log
into one array."""

import span_slice

PHASES = ("log.concat",)


def read(run):
    return span_slice.share(run, PHASES)

"""Share of the profiler slice in the log engine's device finish: pad to a
power of two, two `device_put`s, the jitted finish and the D2H wait."""

import span_slice

PHASES = ("log.finish.pad", "log.finish.device")


def read(run):
    return span_slice.share(run, PHASES)

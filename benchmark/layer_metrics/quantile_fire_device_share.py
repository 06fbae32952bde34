"""Share of the profiler slice a fire waits on the device: the tiled
gather + cumsum + selection of the fired sketches (`state.get.device`)
and the clearing of their slots (`state.clear.device`), self time."""

import span_slice

PHASES = ("state.get.device", "state.clear.device")


def read(run):
    return span_slice.share(run, PHASES)

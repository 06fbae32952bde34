"""Share of the profiler slice a fire spends on rows in the host tier:
finalising them in tiles through `state.result`, and releasing them."""

import span_slice

PHASES = ("state.fire.spill", "state.clear.spill")


def read(run):
    return span_slice.share(run, PHASES)

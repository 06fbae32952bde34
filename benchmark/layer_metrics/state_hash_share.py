"""Share of the profiler slice in the tpu state backend's per-value
extract + `stable_hash64` loop."""

import span_slice

PHASES = ("state.add.hash",)


def read(run):
    return span_slice.share(run, PHASES)

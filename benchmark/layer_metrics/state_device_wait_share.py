"""Share of the profiler slice in the tpu state backend's device calls:
pad, dispatch and the wait for the result.  Self time, so a compile
inside one of them (`jax.compile`) is not in it."""

import span_slice

PHASES = ("state.flush", "state.get.device", "state.clear.device")


def read(run):
    return span_slice.share(run, PHASES)

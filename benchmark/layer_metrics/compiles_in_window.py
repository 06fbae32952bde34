"""Programs the process had to compile, or read from the compile
cache, between ``t0`` and the last row of the last measured window:
``jax.monitoring`` backend-compile events by the host time at which
each ended.  The warm-up windows use every shape that does not follow
the data, so what is counted here every user pays in every window."""


def read(run):
    return run["compiles_in_window"]

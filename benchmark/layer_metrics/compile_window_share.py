"""Share of the measured window spent in backend compiles (or reads
from the compile cache) that ended inside it: ``jax.monitoring``
durations.  Above 0 only where a program's shape follows the data."""


def read(run):
    return 100.0 * run["compile_s_in_window"] / run["window_s"]

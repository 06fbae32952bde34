"""Share of the profiler slice (one whole window period in mid-run) in
which an XLA op ran on the device: ``xplane.reduce_trace``."""


def read(run):
    if run["trace"] is None or not run["slice_s"]:
        return None
    return 100.0 * run["trace"]["busy_s"] / run["slice_s"]

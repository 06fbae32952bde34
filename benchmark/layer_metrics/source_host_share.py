"""Share of the measured window the host spent inside the benchmark
source's ``emit_step`` (making a batch and handing it to the
exchange): the harness's own timer."""


def read(run):
    return 100.0 * run["end"]["source_s"] / run["window_s"]

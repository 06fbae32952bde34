"""Share of the measured window inside the window operator's
watermark entry: fire, emit, and the sink chained after it."""


def read(run):
    if not run["end"]["fire_calls"]:
        return None
    return 100.0 * run["end"]["fire_s"] / run["window_s"]

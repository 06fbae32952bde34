"""Share of the measured window inside the native C++ entry points:
the sum of ``tracing.kernel_stats()[*].total_ms`` (host clock around
synchronous calls, always on)."""


def read(run):
    if run["end"]["native_dispatches"] == run["t0"]["native_dispatches"]:
        return None
    ms = run["end"]["native_ms"] - run["t0"]["native_ms"]
    return 100.0 * (ms / 1e3) / run["window_s"]

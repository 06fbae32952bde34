"""Peak device memory of the fullest chip, from
``memory_stats()["peak_bytes_in_use"]`` after the window."""


def read(run):
    if not run["memory_peak_bytes"]:
        return None
    return run["memory_peak_bytes"] / 2 ** 30

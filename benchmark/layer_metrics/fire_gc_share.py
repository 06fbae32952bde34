"""Share of the fires spent in CPython's cyclic collector: collector
time booked under `window.watermark`, its children's included, ÷ the
total of `window.watermark`, both over the measured fire periods
(`period_history`)."""

import period_history


def read(run):
    t = period_history.table(run)
    if t is None:
        return None
    row = t["phases"].get(period_history.WATERMARK)
    if row is None or not row["total_s_sum"]:
        return None
    return 100.0 * row["gc_under_s_sum"] / row["total_s_sum"]

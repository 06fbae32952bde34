"""`phase_coverage_share` over the whole measured interval: how much
of the window operators' time lies in something named.  The two
top-level phases' totals less their own unnamed time ÷ their totals,
over the measured fire periods (`period_history`).  Their own self
time already leaves out the compiles and collections booked on them
(those are named: `jax.compile`, `py.gc`); the native kernels called
straight from them (`native_ms`: the SQL route's sort and fire) are
named too."""

import period_history


def read(run):
    t = period_history.table(run)
    if t is None:
        return None
    rows = [t["phases"][n] for n in period_history.TOP if n in t["phases"]]
    total = sum(r["total_s_sum"] for r in rows)
    if not total:
        return None
    unnamed = sum(r["self_s_sum"] - r["native_s_sum"] for r in rows)
    return 100.0 * (1.0 - unnamed / total)

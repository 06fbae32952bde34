"""Share of the measured interval spent in CPython's cyclic collector,
in whatever phase a collection started or in none: Σ collector time of
the measured fire periods ÷ Σ of their lengths (`period_history`, from
the program's own `Tracer.periods()`; the `[periods]` line splits it
by phase and by generation)."""

import period_history


def read(run):
    t = period_history.table(run)
    if t is None:
        return None
    return 100.0 * t["gc"]["gc_s_sum"] / t["interval_s"]

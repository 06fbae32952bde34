"""Share of the measured interval in moving the sessions' timers at
ingest: a window that grew loses its timer and gets another, a window
swallowed by a merge loses its own (`timers.register` +
`timers.delete`, self time over the measured fire periods,
`period_history`)."""

import period_history

PHASES = ("timers.register", "timers.delete")


def read(run):
    return period_history.share(run, PHASES)

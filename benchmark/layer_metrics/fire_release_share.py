"""Share of the measured interval spent freeing the fire buffer: the
row tuples, and on the default door the key and result lists made for
the fire (`window.fire.release`, self time over the measured fire
periods; `period_history`)."""

import period_history

PHASES = ("window.fire.release",)


def read(run):
    return period_history.share(run, PHASES)

"""Share of the measured interval in the batched MergingWindowSet: the
sort of a batch's rows by (key, timestamp), the cut into runs, the
merge with the keys' open windows and the persisting of their
mappings (`window.ingest.sessions`, self time over the measured fire
periods, `period_history`)."""

import period_history

PHASES = ("window.ingest.sessions",)


def read(run):
    return period_history.share(run, PHASES)

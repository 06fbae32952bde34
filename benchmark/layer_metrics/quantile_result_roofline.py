"""The fire's gather + cumsum + selection (`state.result`) against the
HBM roofline: every gathered slot's buckets read once and two float32
quantiles written ÷ its device time in the slice ÷ peak bytes/s."""

import sliding


def read(run):
    rows = sliding.result_rows(run)
    return sliding.roofline_share(
        run, sliding.RESULT_PROGRAM,
        rows and sliding.result_bytes(rows, run["config"]))

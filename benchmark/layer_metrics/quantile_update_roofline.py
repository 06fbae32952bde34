"""The quantile sketch's scatter-add (`state.update`) against the HBM
roofline: per row a slot, a value and a mask bit read and one int32
cell read and written ÷ its device time in the slice ÷ peak bytes/s.
Low by nature: a 16,384-cell scatter is bound by latency, not bytes."""

import sliding


def read(run):
    rows = sliding.update_rows(run)
    return sliding.roofline_share(
        run, sliding.UPDATE_PROGRAM,
        rows and rows * sliding.UPDATE_ROW_BYTES)

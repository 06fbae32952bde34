"""The Count-Min sketch's scatter-add (`state.update`) against the HBM
roofline: per flushed row `depth` int32 cells of the table read and
written, the slot's total read and written, the row's slot and two
hash lanes read (`session.update_row_bytes`) ÷ its device time in the
slice ÷ peak bytes/s.  Low by nature: a scatter of `depth` cells a
row is bound by latency, not bytes."""

import session


def read(run):
    rows = session.update_rows(run)
    return session.roofline_share(
        run, session.UPDATE_PROGRAM,
        rows and rows * session.update_row_bytes(run["config"]))

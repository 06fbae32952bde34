"""The fire's point queries (`state.result`) against the HBM roofline:
per fired slot `depth` int32 cells gathered for each tracked item, the
total gathered, the results written (`session.result_row_bytes`) ÷ its
device time in the slice ÷ peak bytes/s."""

import session


def read(run):
    rows = session.result_rows(run)
    return session.roofline_share(
        run, session.RESULT_PROGRAM,
        rows and rows * session.result_row_bytes(run["config"]))

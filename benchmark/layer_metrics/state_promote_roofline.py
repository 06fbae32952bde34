"""The batched promotion scatter (`state.promote`) against the HBM
roofline: one tile of rows read and written once per dispatch ÷ its
device time in the slice ÷ peak bytes/s."""

import spill


def read(run):
    return spill.roofline_share(run, spill.PROMOTE_PROGRAM,
                                spill.promote_rows(run["config"]))

"""The eviction gather (`state.evict`) against the HBM roofline: rows
read and written once ÷ its device time in the slice ÷ peak bytes/s."""

import spill


def read(run):
    return spill.roofline_share(run, spill.EVICT_PROGRAM,
                                spill.evict_rows(run["config"]))

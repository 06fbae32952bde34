"""The t-digest's ``state.update`` against the HBM roofline: per flush
its rows (an int32 slot and a float32 value read, one buffer cell
written) and each digest it compressed (read whole, 3,088 B; its
centroids and scalars written, 1,040 B) ÷ its device time in the slice
÷ peak bytes/s.  Low by nature: the append is a kernel step a row and
the compress a sequential pass on the vector unit, not a stream of
bytes."""

import sliding
import tdigest


def read(run):
    return sliding.roofline_share(run, sliding.UPDATE_PROGRAM,
                                  tdigest.update_bytes(run["config"]))

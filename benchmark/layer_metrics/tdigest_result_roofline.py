"""The fire's compress and interpolation of the fired digests
(``state.result``) against the HBM roofline: every fired digest read
whole (3,088 B) and two float32 quantiles written ÷ its device time in
the slice ÷ peak bytes/s.  The compress is a sequential pass on the
vector unit, so this reads far below 100%."""

import sliding
import tdigest


def read(run):
    return sliding.roofline_share(run, sliding.RESULT_PROGRAM,
                                  tdigest.result_bytes(run))

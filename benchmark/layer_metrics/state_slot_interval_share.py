"""`state_slot_share` over the whole measured interval: self time of
the tpu state backend's host-side slot work (claiming at ingest,
lookup at the fire, release at the clear) in the measured fire periods
÷ Σ of their lengths, collector time apart (`period_history`)."""

import period_history

PHASES = ("state.add.slots", "state.get.lookup", "state.clear.slots")


def read(run):
    return period_history.share(run, PHASES)

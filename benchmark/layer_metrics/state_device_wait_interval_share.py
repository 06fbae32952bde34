"""`state_device_wait_share` over the whole measured interval: self
time of the phases in which the tpu state backend dispatches to the
device or waits for it, in the measured fire periods ÷ Σ of their
lengths; a compile or a collection inside is not in it
(`period_history`)."""

import period_history

PHASES = ("state.flush", "state.get.device", "state.clear.device")


def read(run):
    return period_history.share(run, PHASES)

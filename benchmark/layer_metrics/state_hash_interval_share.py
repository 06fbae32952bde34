"""`state_hash_share` over the whole measured interval: self time of
`state.add.hash` in the measured fire periods ÷ Σ of their lengths,
collector time apart (`period_history`)."""

import period_history

PHASES = ("state.add.hash",)


def read(run):
    return period_history.share(run, PHASES)

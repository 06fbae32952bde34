"""`fire_emit_share` over the whole measured interval: self time of
turning fired state into result rows (`window.fire.batch`,
`window.fire.columnarize`) in the measured fire periods ÷ Σ of their
lengths, collector time apart (`period_history`)."""

import period_history

PHASES = ("window.fire.batch", "window.fire.columnarize")


def read(run):
    return period_history.share(run, PHASES)

"""Share of the measured interval in the tpu state backend's ingest of
session rows: slot claims per (key, state window) row, the item
column's hash, the flushes' scatter-add, and the batched merge of two
state windows (`state.add.slots` + `state.add.hash` + `state.flush` +
`state.merge`, self time over the measured fire periods,
`period_history`)."""

import period_history

PHASES = ("state.add.slots", "state.add.hash", "state.flush",
          "state.merge")


def read(run):
    return period_history.share(run, PHASES)

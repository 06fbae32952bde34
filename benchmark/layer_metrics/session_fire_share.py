"""Share of the fires in what is the sessions' own: mapping each swept
(window, key) to its state window and retiring it, the probe of the
slot index under a namespace per row, and the release of the fired
slots (`window.fire.sessions` + `state.get.lookup` +
`state.clear.slots`, self time, ÷ the total of `window.watermark`,
both over the measured fire periods, `period_history`)."""

import period_history

PHASES = ("window.fire.sessions", "state.get.lookup", "state.clear.slots")


def read(run):
    t = period_history.table(run)
    if t is None:
        return None
    fires = t["phases"].get(period_history.WATERMARK)
    rows = [t["phases"][n] for n in PHASES if n in t["phases"]]
    if fires is None or not fires["total_s_sum"] or not rows:
        return None
    return 100.0 * sum(r["self_s_sum"] for r in rows) / fires["total_s_sum"]

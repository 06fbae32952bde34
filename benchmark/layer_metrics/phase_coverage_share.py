"""How much of the window operators' time lies in a named child phase:
self time of everything nested in `window.ingest` / `window.watermark`
(leaf phases, `native.*`, `jax.compile`) ÷ the total of those two.
What is missing is the operators' own self time: code in no phase."""

import span_slice


def read(run):
    t = span_slice.table(run)
    if t is None or not t["top_total_s"]:
        return None
    return 100.0 * t["under_top_self_s"] / t["top_total_s"]

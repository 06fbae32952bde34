"""The longest synchronous part of a checkpoint in the measured
window, ms: `checkpoint.sync`'s total in the fire period that holds it
(a barrier's parts on the source's and the window operator's tasks lie
inside one period and add up), the largest over the program's own fire
periods between `t0` and the end of the window
(`checkpointing.sync_ms_max`, off `Tracer.periods()`: a longer stall
reads higher and silences nothing)."""

import checkpointing


def read(run):
    return checkpointing.sync_ms_max()

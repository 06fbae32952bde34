"""Checkpoints completed between `t0` and the end of the measured
window, by the coordinator's `completed_count`."""

import checkpointing


def read(run):
    return checkpointing.counted("completed")

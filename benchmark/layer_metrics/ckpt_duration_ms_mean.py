"""Trigger -> durable, ms: the mean over the checkpoints triggered
and completed inside the measured window, by the coordinator's own
`CheckpointStats` (`duration_ms`: the synchronous parts, the capture's
transfer, the encoding and the file write)."""

import checkpointing


def read(run):
    durations = checkpointing.completed_durations_ms()
    return sum(durations) / len(durations) if durations else None

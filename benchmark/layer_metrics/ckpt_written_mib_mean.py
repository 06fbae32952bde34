"""MiB a checkpoint wrote, the mean over those completed inside the
measured window: the coordinator's `state_bytes` (the checkpoint's
file and the chunks it stored anew; a chunk an earlier checkpoint
holds is shared, not written)."""

import checkpointing


def read(run):
    written = checkpointing.written_bytes()
    return sum(written) / len(written) / 2 ** 20 if written else None

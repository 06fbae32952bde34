"""The capture's device programs (`state.snapshot.copy`, `.count`,
`.cells<L>`, `.rows`) against the HBM roofline, over their dispatches
inside the traced slice and no others: rows a dispatch reads (its
tile's width) x the slot's bytes, read once whatever encodes them
(`checkpointing.snapshot_row_bytes`) / those dispatches' device time /
peak bytes/s.  The traced period is one a barrier falls into
(`checkpointing._aim_slice`)."""

import checkpointing


def read(run):
    return checkpointing.snapshot_roofline(run)

"""Share of the measured window the task's thread spent in the
synchronous part of checkpoints: `checkpoint.sync` (barrier taken ->
ack handed over; its children `window.snapshot`, `state.snapshot.flush`
/ `.index` / `.dispatch`), total time between `t0` and the end of the
window off the tracer's always-on books (`checkpointing.sync_share`)."""

import checkpointing


def read(run):
    return checkpointing.sync_share(run)

"""Mean rows per device micro-batch flush of the keyed state, from the
program's exact counters: ``STATE_STATS.flush_rows / flush_batches``
over the measured window."""


def read(run):
    batches = run["end"]["flush_batches"] - run["t0"]["flush_batches"]
    if batches <= 0:
        return None
    return (run["end"]["flush_rows"] - run["t0"]["flush_rows"]) / batches

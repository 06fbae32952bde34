"""Share of the profiler slice the engine's part of a fire takes on the
default door: `device_window.fire`'s own time around
`engine.advance_watermark`, the log's concat, the pad and the device
finish, with the native sort-and-compact calls nested in them.  What
is left of the fire is the per-key emit (`fire_emit_share`)."""

import span_slice

PHASES = ("device_window.fire", "log.concat", "log.finish.pad",
          "log.finish.device", "native.hll_log_compact",
          "native.hll_log_fire")


def read(run):
    return span_slice.share(run, PHASES)

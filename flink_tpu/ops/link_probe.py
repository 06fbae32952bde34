"""Host↔device link micro-probe for engine-tier auto-selection.

Some engine choices hinge on how the accelerator is attached, not on
what it nominally is: the log engines' window-fire finish
(``finish_tier="auto"``, flink_tpu/streaming/log_windows.py) can run
its dense estimate phase either in C++ on the host or as one jitted
scan on the device, and the device finish has to ship the compacted
cells over the link first.

This module measures the H2D link ONCE per process with plain
``jax.device_put`` transfers — deliberately no jit, so the probe costs
a few small transfers and never a compile — and exposes a tier
recommendation: below ``DEVICE_FINISH_MIN_H2D_GBPS`` the finish stays
on the host.  The threshold has not been re-priced on a directly
attached chip; a reading near it can land on either side from one
process start to the next.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

#: resolved once per process; force=True re-measures
_cache: Dict[str, float] = {}

#: H2D bandwidth above which the device-side window finish is
#: expected to win
DEVICE_FINISH_MIN_H2D_GBPS = 4.0

_PROBE_BYTES = 8 << 20


def _measure() -> Dict[str, float]:
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        # same memory domain: "transfers" are memcpy and the "device"
        # is this host — the C++ finish is the faster same-silicon path
        return {"h2d_gbps": float("inf"), "cpu": 1.0}
    # warm the transfer path (lazy backend init, pinning)
    jax.device_put(np.zeros(4096, np.uint8), dev).block_until_ready()

    def best_of(nbytes: int, reps: int) -> float:
        buf = np.zeros(nbytes, np.uint8)
        best = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            arr = jax.device_put(buf, dev).block_until_ready()
            best = max(best, nbytes / (time.perf_counter() - t0) / 1e9)
            del arr
        return best

    # staged payloads: slow links must not pay seconds of probing
    # (1 MB x3 is <=300 ms even at 0.01 GB/s), while fast links
    # escalate until the payload amortizes the per-transfer dispatch
    # latency.  The escalation gates sit far BELOW the stage's payload
    # bandwidth ceiling: a fast link reads artificially low on a small
    # payload (1 MB at 20 GB/s with ~1 ms of dispatch latency measures
    # <1 GB/s), so any reading that latency alone could explain
    # escalates to the next payload.  best-of per stage: the result is
    # cached for the process, so one contended sample must not
    # misclassify the link.
    h2d = best_of(_PROBE_BYTES // 8, 3)
    if h2d > 0.2:
        # 1 MB above 0.2 GB/s is <=5 ms/transfer — could be pure
        # dispatch latency on a multi-GB/s link; re-measure with 8 MB
        h2d = max(h2d, best_of(_PROBE_BYTES, 3))
    if h2d > DEVICE_FINISH_MIN_H2D_GBPS / 4:
        # within reach of the decision threshold: confirm with a
        # payload big enough to amortize per-transfer overhead
        h2d = max(h2d, best_of(8 * _PROBE_BYTES, 3))
    # only h2d drives the decision, so only h2d is measured
    return {"h2d_gbps": h2d, "cpu": 0.0}


def measure(force: bool = False) -> Dict[str, float]:
    """Cached link measurements: {h2d_gbps, cpu}."""
    global _cache
    if force or not _cache:
        _cache = _measure()
    return _cache


def recommended_finish_tier(override: Optional[str] = None) -> str:
    """"host" or "device" for the log engines' fire finish.  An
    explicit override ("host"/"device") passes through untouched."""
    if override in ("host", "device"):
        return override
    m = measure()
    if m["cpu"]:
        return "host"
    return ("device" if m["h2d_gbps"] >= DEVICE_FINISH_MIN_H2D_GBPS
            else "host")

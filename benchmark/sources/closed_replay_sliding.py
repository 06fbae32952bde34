"""Closed-loop replay for a sliding-window job over (key, value)
events: ``closed_replay``'s own ``ReplaySource`` with a value pool in
the place of its user pool, over a timeline whose warm-up is long
enough for the sliding windows to fill.

What ``closed_replay`` calls a window is here one SLIDE PERIOD (the
configuration's ``window_ms``): the source emits ``events_per_window``
events into every period, the watermark that follows the first batch
of period ``w + 1`` fires the sliding window whose last pane is period
``w``, and the job emits that pane's start as its window column, so
the harness's clocks index fires by period as they do for a tumbling
job.  ``emitted()`` hands the reference one entry per period (pane).
"""

from __future__ import annotations

import time

import numpy as np

import loader
from timeline import Timeline

ReplaySource = loader.load_module("sources", "closed_replay").ReplaySource

#: periods emitted before ``t0``, beyond the panes of one window.  A
#: window of ten panes is full from period 9 on; by period 12 the
#: state table has reached its working capacity and the update, result
#: and clear programs their last shapes, so nothing compiles after
#: ``t0``
WARMUP_PERIODS_BEYOND_A_WINDOW = 2
#: a traced run profiles this period after the warm-up's last, as
#: ``closed_replay`` does
PROFILE_PERIOD_AFTER_WARMUP = 2
#: slide periods drawn from the seed: more than a run holds
POOL_PERIODS = 256


def make(config, traffic, seed, seconds, clock=time.perf_counter):
    """The source of one run: ``POOL_PERIODS`` periods of
    ``events_per_window`` events drawn from the seed, keys by the
    mix's distribution, values ``exp(N(value_mu, value_sigma))`` as
    float64."""
    epw, batch = config["events_per_window"], config["batch_rows"]
    if epw % batch:
        raise loader.CellError(
            f"events_per_window {epw} is not a whole number of "
            f"{batch}-row batches")
    if config["window_ms"] != config["slide_ms"]:
        raise loader.CellError(
            f"the harness's clocks index a fire by window_ms "
            f"{config['window_ms']}, the job by its slide "
            f"{config['slide_ms']}")
    warmup = config["window_size_ms"] // config["slide_ms"] \
        + WARMUP_PERIODS_BEYOND_A_WINDOW
    params = traffic["params"]
    rng = np.random.default_rng(seed)
    generator = loader.load_module("generators", traffic["key_distribution"])
    keys = generator.draw(rng, POOL_PERIODS * epw, config["key_space"],
                          params)
    keys = np.ascontiguousarray(keys, np.int64).reshape(POOL_PERIODS, epw)
    values = np.exp(rng.normal(params["value_mu"], params["value_sigma"],
                               (POOL_PERIODS, epw)))
    timeline = Timeline(warmup, seconds,
                        warmup + PROFILE_PERIOD_AFTER_WARMUP, clock)
    return ReplaySource(keys, values, epw, batch, config["window_ms"],
                        timeline)

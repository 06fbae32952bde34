"""Config #2 on the north-star route under a device-slot budget:
``datastream_state``'s job, word for word, with the keys the
configuration lists under ``state_backend_config`` set in the
environment's ``Configuration`` the way ``docs/state.md`` tells a user
to — so the budget reaches ``TpuKeyedStateBackend`` through
``env.execute()`` or not at all.  Nothing here constructs a backend.
"""

import loader
import spill
from flink_tpu.core.config import Configuration

_state = loader.load_module("jobs", "datastream_state")
UserHll, emit_row = _state.UserHll, _state.emit_row

BUDGET_KEY = "state.backend.tpu.max-device-slots"


def build(env, source, sink, config):
    for key, value in config["state_backend_config"].items():
        env.config.set(key, value)
    _state.build(env, source, sink, config)
    # a tree whose executors are handed the backend's name alone would
    # run this deployment uncapped, into RESOURCE_EXHAUSTED at the
    # 1,048,577th slot: refuse it here, before any data moves
    handed = env._make_executor().state_backend
    if not isinstance(handed, Configuration) or \
            handed.get_integer(BUDGET_KEY) != \
            config["state_backend_config"][BUDGET_KEY]:
        raise SystemExit(
            f"benchmark: {config['name']} needs {BUDGET_KEY} to reach the "
            f"state backend through env.execute(); this tree's executor "
            f"is handed {handed!r}, so the backend would run uncapped")
    spill.mark_counters(source.timeline)


def describe(op):
    """Facts about the route that ran, for an earlier line."""
    state = op.window_state
    return {**_state.describe(op),
            "budget": state.max_device_slots,
            "promotions": state.promotions,
            "budget_overruns": state.budget_overruns,
            # the last fire of a run is its one-batch closing window:
            # what the measured windows did is in the counters
            "in_measured_windows": {name: spill.counted(name)
                                    for name in spill.COUNTERS}}

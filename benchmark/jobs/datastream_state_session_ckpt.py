"""Config #4 as its users deploy it: ``datastream_state_session``'s
job, word for word (its ``build`` is loaded and called), with
checkpointing switched on from the configuration's ``checkpoint``
block: ``env.enable_checkpointing(interval, mode, async_persist)`` and
``env.set_checkpoint_storage("filesystem", directory, retain)``, the
directory ``benchmark_out/checkpoints/<configuration>/``, emptied
first.  ``checkpointing.enable`` refuses to build where the executor
would get no coordinator or the environment has no door to the running
job's client.
"""

import checkpointing
import loader

_plain = loader.load_module("jobs", checkpointing.PLAIN_JOB)


def build(env, source, sink, config):
    _plain.build(env, source, sink, config)
    checkpointing.enable(env, source, config)


def describe(op):
    return _plain.describe(op)

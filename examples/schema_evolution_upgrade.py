"""Upgrading a stateful job across a schema change — the flink-avro
state-evolution story (core/records.py).

A keyed job counts events per user into a schema'd record.  We run it
under schema v1, stop with a savepoint, then resume the SAME state
under schema v2 (a new field with a default, a long->double
promotion): restored values migrate via reader/writer resolution and
the stream finishes exactly-once.

    python examples/schema_evolution_upgrade.py
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))  # run from anywhere


import os
import tempfile
import time

from flink_tpu.core.records import RecordSchema, RecordSerializer
from flink_tpu.core.state import ValueStateDescriptor
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.operators import KeyedProcessFunction
from flink_tpu.streaming.sources import CollectSink, FromCollectionSource

V1 = RecordSchema([("count", "long")])
V2 = RecordSchema([("count", "double"),            # long -> double
                   ("region", "string", "unknown")])  # added w/ default


class Profile(KeyedProcessFunction):
    def __init__(self, schema):
        self.schema = schema

    def process_element(self, value, ctx, out):
        st = ctx.get_state(ValueStateDescriptor(
            "profile", serializer=RecordSerializer(self.schema)))
        cur = st.value() or {f.name: (f.default if f.has_default else 0)
                             for f in self.schema.fields}
        cur["count"] += 1
        st.update(cur)
        out.collect((value % 4, dict(cur)))


class Gated(FromCollectionSource):
    released = False

    def emit_step(self, ctx, max_records):
        if not type(self).released and self.offset >= 200:
            time.sleep(0.002)
            return True
        return super().emit_step(ctx, max_records)


def run(schema, savepoint=None, events=tuple(range(1000))):
    env = StreamExecutionEnvironment()
    env.enable_checkpointing(10)
    if savepoint:
        env.set_savepoint_restore(savepoint)
    sink = CollectSink()
    (env.add_source(Gated(list(events)), name="events")
        .key_by(lambda v: v % 4)
        .process(Profile(schema))
        .add_sink(sink))
    return env, sink


def main():
    d = tempfile.mkdtemp()
    env, _ = run(V1)
    client = env.execute_async("profiles-v1")
    path = client.stop_with_savepoint(os.path.join(d, "sp"))
    print(f"v1 job savepointed to {path}")

    Gated.released = True
    env2, sink2 = run(V2, savepoint=path)
    env2.execute("profiles-v2")
    finals = {}
    for k, rec in sink2.values:
        finals[k] = rec
    for k in sorted(finals):
        print(f"key {k}: {finals[k]}  "
              f"(count promoted to float, region defaulted)")
    assert all(isinstance(r["count"], float) for r in finals.values())
    assert all(r["region"] == "unknown" for r in finals.values())
    total = sum(r["count"] for r in finals.values())
    print(f"total counted across keys: {total:.0f} / 1000 "
          f"(exactly-once across the upgrade)")


if __name__ == "__main__":
    main()

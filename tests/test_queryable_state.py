"""Queryable state: write side + the external read path
(ref: flink-queryable-state — KvStateServerImpl/QueryableStateClient,
registration via AbstractKeyedStateBackend.java:382-389)."""

import time

import pytest

from flink_tpu.runtime.queryable import (
    DEFAULT_REGISTRY,
    KvStateRegistry,
    QueryableStateClient,
)
from flink_tpu.streaming.datastream import StreamExecutionEnvironment
from flink_tpu.streaming.sources import SourceFunction


@pytest.fixture(autouse=True)
def _clean_registry():
    DEFAULT_REGISTRY.unregister_all()
    yield
    DEFAULT_REGISTRY.unregister_all()


def test_query_after_finite_job():
    env = StreamExecutionEnvironment()
    (env.from_collection([("a", 1), ("b", 5), ("a", 3)])
        .key_by(lambda v: v[0])
        .as_queryable_state("latest"))
    env.execute("queryable-finite")
    client = QueryableStateClient()
    assert client.get_kv_state("latest", "a") == ("a", 3)
    assert client.get_kv_state("latest", "b") == ("b", 5)


def test_query_unknown_state_or_key():
    client = QueryableStateClient()
    with pytest.raises(KeyError):
        client.get_kv_state("nope", "k")
    env = StreamExecutionEnvironment()
    (env.from_collection([("a", 1)])
        .key_by(lambda v: v[0])
        .as_queryable_state("s1"))
    env.execute("queryable-2")
    assert client.get_kv_state("s1", "never-seen") is None


def test_query_live_unbounded_job():
    """The real shape: query while the job is running."""

    class Counter(SourceFunction):
        def __init__(self):
            self._running = True

        def run(self, ctx):
            i = 0
            while self._running:
                ctx.collect(("k", i))
                i += 1
                time.sleep(0.001)

        def cancel(self):
            self._running = False

    env = StreamExecutionEnvironment()
    (env.add_source(Counter())
        .key_by(lambda v: v[0])
        .as_queryable_state("live"))
    client = env.execute_async("queryable-live")
    q = QueryableStateClient()
    deadline = time.time() + 10
    seen = None
    while time.time() < deadline:
        try:
            seen = q.get_kv_state("live", "k")
            if seen is not None and seen[1] > 10:
                break
        except KeyError:
            pass
        time.sleep(0.01)
    client.cancel()
    client.wait(timeout=10)
    assert seen is not None and seen[1] > 10


def test_parallel_instances_route_by_key_group():
    env = StreamExecutionEnvironment()
    (env.from_collection([(f"k{i}", i) for i in range(40)])
        .rebalance()
        .map(lambda v: v, name="spread")
        .set_parallelism(4)
        .key_by(lambda v: v[0])
        .as_queryable_state("sharded"))
    env.execute("queryable-sharded")
    client = QueryableStateClient()
    for i in range(40):
        assert client.get_kv_state("sharded", f"k{i}") == (f"k{i}", i)


def test_custom_registry_isolated():
    reg = KvStateRegistry()
    client = QueryableStateClient(reg)
    with pytest.raises(KeyError):
        client.get_kv_state("anything", 1)


def test_query_device_backed_state():
    """Queryable reads against the TPU backend's device aggregation
    state (round-2 verdict item 5: the read path used to raise
    NotImplementedError for device-backed state)."""
    import numpy as np
    from flink_tpu.core.keygroups import KeyGroupRange
    from flink_tpu.core.state import AggregatingStateDescriptor
    from flink_tpu.ops.device_agg import SumAggregate
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend

    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128)
    desc = AggregatingStateDescriptor("dev_sum", SumAggregate(np.float64))
    st = be.get_partitioned_state((), desc)
    for k, v in [("a", 2.0), ("b", 5.0), ("a", 3.0)]:
        be.set_current_key(k)
        st.add(v)
    DEFAULT_REGISTRY.register("dev_sum", KeyGroupRange(0, 127), be, desc)
    client = QueryableStateClient()
    # pending adds flushed by the owner; queries see the device value
    st._flush()
    assert client.get_kv_state("dev_sum", "a", namespace=()) == 5.0
    assert client.get_kv_state("dev_sum", "b", namespace=()) == 5.0
    assert client.get_kv_state("dev_sum", "nope", namespace=()) is None
    # dirty-read semantics: an unflushed add is invisible
    be.set_current_key("a")
    st.add(10.0)
    assert client.get_kv_state("dev_sum", "a", namespace=()) == 5.0
    st._flush()
    assert client.get_kv_state("dev_sum", "a", namespace=()) == 15.0


def test_query_device_state_spilled_to_host_tier():
    """A key evicted to the host-RAM spill tier still answers queries
    (served from its spilled row, no promotion, no owner mutation)."""
    import numpy as np
    from flink_tpu.core.keygroups import KeyGroupRange
    from flink_tpu.core.state import AggregatingStateDescriptor
    from flink_tpu.ops.device_agg import SumAggregate
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend

    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128,
                              initial_capacity=8, microbatch=2,
                              max_device_slots=8)
    desc = AggregatingStateDescriptor("spill_sum",
                                      SumAggregate(np.float64))
    st = be.get_partitioned_state((), desc)
    keys = [f"k{i}" for i in range(40)]
    st.add_batch(keys, (), np.arange(40, dtype=np.float64))
    st._flush()
    assert st.evictions > 0
    spilled = next(iter(st.host_tier))[0] if st.host_tier else None
    assert spilled is not None
    DEFAULT_REGISTRY.register("spill_sum", KeyGroupRange(0, 127), be,
                              desc)
    client = QueryableStateClient()
    promotions_before = st.promotions
    v = client.get_kv_state("spill_sum", spilled, namespace=())
    assert v == float(spilled[1:])      # value == key index
    assert st.promotions == promotions_before  # read did not promote
    # a device-resident key answers too
    resident = st.slot_key[np.flatnonzero(st._slot_live)[0]]
    assert st.slot_index.get(resident, ()) is not None
    assert client.get_kv_state("spill_sum", resident,
                               namespace=()) == float(resident[1:])


def test_query_device_state_through_job_api():
    """as_queryable_state with a device aggregate through the
    DataStream API: the end-to-end registration + read path."""
    import numpy as np
    from flink_tpu.core.state import AggregatingStateDescriptor
    from flink_tpu.ops.device_agg import SumAggregate

    class TupleSum(SumAggregate):
        def __init__(self):
            super().__init__(np.float64)

        def extract_value(self, v):
            return v[1]

    env = StreamExecutionEnvironment()
    env.set_state_backend("tpu")
    (env.from_collection([("a", 1.0), ("b", 5.0), ("a", 3.0)])
        .key_by(lambda v: v[0])
        .as_queryable_state(
            "dev_totals",
            AggregatingStateDescriptor("dev_totals", TupleSum())))
    env.execute("queryable-device")
    client = QueryableStateClient()
    assert client.get_kv_state("dev_totals", "a") == 4.0
    assert client.get_kv_state("dev_totals", "b") == 5.0


def test_query_new_key_with_only_pending_adds_is_invisible():
    """A key whose FIRST adds are still in the pending micro-batch
    must read as absent (None / default), not as the init accumulator
    (code-review regression: a fresh slot in slot_index surfaced 0.0
    before anything had flushed)."""
    import numpy as np
    from flink_tpu.core.keygroups import KeyGroupRange
    from flink_tpu.core.state import AggregatingStateDescriptor
    from flink_tpu.ops.device_agg import SumAggregate
    from flink_tpu.state.tpu_backend import TpuKeyedStateBackend

    be = TpuKeyedStateBackend(KeyGroupRange(0, 127), 128)
    desc = AggregatingStateDescriptor("pend_sum", SumAggregate(np.float64))
    st = be.get_partitioned_state((), desc)
    be.set_current_key("fresh")
    st.add(7.0)                       # pending, never flushed
    DEFAULT_REGISTRY.register("pend_sum", KeyGroupRange(0, 127), be, desc)
    client = QueryableStateClient()
    assert client.get_kv_state("pend_sum", "fresh", namespace=()) is None
    st._flush()
    assert client.get_kv_state("pend_sum", "fresh", namespace=()) == 7.0


def test_query_all_state_kinds_both_backends():
    """Every state kind answers through the registry on BOTH backends
    (VERDICT r4 weak #8): list/map over the table, aggregating states
    finalize their accumulator (the state.get() contract, not the raw
    acc), device-backed aggregates read through query_by_key."""
    import numpy as np
    from flink_tpu.core.keygroups import KeyGroupRange
    from flink_tpu.core.state import (
        AggregatingStateDescriptor,
        ListStateDescriptor,
        MapStateDescriptor,
        ReducingStateDescriptor,
        ValueStateDescriptor,
    )
    from flink_tpu.state.loader import load_state_backend

    class PyAvg:
        def create_accumulator(self):
            return (0.0, 0)

        def add(self, v, acc):
            return (acc[0] + v, acc[1] + 1)

        def get_result(self, acc):
            return acc[0] / acc[1]

        def merge(self, a, b):
            return (a[0] + b[0], a[1] + b[1])

    from flink_tpu.core.functions import AggregateFunction
    PyAvg = type("PyAvg", (AggregateFunction,), dict(PyAvg.__dict__))

    for backend_name in ("heap", "tpu"):
        b = load_state_backend(backend_name, KeyGroupRange(0, 127), 128)
        b.set_current_key(5)
        descs = {
            "qv": ValueStateDescriptor("qv"),
            "ql": ListStateDescriptor("ql"),
            "qm": MapStateDescriptor("qm"),
            "qr": ReducingStateDescriptor("qr", lambda a, c: a + c),
            "qa": AggregatingStateDescriptor("qa", PyAvg()),
        }
        states = {n: b.get_or_create_keyed_state(d)
                  for n, d in descs.items()}
        states["qv"].update(7)
        states["ql"].add(1)
        states["ql"].add(2)
        states["qm"].put("k", 3)
        states["qr"].add(4)
        states["qr"].add(6)
        states["qa"].add(2.0)
        states["qa"].add(4.0)
        reg = KvStateRegistry()
        client = QueryableStateClient(reg)
        for n, d in descs.items():
            reg.register(n, KeyGroupRange(0, 127), b, d)
        assert client.get_kv_state("qv", 5) == 7
        assert client.get_kv_state("ql", 5) == [1, 2]
        assert client.get_kv_state("qm", 5) == {"k": 3}
        assert client.get_kv_state("qr", 5) == 10
        # finalized result, not the raw (sum, count) accumulator
        assert client.get_kv_state("qa", 5) == 3.0

"""The bulk timer sweep (`pop_due_event_time_timers`) that feeds the
batched window fire path: pop-order parity with `advance_watermark`,
dedup, bulk registration/deletion seq contracts, and snapshot
round-trips of a half-swept heap."""

import copy

import numpy as np
import pytest

from flink_tpu.core.keygroups import KeyGroupRange
from flink_tpu.streaming.timers import InternalTimerService


class _FakeBackend:
    def __init__(self, max_parallelism=128):
        self.current_key = None
        self.max_parallelism = max_parallelism
        self.key_group_range = KeyGroupRange(0, max_parallelism - 1)

    def set_current_key(self, key):
        self.current_key = key


class _Recorder:
    """Triggerable that records (timestamp, key, namespace) fire order
    plus the backend key context at fire time."""

    def __init__(self, backend):
        self.backend = backend
        self.fired = []

    def on_event_time(self, timer):
        assert self.backend.current_key == timer.key
        self.fired.append((timer.timestamp, timer.key, timer.namespace))

    def on_processing_time(self, timer):
        raise AssertionError("no processing-time timers in these tests")


def _service():
    backend = _FakeBackend()
    rec = _Recorder(backend)
    svc = InternalTimerService("t", backend, None, rec)
    return svc, backend, rec


def _sweep(svc, watermark):
    """The bulk sweep's runs end to end, as (timestamps, keys,
    namespaces) columns in firing order."""
    ts, keys, ns = [], [], []
    for timestamp, namespace, run in svc.pop_due_event_time_timers(watermark):
        assert run  # no empty run is handed over
        ts.extend([timestamp] * len(run))
        keys.extend(run)
        ns.extend([namespace] * len(run))
    return ts, keys, ns


def _register(svc, backend, entries):
    for ts, key, ns in entries:
        backend.set_current_key(key)
        svc.register_event_time_timer(ns, ts)


MIXED = [
    (5, "a", (0, 5)),
    (3, "b", (0, 3)),
    (5, "b", (0, 5)),     # same ts as first — registration order decides
    (9, "a", (4, 9)),
    (3, "a", (0, 3)),
    (7, "c", (2, 7)),
    (12, "a", (7, 12)),   # beyond the sweep watermark
    (12, "b", (7, 12)),
]


def test_sweep_matches_advance_watermark_order():
    svc1, b1, rec = _service()
    _register(svc1, b1, MIXED)
    svc2, b2, _ = _service()
    _register(svc2, b2, MIXED)

    svc1.advance_watermark(9)
    ts, keys, ns = _sweep(svc2, 9)

    assert list(zip(ts, keys, ns)) == rec.fired
    assert svc1.current_watermark == svc2.current_watermark == 9
    # identical survivors: only the ts=12 timers
    assert sorted(svc1.event_time_timers()) \
        == sorted(svc2.event_time_timers()) \
        == [(12, "a", (7, 12)), (12, "b", (7, 12))]
    assert svc2.num_event_time_timers() == 2


def test_sweep_skips_lazily_deleted_timers():
    svc, backend, _ = _service()
    _register(svc, backend, MIXED)
    backend.set_current_key("b")
    svc.delete_event_time_timer((0, 5), 5)
    ts, keys, ns = _sweep(svc, 9)
    assert (5, "b", (0, 5)) not in set(zip(ts, keys, ns))
    assert len(ts) == 5


def test_sweep_dedup_single_pop_per_entry():
    svc, backend, _ = _service()
    backend.set_current_key("k")
    for _ in range(3):  # re-registration is a no-op
        svc.register_event_time_timer((0, 4), 4)
    ts, keys, ns = _sweep(svc, 10)
    assert ts == [4] and keys == ["k"] and ns == [(0, 4)]
    # the swept timer is gone: a second sweep finds nothing
    assert svc.pop_due_event_time_timers(10) == []


def test_bulk_registration_preserves_registration_order():
    """Same-timestamp timers pop in bulk-registration (first
    occurrence) order — the seq contract the batched window ingest
    relies on for deterministic same-timestamp fire order."""
    svc, backend, _ = _service()
    svc.register_event_time_timers_bulk((0, 8), 8, ["x", "y", "x", "z"])
    svc.register_event_time_timers_bulk((0, 8), 8, ["y", "w"])  # dups free
    ts, keys, ns = _sweep(svc, 8)
    assert keys == ["x", "y", "z", "w"]
    assert ts == [8, 8, 8, 8]


def test_bulk_delete_matches_scalar_delete():
    svc, backend, _ = _service()
    _register(svc, backend, MIXED)
    svc.delete_event_time_timers_bulk((0, 3), 3, ["b", "nobody"])
    svc.delete_event_time_timers_bulk((2, 7), 7, ["c"])
    # absent run: no-op, same as the scalar delete
    svc.delete_event_time_timers_bulk((0, 99), 99, ["zz"])
    ts, keys, ns = _sweep(svc, 9)
    got = set(zip(ts, keys, ns))
    assert (3, "b", (0, 3)) not in got
    assert (7, "c", (2, 7)) not in got
    assert len(ts) == 4


def test_half_swept_heap_snapshot_round_trip():
    """Snapshot after a partial sweep: popped timers must NOT revive,
    undue timers must survive and fire in the same order as an
    unsnapshotted service."""
    svc, backend, rec = _service()
    _register(svc, backend, MIXED)
    svc.pop_due_event_time_timers(5)  # pops ts 3,3,5,5
    snap = svc.snapshot()
    assert snap["watermark"] == 5

    svc2, b2, rec2 = _service()
    svc2.restore([snap])
    assert svc2.num_event_time_timers() == svc.num_event_time_timers() == 4

    ts, keys, ns = _sweep(svc, 100)
    ts2, keys2, ns2 = _sweep(svc2, 100)
    assert sorted(zip(ts, keys, ns)) == sorted(zip(ts2, keys2, ns2))
    # per-timestamp order: restore rebuilds seq from set iteration, so
    # only the (timestamp) order is contractual across a restore —
    # which both sides honor
    assert ts == sorted(ts) and ts2 == sorted(ts2)


def test_sweep_then_advance_watermark_interleave():
    """A sweep and the scalar drain compose: timers registered after a
    sweep fire normally through advance_watermark."""
    svc, backend, rec = _service()
    _register(svc, backend, MIXED[:4])
    svc.pop_due_event_time_timers(5)
    _register(svc, backend, [(6, "z", (0, 6))])
    svc.advance_watermark(9)
    assert rec.fired == [(6, "z", (0, 6)), (9, "a", (4, 9))]


@pytest.mark.parametrize("watermark", [-1, 0, 2])
def test_sweep_below_all_timers_is_empty(watermark):
    svc, backend, _ = _service()
    _register(svc, backend, MIXED)
    before = svc.num_event_time_timers()
    assert svc.pop_due_event_time_timers(watermark) == []
    assert svc.num_event_time_timers() == before


# ---- the store against a plain one-entry-per-timer reference ---------
# Every scenario is a list of steps run against the service and against
# `_PlainTimers`: a dict of live timers with their registration
# numbers, the next to fire found by min().  A removal is a removal, so
# a timer deleted and registered again fires at its NEW place (the
# heap of one node per timer this store replaced fired it at the old
# one: test_delete_then_register_fires_at_the_new_place).

class _PlainTimers:
    def __init__(self):
        self.live = {}
        self.seq = 0

    def register(self, ts, key, ns):
        if (ts, key, ns) not in self.live:
            self.live[(ts, key, ns)] = self.seq
            self.seq += 1

    def delete(self, ts, key, ns):
        self.live.pop((ts, key, ns), None)

    def advance(self, watermark, on_fire):
        fired = []
        while True:
            due = [(ts, seq, key, ns)
                   for (ts, key, ns), seq in self.live.items()
                   if ts <= watermark]
            if not due:
                return fired
            ts, _, key, ns = min(due, key=lambda t: t[:2])
            del self.live[(ts, key, ns)]
            fired.append((ts, key, ns))
            for step in on_fire.get((ts, key, ns), ()):
                self.apply(step)

    def apply(self, step):
        op, ts, ns, keys = step
        for key in keys:
            (self.delete if op.startswith("delete") else self.register)(
                ts, key, ns)


class _Driver:
    """Runs the same steps through the service's entries: the scalar
    ones per key, the bulk ones where a step asks for them; the
    processing-time ones when `domain` says so."""

    def __init__(self, domain, on_fire):
        from flink_tpu.streaming.timers import TestProcessingTimeService
        self.backend = _FakeBackend()
        self.pts = TestProcessingTimeService()
        self.domain = domain
        self.on_fire = on_fire
        self.fired = []
        self.svc = InternalTimerService("t", self.backend, self.pts, self)

    def _timer(self, timer):
        assert self.backend.current_key == timer.key
        entry = (timer.timestamp, timer.key, timer.namespace)
        self.fired.append(entry)
        for step in self.on_fire.get(entry, ()):
            self.apply(step)

    on_event_time = on_processing_time = _timer

    def apply(self, step):
        op, ts, ns, keys = step
        svc = self.svc
        if self.domain == "processing":
            entry = (svc.delete_processing_time_timer
                     if op.startswith("delete")
                     else svc.register_processing_time_timer)
        elif op == "register_bulk":
            return svc.register_event_time_timers_bulk(ns, ts, keys)
        elif op == "delete_bulk":
            return svc.delete_event_time_timers_bulk(ns, ts, keys)
        else:
            entry = (svc.delete_event_time_timer if op == "delete"
                     else svc.register_event_time_timer)
        for key in keys:
            self.backend.set_current_key(key)
            entry(ns, ts)

    def live(self):
        return sorted(self.svc.processing_time_timers()
                      if self.domain == "processing"
                      else self.svc.event_time_timers())

    def count(self):
        return (self.svc.num_processing_time_timers()
                if self.domain == "processing"
                else self.svc.num_event_time_timers())

    def advance(self, watermark, sweep=False):
        self.fired = []
        if self.domain == "processing":
            self.pts.set_current_time(watermark)
        elif sweep:
            self.fired = list(zip(*_sweep(self.svc, watermark)))
        else:
            self.svc.advance_watermark(watermark)
        return self.fired


A, B, C = (0, 10), (10, 20), (20, 30)   # tumbling 10; lateness 10 below

SCENARIOS = {
    # window A's cleanup timers and window B's fire timers are both at
    # 19 and interleave by registration under out-of-order input
    "two_namespaces_tie_interleaved": (
        [("register", 9, A, ["a"]), ("register", 19, A, ["a"]),
         ("register", 19, B, ["b"]), ("register", 9, A, ["c"]),
         ("register", 19, A, ["c"]), ("register", 19, B, ["a"]),
         ("register", 19, A, ["d"]), ("register", 29, B, ["b", "a"])],
        {}, [19, 40]),
    # ... and in order: two whole runs, no cut
    "two_namespaces_tie_in_order": (
        [("register_bulk", 19, A, ["a", "b", "c"]),
         ("register_bulk", 19, B, ["c", "a"]),
         ("register_bulk", 19, C, ["z"])],
        {}, [19]),
    "bulk_across_batches_with_repeats": (
        [("register_bulk", 9, A, ["x", "y", "x", "z"]),
         ("register_bulk", 19, B, ["y"]),
         ("register_bulk", 9, A, ["y", "w", "x"]),
         ("register", 9, A, ["v"]),
         ("register_bulk", 9, A, ["u", "v", "t", "u"]),
         ("register_bulk", 9, A, [])],
        {}, [9, 19]),
    # a bulk run meets a tie: its numbers come out of its spans
    "bulk_runs_tie_interleaved": (
        [("register_bulk", 19, A, ["a", "b"]),
         ("register_bulk", 19, B, ["a", "b"]),
         ("register_bulk", 19, A, ["b", "c", "d"]),
         ("register", 19, B, ["e"]),
         ("register_bulk", 19, A, ["f"])],
        {}, [19]),
    "delete_then_register": (
        [("register", 9, A, ["a", "b", "c"]), ("delete", 9, A, ["a"]),
         ("register", 9, A, ["a"]), ("register", 9, A, ["d"])],
        {}, [9]),
    "delete_from_a_bulk_run_then_bulk_again": (
        [("register_bulk", 9, A, ["a", "b", "c", "d"]),
         ("delete", 9, A, ["b"]),
         ("register_bulk", 9, A, ["e", "b", "a"]),
         ("delete_bulk", 9, A, ["d", "nobody"]),
         ("register", 9, A, ["d"])],
        {}, [9]),
    "run_emptied_by_deletes": (
        [("register", 5, A, ["a", "b"]), ("register", 7, A, ["a"]),
         ("delete", 5, A, ["a"]), ("delete", 5, A, ["b"]),
         ("delete", 5, A, ["b"]), ("delete_bulk", 7, A, ["a"]),
         ("register", 8, B, ["k"])],
        {}, [9]),
    # a callback registers timers at or below the watermark: earlier
    # than the one that fires, tied with it, and in its own run
    "callback_registers_below_the_watermark": (
        [("register", 5, A, ["a", "b", "c"]), ("register", 8, A, ["a"])],
        {(5, "a", A): [("register", 3, B, ["early"]),
                       ("register", 5, B, ["tied"]),
                       ("register", 5, A, ["own", "b"]),
                       ("register", 8, A, ["late"]),
                       ("register", 99, A, ["beyond"])],
         (3, "early", B): [("register", 2, C, ["earlier_still"])]},
        [9]),
    "callback_deletes_and_registers_anew": (
        [("register", 5, A, ["a", "b", "c", "d"]), ("register", 6, B, ["a"])],
        {(5, "a", A): [("delete", 5, A, ["c"]), ("delete", 5, A, ["b"]),
                       ("register", 5, A, ["b"]), ("delete", 6, B, ["a"])],
         (5, "d", A): [("register", 5, A, ["a"])]},
        [9]),
    "callback_empties_the_run_it_fires_from": (
        [("register", 5, A, ["a", "b", "c"]), ("register", 7, A, ["x"])],
        {(5, "a", A): [("delete", 5, A, ["b", "c"]),
                       ("register", 5, A, ["c"])]},
        [9]),
    # one key per timestamp, the shape a process function gives
    "one_key_per_timestamp": (
        [("register", t, (), [f"k{t % 3}"]) for t in (7, 3, 9, 1, 3, 8)],
        {}, [5, 9]),
    # runs of one key (kept as a pair) that a second key joins, by the
    # scalar and by the bulk entry, tied with each other on a timestamp
    "runs_of_one_key_grow_and_tie": (
        [("register", 5, A, ["a"]), ("register", 5, B, ["b"]),
         ("register", 5, C, ["c"]), ("register", 5, A, ["a", "d"]),
         ("register_bulk", 5, B, ["e", "b", "f"]),
         ("delete", 5, C, ["nobody"]), ("register", 7, C, ["c"]),
         ("delete_bulk", 7, C, ["x", "c"]), ("register", 5, A, ["g"])],
        {}, [9]),
    # a callback's deletes leave more stale heap nodes than runs: the
    # heap is rebuilt under the drain's feet
    "callback_deletes_enough_for_a_heap_rebuild": (
        [("register", t, A, ["k"]) for t in range(100)]
        + [("register", 50, B, ["k", "j"])],
        {(0, "k", A): [("delete", t, A, ["k"]) for t in range(1, 90)]
         + [("register", 40, C, ["new"]), ("delete", 50, B, ["k"])],
         (95, "k", A): [("register", 20, A, ["back"])]},
        [200]),
}


def _play(driver, steps, watermarks, sweep=False):
    for step in steps:
        driver.apply(step)
    return [(driver.advance(wm, sweep), driver.live()) for wm in watermarks]


@pytest.mark.parametrize("domain", ["event", "processing"])
@pytest.mark.parametrize("name", list(SCENARIOS))
def test_drain_fires_as_one_entry_per_timer_would(name, domain):
    steps, on_fire, watermarks = SCENARIOS[name]
    plain = _PlainTimers()
    for step in steps:
        plain.apply(step)
    want = [(plain.advance(wm, on_fire), sorted(plain.live))
            for wm in watermarks]
    assert any(fired for fired, _ in want)
    driver = _Driver(domain, on_fire)
    assert _play(driver, steps, watermarks) == want
    assert driver.count() == len(plain.live)


@pytest.mark.parametrize("name", [n for n, s in SCENARIOS.items()
                                  if not s[1]])
def test_sweep_hands_over_the_drains_order_in_maximal_runs(name):
    """The bulk sweep is for callers whose callbacks register nothing:
    its runs end to end are the drain's order, and a run ends only
    where the timestamp or the namespace changes."""
    steps, _, watermarks = SCENARIOS[name]
    assert _play(_Driver("event", {}), steps, watermarks, sweep=True) \
        == _play(_Driver("event", {}), steps, watermarks)
    driver = _Driver("event", {})
    for step in steps:
        driver.apply(step)
    runs = driver.svc.pop_due_event_time_timers(watermarks[-1])
    heads = [(ts, ns) for ts, ns, _ in runs]
    assert all(a != b for a, b in zip(heads, heads[1:]))
    assert [ts for ts, _ in heads] == sorted(ts for ts, _ in heads)


class _Once(dict):
    """Callbacks that each run the first time their timer fires only:
    random ones would re-arm each other for ever."""

    def get(self, timer, default=()):
        return self.pop(timer, default)


def _random_steps(rng, n):
    namespaces = [A, B, C]
    ops = ["register"] * 4 + ["register_bulk"] * 3 + ["delete", "delete_bulk"]
    steps = []
    for _ in range(n):
        op = ops[rng.integers(len(ops))]
        keys = rng.integers(0, 6, rng.integers(1, 5)).tolist()
        steps.append((op, int(rng.integers(0, 8)),
                      namespaces[rng.integers(3)], keys))
    return steps


@pytest.mark.parametrize("seed", range(12))
def test_random_steps_fire_as_one_entry_per_timer_would(seed):
    """Random registrations and deletes over few keys, timestamps and
    namespaces (so runs tie, grow from one key and empty), callbacks
    that do more of the same mid-drain; watermarks in three steps."""
    rng = np.random.default_rng(seed)
    steps = _random_steps(rng, 40)
    plain = _PlainTimers()
    for step in steps:
        plain.apply(step)
    on_fire = {timer: [("delete" if step[0].startswith("delete")
                        else "register", *step[1:])
                       for step in _random_steps(rng, 3)]
               for timer in list(plain.live)[::3]}
    watermarks = [2, 5, 9]
    # no callbacks: the drain and the bulk sweep, all timers at once
    all_at_once = [(copy.deepcopy(plain).advance(9, {}), [])]
    for sweep in (False, True):
        assert _play(_Driver("event", {}), steps, [9], sweep) == all_at_once
    once = _Once(on_fire)
    want = [(plain.advance(wm, once), sorted(plain.live))
            for wm in watermarks]
    assert len(once) < len(on_fire)  # callbacks did run
    for domain in ("event", "processing"):
        driver = _Driver(domain, _Once(on_fire))
        assert _play(driver, steps, watermarks) == want
        assert driver.count() == len(plain.live)


def test_a_run_made_anew_again_and_again_leaves_no_pile_of_nodes():
    """Delete-all then register of the SAME (timestamp, namespace):
    each round leaves a stale node behind, the rebuild takes them."""
    svc, backend, rec = _service()
    backend.set_current_key("k")
    svc.register_event_time_timer(B, 19)
    for _ in range(1000):
        svc.register_event_time_timer(A, 9)
        svc.delete_event_time_timer(A, 9)
    svc.register_event_time_timer(A, 9)
    assert len(svc._event.heap) <= 2 * 2 + 32 + 1
    svc.advance_watermark(30)
    assert rec.fired == [(9, "k", A), (19, "k", B)]
    assert svc._event.heap == [] and svc._event.runs == {}


def test_delete_then_register_fires_at_the_new_place():
    """Pinned: a removal is real, so the timer registered again is a
    new timer behind those registered in between."""
    svc, backend, rec = _service()
    _register(svc, backend, [(5, "a", A), (5, "b", A)])
    backend.set_current_key("a")
    svc.delete_event_time_timer(A, 5)
    _register(svc, backend, [(5, "c", A), (5, "a", A)])
    svc.advance_watermark(5)
    assert [key for _, key, _ in rec.fired] == ["b", "c", "a"]


def test_a_tumbling_windows_timers_are_one_run_and_one_heap_node():
    svc, backend, _ = _service()
    for batch in range(4):
        svc.register_event_time_timers_bulk(
            A, 9, list(range(batch * 500, batch * 500 + 1000)))
    store = svc._event
    assert store.heap == [(9, 0, (9, A))] and svc.num_event_time_timers() == 2500
    assert list(store.runs) == [(9, A)] and store.spans == {(9, A): [[2500, 0]]}
    (run,) = svc.pop_due_event_time_timers(9)
    assert run == (9, A, list(range(2500)))
    assert store.heap == [] and store.runs == {} and store.spans == {}


def test_deletes_leave_no_pile_of_stale_heap_nodes():
    """A session that keeps moving its timer: the store holds a node
    for the live timestamp and a bounded number of stale ones."""
    svc, backend, rec = _service()
    backend.set_current_key("k")
    for t in range(10_000):
        svc.delete_event_time_timer((0, t), t)
        svc.register_event_time_timer((0, t + 1), t + 1)
    assert svc.num_event_time_timers() == 1
    assert len(svc._event.runs) == 1
    assert len(svc._event.heap) <= 2 * 1 + 32 + 1
    svc.advance_watermark(10 ** 6)
    assert rec.fired == [(10_000, "k", (0, 10_000))]
    assert svc._event.heap == []


def test_processing_time_timers_arm_the_clock_for_the_earliest_live_one():
    from flink_tpu.streaming.timers import TestProcessingTimeService

    class Rec:
        def __init__(self):
            self.fired = []

        def on_processing_time(self, timer):
            self.fired.append((timer.timestamp, timer.key))

    backend, pts, rec = _FakeBackend(), TestProcessingTimeService(), Rec()
    svc = InternalTimerService("t", backend, pts, rec)
    backend.set_current_key("a")
    for t in (30, 10, 20):
        svc.register_processing_time_timer((), t)
    svc.delete_processing_time_timer((), 20)
    pts.set_current_time(10)
    assert rec.fired == [(10, "a")]
    # the next wake-up is for 30, the earliest LIVE timer, not for 20
    assert svc._next_proc_registered == 30
    pts.set_current_time(30)
    assert rec.fired == [(10, "a"), (30, "a")]
    assert svc.num_processing_time_timers() == 0 and not pts.has_pending()


def _parent_shape_snapshot(max_parallelism=128):
    """A snapshot as the parent's InternalTimerService.snapshot() wrote
    it: {"watermark", "event": {key group: [(ts, key, ns)]}, "proc"}."""
    from flink_tpu.core.keygroups import assign_to_key_group
    event = [(19, "a", A), (9, "b", A), (19, "b", B), (19, "c", A),
             (29, "a", C), (9, "a", A)]
    proc = [(50, "a", ()), (40, "z", ())]
    snap = {"watermark": 4, "event": {}, "proc": {}}
    for name, timers in (("event", event), ("proc", proc)):
        for ts, key, ns in timers:
            snap[name].setdefault(
                assign_to_key_group(key, max_parallelism), []).append(
                    (ts, key, ns))
    return snap, event, proc


def test_a_snapshot_in_the_parents_shape_restores_and_fires():
    snap, event, proc = _parent_shape_snapshot()
    driver = _Driver("event", {})
    driver.svc.restore([snap])
    assert sorted(driver.svc.event_time_timers()) == sorted(event)
    assert sorted(driver.svc.processing_time_timers()) == sorted(proc)
    # the shape goes out as it came in
    again = driver.svc.snapshot()
    assert set(again) == {"watermark", "event", "proc"}
    for name in ("event", "proc"):
        assert set(again[name]) == set(snap[name])
        for kg, timers in again[name].items():
            assert sorted(timers) == sorted(snap[name][kg])
            assert all(type(t) is tuple and len(t) == 3 for t in timers)
    fired = driver.advance(19)
    assert [ts for ts, _, _ in fired] == [9, 9, 19, 19, 19]
    assert sorted(fired) == sorted(t for t in event if t[0] <= 19)
    assert driver.live() == [(29, "a", C)]
    # restored processing-time timers are armed on the clock
    driver.pts.set_current_time(45)
    assert driver.fired[-1] == (40, "z", ())


def test_restore_keeps_only_the_key_groups_of_its_range():
    snap, event, _ = _parent_shape_snapshot()
    halves = []
    for lo, hi in ((0, 63), (64, 127)):
        backend = _FakeBackend()
        backend.key_group_range = KeyGroupRange(lo, hi)
        svc = InternalTimerService("t", backend, None, _Recorder(backend))
        svc.restore([{**snap, "proc": {}}])
        halves.append(sorted(svc.event_time_timers()))
    assert sorted(halves[0] + halves[1]) == sorted(event)
    assert halves[0] and halves[1]

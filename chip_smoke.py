#!/usr/bin/env python3
"""chip_smoke.py — BASELINE.json config #2 end to end on one TPU chip.

    python3 chip_smoke.py [--seed N] [--events-per-window N]

The quickest proof that the system still starts on the chip: one
process, no children, data made from ``--seed``.  It drives
``keyBy().window(1 s).aggregate(HLL)`` — 1,000,000 uniform int64 keys,
HLL precision 12, three 1 s tumbling event-time windows of 2^22 events
— through ``StreamExecutionEnvironment.execute()`` on every route a
user can reach, checks each route's output against a plain
``np.unique`` count of the same events, and ends with one report.

Legs:
  1  device gate        platform must be "tpu"
  2  state backend      env.set_state_backend("tpu"): WindowOperator →
                        TpuKeyedStateBackend → DeviceAggregatingState,
                        ~4 GiB of registers live in HBM per window
  2b spill tier         the same route at the north star's 10,000,000
                        keys under state.backend.tpu.max-device-slots =
                        2^20, set in the environment's Configuration:
                        2^21 events a window, ~1.89M live keys, so the
                        coldest rows go to host RAM in bulk, come back
                        on access and fire from there
  2c sliding quantiles  config #3 on the same route: sliding 10 s / 1 s
                        p50/p99 (QuantileSketchAggregate) over 10,000,000
                        Zipf keys, 14 slides of 16,384 events under a
                        budget of 2^19 slots: ten state rows an event,
                        every window against exact order statistics,
                        every flush against the full-grown table by the
                        tile kernel (on the TPU; no cell scattered)
  2d session Count-Min  config #4 on the same route: 10 s-gap session
                        windows of Count-Min sketches over 1,000,000
                        Zipf keys, read from a 4-partition replayable
                        log by the program's own connector, 14 periods
                        of 32,768 events under a budget of 2^17 slots,
                        every session against tests/
                        session_countmin_reference.py; fails on an
                        eviction, a boxed batch, a late row or a
                        per-key probe of the slot index beyond the
                        merges' targets
  2e session checkpoint leg 2d's job checkpointing every 5 s
                        (asynchronous, to a filesystem directory); the
                        consumer waits two periods before the end of
                        the log for one of the open sessions at their
                        working number; one restore from what that
                        directory retains by a second job, whose rows
                        must be the first job's for every session that
                        fired after the checkpoint; prints the
                        barrier's synchronous ms, the capture's and
                        the written bytes, trigger -> durable ms,
                        restore s
  3a SQL                TUMBLE + APPROX_COUNT_DISTINCT (config #5)
  3b DataStream default aggregate() → DeviceWindowOperator's batch door
  4  device kernels     the entry() step, the log tier's device finish
                        against its host finish, an AvgAggregate job on
                        the scatter tier
  5  fused chain        map → filter → keyBy as ONE jitted program
  6  mesh (>= 4 chips)  leg 3a over a 4-device mesh

Exit status 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", ...}}`` only when every
leg passed on a TPU.  ``--cpu-preflight`` runs every leg at a tiny
size on whatever device jax has — a rehearsal for the sandbox, printed
as such; without it, no chip is a failure.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import sys
import time
import traceback

try:
    import jax
    import numpy as np

    import flink_tpu
except ImportError as e:  # alone in a directory: nothing to smoke
    print(f"chip_smoke: cannot import the system beside this file: {e}",
          file=sys.stderr)
    sys.exit(2)

import flink_tpu.native as nat  # noqa: E402
from flink_tpu.core.config import Configuration  # noqa: E402
from flink_tpu.ops import link_probe  # noqa: E402
from flink_tpu.ops.device_agg import AvgAggregate, SumAggregate  # noqa: E402
from flink_tpu.connectors.log_connector import (  # noqa: E402
    ReplayableLogSource,
)
from flink_tpu.connectors.partitioned_log import (  # noqa: E402
    ColumnarPartitionedLog,
)
from flink_tpu.ops.sketches import (  # noqa: E402
    CountMinSketchAggregate,
    HyperLogLogAggregate,
    QuantileSketchAggregate,
)
from flink_tpu.runtime import tracing  # noqa: E402
from flink_tpu.runtime.device_stats import get_telemetry  # noqa: E402
from flink_tpu.state.stats import STATE_STATS  # noqa: E402
from flink_tpu.streaming import chain_fusion  # noqa: E402
from flink_tpu.streaming.columnar import (  # noqa: E402
    ColumnarSource,
    ColumnarWindowOperator,
)
from flink_tpu.streaming.datastream import (  # noqa: E402
    StreamExecutionEnvironment,
)
from flink_tpu.streaming.device_window_operator import (  # noqa: E402
    DeviceWindowOperator,
)
from flink_tpu.streaming.elements import RecordBatch  # noqa: E402
from flink_tpu.streaming.sources import SinkFunction  # noqa: E402
from flink_tpu.streaming.window_operator import WindowOperator  # noqa: E402
from flink_tpu.streaming.windowing import (  # noqa: E402
    EventTimeSessionWindows,
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.table import StreamTableEnvironment  # noqa: E402

WINDOW_MS = 1000
#: leg 2e checkpoints as the cell `session_cm_log_ckpt.zipf` does
CHECKPOINT_INTERVAL_MS = 5000
#: operators work in batches of this many rows; it also bounds how many
#: slots a batch that straddles a window end can claim before the old
#: window's slots are released
BATCH_ROWS = 8192

FULL = dict(keys=1_000_000, events_per_window=1 << 22, windows=3,
            precision=12, side_events=1 << 20, fused_events=1 << 18,
            fused_keys=4096,
            spill=dict(keys=10_000_000, events_per_window=1 << 21,
                       budget=1 << 20, microbatch=None),
            sliding=dict(keys=10_000_000, events_per_slide=1 << 14,
                         slides=14, budget=1 << 19),
            session=dict(keys=1_000_000, items=1 << 20,
                         events_per_period=1 << 15, periods=14,
                         gap_ms=10_000, budget=1 << 17))
TINY = dict(keys=256, events_per_window=4096, windows=3,
            precision=12, side_events=4096, fused_events=8192,
            fused_keys=64,
            # the microbatch bounds the LRU's protected stamp window
            # (2 x microbatch + 16 touches): the default's would cover
            # every slot of a tiny budget, and nothing could be evicted
            spill=dict(keys=6000, events_per_window=4096, budget=1024,
                       microbatch=64),
            sliding=dict(keys=500, events_per_slide=512, slides=14,
                         budget=1 << 14),
            session=dict(keys=3000, items=64, events_per_period=512,
                         periods=14, gap_ms=3000, budget=1 << 14))


# ---------------------------------------------------------------------
# user code of the jobs: source, sink, aggregates
# ---------------------------------------------------------------------

class _BatchElements:
    """SourceContext view that forwards a RecordBatch as a first-class
    stream ELEMENT (the DataStream pipeline's convention) where
    ColumnarSource collects it as one record's VALUE (the SQL tier's
    convention)."""

    def __init__(self, ctx):
        self._ctx = ctx

    def collect(self, batch):
        self._ctx.collect_batch(batch)

    def emit_watermark(self, watermark):
        self._ctx.emit_watermark(watermark)


class EventSource(ColumnarSource):
    """Config #2's synthetic source: array-born (key, value, ts) rows
    in BATCH_ROWS-row batches, a watermark after each."""

    def __init__(self, keys, values, ts):
        super().__init__({"f0": keys, "f1": values, "f2": ts},
                         rowtime="f2", chunk=BATCH_ROWS)

    def emit_step(self, ctx, max_records):
        return super().emit_step(_BatchElements(ctx), max_records)


class ArraySink(SinkFunction):
    """Keeps what arrives as column chunks: rows (tuples), batch
    elements, or RecordBatch-valued records from the SQL tier."""

    def __init__(self):
        self.rows = []
        self.chunks = []

    def invoke(self, value, context=None):
        if isinstance(value, RecordBatch):
            self.invoke_batch(value)
        else:
            self.rows.append(value)

    def invoke_batch(self, batch):
        self.chunks.append(tuple(batch.cols.values()))

    def columns(self):
        """One array per output field, arrival order."""
        chunks = list(self.chunks)
        if self.rows:
            chunks.append(tuple(np.asarray(c) for c in zip(*self.rows)))
        if not chunks:
            return ()
        return tuple(np.concatenate([c[i] for c in chunks])
                     for i in range(len(chunks[0])))


class FlushMarkingSource(EventSource):
    """Before each batch, notes how large the state table of the job's
    window operator has grown and what the flush counters read."""

    def __init__(self, keys, values, ts):
        super().__init__(keys, values, ts)
        self.ops = []
        self.marks = []

    def emit_step(self, ctx, max_records):
        for op in self.ops:
            if type(op) is WindowOperator and op.columnar_rows:
                self.marks.append((op.window_state.capacity,
                                   STATE_STATS.flush_batches,
                                   STATE_STATS.flush_row_form_batches))
        return super().emit_step(ctx, max_records)


class UserHll(HyperLogLogAggregate):
    """COUNT DISTINCT over field 1 (the user) of a (key, user) row."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


class ValueQuantiles(QuantileSketchAggregate):
    """p50 / p99 over field 1 (the value) of a (key, value) row."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


class FieldAvg(AvgAggregate):
    def extract_value(self, value):
        return value[1]


class FieldSum(SumAggregate):
    def __init__(self):
        super().__init__(np.float32)

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1].astype(np.float32)


def emit_row(key, window, vals):
    return [(key, window.start, float(vals[0]))]


def capture_operators(env):
    """Every operator instance the executor builds for this job, so a
    leg can name the engine that really ran."""
    made = []
    for node in env.get_stream_graph().nodes.values():
        def factory(inner=node.operator_factory):
            op = inner()
            made.append(op)
            return op
        node.operator_factory = factory
    return made


def one_of(ops, cls):
    """The one instance of `cls` that processed rows (the pre-flight
    linter dry-constructs operators too)."""
    found = [op for op in ops if type(op) is cls
             and (getattr(op, "engine", None) is not None
                  or op.columnar_rows or op.boxed_rows)]
    if len(found) != 1:
        raise AssertionError(
            f"expected one working {cls.__name__}, the job built "
            f"{[type(op).__name__ for op in ops]}")
    return found[0]


def run_window_job(name, arrays, agg, on_state_backend=False,
                   configuration=None):
    """source → keyBy(field 0) → 1 s tumbling window → aggregate →
    sink, through env.execute().  `on_state_backend` takes the scalar
    WindowOperator with its state in the `tpu` backend; otherwise the
    default aggregate() picks the operator.  `configuration` is the
    environment's.  Returns (operators, sink)."""
    env = StreamExecutionEnvironment(configuration)
    windowed = (env.add_source(EventSource(*arrays), name="events")
                .key_by(0)
                .window(TumblingEventTimeWindows.of(WINDOW_MS)))
    if on_state_backend:
        env.set_state_backend("tpu")
        windowed.disable_device_operator()
    sink = ArraySink()
    windowed.aggregate(agg, window_function=emit_row).add_sink(sink)
    ops = capture_operators(env)
    env.execute(name)
    return ops, sink


def by_key_window(keys, starts, values, n_keys):
    """(key, window) rows → (flat ids sorted, values in that order)."""
    flat = (np.asarray(starts, np.int64) // WINDOW_MS) * n_keys \
        + np.asarray(keys, np.int64)
    order = np.argsort(flat, kind="stable")
    return flat[order], np.asarray(values)[order]


# ---------------------------------------------------------------------
# data and the plain reference
# ---------------------------------------------------------------------

def make_events(seed, n_keys, events_per_window, windows):
    """Uniform int64 keys and users, time-sorted, exactly
    events_per_window events in each 1 s window."""
    rng = np.random.default_rng(seed)
    n = events_per_window * windows
    keys = rng.integers(0, n_keys, n, dtype=np.int64)
    users = rng.integers(0, 1 << 40, n, dtype=np.int64)
    ts = (np.arange(n, dtype=np.int64) * WINDOW_MS) // events_per_window
    return keys, users, ts


def exact_distinct(keys, users, ts):
    """{window start: (sorted keys, exact distinct users per key)} by
    np.unique — independent of every hash and sketch under test."""
    assert int(keys.max()) < (1 << 24) and int(users.max()) < (1 << 40)
    starts = ts - ts % WINDOW_MS
    ref = {}
    for w in np.unique(starts).tolist():
        m = starts == w
        pairs = np.unique((keys[m].astype(np.uint64) << np.uint64(40))
                          | users[m].astype(np.uint64))
        k, c = np.unique(pairs >> np.uint64(40), return_counts=True)
        ref[w] = (k.astype(np.int64), c)
    return ref


def check_hll(got_keys, got_starts, got_est, ref, precision):
    """Every (key, window) emitted exactly once, and the estimates
    within HyperLogLog's bounds of the exact counts.  Returns
    (problems, facts)."""
    m = 1 << precision
    sigma = 1.04 / np.sqrt(m)
    problems = []
    got_keys = np.asarray(got_keys, np.int64)
    got_starts = np.asarray(got_starts, np.int64)
    got_est = np.asarray(got_est, np.float64)
    extra = sorted(set(np.unique(got_starts).tolist()) - set(ref))
    if extra:
        problems.append(f"windows nobody asked for: {extra[:5]}")
    errs, exact = [], []
    for w, (rk, rc) in ref.items():
        sel = got_starts == w
        order = np.argsort(got_keys[sel], kind="stable")
        gk, ge = got_keys[sel][order], got_est[sel][order]
        if not np.array_equal(gk, rk):
            problems.append(
                f"window {w}: emitted {len(gk)} (key, window) rows "
                f"({len(np.unique(gk))} distinct keys), reference has "
                f"{len(rk)}")
            continue
        errs.append(ge - rc)
        exact.append(rc.astype(np.float64))
    if not errs:
        return problems or ["nothing to compare"], {}
    err, exact = np.concatenate(errs), np.concatenate(exact)
    if not np.isfinite(err).all():
        problems.append("non-finite estimates")
    # a hard bound no healthy sketch crosses: three lost registers, or
    # six standard errors
    worst = np.abs(err) - np.maximum(3.0, 6.0 * sigma * exact)
    if (worst > 0).any():
        i = int(np.argmax(worst))
        problems.append(f"estimate {exact[i] + err[i]:.3f} for an exact "
                        f"count of {exact[i]:.0f}")
    rms = float(np.sqrt(np.mean((err / exact) ** 2)))
    if rms > sigma:
        problems.append(f"rms relative error {rms:.5f} > 1.04/sqrt(m) "
                        f"= {sigma:.5f}")
    # an estimate misses by more than 0.5 only when two of the key's
    # values share a register; lost or misrouted updates show up as
    # more misses than the birthday bound explains
    counts, freq = np.unique(exact.astype(np.int64), return_counts=True)
    p_clean = np.array([np.prod(1.0 - np.arange(min(c, m)) / m)
                        for c in counts.tolist()])
    expected = float(((1.0 - p_clean) * freq).sum())
    off = int((np.abs(err) > 0.5).sum())
    if off > 2.0 * expected + 10:
        problems.append(f"{off} estimates off by more than 0.5, register "
                        f"collisions explain {expected:.1f}")
    return problems, {"key_windows": int(len(err)),
                      "rms_rel_err": round(rms, 6),
                      "max_abs_err": round(float(np.abs(err).max()), 3),
                      "off_by_half": off,
                      "collisions_expected": round(expected, 1)}


# ---------------------------------------------------------------------
# compile accounting: every jit, traced_jit or not
# ---------------------------------------------------------------------

class CompileMeter:
    def __init__(self):
        self.count = collections.Counter()
        self.secs = collections.defaultdict(float)
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.count.update([name]))
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, name, secs, **kw):
        self.count[name] += 1
        self.secs[name] += secs

    def report(self):
        c = "/jax/core/compile/backend_compile_duration"
        return {"backend_compiles": self.count[c],
                "backend_compile_s": round(self.secs[c], 2),
                "cache_hits": self.count["/jax/compilation_cache/cache_hits"],
                "cache_misses":
                    self.count["/jax/compilation_cache/cache_misses"]}


# ---------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------

def leg_device_gate(cfg):
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"[1] device: {info}; jax {jax.__version__}; native library "
          f"{nat.library_path()}; compile cache "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    if not nat.available():
        raise AssertionError(f"native runtime: {nat.load_error()}")
    if dev.platform != "tpu" and not cfg["preflight"]:
        raise AssertionError(
            f"no TPU: jax found {dev.platform} ({dev.device_kind}); "
            f"--cpu-preflight rehearses on it at a tiny size")
    return [], info


def boxed_problems(op, events):
    """Every event must have entered `op` as part of a batch."""
    if op.columnar_rows == events and not op.boxed_fallbacks:
        return []
    return [f"columnar_rows {op.columnar_rows} of {events} events, "
            f"{op.boxed_fallbacks} boxed fallbacks "
            f"({op.columnar_fallback_reason})"]


def fire_tail_problems(op, result_rows):
    """Every result row must have left its fire with no StreamRecord
    of its own (the window function here is a plain callable)."""
    if op.fire_rows_direct == result_rows and not op.fire_rows_via_records:
        return []
    return [f"fire_rows_direct {op.fire_rows_direct} of {result_rows} "
            f"result rows, {op.fire_rows_via_records} through a "
            f"StreamRecord"]


def timer_run_problems(op, windows, result_rows):
    """Every fired key's one timer must have left the store as part
    of its window's run: a sweep hands over whole runs, one per
    fired window (tumbling, lateness 0)."""
    if op.timers_swept == result_rows and op.timer_runs == windows:
        return []
    return [f"timers_swept {op.timers_swept} of {result_rows} result "
            f"rows in {op.timer_runs} runs, {windows} windows fired"]


#: how the tpu backend took a leg's integer columns: the values hashed
#: whole, the keys probed on a window's integer table
COLUMN_COUNTS = ("hash_column_rows", "hash_per_value_rows",
                 "bulk_probe_rows", "int_table_rows", "int_table_demotions")


def column_counts():
    return tuple(getattr(STATE_STATS, name) for name in COLUMN_COUNTS)


def column_problems(before, events):
    """Every event's user must have been hashed as part of its batch's
    column since `before` (`column_counts()` then), and every bulk probe
    of the slot index (an event's, a fired key's, a cleared key's)
    must have met an integer table; returns (problems, facts)."""
    facts = {name: now - then for name, now, then
             in zip(COLUMN_COUNTS, column_counts(), before)}
    problems = []
    if facts["hash_column_rows"] != events or facts["hash_per_value_rows"]:
        problems.append(
            f"hash_column_rows {facts['hash_column_rows']} of {events} "
            f"events, {facts['hash_per_value_rows']} hashed a value at a "
            f"time")
    if facts["int_table_rows"] != facts["bulk_probe_rows"] \
            or facts["int_table_demotions"]:
        problems.append(
            f"int_table_rows {facts['int_table_rows']} of "
            f"{facts['bulk_probe_rows']} bulk-probed rows, "
            f"{facts['int_table_demotions']} tables became dicts")
    return problems, facts


def leg_state_backend(cfg, events, ref):
    keys = events[0]
    counted = column_counts()
    ops, sink = run_window_job("chip-smoke-state-backend", events,
                               UserHll(cfg["precision"]),
                               on_state_backend=True)
    wop = one_of(ops, WindowOperator)
    state = wop.window_state
    cols = sink.columns()
    problems, facts = check_hll(*cols, ref, cfg["precision"])
    problems += boxed_problems(wop, len(keys))
    problems += fire_tail_problems(wop, len(cols[0]))
    problems += timer_run_problems(wop, len(ref), len(cols[0]))
    column_faults, column_facts = column_problems(counted, len(keys))
    problems += column_faults
    regs = state.device_state["regs"]
    return problems, {
        "route": "WindowOperator.process_batch -> "
                 f"{type(wop.keyed_backend).__name__}.add_batch -> "
                 f"{type(state).__name__}",
        "events": len(keys), "columnar_rows": wop.columnar_rows,
        "boxed_fallbacks": wop.boxed_fallbacks,
        "fire_rows_direct": wop.fire_rows_direct,
        "fire_rows_via_records": wop.fire_rows_via_records,
        "timers_swept": wop.timers_swept, "timer_runs": wop.timer_runs,
        "slots": state.capacity,
        "register_bytes": int(regs.size) * regs.dtype.itemsize,
        "evictions": state.evictions, **column_facts, **facts}


def leg_state_spill(cfg, seed):
    """Leg 2's route under a device-slot budget the window's live keys
    outgrow.  The budget is set where docs/state.md tells a user to
    set it: in the environment's Configuration."""
    spill = cfg["spill"]
    events = make_events(seed + 1, spill["keys"],
                         spill["events_per_window"], cfg["windows"])
    ref = exact_distinct(*events)
    conf = Configuration().set("state.backend.tpu.max-device-slots",
                               spill["budget"])
    if spill["microbatch"] is not None:
        conf.set("state.backend.tpu.microbatch-size", spill["microbatch"])
    counted = column_counts()
    ops, sink = run_window_job("chip-smoke-state-spill", events,
                               UserHll(cfg["precision"]),
                               on_state_backend=True, configuration=conf)
    wop = one_of(ops, WindowOperator)
    state = wop.window_state
    cols = sink.columns()
    problems, facts = check_hll(*cols, ref, cfg["precision"])
    problems += boxed_problems(wop, len(events[0]))
    problems += fire_tail_problems(wop, len(cols[0]))
    column_faults, column_facts = column_problems(counted, len(events[0]))
    problems += column_faults
    if state.max_device_slots != spill["budget"]:
        problems.append(f"the backend's budget is {state.max_device_slots}, "
                        f"the Configuration says {spill['budget']}")
    if state.capacity > spill["budget"] or state.budget_overruns:
        problems.append(f"capacity {state.capacity} of a budget of "
                        f"{spill['budget']}, {state.budget_overruns} "
                        f"overruns")
    if not (state.evictions and state.promotions):
        problems.append(f"{state.evictions} evictions, "
                        f"{state.promotions} promotions: the tier did "
                        f"nothing")
    regs = state.device_state["regs"]
    return problems, {
        "keys": spill["keys"], "events": len(events[0]),
        "budget": spill["budget"], "slots": state.capacity,
        "register_bytes": int(regs.size) * regs.dtype.itemsize,
        "evictions": state.evictions, "promotions": state.promotions,
        "budget_overruns": state.budget_overruns,
        "live_keys_per_window": [len(k) for k, _ in ref.values()],
        **column_facts, **facts}


#: config #3's windows; the slide is the source period too
SLIDING_SIZE_MS, SLIDING_SLIDE_MS = 10_000, 1_000


def emit_quantiles(key, window, vals):
    p50, p99 = vals[0]
    return [(key, window.end - SLIDING_SLIDE_MS, float(p50), float(p99))]


def load_reference(name):
    """``tests/<name>.py``, a plain reference from the file beside
    this one."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tests", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def zipf_column(rng, n, space):
    """Zipf 0.99 over `space` ids, rank -> id by a permutation."""
    weights = np.arange(1, space + 1, dtype=np.float64) ** -0.99
    cumulative = np.cumsum(weights)
    ranks = np.searchsorted(cumulative, rng.random(n) * cumulative[-1],
                            side="right")
    return rng.permutation(space)[np.minimum(ranks, space - 1)] \
        .astype(np.int64)


def leg_state_sliding(cfg, seed):
    """Leg 2's route under a sliding assigner and a value-carrying
    aggregate: every event lands in ten (key, window) sketches, a
    window fires every slide, and the budget holds all ten windows, so
    the spill tier must stay idle."""
    sl = cfg["sliding"]
    rng = np.random.default_rng(seed + 2)
    n = sl["events_per_slide"] * sl["slides"]
    keys = zipf_column(rng, n, sl["keys"])
    values = np.exp(rng.normal(3.0, 1.0, n))
    ts = (np.arange(n, dtype=np.int64) * SLIDING_SLIDE_MS) \
        // sl["events_per_slide"]
    env = StreamExecutionEnvironment(Configuration().set(
        "state.backend.tpu.max-device-slots", sl["budget"]))
    env.set_state_backend("tpu")
    source = FlushMarkingSource(keys, values, ts)
    windowed = (env.add_source(source, name="events")
                .key_by(0)
                .window(SlidingEventTimeWindows.of(SLIDING_SIZE_MS,
                                                   SLIDING_SLIDE_MS)))
    windowed.disable_device_operator()
    sink = ArraySink()
    agg = ValueQuantiles()
    windowed.aggregate(agg, window_function=emit_quantiles).add_sink(sink)
    source.ops = ops = capture_operators(env)
    env.execute("chip-smoke-state-sliding")
    wop = one_of(ops, WindowOperator)
    state = wop.window_state
    got_keys, got_starts, p50, p99 = sink.columns()
    order = np.argsort(got_starts, kind="stable")
    cuts = np.flatnonzero(np.diff(got_starts[order])) + 1
    results = {int(got_starts[part[0]]): (got_keys[part], got_starts[part],
                                          p50[part], p99[part])
               for part in np.split(order, cuts)}
    per = sl["events_per_slide"]
    emitted = [(p, None, lambda p=p: (keys[p * per:(p + 1) * per],
                                      values[p * per:(p + 1) * per]))
               for p in range(sl["slides"])]
    verdict = load_reference("quantile_sliding_reference").check(
        {"slide_ms": SLIDING_SLIDE_MS, "window_size_ms": SLIDING_SIZE_MS,
         "quantiles": list(agg.quantiles), "relative_accuracy": 0.01},
        emitted, results)
    problems = list(verdict["problems"])
    if verdict["failed"]:
        problems.append(f"{verdict['failed']} of {verdict['attempted']} "
                        f"quantiles failed")
    problems += boxed_problems(wop, n)
    problems += fire_tail_problems(wop, len(got_keys))
    if state.evictions or state.budget_overruns \
            or state.capacity > sl["budget"]:
        problems.append(f"{state.evictions} evictions, "
                        f"{state.budget_overruns} overruns, capacity "
                        f"{state.capacity} of a budget of {sl['budget']}: "
                        f"ten windows were to fit the device")
    panes = SLIDING_SIZE_MS // SLIDING_SLIDE_MS
    if wop.window_rows != panes * n:
        problems.append(f"{wop.window_rows} state rows for {n} events, "
                        f"{panes} an event expected")
    # the flushes since the table reached the size it ended at (a flush
    # is two batches' rows in one window: small against that table,
    # not against the 4,096 slots it started from) took the tile
    # kernel, every one; off the TPU there is no such kernel to take
    grown = [m for m in source.marks if m[0] == state.capacity][:1]
    flushes, in_place = (
        (STATE_STATS.flush_batches - grown[0][1],
         STATE_STATS.flush_row_form_batches - grown[0][2])
        if grown else (0, 0))
    if not cfg["preflight"] and not 0 < flushes == in_place:
        problems.append(
            f"of {flushes} flushes of {2 * BATCH_ROWS} rows against the "
            f"full-grown table of {state.capacity} slots {in_place} ran "
            f"in place: state.update rewrites the whole table around "
            f"the others (ops/sketches.py quantile_update_form)")
    hist = state.device_state["hist"]
    return problems, {
        "keys": sl["keys"], "events": n, "budget": sl["budget"],
        "slots": state.capacity,
        "table_bytes": int(hist.size) * hist.dtype.itemsize,
        "batches_marked": len(source.marks),
        "flushes_at_full_size": flushes, "flushes_in_place": in_place,
        "rows_per_event": wop.window_rows / n,
        "windows_touched": wop.windows_touched,
        "evictions": state.evictions,
        "budget_overruns": state.budget_overruns,
        "result_rows": len(got_keys), "windows_fired": len(results),
        "timers_swept": wop.timers_swept, "timer_runs": wop.timer_runs,
        **verdict["facts"]}


SESSION_PARTITIONS = 4
SESSION_WATCH = 8


class ItemCounts(CountMinSketchAggregate):
    """A count of one per event over field 1 (the item)."""

    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


def emit_session(key, window, vals):
    return [(key, (window.end - 1) // WINDOW_MS * WINDOW_MS, window.start,
             window.end, *vals[0].tolist())]


def session_stream(cfg, seed):
    """Legs 2d and 2e's events in a 4-partition columnar log, period by
    period, round-robin; returns (log, keys, items, timestamps, the
    tracked items)."""
    se = cfg["session"]
    rng = np.random.default_rng(seed + 3)
    per, periods, parts = se["events_per_period"], se["periods"], \
        SESSION_PARTITIONS
    n = per * periods
    keys = zipf_column(rng, n, se["keys"])
    items = zipf_column(rng, n, se["items"])
    counts = np.bincount(items, minlength=se["items"])
    watch = tuple(int(i) for i in np.lexsort(
        (np.arange(len(counts)), -counts))[:SESSION_WATCH])
    ts = (np.arange(n, dtype=np.int64) // per) * WINDOW_MS \
        + 1 + ((np.arange(n, dtype=np.int64) % per)
               * (WINDOW_MS - 1)) // per
    log = ColumnarPartitionedLog(parts)
    for p in range(periods):
        for part in range(parts):
            rows = slice(p * per + part, (p + 1) * per, parts)
            log.append_columns(part, {"f0": keys[rows], "f1": items[rows]},
                               ts[rows])
    return log, keys, items, ts, watch


def session_job(cfg, log, source, watch):
    """Legs 2d and 2e's job over `source`: (env, sink)."""
    se = cfg["session"]
    env = StreamExecutionEnvironment(Configuration().set(
        "state.backend.tpu.max-device-slots", se["budget"]))
    env.set_state_backend("tpu")
    windowed = (env.add_source(source, name="log")
                .key_by(0)
                .window(EventTimeSessionWindows.with_gap(se["gap_ms"])))
    windowed.disable_device_operator()
    sink = ArraySink()
    windowed.aggregate(
        ItemCounts(unit_weights=True, queries=watch),
        window_function=emit_session).add_sink(sink)
    return env, sink


class CheckpointedConsumer(ReplayableLogSource):
    """Leg 2e's consumer: once it has read `wait_at` rows of every
    partition it reads on only when a checkpoint that was triggered
    after it stopped is durable (the job checkpoints on its interval;
    nothing is asked for).  Class attributes: the executor copies a
    function per attempt."""

    client = None
    wait_at = 0
    waiting = None  # the books when it stopped, and the newest checkpoint
    durable = None  # the books once one triggered since is durable, its id

    @staticmethod
    def books(**more):
        return {"sync_ms": tracing.get_tracer().stats().get(
                    "checkpoint.sync", {}).get("total_ms", 0.0),
                "rows": STATE_STATS.snapshot_columns,
                "bytes_device": STATE_STATS.snapshot_bytes_device, **more}

    def emit_step(self, ctx, max_records):
        cls = type(self)
        if cls.durable is None \
                and min(self.offsets.values()) >= cls.wait_at:
            coordinator = cls.client.executor_state["coordinator"]
            if cls.waiting is None:
                cls.waiting = cls.books(
                    newest=max(coordinator.stats, default=0))
            done = [cid for cid, st in coordinator.stats.items()
                    if cid > cls.waiting["newest"]
                    and st.status == "completed"]
            if not done:
                return True
            cls.durable = cls.books(checkpoint=done[0])
        return super().emit_step(
            ctx, self.batch_per_partition * self.log.num_partitions)


class TimedConsumer(ReplayableLogSource):
    """Leg 2e's second consumer: notes when it is first asked for
    rows (the restore is over then)."""

    first_step = None

    def emit_step(self, ctx, max_records):
        if type(self).first_step is None:
            type(self).first_step = time.perf_counter()
        return super().emit_step(
            ctx, self.batch_per_partition * self.log.num_partitions)


def leg_state_sessions_checkpoint(cfg, seed):
    """Leg 2d's job with checkpoints every `CHECKPOINT_INTERVAL_MS`
    to a filesystem directory, asynchronously; the consumer waits for
    one taken with the open sessions at their working number (two
    periods before the end of the log) and its numbers are printed; a
    second job over a plain consumer of the same log starts from what
    the directory retains through `set_savepoint_restore`, and must
    emit the first job's rows for every session that fired after that
    checkpoint, integer for integer."""
    import shutil
    import tempfile
    from flink_tpu.runtime.checkpoints import load_retained_checkpoint
    se = cfg["session"]
    log, keys, items, ts, watch = session_stream(cfg, seed)
    per, periods, parts = se["events_per_period"], se["periods"], \
        SESSION_PARTITIONS
    directory = tempfile.mkdtemp(prefix="chip-smoke-chk-")
    problems = []
    try:
        CheckpointedConsumer.client = None
        CheckpointedConsumer.waiting = CheckpointedConsumer.durable = None
        CheckpointedConsumer.wait_at = (periods - 2) * per // parts
        source = CheckpointedConsumer(log, bounded=True,
                                      watermark_lag_ms=WINDOW_MS,
                                      batch_per_partition=per // parts)
        env, sink = session_job(cfg, log, source, watch)
        env.enable_checkpointing(CHECKPOINT_INTERVAL_MS, async_persist=True)
        env.set_checkpoint_storage("filesystem", directory, retain=1)
        env.register_job_listener(
            lambda client: setattr(CheckpointedConsumer, "client", client))
        ops = capture_operators(env)
        env.execute("chip-smoke-sessions-checkpointed")
        first = np.stack([np.asarray(c, np.int64)
                          for c in sink.columns()], axis=1)
        if CheckpointedConsumer.durable is None:
            return ["no checkpoint became durable while the consumer "
                    "waited"], {}
        then, now = CheckpointedConsumer.waiting, CheckpointedConsumer.durable
        cid = now["checkpoint"]
        sync_ms = now["sync_ms"] - then["sync_ms"]
        stats = CheckpointedConsumer.client.executor_state[
            "coordinator"].stats[cid]
        wop = one_of(ops, WindowOperator)
        live = now["rows"] - then["rows"]
        captured = now["bytes_device"] - then["bytes_device"]
        # the first job's table goes before the second's comes
        for arr in wop.window_state.device_state.values():
            arr.delete()
        wop.window_state.device_state = {}
        del ops, wop, env, sink, source
        gc.collect()
        point = load_retained_checkpoint(directory)
        watermark = None
        for task in point["tasks"].values():
            for snap in task["operators"].values():
                timers = snap.get("timers")
                if timers and timers["event"]:
                    watermark = timers["watermark"]
        TimedConsumer.first_step = None
        env2, sink2 = session_job(cfg, log, TimedConsumer(
            log, bounded=True, watermark_lag_ms=WINDOW_MS,
            batch_per_partition=per // parts), watch)
        env2.set_savepoint_restore(directory)
        ops2 = capture_operators(env2)
        t0 = time.perf_counter()
        env2.execute("chip-smoke-sessions-restored")
        t1 = time.perf_counter()
        wop2 = one_of(ops2, WindowOperator)
        again = np.stack([np.asarray(c, np.int64)
                          for c in sink2.columns()], axis=1)
        # columns: key, period, session start, session end, total, ...
        expected = first[first[:, 3] - 1 > watermark]

        def ordered(rows):
            return rows[np.lexsort(rows.T[::-1])]
        if again.shape != expected.shape or not np.array_equal(
                ordered(again), ordered(expected)):
            problems.append(
                f"the restored job emitted {len(again)} rows, the first "
                f"{len(expected)} for the sessions that fired after "
                f"checkpoint {point['checkpoint_id']}, and they differ")
        if point["checkpoint_id"] < cid:
            problems.append(f"the directory retains checkpoint "
                            f"{point['checkpoint_id']}, older than {cid}")
        if wop2.num_late_records_dropped:
            problems.append(f"{wop2.num_late_records_dropped} rows "
                            f"dropped as late after the restore")
        if wop2.window_state.evictions:
            problems.append(f"{wop2.window_state.evictions} evictions "
                            f"after the restore")
        first_step = TimedConsumer.first_step or t1
        return problems, {
            "checkpoint": cid, "restored_from": point["checkpoint_id"],
            "rows_in_checkpoint": live,
            "sync_ms": round(sync_ms, 1),
            "capture_bytes_device": captured,
            "written_bytes": stats.state_bytes,
            "trigger_to_durable_ms": round(stats.duration_ms, 1),
            "restore_s": round(first_step - t0, 2),
            "catch_up_s": round(t1 - first_step, 2),
            "rows_after_checkpoint": len(expected),
            "rows_restored_job": len(again),
            "open_across_checkpoint":
                int((expected[:, 2] <= watermark).sum())}
    finally:
        shutil.rmtree(directory, ignore_errors=True)


def leg_state_sessions(cfg, seed):
    """Leg 2's route under the first merging assigner, fed by the
    program's own log connector: a producer fills a 4-partition
    columnar log period by period, round-robin, the bounded consumer
    reads one chunk a partition a step and emits one watermark lagging
    by a period; every open session is a Count-Min sketch in a slot of
    its own, and the budget holds them all."""
    se = cfg["session"]
    log, keys, items, ts, watch = session_stream(cfg, seed)
    per, periods, parts = se["events_per_period"], se["periods"], \
        SESSION_PARTITIONS
    n = per * periods
    source = ReplayableLogSource(log, bounded=True,
                                 watermark_lag_ms=WINDOW_MS,
                                 batch_per_partition=per // parts)
    env, sink = session_job(cfg, log, source, watch)
    ops = capture_operators(env)
    probes = STATE_STATS.per_key_probe_rows
    env.execute("chip-smoke-state-sessions")
    wop = one_of(ops, WindowOperator)
    state = wop.window_state
    cols = sink.columns()
    # the consumer read partition by partition, a chunk each a period
    arrival = np.concatenate([
        np.arange(p * per + part, (p + 1) * per, parts)
        for p in range(periods) for part in range(parts)])
    verdict = load_reference("session_countmin_reference").check(
        {"gap_ms": se["gap_ms"], "window_ms": WINDOW_MS, "depth": 4,
         "width": 2048},
        [(0, None, lambda: (keys[arrival], items[arrival], ts[arrival],
                            watch))],
        {0: cols} if cols else {})
    problems = list(verdict["problems"])
    if verdict["failed"]:
        problems.append(f"{verdict['failed']} of {verdict['attempted']} "
                        f"session facts failed")
    problems += boxed_problems(wop, n)
    problems += fire_tail_problems(wop, len(cols[0]) if cols else 0)
    if state.evictions or state.budget_overruns \
            or state.capacity > se["budget"]:
        problems.append(f"{state.evictions} evictions, "
                        f"{state.budget_overruns} overruns, capacity "
                        f"{state.capacity} of a budget of {se['budget']}: "
                        f"the open sessions were to fit the device")
    if wop.num_late_records_dropped:
        problems.append(f"{wop.num_late_records_dropped} rows dropped as "
                        f"late under a watermark that lags by a period")
    # a merge of two state windows finds its target's slot through
    # the per-key door, once; nothing else of this job may
    per_key = STATE_STATS.per_key_probe_rows - probes
    if per_key > STATE_STATS.merged_rows:
        problems.append(f"{per_key} rows resolved by the per-key door of "
                        f"the slot index against "
                        f"{STATE_STATS.merged_rows} state windows merged: "
                        f"the batched session path probes in bulk")
    if log.committed_offsets != {part: n // parts for part in range(parts)}:
        problems.append(f"offsets committed at the end of the stream: "
                        f"{log.committed_offsets}")
    table = state.device_state["table"]
    return problems, {
        "keys": se["keys"], "events": n, "budget": se["budget"],
        "slots": state.capacity,
        "table_bytes": int(table.size) * table.dtype.itemsize,
        "sessions_opened": wop.sessions_opened,
        "sessions_extended": wop.sessions_extended,
        "session_windows_merged": wop.session_windows_merged,
        "state_windows_merged": STATE_STATS.merged_rows,
        "per_key_probe_rows": per_key,
        "evictions": state.evictions,
        "budget_overruns": state.budget_overruns,
        "result_rows": len(cols[0]) if cols else 0,
        "timers_swept": wop.timers_swept, "timer_runs": wop.timer_runs,
        **verdict["facts"]}


def leg_sql(cfg, events, ref, mesh=None):
    keys, users, ts = events
    env = StreamExecutionEnvironment()
    if mesh is not None:
        env.set_mesh(mesh)
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table("ev", t_env.from_columns(
        {"k": keys, "u": users, "ts": ts}, rowtime="ts"))
    out = t_env.sql_query(
        "SELECT k, TUMBLE_START(ts) AS ws, APPROX_COUNT_DISTINCT(u) AS d "
        "FROM ev GROUP BY TUMBLE(ts, INTERVAL '1' SECOND), k")
    sink = ArraySink()
    out.to_append_stream(batched=True).add_sink(sink)
    ops = capture_operators(env)
    env.execute("chip-smoke-sql")
    cop = one_of(ops, ColumnarWindowOperator)
    cols = sink.columns()
    problems, facts = check_hll(*cols, ref, cfg["precision"])
    facts = {"route": "SQL TUMBLE + APPROX_COUNT_DISTINCT -> "
                      "ColumnarWindowOperator",
             "events": len(keys), **engine_facts(cop.engine), **facts}
    # engine and columns go on to the mesh leg
    return problems, facts, (cop.engine, cols)


def leg_datastream_default(cfg, events, ref):
    """The default aggregate(): DeviceWindowOperator, every batch by
    its batch door."""
    keys = events[0]
    ops, sink = run_window_job("chip-smoke-datastream", events,
                               UserHll(cfg["precision"]))
    dop = one_of(ops, DeviceWindowOperator)
    cols = sink.columns()
    problems, facts = check_hll(*cols, ref, cfg["precision"])
    problems += boxed_problems(dop, len(keys))
    problems += fire_tail_problems(dop, len(cols[0]))
    return problems, {"route": "aggregate() -> "
                               "DeviceWindowOperator.process_batch",
                      "events": len(keys),
                      "columnar_rows": dop.columnar_rows,
                      "boxed_fallbacks": dop.boxed_fallbacks,
                      "fire_rows_direct": dop.fire_rows_direct,
                      "fire_rows_via_records": dop.fire_rows_via_records,
                      **engine_facts(dop.engine), **facts}


def engine_facts(engine):
    mode = getattr(engine, "mode", None)
    if mode is None and getattr(engine, "shards", None):
        mode = getattr(engine.shards[0], "mode", None)
    h2d = link_probe.measure()["h2d_gbps"]
    return {"engine": type(engine).__name__,
            "finish_tier": getattr(mode, "finish_tier", None),
            "h2d_gbps": round(h2d, 3) if np.isfinite(h2d) else str(h2d)}


def leg_entry_step(cfg):
    """The jitted step of __graft_entry__.entry() against numpy."""
    import __graft_entry__ as graft
    step, args = graft.entry()
    out = jax.jit(step)(*args)
    regs = np.asarray(out["regs"])
    state, slots, _values, vh_hi, vh_lo, _mask = args
    agg = HyperLogLogAggregate(precision=12)
    rank, reg = agg.compress_value_hash(np.asarray(vh_hi), np.asarray(vh_lo))
    want = np.zeros(state["regs"].shape, np.uint8)
    np.maximum.at(want, (np.asarray(slots), reg.astype(np.int64)), rank)
    problems = []
    if regs.shape != want.shape or not np.array_equal(regs, want):
        problems.append("entry() step differs from the numpy scatter-max")
    return problems, {"route": "jit(__graft_entry__.entry step)",
                      "registers_set": int((regs > 0).sum())}


def leg_device_finish(cfg, events):
    """One window fired by the log tier with the finish on the device
    equals the same window with the finish on the host."""
    from flink_tpu.streaming.log_windows import LogStructuredTumblingWindows
    from flink_tpu.streaming.vectorized import hash_keys_np
    n = cfg["events_per_window"]
    keys, users, ts = (a[:n] for a in events)
    vh = hash_keys_np(users)
    fired = {}
    for tier in ("host", "device"):
        eng = LogStructuredTumblingWindows(
            HyperLogLogAggregate(cfg["precision"]), WINDOW_MS,
            finish_tier=tier)
        eng.emit_arrays = True
        eng.process_batch(keys, ts, None, value_hashes=vh)
        eng.advance_watermark(WINDOW_MS - 1)
        k, r, _s, _e = eng.fired[0]
        order = np.argsort(k, kind="stable")
        fired[tier] = (np.asarray(k)[order], np.asarray(r)[order])
    problems = []
    (hk, hr), (dk, dr) = fired["host"], fired["device"]
    if not np.array_equal(hk, dk):
        problems.append("device finish fired other keys than the host")
    elif not np.allclose(dr, hr, rtol=1e-3, atol=1e-3):
        problems.append(f"device finish differs from host finish by up "
                        f"to {float(np.abs(dr - hr).max()):.4f}")
    return problems, {
        "route": "LogStructuredTumblingWindows(finish_tier='device') "
                 "vs 'host'",
        "events": n, "keys_fired": int(len(hk))}


def leg_avg_job(cfg, seed):
    """An aggregate with no cell decomposition rides the scatter tier
    (VectorizedTumblingWindows): its contiguous fire and clear
    kernels and the full-arena fire."""
    n_w = cfg["side_events"]
    rng = np.random.default_rng(seed + 1)
    keys = rng.integers(0, cfg["keys"], 2 * n_w, dtype=np.int64)
    vals = rng.integers(0, 1000, 2 * n_w, dtype=np.int64)
    ts = (np.arange(2 * n_w, dtype=np.int64) * WINDOW_MS) // n_w
    before = dispatches()
    ops, sink = run_window_job("chip-smoke-avg", (keys, vals, ts),
                               FieldAvg())
    dop = one_of(ops, DeviceWindowOperator)
    uniq, inv = np.unique((ts // WINDOW_MS) * cfg["keys"] + keys,
                          return_inverse=True)
    want = np.bincount(inv, vals.astype(np.float64)) / np.bincount(inv)
    got_flat, got = by_key_window(*sink.columns(), cfg["keys"])
    problems = []
    if not np.array_equal(got_flat, uniq):
        problems.append(f"emitted {len(got_flat)} (key, window) rows, "
                        f"reference has {len(uniq)}")
    elif not np.allclose(got, want, rtol=1e-5):
        problems.append("averages differ from numpy")
    ran = {name: n - before.get(name, 0)
           for name, n in dispatches().items()
           if name.startswith("window.") and n > before.get(name, 0)}
    if type(dop.engine).__name__ != "VectorizedTumblingWindows":
        problems.append(f"engine {type(dop.engine).__name__}")
    if not cfg["preflight"]:
        # at a tiny size no window owns a whole fire tile
        for kernel in ("window.result_contig", "window.clear_contig",
                       "window.result_all"):
            if kernel not in ran:
                problems.append(f"{kernel} never dispatched")
    return problems, {"route": "aggregate(AvgAggregate) -> "
                               "DeviceWindowOperator",
                      "engine": type(dop.engine).__name__,
                      "events": 2 * n_w, "key_windows": int(len(uniq)),
                      "kernels": ran}


def dispatches():
    return {name: s["recompiles"] + s["cache_hits"]
            for name, s in tracing.jit_stats().items()}


def leg_fused_chain(cfg, seed, float64):
    """source → map → filter → keyBy → window on the tpu backend.  At
    parallelism 2 the keyBy exchange folds into the fused program:
    its splitmix64 and its value-sort partition run on the device."""
    n, n_keys = cfg["fused_events"], cfg["fused_keys"]
    rng = np.random.default_rng(seed + 2)
    keys = rng.integers(0, n_keys, n, dtype=np.int64)
    vals = rng.integers(0, 100, n, dtype=np.int64)
    if float64:
        vals = vals.astype(np.float64)
    ts = (np.arange(n, dtype=np.int64) * 2 * WINDOW_MS) // n
    stats = chain_fusion.FUSION_STATS
    stats.reset()
    env = StreamExecutionEnvironment()
    env.set_state_backend("tpu").set_parallelism(2)
    sink = ArraySink()
    (env.add_source(EventSource(keys, vals, ts), name="events")
        .map(lambda t: (t[0], t[1] * 3 + 1))
        .filter(lambda t: t[1] % 5 != 0)
        .key_by(0)
        .window(TumblingEventTimeWindows.of(WINDOW_MS))
        .disable_device_operator()
        .aggregate(FieldSum(), window_function=emit_row)
        .add_sink(sink))
    ops = capture_operators(env)
    env.execute("chip-smoke-fused")
    programs = [op._fused_chain for op in ops
                if op.__dict__.get("_fused_chain") is not None]
    modes = sorted({mode for p in programs for mode, _, _ in p._fns})
    meshed = any(use_mesh for p in programs for _, _, use_mesh in p._fns)
    mapped = vals * 3 + 1
    keep = mapped % 5 != 0
    flat = (ts[keep] // WINDOW_MS) * n_keys + keys[keep]
    uniq, inv = np.unique(flat, return_inverse=True)
    want = np.bincount(inv, mapped[keep].astype(np.float64))
    got_flat, got = by_key_window(*sink.columns(), n_keys)
    problems = []
    if not np.array_equal(got_flat, uniq) or not np.array_equal(got, want):
        problems.append("window sums differ from numpy")
    facts = {"route": "source -> map -> filter -> keyBy(fused) -> "
                      "WindowOperator on the tpu backend",
             "events": n, "programs": stats.programs, "modes": modes,
             "sharded_over_devices": meshed,
             "fused_batches": stats.fused_batches,
             "demotions": stats.demotions,
             "last_demotion": stats.last_demotion}
    if not float64 and (stats.fused_batches == 0 or stats.demotions
                        or "route" not in modes):
        problems.append(f"int64 chain: {stats.fused_batches} fused "
                        f"batches in modes {modes}, {stats.demotions} "
                        f"demotions ({stats.last_demotion})")
    return problems, facts


def leg_mesh(cfg, events, ref, one_chip_cols):
    """Leg 3a over four devices: the mesh log tier."""
    from jax.sharding import Mesh
    telemetry = get_telemetry()
    telemetry.enable()
    try:
        mesh = Mesh(np.array(jax.devices()[:4]), ("kg",))
        problems, facts, (engine, cols) = leg_sql(cfg, events, ref,
                                                  mesh=mesh)
        rounds = sum(p["rounds"] for p in
                     telemetry.payload()["exchange_phases"].values())
    finally:
        telemetry.disable()

    def by_key(c):
        k, ws, d = (np.asarray(a) for a in c)
        order = np.lexsort((k, ws))
        return k[order], ws[order], d[order]
    a, b = by_key(cols), by_key(one_chip_cols)
    if not (np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
            and np.allclose(a[2], b[2], rtol=1e-3, atol=1e-3)):
        problems.append("mesh results differ from the one-chip run")
    if rounds == 0:
        problems.append("no exchange round ran")
    # where the exchange leaves its output: one shard per device
    S, m = engine.n_shards, engine.step_batch // engine.n_shards
    recv, _counts = engine._packed_exchange(
        np.zeros((S, m, engine.n_lanes), np.uint32),
        np.full((S, m), S, np.int32))
    devices = {s.device for s in recv.addressable_shards}
    if len(devices) != 4:
        problems.append(f"exchange output on {len(devices)} devices")
    facts.update(route=facts["route"] + " over a 4-device mesh",
                 exchange_rounds=rounds,
                 exchange_devices=sorted(str(d) for d in devices))
    return problems, facts


# ---------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--events-per-window", type=int, default=None,
                    help="cut the events per window (never keys or "
                         "precision) when wall time forces one")
    ap.add_argument("--cpu-preflight", action="store_true",
                    help="rehearse every leg at a tiny size on any device")
    args = ap.parse_args(argv)
    cfg = dict(TINY if args.cpu_preflight else FULL,
               preflight=args.cpu_preflight)
    if args.cpu_preflight:
        print("chip_smoke: --cpu-preflight — a rehearsal at a tiny size, "
              "NOT a chip run", flush=True)
    if args.events_per_window is not None:
        cfg["events_per_window"] = args.events_per_window
    cut = ("none" if cfg["events_per_window"] == FULL["events_per_window"]
           else f"events per window {cfg['events_per_window']} instead "
                f"of {FULL['events_per_window']}")
    print(f"chip_smoke: seed {args.seed}, {cfg['keys']} keys, HLL "
          f"precision {cfg['precision']}, {cfg['windows']} windows of "
          f"{cfg['events_per_window']} events; cut: {cut}", flush=True)

    meter = CompileMeter()
    t_start = time.perf_counter()
    report = {}
    failed = []

    def run(name, fn, *a, required=True):
        """One leg: a failure is recorded, printed with its traceback,
        and the remaining legs still run — one report covers them.
        Returns what the leg hands on to a later leg, if anything."""
        t0 = time.perf_counter()
        extra = None
        try:
            problems, facts, *rest = fn(*a)
            extra = rest[0] if rest else None
        except Exception as e:  # noqa: BLE001 — leg boundary
            traceback.print_exc()
            problems, facts = [f"{type(e).__name__}: {e}"], {}
        facts["wall_s"] = round(time.perf_counter() - t0, 2)
        facts["passed"] = not problems
        # a leg's job is a web of reference cycles: without this its
        # device state (4 GiB after leg 2) is still there in the next
        gc.collect()
        if problems:
            facts["problems"] = problems
            if required:
                failed.append(name)
        report[name] = facts
        print(f"[{name}] {'ok' if not problems else 'FAILED'} "
              f"{json.dumps(facts, default=str)}", flush=True)
        return extra

    run("1 device gate", leg_device_gate, cfg)
    if failed:
        return 1
    device = {k: report["1 device gate"][k]
              for k in ("platform", "kind", "count")}

    events = make_events(args.seed, cfg["keys"], cfg["events_per_window"],
                         cfg["windows"])
    ref = exact_distinct(*events)
    run("2 state backend", leg_state_backend, cfg, events, ref)
    run("2b spill tier", leg_state_spill, cfg, args.seed)
    run("2c sliding quantiles", leg_state_sliding, cfg, args.seed)
    run("2d session count-min", leg_state_sessions, cfg, args.seed)
    run("2e session checkpoint", leg_state_sessions_checkpoint, cfg,
        args.seed)
    sql = run("3a sql", leg_sql, cfg, events, ref)
    run("3b datastream", leg_datastream_default, cfg, events, ref)
    run("4a entry step", leg_entry_step, cfg)
    run("4b device finish", leg_device_finish, cfg, events)
    run("4c avg job", leg_avg_job, cfg, args.seed)
    run("5 fused chain int64", leg_fused_chain, cfg, args.seed, False)
    run("5 fused chain float64 (not required)", leg_fused_chain, cfg,
        args.seed, True, required=False)
    if device["count"] >= 4 and sql is not None:
        run("6 mesh", leg_mesh, cfg, events, ref, sql[1])
    else:
        print(f"[6 mesh] not run: {device['count']} device(s) visible",
              flush=True)

    jit = tracing.jit_stats()
    hbm = get_telemetry().hbm_snapshot()
    summary = {
        "device": device, "cut": cut, "preflight": cfg["preflight"],
        "wall_s": round(time.perf_counter() - t_start, 1),
        "traced_jit": {
            "compiles": sum(s["recompiles"] for s in jit.values()),
            "compile_s": round(sum(s["compile_time_ms"]
                                   for s in jit.values()) / 1e3, 2)},
        "all_jits": meter.report(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "native_library": nat.library_path(),
        "hbm": hbm,
    }
    if hbm["source"] != "memory_stats" and not cfg["preflight"]:
        failed.append("hbm_snapshot fell back to framework accounting")
    print("chip_smoke report: " + json.dumps(summary, default=str),
          flush=True)
    if failed:
        print(f"chip_smoke: FAILED — {failed}", flush=True)
        return 1
    result = {"ok": True, "device": device}
    if cfg["preflight"]:
        result["preflight"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""flink_tpu benchmark suite — BASELINE.md configs on real hardware.

Measures the framework's windowed-aggregation engines against HONEST
compiled baselines: the per-record work of the reference's heap
keyed-state backend (hashmap probe + scalar accumulator update per
record, HeapAggregatingState.java:80-89) implemented in -O3 C++
(native/host_runtime.cpp), not a Python strawman.

Two engine tiers are measured (both user-reachable):
  - log-structured combiner tier (streaming/log_windows.py): ingest
    appends cells to per-window logs; fires sort + segment-reduce.
    The default engine for these workloads and the headline numbers.
  - device-resident scatter tier (streaming/vectorized.py): state
    lives in TPU HBM, ingest is a jitted scatter.  Reported as
    hll_scatter; it is the multi-chip path and wins when per-slot
    state is reused across many windows.

Configs (BASELINE.md):
  1. wordcount      tumbling 5s sum per word          (SocketWindowWordCount shape)
  2. hll            tumbling 1s HLL COUNT DISTINCT, 1M keys, precision 12  [headline]
  3. sliding_quant  sliding 10s/1s quantile sketch, 10M key space
  4. session_cm     session(1s gap) Count-Min totals

Output contract: ONE JSON line on stdout (the headline config #2);
the full per-config table goes to stderr and bench_report.json.

Methodology notes:
  - every timed region ends with a device->host sync (a D2H read), so
    async dispatch cannot hide incomplete work;
  - baselines are timed inside C++ (std::chrono around the loop) and
    reported as the BEST of 3 runs (most favorable to the baseline);
    the TPU rate is also best-of-N;
  - the TPU path includes host hashing (native C++ splitmix64), slot
    resolution (native C++ open-addressing index), H2D transfer,
    device scatter aggregation, and the window fire (gather+estimate);
  - no rate from this file has been taken on the chip builders have
    now; a config that raises makes the run exit non-zero.
"""

import json
import sys
import time

import numpy as np

import flink_tpu.native as nat
from flink_tpu.ops.device_agg import SumAggregate
from flink_tpu.ops.sketches import (
    CountMinSketchAggregate,
    HyperLogLogAggregate,
    QuantileSketchAggregate,
)
from flink_tpu.streaming.log_windows import (
    LogStructuredSessionWindows,
    LogStructuredSlidingWindows,
    LogStructuredTumblingWindows,
)
from flink_tpu.streaming.vectorized import (
    VectorizedTumblingWindows,
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def best_of(fn, reps=3):
    """Max rate over reps — the machine is shared and noisy; the best
    run is the least-contended estimate for BOTH sides."""
    return max(fn() for _ in range(reps))


def synth(n, n_keys, t_span, seed):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, n).astype(np.uint64)
    ts = np.sort(rng.integers(0, t_span, n).astype(np.int64))
    users = rng.integers(0, 2 ** 63, n).astype(np.uint64)
    return keys, ts, users


def run_engine(engine, kh, ts, values, vhs, horizon, chunk=1 << 20,
               warm_shift=10_000_000, reps=2, chunk_watermarks=False):
    """Feed an engine in chunks; watermark+fire at the end; D2H-synced
    timing.  Warmup runs ONE full chunk far in the past (compiling the
    ingest, flush, and fire shapes) so the timed region sees only
    cached programs; the timed main phase then processes every event.
    Returns events/s over the timed phase."""
    n = len(kh)
    flush = getattr(engine, "flush", lambda: None)
    warm = min(chunk, n)
    engine.process_batch(kh[:warm], ts[:warm] - warm_shift,
                         None if values is None else values[:warm],
                         key_hashes=kh[:warm],
                         value_hashes=None if vhs is None else vhs[:warm])
    if chunk_watermarks:
        flush()
        engine.advance_watermark(int(ts[warm - 1]) - warm_shift - 1)
    flush()
    engine.advance_watermark(horizon - warm_shift)
    engine.block_until_ready()
    engine.emitted.clear()
    if hasattr(engine, "fired"):
        engine.fired.clear()

    best = 0.0
    span = horizon + 1
    for rep in range(reps):
        shift = rep * 2 * span
        t0 = time.perf_counter()
        for i in range(0, n, chunk):
            sl = slice(i, i + chunk)
            engine.process_batch(kh[sl], ts[sl] + shift,
                                 None if values is None else values[sl],
                                 key_hashes=kh[sl],
                                 value_hashes=None if vhs is None else vhs[sl])
            if chunk_watermarks:
                # streaming watermark cadence: retire completed windows
                # as the event time advances, so live state stays
                # bounded (without this, a session run keeps EVERY
                # (key, session) slot live until the end — 8 GB at
                # config #4 scale).  Input is time-sorted, so the
                # chunk max is a safe watermark.
                flush()
                engine.advance_watermark(int(ts[sl][-1]) + shift - 1)
        flush()
        engine.advance_watermark(horizon + shift)
        engine.block_until_ready()
        elapsed = time.perf_counter() - t0
        best = max(best, n / elapsed)
        if rep < reps - 1:
            engine.emitted.clear()
            if hasattr(engine, "fired"):
                engine.fired.clear()
    return best


# ---------------------------------------------------------------------
# Config #2 — headline: tumbling 1s HLL COUNT DISTINCT, 1M keys, p12
# ---------------------------------------------------------------------

def _hll_workload(n_events, n_keys, precision):
    """Shared config-#2 workload + compiled baseline for the three
    hll entries (log/host, log/device, scatter): ONE definition so
    they stay comparable."""
    keys, ts, users = synth(n_events, n_keys, 1000, seed=7)
    kh = nat.splitmix64(keys)
    vh = nat.splitmix64(users)
    base_n = 1 << 22
    base_rate = best_of(lambda: nat.heap_tumbling_baseline(
        kh[:base_n], vh[:base_n], None, "hll", precision=precision,
        capacity=2 * n_keys))
    return keys, ts, kh, vh, base_rate


def bench_hll(n_events=1 << 23, n_keys=1_000_000, precision=12):
    """Log-structured combiner tier (the framework's default engine
    for this workload)."""
    keys, ts, kh, vh, base_rate = _hll_workload(n_events, n_keys, precision)

    agg = HyperLogLogAggregate(precision=precision)
    eng = LogStructuredTumblingWindows(agg, 1000)
    eng.emit_arrays = True
    rate = run_engine(eng, keys, ts, None, vh, horizon=999, reps=4)
    fired = sum(len(k) for k, _, _, _ in eng.fired)
    assert fired > 0.9 * min(n_keys, n_events), fired

    # p99 window-fire latency (the second BASELINE.json metric): many
    # 1s windows, each fire timed individually
    lat_n = 1 << 22
    lkeys, lts, lusers = synth(lat_n, n_keys, 16_000, seed=8)
    lvh = nat.splitmix64(lusers)
    lat_eng = LogStructuredTumblingWindows(agg, 1000)
    lat_eng.emit_arrays = True
    lat_eng.process_batch(lkeys, lts, None, value_hashes=lvh)
    lats = []
    for w_end in range(1000, 17_000, 1000):
        t0 = time.perf_counter()
        lat_eng.advance_watermark(w_end - 1)
        lats.append(time.perf_counter() - t0)
    p99_ms = float(np.quantile(np.asarray(lats), 0.99) * 1e3)
    return rate, base_rate, {"fire_p99_ms": round(p99_ms, 1)}


def bench_hll_10m(n_events=1 << 23, n_keys=10_000_000, precision=12):
    """North-star scale (BASELINE.json: "10M-key tumbling-window HLL
    COUNT DISTINCT"): 10M keyspace, 1s windows over a 10s span (~0.8M
    distinct keys live per window).  The baseline is the windowed
    variant (per-window state + cleanup on fire) — at this scale the
    dense all-keys register file would not exist in any backend."""
    keys, ts, users = synth(n_events, n_keys, 10_000, seed=21)
    kh = nat.splitmix64(keys)
    vh = nat.splitmix64(users)
    base_n = 1 << 22
    base_rate = best_of(lambda: nat.heap_windowed_hll_baseline(
        kh[:base_n], vh[:base_n], ts[:base_n], 1000,
        precision=precision, capacity=1 << 21))
    agg = HyperLogLogAggregate(precision=precision)
    eng = LogStructuredTumblingWindows(agg, 1000)
    eng.emit_arrays = True
    rate = run_engine(eng, keys, ts, None, vh, horizon=9999,
                      chunk_watermarks=True, reps=2)
    fired = sum(len(k) for k, _, _, _ in eng.fired)
    assert fired > 4_000_000, fired   # ~0.8M keys x 10 windows
    return rate, base_rate


def bench_hll_device(n_events=1 << 23, n_keys=1_000_000, precision=12):
    """Log tier with the window-fire finish forced ON DEVICE
    (finish_tier="device": C++ sort/compact, then one jitted
    exp2/cumsum/estimate scan on the TPU).  Measured, not asserted:
    this entry keeps the device path's cost a number on every
    attachment, whichever tier link_probe picks."""
    keys, ts, kh, vh, base_rate = _hll_workload(n_events, n_keys, precision)
    agg = HyperLogLogAggregate(precision=precision)
    eng = LogStructuredTumblingWindows(agg, 1000, finish_tier="device")
    eng.emit_arrays = True
    rate = run_engine(eng, keys, ts, None, vh, horizon=999, reps=3)
    fired = sum(len(k) for k, _, _, _ in eng.fired)
    assert fired > 0.9 * min(n_keys, n_events), fired
    return rate, base_rate


def bench_hll_scatter(n_events=1 << 23, n_keys=1_000_000, precision=12):
    """Device-resident scatter tier on the same workload (state in TPU
    HBM; the multi-chip path).  Capacity is sized to the keyspace
    (1.25x) rather than the next power of two: the window fire reads
    the whole register file once (full-arena fast path), so slack
    capacity is pure bandwidth tax."""
    keys, ts, kh, vh, base_rate = _hll_workload(n_events, n_keys, precision)
    agg = HyperLogLogAggregate(precision=precision)
    eng = VectorizedTumblingWindows(agg, 1000,
                                    initial_capacity=n_keys + n_keys // 4,
                                    microbatch=1 << 20)
    eng.emit_arrays = True
    # 6 reps: best-of-N needs enough N to catch a quiet window
    tpu_rate = run_engine(eng, kh, ts, None, vh, horizon=999, reps=6)
    fired = sum(len(k) for k, _, _, _ in eng.fired)
    assert fired > 0.9 * min(n_keys, n_events), fired
    return tpu_rate, base_rate


# ---------------------------------------------------------------------
# Config #1 — wordcount: tumbling 5s sum per word
# ---------------------------------------------------------------------

def bench_wordcount(n_events=1 << 23, n_words=50_000):
    keys, ts, _ = synth(n_events, n_words, 5000, seed=3)
    kh = nat.splitmix64(keys)
    ones = np.ones(n_events, np.float64)
    base_rate = best_of(lambda: nat.heap_tumbling_baseline(
        kh[:1 << 22], None, ones[:1 << 22], "sum"))
    eng = LogStructuredTumblingWindows(SumAggregate(np.float64), 5000)
    eng.emit_arrays = True
    rate = run_engine(eng, keys, ts, ones, None, horizon=4999, reps=3)
    assert sum(len(k) for k, _, _, _ in eng.fired) > 0.9 * n_words
    return rate, base_rate


def bench_wordcount_str(n_events=1 << 23, n_words=50_000):
    """Config #1's REAL shape: keyBy("word") over strings
    (SocketWindowWordCount.java:79).  The engine is the tier
    DeviceWindowOperator selects for this job
    (StringSumTumblingWindows): one fused C++ pass per batch interns
    each word and accumulates into a dense id-indexed window sum —
    phase-split so the hash/probe/verify loops run with full ILP.
    The baseline pays the reference heap backend's per-record string
    work (hash + probe with string-equality verification + add),
    compiled — per record, so it cannot phase-split."""
    from flink_tpu.streaming.log_windows import StringSumTumblingWindows
    rng = np.random.default_rng(17)
    vocab = np.asarray([f"word{i}" for i in range(n_words)])
    idx = rng.integers(0, n_words, n_events)
    words = vocab[idx]                       # '<U9' fixed-width rows
    ts = np.sort(rng.integers(0, 5000, n_events).astype(np.int64))
    ones = np.ones(n_events, np.float64)

    base_n = 1 << 22
    chunk = 1 << 20
    eng = StringSumTumblingWindows(SumAggregate(np.float64), 5000)
    eng.emit_arrays = True

    def one_pass(shift):
        for i in range(0, n_events, chunk):
            sl = slice(i, i + chunk)
            eng.process_batch(words[sl], ts[sl] + shift, ones[sl])
        eng.advance_watermark(4999 + shift)
        out_words = sum(len(k) for k, _r, _s, _e in eng.fired)
        eng.fired.clear()
        return out_words

    fired = one_pass(-10_000_000)  # warm
    assert fired > 0.9 * n_words, fired
    # INTERLEAVED A/B: baseline and engine passes alternate within
    # one process, so minutes-scale contention drift on a shared host
    # hits both sides equally and the RATIO stays comparable
    # (sequential phases put all drift on whichever side ran second)
    best = 0.0
    base_rate = 0.0
    for rep in range(5):
        base_rate = max(base_rate, nat.heap_tumbling_baseline_str(
            words[:base_n], ones[:base_n], capacity=2 * n_words))
        shift = (rep + 1) * 10_000
        t0 = time.perf_counter()
        fired = one_pass(shift)
        best = max(best, n_events / (time.perf_counter() - t0))
        assert fired > 0.9 * n_words, fired
    return best, base_rate


# ---------------------------------------------------------------------
# Config #3 — sliding 10s/1s quantile sketch (t-digest role), 10M keys
# ---------------------------------------------------------------------

def bench_sliding_quantile(n_events=1 << 21, n_keys=10_000_000):
    keys, ts, _ = synth(n_events, n_keys, 10_000, seed=5)
    kh = nat.splitmix64(keys)
    rng = np.random.default_rng(9)
    vals = (rng.lognormal(3.0, 1.0, n_events)).astype(np.float32)

    base_rate = best_of(lambda: nat.heap_sliding_hist_baseline(
        kh[:1 << 20], vals[:1 << 20], ts[:1 << 20], 10_000, 1000,
        n_buckets=128))

    agg = QuantileSketchAggregate(quantiles=(0.5, 0.99),
                                  relative_accuracy=0.05,
                                  min_value=1e-3, max_value=1e6)
    eng = LogStructuredSlidingWindows(agg, 10_000, 1000)
    eng.emit_arrays = True
    rate = run_engine(eng, keys, ts, vals, None, horizon=19_999,
                      chunk=1 << 19, reps=2)
    assert eng.fired, "no sliding windows fired"
    return rate, base_rate


# ---------------------------------------------------------------------
# Config #4 — session windows (1s gap) + Count-Min totals
# ---------------------------------------------------------------------

def bench_session_cm(n_events=1 << 21, n_keys=100_000):
    keys, ts, users = synth(n_events, n_keys, 30_000, seed=11)
    kh = nat.splitmix64(keys)
    vh = nat.splitmix64(users)
    # both sides use the same sketch geometry; width 256 keeps the
    # baseline's all-keys-live table (capacity * depth * width * 4B =
    # 0.5 GB) within host RAM
    depth, width = 4, 256

    base_rate = best_of(lambda: nat.heap_session_cm_baseline(
        kh[:1 << 20], vh[:1 << 20], ts[:1 << 20], 1000,
        depth=depth, width=width, capacity=2 * n_keys))

    agg = CountMinSketchAggregate(depth=depth, width=width)
    eng = LogStructuredSessionWindows(agg, 1000)
    eng.emit_arrays = True
    rate = run_engine(eng, keys, ts,
                      np.ones(n_events, np.float32), vh,
                      horizon=60_000, chunk=1 << 19,
                      chunk_watermarks=True, reps=2)
    assert eng.fired, "no sessions fired"
    return rate, base_rate


# ---------------------------------------------------------------------
# generic_agg — ARBITRARY Python AggregateFunction on the generic
# vectorized log tier (streaming/generic_agg.py): a custom streaming
# log-sum-exp (log-probability accumulation; float32 (max, scaled-sum)
# accumulator, two exps per record) over tumbling 1s windows, 1M keys.
# The baseline does the identical per-record work compiled: probe +
# stable (m, s) update with two expf calls
# (ref: WindowOperator.java:291-421 per-record contract).
# ---------------------------------------------------------------------

# ---------------------------------------------------------------------
# cep — STRICT next-chain pattern matching (cep/vectorized.py): the
# "three escalating events within T" alert shape over 1M keys, user
# conditions as Python lambdas COMPILED to predicate bytecode and
# evaluated inside the fused C++ kernel (ft_cep_advance_prog: masks +
# state + NFA advance, zero per-batch Python condition work).
# Baseline: the identical per-record strict-chain NFA compiled
# (ft_cep_strict_baseline — probe + shift, conditions inlined;
# favorable to the baseline).
# ---------------------------------------------------------------------

def bench_cep(n_events=1 << 22, n_keys=1_000_000, within=5_000_000):
    from flink_tpu.cep.pattern import Pattern
    from flink_tpu.cep.vectorized import VectorizedStrictNFA

    rng = np.random.default_rng(23)
    keys = rng.integers(0, n_keys, n_events).astype(np.uint64)
    ts = np.arange(n_events, dtype=np.int64)
    vals = rng.random(n_events) * 200
    kh = nat.splitmix64(keys)

    def baseline():
        return nat.cep_strict_baseline(kh, vals, ts, 4.0, 100.0,
                                       180.0, within,
                                       capacity=2 * n_keys)

    def make_pat():
        return (Pattern.begin("a").where(lambda e: e < 4.0)
                .next("b").where(lambda e: e >= 100.0)
                .next("c").where(lambda e: e >= 180.0)
                .within(within))

    # steady state: key table warm (the baseline's table is pre-sized
    # the same way), sustained batches
    eng = VectorizedStrictNFA(make_pat())
    eng.advance_batch(keys, ts - (1 << 40), cols=[vals],
                      vspec="scalar")
    # the lambdas lower to predicate bytecode: condition masks are
    # computed inside the kernel, not as numpy passes
    assert eng.mode == "compiled", eng.mode
    eng.matches.clear()
    base_rate, base_matches = baseline()   # warm
    best = 0.0
    matches = 0
    chunk = 1 << 21
    # INTERLEAVED A/B (same discipline as wordcount_str): baseline
    # and engine passes alternate within one process so contention
    # drift hits both sides equally and the ratio stays comparable
    for rep in range(5):
        base_rate = max(base_rate, baseline()[0])
        n0 = len(eng.matches)
        t0 = time.perf_counter()
        for i in range(0, n_events, chunk):
            sl = slice(i, i + chunk)
            eng.advance_batch(keys[sl],
                              ts[sl] + (rep + 1) * (1 << 41),
                              cols=[vals[sl]], vspec="scalar")
        best = max(best, n_events / (time.perf_counter() - t0))
        matches = len(eng.matches) - n0
    assert matches == base_matches, (matches, base_matches)
    return best, base_rate


# ---------------------------------------------------------------------
# cep_followed_by — skip-till-next (followedBy) chain on the native
# run-list tier (cep/vectorized.py → ft_cepr_advance_prog): per-key
# per-stage run LISTS, whole-list splice transitions, compiled
# predicates.  Baseline: the identical per-record skip-till-next NFA
# compiled (ft_cep_followed_baseline — pooled run lists, conditions
# inlined).
# ---------------------------------------------------------------------

def bench_cep_followed_by(n_events=1 << 22, n_keys=100_000,
                          within=200_000):
    from flink_tpu.cep.pattern import Pattern
    from flink_tpu.cep.vectorized import VectorizedStrictNFA

    rng = np.random.default_rng(29)
    keys = rng.integers(0, n_keys, n_events).astype(np.uint64)
    ts = np.arange(n_events, dtype=np.int64)
    vals = rng.random(n_events) * 200
    kh = nat.splitmix64(keys)

    def baseline():
        return nat.cep_followed_baseline(kh, vals, ts, 4.0, 198.0,
                                         within=within,
                                         capacity=2 * n_keys)

    def make_pat():
        return (Pattern.begin("a").where(lambda e: e < 4.0)
                .followed_by("b").where(lambda e: e >= 198.0)
                .within(within))

    eng = VectorizedStrictNFA(make_pat())
    eng.advance_batch(keys, ts - (1 << 40), cols=[vals],
                      vspec="scalar")
    assert eng.mode == "compiled", eng.mode
    assert eng._nat_runs is not None, "run-list tier not engaged"
    eng.matches.clear()
    base_rate, base_matches = baseline()   # warm
    best = 0.0
    matches = 0
    chunk = 1 << 21
    # interleaved A/B, as for cep
    for rep in range(5):
        base_rate = max(base_rate, baseline()[0])
        n0 = len(eng.matches)
        t0 = time.perf_counter()
        for i in range(0, n_events, chunk):
            sl = slice(i, i + chunk)
            eng.advance_batch(keys[sl],
                              ts[sl] + (rep + 1) * (1 << 41),
                              cols=[vals[sl]], vspec="scalar")
        best = max(best, n_events / (time.perf_counter() - t0))
        matches = len(eng.matches) - n0
    assert matches == base_matches, (matches, base_matches)
    assert matches > 0
    return best, base_rate


from flink_tpu.core.functions import AggregateFunction


class _StreamingLogSumExp(AggregateFunction):
    """The bench's custom aggregate — deliberately a plain Python
    AggregateFunction no engine tier knows about (the generic tier's
    lift probe discovers its array semantics at runtime)."""

    def create_accumulator(self):
        return (np.float32(-np.inf), np.float32(0.0))

    def add(self, x, acc):
        m, s = acc
        m2 = np.maximum(m, x)
        return (m2, s * np.exp(m - m2) + np.exp(x - m2))

    def get_result(self, acc):
        m, s = acc
        return m + np.log(s)

    def merge(self, a, b):
        m = np.maximum(a[0], b[0])
        return (m, a[1] * np.exp(a[0] - m) + b[1] * np.exp(b[0] - m))


class _MeanMaxAgg(AggregateFunction):
    """Adversarial MINIMAL custom aggregate (3-double tuple, no math)
    for the generic_agg_minimal diagnostic: with no math to amortize,
    this shape cannot beat a compiled probe loop on a 1-core host."""

    def create_accumulator(self):
        return (0.0, 0.0, -np.inf)

    def add(self, v, acc):
        s, c, m = acc
        return (s + v, c + 1.0, np.maximum(m, v))

    def get_result(self, acc):
        s, c, m = acc
        return (s / c, m)

    def merge(self, a, b):
        return (a[0] + b[0], a[1] + b[1], np.maximum(a[2], b[2]))


def bench_generic_agg_minimal(n_events=1 << 23, n_keys=1_000_000):
    """Diagnostic (NOT in the default suite — run `python bench.py
    generic_agg_minimal`): the worst case for the generic tier, a
    trivial (sum, count, max) accumulator where the compiled baseline
    is latency-optimal (about 0.5x when last run)."""
    from flink_tpu.streaming.generic_agg import GenericLogTumblingWindows

    rng = np.random.default_rng(17)
    keys = rng.integers(0, n_keys, n_events).astype(np.uint64)
    ts = np.sort(rng.integers(0, 1000, n_events).astype(np.int64))
    vals = rng.random(n_events)
    kh = nat.splitmix64(keys)
    base_n = 1 << 22
    base_rate = best_of(lambda: nat.heap_tumbling_meanmax_baseline(
        kh[:base_n], vals[:base_n], capacity=2 * n_keys))
    eng = GenericLogTumblingWindows(_MeanMaxAgg(), 1000,
                                    compact_threshold=n_events)
    eng.emit_arrays = True
    rate = run_engine(eng, keys, ts, vals, None, horizon=999, reps=4)
    assert eng.mode == "lifted", eng.mode
    return rate, base_rate


def bench_generic_agg(n_events=1 << 23, n_keys=1_000_000):
    """Generic vectorized tier vs compiled per-record baseline on a
    custom Python aggregate."""
    from flink_tpu.streaming.generic_agg import GenericLogTumblingWindows

    rng = np.random.default_rng(17)
    keys = rng.integers(0, n_keys, n_events).astype(np.uint64)
    ts = np.sort(rng.integers(0, 1000, n_events).astype(np.int64))
    scores = (rng.random(n_events) * 4).astype(np.float32)
    kh = nat.splitmix64(keys)
    base_n = 1 << 22
    base_rate = best_of(lambda: nat.heap_tumbling_lse_baseline(
        kh[:base_n], scores[:base_n], capacity=2 * n_keys))

    # whole-window fold config: the 1s window folds once at fire (the
    # compaction threshold is the documented memory/throughput knob)
    eng = GenericLogTumblingWindows(_StreamingLogSumExp(), 1000,
                                    compact_threshold=n_events)
    eng.emit_arrays = True
    rate = run_engine(eng, keys, ts, scores, None, horizon=999, reps=4)
    assert eng.mode == "lifted", eng.mode
    fired = sum(len(k) for k, *_ in eng.fired)
    assert fired > 0.9 * min(n_keys, n_events), fired
    return rate, base_rate


# ---------------------------------------------------------------------
# Config #5 — SQL: APPROX_COUNT_DISTINCT GROUP BY TUMBLE through the
# full framework path (parser → planner → DeviceWindowOperator →
# streaming executor); measures the per-record framework overhead on
# top of the engine rate, against the same compiled HLL baseline.
# ---------------------------------------------------------------------

def bench_sql(n_events=1 << 22, n_keys=500_000, precision=12):
    """SQL through the full framework path: parser → planner →
    columnar physical plan (RecordBatch tier) → streaming executor.
    The planner compiles the TUMBLE + APPROX_COUNT_DISTINCT GROUP BY
    onto ColumnarWindowOperator (the Blink-planner-style vectorized
    lowering); row-at-a-time plans remain the general path."""
    from flink_tpu.streaming.columnar import ColumnarCollectSink
    from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    from flink_tpu.table import StreamTableEnvironment

    keys, ts, users = synth(n_events, n_keys, 1000, seed=13)
    kh = nat.splitmix64(keys)
    vh = nat.splitmix64(users)
    base_rate = best_of(lambda: nat.heap_tumbling_baseline(
        kh, vh, None, "hll", precision=precision, capacity=2 * n_keys))

    # one-time process init outside the timed region (run_engine's
    # warmup excludes the same costs for the engine-level configs):
    # the finish-tier link probe and the backend client
    from flink_tpu.ops import link_probe
    link_probe.measure()

    def one_run():
        env = StreamExecutionEnvironment()
        t_env = StreamTableEnvironment.create(env)
        t_env.register_table(
            "ev", t_env.from_columns({"k": keys, "u": users, "ts": ts},
                                     rowtime="ts"))
        out = t_env.sql_query(
            "SELECT k, APPROX_COUNT_DISTINCT(u) AS d "
            "FROM ev GROUP BY TUMBLE(ts, INTERVAL '1' SECOND), k")
        assert getattr(out, "columnar", False), \
            "sql bench plan fell off the columnar tier"
        sink = ColumnarCollectSink()
        out.to_append_stream(batched=True).add_sink(sink)
        t0 = time.perf_counter()
        env.execute("bench-sql")
        elapsed = time.perf_counter() - t0
        assert sink.total_rows() > 0.9 * n_keys, sink.total_rows()
        return n_events / elapsed

    one_run()  # warm (parser/planner/source/engine code paths)
    return best_of(one_run, reps=3), base_rate


def bench_sql_join(n_each=1 << 21, n_keys=100_000, bound_ms=500,
                   span_ms=60_000):
    """Windowed stream-stream join on the columnar tier: SQL
    JOIN ... ON equi-key AND rowtime BETWEEN +-bound compiles onto
    ColumnarIntervalJoinOperator (vectorized hash join per batch,
    watermark-pruned buffers).  Baseline: the per-record time-bounded
    join (probe per-key time-sorted buffer + range walk per record),
    compiled, both inputs merged in event-time order."""
    from flink_tpu.streaming.columnar import ColumnarCollectSink
    from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    from flink_tpu.table import StreamTableEnvironment

    rng = np.random.default_rng(23)
    lk = rng.integers(0, n_keys, n_each).astype(np.uint64)
    lts = np.sort(rng.integers(0, span_ms, n_each).astype(np.int64))
    rk = rng.integers(0, n_keys, n_each).astype(np.uint64)
    rts = np.sort(rng.integers(0, span_ms, n_each).astype(np.int64))

    def baseline():
        return nat.interval_join_baseline(
            nat.splitmix64(lk), lts, nat.splitmix64(rk), rts,
            -bound_ms, bound_ms, capacity=2 * n_keys)

    base_rate, base_pairs = baseline()   # warm

    def engine_run():
        env = StreamExecutionEnvironment()
        t_env = StreamTableEnvironment.create(env)
        t_env.register_table("l", t_env.from_columns(
            {"lid": np.arange(n_each), "k": lk, "ts": lts},
            rowtime="ts", chunk=1 << 20))
        t_env.register_table("r", t_env.from_columns(
            {"rid": np.arange(n_each), "rk": rk, "rts": rts},
            rowtime="rts", chunk=1 << 20))
        out = t_env.sql_query(
            "SELECT a.lid, b.rid FROM l AS a JOIN r AS b "
            "ON a.k = b.rk AND a.ts BETWEEN b.rts - INTERVAL "
            f"'{bound_ms}' MILLISECOND AND b.rts + INTERVAL "
            f"'{bound_ms}' MILLISECOND")
        assert getattr(out, "columnar", False), \
            "join fell off the columnar tier"
        sink = ColumnarCollectSink()
        out.to_append_stream(batched=True).add_sink(sink)
        t0 = time.perf_counter()
        env.execute("bench-sql-join")
        elapsed = time.perf_counter() - t0
        assert sink.total_rows() == base_pairs, \
            (sink.total_rows(), base_pairs)
        return 2 * n_each / elapsed

    engine_run()   # warm (parser/planner/source/engine code paths)
    # INTERLEAVED A/B (same discipline as wordcount_str): baseline
    # and engine passes alternate within one process so contention
    # drift hits both sides equally and the ratio stays comparable
    best = 0.0
    for _rep in range(3):
        base_rate = max(base_rate, baseline()[0])
        best = max(best, engine_run())
    return best, base_rate


def bench_shuffle(n_events=1 << 17, n_keys=1024):
    """Cross-host shuffle data plane: a keyBy exchange of (int, str,
    float) tuple records through the batched router fan-out onto real
    TCP DataServer/DataClient channels.  A/B is INTERLEAVED in one
    process: the columnar zero-copy wire codec with batch-mode
    consumer decode (A) against the per-batch pickle path (B,
    COLUMNAR_ENABLED off) over the identical record stream — both
    sides pay the same router, socket, credit, and decode loop; the
    codec tier and the consumer's boxing differ.  The subscription is
    batch-mode for both passes: pickle frames pass through it as
    records, so B is unchanged while A skips per-record boxing."""
    from flink_tpu.core.functions import as_key_selector
    from flink_tpu.runtime import netchannel
    from flink_tpu.runtime.local import _RouterOutput
    from flink_tpu.runtime.netchannel import DataClient, DataServer
    from flink_tpu.streaming.elements import StreamRecord
    from flink_tpu.streaming.partitioners import KeyGroupStreamPartitioner

    rng = np.random.default_rng(23)
    keys = rng.integers(0, n_keys, n_events)
    records = [StreamRecord((int(k), f"user{k}", float(k) * 0.5), int(i))
               for i, k in enumerate(keys)]

    class _CountSink:
        """Consumer-side `_InputChannel` stand-in that drains
        instantly, so the credit window stays open and the wire is
        the bottleneck being measured."""
        blocked = False
        capacity = 1 << 30
        queue = ()

        def __init__(self):
            self.count = 0

        def push(self, el):
            self.count += len(el) if el.is_batch else 1

        def push_batch(self, els):
            for el in els:
                self.push(el)

    n_ch = 4
    server = DataServer()
    client = DataClient()
    sinks = [_CountSink() for _ in range(n_ch)]
    outs = []
    router = _RouterOutput()
    for c in range(n_ch):
        key = ("bench-shuffle", 0, 1, c, 0)
        outs.append(server.register_out_channel(key, capacity=1 << 20))
        client.subscribe(server.address, key, sinks[c], capacity=1 << 20,
                         columnar=True)
    router.add_route(
        KeyGroupStreamPartitioner(as_key_selector(lambda v: v[0]), 128),
        outs)

    def one_pass(columnar):
        netchannel.COLUMNAR_ENABLED = columnar
        for s in sinks:
            s.count = 0
        t0 = time.perf_counter()
        for r in records:
            router.collect(r)
        router.flush_records()
        server.wake()
        while sum(s.count for s in sinks) < n_events:
            if client.error is not None:
                raise client.error
            client.replenish_credits()
            time.sleep(0.0005)
        return n_events / (time.perf_counter() - t0)

    try:
        one_pass(True)   # warm: connections, allocator, first frames
        one_pass(False)
        col_rate = pkl_rate = 0.0
        for _rep in range(4):
            pkl_rate = max(pkl_rate, one_pass(False))
            col_rate = max(col_rate, one_pass(True))
    finally:
        netchannel.COLUMNAR_ENABLED = True
        client.stop()
        server.stop()
    snap = netchannel.NET_STATS.snapshot()
    return col_rate, pkl_rate, {
        "frames_columnar": snap["framesColumnar"],
        "frames_pickle": snap["framesPickle"],
        "frame_bytes_mean": round(snap["frameBytesMean"]),
    }


def bench_columnar_chain(n_events=1 << 17, n_keys=256, window_ms=1000,
                         chunk=8192):
    """End-to-end columnar operator pipeline over real TCP: batched
    source -> map -> filter (column kernels) -> vectorized keyBy split
    -> wire -> batch-mode decode -> generic tumbling-window sum (A)
    against the identical chain fed per-record with boxed decode (B).
    A/B is INTERLEAVED in one process and both passes must produce
    the same window sums — this measures exactly the per-record
    StreamRecord tax the batch element model removes."""
    from flink_tpu.core.functions import (
        AggregateFunction,
        _LambdaFilter,
        _LambdaMap,
        as_key_selector,
    )
    from flink_tpu.runtime import netchannel
    from flink_tpu.runtime.local import _ChainedOutput, _RouterOutput
    from flink_tpu.runtime.netchannel import DataClient, DataServer
    from flink_tpu.streaming.elements import (
        MAX_TIMESTAMP,
        RecordBatch,
        StreamRecord,
        Watermark,
    )
    from flink_tpu.streaming.generic_agg import GenericWindowOperator
    from flink_tpu.streaming.operators import (
        Output,
        StreamFilter,
        StreamMap,
    )
    from flink_tpu.streaming.partitioners import KeyGroupStreamPartitioner
    from flink_tpu.streaming.windowing import TumblingEventTimeWindows

    rng = np.random.default_rng(23)
    keys64 = rng.integers(0, n_keys, n_events).astype(np.int64)
    vals64 = rng.integers(0, 100, n_events).astype(np.int64)
    ts64 = np.arange(n_events, dtype=np.int64)
    records = [StreamRecord((int(k), int(v)), int(t))
               for k, v, t in zip(keys64, vals64, ts64)]
    # numpy reference for the whole pipeline (exact: int sums)
    v3 = vals64 * 3
    keep = (v3 % 7) != 0
    wstart = ts64 - ts64 % window_ms
    expected_rows = int(np.count_nonzero(keep))
    ref = {}
    for k, w, v in zip(keys64[keep].tolist(), wstart[keep].tolist(),
                       v3[keep].tolist()):
        ref[(k, w)] = ref.get((k, w), 0) + v
    expected = sorted((k, w, s) for (k, w), s in ref.items())

    class SumAgg(AggregateFunction):
        def create_accumulator(self):
            return 0

        def add(self, value, acc):
            return acc + value[1]

        def get_result(self, acc):
            return acc

        def merge(self, a, b):
            return a + b

    class _ResultOut(Output):
        def __init__(self):
            self.values = []

        def collect(self, record):
            self.values.append(record.value)

        def emit_watermark(self, watermark):
            pass

    class _ChainSink:
        """Consumer-side `_InputChannel` stand-in feeding the window
        operator directly on the reader thread (A gets RecordBatches,
        B gets per-record StreamRecords — same wire, same operator)."""
        blocked = False
        capacity = 1 << 30
        queue = ()

        def __init__(self):
            self.rows = 0
            self.head = None

        def push(self, el):
            if el.is_batch:
                self.head.process_batch(el)
                self.rows += len(el)
            else:
                self.head.process_element(el)
                self.rows += 1

        def push_batch(self, els):
            for el in els:
                self.push(el)

    n_ch = 4
    server = DataServer()
    clients, sinks, routers = [], [], []
    for columnar, tag in ((True, "A"), (False, "B")):
        client = DataClient()
        side_sinks = [_ChainSink() for _ in range(n_ch)]
        router = _RouterOutput()
        outs = []
        for c in range(n_ch):
            key = (f"bench-colchain-{tag}", 0, 1, c, 0)
            outs.append(server.register_out_channel(key, capacity=1 << 20))
            client.subscribe(server.address, key, side_sinks[c],
                             capacity=1 << 20, columnar=columnar)
        router.add_route(KeyGroupStreamPartitioner(as_key_selector(0), 128),
                         outs)
        clients.append(client)
        sinks.append(side_sinks)
        routers.append(router)

    def one_pass(batched):
        client = clients[0 if batched else 1]
        side = sinks[0 if batched else 1]
        router = routers[0 if batched else 1]
        # fresh operators per pass: kernel probes and window state are
        # per-run
        map_op = StreamMap(_LambdaMap(lambda t: (t[0], t[1] * 3)))
        filt_op = StreamFilter(_LambdaFilter(lambda t: t[1] % 7 != 0))
        filt_op.setup(router)
        map_op.setup(_ChainedOutput(filt_op, router))
        map_op.open()
        filt_op.open()
        results = []
        for s in side:
            gwo = GenericWindowOperator(
                TumblingEventTimeWindows.of(window_ms), SumAgg(),
                window_function=lambda k, w, rs: [(k, w.start, rs[0])])
            out = _ResultOut()
            gwo.setup(out, key_selector=as_key_selector(0))
            gwo.open()
            s.head = gwo
            s.rows = 0
            results.append(out)
        t0 = time.perf_counter()
        if batched:
            for i in range(0, n_events, chunk):
                map_op.process_batch(RecordBatch(
                    {"f0": keys64[i:i + chunk], "f1": vals64[i:i + chunk]},
                    ts64[i:i + chunk]))
        else:
            for r in records:
                map_op.process_element(r)
        router.flush_records()
        server.wake()
        while sum(s.rows for s in side) < expected_rows:
            if client.error is not None:
                raise client.error
            client.replenish_credits()
            time.sleep(0.0005)
        for s in side:
            s.head.process_watermark(Watermark(MAX_TIMESTAMP))
        elapsed = time.perf_counter() - t0
        got = sorted((int(k), int(w), int(v))
                     for out in results for k, w, v in out.values)
        assert got == expected, \
            f"{'batched' if batched else 'boxed'} pipeline diverged " \
            f"({len(got)} vs {len(expected)} windows)"
        if batched:
            assert map_op.boxed_fallbacks == 0 \
                and filt_op.boxed_fallbacks == 0, (
                    map_op.columnar_fallback_reason,
                    filt_op.columnar_fallback_reason)
        return n_events / elapsed

    try:
        one_pass(True)    # warm: connections, probes, engine dispatch
        one_pass(False)
        col_rate = box_rate = 0.0
        for _rep in range(3):
            box_rate = max(box_rate, one_pass(False))
            col_rate = max(col_rate, one_pass(True))
    finally:
        for client in clients:
            client.stop()
        server.stop()
    snap = netchannel.NET_STATS.snapshot()
    return col_rate, box_rate, {
        "rows_after_filter": expected_rows,
        "frames_columnar": snap["framesColumnar"],
        "frames_pickle": snap["framesPickle"],
    }


def bench_fused_chain(n_events=1 << 18, n_keys=256, window_ms=1000,
                      chunk=1 << 16):
    """Chain fusion A/B on the SAME columnar graph over real TCP:
    batched source -> map x4 / filter x2 -> keyBy split -> wire ->
    batch-mode decode -> tumbling-window sum, with (A) the six-stage
    map/filter/hash/route prefix lowered into ONE jitted fused chain
    program (streaming/chain_fusion.py) against (B) the identical
    chain on per-operator column-kernel dispatch.  Interleaved in one
    process, both sides asserted against a numpy reference, zero boxed
    fallbacks and zero demotions required.  The timed leg is the
    producer dispatch (batch push through the chain + channel fan-out
    + flush); the TCP drain and the window fold are identical on both
    sides and verified untimed — the delta is exactly the per-operator
    dispatch + host-intermediate tax fusion removes.

    Under --device-ledger the fused region must cross the host-device
    boundary ONLY at the chain edges: every transfer recorded during
    an A pass carries the `chain.boundary` tag (no intra-chain
    H2D/D2H), and the program shows up in the kernel table under its
    `chain.<head>-><tail>` label."""
    from flink_tpu.core.functions import (
        AggregateFunction,
        _LambdaFilter,
        _LambdaMap,
        as_key_selector,
    )
    from flink_tpu.runtime.device_stats import TELEMETRY
    from flink_tpu.runtime.local import _ChainedOutput, _RouterOutput
    from flink_tpu.runtime.netchannel import DataClient, DataServer
    from flink_tpu.streaming import chain_fusion
    from flink_tpu.streaming.elements import (
        MAX_TIMESTAMP,
        RecordBatch,
        Watermark,
    )
    from flink_tpu.streaming.generic_agg import GenericWindowOperator
    from flink_tpu.streaming.operators import (
        Output,
        StreamFilter,
        StreamMap,
    )
    from flink_tpu.streaming.partitioners import KeyGroupStreamPartitioner
    from flink_tpu.streaming.windowing import TumblingEventTimeWindows

    rng = np.random.default_rng(29)
    keys64 = rng.integers(0, n_keys, n_events).astype(np.int64)
    vals64 = rng.integers(0, 100, n_events).astype(np.int64)
    ts64 = np.arange(n_events, dtype=np.int64)
    # numpy reference for the whole pipeline (exact: int sums); mask
    # conjunction commutes, so both filters apply to the full column
    v2 = vals64 * 3 + 17
    keep = (v2 % 7) != 0
    v3 = v2 * 5 - 2
    keep &= (v3 % 11) != 3
    v4 = v3 // 2
    wstart = ts64 - ts64 % window_ms
    expected_rows = int(np.count_nonzero(keep))
    ref = {}
    for k, w, v in zip(keys64[keep].tolist(), wstart[keep].tolist(),
                       v4[keep].tolist()):
        ref[(k, w)] = ref.get((k, w), 0) + v
    expected = sorted((k, w, s) for (k, w), s in ref.items())

    class SumAgg(AggregateFunction):
        def create_accumulator(self):
            return 0

        def add(self, value, acc):
            return acc + value[1]

        def get_result(self, acc):
            return acc

        def merge(self, a, b):
            return a + b

    class _ResultOut(Output):
        def __init__(self):
            self.values = []

        def collect(self, record):
            self.values.append(record.value)

        def emit_watermark(self, watermark):
            pass

    class _ChainSink:
        blocked = False
        capacity = 1 << 30
        queue = ()

        def __init__(self):
            self.rows = 0
            self.head = None

        def push(self, el):
            if el.is_batch:
                self.head.process_batch(el)
                self.rows += len(el)
            else:
                self.head.process_element(el)
                self.rows += 1

        def push_batch(self, els):
            for el in els:
                self.push(el)

    # the prefix under test: six liftable stages ending in the keyBy
    # split — deep enough that per-operator dispatch pays six kernel
    # hops, two compactions and a host partition per batch where the
    # fused program pays one device program.  Operators (and the A
    # side's compiled program) live across passes, exactly like a
    # deployed subtask.
    def build_chain(router):
        ops = [
            StreamMap(_LambdaMap(lambda t: (t[0], t[1] * 3))),
            StreamMap(_LambdaMap(lambda t: (t[0], t[1] + 17))),
            StreamFilter(_LambdaFilter(lambda t: t[1] % 7 != 0)),
            StreamMap(_LambdaMap(lambda t: (t[0], t[1] * 5 - 2))),
            StreamFilter(_LambdaFilter(lambda t: t[1] % 11 != 3)),
            StreamMap(_LambdaMap(lambda t: (t[0], t[1] // 2))),
        ]
        ops[-1].setup(router)
        for k in range(len(ops) - 2, -1, -1):
            ops[k].setup(_ChainedOutput(ops[k + 1], router))
        for op in ops:
            op.open()
        return ops

    n_ch = 4
    server = DataServer()
    clients, sinks, routers, chains, progs = [], [], [], [], []
    for tag in ("A", "B"):
        client = DataClient()
        side_sinks = [_ChainSink() for _ in range(n_ch)]
        router = _RouterOutput()
        outs = []
        for c in range(n_ch):
            key = (f"bench-fused-{tag}", 0, 1, c, 0)
            outs.append(server.register_out_channel(key, capacity=1 << 20))
            client.subscribe(server.address, key, side_sinks[c],
                             capacity=1 << 20, columnar=True)
        router.add_route(KeyGroupStreamPartitioner(as_key_selector(0), 128),
                         outs)
        ops = build_chain(router)
        prog = None
        if tag == "A":
            prog = chain_fusion.compile_chain(ops, router=router)
            assert prog is not None and prog.route_field == 0 \
                and len(prog.kernel_ops) == len(ops), \
                "the whole map/filter->keyBy prefix must compile"
        clients.append(client)
        sinks.append(side_sinks)
        routers.append(router)
        chains.append(ops)
        progs.append(prog)

    ledger_tags = set()
    fused_batches = [0]
    fused_passes = [0]

    def one_pass(fused):
        i_side = 0 if fused else 1
        client, side = clients[i_side], sinks[i_side]
        router, ops = routers[i_side], chains[i_side]
        prog = progs[i_side]
        results = []
        for s in side:
            gwo = GenericWindowOperator(
                TumblingEventTimeWindows.of(window_ms), SumAgg(),
                window_function=lambda k, w, rs: [(k, w.start, rs[0])])
            out = _ResultOut()
            gwo.setup(out, key_selector=as_key_selector(0))
            gwo.open()
            s.head = gwo
            s.rows = 0
            results.append(out)
        pre_transfers = (set(TELEMETRY.payload()["transfers"])
                         if fused and TELEMETRY.enabled else None)
        # timed: the producer dispatch leg (chain kernels, hash +
        # partition, channel fan-out, flush).  Drain + window fold are
        # identical on both sides and verified below, untimed.
        t0 = time.perf_counter()
        for i in range(0, n_events, chunk):
            batch = RecordBatch(
                {"f0": keys64[i:i + chunk], "f1": vals64[i:i + chunk]},
                ts64[i:i + chunk])
            if fused and prog.wants(batch):
                prog.run(batch)
            else:
                ops[0].process_batch(batch)
        router.flush_records()
        elapsed = time.perf_counter() - t0
        server.wake()
        while sum(s.rows for s in side) < expected_rows:
            if client.error is not None:
                raise client.error
            client.replenish_credits()
            time.sleep(0.0005)
        for s in side:
            s.head.process_watermark(Watermark(MAX_TIMESTAMP))
        got = sorted((int(k), int(w), int(v))
                     for out in results for k, w, v in out.values)
        assert got == expected, \
            f"{'fused' if fused else 'per-operator'} pipeline diverged " \
            f"({len(got)} vs {len(expected)} windows)"
        for op in ops:
            assert op.boxed_fallbacks == 0, \
                (type(op).__name__, op.columnar_fallback_reason)
        if fused:
            fused_passes[0] += 1
            assert prog.active, \
                f"fused chain demoted: {prog.demoted_reason}"
            assert ops[0].fused_rows == n_events * fused_passes[0], \
                "every batch must ride the fused program"
            fused_batches[0] = n_events // chunk
            if pre_transfers is not None:
                new = set(TELEMETRY.payload()["transfers"]) - pre_transfers
                tags = {t.split(".", 1)[1] for t in new}
                ledger_tags.update(tags)
                assert tags <= {"chain.boundary"}, \
                    f"intra-chain host round-trips: {tags}"
        return n_events / elapsed

    try:
        one_pass(True)    # warm: connections, probes, jit traces
        one_pass(False)
        fused_rate = perop_rate = 0.0
        for _rep in range(5):
            perop_rate = max(perop_rate, one_pass(False))
            fused_rate = max(fused_rate, one_pass(True))
    finally:
        for client in clients:
            client.stop()
        server.stop()

    # dispatch-only rail: the same six-stage chain into counting
    # channels (no wire, no consumer) — isolates the per-operator
    # dispatch + host-intermediate tax fusion removes from the shared
    # TCP/serialize cost that dominates (and adds noise to) the
    # end-to-end leg above
    class _CountCh:
        def __init__(self):
            self.rows = 0

        def push(self, el):
            self.rows += len(el)

    class _LocalRouter:
        def __init__(self, channels):
            self.routes = [(KeyGroupStreamPartitioner(
                as_key_selector(0), 128), channels, None)]
            self.records_out_counter = None

        def flush_records(self):
            pass

        def collect_batch(self, batch):
            for part, channels, _tag in self.routes:
                for idx, sub in part.split_batch(batch, len(channels)):
                    channels[idx].push(sub)

    rails = {}
    for fused in (True, False):
        chans = [_CountCh() for _ in range(n_ch)]
        router = _LocalRouter(chans)
        ops = build_chain(router)
        prog = (chain_fusion.compile_chain(ops, router=router)
                if fused else None)
        rails[fused] = (chans, ops, prog)

    def dispatch_pass(fused):
        chans, ops, prog = rails[fused]
        for c in chans:
            c.rows = 0
        t0 = time.perf_counter()
        for i in range(0, n_events, chunk):
            batch = RecordBatch(
                {"f0": keys64[i:i + chunk],
                 "f1": vals64[i:i + chunk]}, ts64[i:i + chunk])
            if fused and prog.wants(batch):
                prog.run(batch)
            else:
                ops[0].process_batch(batch)
        el = time.perf_counter() - t0
        assert sum(c.rows for c in chans) == expected_rows
        if fused:
            assert prog.active, prog.demoted_reason
        return n_events / el

    dispatch_pass(True)   # warm probes / jit traces
    dispatch_pass(False)
    disp_fused = disp_perop = 0.0
    for _rep in range(5):
        disp_perop = max(disp_perop, dispatch_pass(False))
        disp_fused = max(disp_fused, dispatch_pass(True))

    extra = {
        "rows_after_filter": expected_rows,
        "fused_batches_per_pass": fused_batches[0],
        "demotions": chain_fusion.FUSION_STATS.demotions,
        "dispatch_only": {
            "fused_events_per_sec": int(disp_fused),
            "perop_events_per_sec": int(disp_perop),
            "ratio": round(disp_fused / disp_perop, 2),
        },
    }
    if TELEMETRY.enabled:
        extra["fused_region_transfer_tags"] = sorted(ledger_tags)
        kernels = TELEMETRY.payload()["kernels"]
        extra["chain_kernel_labels"] = sorted(
            k for k in kernels if k.startswith("chain."))
    return fused_rate, perop_rate, extra


def bench_state_chain(n_events=1 << 17, n_keys=64, window_ms=16000,
                      chunk=8192):
    """Keyed window state ingest: the identical tumbling event-time
    sum on the identical backend, (A) fed whole RecordBatches through
    `WindowOperator.process_batch` -> `backend.add_batch` against (B)
    fed per-record through `process_element` -> per-row state.add.
    Watermark cadence is identical (one per chunk), both sides' window
    output must match a numpy reference, and A must take the columnar
    path for every row — the delta is exactly the per-row state tax.
    Headline = the TPU backend pair; the heap pair rides in extras.
    The config is ingest-dominated (2k rows per (key, window) group):
    window FIRES still walk a per-(key, window) timer + state.get on
    both sides, so fire-heavy configs measure that shared path, not
    the ingest tax this bench exists to isolate."""
    from flink_tpu.core.functions import as_key_selector
    from flink_tpu.core.state import AggregatingStateDescriptor
    from flink_tpu.ops.device_agg import SumAggregate
    from flink_tpu.streaming.elements import RecordBatch, StreamRecord
    from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
    from flink_tpu.streaming.window_operator import WindowOperator
    from flink_tpu.streaming.windowing import TumblingEventTimeWindows

    rng = np.random.default_rng(31)
    keys64 = rng.integers(0, n_keys, n_events).astype(np.int64)
    vals64 = rng.integers(0, 100, n_events).astype(np.int64)
    ts64 = np.arange(n_events, dtype=np.int64)
    vals_f = vals64.astype(np.float64)
    records = [StreamRecord((int(k), float(v)), int(t))
               for k, v, t in zip(keys64, vals64, ts64)]
    # numpy reference (exact: small ints sum exactly in float32)
    wstart = ts64 - ts64 % window_ms
    ref = {}
    for k, w, v in zip(keys64.tolist(), wstart.tolist(), vals64.tolist()):
        ref[(k, w)] = ref.get((k, w), 0) + v
    expected = sorted((k, w, float(s)) for (k, w), s in ref.items())

    class _KVSum(SumAggregate):
        def __init__(self):
            super().__init__(np.float32)

        def extract_value(self, value):
            return value[1] if isinstance(value, tuple) else value

    def one_pass(backend, batched):
        op = WindowOperator(
            TumblingEventTimeWindows.of(window_ms),
            AggregatingStateDescriptor("bench-sum", _KVSum()),
            window_function=lambda k, w, vs: [(k, w.start, float(v))
                                              for v in vs])
        h = OneInputStreamOperatorTestHarness(
            op, key_selector=as_key_selector(0), state_backend=backend)
        h.open()
        t0 = time.perf_counter()
        if batched:
            for i in range(0, n_events, chunk):
                h.process_batch(RecordBatch(
                    {"f0": keys64[i:i + chunk], "f1": vals_f[i:i + chunk]},
                    ts=ts64[i:i + chunk]))
                h.process_watermark(int(ts64[min(i + chunk, n_events) - 1]))
        else:
            for i, r in enumerate(records):
                h.process_element(r)
                if (i + 1) % chunk == 0 or i == n_events - 1:
                    h.process_watermark(r.timestamp)
        h.process_watermark(1 << 60)
        elapsed = time.perf_counter() - t0
        got = sorted((int(k), int(w), float(v))
                     for k, w, v in h.extract_output_values())
        assert got == expected, \
            f"{backend} {'batched' if batched else 'per-row'} window " \
            f"state diverged ({len(got)} vs {len(expected)} emissions)"
        if batched:
            assert op.boxed_fallbacks == 0 and op.columnar_rows == n_events, \
                (op.boxed_fallbacks, op.columnar_fallback_reason)
        return n_events / elapsed

    # the A/B isolates the per-row state tax: the introspection plane
    # must stay disabled so its ingest hooks cannot skew either side
    from flink_tpu.state.introspect import INTROSPECTION
    assert not INTROSPECTION.enabled, \
        "state introspection must be off during the state_chain A/B"
    rates = {}
    for backend in ("tpu", "heap"):
        one_pass(backend, True)    # warm: device tables, jit, dispatch
        one_pass(backend, False)
        batch_rate = row_rate = 0.0
        for _rep in range(3):
            row_rate = max(row_rate, one_pass(backend, False))
            batch_rate = max(batch_rate, one_pass(backend, True))
        rates[backend] = (batch_rate, row_rate)
        log(f"[bench] state_chain[{backend}]: batch "
            f"{batch_rate/1e6:.2f} M ev/s, per-row {row_rate/1e6:.2f} "
            f"M ev/s, ratio {batch_rate/row_rate:.2f}x")
    batch_rate, row_rate = rates["tpu"]
    assert batch_rate >= 2.0 * row_rate, \
        f"batched state ingest only {batch_rate/row_rate:.2f}x over " \
        f"per-row on the tpu backend (acceptance floor is 2x)"
    return batch_rate, row_rate, {
        "heap_batch_events_per_sec": round(rates["heap"][0]),
        "heap_row_events_per_sec": round(rates["heap"][1]),
        "heap_vs_row": round(rates["heap"][0] / rates["heap"][1], 2),
        "window_emissions": len(expected),
    }


def bench_state_chain_fires(n_events=1 << 17, n_keys=256, window_ms=1000,
                            chunk=8192):
    """Fire-dominated twin of state_chain: 256 keys x 1s tumbling
    windows over a 131s event span = ~34k window FIRES, with a
    watermark per chunk so fires interleave with ingest.  Both sides
    ingest through the identical columnar process_batch path — the A/B
    toggle is `WindowOperator.batch_fires`: (A) the columnar timer
    sweep + one-gather watermark fire against (B) the per-timer scalar
    drain (one state.get / one D2H per fired (key, window) on the
    device backend).  Both sides' emissions must match the numpy
    reference, so the delta is exactly the per-fire tax.  Headline =
    the TPU backend pair; the heap pair rides in extras."""
    from flink_tpu.core.functions import as_key_selector
    from flink_tpu.core.state import AggregatingStateDescriptor
    from flink_tpu.ops.device_agg import SumAggregate
    from flink_tpu.streaming.elements import RecordBatch
    from flink_tpu.streaming.harness import OneInputStreamOperatorTestHarness
    from flink_tpu.streaming.window_operator import WindowOperator
    from flink_tpu.streaming.windowing import TumblingEventTimeWindows

    rng = np.random.default_rng(37)
    keys64 = rng.integers(0, n_keys, n_events).astype(np.int64)
    vals64 = rng.integers(0, 100, n_events).astype(np.int64)
    ts64 = np.arange(n_events, dtype=np.int64)
    vals_f = vals64.astype(np.float64)
    wstart = ts64 - ts64 % window_ms
    ref = {}
    for k, w, v in zip(keys64.tolist(), wstart.tolist(), vals64.tolist()):
        ref[(k, w)] = ref.get((k, w), 0) + v
    expected = sorted((k, w, float(s)) for (k, w), s in ref.items())

    class _KVSum(SumAggregate):
        def __init__(self):
            super().__init__(np.float32)

        def extract_value(self, value):
            return value[1] if isinstance(value, tuple) else value

    def one_pass(backend, batch_fires):
        op = WindowOperator(
            TumblingEventTimeWindows.of(window_ms),
            AggregatingStateDescriptor("bench-fire-sum", _KVSum()),
            window_function=lambda k, w, vs: [(k, w.start, float(v))
                                              for v in vs])
        op.batch_fires = batch_fires
        h = OneInputStreamOperatorTestHarness(
            op, key_selector=as_key_selector(0), state_backend=backend)
        h.open()
        t0 = time.perf_counter()
        for i in range(0, n_events, chunk):
            h.process_batch(RecordBatch(
                {"f0": keys64[i:i + chunk], "f1": vals_f[i:i + chunk]},
                ts=ts64[i:i + chunk]))
            h.process_watermark(int(ts64[min(i + chunk, n_events) - 1]))
        h.process_watermark(1 << 60)
        elapsed = time.perf_counter() - t0
        got = sorted((int(k), int(w), float(v))
                     for k, w, v in h.extract_output_values())
        assert got == expected, \
            f"{backend} {'batched' if batch_fires else 'per-timer'} " \
            f"fire path diverged ({len(got)} vs {len(expected)} windows)"
        assert op.boxed_fallbacks == 0 and op.columnar_rows == n_events, \
            (op.boxed_fallbacks, op.columnar_fallback_reason)
        return len(expected) / elapsed

    rates = {}
    for backend in ("tpu", "heap"):
        one_pass(backend, True)    # warm: device tables, jit, dispatch
        one_pass(backend, False)
        batch_rate = row_rate = 0.0
        for _rep in range(3):
            row_rate = max(row_rate, one_pass(backend, False))
            batch_rate = max(batch_rate, one_pass(backend, True))
        rates[backend] = (batch_rate, row_rate)
        log(f"[bench] state_chain_fires[{backend}]: batch "
            f"{batch_rate/1e3:.1f} k fires/s, per-timer "
            f"{row_rate/1e3:.1f} k fires/s, ratio "
            f"{batch_rate/row_rate:.2f}x")
    batch_rate, row_rate = rates["tpu"]
    assert batch_rate >= 2.0 * row_rate, \
        f"batched window fires only {batch_rate/row_rate:.2f}x over " \
        f"per-timer on the tpu backend (acceptance floor is 2x)"
    return batch_rate, row_rate, {
        "heap_batch_fires_per_sec": round(rates["heap"][0]),
        "heap_row_fires_per_sec": round(rates["heap"][1]),
        "heap_vs_row": round(rates["heap"][0] / rates["heap"][1], 2),
        "window_fires": len(expected),
    }


def chaos_smoke() -> int:
    """One seeded chaos run per executor: injected storage failures,
    lost checkpoint acks, and a task crash must leave the output
    multiset identical to a fault-free run (exactly-once)."""
    from flink_tpu.runtime.chaos import run_chaos_case

    failures = 0
    for executor in ("local", "minicluster"):
        log(f"[chaos] {executor}: seeded fault schedule ...")
        t0 = time.perf_counter()
        r = run_chaos_case(executor, seed=7)
        ok = r["chaos"] == r["baseline"]
        failures += 0 if ok else 1
        log(f"[chaos] {executor}: exactly_once={'OK' if ok else 'BROKEN'} "
            f"restarts={r['restarts']} "
            f"timeouts={r['counters'].get('checkpoint_timeouts', 0)} "
            f"retries={r['counters'].get('retries_total', 0)} "
            f"({time.perf_counter() - t0:.1f}s)")
    print(json.dumps({"chaos_smoke": "pass" if failures == 0 else "fail"}))
    return 1 if failures else 0


def main():
    # --trace: attach the tracer for the whole run and write the
    # Chrome trace-event file next to the report, so perf PRs can ship
    # kernel-level evidence for every headline number
    argv = sys.argv[1:]
    trace = "--trace" in argv
    if trace:
        argv = [a for a in argv if a != "--trace"]
        from flink_tpu.runtime import tracing
        tracing.get_tracer().enabled = True
    # --device-ledger: enable the device telemetry plane for the whole
    # run and ship its payload (per-tag transfer ledger, per-kernel
    # attribution, exchange phase breakdown, fire/flush counters) into
    # bench_report.json under "device_ledger"
    device_ledger = "--device-ledger" in argv
    if device_ledger:
        argv = [a for a in argv if a != "--device-ledger"]
        from flink_tpu.runtime.device_stats import get_telemetry
        get_telemetry().enable()
    # --flame: attach the sampling profiler for the whole run and ship
    # the folded collapsed-stack profile (per-vertex tries, on/off-CPU
    # split) into bench_report.json under "flame"
    flame = "--flame" in argv
    if flame:
        argv = [a for a in argv if a != "--flame"]
        from flink_tpu.runtime.profiler import get_profiler
        get_profiler().enable()
    # --chaos-smoke: one seeded chaos case per executor (the
    # tests/test_chaos.py harness), exits non-zero if exactly-once
    # breaks — a quick fault-tolerance gate without the full suite
    if "--chaos-smoke" in argv:
        sys.exit(chaos_smoke())
    # single-config runs MERGE into the existing report instead of
    # clobbering the other configs' results
    results = {}
    if argv:
        try:
            with open("bench_report.json") as f:
                results = json.load(f)
        except (OSError, ValueError):
            pass
    suite = [
        ("wordcount", bench_wordcount),
        ("wordcount_str", bench_wordcount_str),
        ("hll", bench_hll),
        ("hll_10m", bench_hll_10m),
        ("hll_scatter", bench_hll_scatter),
        ("hll_device", bench_hll_device),
        ("sliding_quantile", bench_sliding_quantile),
        ("session_cm", bench_session_cm),
        ("generic_agg", bench_generic_agg),
        ("cep", bench_cep),
        ("cep_followed_by", bench_cep_followed_by),
        ("sql", bench_sql),
        ("sql_join", bench_sql_join),
        ("shuffle", bench_shuffle),
        ("columnar_chain", bench_columnar_chain),
        ("fused_chain", bench_fused_chain),
        ("state_chain", bench_state_chain),
        ("state_chain_fires", bench_state_chain_fires),
    ]
    # diagnostics: runnable by name, excluded from the default suite
    # (they document measured LIMITS, not headline configs)
    extras = [("generic_agg_minimal", bench_generic_agg_minimal)]
    only = argv[0] if argv else None
    if only is not None and only in {n for n, _ in extras}:
        suite = extras
    elif only is not None and only not in {n for n, _ in suite}:
        log(f"[bench] unknown config {only!r}; "
            f"choose from {[n for n, _ in suite + extras]}")
        sys.exit(2)
    for name, fn in suite:
        if only and name != only:
            continue
        log(f"[bench] running {name} ...")
        if flame:
            # benchmarks drive kernels from this thread directly (no
            # executor loop to stamp scopes), so attribute the whole
            # pattern to a synthetic vertex — the folded profile then
            # reads `<pattern>;frames...`
            import types as _types
            from flink_tpu.runtime.profiler import get_profiler
            get_profiler().set_scope(_types.SimpleNamespace(
                profiler_scope=("bench", f"0_{name}", 0)))
        t0 = time.perf_counter()
        try:
            out = fn()
            tpu_rate, base_rate = out[0], out[1]
            extra = out[2] if len(out) > 2 else {}
        except Exception as e:  # noqa: BLE001 — one config must never
            # take down the suite (the driver needs the headline line)
            log(f"[bench] {name} FAILED: {type(e).__name__}: {e}")
            results[name] = {"error": f"{type(e).__name__}: {e}",
                             "wall_s": round(time.perf_counter() - t0, 1)}
            continue
        results[name] = {
            "tpu_events_per_sec": round(tpu_rate),
            "baseline_events_per_sec": round(base_rate),
            "vs_baseline": round(tpu_rate / base_rate, 2),
            "wall_s": round(time.perf_counter() - t0, 1),
            **extra,
        }
        log(f"[bench] {name}: tpu {tpu_rate/1e6:.2f} M ev/s, "
            f"C++ baseline {base_rate/1e6:.2f} M ev/s, "
            f"ratio {tpu_rate/base_rate:.2f}x")

    if trace:
        from flink_tpu.runtime import tracing
        tracer = tracing.get_tracer()
        n = tracer.write_chrome_trace("bench_trace.json")
        log(f"[bench] trace: {n} events -> bench_trace.json")
        top_spans = sorted(tracer.stats().items(),
                           key=lambda kv: -kv[1]["total_ms"])[:20]
        for name, s in top_spans:
            log(f"[bench]   span {name}: n={s['count']} "
                f"total={s['total_ms']:.1f}ms self={s['self_ms']:.1f}ms")
        for name, s in sorted(tracing.kernel_stats().items(),
                              key=lambda kv: -kv[1]["total_ms"])[:20]:
            log(f"[bench]   native.{name}: n={s['dispatches']} "
                f"total={s['total_ms']:.1f}ms p99={s['p99_ms']:.3f}ms")
        # lane-merged view: MiniCluster configs run worker threads in
        # this process, so the merged trace shows one lane per worker
        merged = tracing.build_cluster_trace(tracer.lane_buffers())
        lanes = (merged.get("metadata") or {}).get("lanes") or {}
        with open("bench_trace_cluster.json", "w") as f:
            json.dump(merged, f)
        log(f"[bench] cluster trace: {len(lanes)} lane(s) -> "
            f"bench_trace_cluster.json"
            + (f"; {tracer.dropped} events dropped at the ring limit"
               if tracer.dropped else ""))

    if device_ledger:
        from flink_tpu.runtime.device_stats import get_telemetry
        ledger = get_telemetry().payload()
        results["device_ledger"] = ledger
        tot, ctr = ledger["totals"], ledger["counters"]
        log(f"[bench] device ledger: h2d {tot['h2d']['bytes']:,} B / "
            f"{tot['h2d']['total_ms']:.1f} ms, "
            f"d2h {tot['d2h']['bytes']:,} B / "
            f"{tot['d2h']['total_ms']:.1f} ms; "
            f"flushes {ctr['flushes']:,}, fire reads "
            f"{ctr['fire_reads']:,}, fire/flush "
            f"{ctr['fire_flush_ratio']:.2f}")
        for tag, ph in (ledger.get("exchange_phases") or {}).items():
            log(f"[bench]   exchange {tag}: rounds={ph['rounds']} "
                f"pack={ph['pack_ms']:.1f}ms h2d={ph['h2d_ms']:.1f}ms "
                f"collective={ph['collective_ms']:.1f}ms "
                f"d2h={ph['d2h_ms']:.1f}ms")

    if flame:
        from flink_tpu.runtime.profiler import collapsed_lines, get_profiler
        profiler = get_profiler()
        profiler.disable()
        export = profiler.export()
        folded = collapsed_lines(export)
        results["flame"] = {
            "hz": export["hz"],
            "samples": export["samples"],
            "dropped": export["dropped"],
            "folded": folded,
        }
        log(f"[bench] flame: {export['samples']['total']} samples "
            f"({export['samples']['on_cpu']} on-CPU / "
            f"{export['samples']['off_cpu']} off-CPU / "
            f"{export['samples']['backpressured']} backpressured), "
            f"{len(folded)} folded stacks"
            + (f"; {export['dropped']} samples truncated at the node "
               f"cap" if export["dropped"] else ""))

    with open("bench_report.json", "w") as f:
        json.dump(results, f, indent=2)
    log(f"[bench] report: {json.dumps(results)}")

    # headline = config #2 measured THIS run; fall back to a config
    # from this run only (a merged-in stale entry must not become the
    # stdout headline).  A config that raised fails the run: no
    # headline, exit 1.
    ran = {n for n, _ in suite if only is None or n == only}
    ok = {n: r for n, r in results.items()
          if "error" not in r and n in ran}
    head = ok.get("hll") or (next(iter(ok.values())) if ok else None)
    if len(ok) < len(ran):
        head = None
    if head is None:
        print(json.dumps({"metric": "windowed_hll_events_per_sec",
                          "value": 0, "unit": "events/s",
                          "vs_baseline": 0.0}))
        sys.exit(1)
    print(json.dumps({
        "metric": "windowed_hll_events_per_sec",
        "value": head["tpu_events_per_sec"],
        "unit": "events/s",
        "vs_baseline": head["vs_baseline"],
    }))


if __name__ == "__main__":
    main()

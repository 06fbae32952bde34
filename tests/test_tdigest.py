"""``TDigestAggregate`` (a merging t-digest, ``flink_tpu/ops/tdigest.py``)
against its plain reference (``tests/tdigest_reference.py``: numpy,
float64, nothing of the system): the device programs in both forms of
the append, a digest that overflows its buffer several times inside one
flush, micro-batch cuts that must not change a bit, the heap and tpu
backends running the same job, the centroid bound, a bfloat16 variant
that the comparison must refuse, and the default door that refuses
the aggregate over sliding windows."""

import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tdigest_reference as reference
import test_sliding_quantiles as sq
from flink_tpu.core.config import Configuration
from flink_tpu.ops import tdigest
from flink_tpu.ops.tdigest import TDigestAggregate
from flink_tpu.state.stats import STATE_STATS, agg_counters

DELTA, BUFFER, CENTROIDS = 100, 512, 128
QUANTILES = (0.5, 0.99)


def check_config(key_space):
    return {"slide_ms": sq.SLIDE_MS, "window_size_ms": sq.SIZE_MS,
            "key_space": key_space, "quantiles": list(QUANTILES),
            "delta": DELTA, "buffer": BUFFER, "centroids": CENTROIDS,
            "estimate_tolerance": 1e-4, "rank_bound_units": 1}


def stream(seed, n, slots, hot=0.0):
    """Slots (one taking a share ``hot`` of the rows) and lognormal
    float32 values."""
    rng = np.random.default_rng(seed)
    s = rng.integers(0, slots, n).astype(np.int32)
    s[rng.random(n) < hot] = slots // 2
    v = np.exp(rng.normal(3.0, 1.0, n)).astype(np.float32)
    return s, v


def run_updates(agg, slots, values, capacity, batch, form="xla",
                monkeypatch=None):
    """The stream through ``update`` in batches of ``batch`` rows (the
    last padded to the same shape with masked rows)."""
    if form == "kernels":
        # the kernels themselves, interpreted: what the TPU branches run
        monkeypatch.setattr(
            tdigest, "_append",
            lambda buf, s, c, v: tdigest._append_tiles_tpu(
                buf, s, c, v, interpret=True))
        monkeypatch.setattr(
            tdigest, "_pass",
            lambda m_t, w_t, nf, h, s, c_out: tdigest._pass_tpu(
                m_t, w_t, nf, h, s, c_out, interpret=True))
    update = jax.jit(agg.counted_update)
    state = agg.init_state(capacity)
    counts = np.zeros(len(agg.counters), np.int64)
    for i in range(0, len(slots), batch):
        s = np.zeros(batch, np.int32)
        v = np.zeros(batch, np.float32)
        m = np.zeros(batch, bool)
        part = slice(i, i + batch)
        k = len(slots[part])
        s[:k], v[:k], m[:k] = slots[part], values[part], True
        zero = jnp.zeros(batch, jnp.uint32)
        state, c = update(state, jnp.asarray(s), jnp.asarray(v), zero, zero,
                          jnp.asarray(m))
        counts += np.asarray(c)
    return state, counts


def answers(agg, state, capacity):
    return np.asarray(jax.jit(agg.result)(
        state, jnp.arange(capacity, dtype=jnp.int32)))


def reference_answers(slots, values):
    keys, n, est, flags, _ = reference.digest_streams(
        slots.astype(np.int64), values, DELTA, BUFFER, CENTROIDS, QUANTILES)
    return keys, n, est, flags


# ---- the device programs against the reference --------------------------

@pytest.mark.parametrize("form", ["xla", "kernels"])
@pytest.mark.parametrize("seed, rows, slots, hot, batch", [
    (1, 3000, 48, 0.4, 256),     # a hot digest compressing across flushes
    (2, 2600, 8, 0.9, 2048),     # one overflowing B four times in a flush
    (3, 700, 300, 0.0, 128),     # small digests: appends alone
])
def test_update_and_result_match_the_reference(form, seed, rows, slots, hot,
                                               batch, monkeypatch):
    agg = TDigestAggregate()
    capacity = 8 * (-(-slots // 8))
    s, v = stream(seed, rows, slots, hot)
    state, counts = run_updates(agg, s, v, capacity, batch, form,
                                monkeypatch)
    keys, n, est, flags = reference_answers(s, v)
    got = answers(agg, state, capacity)[keys]
    rel = np.abs(got - est) / est
    assert rel[~flags].max() <= 1e-5
    assert np.asarray(state["count"])[keys].tolist() == n.tolist()
    # the compresses the update counted are those the count implies
    assert counts[0] == int(((n - 1) // BUFFER).sum())
    if hot >= 0.9:
        assert n.max() > 4 * BUFFER and counts[1] >= 4


@pytest.mark.parametrize("digests, steps, c_out", [
    (37, 640, 128),     # an ingest block: centroids and a full buffer
    (200, 8, 8),        # a short class of the fire
    (5, 1280, 128),     # a merge: two digests' centroids and buffers
])
def test_the_pass_kernel_is_the_loop_bit_for_bit(digests, steps, c_out):
    rng = np.random.default_rng(steps)
    w = (rng.random((digests, steps)) < 0.8) \
        * rng.integers(1, 4, (digests, steps))
    m = np.exp(rng.normal(3.0, 1.0, (digests, steps)))
    m = np.sort(np.where(w > 0, m, np.inf), axis=1).astype(np.float32)
    w = np.take_along_axis(w, np.argsort(np.where(w > 0, 0, 1), axis=1,
                                         kind="stable"), axis=1)
    args = (jnp.asarray(m.T), jnp.asarray(w.T.astype(np.int32)),
            jnp.asarray(w.sum(axis=1).astype(np.float32)))
    agg = TDigestAggregate()
    loop = tdigest._pass_loop(*args, agg.h, agg.s, c_out)
    kernel = tdigest._pass_tpu(*args, agg.h, agg.s, c_out, interpret=True)
    for a, b in zip(loop, kernel):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(loop[1]).sum(axis=1) == w.sum(axis=1)).all()


def test_a_hot_digest_overflows_several_times_inside_one_flush():
    agg = TDigestAggregate()
    s, v = stream(4, 1800, 8)
    s[:] = 3
    state, counts = run_updates(agg, s, v, 8, 2048)
    assert counts.tolist() == [3, 3, 0]    # 1800 values: three compresses
    fill = int(np.asarray(state["fill"])[3])
    assert fill == 1800 - 3 * BUFFER
    keys, n, est, flags = reference_answers(s, v)
    got = answers(agg, state, 8)[3]
    assert np.allclose(got, est[0], rtol=1e-5, atol=0)


@pytest.mark.parametrize("batch", [1, 7, 1024])
def test_micro_batch_cuts_leave_every_bit(batch):
    agg = TDigestAggregate()
    s, v = stream(5, 1400, 6, 0.6)
    whole, _ = run_updates(agg, s, v, 8, 2048)
    cut, _ = run_updates(agg, s, v, 8, batch)
    fill = np.asarray(whole["fill"])
    assert np.array_equal(fill, np.asarray(cut["fill"]))
    for name in ("mean", "weight", "count", "min", "max"):
        assert np.array_equal(np.asarray(whole[name]), np.asarray(cut[name]))
    cells = np.arange(BUFFER)[None, :] < fill[:, None]
    assert np.array_equal(np.asarray(whole["buf"])[cells],
                          np.asarray(cut["buf"])[cells])
    assert np.array_equal(answers(agg, whole, 8), answers(agg, cut, 8),
                          equal_nan=True)


def test_merge_slots_compresses_the_union():
    """Both digests' centroids, then both buffers, compressed into the
    destination, whose buffer empties."""
    agg = TDigestAggregate()
    s, v = stream(6, 1500, 2, 0.0)
    state, _ = run_updates(agg, s, v, 8, 256)
    merged = jax.jit(agg.merge_slots)(state, jnp.array([0], jnp.int32),
                                      jnp.array([1], jnp.int32))
    # the reference compresses the same union, taken from the state
    fill = np.asarray(state["fill"])
    weight = np.asarray(state["weight"])[:2].astype(np.int64)
    mean = np.asarray(state["mean"])[:2].astype(np.float64)
    live = weight > 0
    ref = reference.Digests(1, DELTA, CENTROIDS + 2 * BUFFER)
    ref.mean[0, :live.sum()] = mean[live]
    ref.weight[0, :live.sum()] = weight[live]
    buf = np.concatenate([np.asarray(state["buf"])[k, :fill[k]]
                          for k in (0, 1)]).astype(np.float64)
    ref.compress(np.array([0]), buf[None, :], np.array([len(buf)]))
    est = ref.quantiles(np.array([len(v)]), np.array([v.min()], np.float64),
                        np.array([v.max()], np.float64), QUANTILES)
    assert int(np.asarray(merged["count"])[0]) == len(v)
    assert int(np.asarray(merged["fill"])[0]) == 0
    assert np.allclose(answers(agg, merged, 8)[0], est[0], rtol=1e-5,
                       atol=0)


# ---- the centroid bound --------------------------------------------------

@pytest.mark.parametrize("order", ["arrival", "ascending", "descending"])
def test_a_digest_holds_at_most_delta_centroids(order):
    rng = np.random.default_rng(7)
    v = np.exp(rng.normal(3.0, 1.0, 20000)).astype(np.float32)
    if order != "arrival":
        v = np.sort(v)[::1 if order == "ascending" else -1]
    digest = reference.digest_of(v, DELTA, BUFFER, CENTROIDS)
    weight = digest.weight[0]
    real = weight > 0
    assert 1 < real.sum() <= DELTA
    # every centroid the last compress merged spans at most 1 in k1
    n = weight.sum()
    edges = np.concatenate([[0], np.cumsum(weight[real])]) / n

    def k1(q):
        return DELTA / (2 * math.pi) * np.arcsin(2 * np.clip(q, 0, 1) - 1)

    span = k1(edges[1:]) - k1(edges[:-1])
    merged = digest.folded[0][real] > 1
    assert merged.any() and (span[merged] <= 1 + 1e-9).all()
    # and the device's digest of the same stream holds as many
    agg = TDigestAggregate()
    state, _ = run_updates(agg, np.zeros(len(v), np.int32), v, 8, 4096)
    state = jax.jit(agg.merge_slots)(state, jnp.array([0], jnp.int32),
                                     jnp.array([1], jnp.int32))
    assert 1 < int((np.asarray(state["weight"])[0] > 0).sum()) <= DELTA


@pytest.mark.parametrize("q", [0.5, 0.99, 0.999, 0.9999, 0.99999,
                               0.12345678, 1.0])
def test_ranks_are_exact_where_count_times_numerator_passes_int32(q):
    """ceil(q n) of counts up to int32's largest, where n times q's
    numerator does not fit int32 (p99.99 from 214,749 values on)."""
    n = np.array([0, 1, 2, 99, 214_748, 214_749, 300_000, 2_147_483,
                  1 << 30, 2**31 - 1], np.int64)
    agg = TDigestAggregate(quantiles=(q,))
    (frac,) = agg._q_fractions
    got = jax.jit(lambda n: tdigest._ceil_mul_div(
        n, frac.numerator, frac.denominator))(jnp.asarray(n, jnp.int32))
    assert np.maximum(np.asarray(got), 1).tolist() \
        == reference.ranks(n, q).tolist()


def test_a_big_digest_answers_p9999_from_its_top():
    """Two centroids of 150,000 values: p99.99 interpolates between the
    upper centroid and the max by the written rule (it answered the min
    while ceil(q n) was computed as n * 9999 in int32)."""
    agg = TDigestAggregate(quantiles=(0.5, 0.9999))
    state = agg.init_state(8)
    half = 150_000
    state = {**state,
             "mean": state["mean"].at[0, :2].set(jnp.array([10.0, 20.0])),
             "weight": state["weight"].at[0, :2].set(half),
             "count": state["count"].at[0].set(2 * half),
             "min": state["min"].at[0].set(0.0),
             "max": state["max"].at[0].set(30.0)}
    got = np.asarray(jax.jit(agg.result)(state, jnp.array([0], jnp.int32)))
    t = reference.ranks(np.array([2 * half]), 0.9999)[0] - 0.5
    want = 20.0 + 10.0 * (t - 1.5 * half) / (0.5 * half)
    assert got[0, 1] == pytest.approx(want, rel=1e-6)
    assert got[0, 0] == pytest.approx(10.0 + 10.0 * (half - 0.5 - 0.5 * half)
                                      / half, rel=1e-6)


@pytest.mark.parametrize("rows", [5, 8])
def test_a_fire_counts_the_entries_of_its_real_rows_on_the_device(rows):
    """`counted_result` sums the entries (centroids and buffered values)
    of its first `rows` digests; the padding after them repeats the
    first slot, as a fire's tile does, and is not counted."""
    agg = TDigestAggregate()
    s, v = stream(9, 3000, 8, 0.3)
    state, _ = run_updates(agg, s, v, 8, 256)
    slots = np.array([3, 1, 4, 0, 5, 6, 2, 7], np.int32)[:rows]
    padded = np.concatenate([slots, np.full(8 - rows, slots[0], np.int32)])
    res, counts = jax.jit(agg.counted_result)(state, jnp.asarray(padded),
                                              np.int32(rows))
    held = (np.asarray(state["weight"]) > 0).sum(axis=1) \
        + np.asarray(state["fill"])
    assert np.asarray(counts).tolist() == [0, 0, int(held[slots].sum())]
    np.testing.assert_array_equal(np.asarray(res),
                                  answers(agg, state, 8)[padded])


def test_a_quantile_the_ranks_cannot_hold_is_refused():
    with pytest.raises(ValueError, match="2\\^29"):
        TDigestAggregate(quantiles=(0.1234567891,))


# ---- what the comparison refuses ----------------------------------------

def test_bfloat16_means_fail_the_estimate_tolerance():
    """The nearest precision below float32 for the centroid means: the
    comparison of (b) must see it."""
    class Bf16Means(TDigestAggregate):
        mean_dtype = np.dtype(jnp.bfloat16)

    agg = Bf16Means()
    s, v = stream(8, 6000, 6, 0.0)
    state, _ = run_updates(agg, s, v, 8, 1024)
    got = answers(agg, state, 8)
    checker = reference.DigestChecker(check_config(8))
    keys = np.arange(6, dtype=np.int64)
    checker.add_window(0, s.astype(np.int64), v.astype(np.float64), keys,
                       got[:6])
    problems, facts = checker.verdict()
    assert checker.failed > 0 and facts["max_rel_err"] > 1e-4
    # by the estimate's limit: at this size the rank bound still holds
    # (at the cell's load bfloat16 means break it too)
    assert facts["max_rank_err_units"] <= 1
    # float32 means pass the same comparison
    agg = TDigestAggregate()
    state, _ = run_updates(agg, s, v, 8, 1024)
    checker = reference.DigestChecker(check_config(8))
    checker.add_window(0, s.astype(np.int64), v.astype(np.float64), keys,
                       answers(agg, state, 8)[:6])
    assert checker.failed == 0


def test_the_reference_twins_are_byte_for_byte_one_file():
    root = pathlib.Path(__file__).resolve().parent.parent
    assert (root / "tests" / "tdigest_reference.py").read_bytes() == (
        root / "benchmark" / "references" / "tdigest_sliding.py").read_bytes()


# ---- through env.execute() -----------------------------------------------

class ValueDigest(TDigestAggregate):
    def extract_value(self, value):
        return value[1]

    def extract_column(self, values):
        return values[1]


N_KEYS, PER_SLIDE, SLIDES = 40, 256, 13
EVENTS = sq.make_events(44, N_KEYS, PER_SLIDE, SLIDES)


@pytest.mark.parametrize("chunk", [64, 100, 256])
def test_the_job_on_the_tpu_backend_passes_the_comparison(chunk):
    before = agg_counters()
    op, results = sq.run_job(EVENTS, chunk, agg=ValueDigest())
    verdict = reference.check(check_config(N_KEYS),
                              sq.emitted_panes(EVENTS, PER_SLIDE), results)
    assert verdict["problems"] == [] and verdict["failed"] == 0
    assert sorted(results) == [w * sq.SLIDE_MS
                               for w in range(SLIDES + sq.PANES - 1)]
    assert op.boxed_fallbacks == 0
    after = agg_counters()
    # a hot key's windows hold more than B values: ingest compressed
    assert after["tdigest_ingest_compresses"] \
        > before.get("tdigest_ingest_compresses", 0)
    # every fired digest held one entry at least
    assert after["tdigest_fired_entries"] \
        - before.get("tdigest_fired_entries", 0) \
        >= sum(len(r[0]) for r in results.values())


@pytest.mark.parametrize("microbatch", [7, 64, 1024])
def test_the_heap_and_tpu_backends_emit_the_same_rows(microbatch):
    events = sq.make_events(45, 12, 96, 12)
    _, tpu = sq.run_job(events, 80, agg=ValueDigest(),
                        configuration=Configuration().set(
                            "state.backend.tpu.microbatch-size", microbatch))
    _, heap = sq.run_job(events, 80, backend="heap", agg=ValueDigest())
    assert sorted(tpu) == sorted(heap)
    for window in tpu:
        a = sorted(zip(*[c.tolist() for c in tpu[window]]))
        b = sorted(zip(*[c.tolist() for c in heap[window]]))
        assert a == b


def test_the_default_door_refuses_sliding_windows_and_names_the_route():
    from flink_tpu.streaming.datastream import StreamExecutionEnvironment
    from flink_tpu.streaming.sources import CollectSink
    from flink_tpu.streaming.windowing import SlidingEventTimeWindows
    env = StreamExecutionEnvironment()
    (env.add_source(sq.EventSource(*EVENTS, 64), name="events")
     .key_by(0)
     .window(SlidingEventTimeWindows.of(sq.SIZE_MS, sq.SLIDE_MS))
     .aggregate(ValueDigest(), window_function=sq.emit_row)
     .add_sink(CollectSink()))
    with pytest.raises(Exception, match="disable_device_operator"):
        env.execute("tdigest-default-door")


def test_state_stats_keep_the_counts_by_name():
    before = dict(STATE_STATS.agg_counters)
    STATE_STATS.note_agg_counts(("x_test",), [3])
    assert STATE_STATS.agg_counters["x_test"] == before.get("x_test", 0) + 3


@pytest.mark.parametrize("rows, capacity, width, form", [
    (16384, 1 << 19, 512, "tiles"),    # the cell's flush
    (32768, 1 << 19, 512, "tiles"),    # two batches and a chunk pending
    (65536, 1 << 19, 512, "cells"),    # past the kernel's scalars
    (1, 8, 512, "cells"),              # the scalar twin
    (256, 12, 512, "cells"),           # no whole tiles of slots
    (256, 16, 500, "cells"),           # nor of cells
])
def test_the_append_rule_follows_the_shapes(rows, capacity, width, form):
    assert tdigest.append_form(rows, capacity, width) == form

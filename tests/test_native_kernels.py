"""Property tests for the native log-engine kernels against pure-python
references: radix sort grouping over adversarial key patterns, dedup
correctness, session splitting, and the sum table's exactness."""

import numpy as np
import pytest

import flink_tpu.native as nat

pytestmark = pytest.mark.skipif(not nat.available(),
                                reason="native runtime unavailable")


_EXTREMES = np.array([0, 1, 2 ** 63 - 1, 2 ** 64 - 1,
                      0x9E3779B97F4A7C15], np.uint64)

KEY_PATTERNS = [
    ("uniform_small", lambda rng, n: rng.integers(0, 50, n)),
    ("uniform_wide", lambda rng, n: rng.integers(0, 2 ** 63, n)),
    ("all_equal", lambda rng, n: np.full(n, 7)),
    # index-select keeps the exact uint64 bit patterns (choice over a
    # python list would round-trip through float64 and corrupt them)
    ("extremes", lambda rng, n: _EXTREMES[rng.integers(0, 5, n)]),
    ("high_bits_only", lambda rng, n: rng.integers(0, 4, n).astype(
        np.uint64) << np.uint64(60)),
]
_SEED = {name: i * 1000 + 17 for i, (name, _) in enumerate(KEY_PATTERNS)}


@pytest.mark.parametrize("name,gen", KEY_PATTERNS)
def test_sum_log_fire_matches_python(name, gen):
    rng = np.random.default_rng(_SEED[name])
    n = 5000
    keys = gen(rng, n).astype(np.uint64)
    vals = rng.random(n)
    ok, osum = nat.sum_log_fire(keys, vals)
    want = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        want[k] = want.get(k, 0.0) + v
    got = dict(zip(ok.tolist(), osum.tolist()))
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-9)
    # key-sorted output
    assert np.all(np.diff(ok.astype(np.uint64)) > 0) or len(ok) <= 1


@pytest.mark.parametrize("name,gen", KEY_PATTERNS)
def test_hll_compact_matches_python(name, gen):
    rng = np.random.default_rng(_SEED[name] + 1)
    n = 4000
    keys = gen(rng, n).astype(np.uint64)
    regs = rng.integers(0, 1024, n).astype(np.uint16)
    ranks = rng.integers(1, 40, n).astype(np.uint8)
    ck, cr, crk, ends = nat.hll_log_compact(keys, regs, ranks, 10)
    want = {}
    for k, r, rk in zip(keys.tolist(), regs.tolist(), ranks.tolist()):
        cur = want.setdefault(k, {})
        cur[r] = max(cur.get(r, 0), rk)
    got = {}
    for k, r, rk in zip(ck.tolist(), cr.tolist(), crk.tolist()):
        got.setdefault(k, {})[r] = rk
    assert got == want
    # ends partition the cells by key
    assert ends[-1] == len(ck)
    assert np.all(np.diff(ends) > 0)


def test_empty_inputs():
    e64 = np.empty(0, np.uint64)
    ok, osum = nat.sum_log_fire(e64, np.empty(0))
    assert len(ok) == 0
    ck, cr, crk, ends = nat.hll_log_compact(
        e64, np.empty(0, np.uint16), np.empty(0, np.uint8), 10)
    assert len(ck) == 0 and len(ends) == 0


def test_session_fire_negative_timestamps():
    """Signed timestamps order correctly under the radix (sign-bit
    bias): a session spanning negative->positive time stays one run."""
    keys = np.array([5, 5, 5], np.uint64)
    ts = np.array([-1500, -800, -100], np.int64)
    ok, os_, oe, ot, retained = nat.session_log_fire(
        keys, ts, np.ones(3, np.float32),
        np.array([1, 2, 3], np.uint64), 1000, 10_000, 2, 32)
    assert len(ok) == 1
    assert (int(os_[0]), int(oe[0]), float(ot[0])) == (-1500, 900, 3.0)
    assert len(retained[0]) == 0


def test_session_fire_retains_open_sessions():
    keys = np.array([1, 1, 2], np.uint64)
    ts = np.array([0, 100, 5000], np.int64)
    ok, os_, oe, ot, retained = nat.session_log_fire(
        keys, ts, np.ones(3, np.float32),
        np.array([9, 9, 9], np.uint64), 500, 4000, 2, 32)
    # key 1's session [0, 600) closed; key 2's [5000, 5500) still open
    assert [int(k) for k in ok] == [1]
    rk, rt, rw, rv = retained
    assert rk.tolist() == [2] and rt.tolist() == [5000]


def test_qsketch_fire_quantile_positions():
    # one key, bucket counts chosen so q50/q99 land in known buckets
    keys = np.zeros(100, np.uint64)
    buckets = np.concatenate([np.full(50, 3), np.full(49, 7),
                              np.full(1, 9)]).astype(np.uint16)
    import math
    log_gamma = math.log(1.1)
    ok, q = nat.qsketch_log_fire(keys, buckets, 16, [0.5, 0.99],
                                 log_gamma, 0, 1.0)
    assert len(ok) == 1
    b50 = math.exp((3 - 0.5) * log_gamma)
    b99 = math.exp((7 - 0.5) * log_gamma)
    assert q[0, 0] == pytest.approx(b50, rel=1e-9)
    assert q[0, 1] == pytest.approx(b99, rel=1e-9)


def test_sumtab_growth_from_small():
    """The dense table starts tiny and grows; sums survive rehashes."""
    t = nat.NativeSumTable(16)
    rng = np.random.default_rng(31)
    keys = rng.integers(0, 3000, 30_000).astype(np.uint64)
    vals = rng.random(30_000)
    consumed = t.ingest(keys, vals, 1 << 19)
    assert consumed == len(keys)
    ek, es = t.export()
    want = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        want[k] = want.get(k, 0.0) + v
    got = dict(zip(ek.tolist(), es.tolist()))
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-9)


# ---- string interner -------------------------------------------------------

def test_interner_dense_first_seen_ids():
    it = nat.NativeStringInterner()
    a = np.asarray(["b", "a", "b", "c", "a"])
    ids, first = it.intern(a)
    assert ids.tolist() == [0, 1, 0, 2, 1]
    assert a[first].tolist() == ["b", "a", "c"]
    assert it.n == 3


def test_interner_width_independent():
    """The same word must intern to the same id whatever fixed width
    its batch happened to have."""
    it = nat.NativeStringInterner()
    ids1, _ = it.intern(np.asarray(["cat", "x"]))          # <U3
    ids2, _ = it.intern(np.asarray(["cat", "elephantine"]))  # <U11
    assert ids1[0] == ids2[0]
    assert it.n == 3


def test_interner_collision_exactness():
    """Grouping is content-exact: a large vocabulary interns with no
    id collisions and round-trips through the directory."""
    rng = np.random.default_rng(3)
    vocab = np.asarray([f"w{i}suffix{i % 97}" for i in range(20_000)])
    order = rng.permutation(40_000) % 20_000
    batch = vocab[order]
    it = nat.NativeStringInterner()
    ids, first = it.intern(batch)
    assert it.n == 20_000
    directory = batch[first]
    # every occurrence maps back to its own word
    assert (directory[ids.astype(np.int64)] == batch).all()


def test_interner_unicode_and_bytes():
    it = nat.NativeStringInterner()
    ids, _ = it.intern(np.asarray(["héllo", "日本語", "héllo"]))
    assert ids.tolist() == [0, 1, 0]
    itb = nat.NativeStringInterner()
    idsb, _ = itb.intern(np.asarray([b"ab", b"cd", b"ab"]))
    assert idsb.tolist() == [0, 1, 0]


def test_interner_empty_strings_and_restore_order():
    it = nat.NativeStringInterner()
    a = np.asarray(["", "x", ""])
    ids, first = it.intern(a)
    assert ids.tolist() == [0, 1, 0]
    # restore contract: re-interning the directory in order on a fresh
    # interner reproduces the ids
    directory = a[first]
    it2 = nat.NativeStringInterner()
    ids2, _ = it2.intern(directory)
    assert ids2.tolist() == list(range(len(directory)))


def test_string_baseline_runs():
    words = np.asarray([f"w{i % 100}" for i in range(5000)])
    rate = nat.heap_tumbling_baseline_str(words, np.ones(5000))
    assert rate > 0


def test_ivjoin_many_small_batches_with_pruning():
    """Streaming-lifetime shape for the LSM join core: thousands of
    tiny pushes with the watermark keeping pace — results must match
    one big push, and tails must keep folding (bounded run count is
    what the IV_MAX_TAILS merge trigger guarantees)."""
    import numpy as np
    import flink_tpu.native as nat
    if not nat.available():
        import pytest
        pytest.skip("native runtime required")
    rng = np.random.default_rng(5)
    n = 40_000
    lk = nat.splitmix64(rng.integers(0, 300, n).astype(np.uint64))
    lts = np.sort(rng.integers(0, 200_000, n).astype(np.int64))
    rk = nat.splitmix64(rng.integers(0, 300, n).astype(np.uint64))
    rts = np.sort(rng.integers(0, 200_000, n).astype(np.int64))

    # reference: one push per side, no pruning
    big = nat.NativeIntervalJoin(-50, 50)
    bl, br = big.push(0, lk, lts)
    bl2, br2 = big.push(1, rk, rts)
    want = set(zip(bl.tolist(), br.tolist())) \
        | set(zip(bl2.tolist(), br2.tolist()))

    # 800 interleaved pushes of 100 rows with a trailing watermark
    # (prunes rows already matched — emitted pairs are unaffected)
    small = nat.NativeIntervalJoin(-50, 50)
    got = set()
    step = 100
    for off in range(0, n, step):
        for side, (k, t) in ((0, (lk, lts)), (1, (rk, rts))):
            l, r = small.push(side, k[off:off + step],
                              t[off:off + step])
            got.update(zip(l.tolist(), r.tolist()))
        wm = int(min(lts[min(off + step, n) - 1],
                     rts[min(off + step, n) - 1])) - 200
        small.prune(wm)
    assert got == want and len(want) > 2_000


def test_session_fire_two_segment_retained_merge():
    """The retained tuple from one fire feeds the next verbatim
    (key-major contract): chained two-segment fires must produce
    exactly the sessions of one big fire."""
    import numpy as np
    import flink_tpu.native as nat
    if not nat.available():
        import pytest
        pytest.skip("native runtime required")
    rng = np.random.default_rng(17)
    n = 30_000
    keys = rng.integers(0, 500, n).astype(np.uint64)
    ts = np.sort(rng.integers(0, 100_000, n)).astype(np.int64)
    w = np.ones(n, np.float32)
    vh = nat.splitmix64(rng.integers(0, 1 << 30, n).astype(np.uint64))

    # oracle: single fire over everything
    ok, os_, oe, ot, _ = nat.session_log_fire(keys, ts, w, vh,
                                              800, 10**9, 4, 128)
    want = {(int(k), int(s), int(e)): t
            for k, s, e, t in zip(ok, os_, oe, ot)}

    # chained: 6 chunked fires, retained tuple passed back verbatim
    got = {}
    ret = None
    chunk = n // 6 + 1
    for off in range(0, n, chunk):
        hi = min(off + chunk, n)
        wm = int(ts[hi - 1]) - 1500 if hi < n else 10**9
        ok, os_, oe, ot, ret = nat.session_log_fire(
            keys[off:hi], ts[off:hi], w[off:hi], vh[off:hi],
            800, wm, 4, 128, retained=ret)
        for k, s, e, t in zip(ok, os_, oe, ot):
            got[(int(k), int(s), int(e))] = t
        if len(ret[0]) == 0:
            ret = None
    assert got == want and len(want) > 1000


def test_session_fire_guard_demotes_predating_rows():
    """A new row that predates a retained row (out-of-order across the
    fire boundary) must demote the kernel to the pooled double-sort —
    sessions still merge correctly."""
    import numpy as np
    import flink_tpu.native as nat
    if not nat.available():
        import pytest
        pytest.skip("native runtime required")
    k = np.array([7, 7], np.uint64)
    w = np.ones(2, np.float32)
    vh = nat.splitmix64(np.array([1, 2], np.uint64))
    # fire 1: both rows open (watermark behind), retained comes back
    _, _, _, _, ret = nat.session_log_fire(
        k, np.array([1000, 1400], np.int64), w, vh, 500, 0, 2, 64)
    assert len(ret[0]) == 2
    # fire 2: a new row at ts=700 PREDATES retained max (1400) and
    # bridges nothing; plus a row at 1650 extending the session
    k2 = np.array([7, 7], np.uint64)
    ok, os_, oe, ot, ret2 = nat.session_log_fire(
        k2, np.array([700, 1650], np.int64), w, vh[:2], 500, 10**9,
        2, 64, retained=ret)
    got = {(int(s), int(e)): int(t) for s, e, t in zip(os_, oe, ot)}
    # 700 joins [1000,1400,1650] because 1000-700 <= 500: one session
    # [700, 2150) of 4 events
    assert got == {(700, 2150): 4}, got


# ---- the integer table (the tpu backend's slot index) ----------------

_I64 = np.iinfo(np.int64)
INT_KEY_PATTERNS = [
    ("small", lambda rng, n: rng.integers(-50, 50, n)),
    ("wide", lambda rng, n: rng.integers(_I64.min, _I64.max, n,
                                         endpoint=True)),
    ("ends", lambda rng, n: np.array([0, -1, _I64.min, _I64.max, 1])[
        rng.integers(0, 5, n)]),
    # keys that share their low bits, and keys that share their high ones
    ("strided", lambda rng, n: rng.integers(0, 400, n) << 40),
    ("dense", lambda rng, n: rng.integers(0, 5000, n) + (1 << 62)),
]


@pytest.mark.parametrize("name,gen", INT_KEY_PATTERNS)
def test_int_table_is_a_dict_on_random_batches(name, gen):
    """probe + assign, lookup, take, set and the three scalar calls in
    random order against a Python dict: equal ids, `first` the rows of
    first appearance, and `export` the dict's own order throughout."""
    rng = np.random.default_rng([38, len(name)])
    table, ref = nat.NativeIntTable(), {}
    next_id = 0
    for step in range(300):
        n = int(rng.integers(1, 200))
        keys = np.ascontiguousarray(gen(rng, n), np.int64)
        call = int(rng.integers(0, 7))
        if call <= 1:
            ids, first = table.probe(keys)
            new = list(dict.fromkeys(k for k in keys.tolist()
                                     if k not in ref))
            assert keys[first].tolist() == new
            assert (ids < -1).sum() == sum(k not in ref
                                           for k in keys.tolist())
            # a reader between the two phases finds no pending key
            assert all(table.get(k) == -1 for k in new[:3])
            fresh = np.arange(next_id, next_id + len(new))
            next_id += len(new)
            table.assign(fresh, ids)
            ref.update(zip(new, fresh.tolist()))
            assert ids.tolist() == [ref[k] for k in keys.tolist()]
        elif call == 2:
            assert table.lookup(keys).tolist() == [
                ref.get(k, -1) for k in keys.tolist()]
        elif call == 3:
            assert table.lookup(keys, take=True).tolist() == [
                ref.pop(k, -1) for k in keys.tolist()]
        elif call == 4:
            ids = rng.integers(0, 1 << 40, n)
            table.set(keys, ids)
            ref.update(zip(keys.tolist(), ids.tolist()))
        else:
            key = int(keys[0])
            if call == 5:
                assert table.pop(key) == ref.pop(key, -1)
                assert table.get(key) == -1
            else:
                table.put(key, step)
                ref[key] = step
                assert table.get(key) == step
        assert len(table) == len(ref)
        if step % 25 == 0 or step == 299:
            got_keys, got_ids = table.export()
            assert got_keys.tolist() == list(ref)
            assert got_ids.tolist() == list(ref.values())


def test_int_table_refills_after_it_was_emptied():
    """A window's life: filled batch by batch through several
    doublings, emptied by one take, filled again."""
    rng = np.random.default_rng(7)
    for table in (nat.NativeIntTable(), nat.NativeIntTable(room=200_000),
                  nat.NativeIntTable(room=5)):
        _fill_and_empty_twice(table, rng)


def _fill_and_empty_twice(table, rng):
    for _ in range(2):
        keys = rng.permutation(200_000).astype(np.int64) - 100_000
        for lo in range(0, len(keys), 8192):
            ids, first = table.probe(keys[lo:lo + 8192])
            assert len(first) == len(ids)
            table.assign(np.arange(lo, lo + len(ids)), ids)
        assert len(table) == 200_000
        assert table.lookup(keys).tolist() == list(range(200_000))
        assert table.export()[0].tolist() == keys.tolist()
        taken = table.lookup(np.concatenate([keys[::-1], keys[:10]]),
                             take=True)
        assert taken[:200_000].tolist() == list(range(199_999, -1, -1))
        assert (taken[200_000:] == -1).all() and len(table) == 0
        assert table.peak() == 200_000


def test_slot_index_of_the_vectorized_tier_behaves_as_before():
    """`NativeSlotIndex` (the vectorized window tier, CEP) is keyed by
    64-bit hash, gives its new hashes the caller's slots in order of
    first appearance and exports by table position: unchanged by the
    integer table beside it."""
    rng = np.random.default_rng(11)
    index, ref = nat.NativeSlotIndex(16), {}
    handed = [0]

    def alloc(n):
        handed[0] += n
        return np.arange(handed[0] - n, handed[0])

    for _ in range(40):
        hashes = rng.integers(2, 3000, 500).astype(np.uint64) \
            * np.uint64(0x9E3779B97F4A7C15)
        hashes[7] = 0  # the one hash the table stores under another
        new = list(dict.fromkeys(h for h in hashes.tolist() if h not in ref))
        slots, is_new, first = index.lookup_or_insert(hashes, alloc)
        assert hashes[first].tolist() == new and is_new.all()
        ref.update(zip(new, range(len(ref), len(ref) + len(new))))
        assert slots.tolist() == [ref[h] for h in hashes.tolist()]
    assert index.n == len(ref) == handed[0]
    hashes, slots = index.export()
    # (0 comes back as the constant it is stored under)
    assert dict(zip(hashes.tolist(), slots.tolist())) \
        == {h or 0x9E3779B97F4A7C15: slot for h, slot in ref.items()}
    restored = nat.NativeSlotIndex(16)
    restored.set_bulk(hashes, slots)
    again = restored.lookup_or_insert(hashes, alloc)
    assert again[0].tolist() == slots.tolist() and len(again[2]) == 0

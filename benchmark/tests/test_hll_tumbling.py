"""The comparison that decides ``correct``: exact on exact results,
one duplicated plus one missing (key, window) are two failures, and
every bound the checker was given beyond ``chip_smoke.py``'s six sigma
has its evidence here."""

import numpy as np

import loader

reference = loader.load_module("references", "hll_tumbling")
SIGMA = 1.04 / 64.0


def window(seed=0, n=20_000, key_space=500):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, key_space, n, dtype=np.int64)
    users = rng.integers(0, 1 << 40, n, dtype=np.int64)
    return reference.exact_distinct(keys, users)


def test_exact_distinct_counts_pairs_once():
    keys = np.array([3, 3, 3, 5, 5, 9], np.int64)
    users = np.array([1, 1, 2, 7, 8, 4], np.int64)
    k, c = reference.exact_distinct(keys, users)
    assert k.tolist() == [3, 5, 9] and c.tolist() == [2, 2, 1]


def test_exact_results_pass():
    rk, rc = window()
    checker = reference.HllChecker(12, 500)
    shuffle = np.random.default_rng(1).permutation(len(rk))
    assert checker.add_window(0, rk[shuffle], rc[shuffle].astype(float),
                              rk, rc) == 0
    problems, facts = checker.verdict()
    assert not problems and checker.failed == 0
    assert checker.attempted == len(rk) == facts["key_windows"]


def test_one_duplicate_and_one_missing_are_two_failures():
    rk, rc = window()
    gk = np.concatenate([rk[1:], rk[5:6]])       # key 0 missing, key 5 twice
    ge = np.concatenate([rc[1:], rc[5:6]]).astype(float)
    checker = reference.HllChecker(12, 500)
    checker.add_window(0, gk, ge, rk, rc)
    problems, _ = checker.verdict()
    assert checker.failed == 2 and problems
    correct = not checker.failed and not problems
    assert correct is False


def test_estimates_out_of_bound_nan_and_stray_keys_fail():
    rk, rc = window()
    ge = rc.astype(float)
    ge[0] += 12.0           # beyond anything a key of ~40 values may lose
    ge[1] = np.nan
    gk = rk.copy()
    checker = reference.HllChecker(12, 500)
    checker.add_window(0, np.append(gk, 500), np.append(ge, 1.0), rk, rc)
    assert checker.failed == 3


def test_lost_updates_fail_the_pooled_tests():
    # every estimate one low: inside the hard bound, far more misses
    # than register collisions explain
    rk, rc = window(n=4000)
    checker = reference.HllChecker(12, 500)
    checker.add_window(0, rk, rc - 1.0, rk, rc)
    problems, _ = checker.verdict()
    assert checker.failed == 0 and problems


def test_a_window_nobody_asked_for_fails_every_row():
    checker = reference.HllChecker(12, 500)
    checker.add_stray_window(5000, 12)
    assert checker.failed == 12 and checker.verdict()[0]


def simulate_hll(n, trials, rng, p=12):
    """Classical HyperLogLog (Flajolet et al. 2007) on ``n`` distinct
    values with ideal hashes, ``trials`` times: alpha_m bias
    correction, linear counting where the raw estimate is at most 2.5m
    and a register is empty.  Plain numpy, no code of the system."""
    m = 1 << p
    hashes = rng.integers(0, 1 << 63, (trials, n), dtype=np.int64)
    register = hashes & (m - 1)
    rest = (hashes >> p).astype(np.float64) + 0.5
    # position of the first 1 bit of the remaining 63 - p bits
    rho = (63 - p) - np.floor(np.log2(rest)).astype(np.int64)
    sketch = np.zeros((trials, m), np.int64)
    np.maximum.at(sketch, (np.arange(trials)[:, None], register), rho)
    raw = 0.7213 / (1 + 1.079 / m) * m * m / (2.0 ** -sketch).sum(axis=1)
    empty = (sketch == 0).sum(axis=1)
    linear = m * np.log(m / np.maximum(empty, 1))
    return np.where((raw <= 2.5 * m) & (empty > 0), linear, raw)


def test_simulation_behind_the_ten_sigma_range():
    """Where the classical estimator leaves linear counting (raw
    estimate near 2.5m, so n between 2m and 4m) it is biased upwards,
    by up to 1.3 standard errors, and right at the switch (n about
    10,000) a key answers from either branch, so its estimates spread
    by 1.6 standard errors.  Six standard errors are there only about
    three of the real spread above the real mean; ten are six, which
    is what six are everywhere else."""
    rng = np.random.default_rng(12)
    m = 4096
    z = {n: (simulate_hll(n, 400, rng) - n) / (SIGMA * n)
         for n in (2 * m, 10_000, 10_240, 3 * m, 4 * m, 6 * m)}
    assert 0.6 < z[10_000].mean() < 1.5 and 1.3 < z[10_000].std() < 1.9
    assert 1.0 < z[10_240].mean() < 1.7
    assert (6.0 - z[10_000].mean()) / z[10_000].std() < 3.7
    assert 5.0 < (10.0 - z[10_000].mean()) / z[10_000].std() < 7.0
    for n in (2 * m, 10_000, 10_240, 3 * m, 4 * m):
        assert np.abs(z[n]).max() < 6.0
    assert abs(z[6 * m].mean()) < 0.3 and z[6 * m].std() < 1.1
    small = (simulate_hll(1_000, 400, rng) - 1_000) / (SIGMA * 1_000)
    assert abs(small.mean()) < 0.3 and small.std() < 1.0
    checker = reference.HllChecker(12, 10)
    assert checker.hard_bound([2 * m - 1, 2 * m, 4 * m, 4 * m + 1]).tolist() \
        == [6 * SIGMA * (2 * m - 1), 10 * SIGMA * 2 * m,
            10 * SIGMA * 4 * m, 6 * SIGMA * (4 * m + 1)]


def test_a_small_key_is_low_by_the_values_that_share_registers():
    """The model behind the small-key rule, by simulation: with c of a
    key's n values sharing a register, linear counting answers
    m ln(m / (m - n + c)), which is low by c less the expected number;
    and the tail of c is the Poisson(n (n - 1) / 2m)'s or a little
    lighter, so the Poisson admits no fewer rows than it should."""
    rng = np.random.default_rng(3)
    m, n, trials = 4096, 57, 200_000
    registers = rng.integers(0, m, (trials, n))
    registers.sort(axis=1)
    shared = (np.diff(registers, axis=1) == 0).sum(axis=1)
    estimate = m * np.log(m / (m - n + shared))
    lam = n * (n - 1) / (2.0 * m)
    assert np.allclose(estimate - n, -(shared - lam), atol=0.45)
    # seed 107 on the chip: n = 57, six shared, estimate 51.32
    assert round(float(m * np.log(m / (m - 57 + 6))), 2) == 51.32
    lam_table, above = reference.collision_tails(m, 512)
    assert lam_table[n] == lam
    for k in (0, 1, 2, 3):
        seen = (shared > k).mean()
        noise = 4 * np.sqrt(above[n, k] / trials)
        assert 0.75 * above[n, k] - noise < seen < above[n, k] + noise


def test_the_hard_bound_is_wider_where_the_estimator_leaves_linear_counting():
    """An 8-sigma miss is inside the bound where the classical
    estimator leaves linear counting, and nowhere else."""
    sigma = SIGMA
    rk = np.array([1, 2, 3], np.int64)
    rc = np.array([10_000, 100_000, 1_000], np.int64)
    ge = rc * (1.0 + 8.0 * sigma)
    checker = reference.HllChecker(12, 10)
    assert checker.add_window(0, rk, ge, rk, rc) == 2
    checker = reference.HllChecker(12, 10)
    assert checker.add_window(0, rk[:1], rc[:1] * (1.0 + 11.0 * sigma),
                              rk[:1], rc[:1]) == 1


def test_a_small_key_may_lose_as_many_values_as_can_share_registers():
    """n = 57 at p12: 0.39 colliding values expected, six seen once in
    14M keys on the chip (6 sigma n = 5.56 called that a failure).
    Such a row is admitted up to the 1e-10 quantile (9), only when it
    is LOW, and counted for the pooled test."""
    table = reference.collision_quantile(4096, 512)
    assert table[57] == 9 and table[4] == 3 and table[8] == 3
    assert table[300] < 6 * SIGMA * 300 and table[512] < 6 * SIGMA * 512
    rk = np.array([1, 2, 3, 4], np.int64)
    rc = np.array([57, 57, 57, 57], np.int64)
    checker = reference.HllChecker(12, 10)
    # six shared registers; eleven; six too HIGH, which shared
    # registers cannot do; and one as it should be
    assert checker.add_window(0, rk, np.array([51.32, 46.9, 62.68, 57.0]),
                              rk, rc) == 2
    _, facts = checker.verdict()
    assert facts["small_keys_below_bound"] == 1


def test_small_keys_below_the_bound_are_held_to_their_poisson_rate():
    """One such key in some million is what shared registers do (about
    3.5e-6 of keys with n = 57); twenty in ten thousand are lost
    updates, whatever each row's own bound admits."""
    keys = np.arange(10_000, dtype=np.int64)
    counts = np.full(10_000, 57, np.int64)
    estimates = counts.astype(np.float64)
    estimates[0] = 51.32
    checker = reference.HllChecker(12, 10_000)
    assert checker.add_window(0, keys, estimates, keys, counts) == 0
    problems, facts = checker.verdict()
    assert not problems and facts["small_keys_below_bound"] == 1
    assert 0.02 < facts["below_bound_expected"] < 0.06
    estimates[:20] = 51.32
    checker = reference.HllChecker(12, 10_000)
    assert checker.add_window(0, keys, estimates, keys, counts) == 0
    problems, _ = checker.verdict()
    assert any("below the hard bound" in p for p in problems)
    # a replayed window is held row by row and not counted again
    checker = reference.HllChecker(12, 10_000)
    estimates[1:20] = 57.0
    for replay in range(30):
        assert checker.add_window(replay * 1000, keys, estimates, keys,
                                  counts, pooled=replay == 0) == 0
    problems, facts = checker.verdict()
    assert not problems and facts["small_keys_below_bound"] == 1
    assert checker.attempted == 30 * 10_000


def test_check_holds_every_emitted_window_to_the_rows_it_carried():
    rng = np.random.default_rng(4)
    config = {"window_ms": 1000, "hll_precision": 12, "key_space": 500}
    pool = [(rng.integers(0, 500, 4000, dtype=np.int64),
             rng.integers(0, 1 << 40, 4000, dtype=np.int64))
            for _ in range(2)]
    # windows 0 and 1 fresh, window 2 replays entry 0, window 3 holds
    # the first 100 rows of entry 1
    emitted = [(0, (0, 4000), lambda: pool[0]),
               (1, (1, 4000), lambda: pool[1]),
               (2, (0, 4000), lambda: pool[0]),
               (3, (1, 100), lambda: (pool[1][0][:100], pool[1][1][:100]))]
    results = {}
    for window, _id, columns in emitted:
        k, c = reference.exact_distinct(*columns())
        results[window * 1000] = (k, np.full(len(k), window * 1000),
                                  c.astype(np.float64))
    verdict = reference.check(config, emitted, results)
    assert verdict["failed"] == 0 and not verdict["problems"]
    assert verdict["attempted"] == sum(len(r[0]) for r in results.values())
    # pooled facts count independent windows only (0, 1 and 3)
    assert verdict["facts"]["key_windows"] == sum(
        len(results[w][0]) for w in (0, 1000, 3000))
    # a missing window, and one nobody asked for
    results[9000] = results.pop(2000)
    verdict = reference.check(config, emitted, results)
    assert verdict["failed"] == 2 * len(results[9000][0])
    assert verdict["problems"]

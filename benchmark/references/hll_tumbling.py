"""Per-key COUNT DISTINCT over tumbling event-time windows, answered
by a HyperLogLog sketch: the plain reference and the comparison that
decides ``correct``.  Found by the configuration's ``reference`` name;
``check(config, emitted, results)`` is what the harness calls.

``exact_distinct`` is ``chip_smoke.py``'s: exact distinct counts by
``np.unique``, independent of every hash and sketch under test.
``HllChecker`` holds a run's results to the guarantees the
configuration files state: every ``(key, window)`` emitted exactly
once, and every estimate within HyperLogLog's bounds of the exact
count (``chip_smoke.py``'s ``check_hll``, counted per row and summed
over windows so that some tens of million-row windows check in
seconds).  The per-row bound is six standard errors, ten where the
classical estimator leaves linear counting (a range uniform keys
never reach and Zipf keys do; ``tests/test_hll_tumbling.py`` holds the
simulation).  A small key's estimate may fall below that bound when
more of its values than expected share a register; such rows are
admitted one by one only up to the 1e-10 quantile of that number, and
all together only as often as the Poisson tail says they occur.
"""

from __future__ import annotations

import numpy as np


def exact_distinct(keys, users):
    """(sorted keys, exact distinct users per key) of one window."""
    if int(keys.max()) >= (1 << 23) or int(users.max()) >= (1 << 40) \
            or int(keys.min()) < 0 or int(users.min()) < 0:
        raise ValueError("keys must be < 2^23 and users < 2^40")
    pairs = np.unique((keys.astype(np.uint64) << np.uint64(40))
                      | users.astype(np.uint64))
    k, c = np.unique(pairs >> np.uint64(40), return_counts=True)
    return k.astype(np.int64), c.astype(np.int64)


def collision_tails(m, n_max):
    """For n = 0..n_max values of one key: ``lam[n]``, how many of
    them are expected to share a register with another (n (n - 1) /
    2m), and ``above[n, k]``, the chance that more than k do, their
    number taken as Poisson.  Linear counting corrects the expected
    number, so a small key's estimate is low by the rest, and that
    tail is the Poisson's: far heavier than a Gaussian's at six
    sigma."""
    n = np.arange(n_max + 1, dtype=np.float64)
    lam = np.maximum(n * (n - 1) / (2.0 * m), 1e-300)
    k = np.arange(int(4 * lam.max()) + 64, dtype=np.float64)
    log_factorial = np.concatenate([[0.0], np.cumsum(np.log(k[1:]))])
    pmf = np.exp(-lam[:, None] + k[None, :] * np.log(lam)[:, None]
                 - log_factorial[None, :])
    above = np.cumsum(pmf[:, ::-1], axis=1)[:, ::-1] - pmf   # P(X > k)
    return lam, above


def collision_quantile(m, n_max, p=1e-10):
    """For n = 0..n_max: how many more of n values than expected can
    share a register with another, with probability <= p."""
    lam, above = collision_tails(m, n_max)
    return np.argmax(above <= p, axis=1) - np.floor(lam).astype(int)


SMALL = 512     # from about 250 values on, six standard errors exceed
                # anything shared registers can cost


class HllChecker:
    """Counts failed ``(key, window)`` rows window by window; the
    pooled tests (RMS relative error, misses against the birthday
    bound of register collisions, small keys below the hard bound
    against the Poisson tail) come with :meth:`verdict`."""

    def __init__(self, precision, key_space):
        self.m = m = 1 << precision
        self.sigma = 1.04 / np.sqrt(m)
        self.key_space = key_space
        #: chance that c values of one key share no register
        self._p_clean = np.cumprod(1.0 - np.arange(m) / m)
        n = np.arange(SMALL + 1)
        lam, above = collision_tails(m, SMALL)
        #: a small key's estimate may be low by this much at most
        self._collisions_at_most = collision_quantile(m, SMALL)
        #: chance that a key of n values falls below the hard bound
        #: because of shared registers alone
        self._p_below = above[n, np.floor(lam + self.hard_bound(n))
                              .astype(np.int64)]
        self.attempted = 0
        self.failed = 0
        self.problems = []
        self._n = 0
        self._sum_sq_rel = 0.0
        self._max_abs = 0.0
        self._off = 0
        self._collisions = 0.0
        self._below = 0
        self._below_expected = 0.0

    def hard_bound(self, n):
        """A bound no healthy sketch crosses: three lost registers;
        six standard errors; ten where the classical estimator
        switches from linear counting to the raw estimate (n between
        2m and 4m: bias up to +1.3 sigma, and a spread of 1.6 sigma
        right at the switch, by the simulation in
        ``tests/test_hll_tumbling.py``; ten are there what six are
        elsewhere)."""
        n = np.asarray(n, np.float64)
        errors = np.where((n >= 2 * self.m) & (n <= 4 * self.m), 10.0, 6.0)
        return np.maximum(3.0, errors * self.sigma * n)

    def add_stray_window(self, window_start, rows):
        """Results for a window no event belongs to."""
        self.failed += rows
        self._problem(f"window {window_start}: {rows} rows nobody asked for")

    def add_window(self, window_start, got_keys, got_est, ref_keys,
                   ref_counts, pooled=True):
        """One window's emitted rows against its reference.  Failed
        rows: expected and missing, emitted more than once or for a
        key with no event, or an estimate out of bound (NaN too).
        ``pooled=False`` for a window whose rows an earlier window
        carried: its rows are held to the same bounds, and left out of
        the pooled tests, which count independent draws."""
        K = self.key_space
        gk = np.asarray(got_keys, np.int64)
        ge = np.asarray(got_est, np.float64)
        inside = (gk >= 0) & (gk < K)
        stray = int((~inside).sum())
        if stray:
            gk, ge = gk[inside], ge[inside]
        emitted = np.bincount(gk, minlength=K)
        exact = np.zeros(K, np.int64)
        exact[ref_keys] = ref_counts
        expected = exact > 0
        missing = int((expected & (emitted == 0)).sum())
        surplus = int(emitted.sum() - (expected & (emitted > 0)).sum())
        est = np.full(K, np.nan)
        est[gk] = ge
        once = expected & (emitted == 1)
        n = exact[once]
        err = est[once] - n
        good = np.abs(err) <= self.hard_bound(n)
        # a small key below the hard bound: admitted while as many of
        # its values as that can share registers at all
        small = np.minimum(n, SMALL)
        below = ~good & (n < SMALL) \
            & (-err <= self._collisions_at_most[small]) & (err < 0)
        bad = ~(good | below)
        failed = missing + surplus + stray + int(bad.sum())
        if failed:
            self._problem(
                f"window {window_start}: {missing} missing, {surplus} "
                f"duplicated or unasked, {stray} outside the key space, "
                f"{int(bad.sum())} estimates out of bound, of "
                f"{len(ref_keys)} expected rows")
        self.attempted += len(ref_keys)
        self.failed += failed
        if pooled and good.any():
            self._n += int(good.sum())
            self._sum_sq_rel += float(((err[good] / n[good]) ** 2).sum())
            self._max_abs = max(self._max_abs,
                                float(np.abs(err[good]).max()))
            self._off += int((np.abs(err[good]) > 0.5).sum())
        if pooled:
            c = np.minimum(np.asarray(ref_counts), self.m)
            self._collisions += float((1.0 - self._p_clean[c - 1]).sum())
            self._below += int(below.sum())
            self._below_expected += float(self._p_below[small].sum())
        return failed

    def _problem(self, text):
        if len(self.problems) < 20:
            self.problems.append(text)

    def verdict(self):
        """(problems, facts) over every window added."""
        problems = list(self.problems)
        if not self._n:
            return problems or ["nothing to compare"], {}
        rms = float(np.sqrt(self._sum_sq_rel / self._n))
        if rms > self.sigma:
            problems.append(f"rms relative error {rms:.5f} > 1.04/sqrt(m) "
                            f"= {self.sigma:.5f}")
        # an estimate misses by more than 0.5 only when two of the
        # key's values share a register; lost or misrouted updates show
        # up as more misses than the birthday bound explains
        if self._off > 2.0 * self._collisions + 10:
            problems.append(
                f"{self._off} estimates off by more than 0.5, register "
                f"collisions explain {self._collisions:.1f}")
        # small keys below the hard bound are rare events with a known
        # rate: a handful in some ten million rows, not more
        expect = self._below_expected
        if self._below > expect + 6.0 * np.sqrt(expect) + 3:
            problems.append(
                f"{self._below} small keys below the hard bound, shared "
                f"registers explain {expect:.2f}")
        return problems, {"key_windows": self._n,
                          "rms_rel_err": round(rms, 6),
                          "max_abs_err": round(self._max_abs, 3),
                          "off_by_half": self._off,
                          "collisions_expected": round(self._collisions, 1),
                          "small_keys_below_bound": self._below,
                          "below_bound_expected": round(expect, 3)}


def check(config, emitted, results):
    """Every window the source emitted into against the plain
    reference of the rows it carried.  ``emitted``: the source's
    ``Emitted`` entries; ``results``: {window start: result columns
    (key, window start, estimate)} as the sink kept them."""
    window_ms = config["window_ms"]
    checker = HllChecker(config["hll_precision"], config["key_space"])
    results = dict(results)
    references = {}
    for window, data_id, columns in emitted:
        first = data_id not in references
        if first:
            references[data_id] = exact_distinct(*columns())
        got = results.pop(window * window_ms, None)
        keys, estimates = (got[0], got[2]) if got is not None else ((), ())
        checker.add_window(window * window_ms, keys, estimates,
                           *references[data_id], pooled=first)
    for window_start, got in results.items():
        checker.add_stray_window(window_start, len(got[0]))
    problems, facts = checker.verdict()
    return {"attempted": checker.attempted, "failed": checker.failed,
            "problems": problems, "facts": facts}

"""Per-key quantiles over sliding event-time windows, answered by a
relative-error sketch: the plain reference and the comparison that
decides ``correct``.  Found by the configuration's ``reference`` name;
``check(config, emitted, results)`` is what the harness calls.  numpy
in float64, nothing of the system; ``tests/quantile_sliding_reference.py``
is this file, byte for byte.

The reference answer for a key in a window is an exact ORDER STATISTIC:
of the key's n values in the window, sorted, the one of rank
ceil(q * n) (counted from 1), the rank taken in whole numbers so that
q * n on an integer is that integer.  A DDSketch of relative accuracy
alpha (Masson, Rim, Lee, VLDB 2019) answers with the mid-point
2 * gamma^(i+1) / (gamma + 1) of the bucket [gamma^i, gamma^(i+1))
that value fell into, gamma = (1 + alpha) / (1 - alpha), which is
within alpha of every value of the bucket, relatively.

**The bound** is therefore ``alpha * (1 + SLACK)``.  The slack is not
a bucket's: the sketch under test buckets in float32, so a value
within float32's rounding of a bucket edge may land in the bucket next
door, whose mid-point is off by alpha plus the value's distance from
the edge.  That distance is the error of ``log(v) / log(gamma)`` in
bucket widths times log(gamma) = 0.02.  On a CPU it is 1e-6,
relatively.  On a TPU v5e, whose float32 log and divide are coarser,
a probe over every float32 within 16,384 ulps of a bucket edge
between 1e-3 and 1e5 (a range no draw of this mix leaves) found values
misplaced up to 1.02e-4 from their edge and a worst error of
0.0101017, the mid-point's own exp 4.8e-6 of it; eleven runs of the
cell, 106M quantiles, read 0.0100772 at worst (PR 33).  2% of alpha
(2e-4 at alpha 0.01) is twice the probe's reading, and far below what
dropping the mid-point correction (gamma - 1 = 2.02 alpha at a
bucket's lower edge) or bucketing in bfloat16 would need (0.033 to
0.049 where bfloat16 is honoured; on the chip XLA keeps float32
through such casts, and still reads 0.01024 to 0.01045).
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

#: room above alpha, as a share of alpha (see the module's docstring)
SLACK = 0.02


def bound(config):
    """Largest admitted |estimate - exact| / exact."""
    return config["relative_accuracy"] * (1.0 + SLACK)


def ranks(n, q):
    """ceil(q * n) for every count in ``n``, at least 1, in whole
    numbers (0.99 * 300 is 297, not 297.00000000000006)."""
    q = Fraction(str(q))
    return np.maximum(-((-n * q.numerator) // q.denominator), 1)


def exact_quantiles(keys, values, quantiles):
    """(sorted distinct keys, values per key, float64[K, Q] with the
    order statistic of rank ceil(q * n) of each key's values)."""
    order = np.lexsort((values, keys))
    k, v = keys[order], values[order]
    first = np.flatnonzero(np.concatenate([[True], k[1:] != k[:-1]]))
    n = np.diff(np.concatenate([first, [len(k)]]))
    out = np.empty((len(first), len(quantiles)), np.float64)
    for j, q in enumerate(quantiles):
        out[:, j] = v[first + ranks(n, q) - 1]
    return k[first].astype(np.int64), n.astype(np.int64), out


class QuantileChecker:
    """Counts, over the windows added: quantiles asked for, quantiles
    missing, duplicated, unasked or out of bound."""

    def __init__(self, n_quantiles, limit):
        self.q = n_quantiles
        self.limit = limit
        self.attempted = 0
        self.failed = 0
        self.problems = []
        self._rows = 0
        self._max_rel = 0.0
        self._sum_rel = 0.0
        self._over_alpha = 0

    def add_window(self, window_start, got_keys, got, ref_keys, ref,
                   alpha):
        """``got``: float[G, Q] estimates of ``got_keys`` as emitted;
        ``ref``: float64[K, Q] of the sorted ``ref_keys``."""
        got_keys = np.asarray(got_keys, np.int64)
        got = np.asarray(got, np.float64).reshape(len(got_keys), self.q)
        order = np.argsort(got_keys, kind="stable")
        got_keys, got = got_keys[order], got[order]
        pos = np.searchsorted(ref_keys, got_keys)
        pos_c = np.minimum(pos, max(len(ref_keys) - 1, 0))
        known = (ref_keys[pos_c] == got_keys) if len(ref_keys) \
            else np.zeros(len(got_keys), bool)
        # the first row of each asked-for key is compared; every other
        # emitted row is one too many
        lead = known & np.concatenate([[True],
                                       got_keys[1:] != got_keys[:-1]])
        surplus = int((~lead).sum())
        missing = len(ref_keys) - int(lead.sum())
        exact = ref[pos_c[lead]]
        with np.errstate(divide="ignore", invalid="ignore"):
            rel = np.abs(got[lead] - exact) / exact
        bad = ~(rel <= self.limit)   # a NaN is out of bound
        failed = int(bad.sum()) + self.q * (missing + surplus)
        if failed:
            self._problem(
                f"window {window_start}: {missing} keys missing, {surplus} "
                f"rows duplicated or unasked, {int(bad.sum())} quantiles "
                f"out of bound (worst {float(np.nanmax(rel, initial=0)):.6f} "
                f"> {self.limit:.6f}), of {len(ref_keys)} expected keys")
        self.attempted += self.q * len(ref_keys)
        self.failed += failed
        if rel.size:
            finite = rel[np.isfinite(rel)]
            self._rows += finite.size
            self._sum_rel += float(finite.sum())
            self._max_rel = max(self._max_rel,
                                float(finite.max(initial=0.0)))
            self._over_alpha += int((finite > alpha).sum())
        return failed

    def add_stray_window(self, window_start, rows):
        """Rows for a window the source emitted nothing into."""
        self.failed += self.q * rows
        self._problem(f"window {window_start}: {rows} rows for a window "
                      f"no event fell into")

    def _problem(self, text):
        if len(self.problems) < 20:
            self.problems.append(text)

    def verdict(self):
        problems = list(self.problems)
        if not self._rows:
            return problems or ["nothing to compare"], {}
        return problems, {"quantiles_compared": self._rows,
                          "max_rel_err": self._max_rel,
                          "mean_rel_err": self._sum_rel / self._rows,
                          "over_alpha": self._over_alpha,
                          "bound": self.limit}


def check(config, emitted, results):
    """Every sliding window some emitted event fell into, the partial
    ones at both ends of the stream too, against the order statistics
    of the rows it held.  ``emitted``: the source's ``Emitted``
    entries, one per slide period (pane) with its (keys, values);
    ``results``: {start of the window's LAST pane: result columns
    (key, that start, one column per quantile)} as the sink kept
    them."""
    slide = config["slide_ms"]
    panes_per_window = config["window_size_ms"] // slide
    quantiles = config["quantiles"]
    checker = QuantileChecker(len(quantiles), bound(config))
    panes = {window: columns for window, _, columns in emitted}
    results = dict(results)
    if panes:
        # the window whose last pane is `last` covers event time
        # [(last + 1) * slide - size, (last + 1) * slide)
        for last in range(min(panes), max(panes) + panes_per_window):
            held = [panes[p]() for p in
                    range(last - panes_per_window + 1, last + 1)
                    if p in panes]
            if not held:
                continue
            ref_keys, _, ref = exact_quantiles(
                np.concatenate([k for k, _ in held]),
                np.concatenate([np.asarray(v, np.float64)
                                for _, v in held]), quantiles)
            got = results.pop(last * slide, None)
            if got is None:
                got_keys, got_q = (), np.empty((0, len(quantiles)))
            else:
                got_keys, got_q = got[0], np.stack(
                    [np.asarray(c, np.float64) for c in got[2:]], axis=1)
            checker.add_window(last * slide, got_keys, got_q, ref_keys, ref,
                               config["relative_accuracy"])
    for window_start, got in results.items():
        checker.add_stray_window(window_start, len(got[0]))
    problems, facts = checker.verdict()
    return {"attempted": checker.attempted, "failed": checker.failed,
            "problems": problems, "facts": facts}

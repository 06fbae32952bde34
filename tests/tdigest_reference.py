"""Per-key quantiles over sliding event-time windows, answered by a
merging t-digest: the plain reference and the comparison that decides
``correct``.  Found by the configuration's ``reference`` name;
``check(config, emitted, results)`` is what the harness calls.  numpy
in float64 over the float32-rounded values, nothing of the system;
``tests/tdigest_reference.py`` is this file, byte for byte.

**The digest** (Dunning & Ertl, arXiv:1902.04023, the merging variant,
scale function k1 = delta / (2 pi) asin(2q - 1)), per (key, window), in
arrival order: each value goes into a buffer of B; the value that
would be the (B+1)-th first triggers a compress.  A compress sorts the
centroids and the buffer together by mean (stable: centroids, then the
buffer in arrival order) and walks them once: the next entry joins the
current centroid while n - W <= n h or S <= s sqrt(W (n - W)) - (2W - n) h
(W the weight left of the current centroid, S its weight with the
entry, n the count, h = sin^2(pi / delta), s = sin(2 pi / delta):
n k1^-1(k1(W / n) + 1) written without a transcendental), else starts
a new one; a centroid's mean is its weighted sum over its weight.  The
answer: compress, then of the points (0, min), (each centroid's left
edge + weight / 2, its mean), (n, max), the linear interpolation at
t = ceil(q n) - 1/2.  The digests of a window are walked together,
position by position, digests as vectors.

**The comparison**, for every (key, window) any event fell into:

(a) exactly one row;
(b) p50 and p99 within ``estimate_tolerance`` (relative) of this
    digest's estimates, unless the digest is FLAGGED: one of its merge
    tests came within ``DECISION_ROOM`` (relative) of its limit, or
    two of its sorted entries of which one is a merged centroid lay
    within ``TIE_ROOM`` (relative) of each other, so that float32 on
    the system's side may have walked another path.  A flagged digest
    is held to (c) alone; how many were flagged is a fact;
(c) every estimate within ``rank_bound_units`` k1-units of rank of the
    exact order statistic: the estimate's place among the window's
    values of that key, [#values < x, #values <= x] / n, lies within
    units x 2 pi sqrt(q (1 - q)) / delta + 1/n of q.
"""

from __future__ import annotations

import math

import numpy as np

try:  # in the benchmark, beside its loader
    import loader
    _orders = loader.load_module("references", "quantile_sliding")
except ImportError:  # in the tests, beside the twin of that file
    import importlib.util
    import os
    _spec = importlib.util.spec_from_file_location(
        "quantile_sliding_reference",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "quantile_sliding_reference.py"))
    _orders = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_orders)

#: a merge test this close to its limit, relatively, flags the digest
DECISION_ROOM = 2.0 ** -16
#: sorted neighbours this close, relatively, one a merged centroid,
#: flag the digest (its mean is float32 on the system's side)
TIE_ROOM = 2.0 ** -17


def ranks(n, q):
    """ceil(q * n), at least 1, in whole numbers."""
    return _orders.ranks(n, q)


class Digests:
    """The t-digests of many streams at once: centroids (mean, weight)
    [K, C] sorted by mean, the flags, and per centroid how many entries
    its last compress folded into it."""

    def __init__(self, k, delta, centroids):
        self.delta = delta
        self.h = math.sin(math.pi / delta) ** 2
        self.s = math.sin(2 * math.pi / delta)
        self.mean = np.zeros((k, centroids))
        self.weight = np.zeros((k, centroids), np.int64)
        self.folded = np.zeros((k, centroids), np.int64)
        self.flagged = np.zeros(k, bool)

    def compress(self, which, buf, nbuf):
        """Compress digests ``which`` with their buffers (float64[G, *],
        the first ``nbuf`` of each row real), in classes of like
        length so that a short digest is not walked as far as a long
        one."""
        if not len(which):
            return
        ncent = (self.weight[which] > 0).sum(axis=1)
        length = ncent + nbuf
        cls = np.ceil(np.log2(np.maximum(length, 1))).astype(np.int64)
        for c in np.unique(cls):
            part = np.flatnonzero(cls == c)
            width = int(length[part].max())
            self._compress(which[part], buf[part], nbuf[part], width)

    def _compress(self, which, buf, nbuf, width):
        g = len(which)
        c_in = min(width, self.mean.shape[1])
        b_in = min(width, buf.shape[1])
        cells = np.arange(b_in)[None, :]
        bw = (cells < nbuf[:, None]).astype(np.int64)
        w = np.concatenate([self.weight[which, :c_in], bw], axis=1)
        m = np.concatenate([self.mean[which, :c_in], buf[:, :b_in]], axis=1)
        m = np.where(w > 0, m, np.inf)
        order = np.argsort(m, axis=1, kind="stable")
        m = np.take_along_axis(m, order, axis=1)[:, :width]
        w = np.take_along_axis(w, order, axis=1)[:, :width]
        # a tie the system's float32 means may order the other way
        merged = np.concatenate([self.weight[which, :c_in] > 1,
                                 np.zeros_like(bw, bool)], axis=1)
        if merged.any():
            merged = np.take_along_axis(merged, order, axis=1)[:, :width]
            with np.errstate(invalid="ignore"):
                close = np.abs(np.diff(m, axis=1)) \
                    <= TIE_ROOM * np.abs(m[:, 1:])
            self.flagged[which] |= (close & (merged[:, 1:] | merged[:, :-1])
                                    & (w[:, 1:] > 0)).any(axis=1)
        # the pass, a centroid at a time: the limit depends on the left
        # edge alone, so a centroid runs from its first entry to the
        # last whose cumulative weight stays within it
        n = w.sum(axis=1)
        real_len = (w > 0).sum(axis=1)
        cum = np.cumsum(w, axis=1)
        csum = np.cumsum(np.where(w > 0, w * np.where(w > 0, m, 0.0), 0.0),
                         axis=1)
        stride = int(n.max(initial=0)) + 2
        flat = (np.arange(g)[:, None] * stride + cum).ravel()
        h, s = self.h, self.s
        left = np.zeros(g, np.int64)
        start = np.zeros(g, np.int64)
        osum = np.zeros((g, width))
        ow = np.zeros((g, width), np.int64)
        folded = np.zeros((g, width), np.int64)
        flag = np.zeros(g, bool)
        live = real_len > 0
        k = 0
        while live.any():
            at = np.flatnonzero(live)
            s0, wl, na = start[at], left[at], n[at]
            wf, nf = wl.astype(np.float64), na.astype(np.float64)
            top = nf - wf
            reach = s * np.sqrt(wf * top)
            lean = (2 * wf - nf) * h
            limit = reach - lean
            tail = top <= nf * h
            bound = np.where(tail, na, wl + np.floor(limit)).astype(np.int64)
            e = np.searchsorted(flat, at * stride + bound, side="right") \
                - 1 - at * width
            e = np.clip(e, s0, real_len[at] - 1)
            more = s0 + 1 < real_len[at]
            room = DECISION_ROOM * (reach + np.abs(lean) + 1)
            near = np.zeros(len(at), bool)
            for j, ok in ((e, e > s0), (e + 1, e + 1 < real_len[at])):
                jj = np.minimum(j, width - 1)
                near |= ok & (np.abs(cum[at, jj] - wl - limit) <= room)
            flag[at] |= more & ((np.abs(top - nf * h) <= DECISION_ROOM * nf)
                                | (~tail & near))
            before = np.where(s0 > 0, csum[at, np.maximum(s0 - 1, 0)], 0.0)
            osum[at, k] = csum[at, e] - before
            ow[at, k] = cum[at, e] - wl
            folded[at, k] = e - s0 + 1
            left[at] = cum[at, e]
            start[at] = e + 1
            live[at] = e + 1 < real_len[at]
            k += 1
        self.flagged[which] |= flag
        keep = min(width, self.mean.shape[1])
        if (ow[:, keep:] > 0).any():
            raise AssertionError("a compress made more centroids than "
                                 "the digest holds")
        with np.errstate(invalid="ignore", divide="ignore"):
            mean = np.where(ow > 0, osum / np.maximum(ow, 1), 0.0)
        self.mean[which] = 0.0
        self.weight[which] = 0
        self.folded[which] = 0
        self.mean[which, :keep] = mean[:, :keep]
        self.weight[which, :keep] = ow[:, :keep]
        self.folded[which, :keep] = folded[:, :keep]

    def quantiles(self, n, lo, hi, quantiles):
        """float64[K, Q] by the written interpolation rule."""
        weight = self.weight
        real = weight > 0
        left = np.cumsum(weight, axis=1) - weight
        centre = left + weight / 2.0
        nf = n.astype(np.float64)[:, None]
        pos = np.concatenate([np.zeros_like(nf), np.where(real, centre, nf),
                              nf], axis=1)
        val = np.concatenate([lo[:, None], np.where(real, self.mean,
                                                    hi[:, None]),
                              hi[:, None]], axis=1)
        out = np.empty((len(n), len(quantiles)))
        for col, q in enumerate(quantiles):
            t = ranks(n, q).astype(np.float64)[:, None] - 0.5
            b = np.clip((pos < t).sum(axis=1, keepdims=True), 1,
                        pos.shape[1] - 1)
            pa = np.take_along_axis(pos, b - 1, axis=1)
            pb = np.take_along_axis(pos, b, axis=1)
            va = np.take_along_axis(val, b - 1, axis=1)
            vb = np.take_along_axis(val, b, axis=1)
            out[:, col] = (va + (vb - va) * ((t - pa) / (pb - pa)))[:, 0]
        return out


def _digest_runs(v, first, n, delta, buffer, centroids):
    """The ``Digests`` of the runs of ``v`` that start at ``first`` and
    hold ``n`` values each, in their order."""
    digests = Digests(len(first), delta,
                      min(centroids, int(n.max(initial=1))))
    compresses = (n - 1) // buffer
    cells = np.arange(buffer)[None, :]
    for r in range(1, int(compresses.max(initial=0)) + 1):
        which = np.flatnonzero(compresses >= r)
        buf = v[first[which, None] + (r - 1) * buffer + cells]
        digests.compress(which, buf, np.full(len(which), buffer))
    rest = n - compresses * buffer
    at = first[:, None] + (compresses * buffer)[:, None] \
        + np.arange(int(rest.max(initial=0)))[None, :]
    digests.compress(np.arange(len(first)),
                     v[np.minimum(at, len(v) - 1)], rest)
    return digests


def digest_of(values, delta, buffer, centroids):
    """The ``Digests`` of one stream of values, in arrival order."""
    v = np.asarray(values, np.float32).astype(np.float64)
    return _digest_runs(v, np.zeros(1, np.int64), np.array([len(v)]),
                        delta, buffer, centroids)


def digest_streams(keys, values, delta, buffer, centroids, quantiles):
    """The t-digest of each key's values in arrival order, answered.
    ``values`` as the system takes them in (float32), ``keys`` int64.
    Returns (sorted distinct keys, counts, float64[K, Q] estimates, the
    digests' flags, the values sorted by (key, value)).  Digests of
    like counts are walked together."""
    keys = np.asarray(keys, np.int64)
    values = np.asarray(values, np.float32).astype(np.float64)
    order = np.argsort(keys, kind="stable")
    k, v = keys[order], values[order]
    first = np.flatnonzero(np.concatenate([[True], k[1:] != k[:-1]])) \
        if len(k) else np.zeros(0, np.int64)
    n = np.diff(np.concatenate([first, [len(k)]])).astype(np.int64)
    lo = np.minimum.reduceat(v, first) if len(v) else np.zeros(0)
    hi = np.maximum.reduceat(v, first) if len(v) else np.zeros(0)
    ranked = values[np.argsort(_key_value_order(keys, values))]
    est = np.empty((len(first), len(quantiles)))
    flagged = np.zeros(len(first), bool)
    # a digest too small for any two entries to merge holds its values
    # as centroids of weight 1, and the rule answers the order statistic
    few = n <= singletons_below(delta)
    for col, q in enumerate(quantiles):
        est[few, col] = ranked[first[few] + ranks(n[few], q) - 1]
    cls = np.ceil(np.log2(np.maximum(n, 1))).astype(np.int64)
    for c in np.unique(cls[~few]):
        idx = np.flatnonzero((cls == c) & ~few)
        digests = _digest_runs(v, first[idx], n[idx], delta, buffer,
                               centroids)
        est[idx] = digests.quantiles(n[idx], lo[idx], hi[idx], quantiles)
        flagged[idx] = digests.flagged
    return k[first], n, est, flagged, ranked


def singletons_below(delta):
    """The largest count at which no merge test of a compress can pass,
    with room: two entries weigh at least 2, the limit
    s sqrt(W (n - W)) - (2W - n) h is below n (s / 2 + h), and the
    tail's test n - W <= n h needs n h >= 1; three quarters of the
    count where the first bound reaches 2."""
    h = math.sin(math.pi / delta) ** 2
    s = math.sin(2 * math.pi / delta)
    return int(min(2 / (s / 2 + h), 1 / h) * 0.75)


def _key_value_order(keys, values):
    """One int64 per row that sorts as (key, value) does: the key above
    32 bits, the float32 value's bits made monotone below."""
    if len(keys) and (keys.min() < 0 or keys.max() >= 1 << 31):
        raise ValueError("keys must lie in [0, 2^31)")
    bits = values.astype(np.float32).view(np.uint32).astype(np.int64)
    mono = np.where(bits >> 31, 0xFFFFFFFF ^ bits, bits | (1 << 31))
    return (keys << 32) | mono


def rank_error(sorted_values, n, runs, estimate, q):
    """Distance of q from [#values < x, #values <= x] / n, x the float32
    estimate of run ``runs[i]``; ``sorted_values`` holds every run's
    values, sorted, run after run, ``n`` the counts of all runs."""
    first = np.concatenate([[0], np.cumsum(n)[:-1]]).astype(np.int64)
    owner = np.repeat(np.arange(len(n), dtype=np.int64), n)
    placed = _key_value_order(owner, sorted_values)
    asked = _key_value_order(runs.astype(np.int64), estimate)
    below = (np.searchsorted(placed, asked, side="left") - first[runs]) \
        / n[runs]
    upto = (np.searchsorted(placed, asked, side="right") - first[runs]) \
        / n[runs]
    return np.maximum(np.maximum(below - q, q - upto), 0.0)


def rank_room(n, q, delta, units):
    """units k1-units of rank at q, and one value."""
    return units * 2 * math.pi * math.sqrt(q * (1 - q)) / delta + 1.0 / n


class DigestChecker:
    """Counts over the windows added: quantiles asked for, and those
    missing, duplicated, unasked, off the digest or off the rank
    bound."""

    def __init__(self, config):
        self.config = config
        self.quantiles = config["quantiles"]
        self.q = len(self.quantiles)
        self.tolerance = config["estimate_tolerance"]
        self.units = config["rank_bound_units"]
        self.attempted = 0
        self.failed = 0
        self.problems = []
        self.digests = 0
        self.flagged = 0
        self.compared = 0
        self.max_rel = 0.0
        self.max_rank_units = 0.0

    def add_window(self, window_start, keys, values, got_keys, got):
        """One window, or several whose keys were made distinct."""
        c = self.config
        ref_keys, n, ref, flags, sorted_values = digest_streams(
            keys, values, c["delta"], c["buffer"], c["centroids"],
            self.quantiles)
        got_keys = np.asarray(got_keys, np.int64)
        got = np.asarray(got, np.float64).reshape(len(got_keys), self.q)
        order = np.argsort(got_keys, kind="stable")
        got_keys, got = got_keys[order], got[order]
        pos = np.searchsorted(ref_keys, got_keys)
        pos_c = np.minimum(pos, max(len(ref_keys) - 1, 0))
        known = (ref_keys[pos_c] == got_keys) if len(ref_keys) \
            else np.zeros(len(got_keys), bool)
        lead = known & np.concatenate([[True],
                                       got_keys[1:] != got_keys[:-1]])
        surplus = int((~lead).sum())
        missing = len(ref_keys) - int(lead.sum())
        at = pos_c[lead]
        est = got[lead]
        with np.errstate(divide="ignore", invalid="ignore"):
            rel = np.abs(est - ref[at]) / np.abs(ref[at])
        held = ~flags[at]
        off_digest = held[:, None] & ~(rel <= self.tolerance)
        off_rank = np.zeros_like(off_digest)
        for col, q in enumerate(self.quantiles):
            err = rank_error(sorted_values, n, at, est[:, col], q)
            room = rank_room(n[at], q, c["delta"], self.units)
            off_rank[:, col] = ~(err <= room)
            unit = 2 * math.pi * math.sqrt(q * (1 - q)) / c["delta"]
            if len(err):
                self.max_rank_units = max(self.max_rank_units, float(
                    np.max(np.maximum(err - 1.0 / n[at], 0) / unit)))
        bad = off_digest | off_rank
        failed = int(bad.sum()) + self.q * (missing + surplus)
        if failed:
            self._problem(
                f"window {window_start}: {missing} keys missing, {surplus} "
                f"rows duplicated or unasked, {int(off_digest.sum())} "
                f"quantiles off the digest (worst "
                f"{float(np.nanmax(np.where(held[:, None], rel, 0), initial=0)):.3g}"
                f" > {self.tolerance:g}), {int(off_rank.sum())} off the "
                f"rank bound, of {len(ref_keys)} expected keys")
        self.attempted += self.q * len(ref_keys)
        self.failed += failed
        self.digests += len(ref_keys)
        self.flagged += int(flags.sum())
        kept = rel[held]
        kept = kept[np.isfinite(kept)]
        self.compared += kept.size
        self.max_rel = max(self.max_rel, float(kept.max(initial=0.0)))
        return failed

    def add_stray_window(self, window_start, rows):
        self.failed += self.q * rows
        self._problem(f"window {window_start}: {rows} rows for a window "
                      f"no event fell into")

    def _problem(self, text):
        if len(self.problems) < 20:
            self.problems.append(text)

    def verdict(self):
        problems = list(self.problems)
        if not self.digests:
            return problems or ["nothing to compare"], {}
        return problems, {"digests": self.digests,
                          "flagged_digests": self.flagged,
                          "quantiles_compared": self.compared,
                          "max_rel_err": self.max_rel,
                          "tolerance": self.tolerance,
                          "max_rank_err_units": self.max_rank_units,
                          "rank_bound_units": self.units}


#: windows whose digests are walked together (keys made distinct by
#: the window's place in the group)
WINDOWS_AT_ONCE = 16


def check(config, emitted, results):
    """Every sliding window some emitted event fell into, the partial
    ones at both ends of the stream too.  ``emitted``: the source's
    entries, one per slide period (pane) with its (keys, values) in
    arrival order; ``results``: {start of the window's LAST pane:
    result columns (key, that start, p50, p99)} as the sink kept
    them.  Windows go to the checker in groups of ``WINDOWS_AT_ONCE``,
    a key k of the group's i-th window as i * key_space + k."""
    slide = config["slide_ms"]
    panes_per_window = config["window_size_ms"] // slide
    span = config["key_space"]
    checker = DigestChecker(config)
    panes = {window: columns for window, _, columns in emitted}
    results = dict(results)
    group = []

    def flush():
        if not group:
            return
        label = f"{group[0][0]}..{group[-1][0]}"
        parts = list(zip(*[g[1:] for g in group]))
        checker.add_window(label, *(np.concatenate(p) for p in parts))
        group.clear()

    if panes:
        for last in range(min(panes), max(panes) + panes_per_window):
            held = [panes[p]() for p in
                    range(last - panes_per_window + 1, last + 1)
                    if p in panes]
            if not held:
                continue
            at = len(group) * span
            keys = np.concatenate([np.asarray(k, np.int64) for k, _ in held])
            values = np.concatenate([np.asarray(v, np.float64)
                                     for _, v in held])
            got = results.pop(last * slide, None)
            if got is None:
                got_keys, got_q = np.zeros(0, np.int64), \
                    np.empty((0, checker.q))
            else:
                got_keys = np.asarray(got[0], np.int64)
                # a key outside the key space matches no asked-for key
                got_keys = np.where((got_keys >= 0) & (got_keys < span),
                                    got_keys + at, -1)
                got_q = np.stack([np.asarray(col, np.float64)
                                  for col in got[2:]], axis=1)
            group.append((last * slide, keys + at, values, got_keys, got_q))
            if len(group) == WINDOWS_AT_ONCE:
                flush()
    flush()
    for window_start, got in results.items():
        checker.add_stray_window(window_start, len(got[0]))
    problems, facts = checker.verdict()
    return {"attempted": checker.attempted, "failed": checker.failed,
            "problems": problems, "facts": facts}

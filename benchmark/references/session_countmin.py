"""Per-key Count-Min sketches over event-time session windows: the
plain reference and the comparison that decides ``correct``.  Found by
the configuration's ``reference`` name; ``check(config, emitted,
results)`` is what the harness calls.  numpy in int64, nothing of the
system and no hash; ``tests/session_countmin_reference.py`` is this
file, byte for byte.

The reference sessionises the events itself: every period's rows
concatenated, sorted by (key, timestamp), a session cut where two
timestamps of a key lie MORE than the gap apart (windows that abut
merge: ``[t, t + gap)`` and ``[t + gap, ...)`` are one session, as the
system's ``TimeWindow.intersects`` has it), each session
``[first timestamp, last timestamp + gap)`` with its exact count and
the exact count of every tracked item in it.

Against the rows the job emitted, ``(key, period, session start,
session end, total, est_0 .. est_W-1)``:

- **the sessions**: the same set of (key, start, end), each once.  A
  session that is missing, a row for a session no event made (a
  session whose growth at the front or whose merge was dropped has
  another start or end: it is both) and a second row for a session
  are ``1 + W`` failures each.
- **the total** equals the session's exact count: the side counter is
  exact, one failure where it is not.
- **est >= exact, always**: every increment lands in all ``depth``
  rows of the table and a merge adds tables, so a cell never holds
  less than the item's own count.  An implementation that skips part
  of the table, loses a merged session's table or reads the wrong
  slot under-counts somewhere: one failure a pair.
- **est <= exact + floor(e / width x total)** for all but a share
  delta = e^-depth of the (session, tracked item) pairs: Cormode &
  Muthukrishnan (J. Algorithms 2005), Theorem 1, with eps = e / width:
  the expected overshoot of one row is (total - exact) / width, by
  Markov's inequality it passes e times that with probability under
  1 / e, and all ``depth`` independent rows pass it with probability
  under e^-depth.  The bound is taken per pair and the share twice:
  **over all pairs**, and **over the pairs of the large sessions**,
  those whose bound is at least 1 (a total of width / e = 754 events
  and more at width 2,048; which sessions are large says nothing
  about the hashes, so the theorem holds for them alone), once they
  number 1 / delta pairs, so that one chance overshoot is within the
  share.  The first catches an implementation that answers with the
  total or keeps a table of a few columns: nearly every pair of a
  session of more than one item then overshoots.  It cannot see a
  fault that small sessions do not show: nearly all of this mix's
  pairs belong to sessions of a few events, whose bound is
  floor(0.0013 x total) = 0 and whose estimate is exact in any table
  unless two of a handful of items collide in every row read, so an
  implementation that reads ONE of the ``depth`` rows (or loses the
  others in a merge) stays under delta there.  The second catches
  that: among the large sessions' pairs one row alone is over its
  bound in 3% to 25% (the share swings with which heavy items
  collide in that row), all ``depth`` in none to speak of.  Neither sees a table of half the width: the
  paper's delta leaves room for it.  When a share passes delta every
  pair it was taken over that is over its bound is a failure.

``attempted`` = sessions x (1 + W).
"""

from __future__ import annotations

import math

import numpy as np


def sessions_of(keys, ts, items, watch, gap):
    """The sessions of a stream: int64 columns ``key``, ``start``,
    ``end``, ``total`` and ``exact`` (int64[S, W]: the count of each
    tracked item), sessions in (key, start) order."""
    order = np.lexsort((ts, keys))
    k, t, item = keys[order], ts[order], items[order]
    cut = np.ones(len(k), bool)
    cut[1:] = (k[1:] != k[:-1]) | (np.diff(t) > gap)
    first = np.flatnonzero(cut)
    last = np.append(first[1:], len(k)) - 1
    exact = np.stack(
        [np.add.reduceat((item == w).astype(np.int64), first)
         for w in watch], axis=1) if len(first) else \
        np.zeros((0, len(watch)), np.int64)
    return {"key": k[first], "start": t[first], "end": t[last] + gap,
            "total": last - first + 1, "exact": exact}


def check(config, emitted, results):
    """``emitted``: the source's ``Emitted`` entries, one per period,
    ``columns()`` = (keys, items, timestamps, tracked items);
    ``results``: {period start: result columns (key, period, session
    start, session end, total, est_0 ..)} as the sink kept them."""
    watch = None
    held = []
    for _, _, columns in emitted:
        keys, items, ts, watch = columns()
        held.append((keys, items, ts))
    if not held:
        return {"attempted": 0, "failed": 0,
                "problems": ["nothing to compare"], "facts": {}}
    w = len(watch)
    ref = sessions_of(*(np.concatenate([h[i] for h in held]).astype(np.int64)
                        for i in (0, 2, 1)), watch, config["gap_ms"])
    n = len(ref["key"])
    attempted = n * (1 + w)
    got = [np.concatenate([np.asarray(cols[c], np.int64)
                           for cols in results.values()])
           for c in range(4 + 1 + w)] if results else \
        [np.zeros(0, np.int64)] * (5 + w)
    g_key, g_period, g_start, g_end, g_total = got[:5]
    g_est = np.stack(got[5:], axis=1) if w else np.zeros((len(g_key), 0))
    problems = []
    failed = 0
    # a row's period is the one its session's last millisecond is in
    wrong_period = g_period != (g_end - 1) // config["window_ms"] \
        * config["window_ms"]
    # one id per distinct (key, start, end) of both sides; the first
    # emitted row of each session asked for is compared, every other
    # emitted row is one too many
    ref_id, got_id = _ids((ref["key"], ref["start"], ref["end"]),
                          (g_key, g_start, g_end))
    ref_row = np.full(len(ref_id) + len(got_id), -1, np.int64)
    ref_row[ref_id] = np.arange(n)
    at = ref_row[got_id]
    first = np.zeros(len(got_id), bool)
    first[np.unique(got_id, return_index=True)[1]] = True
    lead = (at >= 0) & first
    surplus = int((~lead).sum())
    missing = n - int(lead.sum())
    if missing or surplus:
        failed += (1 + w) * (missing + surplus)
        problems.append(f"{missing} sessions missing, {surplus} rows "
                        f"duplicated or for a session no event made, of "
                        f"{n} sessions")
    if wrong_period[lead].any():
        bad = int(wrong_period[lead].sum())
        failed += bad
        problems.append(f"{bad} sessions under another period than "
                        f"their end's")
    at = at[lead]
    total, exact = ref["total"][at], ref["exact"][at]
    est = g_est[lead]
    bad_total = int((g_total[lead] != total).sum())
    if bad_total:
        failed += bad_total
        problems.append(f"{bad_total} totals differ from the exact count")
    # a Count-Min sketch never under-counts
    under = int((est < exact).sum())
    if under:
        failed += under
        problems.append(f"{under} estimates below the exact count")
    # ... and over-counts by more than eps x total in a share delta,
    # of all pairs and of the large sessions' (a bound of 1 and more)
    eps = math.e / config["width"]
    delta = math.exp(-config["depth"])
    bound = np.floor(eps * total)
    over = est > exact + bound[:, None]
    large = bound >= 1
    pairs, large_pairs = int(over.size), int(over[large].size)
    over_n, large_over = int(over.sum()), int(over[large].sum())
    beyond = np.zeros(over.shape, bool)
    if pairs and over_n > delta * pairs:
        beyond |= over
        problems.append(f"{over_n} of {pairs} estimates beyond exact + "
                        f"floor(e / {config['width']} x total): a share "
                        f"of {over_n / pairs:.4f} > e^-{config['depth']}")
    if large_pairs * delta >= 1 and large_over > delta * large_pairs:
        beyond[large] |= over[large]
        problems.append(f"{large_over} of {large_pairs} estimates of "
                        f"sessions whose bound is at least 1 beyond exact "
                        f"+ floor(e / {config['width']} x total): a share "
                        f"of {large_over / large_pairs:.4f} > "
                        f"e^-{config['depth']}")
    failed += int(beyond.sum())
    inexact = int((est != exact).sum())
    facts = {"sessions": n, "pairs_compared": pairs,
             "estimates_over_bound": over_n,
             "over_bound_share": over_n / pairs if pairs else 0.0,
             "allowed_share": delta,
             "large_pairs_compared": large_pairs,
             "large_estimates_over_bound": large_over,
             "large_over_bound_share":
                 large_over / large_pairs if large_pairs else 0.0,
             "estimates_inexact": inexact,
             "worst_overshoot": int((est - exact).max(initial=0)),
             "largest_session": int(ref["total"].max(initial=0)),
             "events": int(ref["total"].sum())}
    return {"attempted": attempted, "failed": failed,
            "problems": problems[:20], "facts": facts}


def _ids(ref, got):
    """Two sets of (key, start, end) columns numbered together: equal
    triples get equal ids, ids below the number of rows."""
    n = len(ref[0])
    key, start, end = (np.concatenate([r, g]) for r, g in zip(ref, got))
    order = np.lexsort((end, start, key))
    new = np.ones(len(order), bool)
    new[1:] = ((key[order][1:] != key[order][:-1])
               | (start[order][1:] != start[order][:-1])
               | (end[order][1:] != end[order][:-1]))
    ids = np.empty(len(order), np.int64)
    ids[order] = np.cumsum(new) - 1
    return ids[:n], ids[n:]

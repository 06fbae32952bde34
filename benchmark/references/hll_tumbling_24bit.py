"""``hll_tumbling`` for key spaces up to 2^24: the same reference and
the same comparison, word for word, found under a name of its own
because ``hll_tumbling.exact_distinct`` refuses keys from 2^23 on and
``hll_10m`` draws them below 10,000,000.

The exact count packs ``key << 40 | user`` into one uint64: 24 bits of
key beside 40 of user fill it exactly, so nothing but the guard
changes.  This module loads its own instance of ``hll_tumbling`` (the
loader makes one per load) and gives that instance the wider guard;
``check`` and everything it calls are that instance's.
"""

from __future__ import annotations

import numpy as np

import loader

_base = loader.load_module("references", "hll_tumbling")


def exact_distinct(keys, users):
    """(sorted keys, exact distinct users per key) of one window."""
    if int(keys.max()) >= (1 << 24) or int(users.max()) >= (1 << 40) \
            or int(keys.min()) < 0 or int(users.min()) < 0:
        raise ValueError("keys must be < 2^24 and users < 2^40")
    pairs = np.unique((keys.astype(np.uint64) << np.uint64(40))
                      | users.astype(np.uint64))
    k, c = np.unique(pairs >> np.uint64(40), return_counts=True)
    return k.astype(np.int64), c.astype(np.int64)


_base.exact_distinct = exact_distinct
check = _base.check
HllChecker = _base.HllChecker

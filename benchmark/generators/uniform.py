"""Keys uniform over the key space: ``chip_smoke.py``'s draw."""

import numpy as np


def draw(rng, n, key_space, params):
    return rng.integers(0, key_space, n, dtype=np.int64)

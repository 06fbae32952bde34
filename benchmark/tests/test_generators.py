"""Both key distributions are deterministic in the seed; Zipf 0.99
over 1M keys puts 1/H = 6.50% of the draws on its top key."""

import numpy as np
import pytest

import loader


@pytest.mark.parametrize("name,params", [("uniform", {}),
                                         ("zipf", {"exponent": 0.99})])
def test_same_seed_same_keys(name, params):
    gen = loader.load_module("generators", name)
    a = gen.draw(np.random.default_rng(7), 50_000, 1_000_000, params)
    b = gen.draw(np.random.default_rng(7), 50_000, 1_000_000, params)
    c = gen.draw(np.random.default_rng(8), 50_000, 1_000_000, params)
    assert a.dtype == np.int64 and a.shape == (50_000,)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 1_000_000


def test_zipf_top_key_share_and_seeded_hot_set():
    gen = loader.load_module("generators", "zipf")
    keys = gen.draw(np.random.default_rng(1), 2_000_000, 1_000_000,
                    {"exponent": 0.99})
    values, counts = np.unique(keys, return_counts=True)
    top = counts.max() / len(keys)
    assert 0.060 <= top <= 0.070
    # rank -> key is a permutation from the seed: the hot key moves
    other = gen.draw(np.random.default_rng(2), 200_000, 1_000_000,
                     {"exponent": 0.99})
    v2, c2 = np.unique(other, return_counts=True)
    assert values[counts.argmax()] != v2[c2.argmax()]


def test_uniform_covers_the_key_space_evenly():
    gen = loader.load_module("generators", "uniform")
    keys = gen.draw(np.random.default_rng(3), 1_000_000, 1000, {})
    counts = np.bincount(keys, minlength=1000)
    assert counts.min() > 800 and counts.max() < 1200

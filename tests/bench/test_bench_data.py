"""The seeded generators: the same seed gives the same inputs."""
import numpy as np
import pytest

from bench.lib import data

BIG = 2**33 + 12345          # seeds may exceed 32 bits


def test_rows_are_a_function_of_the_seed():
    a = np.asarray(data.slab_rows(data.key_for(BIG, 1, 0), 512, 30, 0.05))
    b = np.asarray(data.slab_rows(data.key_for(BIG, 1, 0), 512, 30, 0.05))
    c = np.asarray(data.slab_rows(data.key_for(BIG + 1, 1, 0), 512, 30,
                                  0.05))
    assert a.shape == (512, 30) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_seed_words_above_32_bits_count():
    k1, k2 = data.root_key(5), data.root_key(5 + 2**32)
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    with pytest.raises(ValueError):
        data.root_key(-1)


def test_anomaly_rate_is_a_parameter():
    X = np.asarray(data.slab_rows(data.key_for(3), 20_000, 8, 0.25))
    # anomalies fill a box; target rows sit near the diagonal band
    w = np.ones(8) / np.sqrt(8)
    off_band = np.linalg.norm(X - np.outer(X @ w, w), axis=1) > 3.0
    assert 0.2 < off_band.mean() < 0.3


def test_feasible_gamma_meets_box_and_equality():
    m = 4096
    g = np.asarray(data.feasible_gamma(data.key_for(1), m, total=0.5,
                                       lo=-10 / m, hi=2 / m), np.float64)
    assert abs(g.sum() - 0.5) < 1e-5
    assert g.min() > 0 and g.max() <= 2 / m


def test_poisson_gaps_are_the_same_set_for_every_seed():
    g = data.poisson_gaps(10_000, 500.0)
    assert np.all(g > 0) and abs(g.mean() - 1 / 500.0) < 1e-4
    r1 = np.random.default_rng([1, 6]).permutation(g)
    r2 = np.random.default_rng([2, 6]).permutation(g)
    assert not np.array_equal(r1, r2)
    assert np.array_equal(np.sort(r1), np.sort(r2))

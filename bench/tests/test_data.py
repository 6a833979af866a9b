"""The benchmark's copy of the paper's generator (Sec. 5.1), found by the
name a configuration gives it."""
import numpy as np
import pytest

from bench import data
from bench.generators import exponential


def test_paper_definition_at_a_fixed_seed():
    # exponential(lambda = 40) in every dimension, clipped to [0, 1], float32
    seed = 2 ** 31 + 3
    want = np.clip(
        np.random.default_rng(seed).exponential(1 / 40.0, size=(5000, 16)), 0, 1
    ).astype(np.float32)
    got = exponential.exponential_dataset(5000, 16, 40.0, seed)
    assert got.dtype == np.float32 and np.array_equal(got, want)
    assert abs(got.mean() - 1 / 40.0) < 1e-3
    assert got.min() >= 0.0 and got.max() <= 1.0


def test_same_seed_same_data_other_seed_other_data():
    cfg = {"generator": "exponential", "lambda": 40.0, "clip": [0.0, 1.0],
           "num_points": 1000, "num_dims": 64}
    a, b, c = data.generate(cfg, 7), data.generate(cfg, 7), data.generate(cfg, 8)
    assert a.shape == (1000, 64) and np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_matches_the_program_generator():
    from repro.data.synthetic import exponential_dataset

    assert np.array_equal(
        exponential.exponential_dataset(2000, 16, 40.0, 11),
        exponential_dataset(2000, 16, seed=11),
    )


def test_unknown_generator_is_refused():
    with pytest.raises(ValueError, match="unknown generator"):
        data.generate({"generator": "no_such_generator"}, 1)

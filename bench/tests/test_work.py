"""The roofline's work model (``bench/work.py``)."""
import itertools

import numpy as np
import pytest

from bench import work


def _brute_candidates(x, eps, k):
    dims = np.argsort(-x.astype(np.float64).var(axis=0), kind="stable")[:k]
    c = np.floor(x[:, dims].astype(np.float64) / eps).astype(np.int64)
    adjacent = (np.abs(c[:, None, :] - c[None, :, :]) <= 1).all(axis=2)
    return int(adjacent.sum())


@pytest.mark.parametrize("n,dims,eps,k", [
    (600, 16, 0.03, 6), (400, 64, 0.16, 6), (300, 3, 0.2, 3), (200, 8, 0.05, 2),
])
def test_plain_grid_candidates_equal_enumeration(n, dims, eps, k):
    x = np.clip(np.random.default_rng(n).exponential(1 / 40.0, (n, dims)), 0, 1)
    x = x.astype(np.float32)
    assert work.plain_grid_candidates(x, eps, k) == _brute_candidates(x, eps, k)


def test_work_and_least_time():
    w = work.tile_eval_work(1000, 16, 10 ** 6, 5000, "pairs")
    assert w == {"flops": 2.0 * 16 * 10 ** 6, "bytes": 4.0 * 1000 * 16 + 8 * 5000}
    assert work.tile_eval_work(1000, 16, 0, 0, "count")["bytes"] == 4.0 * 1000 * 17
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
    assert work.least_time(w, peak) == max(w["flops"] / 1e12, w["bytes"] / 1e9)


def test_offsets_cover_the_3_to_the_k_neighbourhood():
    # two points in diagonal neighbour cells of every indexed dimension
    x = np.array([[0.01] * 4, [0.04] * 4], np.float32)
    assert work.plain_grid_candidates(x, 0.03, 4) == 4
    assert len(list(itertools.product((-1, 0, 1), repeat=4))) == 81

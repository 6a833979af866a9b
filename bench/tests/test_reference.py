"""The plain reference and the comparison that decides ``correct``."""
import numpy as np
import pytest

from bench import reference
from bench.generators.exponential import exponential_dataset


@pytest.fixture(scope="module")
def case():
    x = exponential_dataset(1500, 16, 40.0, 21)
    eps = 0.05
    x64 = x.astype(np.float64)
    d2 = ((x64[:, None, :] - x64[None]) ** 2).sum(-1)
    ref = reference.build(x, eps, block=256)
    return x, eps, d2, ref


def test_counts_equal_float64_brute_force(case):
    x, eps, d2, ref = case
    assert np.array_equal(ref.counts, (d2 <= eps * eps).sum(1))
    # every window pair carries its exact float64 gap
    norms = (x.astype(np.float64) ** 2).sum(1)
    g = (d2[ref.win_i, ref.win_j] - eps * eps) / (
        reference.U * (norms[ref.win_i] + norms[ref.win_j])
    )
    assert np.allclose(ref.win_g, g, rtol=1e-9, atol=1e-6)


def test_exact_answers_read_zero(case):
    x, eps, d2, ref = case
    a, b = np.nonzero(d2 <= eps * eps)
    pairs = np.stack([a, b], 1).astype(np.int32)
    assert reference.readings(ref, ref.counts, pairs) == {"band": 0.0}


def test_one_pair_past_the_boundary_reads_its_gap(case):
    x, eps, d2, ref = case
    outside = np.flatnonzero(ref.win_g > 0)
    k = outside[np.argmin(ref.win_g[outside])]
    i, j, g = ref.win_i[k], ref.win_j[k], ref.win_g[k]
    counts = ref.counts.copy()
    counts[i] += 1
    assert reference.count_band(ref, counts) == pytest.approx(g)
    a, b = np.nonzero(d2 <= eps * eps)
    pairs = np.concatenate([np.stack([a, b], 1), [[i, j]]]).astype(np.int32)
    assert reference.pair_band(ref, pairs) == pytest.approx(g)


def test_gross_faults_read_the_whole_window(case):
    x, eps, d2, ref = case
    a, b = np.nonzero(d2 <= eps * eps)
    pairs = np.stack([a, b], 1).astype(np.int32)
    W = reference.WINDOW
    assert reference.count_band(ref, ref.counts * 2) == W
    assert reference.count_band(ref, ref.counts[:-1]) == W
    assert reference.pair_band(ref, np.concatenate([pairs, pairs[:1]])) == W
    far = pairs.copy()
    far[0, 1] = np.argmax(d2[far[0, 0]])
    assert reference.pair_band(ref, far) == W
    bad = pairs.copy()
    bad[0, 1] = x.shape[0]
    assert reference.pair_band(ref, bad) == W
    # the histograms of both columns are compared, not only the counts
    assert reference.readings(ref, ref.counts, pairs[1:])["band"] == W

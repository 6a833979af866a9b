"""Result-set batching (paper Sec. 3.2.2)."""
import numpy as np

from repro.core.batching import (
    batch_ranges, compute_num_batches, estimate_result_size,
)
from repro.core.grid import build_grid, build_tile_plan
from repro.core import SelfJoinConfig, self_join
from repro.data import exponential_dataset
from repro.kernels import ops


def test_min_three_batches():
    # the paper always pipelines with >= 3 streams/batches
    assert compute_num_batches(10, batch_size=10**8) == 3
    assert compute_num_batches(0, batch_size=10**8) == 3


def test_batch_count_scales_with_result_size():
    assert compute_num_batches(10**9, batch_size=10**8) == 10
    assert compute_num_batches(3 * 10**8 + 1, batch_size=10**8) == 4


def test_batch_ranges_cover_disjointly():
    ranges = list(batch_ranges(1000, 7))
    assert ranges[0][0] == 0 and ranges[-1][1] == 1000
    for (a0, a1), (b0, b1) in zip(ranges, ranges[1:]):
        assert a1 == b0 and a0 < a1


def test_estimate_within_factor_of_truth():
    d = exponential_dataset(1500, 16, seed=30)
    eps = 0.06
    grid = build_grid(d, eps, 4)
    plan = build_tile_plan(grid, 16, sortidu=True)
    tiles, tlen = ops.make_tiles(
        grid.pts_sorted, plan.tile_start, plan.tile_len, 16, 8
    )
    est = estimate_result_size(
        tiles, tlen, plan, eps=eps, backend="jnp",
        sample_frac=0.2,
    )
    truth = self_join(d, SelfJoinConfig(eps=eps, k=4, tile_size=16,
                                        dim_block=8)).stats.num_results
    assert truth / 3 <= est <= truth * 3  # sampling estimate, same magnitude


def test_pairs_mode_uses_batches_and_matches():
    # end-to-end through the batched pairs path with a small batch size
    d = exponential_dataset(400, 16, seed=31)
    cfg = SelfJoinConfig(eps=0.08, k=4, tile_size=16, dim_block=8,
                         batch_size=50, min_batches=3)
    res = self_join(d, cfg, return_pairs=True)
    ref = self_join(d, SelfJoinConfig(eps=0.08, k=4, tile_size=16, dim_block=8))
    assert res.stats.num_results == ref.stats.num_results
    np.testing.assert_array_equal(res.counts, ref.counts)

"""Paper Fig. 7 / Sec. 5.6: the memory-operation model used to select k,
plus the memory-layout ops the engine moved off the host: tile gathering
(per-tile Python loop vs vectorized gather vs in-jit device gather).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import record, timeit
from repro.core.grid import build_grid, build_tile_plan
from repro.core.tuning import estimate_k_costs, select_k
from repro.data import exponential_dataset, paper_dataset
from repro.kernels import ops


def _make_tiles_loop(pts_sorted, tile_start, tile_len, tile_size, dim_block):
    """The pre-engine per-tile host loop, kept here as the baseline."""
    num_tiles = tile_start.shape[0]
    n = pts_sorted.shape[1]
    tiles = np.zeros((max(num_tiles, 1), tile_size, n), dtype=np.float32)
    for i in range(num_tiles):
        s, l = int(tile_start[i]), int(tile_len[i])
        tiles[i, :l] = pts_sorted[s : s + l]
    return ops.block_dims(tiles, dim_block), tile_len.astype(np.int32)


def run():
    d = paper_dataset("Syn16D2M", 0.004)
    ests = estimate_k_costs(d, eps=0.05, ks=[1, 2, 4, 6, 8, 10, 12])
    for e in ests:
        record(
            f"fig7/Syn16D2M/k={e.k}", 0.0,
            f"search_ops={e.search_ops:.3e};compare_ops={e.compare_ops:.3e};"
            f"total={e.total_ops:.3e};cells={e.num_cells}",
        )
    k = select_k(d, 0.05, ks=[1, 2, 4, 6, 8, 10, 12])
    record("fig7/Syn16D2M/selected_k", 0.0, f"k={k}")

    # tiling memops: host loop vs vectorized gather vs device gather ------
    import jax.numpy as jnp

    dd = exponential_dataset(20_000, 16, seed=0)
    grid = build_grid(dd, 0.05, 4)
    plan = build_tile_plan(grid, 32, sortidu=True)
    args = (grid.pts_sorted, plan.tile_start, plan.tile_len, 32, 8)
    loop_us = timeit(lambda: _make_tiles_loop(*args), repeats=3)
    vec_us = timeit(lambda: ops.make_tiles(*args), repeats=3)
    pts_j = jnp.asarray(grid.pts_sorted)
    ts_j = jnp.asarray(plan.tile_start, jnp.int32)
    tl_j = jnp.asarray(plan.tile_len, jnp.int32)

    def dev():
        ops.make_tiles_device(
            pts_j, ts_j, tl_j, tile_size=32, dim_block=8
        ).block_until_ready()

    dev()  # compile outside timing
    dev_us = timeit(dev, repeats=3)
    loop_tiles, _ = _make_tiles_loop(*args)
    vec_tiles, _ = ops.make_tiles(*args)
    assert np.array_equal(loop_tiles, vec_tiles), "tiling layouts diverged"
    record("memops/make_tiles/host_loop", loop_us, f"tiles={plan.num_tiles}")
    record("memops/make_tiles/vectorized", vec_us,
           f"speedup={loop_us / max(vec_us, 1e-9):.2f}x")
    record("memops/make_tiles/device_jit", dev_us,
           f"speedup={loop_us / max(dev_us, 1e-9):.2f}x")


if __name__ == "__main__":
    run()

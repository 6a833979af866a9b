"""Paper Table 2: kernel compute time vs host-side overhead fraction.

The paper shows host-side operations (transfers, table construction) are
0.69-1.8% of the response time in high dimensions.  Here: evaluation
(kernel) time vs index construction + planning + scatter (host side).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import record
from repro.core import SelfJoinConfig
from repro.core.grid import build_grid, build_tile_plan
from repro.core.reorder import variance_reorder
from repro.kernels import ops
from repro.data import paper_dataset


def run():
    for name, scale, eps in [("Syn16D2M", 0.004, 0.05), ("SuSy", 0.0012, 0.02)]:
        d = paper_dataset(name, scale)
        cfg = SelfJoinConfig(eps=eps, k=6, tile_size=32)

        t0 = time.perf_counter()
        work, _ = variance_reorder(d, cfg.sample_frac)
        grid = build_grid(work, eps, cfg.k)
        plan = build_tile_plan(grid, cfg.tile_size, sortidu=True)
        tiles, tlen = ops.make_tiles(
            grid.pts_sorted, plan.tile_start, plan.tile_len,
            cfg.tile_size, cfg.dim_block,
        )
        t_host = time.perf_counter() - t0

        t0 = time.perf_counter()
        counts, _ = ops.tile_counts(
            tiles, tlen, plan.pair_a, plan.pair_b,
            eps=eps, shortc=True,
        )
        t_kernel = time.perf_counter() - t0

        t0 = time.perf_counter()
        out = np.zeros(d.shape[0], np.int64)
        lane = np.arange(cfg.tile_size, dtype=np.int64)
        idx = plan.tile_start[plan.pair_a].astype(np.int64)[:, None] + lane
        valid = lane[None, :] < plan.tile_len[plan.pair_a][:, None]
        np.add.at(out, np.where(valid, idx, 0),
                  np.where(valid, counts.astype(np.int64), 0))
        t_table = time.perf_counter() - t0

        total = t_host + t_kernel + t_table
        overhead = 100.0 * (t_host + t_table) / total
        record(
            f"table2/{name}", total * 1e6,
            f"compute_s={t_kernel:.3f};total_s={total:.3f};overhead={overhead:.1f}%",
        )


if __name__ == "__main__":
    run()

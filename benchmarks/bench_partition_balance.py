"""Paper Fig. 10: computation time histogram of entity-partitioned batches.

Partitions the query set into N_b batches and times each batch's join
against the full dataset; near-equal batch times (small max/min spread) are
what make round-robin assignment near-ideal (paper Sec. 6.2).

Also exercises the grid-indexed distributed tier: the per-batch candidate
cost estimate of ``DistributedSelfJoinEngine`` drives round-robin vs.
``assign_dynamic`` (LPT) worker loads, and the engine's candidate filter
ratio vs. the dense ring is recorded (the repaired-index effect).

``--tiny`` (or BENCH_SMOKE=1) shrinks the datasets so `make bench-smoke`
keeps this path alive at CI scale.  Emits ``BENCH_partition.json`` for the
regression gate: the worker-load balance facts (round-robin and LPT max
loads, LPT never worse than round-robin) are deterministic contracts, the
per-figure wall times are slack-gated metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.common import emit_bench_json, record
from repro.core import (
    DistributedSelfJoinEngine,
    SelfJoinConfig,
    assign_dynamic,
    make_partition,
)
from repro.core.grid import adjacent_cell_pairs, build_grid, build_tile_plan
from repro.core.reorder import variance_reorder
from repro.kernels import ops
from repro.data import paper_dataset

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "partition_times.json")

FULL_CELLS = [("Syn16D2M", 0.002, 0.05, 32), ("SuSy", 0.0008, 0.02, 32)]
TINY_CELLS = [("Syn16D2M", 0.0005, 0.05, 8), ("SuSy", 0.0002, 0.02, 8)]


def batch_times(d, eps, k, n_batches, tile_size=32, dim_block=32):
    work, _ = variance_reorder(d)
    grid = build_grid(work, eps, k)
    plan = build_tile_plan(grid, tile_size, sortidu=True)
    tiles, tlen = ops.make_tiles(
        grid.pts_sorted, plan.tile_start, plan.tile_len, tile_size, dim_block
    )
    part = make_partition(plan.num_pairs, 1, n_batches)
    times = []
    for b in range(part.num_batches):
        lo, hi = part.query_range(b)
        t0 = time.perf_counter()
        ops.tile_counts(
            tiles, tlen, plan.pair_a[lo:hi], plan.pair_b[lo:hi],
            eps=eps, shortc=True,
        )
        times.append(time.perf_counter() - t0)
    return np.asarray(times)


def dist_balance(d, eps, k, workers=8, n_batches=32):
    """Round-robin vs. assign_dynamic worker loads on the indexed engine."""
    cfg = SelfJoinConfig(eps=eps, k=k)
    rr = DistributedSelfJoinEngine(
        d, cfg, num_workers=workers, num_batches=n_batches
    )
    res = rr.count()
    # dynamic loads from the same memoized cost estimates -- no need to
    # build a second engine just to re-run the LPT assignment
    costs = rr.estimate_batch_costs()
    dyn_assign = assign_dynamic(costs, workers)
    dyn_loads = np.zeros(workers)
    np.add.at(dyn_loads, dyn_assign, costs)
    return rr.worker_loads(), dyn_loads, res.stats


def run(tiny: bool = False):
    results = {}
    contracts: dict = {}
    metrics: dict = {}
    info: dict = {"tiny": tiny}
    for name, scale, eps, nb in (TINY_CELLS if tiny else FULL_CELLS):
        d = paper_dataset(name, scale)
        times = batch_times(d, eps, 6, nb)
        results[name] = times.tolist()
        record(
            f"fig10/{name}/Nb={nb}", float(times.sum() * 1e6),
            f"min={times.min():.3f}s;max={times.max():.3f}s;"
            f"rel_spread={(times.max() - times.min()) / times.mean():.3f}",
        )
        rr_loads, dyn_loads, stats = dist_balance(d, eps, 6, n_batches=nb)
        record(
            f"fig10/{name}/dist-balance/p=8", float(rr_loads.max()),
            f"rr_max={rr_loads.max():.0f};dyn_max={dyn_loads.max():.0f};"
            f"candidates={stats.num_candidates};"
            f"dense={stats.num_candidates_dense};"
            f"filter_ratio={stats.candidate_filter_ratio:.3f}",
        )
        # worker loads come from the memoized candidate-cost estimates --
        # deterministic for a fixed dataset, so they gate exactly
        contracts[f"nb/{name}"] = nb
        contracts[f"rr_max_load/{name}"] = int(round(float(rr_loads.max())))
        contracts[f"lpt_max_load/{name}"] = int(round(float(dyn_loads.max())))
        contracts[f"lpt_max_le_rr/{name}"] = bool(
            dyn_loads.max() <= rr_loads.max()
        )
        metrics[f"batch_wall_us/{name}"] = float(times.sum() * 1e6)
        info[f"rel_spread/{name}"] = round(
            float((times.max() - times.min()) / times.mean()), 3
        )
        info[f"filter_ratio/{name}"] = round(
            float(stats.candidate_filter_ratio), 3
        )
    emit_bench_json("partition", contracts=contracts, metrics=metrics, info=info)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(results, f)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--tiny", action="store_true",
        default=os.environ.get("BENCH_SMOKE") == "1",
        help="CI-scale configuration (also via BENCH_SMOKE=1)",
    )
    run(tiny=ap.parse_args().tiny)

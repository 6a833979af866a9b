"""The work a self-join has to do, reckoned from the data alone.

The roofline of the tile evaluation counts the same work whatever
implements it, so nothing here reads the program's counters:

* candidates -- ordered point pairs whose cells are adjacent in a plain
  grid of eps-wide cells over the ``k`` highest-variance dimensions (the
  paper's index, Sec. 3.2.1 and 4.1-4.2, without SORTIDU or tiling);
* flops -- ``2 * n`` per candidate pair (a difference and a multiply-add
  per dimension);
* bytes -- the points read once plus the result written once.
"""
from __future__ import annotations

import itertools

import numpy as np


def plain_grid_candidates(x: np.ndarray, eps: float, k: int) -> int:
    """Ordered point pairs in adjacent cells of an eps-wide grid over the
    ``k`` highest-variance dimensions (self pairs included)."""
    x = np.asarray(x, dtype=np.float64)
    if x.shape[0] == 0:
        return 0
    k = min(k, x.shape[1])
    dims = np.argsort(-x.var(axis=0), kind="stable")[:k]
    coords = np.floor(x[:, dims] / eps).astype(np.int64)
    coords -= coords.min(axis=0) - 1  # keep every neighbour coordinate >= 0
    span = coords.max(axis=0) + 2
    strides = np.cumprod(np.concatenate([[1], span[:-1]])).astype(np.int64)
    cells, counts = np.unique(coords @ strides, return_counts=True)
    cell_coords = np.stack(
        [(cells // s) % m for s, m in zip(strides, span)], axis=1
    )
    total = 0
    for off in itertools.product((-1, 0, 1), repeat=k):
        ids = (cell_coords + np.asarray(off)) @ strides
        at = np.searchsorted(cells, ids)
        at_ok = np.minimum(at, cells.size - 1)
        hit = cells[at_ok] == ids
        total += int((counts[hit] * counts[at_ok[hit]]).sum())
    return total


def tile_eval_work(
    num_points: int, num_dims: int, candidates: int, num_results: int, mode: str
) -> dict:
    """Flops and bytes of one join: float32 points in, the result out
    (int32 counts per point, or int32 (a, b) pairs)."""
    out = 4 * num_points if mode == "count" else 8 * num_results
    return {
        "flops": 2.0 * num_dims * candidates,
        "bytes": 4.0 * num_points * num_dims + out,
    }


def least_time(work: dict, peak: dict) -> float:
    """The least time the chip could take: the larger of its two bounds."""
    return max(
        work["flops"] / peak["flops_per_s"], work["bytes"] / peak["bytes_per_s"]
    )

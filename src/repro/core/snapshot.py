"""Immutable data snapshot of the self-join engine (DESIGN.md #10).

``GridSnapshot`` is the DATA half of the engine split: everything derived
from the point set -- the points themselves, the REORDER permutation, the
grid, the tile plan, the device-resident packed tile tables and the lazy
dense-tier tables -- lives here as a frozen-by-convention value object.
``SelfJoinEngine`` keeps only configuration and the shape-keyed executable
cache (the module-level jitted chunk programs), so compiled programs are
keyed by (chunk shape, backend, bucket) and never by data identity:
swapping a new snapshot behind a warm engine is one reference assignment
and invalidates nothing.

Shape-bucket contract: the device tile table (``tile_rows``), the combined
bipartite order's data segment (``point_rows``) and the dense tile table
(``dense_rows``) are padded to power-of-two row buckets
(``grid.bucket_rows``); ``rebuilt`` and the mutable index's ``compact``
carry the old snapshot's buckets forward as floors, so a rebuild whose data
still fits the old buckets presents byte-identical array SHAPES to every
executable compiled against the previous snapshot -- the no-retrace
contract ``tests/test_mutation.py`` locks via ``ServiceStats.num_traces``.
Padding tile rows carry ``tile_len == 0`` (the sentinel every chunk
program's validity mask already understands) and are never referenced by a
candidate pair list, so they contribute zero work and zero results.

Chunk tables (DESIGN.md #1.5): a candidate pair list reaches the device
as one ``ChunkTable`` per chunk size -- three tables uploaded once, one
row per chunk, their row count a power-of-two bucket of the chunk count
-- and the chunk programs read their chunk's row on the device by its
index.

Nothing here mutates after construction except the two lazy caches (the
chunk tables of each tier and chunk size, and the dense tables), both of
which are pure functions of the frozen state.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.grid import (
    GridIndex,
    TilePlan,
    bucket_rows,
    build_grid,
    build_tile_plan,
    pad_axis0,
)
from repro.core.reorder import apply_reorder, variance_reorder
from repro.core.types import SelfJoinConfig
from repro.kernels import ops

# sentinel for GridSnapshot.build's perm argument: "compute it from the
# config", as opposed to an explicit permutation (or explicit None)
_AUTO_PERM = "auto"


@dataclasses.dataclass(frozen=True)
class ChunkTable:
    """A candidate pair list as device tables, one row per chunk.

    Rows past ``num`` are padding: zero pairs with ``real == 0``, never
    dispatched.  The row count is ``bucket_rows(num)`` (or the fixed count a
    caller asks for), so the programs that read a table compile once per
    bucket.
    """

    pa: jax.Array    # (rows, C) int32 A-side tile index of each pair
    pb: jax.Array    # (rows, C) int32 B-side tile index
    real: jax.Array  # (rows,) int32 real pairs in each row
    num: int         # chunks holding pairs: the rows a loop dispatches

    @property
    def rows(self) -> int:
        return int(self.real.shape[0])


CHUNK_TABLE_UPLOADS = 3  # host->device transfers of one ChunkTable


def _chunk_table(
    pair_a: np.ndarray, pair_b: np.ndarray, chunk: int, cache: dict,
    min_rows: int = 1,
) -> Tuple[ChunkTable, int]:
    """The chunk table of a candidate pair list at ``chunk`` pairs a row,
    cached per chunk size, and the host->device transfers this call made
    (``CHUNK_TABLE_UPLOADS`` on a miss, 0 on a hit)."""
    got = cache.get(chunk)
    if got is not None:
        return got, 0
    with obs.span("engine.chunk_tables", "transfer", chunk=chunk):
        p = int(pair_a.shape[0])
        num = -(-p // chunk)
        rows = bucket_rows(num, min_rows)

        def table(pairs):
            flat = np.zeros(rows * chunk, np.int32)
            flat[:p] = pairs
            return jnp.asarray(flat.reshape(rows, chunk))

        real = np.clip(p - np.arange(rows, dtype=np.int64) * chunk, 0, chunk)
        # int32 on the host: a conversion on the device is one more program
        got = ChunkTable(
            table(pair_a), table(pair_b), jnp.asarray(real.astype(np.int32)),
            num,
        )
    cache[chunk] = got
    return got, CHUNK_TABLE_UPLOADS


def make_dense_plan(n_points: int, tile_size: int) -> TilePlan:
    """Sequential full-tile plan: the dense tier's work list.

    The indexed tier's tiles follow grid-cell boundaries, so in high
    dimensions (many near-singleton cells) they are mostly padding and the
    tile-pair fan-out explodes.  The dense tier re-tiles ``pts_sorted``
    *sequentially* -- every tile full except the last -- and lists the
    complete tile cross product.  Same ``TilePlan`` type, same chunk
    programs downstream; only the pair list and the per-tile layout differ.
    """
    t = int(tile_size)
    num_tiles = -(-int(n_points) // t) if n_points else 0
    tile_start = np.arange(num_tiles, dtype=np.int64) * t
    tile_len = np.minimum(int(n_points) - tile_start, t)
    idx = np.arange(num_tiles, dtype=np.int64)
    return TilePlan(
        tile_size=t,
        tile_start=tile_start.astype(np.int32),
        tile_len=tile_len.astype(np.int32),
        tile_cell=np.zeros(num_tiles, np.int32),  # no cells in the dense tier
        pair_a=np.repeat(idx, num_tiles).astype(np.int32),
        pair_b=np.tile(idx, num_tiles).astype(np.int32),
        num_tile_pairs_total=num_tiles * num_tiles,
        num_candidates=int(n_points) * int(n_points),
    )


@dataclasses.dataclass
class DenseTables:
    """Device-resident dense-tier twin of the snapshot's indexed tables."""

    plan: TilePlan
    tiles: jax.Array          # (dense_rows, NB, T, DB) f32, sequential layout
    tile_len: jax.Array       # (dense_rows,) int32; padding rows are 0
    tile_start: jax.Array     # (dense_rows,) int32 into pts_sorted
    _chunk_cache: Dict[int, ChunkTable] = dataclasses.field(default_factory=dict)

    def chunks(self, chunk: int) -> Tuple[ChunkTable, int]:
        return _chunk_table(self.plan.pair_a, self.plan.pair_b, chunk,
                            self._chunk_cache)


class GridSnapshot:
    """One dataset's complete, frozen index state, resident on device.

    Construct via ``build`` (full pipeline: optional REORDER, grid, tile
    plan, device placement), ``from_arrays`` (the persistence path: arrays
    already built, only device placement runs) or ``rebuilt`` (same points
    at a larger radius, same permutation, buckets floored at this
    snapshot's).  Treat instances as immutable values: a data change means
    a new snapshot and a ``SelfJoinEngine.swap_snapshot``.
    """

    __slots__ = (
        "config", "pts", "perm", "work", "index_eps", "grid", "plan",
        "num_points", "num_dims", "tile_rows", "point_rows", "dense_rows",
        "tiles", "tile_len", "tile_start", "point_order",
        "point_order_padded", "_dense", "_chunk_cache",
    )

    def __init__(
        self,
        config: SelfJoinConfig,
        pts: np.ndarray,
        perm: Optional[np.ndarray],
        work: np.ndarray,
        index_eps: Optional[float],
        grid: Optional[GridIndex],
        plan: Optional[TilePlan],
        *,
        min_tile_rows: int = 1,
        min_point_rows: int = 1,
        min_dense_rows: int = 1,
    ):
        self.config = config
        self.pts = pts
        self.perm = perm
        self.work = work
        self.index_eps = None if index_eps is None else float(index_eps)
        self.grid = grid
        self.plan = plan
        self.num_points, self.num_dims = pts.shape
        n_tiles = plan.num_tiles if plan is not None else 0
        self.tile_rows = bucket_rows(n_tiles, min_tile_rows)
        self.point_rows = bucket_rows(self.num_points, min_point_rows)
        self.dense_rows = bucket_rows(
            -(-self.num_points // config.tile_size), min_dense_rows
        )
        self._dense: Optional[DenseTables] = None
        self._chunk_cache: Dict[int, ChunkTable] = {}
        if grid is None:
            self.tiles = None
            self.tile_len = None
            self.tile_start = None
            self.point_order = None
            self.point_order_padded = None
            return
        with obs.span("engine.plan.place", "plan", tiles=n_tiles):
            self.tile_start = jnp.asarray(
                pad_axis0(plan.tile_start, self.tile_rows), jnp.int32
            )
            self.tile_len = jnp.asarray(
                pad_axis0(plan.tile_len, self.tile_rows), jnp.int32
            )
            # the grid-sort permutation at its REAL length (count scatters
            # and _unsort_counts address exactly N rows) ...
            self.point_order = jnp.asarray(grid.point_order, jnp.int32)
            # ... and padded to the bucket for the combined bipartite order,
            # so the (query | data) order array keeps one shape per bucket
            # across snapshot swaps (pad rows are never decoded)
            self.point_order_padded = jnp.asarray(
                pad_axis0(grid.point_order.astype(np.int64), self.point_rows),
                jnp.int32,
            )
            self.tiles = ops.make_tiles_device(
                jnp.asarray(grid.pts_sorted),
                self.tile_start,
                self.tile_len,
                tile_size=config.tile_size,
                dim_block=config.dim_block,
            )

    # -- constructors ------------------------------------------------------

    @classmethod
    def build(
        cls,
        d: np.ndarray,
        config: SelfJoinConfig,
        eps: Optional[float] = None,
        *,
        perm=_AUTO_PERM,
        min_tile_rows: int = 1,
        min_point_rows: int = 1,
        min_dense_rows: int = 1,
    ) -> "GridSnapshot":
        """Full index build: REORDER (unless ``perm`` is given), grid, plan.

        ``perm=_AUTO_PERM`` computes the variance permutation per
        ``config.reorder``; passing an explicit permutation (or ``None``)
        reuses a previous snapshot's frame -- ``compact`` does this so the
        rebuilt index bins points identically to the one it replaces.
        """
        pts = np.ascontiguousarray(np.asarray(d, dtype=np.float32))
        eps = config.eps if eps is None else float(eps)
        auto = isinstance(perm, str) and perm == _AUTO_PERM
        if auto:
            perm = None
        elif perm is not None:
            perm = np.asarray(perm)
        work = pts
        if perm is not None or (auto and config.reorder and pts.shape[0]):
            with obs.span("engine.plan.reorder", "plan"):
                if perm is None:
                    _, perm = variance_reorder(pts, config.sample_frac)
                work = apply_reorder(pts, perm)
        grid = plan = None
        index_eps = None
        if pts.shape[0]:
            with obs.span("engine.plan.grid", "plan"):
                grid = build_grid(work, eps, config.k)  # eps=0-safe (unit bins)
            with obs.span("engine.plan.tiles", "plan"):
                plan = build_tile_plan(grid, config.tile_size, config.sortidu)
            index_eps = float(eps)
        return cls(
            config, pts, perm, work, index_eps, grid, plan,
            min_tile_rows=min_tile_rows,
            min_point_rows=min_point_rows,
            min_dense_rows=min_dense_rows,
        )

    @classmethod
    def from_arrays(
        cls,
        pts: np.ndarray,
        perm: Optional[np.ndarray],
        grid: Optional[GridIndex],
        plan: Optional[TilePlan],
        index_eps: Optional[float],
        config: SelfJoinConfig,
        *,
        min_tile_rows: int = 1,
        min_point_rows: int = 1,
        min_dense_rows: int = 1,
    ) -> "GridSnapshot":
        """Snapshot over already-built arrays: only device placement runs.

        The persistence re-entry path (``SimilarityIndex.load`` via
        ``SelfJoinEngine.from_prebuilt``): a restarted server re-places the
        saved (perm, grid, plan) triple and is bit-identical to the process
        that saved it.
        """
        pts = np.ascontiguousarray(np.asarray(pts, dtype=np.float32))
        perm = None if perm is None else np.asarray(perm)
        work = pts if perm is None else apply_reorder(pts, perm)
        return cls(
            config, pts, perm, work, index_eps, grid, plan,
            min_tile_rows=min_tile_rows,
            min_point_rows=min_point_rows,
            min_dense_rows=min_dense_rows,
        )

    def rebuilt(self, eps: float) -> "GridSnapshot":
        """Same points, same permutation, new grid at ``eps``.

        Buckets are floored at this snapshot's, so growing the radius (the
        engine's transparent rebuild, or a temporary over-radius serving
        snapshot) never SHRINKS a device shape out from under a warm
        executable.
        """
        return GridSnapshot.build(
            self.pts, self.config, eps,
            perm=self.perm,
            min_tile_rows=self.tile_rows,
            min_point_rows=self.point_rows,
            min_dense_rows=self.dense_rows,
        )

    # -- derived views -----------------------------------------------------

    @property
    def n_pad(self) -> int:
        """Padded dimension count of the tile layout (n -> dim_block multiple)."""
        db = self.config.dim_block
        return ((self.num_dims + db - 1) // db) * db

    @property
    def num_dim_blocks(self) -> int:
        return self.n_pad // self.config.dim_block

    @property
    def data_bounds(self) -> Tuple[np.ndarray, np.ndarray]:
        """Per-dimension (min, max) of the snapshot points, reordered frame."""
        if self.grid is not None:
            return self.grid.data_bounds
        z = np.zeros(self.num_dims, np.float64)
        return z, z

    def chunks(self, chunk: int) -> Tuple[ChunkTable, int]:
        """The chunk table of the self-join candidate pair list, and the
        uploads this call made (0 when it was cached)."""
        return _chunk_table(
            self.plan.pair_a, self.plan.pair_b, chunk, self._chunk_cache
        )

    def dense_tables(self) -> DenseTables:
        """Build (lazily, once per snapshot) the dense-tier tables."""
        if self._dense is None:
            cfg = self.config
            plan = make_dense_plan(self.num_points, cfg.tile_size)
            start = pad_axis0(plan.tile_start, self.dense_rows)
            length = pad_axis0(plan.tile_len, self.dense_rows)
            tiles = ops.make_tiles_device(
                jnp.asarray(self.grid.pts_sorted),
                jnp.asarray(start, jnp.int32),
                jnp.asarray(length, jnp.int32),
                tile_size=cfg.tile_size,
                dim_block=cfg.dim_block,
            )
            self._dense = DenseTables(
                plan=plan,
                tiles=tiles,
                tile_len=jnp.asarray(length, jnp.int32),
                tile_start=jnp.asarray(start, jnp.int32),
            )
        return self._dense

    def packed_tile_table(self, num_tiles: int):
        """Host-side ``(tiles, tile_len)`` padded to ``num_tiles`` rows.

        The fused ring payload (``core/dist_engine.py``): every shard's
        tile table is padded to the fleet-wide maximum so all ring
        positions trace with one shape; padding rows carry ``tile_len ==
        0`` (the sentinel the chunk program's validity mask already
        understands), so they contribute nothing wherever a padded pair
        list references them.
        """
        t, db = self.config.tile_size, self.config.dim_block
        tiles = np.zeros(
            (num_tiles, self.num_dim_blocks, t, db), np.float32
        )
        tile_len = np.zeros(num_tiles, np.int32)
        if self.plan is not None and self.plan.num_tiles:
            real, lens = ops.make_tiles(
                self.grid.pts_sorted,
                self.plan.tile_start,
                self.plan.tile_len,
                t,
                db,
            )
            tiles[: real.shape[0]] = real
            tile_len[: lens.shape[0]] = lens
        return tiles, tile_len

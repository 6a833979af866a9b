"""Device-resident self-join engine (DESIGN.md #1.5, #10).

``SelfJoinEngine`` keeps the entire hot loop of GPU-Join (Gowanlock &
Karsin 2018, Alg. 1 lines 11-19 plus constructNeighborTable) on the
accelerator.  Only index *construction* (REORDER, grid build, tile-pair
planning) runs on the host, exactly as in the paper; everything downstream
is jitted device code:

  tiling       -- ``ops.make_tiles_device``: one vectorized gather replaces
                  the per-tile host loop;
  evaluation   -- the tile distance kernel (Pallas or jnp backend), SHORTC
                  dimension-blocked, eps as a traced scalar;
  count scatter-- per-point neighbour counts accumulate via an in-jit
                  scatter-add over the grid-sorted layout (the host
                  ``np.add.at`` is gone);
  pairs        -- device-side stream compaction (rank-select over the hit
                  mask) into a preallocated ``max_pairs`` buffer with an
                  overflow flag (the host ``np.nonzero`` is gone), already
                  mapped to original point ids via a device gather.

Snapshot/executable split (DESIGN.md #10): every piece of data-derived
state -- points, REORDER permutation, grid, tile plan, device tables,
dense tables -- lives in a frozen ``GridSnapshot`` (``core/snapshot.py``);
the engine holds only configuration and the compiled chunk programs.
Programs are keyed by (mode, chunk shape, backend), never by data
identity, so ``swap_snapshot`` -- one reference assignment -- replaces the
dataset behind a warm engine without invalidating a single executable, as
long as the new snapshot keeps the old shape buckets (it does, by the
floor-carrying contract of ``GridSnapshot.rebuilt`` and the mutable
index's ``compact``).

Chunking / compilation-caching contract: the candidate tile-pair list is
uploaded once as a chunk table (``snapshot.ChunkTable``: fixed-size,
zero-padded rows, a power-of-two bucket of them); eps, the chunk's row
index and the running (buffer, offset, overflow, counts) state are all
traced, so XLA compiles **at most one program per (mode, chunk shape,
table bucket)** and the Python chunk loop dispatches that same executable
-- no host compute, and no transfer but the row index and eps.  The
executables are module-level, shared by every engine instance; a multi-eps
sweep recompiles nothing.

One host chunk driver runs every host-driven join: ``kernel_choice`` picks
a tier's (backend, SHORTC), ``run_count_chunks`` is the count loop and
``exact_pairs`` the count-sized bipartite pairs pass.  The engine's own
joins, the host-driven ring's blocks (``core/dist_engine.py``) and the
serving tier (``repro.join.QueryService``, with its own trace-counted
programs) all call them; only the self-join's pairs mode keeps its
estimate-sized window, capacity regrow and remainder pass
(``SelfJoinEngine.pairs``).

``repro.core.selfjoin.self_join`` is a thin wrapper over this class.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batching as batching_mod
from repro.core import cost as cost_mod
from repro.core.grid import (
    GridIndex,
    QueryTilePlan,
    TilePlan,
    build_query_tile_plan,
    pad_axis0,
)
from repro.core.reorder import apply_reorder
from repro.core.snapshot import ChunkTable, GridSnapshot, _chunk_table
from repro.core.types import (
    EngineConfig,
    SelfJoinConfig,
    SelfJoinResult,
    SelfJoinStats,
)
from repro import obs
from repro.kernels import ops

_MAX_AUTO_GROW = 8  # regrows (or window widenings) before a pairs pass gives up
_PAIRS_HEADROOM = 2.0  # auto pairs capacity = headroom * estimated |R|
_MIN_HIT_WINDOW = 4096  # narrowest rank window a pairs chunk program runs
# Auto-mode first rank window: the rung at or above this many times the
# size estimate's mean hits per chunk.  Syn64D2M at eps 0.18 (a v5e, four
# seeds) has a mean of 4.9-5.0k hits a chunk, p90 7.7-7.9k, max 14.7-18.0k:
# a factor of 1 gives 8,192 ranks and leaves ~8 % of the chunks to the
# remainder pass, which is cheaper than widening every chunk's select --
# a pairs join took 5.0-5.3 s at 1, 5.7-6.0 s at 2 (16,384), 7.8-7.9 s at
# 4.  Syn16D2M (~67 hits a chunk) stays at the 4,096 floor.
_WINDOW_PER_MEAN = 1


def hit_window(n: int, flat_per_chunk: int) -> int:
    """The rank window for ``n`` hits: the power of two at or above ``n``,
    at least 4,096 and at most ``flat_per_chunk`` (C*T*T, a chunk's every
    flag).  Powers of two keep the compiled pairs programs few."""
    pow2 = 1 << max(int(n) - 1, 0).bit_length()
    return min(flat_per_chunk, max(_MIN_HIT_WINDOW, pow2))


def kernel_choice(execution: str, cfg: SelfJoinConfig) -> Tuple[str, bool]:
    """``(backend, shortc)`` of the chunk programs for a tier (``"indexed"``
    | ``"dense"``): the Pallas kernels or their XLA twins per
    ``cfg.use_pallas``; SHORTC only on the indexed tier (the dense backends
    skip no dimension block)."""
    if execution == "dense":
        return ("dense" if cfg.use_pallas else "dense_jnp"), False
    return ("pallas" if cfg.use_pallas else "jnp"), cfg.shortc


# ---------------------------------------------------------------------------
# Device programs.  Module-level so every engine instance shares one jit
# cache; all dynamic state is passed (and returned) as traced values.
# ---------------------------------------------------------------------------


def _chunk_validity(tile_len, tile_start, pa, real, t):
    """(pair_valid (C,), row validity (C,T), scatter rows (C,T))."""
    c = pa.shape[0]
    lane = jnp.arange(t, dtype=jnp.int32)
    pair_valid = jnp.arange(c, dtype=jnp.int32) < real
    valid = pair_valid[:, None] & (lane[None, :] < tile_len[pa][:, None])
    idx = tile_start[pa][:, None] + lane[None, :]
    return pair_valid, valid, idx


def count_chunk_step(
    counts_sorted,  # (N,) int32 running per-point counts, grid-sorted
    skipped_tot,    # ()  int32 running SHORTC skipped-block total
    tiles,          # (num_tiles, NB, T, DB) f32 (ops.make_tiles layout)
    tile_len,       # (num_tiles,) int32
    tile_start,     # (num_tiles,) int32
    pa, pb,         # (C,) int32 padded chunk of the candidate pair list
    real,           # () int32 valid prefix of the chunk
    eps,            # () f32 traced search radius
    *,
    shortc, backend,
):
    """One counts-mode chunk: evaluate + scatter-add, fully traceable.

    This is the body shared by the jitted single-device program below and
    the fused distributed ring program (``core/dist_engine.py``), where the
    tile tables themselves are traced values rotating through ``ppermute``
    -- so nothing here may assume host-side (concrete) inputs.
    """
    counts, skipped = ops.eval_tile_pairs(
        tiles, tile_len, pa, pb, eps, shortc=shortc, backend=backend,
    )
    t = tiles.shape[2]
    n = counts_sorted.shape[0]
    pair_valid, valid, idx = _chunk_validity(tile_len, tile_start, pa, real, t)
    idx = jnp.where(valid, idx, n)  # out-of-range -> dropped
    counts_sorted = counts_sorted.at[idx].add(
        jnp.where(valid, counts, 0), mode="drop"
    )
    skipped_tot = skipped_tot + jnp.where(pair_valid, skipped, 0).sum()
    return counts_sorted, skipped_tot


def chunk_row(pa, pb, real, i):
    """Row ``i`` of a chunk table (``snapshot.ChunkTable``), read on the
    device: the chunk's (C,) pair indices and its real length."""
    def row(x):
        return jax.lax.dynamic_index_in_dim(x, i, keepdims=False)
    return row(pa), row(pb), row(real)


def _count_chunk_traced(
    counts_sorted, skipped_tot, tiles, tile_len, tile_start,
    pa, pb, real, i, eps, *, shortc, backend,
):
    """``count_chunk_step`` on row ``i`` of the chunk table (pa, pb, real)."""
    # Runs only while XLA traces (cache misses), so the obs event stream
    # distinguishes "compiled a new count program" from warm dispatches.
    obs.event("engine.trace", "compile", program="count_chunk")
    return count_chunk_step(
        counts_sorted, skipped_tot, tiles, tile_len, tile_start,
        *chunk_row(pa, pb, real, i), eps, shortc=shortc, backend=backend,
    )


# the program keeps its body's name: the device trace reads it as
# jit_count_chunk_step
_count_chunk_traced.__name__ = "count_chunk_step"
_count_chunk_program = functools.partial(
    jax.jit, static_argnames=("shortc", "backend")
)(_count_chunk_traced)


def pairs_chunk_step(
    buf,            # (cap + hit_cap, 2) int32 result buffer, original ids
    offset,         # ()  int32 pairs found so far (may exceed cap)
    max_chunk_hits, # ()  int32 largest per-chunk hit count seen
    tiles, tile_len, tile_start,
    point_order,    # (N,) int32 grid-sorted -> original id
    pa, pb, real, eps,
    *,
    hit_cap, backend, first_rank=0,
):
    """One pairs-mode chunk: evaluate + compact into ``buf``, fully on device.

    Like ``count_chunk_step`` this is the un-jitted body, so callers that
    need their own trace accounting (the serving tier, ``repro.join``) can
    wrap it in their own ``jax.jit``; the module-level jitted program below
    serves the engine.  For a *bipartite* chunk, ``point_order`` is the
    combined (query | data) position->original-id map and ``tile_start`` the
    combined position table of ``SelfJoinEngine.prepare_query`` -- A-side
    rows then decode to query ids and B-side rows to data ids.  The fused
    distributed ring (``core/dist_engine.py``) runs this same body inside
    its one ``shard_map`` program: the (buf, offset, max_chunk_hits) triple
    becomes the per-worker ring carry and the decode tables are traced
    values rotating through ``ppermute``, so nothing here may assume
    host-side (concrete) inputs.

    Compaction is rank-select, not scatter (scatter over the full C*T*T
    mask serializes badly on CPU XLA): ``_select_hits`` finds the
    (pair, row, lane) position of each output rank
    first_rank..first_rank+hit_cap-1 in flat mask order, and the gathered
    (a, b) rows land in ``buf`` as one contiguous ``dynamic_update_slice``
    block at ``offset``.  With ``first_rank`` 0, ranks past the chunk's
    true hit count select in-range garbage that the next chunk's block (or
    the final slice) overwrites.  Nothing is lost silently: ``offset``
    advances by the exact hit count, the host driver retries with a larger
    buffer when ``offset`` exceeds the capacity, and when
    ``max_chunk_hits`` says a chunk outgrew the rank window, the engine's
    remainder pass (``SelfJoinEngine.pairs``) runs this body again for
    that chunk alone with ``first_rank`` = the first pass's window and
    ``offset`` = the start of the chunk's gap (the rings and the serving
    tier, which call it with ``first_rank`` 0, widen the window and run
    again).  A remainder block is followed by the next chunk's pairs, so
    its rows past the chunk's hits keep what ``buf`` holds.  Per-point
    counts are *not* accumulated here -- they fall out of the finished
    buffer in one scatter (``_counts_from_pairs``).
    """
    _, _, mask = ops.eval_tile_pairs(
        tiles, tile_len, pa, pb, eps,
        shortc=True, backend=backend, return_mask=True,
    )
    c = pa.shape[0]
    cap = buf.shape[0] - hit_cap

    pair_valid = jnp.arange(c, dtype=jnp.int32) < real
    hit = mask.astype(jnp.bool_) & pair_valid[:, None, None]
    p_, i_, j_, nh = _select_hits(hit, hit_cap, first_rank)
    a_orig = point_order[tile_start[pa[p_]] + i_]
    b_orig = point_order[tile_start[pb[p_]] + j_]
    block = jnp.stack([a_orig, b_orig], axis=1)           # (hit_cap, 2)
    woff = jnp.minimum(offset, cap)  # post-overflow blocks land in padding
    if first_rank:
        live = first_rank + jnp.arange(hit_cap, dtype=jnp.int32) < nh
        kept = jax.lax.dynamic_slice(buf, (woff, jnp.int32(0)), block.shape)
        block = jnp.where(live[:, None], block, kept)
    buf = jax.lax.dynamic_update_slice(buf, block, (woff, jnp.int32(0)))

    offset = offset + nh
    max_chunk_hits = jnp.maximum(max_chunk_hits, nh)
    return buf, offset, max_chunk_hits


def _select_hits(hit, hit_cap, first_rank=0):
    """Positions (p, i, j) of hit ranks first_rank..first_rank+hit_cap-1,
    and the hit count.

    ``hit`` is the chunk's (C, T, T) bool mask; ranks follow its flat
    (pair, row, lane) order.  Each rank is resolved level by level: the
    tile pair from the C inclusive pair totals, the row from that pair's
    T inclusive row totals (one row gather), the lane from that row's T
    hits (one row gather).  No prefix sum over all C*T*T flags and no
    loop.
    """
    c, t, _ = hit.shape
    row_incl = jnp.cumsum(hit.sum(-1, dtype=jnp.int32), axis=1)  # (C, T)
    pair_incl = jnp.cumsum(row_incl[:, -1])                      # (C,)
    rank = jnp.arange(hit_cap, dtype=jnp.int32) + first_rank
    p_, rank = _select_level(pair_incl[None, :], rank)
    p_ = jnp.minimum(p_, c - 1)
    i_, rank = _select_level(row_incl[p_], rank)
    i_ = jnp.minimum(i_, t - 1)
    lanes = hit.reshape(c * t, t)[p_ * t + i_].astype(jnp.int32)
    j_, _ = _select_level(jnp.cumsum(lanes, axis=1), rank)
    return p_, i_, jnp.minimum(j_, t - 1), pair_incl[-1]


def _select_level(incl, rank):
    """Per rank: the number of inclusive counts at or below it (the index
    of the entry holding it) and its rank within that entry."""
    below = incl <= rank[:, None]
    return (
        below.sum(1, dtype=jnp.int32),
        rank - jnp.max(jnp.where(below, incl, 0), axis=1),
    )


def _pairs_chunk_traced(
    buf, offset, max_chunk_hits, tiles, tile_len, tile_start, point_order,
    pa, pb, real, i, eps, *, hit_cap, backend, first_rank=0,
):
    """``pairs_chunk_step`` on row ``i`` of the chunk table (pa, pb, real)."""
    obs.event("engine.trace", "compile", program="pairs_chunk")
    return pairs_chunk_step(
        buf, offset, max_chunk_hits, tiles, tile_len, tile_start,
        point_order, *chunk_row(pa, pb, real, i), eps,
        hit_cap=hit_cap, backend=backend, first_rank=first_rank,
    )


_pairs_chunk_traced.__name__ = "pairs_chunk_step"
_pairs_chunk_program = functools.partial(
    jax.jit, static_argnames=("hit_cap", "backend", "first_rank")
)(_pairs_chunk_traced)


@jax.jit
def _counts_from_pairs(counts0, buf, num):
    """Per-point counts from the compacted pair buffer (original order)."""
    rows = jnp.arange(buf.shape[0], dtype=jnp.int32)
    a = jnp.where(rows < num, buf[:, 0], counts0.shape[0])
    return counts0.at[a].add(1, mode="drop")


@jax.jit
def _unsort_counts(counts_sorted, point_order):
    """Grid-sorted counts -> original point order (device scatter)."""
    return jnp.zeros_like(counts_sorted).at[point_order].set(counts_sorted)


# ---------------------------------------------------------------------------
# The bipartite query-plan API (DESIGN.md #8).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class QueryPlanTables:
    """Device-ready combined (query | data) tables for one bipartite batch.

    Produced by ``SelfJoinEngine.prepare_query`` and consumed by the chunk
    driver below (``run_count_chunks`` / ``exact_pairs``) for three
    callers with one layout:

      * ``SelfJoinEngine.count_query`` runs the count chunk program over it;
      * the host-driven ring's (worker, shard) blocks run the count program
        and then the exact pairs pass over it;
      * the serving tier (``repro.join.QueryService``) runs the same two
        with its own trace-counted programs, with ``pad_queries_to``
        rounding the query side up to a shape bucket so a request stream
        reuses a bounded set of executables.

    The fused distributed packer keeps its own padding, but shares the
    underlying ``build_query_plan`` host plan.  ``backend`` / ``shortc``
    are the tier's kernel choice (``kernel_choice``).

    Layout contract: positions ``[0, n_slots)`` are query rows in q-sorted
    order (real rows first, zero padding after), positions ``[n_slots,
    n_slots + point_rows)`` are the engine's grid-sorted data points padded
    to the snapshot's pow2 ``point_rows`` bucket (pad positions are never
    referenced by a valid lane or pair list).  ``tile_start`` and ``order``
    address that combined position space, so the *same* arrays serve counts
    mode (A-side scatter into a ``(n_slots,)`` vector; B-side starts never
    read below ``n_slots``) and pairs mode (both sides decode through
    ``order`` to original query rows / data ids).
    """

    eps: float                     # radius the plan was built for
    nq: int                        # real query rows
    n_slots: int                   # padded query-position space (>= nq)
    qplan: QueryTilePlan           # the host-side plan (stats + q_order live here)
    tiles: jax.Array               # (q_tile_rows + d_tile_rows, NB, T, DB) f32
    tile_len: jax.Array            # (q_tile_rows + d_tile_rows,) int32
    tile_start: jax.Array          # combined position space (B side + n_slots)
    order: jax.Array               # (n_slots + point_rows,) int32 position -> id
    pair_a: np.ndarray             # (P,) int32 combined-table A (query-tile) index
    pair_b: np.ndarray             # (P,) int32 combined-table B (data-tile) index
    backend: str                   # chunk programs' backend for this tier
    shortc: bool                   # SHORTC on (indexed tier only)
    execution: str = "indexed"     # tier the tables realize: "indexed" | "dense"
    cost_indexed: float = 0.0      # cost model's indexed-tier estimate
    cost_dense: float = 0.0        # cost model's dense-tier estimate
    num_candidates: int = 0        # point comparisons this tier will evaluate
    _chunk_cache: Dict[int, ChunkTable] = dataclasses.field(default_factory=dict)

    @property
    def num_pairs(self) -> int:
        return int(self.pair_a.shape[0])

    def chunks(self, chunk: int) -> Tuple[ChunkTable, int]:
        """The chunk table of the candidate pair list, cached per size, and
        the uploads this call made (0 when it was cached)."""
        return _chunk_table(self.pair_a, self.pair_b, chunk, self._chunk_cache)

    def chunk_tables(self, chunk: int, rows: int) -> List[ChunkTable]:
        """The candidate pair list as chunk tables of exactly ``rows`` rows
        (a power of two), uncached.  A program that reads them is keyed by
        ``rows``, never by the batch's chunk count."""
        step = rows * chunk
        return [
            _chunk_table(
                self.pair_a[s : s + step], self.pair_b[s : s + step],
                chunk, {}, rows,
            )[0]
            for s in range(0, self.num_pairs, step)
        ]


class SelfTables(NamedTuple):
    """The device tables of the tier a self-join runs (``_self_tables``):
    the same attributes the chunk driver reads off ``QueryPlanTables``."""

    tiles: jax.Array
    tile_len: jax.Array
    tile_start: jax.Array
    order: jax.Array       # grid-sorted position -> original id
    plan: TilePlan
    chunks: Callable[[int], Tuple[ChunkTable, int]]  # size -> (table, uploads)
    backend: str
    shortc: bool


# ---------------------------------------------------------------------------
# The host chunk driver: the one loop that dispatches chunk programs, for
# the engine's joins, the host-driven ring's blocks and the serving tier.
# ``tab`` is a ``SelfTables`` or a ``QueryPlanTables``; ``program`` is the
# engine's jitted chunk program or a caller's own (the service counts its
# traces), with the same positional signature.
# ---------------------------------------------------------------------------


def run_count_chunks(tab, tables, state, eps, program, span, **attrs):
    """Every row of every chunk table in ``tables`` through the count
    ``program``, from ``state`` = (counts, skipped-block total).  Returns
    the counts, the skipped total and the dispatches made."""
    counts, skipped = state
    tiles, tile_len, tile_start = tab.tiles, tab.tile_len, tab.tile_start
    backend, shortc = tab.backend, tab.shortc
    dispatches = 0
    for table in tables:
        pa, pb, real = table.pa, table.pb, table.real
        for i in range(table.num):
            with obs.span(span, "dispatch", **attrs):
                counts, skipped = program(
                    counts, skipped, tiles, tile_len, tile_start,
                    pa, pb, real, i, eps, shortc=shortc, backend=backend,
                )
        dispatches += table.num
    return counts, skipped, dispatches


def run_pairs_chunks(tab, tables, state, eps, program, hit_cap, span, **attrs):
    """Every row of ``tables`` through the pairs ``program`` at rank window
    ``hit_cap``, from ``state`` = (buffer, offset, max chunk hits).
    Returns the state and each chunk's end offset, left on the device."""
    buf, offset, max_hits = state
    tiles, tile_len, tile_start = tab.tiles, tab.tile_len, tab.tile_start
    order, backend = tab.order, tab.backend
    ends = []
    for table in tables:
        pa, pb, real = table.pa, table.pb, table.real
        for i in range(table.num):
            with obs.span(span, "dispatch", **attrs):
                buf, offset, max_hits = program(
                    buf, offset, max_hits, tiles, tile_len, tile_start,
                    order, pa, pb, real, i, eps,
                    hit_cap=hit_cap, backend=backend,
                )
            ends.append(offset)
    return (buf, offset, max_hits), ends


def exact_pairs(tab, tables, eps, total, program, span, **attrs):
    """The exact bipartite pairs pass: all ``total`` pairs a count pass
    found over ``tab``, as an ``(total, 2)`` host array, and the dispatches
    made.

    The buffer holds the power of two at or above ``total``, so it never
    overflows.  The rank window starts at the 4,096 floor; when a chunk
    outgrew it, the window widens to that chunk's rung and the whole pass
    runs again (a ``<span prefix>.retry`` instant, ``kind="hit_cap"``).
    ``tables`` holds at least one table (its width gives the chunk size).
    """
    flat_per_chunk = tables[0].pa.shape[1] * tab.tiles.shape[2] ** 2
    hit_cap = hit_window(0, flat_per_chunk)
    cap = 1 << (max(total, 1) - 1).bit_length()  # pow2: bounded trace keys
    dispatches = 0
    for _ in range(_MAX_AUTO_GROW + 1):
        state = (
            jnp.zeros((cap + hit_cap, 2), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        )
        (buf, offset, max_hits), ends = run_pairs_chunks(
            tab, tables, state, eps, program, hit_cap, span, **attrs
        )
        dispatches += len(ends)
        max_hits = int(max_hits)
        if max_hits <= hit_cap:
            break
        obs.event(
            span.rsplit(".", 1)[0] + ".retry", "retry", kind="hit_cap",
            max_hits=max_hits, hit_cap=hit_cap, **attrs,
        )
        hit_cap = hit_window(max_hits, flat_per_chunk)
    else:
        raise RuntimeError(
            f"pairs rank window did not converge (max chunk hits "
            f"{max_hits} > hit_cap {hit_cap})"
        )
    num = int(offset)
    if num != total:
        raise RuntimeError(
            f"pairs pass found {num} pairs but the count pass said {total}"
            f" ({span} {attrs})"
        )
    return np.asarray(buf[:num]), dispatches


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------


class SelfJoinEngine:
    """Reusable device-resident self-join over one dataset snapshot.

    Builds a ``GridSnapshot`` once (at construction, for ``config.eps``);
    ``count()`` / ``pairs()`` / ``query()`` reuse both the snapshot and the
    compiled chunk programs; querying a *larger* eps than the snapshot was
    built for transparently swaps in a rebuilt snapshot (a smaller eps
    reuses it -- the candidate set is a superset, and the distance filter
    runs at the queried eps).  ``swap_snapshot`` is the mutable-index
    re-entry point: one reference assignment replaces the dataset and
    invalidates no compiled program.

    ``eps == 0`` is supported (degenerate join: duplicates + self); the
    grid is then binned at unit width, which is correct for any radius
    not exceeding it.  Note the fp32 matmul-form numerics (DESIGN.md #6):
    at eps near 0, exact-duplicate/self matches are only guaranteed on
    quantized coordinates (e.g. a 1/64 grid); on arbitrary fp32 data the
    rounding of ``|a|^2 + |b|^2 - 2ab`` can exceed an eps^2 of ~1e-8.
    """

    def __init__(
        self,
        d: np.ndarray,
        config: SelfJoinConfig,
        engine_config: Optional[EngineConfig] = None,
    ):
        self.config = config
        self.engine = engine_config or EngineConfig()
        with obs.span(
            "engine.snapshot_build", "plan", n=int(np.asarray(d).shape[0])
        ):
            self.snapshot = GridSnapshot.build(d, config)

    @classmethod
    def from_snapshot(
        cls,
        snapshot: GridSnapshot,
        engine_config: Optional[EngineConfig] = None,
    ) -> "SelfJoinEngine":
        """Engine over an existing snapshot (no host build at all)."""
        self = object.__new__(cls)
        self.config = snapshot.config
        self.engine = engine_config or EngineConfig()
        self.snapshot = snapshot
        return self

    @classmethod
    def from_prebuilt(
        cls,
        pts: np.ndarray,
        perm: Optional[np.ndarray],
        grid: Optional[GridIndex],
        plan: Optional[TilePlan],
        index_eps: Optional[float],
        config: SelfJoinConfig,
        engine_config: Optional[EngineConfig] = None,
    ) -> "SelfJoinEngine":
        """Engine over an already-built index: no REORDER, no grid build.

        The persistence path of ``repro.join.SimilarityIndex``: a server
        restart loads the saved (perm, grid, plan) triple and only the
        device placement runs again, so the restarted engine is
        bit-identical to the one that was saved.
        """
        return cls.from_snapshot(
            GridSnapshot.from_arrays(pts, perm, grid, plan, index_eps, config),
            engine_config,
        )

    # -- snapshot management ----------------------------------------------

    def swap_snapshot(self, snapshot: GridSnapshot) -> None:
        """Atomically replace the data snapshot behind the warm executables.

        One reference assignment: requests that pinned the previous
        snapshot keep serving it unchanged, and no compiled program is
        invalidated (programs key on shapes, and the snapshot's pow2 row
        buckets keep shapes stable across a compact/rebuild of the same
        bucket).
        """
        if snapshot.config != self.config:
            raise ValueError(
                "snapshot was built under a different SelfJoinConfig"
            )
        self.snapshot = snapshot

    def snapshot_for(self, eps: float) -> GridSnapshot:
        """A snapshot whose index covers ``eps``, WITHOUT swapping.

        The serving tier's epoch pinning: an over-radius request builds a
        temporary rebuilt snapshot, serves from it, and drops it -- the
        engine's resident snapshot (and every warm executable keyed to its
        buckets) is untouched.
        """
        snap = self.snapshot
        if snap.num_points == 0 or (
            snap.index_eps is not None and eps <= snap.index_eps
        ):
            return snap
        with obs.span(
            "engine.snapshot_rebuild", "plan",
            eps=eps, n=snap.num_points, pinned=True,
        ):
            return snap.rebuilt(eps)

    def _ensure_index(self, eps: float) -> None:
        snap = self.snapshot
        if snap.num_points == 0:
            return
        if snap.index_eps is None or eps > snap.index_eps:
            with obs.span(
                "engine.snapshot_rebuild", "plan", eps=eps, n=snap.num_points
            ):
                self.swap_snapshot(snap.rebuilt(eps))

    # -- views of the snapshot --------------------------------------------

    @property
    def num_points(self) -> int:
        return self.snapshot.num_points

    @property
    def num_dims(self) -> int:
        return self.snapshot.num_dims

    @property
    def grid(self) -> Optional[GridIndex]:
        return self.snapshot.grid

    @property
    def plan(self) -> Optional[TilePlan]:
        return self.snapshot.plan

    @property
    def n_pad(self) -> int:
        """Padded dimension count of the tile layout (n -> dim_block multiple)."""
        return self.snapshot.n_pad

    def resolve_execution(
        self, eps: Optional[float] = None,
        snapshot: Optional[GridSnapshot] = None,
    ) -> cost_mod.TierDecision:
        """Cost-model tier decision for a self-join at ``eps`` (DESIGN.md #9).

        Always computes both estimates (even under a forced mode) so stats
        record what the model thought alongside what actually ran.
        """
        eps = self.config.eps if eps is None else float(eps)
        cfg = self.config
        if snapshot is None:
            if self.num_points == 0:
                return cost_mod.decide(0.0, 0.0, cfg.execution)
            self._ensure_index(eps)
            snapshot = self.snapshot
        if snapshot.num_points == 0:
            return cost_mod.decide(0.0, 0.0, cfg.execution)
        ci = cost_mod.indexed_join_cost(
            snapshot.plan.num_pairs, snapshot.plan.num_candidates,
            cfg.tile_size, snapshot.n_pad,
        )
        cd = cost_mod.dense_join_cost(
            snapshot.num_points, snapshot.num_points,
            cfg.tile_size, snapshot.n_pad,
        )
        return cost_mod.decide(ci, cd, cfg.execution)

    def _base_stats(self, eps: float, snap: GridSnapshot) -> SelfJoinStats:
        stats = SelfJoinStats(
            num_points=snap.num_points,
            num_dims=snap.num_dims,
            k=min(self.config.k, snap.num_dims),
        )
        if snap.plan is not None:
            stats.num_nonempty_cells = snap.grid.num_cells
            stats.num_tiles = snap.plan.num_tiles
            stats.num_tile_pairs_total = snap.plan.num_tile_pairs_total
            stats.num_tile_pairs_evaluated = snap.plan.num_pairs
            stats.num_candidates = snap.plan.num_candidates
            stats.num_candidates_dense = snap.num_points * snap.num_points
        return stats

    def _self_stats(
        self, eps: float, snap: GridSnapshot, dec: cost_mod.TierDecision,
        plan: TilePlan,
    ) -> SelfJoinStats:
        """Stats of a self-join on the tier ``dec`` chose, whose pair list
        is ``plan``'s."""
        stats = self._base_stats(eps, snap)
        stats.execution = dec.execution
        stats.cost_indexed = dec.cost_indexed
        stats.cost_dense = dec.cost_dense
        stats.num_tile_pairs_evaluated = plan.num_pairs
        stats.num_candidates = plan.num_candidates
        return stats

    def build_query_plan(
        self,
        q_pts: np.ndarray,
        eps: Optional[float] = None,
        snapshot: Optional[GridSnapshot] = None,
    ):
        """Bipartite Q-tile x D-tile plan for ``q_pts`` against this index.

        ``q_pts`` is in ORIGINAL coordinates; the engine applies its own
        REORDER permutation.  Shared by ``count_query`` and the fused
        distributed ring packer (``core/dist_engine.py``), which needs the
        plan host-side to pad it into the uniform per-round tables.
        With an explicit ``snapshot`` the plan is built against it (the
        serving tier's pinned epoch); otherwise the engine's resident
        snapshot is used, rebuilt if ``eps`` outgrows it.  Returns ``None``
        when the snapshot indexes no points (every candidate list would be
        empty).
        """
        eps = self.config.eps if eps is None else float(eps)
        if snapshot is None:
            if self.num_points == 0:
                return None
            self._ensure_index(eps)
            snapshot = self.snapshot
        if snapshot.num_points == 0:
            return None
        q_work = (
            apply_reorder(q_pts, snapshot.perm)
            if snapshot.perm is not None else q_pts
        )
        with obs.span(
            "engine.build_query_plan", "plan",
            nq=int(q_work.shape[0]), eps=eps,
        ):
            return build_query_tile_plan(
                snapshot.grid, snapshot.plan, q_work, self.config.sortidu
            )

    def prepare_query(
        self,
        q_pts: np.ndarray,
        eps: Optional[float] = None,
        *,
        pad_queries_to: Optional[int] = None,
        snapshot: Optional[GridSnapshot] = None,
    ) -> Optional[QueryPlanTables]:
        """Build the device-ready combined (query | data) tables for ``q_pts``.

        The query-plan API (DESIGN.md #8): everything between the host-side
        ``build_query_plan`` and the chunk programs -- query tiling on
        device, the concatenated (Q | D) tile table, the combined
        position->original-id map, and the B-side index offset -- shared by
        ``count_query`` and the serving tier.

        ``pad_queries_to`` rounds the *query side* of every device array up
        to that many rows (q-sorted points, query tiles, and the scatter
        target all pad to the same bucket; padding tiles carry length 0 and
        padded positions are never referenced by a valid lane), so all
        batches in the same bucket share one compiled executable.  The data
        side is padded by the snapshot's own pow2 buckets, so tables built
        against two snapshots of the same buckets share shapes too.
        ``snapshot`` pins an explicit snapshot (no engine mutation); by
        default the resident one serves, rebuilt if ``eps`` outgrows it.
        Returns ``None`` when either side is empty.
        """
        with obs.span(
            "engine.prepare_query", "plan", nq=int(np.asarray(q_pts).shape[0])
        ):
            return self._prepare_query_impl(
                q_pts, eps, pad_queries_to=pad_queries_to, snapshot=snapshot
            )

    def _prepare_query_impl(
        self,
        q_pts: np.ndarray,
        eps: Optional[float] = None,
        *,
        pad_queries_to: Optional[int] = None,
        snapshot: Optional[GridSnapshot] = None,
    ) -> Optional[QueryPlanTables]:
        eps = self.config.eps if eps is None else float(eps)
        q_pts = np.ascontiguousarray(np.asarray(q_pts, dtype=np.float32))
        nq = q_pts.shape[0]
        if snapshot is None:
            if nq == 0 or self.num_points == 0:
                return None
            self._ensure_index(eps)
            snapshot = self.snapshot
        snap = snapshot
        if nq == 0 or snap.num_points == 0:
            return None
        qplan = self.build_query_plan(q_pts, eps, snapshot=snap)
        cfg = self.config
        n_slots = nq if pad_queries_to is None else int(pad_queries_to)
        if n_slots < nq:
            raise ValueError(
                f"pad_queries_to={n_slots} smaller than the batch ({nq})"
            )
        # cost-model tier dispatch (DESIGN.md #9): the indexed estimate comes
        # from the grid probe that just ran, the dense estimate from the
        # batch shape alone.  Both tiers share q_sorted / q_order (the dense
        # tier only re-tiles the already-sorted rows sequentially).
        dec = cost_mod.decide(
            cost_mod.indexed_join_cost(
                qplan.num_pairs, qplan.num_candidates, cfg.tile_size,
                snap.n_pad,
            ),
            cost_mod.dense_join_cost(
                nq, snap.num_points, cfg.tile_size, snap.n_pad
            ),
            cfg.execution,
        )
        t = cfg.tile_size
        # every cell holds >= 1 point, so num_q_tiles <= nq <= n_slots: one
        # bucket dimension pads the q-sorted rows AND the q-tile rows
        qt_rows = n_slots
        if pad_queries_to is None:
            qt_rows = qplan.num_q_tiles if dec.execution == "indexed" else -(-nq // t)
        q_sorted = pad_axis0(qplan.q_sorted, n_slots)
        if dec.execution == "dense":
            dt = snap.dense_tables()
            q_start = (np.arange(qt_rows, dtype=np.int64) * t).astype(np.int32)
            q_len = np.clip(nq - q_start.astype(np.int64), 0, t).astype(np.int32)
            nqt = -(-nq // t)  # real (non-empty) query tiles
            pair_a = np.repeat(np.arange(nqt, dtype=np.int64), dt.plan.num_tiles)
            pair_d = np.tile(np.arange(dt.plan.num_tiles, dtype=np.int64), nqt)
            d_tiles, d_len, d_start = dt.tiles, dt.tile_len, dt.tile_start
            num_candidates = nq * snap.num_points
        else:
            q_start = pad_axis0(qplan.q_tile_start, qt_rows)
            q_len = pad_axis0(qplan.q_tile_len, qt_rows)
            pair_a = qplan.pair_q.astype(np.int64)
            pair_d = qplan.pair_d.astype(np.int64)
            d_tiles, d_len, d_start = snap.tiles, snap.tile_len, snap.tile_start
            num_candidates = qplan.num_candidates
        q_tiles = ops.make_tiles_device(
            jnp.asarray(q_sorted),
            jnp.asarray(q_start, jnp.int32),
            jnp.asarray(q_len, jnp.int32),
            tile_size=cfg.tile_size,
            dim_block=cfg.dim_block,
        )
        tiles = ops.row_major(jnp.concatenate([q_tiles, d_tiles], axis=0))
        tile_len = jnp.concatenate([jnp.asarray(q_len, jnp.int32), d_len])
        tile_start = jnp.concatenate(
            [jnp.asarray(q_start, jnp.int32), d_start + n_slots]
        )
        # position -> original id: query rows first (pad rows are never
        # addressed by a valid lane; their fill value is irrelevant), then
        # the data points' grid-sort permutation, padded to the snapshot's
        # point_rows bucket so the shape survives snapshot swaps
        order = jnp.concatenate(
            [
                jnp.asarray(
                    pad_axis0(qplan.q_order.astype(np.int64), n_slots), jnp.int32
                ),
                snap.point_order_padded,
            ]
        )
        pair_b = (pair_d + qt_rows).astype(np.int32)
        backend, shortc = kernel_choice(dec.execution, cfg)
        return QueryPlanTables(
            eps=eps,
            nq=nq,
            n_slots=n_slots,
            qplan=qplan,
            tiles=tiles,
            tile_len=tile_len,
            tile_start=tile_start,
            order=order,
            pair_a=pair_a.astype(np.int32),
            pair_b=pair_b,
            backend=backend,
            shortc=shortc,
            execution=dec.execution,
            cost_indexed=dec.cost_indexed,
            cost_dense=dec.cost_dense,
            num_candidates=num_candidates,
        )

    # -- queries ----------------------------------------------------------

    def _self_tables(
        self, dec: cost_mod.TierDecision, snap: GridSnapshot
    ) -> SelfTables:
        """Device tables of the tier ``dec`` chose, for both modes.

        Both tiers address the same grid-sorted point space (the dense tier
        only re-tiles it), so ``order``, the scatter epilogues and
        ``_unsort_counts`` are tier-independent.
        """
        tier = snap.dense_tables() if dec.execution == "dense" else snap
        return SelfTables(
            tier.tiles, tier.tile_len, tier.tile_start, snap.point_order,
            tier.plan, tier.chunks, *kernel_choice(dec.execution, self.config),
        )

    def count(self, eps: Optional[float] = None) -> SelfJoinResult:
        """Per-point neighbour counts (original order); no pair buffer."""
        eps = self.config.eps if eps is None else float(eps)
        if self.num_points == 0:
            return SelfJoinResult(
                counts=np.zeros(0, np.int64),
                stats=self._base_stats(eps, self.snapshot),
            )
        self._ensure_index(eps)
        snap = self.snapshot
        dec = self.resolve_execution(eps)
        tab = self._self_tables(dec, snap)
        stats = self._self_stats(eps, snap, dec, tab.plan)

        state = (jnp.zeros(snap.num_points, jnp.int32), jnp.zeros((), jnp.int32))
        with obs.span(
            "engine.count", "join",
            n=snap.num_points, eps=eps, tier=dec.execution,
        ):
            table, stats.chunk_table_uploads = tab.chunks(self.engine.count_chunk)
            counts_sorted, skipped_tot, stats.num_chunks = run_count_chunks(
                tab, [table], state, eps, _count_chunk_program,
                "engine.count.chunk",
            )
            stats.num_device_dispatches = stats.num_chunks
            counts = np.asarray(
                _unsort_counts(counts_sorted, snap.point_order)
            ).astype(np.int64)
        stats.num_results = int(counts.sum())
        stats.dim_blocks_skipped = int(skipped_tot)
        stats.dim_blocks_total = tab.plan.num_pairs * snap.num_dim_blocks
        obs.mirror_selfjoin_stats(stats, path="engine", mode="count")
        return SelfJoinResult(counts=counts, stats=stats)

    def count_query(
        self,
        q: np.ndarray,
        eps: Optional[float] = None,
        snapshot: Optional[GridSnapshot] = None,
    ) -> SelfJoinResult:
        """Per-query-point counts of indexed points within eps of each q.

        The bipartite sub-plan of the distributed tier (DESIGN.md #7):
        external query points are binned into this engine's grid, tiled, and
        each (query tile, adjacent data tile) candidate pair runs through the
        same chunked count program as the self-join -- index filtering, SHORTC
        and SORTIDU included.  ``q`` is given in ORIGINAL coordinates (the
        engine applies its own REORDER permutation); counts come back in
        ``q``'s row order.  Self-joining the engine's own dataset equals
        ``count()``:  ``count_query(d).counts == count().counts``.
        """
        eps = self.config.eps if eps is None else float(eps)
        q_pts = np.ascontiguousarray(np.asarray(q, dtype=np.float32))
        nq = q_pts.shape[0]
        tab = self.prepare_query(q_pts, eps, snapshot=snapshot)
        snap = snapshot if snapshot is not None else self.snapshot
        if tab is None:
            return SelfJoinResult(
                counts=np.zeros(nq, np.int64),
                stats=self._base_stats(eps, snap),
            )
        qplan = tab.qplan

        stats = self._base_stats(eps, snap)
        stats.num_points = nq
        stats.num_tile_pairs_total = qplan.num_tile_pairs_total
        stats.num_tile_pairs_evaluated = tab.num_pairs
        stats.num_candidates = tab.num_candidates
        stats.num_candidates_dense = nq * snap.num_points
        stats.num_tiles = int(tab.tiles.shape[0])
        stats.execution = tab.execution
        stats.cost_indexed = tab.cost_indexed
        stats.cost_dense = tab.cost_dense
        state = (jnp.zeros(tab.n_slots, jnp.int32), jnp.zeros((), jnp.int32))
        with obs.span(
            "engine.count_query", "join",
            nq=nq, eps=eps, tier=tab.execution,
        ):
            table, stats.chunk_table_uploads = tab.chunks(self.engine.count_chunk)
            counts_sorted, skipped_tot, stats.num_chunks = run_count_chunks(
                tab, [table], state, eps, _count_chunk_program,
                "engine.count.chunk",
            )
            stats.num_device_dispatches = stats.num_chunks
            counts = np.asarray(
                _unsort_counts(
                    counts_sorted, jnp.asarray(qplan.q_order, jnp.int32)
                )
            ).astype(np.int64)
        stats.num_results = int(counts.sum())
        stats.dim_blocks_skipped = int(skipped_tot)
        stats.dim_blocks_total = tab.num_pairs * snap.num_dim_blocks
        obs.mirror_selfjoin_stats(stats, path="engine", mode="count_query")
        return SelfJoinResult(counts=counts, stats=stats)

    def pairs(
        self,
        eps: Optional[float] = None,
        max_pairs: Optional[int] = None,
        _cap_hint: Optional[int] = None,
        _est_hint: Optional[int] = None,
    ) -> SelfJoinResult:
        """Counts plus the materialized (a, b) pair list, original ids.

        With an explicit ``max_pairs`` (here or in ``EngineConfig``),
        overflow raises ``RuntimeError``.  Otherwise the buffer is sized
        from the paper's result-size estimate (Sec. 3.2.2); on overflow
        the exact |R| is known after the pass, so the buffer regrows to
        it in a single retry.  The estimate also sets the first pass's
        rank window (``_WINDOW_PER_MEAN``); each chunk that outgrew it
        goes again alone for the ranks it is missing (``_remainders``).
        ``_est_hint`` lets ``query()`` supply one shared estimate for a
        whole eps sweep; ``_cap_hint`` sets the capacity with no estimate.
        """
        eps = self.config.eps if eps is None else float(eps)
        if self.num_points == 0:
            return SelfJoinResult(
                counts=np.zeros(0, np.int64),
                stats=self._base_stats(eps, self.snapshot),
                pairs=np.zeros((0, 2), np.int32),
            )
        self._ensure_index(eps)
        snap = self.snapshot
        cfg, eng = self.config, self.engine
        dec = self.resolve_execution(eps)
        tab = self._self_tables(dec, snap)
        plan = tab.plan

        explicit = max_pairs if max_pairs is not None else eng.max_pairs
        auto = explicit is None
        t = cfg.tile_size
        flat_per_chunk = eng.pairs_chunk * t * t
        window = hit_window(0, flat_per_chunk)
        if not auto:
            cap = int(explicit)
        elif _cap_hint is not None:
            cap = int(_cap_hint)
        else:
            est = self._estimate(eps, dec) if _est_hint is None else _est_hint
            cap = batching_mod.suggest_pairs_capacity(est, _PAIRS_HEADROOM)
            num_chunks = max(1, -(-plan.num_pairs // eng.pairs_chunk))
            window = hit_window(
                -(-_WINDOW_PER_MEAN * est // num_chunks), flat_per_chunk
            )

        retries = 0
        dispatches = 0
        rank_rows = 0
        uploads = 0
        stats = self._self_stats(eps, snap, dec, plan)
        while True:
            state = (
                jnp.zeros((cap + window, 2), jnp.int32),
                jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32),
            )
            with obs.span(
                "engine.pairs", "join",
                n=snap.num_points, eps=eps, tier=dec.execution,
                attempt=retries, window=window,
            ):
                table, up = tab.chunks(eng.pairs_chunk)
                uploads += up
                # ends: each chunk's end offset, left on the device
                (buf, offset, max_hits), ends = run_pairs_chunks(
                    tab, [table], state, eps, _pairs_chunk_program, window,
                    "engine.pairs.chunk",
                )
                stats.num_chunks = len(ends)
                dispatches += len(ends)
                rank_rows += stats.num_chunks * window
                num = int(offset)
            # exact |R| is known after a full pass: (auto mode) regrow the
            # buffer to it and run the join again
            if num > cap:
                if auto and retries < _MAX_AUTO_GROW:
                    obs.event(
                        "engine.pairs.retry", "retry", kind="capacity",
                        num=num, cap=cap,
                    )
                    cap = batching_mod.suggest_pairs_capacity(num, 1.0)
                    retries += 1
                    continue
                raise RuntimeError(
                    f"result exceeded max_pairs={cap}; raise the cap or "
                    f"lower eps"
                )
            break

        if int(max_hits) > window:
            buf, extra, rows = self._remainders(
                tab, table, buf, ends, window, flat_per_chunk, int(max_hits),
                eps,
            )
            retries += 1
            dispatches += extra
            rank_rows += rows

        with obs.span("engine.pairs.fetch", "transfer", num=num):
            pairs = np.asarray(buf[:num])
            counts = np.asarray(
                _counts_from_pairs(
                    jnp.zeros(snap.num_points, jnp.int32), buf, offset
                )
            ).astype(np.int64)
        stats.num_results = int(counts.sum())
        stats.dim_blocks_total = plan.num_pairs * snap.num_dim_blocks
        stats.pairs_capacity = cap
        stats.overflow_retries = retries
        stats.num_device_dispatches = dispatches
        stats.rank_rows = rank_rows
        stats.chunk_table_uploads = uploads
        obs.mirror_selfjoin_stats(stats, path="engine", mode="pairs")
        return SelfJoinResult(counts=counts, stats=stats, pairs=pairs)

    @staticmethod
    def _remainders(
        tab, table, buf, ends, window, flat_per_chunk, max_hits, eps
    ):
        """Ranks ``window..`` of every chunk that outgrew the first pass's
        rank window, written into the gap the pass left for them.

        The pass advanced ``offset`` by each chunk's exact hit count, so a
        chunk with ``nh > window`` hits left rows ``[start + window, start +
        nh)`` unwritten.  Each such chunk runs the same chunk program again,
        grouped by the window rung at or above ``nh - window`` (one compile
        per rung), from first rank ``window``.  The buffer gains the widest
        rung as padding so that no block is clamped.  Returns the buffer,
        the dispatches made and their rank-window rows.
        """
        obs.event(
            "engine.pairs.retry", "retry", kind="remainder",
            max_hits=max_hits, hit_cap=window,
        )
        rows = 0
        with obs.span("engine.pairs.remainder", "join") as span:
            end = np.asarray(jax.device_get(ends), np.int64)  # one read back
            nh = np.diff(end, prepend=0)
            over = np.flatnonzero(nh > window)
            rungs: Dict[int, List[int]] = {}
            for i in over:
                rungs.setdefault(
                    hit_window(nh[i] - window, flat_per_chunk), []
                ).append(int(i))
            span.set(chunks=int(over.size), rungs=len(rungs))
            buf = jnp.pad(buf, ((0, max(rungs)), (0, 0)))
            zero = jnp.zeros((), jnp.int32)
            for rung, idx in sorted(rungs.items()):
                for i in idx:
                    obs.event(
                        "engine.pairs.remainder.chunk", "dispatch",
                        rung=rung, chunk=i,
                    )
                    buf, _, _ = _pairs_chunk_program(
                        buf, jnp.int32(end[i] - nh[i] + window), zero,
                        tab.tiles, tab.tile_len, tab.tile_start, tab.order,
                        table.pa, table.pb, table.real, i, eps,
                        hit_cap=rung, backend=tab.backend, first_rank=window,
                    )
                    rows += rung
            buf.block_until_ready()  # the span holds the pass's device time
        return buf, int(over.size), rows

    def _estimate(self, eps: float, dec: cost_mod.TierDecision) -> int:
        """The paper's |R| estimate, which sizes the pairs buffer and the
        first rank window in auto mode.

        The estimate samples the *chosen* tier's candidate pair list with
        the chosen backend, so it reflects the tables that will actually
        run.
        """
        with obs.span("engine.pairs.estimate", "plan", eps=eps):
            tab = self._self_tables(dec, self.snapshot)
            return batching_mod.estimate_result_size(
                tab.tiles, tab.tile_len, tab.plan, eps=eps,
                backend=tab.backend, sample_frac=self.config.sample_frac,
            )

    def query(
        self,
        eps_values: Sequence[float],
        return_pairs: bool = False,
        max_pairs: Optional[int] = None,
    ) -> List[SelfJoinResult]:
        """Multi-eps sweep over one snapshot and one set of executables.

        The snapshot is built once at ``max(eps_values)``; every eps then
        runs through the already-compiled chunk programs (eps is traced, so
        no recompilation happens between sweep points).  In auto-sized pairs
        mode the result-size estimate also runs once, at the largest eps --
        the capacity and first rank window it sets bound every smaller
        sweep point.
        """
        eps_list = [float(e) for e in eps_values]
        if eps_list and self.num_points:
            self._ensure_index(max(eps_list))
        if return_pairs:
            est = None
            explicit = max_pairs if max_pairs is not None else self.engine.max_pairs
            if explicit is None and eps_list and self.num_points:
                dec = self.resolve_execution(max(eps_list))
                est = self._estimate(max(eps_list), dec)
            return [
                self.pairs(e, max_pairs=max_pairs, _est_hint=est)
                for e in eps_list
            ]
        return [self.count(e) for e in eps_list]

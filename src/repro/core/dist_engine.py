"""Grid-indexed distributed self-join (paper Sec. 6 + DESIGN.md #7).

``DistributedSelfJoinEngine`` composes the three pieces the repo grew
separately, into the design the paper actually describes:

  * **entity partitioning** (``core/partition.py``, Sec. 6.2): the query set
    is over-decomposed into N_b batches and assigned to the |p| workers --
    round-robin by default, or cost-estimate-driven LPT (``assign_dynamic``)
    when per-batch cost estimates are requested (paper Figs. 10-11);
  * **ring rotation** (``core/distributed.py``, Sec. 6.3): the dataset is
    entity-partitioned into |p| shards E_0..E_{p-1}; in round r worker k
    holds shard (k - r) mod |p|, so after |p| BSP supersteps every query
    batch has met the whole dataset while only (|p|-1)|D| points crossed
    the wire;
  * **the grid index** (``core/grid.py`` / ``core/engine.py``, Secs. 3-4):
    each worker's local join per round runs through ``build_grid`` /
    ``build_query_tile_plan`` + the engine's chunk programs -- REORDER,
    SORTIDU window pruning and SHORTC included.

The last point is the repair this class exists for: the earlier ring driver
evaluated every (Q_k, E_j) block pair with a dense brute-force matmul,
discarding the index whose filtering is the paper's central contribution
(the distance-similarity predecessor, Gowanlock & Karsin arXiv:1803.04120,
is explicit that every worker runs the full indexed join on its batches).
``SelfJoinResult.stats`` therefore reports both ``num_candidates`` (what the
index evaluated) and ``num_candidates_dense`` (the |Q| x |E| volume the dense
ring pays): their ratio is the distributed filtering power.

Execution model: index construction is host-side (as in the paper) and the
per-round tile evaluation is device code.  Two drivers share that contract:

  * the **host-driven** BSP loop (default): the schedule re-enters Python
    between rounds, so it runs identically on 1 or 8 simulated devices and
    serves as the differential oracle.  It drives no chunk loop of its
    own: a count block is ``SelfJoinEngine.count_query``, a pairs block
    the engine's count loop and exact pairs pass (``run_count_chunks`` /
    ``exact_pairs``), as in the serving tier;
  * the **device-fused** ring (``fused=True``): the per-(worker, round)
    query tile tables and pair lists are packed host-side into uniform
    (fleet-max-padded, sentinel-masked) arrays, the dataset shards' tile
    tables become the ``ppermute`` ring payload of
    ``core.distributed.ring_scan``, and the |p| rounds run as a
    ``fori_loop`` inside ONE compiled ``shard_map`` program -- each round
    evaluated through the same chunked count step as
    ``SelfJoinEngine.count_query`` (``engine.count_chunk_step``).  One
    trace, one dispatch per join; eps stays a traced scalar so an eps sweep
    re-executes the same program.  Its chunk loop is a ``fori_loop`` in
    the program, not the host driver.

Fused pairs mode (DESIGN.md #7b): ``self_join_pairs(fused=True)`` runs the
same one-program ring, but the per-round chunk body is
``engine.pairs_chunk_step`` -- each worker compacts its matched (global
query id, global data id) rows into a preallocated per-worker buffer at a
running cursor that is part of the ring carry, so the cursor (and the
per-chunk max-hit watermark) survives across ``ppermute`` rounds.  The ids
are decoded inside the program through a combined (query | shard) order
table: the query half is packed per (worker, round); the shard half --
``tile_start`` and grid-sort permutation, both pre-offset to global ids --
rides the ring payload next to the shard tile tables.  Overflow accounting
is exact (the cursor advances by true hit counts even past capacity), so
the host retries the one dispatch with a widened rank window or a regrown
buffer, and the retry is rare: capacity is seeded from
``suggest_pairs_capacity`` over the fleet-max per-worker estimate.

Unequal shards from a non-divisible |D| need no sentinel padding on the
host-driven path (shard tile tables are per-shard anyway); the fused path
pads every table to the fleet-wide maximum -- padded tiles carry length 0,
padded pair-list entries sit past the per-chunk ``real`` prefix, and padded
query slots scatter to an out-of-range sentinel dropped by ``mode="drop"``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import batching as batching_mod
from repro.core.distributed import ring_comm_elements, ring_scan
from repro.core.engine import (
    _MAX_AUTO_GROW,
    _PAIRS_HEADROOM,
    SelfJoinEngine,
    _count_chunk_program,
    _pairs_chunk_program,
    count_chunk_step,
    exact_pairs,
    kernel_choice,
    pairs_chunk_step,
    run_count_chunks,
)
from repro.core.grid import adjacent_cell_pairs, build_grid, pad_axis0
from repro.core.partition import EntityPartition, assign_dynamic, make_partition
from repro.core.reorder import apply_reorder, variance_reorder
from repro.core.snapshot import GridSnapshot
from repro.core.types import (
    EngineConfig,
    SelfJoinConfig,
    SelfJoinResult,
    SelfJoinStats,
)
from repro import obs
from repro.kernels import ops

AxisNames = Union[str, Tuple[str, ...]]


@dataclasses.dataclass
class DistributedKnnResult:
    """k nearest neighbours per dataset point, exact, global ids.

    ``indices[i, :]`` are the ids of the k nearest points to point i
    (self included, ties broken by id), -1 padded when k exceeds the
    dataset; ``distances`` are the matching float64 Euclidean distances,
    inf padded.  ``stats`` is the final candidate pass's
    ``SelfJoinStats``.
    """

    indices: np.ndarray      # (n, k) int64
    distances: np.ndarray    # (n, k) float64
    counts: np.ndarray       # (n,) int64 neighbour counts at eps_used
    eps_used: float          # final radius of the adaptive expansion
    eps_rounds: int          # candidate passes run (1 = no growth)
    stats: SelfJoinStats


def _mesh_workers(mesh, axes: AxisNames) -> int:
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    size = 1
    for a in axes_t:
        size *= mesh.shape[a]
    return int(size)


class DistributedSelfJoinEngine:
    """Entity-partitioned, grid-indexed ring self-join over |p| workers.

    ``num_workers`` may be given directly or derived from a ``jax`` mesh
    (``mesh=`` plus the ``axes`` the ring spans -- a 1-axis ``("data",)``
    mesh and the joint ``("pod", "data")`` mesh both work; the ring simply
    spans the product of the named axes, as in ``ring_self_join_counts``).

    ``assignment="round_robin"`` reproduces the paper's default batch
    assignment; ``assignment="dynamic"`` runs the sampling-style cost
    estimate (adjacent-cell candidate volume per batch) through the greedy
    LPT scheduler for straggler mitigation (paper Sec. 6.2).

    ``fused=True`` (requires a mesh whose ring size equals ``num_workers``)
    compiles the whole BSP schedule into one ``shard_map`` program --
    ``count()`` then costs exactly one device dispatch and an eps sweep
    re-executes the same executable (see module docstring / DESIGN.md #7a).
    The default host-driven loop is its differential oracle.
    """

    def __init__(
        self,
        d: np.ndarray,
        config: SelfJoinConfig,
        *,
        num_workers: Optional[int] = None,
        mesh=None,
        axes: AxisNames = "data",
        num_batches: Optional[int] = None,
        assignment: str = "round_robin",
        engine_config: Optional[EngineConfig] = None,
        fused: bool = False,
    ):
        if num_workers is None:
            if mesh is None:
                raise ValueError("pass num_workers or a mesh")
            num_workers = _mesh_workers(mesh, axes)
        if num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        if assignment not in ("round_robin", "dynamic"):
            raise ValueError(f"unknown assignment {assignment!r}")
        if fused:
            if mesh is None:
                raise ValueError("fused=True needs a mesh (one ring position per device)")
            if num_workers != _mesh_workers(mesh, axes):
                raise ValueError(
                    "fused=True requires num_workers == mesh ring size "
                    f"({num_workers} != {_mesh_workers(mesh, axes)})"
                )

        self.config = config
        self.engine_config = engine_config
        self.mesh = mesh
        self.axes = axes
        self._pts = np.ascontiguousarray(np.asarray(d, dtype=np.float32))
        self.num_points, self.num_dims = self._pts.shape
        self.num_workers = int(num_workers)

        # one REORDER frame for the whole dataset, shared by every shard: the
        # f32 distance sums then add coordinates in the same order as the
        # single-device engine, so results at the eps boundary do not depend
        # on the partitioning
        self._perm = None
        if config.reorder and self.num_points:
            _, self._perm = variance_reorder(self._pts, config.sample_frac)

        # dataset shards E_j: contiguous entity partition, unequal tails ok
        self.shard_bounds = np.linspace(
            0, self.num_points, self.num_workers + 1
        ).round().astype(np.int64)
        self.shards: List[SelfJoinEngine] = [
            SelfJoinEngine.from_snapshot(
                GridSnapshot.build(
                    self._pts[self.shard_bounds[j]:self.shard_bounds[j + 1]],
                    config, perm=self._perm,
                ),
                engine_config,
            )
            for j in range(self.num_workers)
        ]

        # query batches Q_l, over-decomposed (N_b defaults to 4|p|)
        n_b = num_batches if num_batches is not None else 4 * self.num_workers
        self.partition: EntityPartition = make_partition(
            self.num_points, self.num_workers, n_b
        )
        self._batch_costs: Optional[np.ndarray] = None
        if assignment == "dynamic":
            self.partition.assignment = assign_dynamic(
                self.estimate_batch_costs(), self.num_workers
            )
        self.assignment = assignment

        # fused-ring state (built lazily on the first fused count)
        self.fused = bool(fused)
        self._fused_pack = None       # packed tables + compiled program
        self.fused_traces = 0         # times the fused count program was traced
        self.fused_executions = 0     # times it was executed
        self.fused_pairs_traces = 0      # fused pairs-program traces
        self.fused_pairs_executions = 0  # fused pairs-program executions

    # -- partitioning -----------------------------------------------------

    def worker_query_index(self, worker: int) -> np.ndarray:
        """Original-order indices of all query points owned by ``worker``."""
        ranges = [
            np.arange(*self.partition.query_range(b), dtype=np.int64)
            for b in self.partition.batches_of(worker)
        ]
        if not ranges:
            return np.zeros(0, np.int64)
        return np.concatenate(ranges)

    def estimate_batch_costs(self) -> np.ndarray:
        """Per-batch candidate-volume estimates from one global grid probe.

        The cost of joining a batch is dominated by its candidate count; the
        grid gives it cheaply: for every point, the total population of its
        3^k adjacent non-empty cells.  One ``build_grid`` over the full
        (reordered) dataset plus one vectorized adjacency probe -- the same
        sampling-pass flavour the paper uses to drive its scheduler.
        """
        if self._batch_costs is not None:
            return self._batch_costs
        costs = np.zeros(self.partition.num_batches, dtype=np.float64)
        if self.num_points == 0:
            self._batch_costs = costs
            return costs
        work = self._pts
        if self._perm is not None:
            work = apply_reorder(self._pts, self._perm)
        grid = build_grid(work, self.config.eps, self.config.k)
        ca, cb = adjacent_cell_pairs(grid)
        cell_cand = np.zeros(grid.num_cells, dtype=np.float64)
        np.add.at(cell_cand, ca, grid.cell_count[cb].astype(np.float64))
        cell_of_point = np.repeat(
            np.arange(grid.num_cells, dtype=np.int64), grid.cell_count
        )
        per_point = np.empty(self.num_points, dtype=np.float64)
        per_point[grid.point_order] = cell_cand[cell_of_point]
        for b in range(self.partition.num_batches):
            lo, hi = self.partition.query_range(b)
            costs[b] = per_point[lo:hi].sum()
        self._batch_costs = costs
        return costs

    def worker_loads(self) -> np.ndarray:
        """Estimated candidate load per worker under the current assignment."""
        costs = self.estimate_batch_costs()
        loads = np.zeros(self.num_workers, dtype=np.float64)
        for b in range(self.partition.num_batches):
            loads[self.partition.assignment[b]] += costs[b]
        return loads

    # -- ring schedule ----------------------------------------------------

    def ring_schedule(self) -> List[List[Tuple[int, int]]]:
        """Round r -> [(worker k, shard it holds)]: shard (k - r) mod |p|."""
        p = self.num_workers
        return [[(k, (k - r) % p) for k in range(p)] for r in range(p)]

    def comm_elements(self) -> int:
        """Ring transport volume in points: (|p| - 1) |D| (paper Sec. 6.3)."""
        return ring_comm_elements(self.num_points, self.num_workers)

    # -- fused device ring (DESIGN.md #7 addendum) -------------------------

    def _pack_fused(self, eps: float):
        """Pack the fused ring's device tables and compile its program.

        Everything host-side happens here, once per index radius: the |p|^2
        bipartite query plans (worker k's batches binned into shard j's
        grid, j = (k - r) mod |p| for round r), padded to fleet-wide maxima
        so one trace fits every ring position, plus the padded shard tile
        tables that form the rotating payload.  eps is NOT baked in -- the
        program takes it as a traced scalar, so a sweep at or below the
        packed radius reuses both the pack and the compiled executable.
        """
        with obs.span(
            "ring.pack", "plan", workers=self.num_workers, eps=float(eps)
        ):
            return self._pack_fused_impl(eps)

    def _pack_fused_impl(self, eps: float):
        p = self.num_workers
        cfg = self.config
        eng = self.engine_config or EngineConfig()
        t = cfg.tile_size
        nb = self.shards[0].snapshot.num_dim_blocks
        db = cfg.dim_block

        q_index = [self.worker_query_index(k) for k in range(p)]
        q_pts = [self._pts[idx] for idx in q_index]
        nq = [int(idx.size) for idx in q_index]
        max_nq = max(max(nq), 1)

        # |p|^2 host-side bipartite plans: worker k meets shard (k - r) % p
        # in round r (None where either side is empty -> fully masked round)
        qplans = []
        for k in range(p):
            row = []
            for r in range(p):
                if nq[k]:
                    with obs.span(
                        "ring.pack.plan", "ring",
                        worker=k, round=r, nq=nq[k],
                    ):
                        row.append(
                            self.shards[(k - r) % p].build_query_plan(
                                q_pts[k], eps
                            )
                        )
                else:
                    row.append(None)
            qplans.append(row)
        flat = [qp for row in qplans for qp in row if qp is not None]
        max_qt = max(max((qp.num_q_tiles for qp in flat), default=0), 1)
        max_dt = max(max((e.snapshot.plan.num_tiles if e.snapshot.plan else 0
                  for e in self.shards), default=0), 1)
        max_pr = max((qp.num_pairs for qp in flat), default=0)
        chunk = max(1, min(eng.count_chunk, max(max_pr, 1)))
        n_chunks = max(-(-max_pr // chunk), 1)
        chunk_p = max(1, min(eng.pairs_chunk, max(max_pr, 1)))
        n_chunks_p = max(-(-max_pr // chunk_p), 1)
        shard_sizes = np.diff(self.shard_bounds)
        max_sn = max(int(shard_sizes.max()) if shard_sizes.size else 0, 1)

        qt = np.zeros((p, p, max_qt, nb, t, db), np.float32)
        qstart = np.zeros((p, p, max_qt), np.int32)
        qlen = np.zeros((p, p, max_qt), np.int32)
        qord = np.full((p, p, max_nq), max_nq, np.int32)   # sentinel: dropped
        pq = np.zeros((p, p, n_chunks, chunk), np.int32)
        pd = np.zeros((p, p, n_chunks, chunk), np.int32)
        real = np.zeros((p, p, n_chunks), np.int32)
        dt = np.zeros((p, max_dt, nb, t, db), np.float32)
        dlen = np.zeros((p, max_dt), np.int32)
        # pairs-mode tables (DESIGN.md #7b): the same plans re-chunked at the
        # pairs granularity, plus the global-id decode tables -- the query
        # half (qog) indexed per (worker, round), the shard half (dstart,
        # dord, already offset to global ids) riding the ring payload
        qog = np.zeros((p, p, max_nq), np.int32)
        pqp = np.zeros((p, p, n_chunks_p, chunk_p), np.int32)
        pdp = np.zeros((p, p, n_chunks_p, chunk_p), np.int32)
        realp = np.zeros((p, p, n_chunks_p), np.int32)
        dstart = np.zeros((p, max_dt), np.int32)
        dord = np.zeros((p, max_sn), np.int32)

        for j, e in enumerate(self.shards):
            dt[j], dlen[j] = e.snapshot.packed_tile_table(max_dt)
            if e.snapshot.plan is not None:
                dstart[j] = pad_axis0(
                    e.snapshot.plan.tile_start.astype(np.int32), max_dt
                )
            if e.snapshot.grid is not None:
                dord[j, : shard_sizes[j]] = (
                    self.shard_bounds[j] + e.snapshot.grid.point_order
                ).astype(np.int32)

        stats_pairs_total = stats_pairs_eval = stats_candidates = 0
        for k in range(p):
            for r in range(p):
                qp = qplans[k][r]
                if qp is None:
                    continue
                stats_pairs_total += qp.num_tile_pairs_total
                stats_pairs_eval += qp.num_pairs
                stats_candidates += qp.num_candidates
                if qp.num_q_tiles:
                    tiles_kr, len_kr = ops.make_tiles(
                        qp.q_sorted, qp.q_tile_start, qp.q_tile_len, t, db
                    )
                    qt[k, r, : tiles_kr.shape[0]] = tiles_kr
                    qlen[k, r] = pad_axis0(len_kr, max_qt)
                    qstart[k, r] = pad_axis0(qp.q_tile_start, max_qt)
                qord[k, r, : nq[k]] = qp.q_order.astype(np.int32)
                # pairs decode: q-sorted position -> GLOBAL query id
                qog[k, r, : nq[k]] = q_index[k][qp.q_order].astype(np.int32)
                if qp.num_pairs:
                    pq[k, r].reshape(-1)[: qp.num_pairs] = qp.pair_q
                    # B side indexes the concatenated [query | shard] table
                    pd[k, r].reshape(-1)[: qp.num_pairs] = qp.pair_d + max_qt
                    real[k, r] = np.clip(
                        qp.num_pairs - np.arange(n_chunks) * chunk, 0, chunk
                    ).astype(np.int32)
                    pqp[k, r].reshape(-1)[: qp.num_pairs] = qp.pair_q
                    pdp[k, r].reshape(-1)[: qp.num_pairs] = qp.pair_d + max_qt
                    realp[k, r] = np.clip(
                        qp.num_pairs - np.arange(n_chunks_p) * chunk_p,
                        0, chunk_p,
                    ).astype(np.int32)

        axes_t = (self.axes,) if isinstance(self.axes, str) else tuple(self.axes)
        ax = axes_t if len(axes_t) > 1 else axes_t[0]
        backend, shortc = kernel_choice("indexed", cfg)
        engine_self = self

        def local(qt, qstart, qlen, qord, pq, pd, real, dt, dlen, eps_in):
            engine_self.fused_traces += 1  # traced once; executions replay it
            obs.event("ring.trace", "compile", program="fused_count")
            qt, qstart, qlen, qord = qt[0], qstart[0], qlen[0], qord[0]
            pq, pd, real = pq[0], pd[0], real[0]
            dt, dlen = dt[0], dlen[0]

            def round_body(r, counts_local, payload):
                d_tiles, d_len = payload
                tiles = jnp.concatenate([qt[r], d_tiles], axis=0)
                tlen = jnp.concatenate([qlen[r], d_len])
                # B-side starts are never read (only pair_a rows scatter)
                tstart = jnp.concatenate([qstart[r], jnp.zeros_like(d_len)])

                def chunk_body(c, counts_sorted):
                    counts_sorted, _ = count_chunk_step(
                        counts_sorted, jnp.zeros((), jnp.int32),
                        tiles, tlen, tstart,
                        pq[r, c], pd[r, c], real[r, c], eps_in,
                        shortc=shortc, backend=backend,
                    )
                    return counts_sorted

                counts_sorted = jax.lax.fori_loop(
                    0, n_chunks, chunk_body, jnp.zeros(max_nq, jnp.int32)
                )
                # per-round q_order: q-sorted position -> worker-local slot
                return counts_local.at[qord[r]].add(counts_sorted, mode="drop")

            counts = ring_scan(
                axes_t, round_body, jnp.zeros(max_nq, jnp.int32), (dt, dlen)
            )
            return counts[None]

        def pspec(arr):
            return P(ax, *([None] * (arr.ndim - 1)))

        # pairs capacity seeding: hit-rate sample on the heaviest (k, r)
        # block (small explicit chunk -- the default 4096-pair padding would
        # dwarf the sample), scaled by each worker's total candidate volume
        best_kr = None
        for k in range(p):
            for r in range(p):
                qp = qplans[k][r]
                if qp is not None and qp.num_pairs and (
                    best_kr is None
                    or qp.num_pairs > qplans[best_kr[0]][best_kr[1]].num_pairs
                ):
                    best_kr = (k, r)
        hit_rate = 0.0
        if best_kr is not None:
            k0, r0 = best_kr
            with obs.span(
                "ring.pack.sample", "plan", worker=k0, round=r0
            ) as _sp:
                qp = qplans[k0][r0]
                j0 = (k0 - r0) % p
                n_s = min(qp.num_pairs, 512)
                rng = np.random.default_rng(0)
                sel = (
                    rng.choice(qp.num_pairs, size=n_s, replace=False)
                    if qp.num_pairs > n_s else np.arange(n_s)
                )
                len_c = np.concatenate([qlen[k0, r0], dlen[j0]])
                counts_s, _ = ops.tile_counts(
                    np.concatenate([qt[k0, r0], dt[j0]], axis=0), len_c,
                    qp.pair_q[sel], qp.pair_d[sel] + max_qt,
                    eps=eps, shortc=shortc,
                    backend=backend, chunk=min(n_s, 512),
                )
                cand_s = float(
                    (len_c[qp.pair_q[sel]].astype(np.float64)
                     * len_c[qp.pair_d[sel] + max_qt]).sum()
                )
                hit_rate = float(counts_s.sum()) / max(cand_s, 1.0)
                _sp.set(hit_rate=hit_rate, sampled_pairs=int(n_s))
        pairs_est = [
            int(np.ceil(hit_rate * sum(
                qp.num_candidates for qp in qplans[k] if qp is not None
            )))
            for k in range(p)
        ]
        pairs_cap = batching_mod.suggest_pairs_capacity(
            max(pairs_est, default=0), _PAIRS_HEADROOM
        )

        # tables go device-resident (with their ring sharding, row-major as
        # every tile table, ops.row_major) at pack time: repeat joins and eps
        # sweeps then transfer only the eps scalar
        def place(a):
            return ops.row_major(
                jax.device_put(a, NamedSharding(self.mesh, pspec(a)))
            )

        args = tuple(
            place(a) for a in (qt, qstart, qlen, qord, pq, pd, real, dt, dlen)
        )
        fn = jax.jit(
            jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=tuple(pspec(a) for a in args) + (P(),),
                out_specs=P(ax, None),
                # off for both backends: pallas_call has no varying-axes rule,
                # and the program's outputs are device-varying by
                # construction, so the check would add nothing
                check_vma=False,
            )
        )

        pairs_extra = tuple(
            place(a) for a in (qog, pqp, pdp, realp, dstart, dord)
        )
        pairs_args = (
            args[0], args[1], args[2],                               # qt qstart qlen
            pairs_extra[0], pairs_extra[1], pairs_extra[2], pairs_extra[3],
            args[7], args[8],                                        # dt dlen
            pairs_extra[4], pairs_extra[5],                          # dstart dord
        )
        pairs_in_specs = tuple(pspec(a) for a in pairs_args) + (P(),)

        def make_pairs_fn(cap: int, hit_cap: int):
            """One-program fused pairs ring, compiled for (cap, hit_cap).

            Same transport as the count program, but the ring carry is the
            per-worker (buffer, cursor, max-chunk-hits) triple of
            ``pairs_chunk_step`` and the payload additionally rotates the
            shard-side decode tables.  Programs are cached per (cap,
            hit_cap) by the caller; a non-overflowing join uses exactly one.
            """

            def local_pairs(qt, qstart, qlen, qog, pqp, pdp, realp,
                            dt, dlen, dstart, dord, eps_in):
                engine_self.fused_pairs_traces += 1
                obs.event(
                    "ring.trace", "compile", program="fused_pairs",
                    cap=cap, hit_cap=hit_cap,
                )
                qt, qstart, qlen, qog = qt[0], qstart[0], qlen[0], qog[0]
                pqp, pdp, realp = pqp[0], pdp[0], realp[0]
                dt, dlen, dstart, dord = dt[0], dlen[0], dstart[0], dord[0]

                def round_body(r, carry, payload):
                    d_tiles, d_len, d_start, d_ord = payload
                    tiles = jnp.concatenate([qt[r], d_tiles], axis=0)
                    tlen = jnp.concatenate([qlen[r], d_len])
                    # combined (query | shard) position space: B-side starts
                    # offset past the query slots, ids decode through the
                    # concatenated order table to GLOBAL point ids
                    tstart = jnp.concatenate([qstart[r], d_start + max_nq])
                    order = jnp.concatenate([qog[r], d_ord])

                    def chunk_body(c, carry2):
                        return pairs_chunk_step(
                            *carry2, tiles, tlen, tstart, order,
                            pqp[r, c], pdp[r, c], realp[r, c], eps_in,
                            hit_cap=hit_cap, backend=backend,
                        )

                    return jax.lax.fori_loop(0, n_chunks_p, chunk_body, carry)

                carry0 = (
                    jnp.zeros((cap + hit_cap, 2), jnp.int32),
                    jnp.zeros((), jnp.int32),
                    jnp.zeros((), jnp.int32),
                )
                buf, off, mh = ring_scan(
                    axes_t, round_body, carry0, (dt, dlen, dstart, dord)
                )
                return buf[None], off[None], mh[None]

            return jax.jit(
                jax.shard_map(
                    local_pairs,
                    mesh=self.mesh,
                    in_specs=pairs_in_specs,
                    out_specs=(P(ax, None, None), P(ax), P(ax)),
                    check_vma=False,  # as for the count program
                )
            )

        self._fused_pack = dict(
            eps=float(eps), fn=fn, args=args,
            q_index=q_index, nq=nq, n_chunks=n_chunks,
            stats=(stats_pairs_total, stats_pairs_eval, stats_candidates),
            pairs_args=pairs_args, make_pairs_fn=make_pairs_fn,
            pairs_fns={},                       # (cap, hit_cap) -> compiled fn
            pairs_cap=pairs_cap, pairs_est=pairs_est,
            n_chunks_p=n_chunks_p,
            pairs_flat_per_chunk=chunk_p * t * t,
            # expected hits in one full pairs chunk, for rank-window seeding
            pairs_hit_est=int(np.ceil(hit_rate * chunk_p * t * t)),
        )
        return self._fused_pack

    def _count_fused(self, eps: float) -> SelfJoinResult:
        """One-dispatch fused ring count (counts == host-driven ``count()``)."""
        pack = self._fused_pack
        if pack is None or eps > pack["eps"]:
            pack = self._pack_fused(max(eps, self.config.eps))
        with obs.span(
            "ring.fused.count", "dispatch",
            workers=self.num_workers, rounds=self.num_workers, eps=eps,
        ):
            out = np.asarray(
                jax.device_get(pack["fn"](*pack["args"], jnp.float32(eps)))
            )
        self.fused_executions += 1
        counts = np.zeros(self.num_points, dtype=np.int64)
        for k in range(self.num_workers):
            counts[pack["q_index"][k]] = out[k, : pack["nq"][k]]
        pairs_total, pairs_eval, candidates = pack["stats"]
        stats = self._index_stats(SelfJoinStats(
            num_points=self.num_points,
            num_dims=self.num_dims,
            k=min(self.config.k, self.num_dims),
            num_workers=self.num_workers,
            num_rounds=self.num_workers,
            comm_elements=self.comm_elements(),
            num_tile_pairs_total=pairs_total,
            num_tile_pairs_evaluated=pairs_eval,
            num_candidates=candidates,
            num_chunks=self.num_workers * pack["n_chunks"],
            num_device_dispatches=1,
            num_candidates_dense=self._dense_candidates(pack["nq"]),
            num_results=int(counts.sum()),
        ))
        obs.mirror_selfjoin_stats(stats, path="ring_fused", mode="count")
        return SelfJoinResult(counts=counts, stats=stats)

    def _index_stats(self, stats: SelfJoinStats) -> SelfJoinStats:
        stats.num_tiles = sum(
            e.snapshot.plan.num_tiles for e in self.shards if e.snapshot.plan
        )
        stats.num_nonempty_cells = sum(
            e.snapshot.grid.num_cells for e in self.shards if e.snapshot.grid
        )
        return stats

    def _dense_candidates(self, nq: List[int]) -> int:
        shard_sizes = np.diff(self.shard_bounds)
        return int(
            sum(
                nq[k] * shard_sizes[j]
                for sched in self.ring_schedule()
                for k, j in sched
            )
        )

    def _pairs_fused(
        self, eps: float, max_pairs: Optional[int] = None
    ) -> SelfJoinResult:
        """One-dispatch fused ring pairs join (DESIGN.md #7b).

        Every worker fills its own (capacity + hit_cap, 2) buffer inside the
        single ``shard_map`` program; the per-worker cursors and max-chunk
        hit watermarks come back with the buffers, so overflow is detected
        exactly on the host.  The retry ladder mirrors
        ``SelfJoinEngine.pairs``: widen the per-chunk rank window first
        (compaction correctness), then regrow the buffer to the measured
        fleet-max |R_k| (auto mode only; an explicit ``max_pairs`` raises).
        Each (cap, hit_cap) compiles once and is cached in the pack, so a
        non-overflowing join costs one trace and one dispatch.
        """
        pack = self._fused_pack
        if pack is None or eps > pack["eps"]:
            pack = self._pack_fused(max(eps, self.config.eps))
        eng = self.engine_config or EngineConfig()
        p = self.num_workers
        explicit = max_pairs if max_pairs is not None else eng.max_pairs
        auto = explicit is None
        cap = pack["pairs_cap"] if auto else int(explicit)
        flat_per_chunk = pack["pairs_flat_per_chunk"]
        # rank-window seed: 4x the sampled expected per-chunk hits absorbs
        # chunk-to-chunk skew, so the first join rarely needs the widen retry
        hit_cap = min(
            flat_per_chunk,
            max(4096, -(-4 * pack["pairs_hit_est"] // 1024) * 1024),
        )
        warm = pack.get("pairs_warm")
        if warm is not None:  # converged settings of an earlier join: 0 retries
            hit_cap = max(hit_cap, warm[1])
            if auto:
                cap = max(cap, warm[0])

        retries = 0
        while True:
            key = (cap, hit_cap)
            fn = pack["pairs_fns"].get(key)
            if fn is None:
                fn = pack["make_pairs_fn"](cap, hit_cap)
                pack["pairs_fns"][key] = fn
            with obs.span(
                "ring.fused.pairs", "dispatch",
                workers=p, rounds=p, eps=eps, attempt=retries,
                cap=cap, hit_cap=hit_cap,
            ):
                buf, off, mh = fn(*pack["pairs_args"], jnp.float32(eps))
            self.fused_pairs_executions += 1
            off_np = np.asarray(jax.device_get(off)).astype(np.int64)
            mh_np = np.asarray(jax.device_get(mh)).astype(np.int64)
            max_off = int(off_np.max()) if off_np.size else 0
            max_mh = int(mh_np.max()) if mh_np.size else 0
            # exact totals are known after the one dispatch, so each
            # overflow kind resolves in one retry (same ladder as
            # SelfJoinEngine.pairs)
            if max_mh > hit_cap:
                if retries >= _MAX_AUTO_GROW:
                    raise RuntimeError(
                        f"fused pairs rank window did not converge "
                        f"(max chunk hits {max_mh} > hit_cap {hit_cap})"
                    )
                obs.event(
                    "ring.pairs.retry", "retry", kind="hit_cap",
                    max_hits=max_mh, hit_cap=hit_cap,
                )
                hit_cap = min(flat_per_chunk, -(-max_mh // 1024) * 1024)
                retries += 1
                continue
            if max_off > cap:
                if auto and retries < _MAX_AUTO_GROW:
                    obs.event(
                        "ring.pairs.retry", "retry", kind="capacity",
                        num=max_off, cap=cap,
                    )
                    cap = batching_mod.suggest_pairs_capacity(max_off, 1.0)
                    retries += 1
                    continue
                raise RuntimeError(
                    f"fused ring worker found {max_off} pairs, exceeding "
                    f"max_pairs={cap}; raise the cap or lower eps"
                )
            if auto:
                pack["pairs_warm"] = (cap, hit_cap)
            break

        buf_np = np.asarray(jax.device_get(buf))
        parts = [buf_np[k, : off_np[k]] for k in range(p)]
        pairs = (
            np.concatenate(parts) if parts else np.zeros((0, 2), np.int32)
        ).astype(np.int32)
        counts = np.zeros(self.num_points, dtype=np.int64)
        if pairs.shape[0]:
            counts = np.bincount(
                pairs[:, 0], minlength=self.num_points
            ).astype(np.int64)
        pairs_total, pairs_eval, candidates = pack["stats"]
        stats = SelfJoinStats(
            num_points=self.num_points,
            num_dims=self.num_dims,
            k=min(self.config.k, self.num_dims),
            num_workers=p,
            num_rounds=p,
            comm_elements=self.comm_elements(),
            num_tile_pairs_total=pairs_total,
            num_tile_pairs_evaluated=pairs_eval,
            num_candidates=candidates,
            num_chunks=p * pack["n_chunks_p"],
            num_device_dispatches=1 + retries,
            pairs_capacity=cap,
            overflow_retries=retries,
            worker_pair_cursors=tuple(int(x) for x in off_np),
            worker_max_chunk_hits=tuple(int(x) for x in mh_np),
            num_candidates_dense=self._dense_candidates(pack["nq"]),
            num_results=int(pairs.shape[0]),
        )
        obs.mirror_selfjoin_stats(stats, path="ring_fused", mode="pairs")
        return SelfJoinResult(
            counts=counts, stats=self._index_stats(stats), pairs=pairs
        )

    def _block_pairs(
        self,
        k: int,
        j: int,
        q_pts_k: np.ndarray,
        eps: float,
        eng: EngineConfig,
        stats: SelfJoinStats,
    ) -> np.ndarray:
        """Exact (global query id, global data id) pairs of one (Q_k, E_j)
        block: the count pass, then the exact pairs pass it sizes
        (``engine.exact_pairs``), as in the serving tier."""
        tab = self.shards[j].prepare_query(q_pts_k, eps)
        if tab is None:
            return np.zeros((0, 2), np.int64)
        eps_d = jnp.float32(eps)
        count_table, uploads = tab.chunks(eng.count_chunk)
        counts_sorted, _, n_count = run_count_chunks(
            tab, [count_table],
            (jnp.zeros(tab.n_slots, jnp.int32), jnp.zeros((), jnp.int32)),
            eps_d, _count_chunk_program, "ring.block.count.chunk",
            worker=k, shard=j,
        )
        total = int(np.asarray(counts_sorted.sum()))
        pairs_table, more = tab.chunks(eng.pairs_chunk)
        blk, n_pairs = exact_pairs(
            tab, [pairs_table], eps_d, total, _pairs_chunk_program,
            "ring.block.pairs.chunk", worker=k, shard=j,
        )
        stats.chunk_table_uploads += uploads + more
        stats.num_device_dispatches += n_count + n_pairs
        stats.num_chunks += n_pairs
        stats.num_tile_pairs_total += tab.qplan.num_tile_pairs_total
        stats.num_tile_pairs_evaluated += tab.num_pairs
        stats.num_candidates += tab.num_candidates

        blk = blk.astype(np.int64)
        if blk.shape[0]:
            # order decodes A-side to q-row ids, B-side to shard-local ids
            blk[:, 0] = self.worker_query_index(k)[blk[:, 0]]
            blk[:, 1] += self.shard_bounds[j]
        return blk

    def _pairs_host(
        self, eps: float, max_pairs: Optional[int] = None
    ) -> SelfJoinResult:
        """Host-driven BSP pairs join: the fused path's differential oracle.

        Same |p|-round schedule as ``count()``, each (worker, shard) block
        materialized through the chunked pairs program and decoded to
        global ids on the host.  Exact by construction (count-first
        sizing); an explicit ``max_pairs`` below the true |R| raises, for
        API symmetry with the fused path.
        """
        eng = self.engine_config or EngineConfig()
        stats = SelfJoinStats(
            num_points=self.num_points,
            num_dims=self.num_dims,
            k=min(self.config.k, self.num_dims),
            num_workers=self.num_workers,
            comm_elements=self.comm_elements(),
        )
        q_index = [self.worker_query_index(k) for k in range(self.num_workers)]
        q_points = [self._pts[idx] for idx in q_index]
        blocks = []
        for r, round_sched in enumerate(self.ring_schedule()):
            with obs.span(
                "ring.round", "ring",
                round=r, workers=self.num_workers, mode="pairs",
            ):
                for k, j in round_sched:
                    if q_index[k].size == 0:
                        continue
                    blocks.append(
                        self._block_pairs(k, j, q_points[k], eps, eng, stats)
                    )
            stats.num_rounds += 1
        pairs = (
            np.concatenate(blocks) if blocks else np.zeros((0, 2), np.int64)
        ).astype(np.int32)
        explicit = max_pairs if max_pairs is not None else eng.max_pairs
        if explicit is not None and pairs.shape[0] > int(explicit):
            raise RuntimeError(
                f"result exceeded max_pairs={int(explicit)}; raise the cap "
                f"or lower eps"
            )
        counts = np.zeros(self.num_points, dtype=np.int64)
        if pairs.shape[0]:
            counts = np.bincount(
                pairs[:, 0], minlength=self.num_points
            ).astype(np.int64)
        stats.num_results = int(pairs.shape[0])
        stats.num_candidates_dense = self._dense_candidates(
            [idx.size for idx in q_index]
        )
        obs.mirror_selfjoin_stats(stats, path="ring_host", mode="pairs")
        return SelfJoinResult(
            counts=counts, stats=self._index_stats(stats), pairs=pairs
        )

    # -- queries ----------------------------------------------------------

    def count(self, eps: Optional[float] = None) -> SelfJoinResult:
        """Per-point neighbour counts (self included), original order.

        Executes the |p|-round BSP schedule: in round r every worker joins
        its query batches against the shard it currently holds, through that
        shard's grid index (``SelfJoinEngine.count_query``).  Counts
        accumulate across rounds; after |p| rounds each query point has met
        every shard exactly once, so the result equals the single-device
        ``SelfJoinEngine.count()`` and the brute-force oracle.

        With ``fused=True`` the same schedule runs as one compiled
        ``shard_map`` program (``_count_fused``); this host-driven loop is
        its differential oracle.
        """
        eps = self.config.eps if eps is None else float(eps)
        if self.fused and self.num_points:
            return self._count_fused(eps)
        counts = np.zeros(self.num_points, dtype=np.int64)
        stats = SelfJoinStats(
            num_points=self.num_points,
            num_dims=self.num_dims,
            k=min(self.config.k, self.num_dims),
            num_workers=self.num_workers,
            comm_elements=self.comm_elements(),
        )
        q_index = [self.worker_query_index(k) for k in range(self.num_workers)]
        q_points = [self._pts[idx] for idx in q_index]
        for r, round_sched in enumerate(self.ring_schedule()):
            with obs.span(
                "ring.round", "ring",
                round=r, workers=self.num_workers, mode="count",
            ):
                for k, j in round_sched:
                    if q_index[k].size == 0:
                        continue
                    res = self.shards[j].count_query(q_points[k], eps)
                    counts[q_index[k]] += res.counts
                    s = res.stats
                    stats.num_tile_pairs_total += s.num_tile_pairs_total
                    stats.num_tile_pairs_evaluated += s.num_tile_pairs_evaluated
                    stats.num_candidates += s.num_candidates
                    stats.num_chunks += s.num_chunks
                    stats.num_device_dispatches += s.num_chunks
                    stats.chunk_table_uploads += s.chunk_table_uploads
                    stats.dim_blocks_skipped += s.dim_blocks_skipped
                    stats.dim_blocks_total += s.dim_blocks_total
            stats.num_rounds += 1
        stats.num_candidates_dense = self._dense_candidates(
            [idx.size for idx in q_index]
        )
        stats.num_results = int(counts.sum())
        obs.mirror_selfjoin_stats(stats, path="ring_host", mode="count")
        return SelfJoinResult(counts=counts, stats=self._index_stats(stats))

    def self_join_pairs(
        self,
        eps: Optional[float] = None,
        max_pairs: Optional[int] = None,
        fused: Optional[bool] = None,
    ) -> SelfJoinResult:
        """Counts plus the materialized (a, b) pair list, GLOBAL ids.

        Distributed analogue of ``SelfJoinEngine.pairs``: both (a, b) and
        (b, a) appear, as does (a, a); ``counts`` equals ``count()``.
        ``fused=None`` follows the engine's construction mode; ``fused=
        False`` forces the host-driven BSP loop (the differential oracle)
        even on a fused engine; ``fused=True`` requires one.  The fused
        path is one device dispatch per non-overflowing join
        (``_pairs_fused``); the host path is |p|^2 blocks of
        count-then-pairs dispatches.  Pair order differs between the two
        paths (per-worker ring order vs schedule order) -- the pair SET is
        identical.
        """
        eps = self.config.eps if eps is None else float(eps)
        use_fused = self.fused if fused is None else bool(fused)
        if use_fused and not self.fused:
            raise ValueError(
                "fused=True requires an engine constructed with fused=True "
                "(a mesh-backed ring)"
            )
        if use_fused and self.num_points:
            return self._pairs_fused(eps, max_pairs)
        return self._pairs_host(eps, max_pairs)

    def knn(
        self,
        k_neighbors: int,
        eps0: Optional[float] = None,
        fused: Optional[bool] = None,
    ) -> DistributedKnnResult:
        """Exact k nearest neighbours of every dataset point, global ids.

        Adaptive eps expansion over the distributed pairs join (the same
        Hybrid-KNN-join recipe as ``QueryService.knn``): run the candidate
        pass at a starting radius (``eps0``, default the build radius),
        double until every point holds >= min(k, n) candidates (capped at
        the bounding-box diagonal, where everything is a candidate), then
        take the exact per-point top-k by (distance, id) from the final
        pair list.  ``fused`` routes the candidate passes exactly as in
        ``self_join_pairs`` -- the fused ring makes each pass one device
        dispatch.
        """
        k = int(k_neighbors)
        if k < 0:
            raise ValueError(f"k_neighbors must be >= 0, got {k}")
        n = self.num_points
        indices = np.full((n, k), -1, np.int64)
        distances = np.full((n, k), np.inf, np.float64)
        if n == 0 or k == 0:
            return DistributedKnnResult(
                indices=indices, distances=distances,
                counts=np.zeros(n, np.int64), eps_used=0.0, eps_rounds=0,
                stats=SelfJoinStats(
                    num_points=n, num_dims=self.num_dims,
                    num_workers=self.num_workers,
                ),
            )
        k_eff = min(k, n)
        lo = self._pts.min(axis=0).astype(np.float64)
        hi = self._pts.max(axis=0).astype(np.float64)
        eps_cap = float(np.sqrt(((hi - lo) ** 2).sum())) * (1.0 + 2**-10) + 1e-6
        eps = self.config.eps if eps0 is None else float(eps0)
        if eps <= 0.0:  # an eps==0 start would never grow by doubling
            eps = eps_cap / 1024.0
        eps = min(eps, eps_cap)
        rounds = 0
        while True:
            obs.event("ring.knn.round", "ring", round=rounds, eps=eps, k=k)
            res = self.self_join_pairs(eps=eps, fused=fused)
            rounds += 1
            if (res.counts >= k_eff).all() or eps >= eps_cap:
                break
            eps = min(2.0 * eps, eps_cap)
        indices, distances = self._topk_from_pairs(res.pairs, k)
        return DistributedKnnResult(
            indices=indices, distances=distances, counts=res.counts,
            eps_used=eps, eps_rounds=rounds, stats=res.stats,
        )

    def _topk_from_pairs(
        self, pairs: np.ndarray, k: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact per-point top-k over the candidate pairs, float64 distances."""
        n = self.num_points
        indices = np.full((n, k), -1, np.int64)
        distances = np.full((n, k), np.inf, np.float64)
        if pairs.shape[0] == 0:
            return indices, distances
        qi = pairs[:, 0].astype(np.int64)
        di = pairs[:, 1].astype(np.int64)
        diffs = self._pts[qi].astype(np.float64) - self._pts[di].astype(
            np.float64
        )
        dist = np.sqrt((diffs * diffs).sum(axis=1))
        order = np.lexsort((di, dist, qi))
        qi, di, dist = qi[order], di[order], dist[order]
        seg = np.cumsum(np.bincount(qi, minlength=n))
        starts = np.concatenate([[0], seg[:-1]])
        rank = np.arange(qi.shape[0]) - starts[qi]
        keep = rank < k
        indices[qi[keep], rank[keep]] = di[keep]
        distances[qi[keep], rank[keep]] = dist[keep]
        return indices, distances

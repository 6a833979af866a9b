"""Distributed self-join: entity partitioning + ring pass (paper Sec. 6.2/6.3).

The paper's strategy for |D| exceeding one device:

  * every node starts with an entity-partitioned query shard Q_k of |D|/|p|
    points and a copy E_k of the same shard;
  * |p| rounds of BSP supersteps: join Q_k against the entry set currently
    held, then send it to node (k+1) mod |p| and receive from (k-1) mod |p|.

This maps 1:1 onto ``shard_map`` + ``jax.lax.ppermute`` on a ring -- the
collective-permute uses ICI neighbour links only (no all-gather), so peak
per-device memory stays at 2 shards and the per-round communication is
exactly |D|/|p| points, totalling (|p|-1)|D| elements as derived in the paper.
Compute of round i overlaps the permute of round i+1 on real hardware (XLA
schedules the independent ops concurrently).

This module owns the **ring transport**: ``ring_scan`` runs the |p| BSP
supersteps as a ``fori_loop`` whose body consumes an arbitrary *pytree*
payload and whose epilogue ``ppermute``-rotates that payload to the next
ring position.  Three payload flavours ride on it:

  * the dense reference below (``make_ring_counts_fn``): the payload is the
    raw point block and the local join is a blocked brute-force count --
    it evaluates every (Q_k, E_j) point pair, discarding the grid index's
    candidate filtering, and is kept for transport measurement
    (`benchmarks/bench_comm.py`) and as the end-to-end ``shard_map``
    correctness oracle;
  * the production count path (``core/dist_engine.py`` with ``fused=True``,
    DESIGN.md #7a): the payload is the shard's padded *tile table*
    (tiles, tile lengths) and the body is the chunked indexed count
    program -- the whole join is one compiled device program;
  * the pairs path (``self_join_pairs(fused=True)``, DESIGN.md #7b): the
    payload additionally rotates the shard's decode tables (tile starts
    and the global-id grid-sort permutation) and the carry is each
    worker's (pairs buffer, cursor, max-chunk-hits) compaction state, so
    matched (query id, data id) rows accumulate across rounds inside the
    same one program.

Works unchanged on a 1-axis mesh ("data") or the joint ("pod","data") axes of
the production mesh -- the ring simply spans both (inter-pod DCI hops occur
once per pod boundary per round).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisNames = Union[str, Tuple[str, ...]]


def _local_counts(q: jax.Array, e: jax.Array, eps2, row_block: int = 1024) -> jax.Array:
    """Per-q counts of e-points within eps (matmul form, row-blocked)."""
    nq = q.shape[0]
    hi = jax.lax.Precision.HIGHEST  # f32 stays f32 on a TPU's MXU
    ne_norm = jnp.einsum("ij,ij->i", e, e, precision=hi)

    pad = (-nq) % row_block
    qp = jnp.pad(q, ((0, pad), (0, 0)))
    blocks = qp.reshape(-1, row_block, q.shape[1])

    def one(qb):
        d2 = (
            jnp.einsum("ij,ij->i", qb, qb, precision=hi)[:, None]
            + ne_norm[None, :]
            - 2.0 * jnp.matmul(qb, e.T, precision=hi)
        )
        return jnp.sum(d2 <= eps2, axis=1, dtype=jnp.int32)

    counts = jax.lax.map(one, blocks).reshape(-1)
    return counts[:nq]


def _ring_perm(size: int) -> Sequence[Tuple[int, int]]:
    return [(j, (j + 1) % size) for j in range(size)]


def ring_scan(axes, body, carry, payload, *, num_rounds=None, overlap=False):
    """Generic BSP ring inside a ``shard_map``'d function.

    Runs ``num_rounds`` (default: the ring size) supersteps of

        carry = body(round, carry, payload)

    rotating ``payload`` -- any pytree of arrays -- one ring position
    forward (``ppermute`` to ``(j + 1) mod |p|``) between rounds.  With
    ``overlap=True`` the permute of round r+1 is *issued before* round r's
    body (the paper's Fig. 4 pipeline: transport overlaps compute; XLA
    schedules the independent ops concurrently on real hardware).

    Under a ``shard_map`` with ``check_vma=True`` the carry must already be
    device-varying over ``axes`` (``jax.lax.pcast(..., to="varying")``).
    """
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    perm = _ring_perm(jax.lax.axis_size(axes_t))
    rotate = functools.partial(
        jax.tree_util.tree_map,
        lambda x: jax.lax.ppermute(x, axes_t, perm),
    )

    def step(r, state):
        carry, pl = state
        if overlap:
            pl_next = rotate(pl)
            carry = body(r, carry, pl)
            pl = pl_next
        else:
            carry = body(r, carry, pl)
            pl = rotate(pl)
        return carry, pl

    n = len(perm) if num_rounds is None else num_rounds
    carry, _ = jax.lax.fori_loop(0, n, step, (carry, payload))
    return carry


def make_ring_counts_fn(mesh: Mesh, axes: AxisNames, eps: float, row_block: int = 1024):
    """Build the shard_map'd ring-join counts program for ``mesh``.

    Input: D sharded on its first axis over ``axes`` (entity partition).
    Output: per-point neighbour counts (self included), identically sharded.
    """
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    eps2 = float(eps) ** 2

    def local(d_block):
        q = d_block

        def body(_, counts, e):
            return counts + _local_counts(q, e, eps2, row_block)

        # the ring carry must be device-varying over the mesh axes
        counts0 = jax.lax.pcast(
            jnp.zeros(q.shape[0], jnp.int32), axes_t, to="varying"
        )
        return ring_scan(axes_t, body, counts0, q)

    spec = P(axes_t if len(axes_t) > 1 else axes_t[0])
    return jax.jit(
        jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)
    )


def ring_self_join_counts(
    d: np.ndarray,
    eps: float,
    mesh: Mesh,
    axes: AxisNames = "data",
    row_block: int = 1024,
) -> np.ndarray:
    """Driver: pad to the partition size, run the ring join, unpad.

    Padding points sit at coordinate 3 + i*eps per row -- farther than any
    possible eps-match to data in [0,1] and to each other, so they contribute
    nothing to real counts and their own counts are sliced away.
    """
    pts = np.asarray(d, dtype=np.float32)
    n_pts, n_dims = pts.shape
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    psize = int(np.prod([mesh.shape[a] for a in axes_t]))
    pad = (-n_pts) % psize
    if pad:
        sentinel = 3.0 + (np.arange(pad, dtype=np.float32) * (2.0 * eps + 1.0))
        pts = np.concatenate(
            [pts, np.tile(sentinel[:, None], (1, n_dims))], axis=0
        )
    spec = P(axes_t if len(axes_t) > 1 else axes_t[0])
    arr = jax.device_put(
        jnp.asarray(pts), NamedSharding(mesh, spec)
    )
    fn = make_ring_counts_fn(mesh, axes, eps, row_block)
    counts = np.asarray(jax.device_get(fn(arr)))
    return counts[:n_pts].astype(np.int64)


def ring_comm_elements(num_points: int, num_workers: int) -> int:
    """Paper Sec. 6.3: total elements communicated = (|p| - 1) |D|."""
    return (num_workers - 1) * num_points

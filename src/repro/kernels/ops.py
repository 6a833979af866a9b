"""Jitted wrappers around the tile-distance evaluation.

Four interchangeable backends with one contract (two per execution tier,
DESIGN.md #9):

  * ``backend="pallas"``    -- the indexed-tier TPU kernel
    (``distance_tile.py``, SHORTC dimension-blocked); compiled by Mosaic on a
    TPU, run by the Pallas interpreter on the CPU.
  * ``backend="jnp"``       -- a vectorized jnp implementation of the same
    blocked algorithm (the XLA path, and the default).
  * ``backend="dense"``     -- the dense-tier TPU kernel (``dense_tile.py``):
    no SHORTC branching, squared distances by the clamped matmul identity
    ``max(|a|^2 + |b|^2 - 2 a.b^T, 0)`` (``ref.matmul_sqdist``).
  * ``backend="dense_jnp"`` -- the XLA twin of the dense kernel.

``repro.core.engine.kernel_choice`` maps an execution tier to its backend
string; the dense backends ignore ``shortc`` and report 0 skipped blocks.

Tile layout: every tile table is ``(num_tiles, NB, T, dim_block)`` f32 --
the n coordinates zero-padded to ``NB * dim_block`` and split into NB dim
blocks, each block of each tile one contiguous ``(T, dim_block)`` slab.
That is the block the Pallas kernels fetch per grid step (Mosaic accepts
only trailing block dims that are (8, 128) multiples or the whole array
extent, and a ``dim_block`` slice of a ``(T, n_pad)`` tile is neither).
``make_tiles`` / ``make_tiles_device`` build it once per index; nothing
transposes a whole table per chunk.  ``dim_block`` is therefore implied by
the table's shape and is not an argument of the evaluation entry points.

Interpret mode is a property of the platform, not a knob: the kernels
resolve it with ``repro.kernels.resolve_interpret`` (on for the CPU, off
for a TPU, and an explicit interpret request on a TPU is an error).

Compilation-caching contract (DESIGN.md #1.5): the candidate pair list is
evaluated in fixed-size, zero-padded chunks, and ``eps`` is always a traced
scalar, so XLA compiles exactly one program per (backend, chunk shape,
table shape) -- never one per dataset, per chunk, or per eps value.  The
building blocks here are traceable (``eval_tile_pairs``,
``make_tiles_device``) so ``repro.core.engine`` can fuse them with its
scatter/compaction epilogues into single device programs; the jitted
``tile_counts`` / ``tile_mask`` entry points below remain the standalone
host-facing API.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from repro.kernels import dense_tile, distance_tile

BACKENDS = ("pallas", "jnp", "dense", "dense_jnp")

# f32 contractions at full precision: a TPU's default matmul precision rounds
# f32 operands to bf16, which moves squared distances by ~1e-3 relative --
# far more than the eps boundary tolerates (DESIGN.md #6)
HIGHEST = jax.lax.Precision.HIGHEST


def block_dims(x, dim_block: int):
    """``(..., T, n)`` -> ``(..., NB, T, dim_block)``, zero-padding n.

    The one place the tile layout is defined; works on numpy (host) and jax
    (traced) arrays alike.  Zero padding is distance-neutral.
    """
    xp = np if isinstance(x, np.ndarray) else jnp
    n = x.shape[-1]
    nb = max(-(-n // dim_block), 1)
    if nb * dim_block != n:
        x = xp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, nb * dim_block - n)])
    x = xp.swapaxes(x.reshape(*x.shape[:-1], nb, dim_block), -2, -3)
    return np.ascontiguousarray(x) if xp is np else x


@functools.lru_cache(maxsize=None)  # one compiled identity per (rank, sharding)
def _relayout_fn(ndim: int, sharding):
    fmt = Format(Layout(major_to_minor=tuple(range(ndim))), sharding)
    return jax.jit(lambda x: x, out_shardings=fmt)


def row_major(x: jax.Array) -> jax.Array:
    """``x`` on device in the row-major layout, committed to its sharding.

    A TPU stores an array whose minor dim is narrower than its 128 lanes
    (a tile table's ``dim_block``) with another dim moved minor-most, and
    every program that reads such a table -- the Pallas kernels and the XLA
    gathers alike -- then copies the whole table back to row-major on each
    call, i.e. once per chunk dispatch.  Re-laying it once, where the table
    is built, removes that copy; jit compiles its readers for the layout the
    committed array carries.  On the CPU row-major is the default layout.
    """
    return _relayout_fn(x.ndim, x.sharding)(x)


def make_tiles(
    pts_sorted: np.ndarray,
    tile_start: np.ndarray,
    tile_len: np.ndarray,
    tile_size: int,
    dim_block: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host tile table ``(num_tiles, NB, T, dim_block)`` with zero padding.

    Zero padding in both the point axis (tail tiles) and the dimension axis
    (n -> NB * dim_block) is distance-neutral; validity is enforced via
    ``tile_len``.  Vectorized gather -- no per-tile host loop.
    """
    num_tiles = tile_start.shape[0]
    n_pts, n = pts_sorted.shape
    if num_tiles == 0:
        empty = np.zeros((1, tile_size, n), dtype=np.float32)
        return block_dims(empty, dim_block), tile_len.astype(np.int32)
    lane = np.arange(tile_size, dtype=np.int64)
    idx = tile_start.astype(np.int64)[:, None] + lane[None, :]   # (num_tiles, T)
    valid = lane[None, :] < tile_len.astype(np.int64)[:, None]
    gathered = pts_sorted[np.minimum(idx, max(n_pts - 1, 0))]    # (num_tiles, T, n)
    tiles = np.where(valid[:, :, None], gathered, 0.0).astype(np.float32)
    return block_dims(tiles, dim_block), tile_len.astype(np.int32)


def make_tiles_device(
    pts_sorted: jax.Array,    # (N, n) f32
    tile_start: jax.Array,    # (num_tiles,) int32
    tile_len: jax.Array,      # (num_tiles,) int32
    *,
    tile_size: int,
    dim_block: int,
) -> jax.Array:
    """Device twin of ``make_tiles``: one gather + pad, inside jit.

    Returns ``(max(num_tiles,1), NB, T, dim_block)`` f32, resident on
    device and ``row_major``.  Out-of-range gathers (tail-tile padding
    lanes) are clamped and then zeroed by the validity mask, so the result
    is bit-identical to the host layout.
    """
    return row_major(_make_tiles_device(
        pts_sorted, tile_start, tile_len,
        tile_size=tile_size, dim_block=dim_block,
    ))


@functools.partial(jax.jit, static_argnames=("tile_size", "dim_block"))
def _make_tiles_device(pts_sorted, tile_start, tile_len, *, tile_size, dim_block):
    num_tiles = tile_start.shape[0]
    n = pts_sorted.shape[1]
    if num_tiles == 0:
        return block_dims(jnp.zeros((1, tile_size, n), jnp.float32), dim_block)
    lane = jnp.arange(tile_size, dtype=jnp.int32)
    idx = tile_start[:, None] + lane[None, :]                    # (num_tiles, T)
    valid = lane[None, :] < tile_len[:, None]
    gathered = pts_sorted[idx]            # OOB rows clamp (jit gather) then mask
    return block_dims(jnp.where(valid[:, :, None], gathered, 0.0), dim_block)


def eval_tile_pairs(
    tiles_pts,
    tile_len,
    pair_a,
    pair_b,
    eps,
    *,
    shortc: bool = True,
    backend: str = "jnp",
    return_mask: bool = False,
):
    """Traceable tile-pair evaluation shared by all backends.

    ``tiles_pts`` is a ``(num_tiles, NB, T, dim_block)`` table; ``eps`` may
    be a python float or a traced f32 scalar.  Returns ``(counts (P,T)
    int32, skipped (P,) int32[, mask (P,T,T) int8])``.  Safe to call inside
    an enclosing ``jax.jit`` (the engine does).
    """
    if backend == "pallas":
        res = distance_tile.tile_pair_distance(
            tiles_pts, tile_len, pair_a, pair_b,
            eps=eps, return_mask=return_mask,
        )
        counts, skipped = res[0], res[1][:, 0]
        if not shortc:  # kernel always short-circuits; zero the stat
            skipped = jnp.zeros_like(skipped)
        return (counts, skipped, res[2]) if return_mask else (counts, skipped)
    if backend == "dense":  # dense tier: no SHORTC, `shortc` is ignored
        res = dense_tile.dense_tile_distance(
            tiles_pts, tile_len, pair_a, pair_b,
            eps=eps, return_mask=return_mask,
        )
        counts = res[0]
        skipped = jnp.zeros((pair_a.shape[0],), jnp.int32)
        return (counts, skipped, res[1]) if return_mask else (counts, skipped)
    if backend == "dense_jnp":
        return _eval_dense_jnp(
            tiles_pts, tile_len, pair_a, pair_b, eps, return_mask=return_mask
        )
    if backend != "jnp":
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    return _eval_jnp(
        tiles_pts, tile_len, pair_a, pair_b, eps,
        shortc=shortc, return_mask=return_mask,
    )


def _pair_validity(tile_len, pair_a, pair_b, t):
    """(P, T, T) lane validity of each (A, B) tile pair."""
    rows = jnp.arange(t, dtype=jnp.int32)
    return (rows[None, :, None] < tile_len[pair_a][:, None, None]) & (
        rows[None, None, :] < tile_len[pair_b][:, None, None]
    )


def _blocked_sqdist(a, b):
    """Unclamped matmul-form squared distances summed over dim blocks.

    ``a``, ``b``: ``(P, K, T, D)`` -> ``(P, T, T)`` f32.
    """
    na = jnp.einsum("pkid,pkid->pi", a, a, precision=HIGHEST)
    nb = jnp.einsum("pkjd,pkjd->pj", b, b, precision=HIGHEST)
    prod = jnp.einsum("pkid,pkjd->pij", a, b, precision=HIGHEST)
    return na[:, :, None] + nb[:, None, :] - 2.0 * prod


def _eval_dense_jnp(tiles_pts, tile_len, pair_a, pair_b, eps, *, return_mask):
    """XLA twin of the dense kernel: clamped matmul identity, no SHORTC."""
    d2 = jnp.maximum(_blocked_sqdist(tiles_pts[pair_a], tiles_pts[pair_b]), 0.0)
    valid = _pair_validity(tile_len, pair_a, pair_b, tiles_pts.shape[2])
    within = (d2 <= jnp.asarray(eps, jnp.float32) ** 2) & valid
    counts = within.sum(axis=2, dtype=jnp.int32)
    skipped = jnp.zeros((pair_a.shape[0],), jnp.int32)
    if return_mask:
        return counts, skipped, within.astype(jnp.int8)
    return counts, skipped


def _eval_jnp(tiles_pts, tile_len, pair_a, pair_b, eps, *, shortc, return_mask):
    """Pure-jnp blocked evaluation (traceable; ``eps`` may be traced)."""
    _, nb_blocks, t, _ = tiles_pts.shape
    p = pair_a.shape[0]
    valid = _pair_validity(tile_len, pair_a, pair_b, t)
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    neg_large = jnp.float32(3.0e38)

    if not shortc:
        d2 = _blocked_sqdist(tiles_pts[pair_a], tiles_pts[pair_b])
        skipped = jnp.zeros((p,), jnp.int32)
    else:
        def body(carry, j):
            d2, done, skipped = carry
            # one dim block of every pair, gathered straight from the table
            ab = tiles_pts[pair_a, j][:, None]
            bb = tiles_pts[pair_b, j][:, None]
            contrib = _blocked_sqdist(ab, bb)
            skipped = skipped + done.astype(jnp.int32)
            d2 = jnp.where(done[:, None, None], d2, d2 + contrib)
            d2_masked = jnp.where(valid, d2, neg_large)
            done = done | (jnp.min(d2_masked, axis=(1, 2)) > eps2)
            return (d2, done, skipped), None

        init = (
            jnp.zeros((p, t, t), jnp.float32),
            jnp.zeros((p,), jnp.bool_),
            jnp.zeros((p,), jnp.int32),
        )
        (d2, _, skipped), _ = jax.lax.scan(
            body, init, jnp.arange(nb_blocks, dtype=jnp.int32)
        )

    within = (d2 <= eps2) & valid
    counts = within.sum(axis=2, dtype=jnp.int32)
    if return_mask:
        return counts, skipped, within.astype(jnp.int8)
    return counts, skipped


@functools.partial(
    jax.jit, static_argnames=("shortc", "backend", "return_mask")
)
def _eval_chunk(
    tiles_pts, tile_len, pair_a, pair_b, eps, *, shortc, backend, return_mask
):
    return eval_tile_pairs(
        tiles_pts, tile_len, pair_a, pair_b, eps,
        shortc=shortc, backend=backend, return_mask=return_mask,
    )


def tile_counts(
    tiles_pts: np.ndarray,
    tile_len: np.ndarray,
    pair_a: np.ndarray,
    pair_b: np.ndarray,
    *,
    eps: float,
    shortc: bool = True,
    backend: str = "jnp",
    chunk: int = 4096,
) -> Tuple[np.ndarray, np.ndarray]:
    """Counts (P, T) and SHORTC-skipped block counts (P,) for all pairs."""
    out_counts, out_skipped = [], []
    tiles_j = row_major(jnp.asarray(tiles_pts))
    len_j = jnp.asarray(tile_len)
    for c, pa, pb, real in _chunks(pair_a, pair_b, chunk):
        counts, skipped = _eval_chunk(
            tiles_j, len_j, pa, pb, eps,
            shortc=shortc, backend=backend, return_mask=False,
        )
        out_counts.append(np.asarray(counts)[:real])
        out_skipped.append(np.asarray(skipped)[:real])
    if not out_counts:
        t = tiles_pts.shape[2]
        return np.zeros((0, t), np.int32), np.zeros((0,), np.int32)
    return np.concatenate(out_counts), np.concatenate(out_skipped)


def tile_mask(
    tiles_pts: np.ndarray,
    tile_len: np.ndarray,
    pair_a: np.ndarray,
    pair_b: np.ndarray,
    *,
    eps: float,
    backend: str = "jnp",
    chunk: int = 512,
):
    """Yield (pair_slice_start, mask (Pc, T, T) int8 numpy) per chunk."""
    done = 0
    tiles_j = row_major(jnp.asarray(tiles_pts))
    len_j = jnp.asarray(tile_len)
    for c, pa, pb, real in _chunks(pair_a, pair_b, chunk):
        _, _, mask = _eval_chunk(
            tiles_j, len_j, pa, pb, eps,
            shortc=True, backend=backend, return_mask=True,
        )
        yield done, np.asarray(mask)[:real]
        done += real


def _chunks(pair_a: np.ndarray, pair_b: np.ndarray, chunk: int):
    """Fixed-size, zero-padded chunks (single XLA program per layout)."""
    p = pair_a.shape[0]
    for s in range(0, p, chunk):
        pa = pair_a[s : s + chunk]
        pb = pair_b[s : s + chunk]
        real = pa.shape[0]
        if real < chunk:
            pa = np.pad(pa, (0, chunk - real))
            pb = np.pad(pb, (0, chunk - real))
        yield s, jnp.asarray(pa, jnp.int32), jnp.asarray(pb, jnp.int32), real

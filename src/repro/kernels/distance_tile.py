"""Pallas TPU kernel: eps-neighbourhood evaluation of candidate tile pairs.

This is the compute hot-spot of the paper (the CUDA self-join kernel,
Alg. 1 lines 11-19) re-thought for the TPU (DESIGN.md #1):

  * each grid program evaluates one candidate tile pair (A, B) of
    ``tile_size`` points each, as the MXU-friendly contraction
    ``d2 = |a|^2 + |b|^2 - 2 a.b^T``;
  * the n coordinate dimensions are processed in ``dim_block``-wide blocks,
    highest variance first (REORDER).  A tile pair short-circuits -- the TPU
    analogue of SHORTC -- when the partial d2 minimum over all valid lanes
    already exceeds eps^2: every remaining block can only grow d2, so all
    pairs are decided and the remaining MXU work is skipped via ``pl.when``;
  * tiles are fetched from HBM into VMEM by BlockSpec index maps driven by
    scalar-prefetched tile indices (the flat candidate work list produced by
    ``repro.core.grid.build_tile_plan``).  The table is laid out
    ``(num_tiles, NB, T, dim_block)`` (``ops.make_tiles``), so one dim block
    of one tile is a full-extent ``(T, dim_block)`` block -- the Mosaic
    tiling rule only accepts trailing block dims that are (8, 128) multiples
    or the whole array extent, and ``dim_block < n_pad`` is neither;
  * ``eps`` is a *runtime* scalar, prefetched into SMEM alongside the tile
    indices (DESIGN.md #1.5): one compiled program serves every eps value,
    which is what lets ``SelfJoinEngine.query`` sweep eps without recompiling.

Grid: ``(P, NB)`` -- P candidate pairs x NB dimension blocks; the dim-block
axis is minor, so VMEM scratch carries the partial d2 across blocks of the
same pair and is reset at block 0.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_NEG_LARGE = 3.0e38  # python float: becomes an inline literal, not a captured const


def _kernel(
    a_idx_ref,      # (P,) int32  scalar prefetch: A tile index per pair
    b_idx_ref,      # (P,) int32  scalar prefetch: B tile index per pair
    tile_len_ref,   # (num_tiles,) int32 scalar prefetch: valid points per tile
    eps2_ref,       # (1,) f32    scalar prefetch: runtime eps^2
    a_ref,          # (1, 1, T, DB) f32 VMEM: current dim block of the A tile
    b_ref,          # (1, 1, T, DB) f32 VMEM: current dim block of the B tile
    counts_ref,     # (1, 1, T) int32 out: per-A-point neighbour count
    skipped_ref,    # (1, 1, 1) int32 out: dim blocks skipped by SHORTC
    d2_ref,         # (T, T) f32 VMEM scratch: partial squared distances
    flags_ref,      # (2,) int32 SMEM scratch: [done, blocks_computed]
    *,
    num_blocks: int,
    tile_size: int,
    out_mask_ref=None,  # optional (1, T, T) int8 out (pairs mode)
):
    p = pl.program_id(0)
    j = pl.program_id(1)
    t = tile_size
    eps2 = eps2_ref[0]

    @pl.when(j == 0)
    def _init():
        d2_ref[:, :] = jnp.zeros((t, t), jnp.float32)
        flags_ref[0] = 0
        flags_ref[1] = 0

    la = tile_len_ref[a_idx_ref[p]]
    lb = tile_len_ref[b_idx_ref[p]]
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    valid = (rows < la) & (cols < lb)

    @pl.when(flags_ref[0] == 0)
    def _accumulate():
        a = a_ref[0, 0]                                # (T, DB)
        b = b_ref[0, 0]
        na = jnp.sum(a * a, axis=1, keepdims=True)     # (T, 1)
        nb = jnp.sum(b * b, axis=1, keepdims=True)     # (T, 1)
        prod = jax.lax.dot_general(
            a,
            b,
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                              # (T, T) = a . b^T
        d2_ref[:, :] = d2_ref[:, :] + na + nb.T - 2.0 * prod
        flags_ref[1] = flags_ref[1] + 1
        # SHORTC (tile granularity): if even the closest still-valid pair
        # already exceeds eps^2, every pair is decided -- skip later blocks.
        d2_masked = jnp.where(valid, d2_ref[:, :], _NEG_LARGE)
        flags_ref[0] = jnp.where(jnp.min(d2_masked) > eps2, 1, 0).astype(jnp.int32)

    @pl.when(j == num_blocks - 1)
    def _finalize():
        within = (d2_ref[:, :] <= eps2) & valid
        counts_ref[0] = jnp.sum(within.astype(jnp.int32), axis=1)[None, :]
        # a vector store: Mosaic refuses scalar stores into VMEM
        skipped_ref[0] = jnp.full((1, 1), num_blocks - flags_ref[1], jnp.int32)
        if out_mask_ref is not None:
            out_mask_ref[0, :, :] = within.astype(jnp.int8)


def _mask_kernel(*refs, num_blocks, tile_size):
    (a_idx, b_idx, tl, eps2, a, b, counts, skipped, mask, d2, flags) = refs
    _kernel(
        a_idx, b_idx, tl, eps2, a, b, counts, skipped, d2, flags,
        num_blocks=num_blocks, tile_size=tile_size,
        out_mask_ref=mask,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "return_mask"))
def tile_pair_distance(
    tiles_pts: jax.Array,   # (num_tiles, NB, T, DB) f32 (ops.make_tiles layout)
    tile_len: jax.Array,    # (num_tiles,) int32
    pair_a: jax.Array,      # (P,) int32
    pair_b: jax.Array,      # (P,) int32
    *,
    eps: float,
    interpret: Optional[bool] = None,
    return_mask: bool = False,
):
    """Evaluate all candidate tile pairs.

    ``eps`` may be a python float or a traced f32 scalar; it is forwarded to
    the kernel as a scalar-prefetch operand, so distinct eps values share one
    executable.  Returns ``(counts (P,T) int32, skipped (P,1) int32)`` and,
    when ``return_mask``, also the per-pair boolean mask ``(P, T, T) int8``.
    ``interpret`` defaults to the platform's mode (``resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    _, nb, t, db = tiles_pts.shape
    p = pair_a.shape[0]
    eps2 = (jnp.asarray(eps, jnp.float32) ** 2).reshape(1)

    tile_spec_a = pl.BlockSpec(
        (1, 1, t, db), lambda pp, jj, a_idx, b_idx, tl, e2: (a_idx[pp], jj, 0, 0)
    )
    tile_spec_b = pl.BlockSpec(
        (1, 1, t, db), lambda pp, jj, a_idx, b_idx, tl, e2: (b_idx[pp], jj, 0, 0)
    )
    counts_spec = pl.BlockSpec((1, 1, t), lambda pp, jj, *_: (pp, 0, 0))
    skip_spec = pl.BlockSpec((1, 1, 1), lambda pp, jj, *_: (pp, 0, 0))

    out_shapes = [
        jax.ShapeDtypeStruct((p, 1, t), jnp.int32),
        jax.ShapeDtypeStruct((p, 1, 1), jnp.int32),
    ]
    out_specs = [counts_spec, skip_spec]
    if return_mask:
        out_shapes.append(jax.ShapeDtypeStruct((p, t, t), jnp.int8))
        out_specs.append(pl.BlockSpec((1, t, t), lambda pp, jj, *_: (pp, 0, 0)))
        body = functools.partial(_mask_kernel, num_blocks=nb, tile_size=t)
    else:
        body = functools.partial(_kernel, num_blocks=nb, tile_size=t)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(p, nb),
        in_specs=[tile_spec_a, tile_spec_b],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((t, t), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    res = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
    )(pair_a, pair_b, tile_len, eps2, tiles_pts, tiles_pts)
    counts, skipped = res[0].reshape(p, t), res[1].reshape(p, 1)
    return (counts, skipped, res[2]) if return_mask else (counts, skipped)
